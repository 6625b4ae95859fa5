"""Tiled MXU Pallas kernels for conv2d forward / grad-input / grad-filter
(/opt/skills/guides/pallas_guide.md patterns, ops/pallas_attention.py
as the in-repo template). Only the int8
forward is routed: see "Routing" below before reaching for the rest.

Tiling: NHWC operands, bf16 on the MXU datapath with f32 VMEM
accumulation (preferred_element_type), channels in 128-lane tiles. The
grid walks one output row per step with an H *input* block of size 1 —
at block size 1 the BlockSpec index map addresses *rows*, so
strided/dilated input-row selection (`oh*stride + kh*dilation`) happens
in the index map and no halo exchange or revisit is needed. Inside the
kernel the kw taps unroll as a Python loop of unit-stride row slices (a
strided conv's row is de-interleaved into its width phases first,
`_deinterleave`) feeding [W-ish, Ci] x [Ci, Co] MXU dots into an f32
accumulator that carries across the sequential (innermost) reduction dim
of the grid:

  forward      grid (N, OH/BH, Co/128, KH*Ci/128 * BH), acc [BH, OW, 128]
  grad-filter  grid (KH, Ci/128, Co/128, N*OH), acc [KW, 128, 128]
  grad-input   = the forward kernel on the stride-dilated cotangent with
                 the spatially flipped filter and transposed-conv padding
                 (lo = (K-1)*d - p, hi = H - Hd + p), so one kernel body
                 serves both directions.

BH is the multi-row pipelining factor: the
filter tile is by far the heaviest HBM stream of the row-walk (for a
3x3 C=128 ResNet block each output row re-reads KH*KW*Ci*Co filter
bytes against one input row), so the reduction dim is extended by BH
output rows with the row index *innermost*. Consecutive grid steps then
keep the same filter block index and Pallas skips the copy — filter
traffic divides by BH while the f32 accumulator grows to [BH, OW, 128]
rows of VMEM, double-buffered input rows stream as before. BH is the
largest of {8, 4, 2, 1} that divides OH and fits the VMEM row budget.

`conv2d_q8` is the forward kernel on int8 operands (quant.py's O3
routing): int8 x/w tiles, int32 VMEM accumulation, and the per-channel
dequantization vector applied to the output row while it is still in
VMEM. It walks the same row-per-step grid as the bf16 kernels and has
not been priced on the chip (PERF.md §7).

`conv2d_stats` is the forward kernel with the Co tile as the *outermost*
grid dim and per-channel sum/sum-of-squares carried in VMEM scratch:
batch statistics while the output row is still in VMEM, then `bn_apply`
normalizes (+activation) in one more sweep, so a conv->bn->act window
never re-reads the conv output from HBM to compute statistics.

Routing (PR 25). On a v5e these kernels held ResNet-50 bs256 at 1.26 %
of peak and XLA's convolution ran the same cell eleven times faster
(PERF.md §6): a grid step here does at most KW dots of [OW <= 112, 128] x
[128, 128] and pays the pipeline's per-step cost whatever it computes.
So no bf16 conv lowers to them: `conv2d`, `conv2d_stats`,
`conv2d_grad_input`, `conv2d_grad_filter` and `bn_apply` stay for their
parity and described-v5e compile tests until a `simplicity` PR deletes
them with those tests (PERF.md §7), and ops/nn_ops.py lowers every float
conv and its backward to lax.conv_general_dilated. Only `conv2d_q8`
is dispatched (AMP O3, through quant.qconv2d), `ineligible` is its
tiling gate (quant.ineligible_conv reports a miss as reason "kernel"),
and KERNELS lists that dispatch for tools/check_registry.py. A conv
kernel written later earns a route by beating XLA's conv on the chip for
a shape, and its predicate is then on that shape. On CPU (the test mesh)
the kernels run under the Pallas interpreter — same code path, no Mosaic
compile — so parity gates run under JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_attention import _compiler_params, _dot, _interpret, _scratch

__all__ = [
    "FALLBACK_REASONS", "KERNELS", "PALLAS_CONV", "bn_apply", "conv2d",
    "conv2d_grad_filter", "conv2d_grad_input", "conv2d_q8",
    "conv2d_stats", "count_fallback", "count_hit", "ineligible",
    "supports",
]

# Read by nothing: the switch it was is gone with the route it switched.
# tests/benchmark/test_run_cpu.py (the benchmark's file, not this
# module's to edit) still setattr's it, which needs the name to exist.
PALLAS_CONV = True

_LANE = 128

# Every reason `ineligible` can return (pinned by check_pallas_table —
# a reason string produced but not listed here would ship an unlabelled
# fallback counter).
FALLBACK_REASONS = frozenset(
    {"mesh", "rank", "groups", "dtype", "channels", "attrs", "geometry"})

# VMEM width budget: each grid step keeps a [Wp, 128] bf16 input row, an
# [OW, 128] f32 accumulator and an [OW, 128] output row resident (double
# buffered by the pipeline), and grad-input re-pads the cotangent to
# W + KWe - 1 with OW' = W. 2048 lanes bounds that resident set around
# 3 MB — comfortably inside the ~16 MB/core VMEM of current TPUs — so
# wider shapes fall back to lax.conv instead of failing Mosaic
# compilation at run time.
_MAX_W = 2048


def ineligible(x, w, strides, paddings, dilations, groups=1, mesh=None):
    """None when the kernels' tiling applies, else the reason.

    `x` is the NHWC operand *post mxu_cast* (AMP convs are bf16 by here;
    a plain f32 conv reads "dtype"), `w` the OIHW filter. The geometry
    rules also cover grad-input through the forward kernel:
    transposed-conv padding stays non-negative iff p <= (K-1)*d per
    spatial dim. `mesh` is the program's SPMD mesh: XLA cannot partition
    a Mosaic custom call (it would gather every operand and run the
    whole conv on each device), and these kernels are not wrapped in
    shard_map, so a step partitioned over more than one device declines.
    """
    if mesh is not None and mesh.size > 1:
        return "mesh"
    if getattr(x, "ndim", 0) != 4 or getattr(w, "ndim", 0) != 4:
        return "rank"
    if (groups or 1) != 1:
        return "groups"   # depthwise/grouped convs keep the lax path
    if getattr(x, "dtype", None) != jnp.bfloat16 or \
            getattr(w, "dtype", None) != jnp.bfloat16:
        return "dtype"
    ci = x.shape[3]
    co, ci_w, kh, kw = w.shape
    if ci % _LANE or co % _LANE or ci_w != ci:
        return "channels"
    if len(strides) != 2 or len(paddings) != 2 or len(dilations) != 2:
        # e.g. Paddle's legal 4-element [top, bottom, left, right]
        # paddings — attrs the symmetric tiling doesn't model
        return "attrs"
    sh, sw = strides
    ph, pw = paddings
    dh, dw = dilations
    keh, kew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    oh = (x.shape[1] + 2 * ph - keh) // sh + 1
    ow = (x.shape[2] + 2 * pw - kew) // sw + 1
    if oh < 1 or ow < 1 or ph > keh - 1 or pw > kew - 1:
        return "geometry"
    if max(x.shape[2] + 2 * pw, x.shape[2] + kew - 1, ow) > _MAX_W:
        # padded width (forward/grad-filter), the grad-input re-pad, or
        # the accumulator row would overflow the VMEM row budget
        return "geometry"
    return None


def supports(x, w, strides, paddings, dilations, groups=1,
             mesh=None) -> bool:
    """Static eligibility, pallas_attention.supports-style."""
    return ineligible(x, w, strides, paddings, dilations, groups,
                      mesh) is None


def count_fallback(op: str, reason: str):
    from .. import telemetry
    telemetry.counter(
        "pallas_fallback_total",
        "lowerings that declined a Pallas kernel for the XLA path (flash "
        "attention to einsum attention), by op and gating reason",
        labels=("op", "reason")).labels(op=op, reason=reason).inc()


def count_hit(op: str):
    from .. import telemetry
    telemetry.counter(
        "pallas_kernel_total",
        "lowerings served by a Pallas kernel, by op: conv2d (conv2d_q8 "
        "under AMP O3; no bf16 conv since PR 25) and "
        "scaled_dot_product_attention (the flash kernels, booked by "
        "ops/nn_ops._sdpa_paths for each lowering of a forward op; the "
        "grad op books nothing)",
        labels=("op",)).labels(op=op).inc()


# --- kernel bodies ------------------------------------------------------

def _phases(kw_n, dw, sw):
    """The width phases (column index mod stride) the kw taps read."""
    return sorted({(kw * dw) % sw for kw in range(kw_n)})


def _deinterleave(xp, kw_n, dw, sw):
    """[N, Hp, Wp, C] -> [N, Hp, P*Wq, C] for a strided conv: the columns
    of each phase the taps read, contiguous and phase after phase (Wq =
    ceil(Wp/sw)). A stride-sw tap is then a unit-stride slice of its
    phase — Mosaic refuses a strided value slice outright and a strided
    ref load of a packed (non-32-bit) dtype, so the stride is taken out
    of the row by XLA before the kernel sees it. Identity at stride 1;
    a 1x1 stride-2 conv carries only the half of the row it reads."""
    if sw == 1:
        return xp
    n, hp, wp, c = xp.shape
    wq = -(-wp // sw)
    xq = jnp.pad(xp, ((0, 0), (0, 0), (0, wq * sw - wp), (0, 0)))
    xq = xq.reshape(n, hp, wq, sw, c)
    return jnp.concatenate(
        [xq[:, :, :, ph, :] for ph in _phases(kw_n, dw, sw)], axis=2)


def _taps(x_row, kw_n, dw, sw, ow):
    """The kw tap slices of one padded, de-interleaved input row: [OW,
    128] each. Tap kw reads padded columns kw*dw + i*sw, i.e. OW
    consecutive columns of phase (kw*dw) % sw from (kw*dw) // sw on;
    they fit the phase — the widest tap ends at padded column
    (KW-1)*dw + (OW-1)*sw <= Wp - 1 by the output-dim equation."""
    phases = _phases(kw_n, dw, sw)
    wq = x_row.shape[0] // len(phases)
    for kw in range(kw_n):
        off = phases.index((kw * dw) % sw) * wq + (kw * dw) // sw
        yield lax.slice(x_row, (off, 0), (off + ow, x_row.shape[1]))


def _dot_i32(a, b, dims):
    """int8 x int8 -> int32 MXU dot (the 2x-rate datapath)."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.int32)


def _fwd_kernel(x_ref, w_ref, *refs, kw_n, dw, sw, ow, n_s, bh):
    """Grid (N, OH/BH, Co/128, KH*Ci/128 * BH): one output row [OW, 128]
    per (n, oh, co), reduction taps streamed innermost with the H-block
    row index `hb` cycling fastest — so the filter block index is
    unchanged for BH consecutive steps and its copy is skipped (module
    docstring). Quantized form (5 refs): int8 operands, int32
    accumulator, per-channel dequant vector applied on the way out."""
    import jax.experimental.pallas as pl
    if len(refs) == 3:
        dq_ref, o_ref, acc = refs
    else:
        (o_ref, acc), dq_ref = refs, None
    ss2 = pl.program_id(3)
    ss = ss2 // bh                 # reduction step: kh * n_ci + ci tile
    hb = ss2 % bh                  # output row within the H block

    @pl.when(ss == 0)
    def _zero():
        acc[pl.ds(hb, 1)] = jnp.zeros((1,) + acc.shape[1:], acc.dtype)

    dot = _dot if acc.dtype == jnp.float32 else _dot_i32
    x_row = x_ref[0, 0]            # [Wp, 128] one padded input row
    wt = w_ref[0]                  # [KW, 128, 128] one kh tap
    total = None
    for kw, xs in enumerate(_taps(x_row, kw_n, dw, sw, ow)):
        t = dot(xs, wt[kw], ((1,), (0,)))
        total = t if total is None else total + t
    acc[pl.ds(hb, 1)] += total[None]

    @pl.when(ss == n_s - 1)
    def _finish():
        row = acc[pl.ds(hb, 1)]
        if dq_ref is not None:
            row = row.astype(jnp.float32) * dq_ref[...]
        o_ref[0, pl.ds(hb, 1)] = row.astype(o_ref.dtype)


def _fwd_stats_kernel(x_ref, w_ref, o_ref, sum_ref, sq_ref, acc, ssum, ssq,
                      *, kw_n, dw, sw, ow, n_s, n_n, n_oh):
    """Forward + per-channel sum/sumsq of the rounded output. Grid
    (Co/128, N, OH, KH*Ci/128) — Co outermost so the [1, 128] statistics
    scratch carries across every output row of its channel tile. The
    statistics are of the *bf16-rounded* y, matching what the unfused bn
    would read back from HBM."""
    import jax.experimental.pallas as pl
    nn = pl.program_id(1)
    hh = pl.program_id(2)
    ss = pl.program_id(3)

    @pl.when(jnp.logical_and(nn == 0, jnp.logical_and(hh == 0, ss == 0)))
    def _zero_stats():
        ssum[...] = jnp.zeros_like(ssum)
        ssq[...] = jnp.zeros_like(ssq)

    @pl.when(ss == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    x_row = x_ref[0, 0]
    wt = w_ref[0]
    for kw, xs in enumerate(_taps(x_row, kw_n, dw, sw, ow)):
        acc[...] += _dot(xs, wt[kw], ((1,), (0,)))

    @pl.when(ss == n_s - 1)
    def _finish():
        y = acc[...].astype(o_ref.dtype)
        o_ref[0, 0] = y
        yf = y.astype(jnp.float32)
        ssum[...] += jnp.sum(yf, axis=0, keepdims=True)
        ssq[...] += jnp.sum(yf * yf, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(nn == n_n - 1,
                             jnp.logical_and(hh == n_oh - 1, ss == n_s - 1)))
    def _write_stats():
        sum_ref[...] = ssum[...]
        sq_ref[...] = ssq[...]


def _wgrad_kernel(x_ref, do_ref, o_ref, acc, *, kw_n, dw, sw, ow, m_n):
    """Grid (KH, Ci/128, Co/128, N*OH): each step contracts one padded
    input row against one cotangent row over OW, accumulating all KW taps
    of a [128, 128] dW tile in one visit."""
    import jax.experimental.pallas as pl
    mm = pl.program_id(3)

    @pl.when(mm == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    x_row = x_ref[0, 0]            # [Wp, 128ci]
    do_row = do_ref[0, 0]          # [OW, 128co]
    for kw, xs in enumerate(_taps(x_row, kw_n, dw, sw, ow)):
        acc[kw] += _dot(xs, do_row, ((0,), (0,)))

    @pl.when(mm == m_n - 1)
    def _finish():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _bn_apply_kernel(x_ref, scale_ref, bias_ref, mean_ref, var_ref, *refs,
                     eps, act):
    """Normalize + activation given precomputed statistics (the
    conv2d_stats epilogue's)."""
    if act is None:
        (ybn_ref,) = refs
        yact_ref = None
    else:
        ybn_ref, yact_ref = refs
    inv = jax.lax.rsqrt(var_ref[...] + eps)
    xb = x_ref[...].astype(jnp.float32)
    y = (xb - mean_ref[...]) * (inv * scale_ref[...]) + bias_ref[...]
    y = y.astype(ybn_ref.dtype)
    ybn_ref[...] = y
    if yact_ref is not None:
        yact_ref[...] = act(y)


# --- pallas_call wrappers -----------------------------------------------

def _block_h(oh: int, ow: int) -> int:
    """Pipelining factor: the largest H block that divides OH and keeps
    the [BH, OW, 128] accumulator + the output block inside a ~3 MB
    VMEM slice of the row budget (4+2 bytes per element, x2 pipeline)."""
    return next(b for b in (8, 4, 2, 1) if oh % b == 0 and b * ow <= 4096)


def _conv_call(x, w_hwio, strides, dilations, pads, out_dtype=None,
               stats=False, dq=None):
    """Shared conv driver. `x` NHWC (unpadded), `w_hwio` [KH, KW, Ci, Co],
    `pads` explicit ((lo_h, hi_h), (lo_w, hi_w)) so the grad-input call
    can pass the asymmetric transposed-conv padding. `dq` (f32 [1, Co])
    selects the int8 form: int8 operands, int32 accumulation, dequant
    on the output row in VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, _, _, ci = x.shape
    kh, kw_n, _, co = w_hwio.shape
    sh, sw = strides
    dh, dw = dilations
    xp = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
    oh = (xp.shape[1] - ((kh - 1) * dh + 1)) // sh + 1
    ow = (xp.shape[2] - ((kw_n - 1) * dw + 1)) // sw + 1
    xp = _deinterleave(xp, kw_n, dw, sw)
    wp = xp.shape[2]
    n_ci = ci // _LANE
    n_s = kh * n_ci
    out_dtype = out_dtype or x.dtype

    if not stats:
        bh = _block_h(oh, ow)
        grid = (n, oh // bh, co // _LANE, n_s * bh)
        x_spec = pl.BlockSpec(
            (1, 1, wp, _LANE),
            lambda nn, hh, cc, ss: (
                nn, (hh * bh + ss % bh) * sh + (ss // bh // n_ci) * dh, 0,
                (ss // bh) % n_ci))
        w_spec = pl.BlockSpec(
            (1, kw_n, _LANE, _LANE),
            lambda nn, hh, cc, ss: (ss // bh // n_ci, 0,
                                    (ss // bh) % n_ci, cc))
        o_spec = pl.BlockSpec((1, bh, ow, _LANE),
                              lambda nn, hh, cc, ss: (nn, hh, 0, cc))
        in_specs = [x_spec, w_spec]
        operands = [xp, w_hwio]
        acc_dtype = jnp.float32
        if dq is not None:
            in_specs.append(pl.BlockSpec((1, _LANE),
                                         lambda nn, hh, cc, ss: (0, cc)))
            operands.append(dq)
            acc_dtype = jnp.int32
        kernel = functools.partial(_fwd_kernel, kw_n=kw_n, dw=dw, sw=sw,
                                   ow=ow, n_s=n_s, bh=bh)
        return pl.pallas_call(
            kernel, name="conv2d" if dq is None else "conv2d_q8",
            grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((n, oh, ow, co), out_dtype),
            scratch_shapes=[pltpu.VMEM((bh, ow, _LANE), acc_dtype)],
            interpret=_interpret(),
            compiler_params=_compiler_params(
                ("parallel", "parallel", "parallel", "arbitrary")),
        )(*operands)

    grid = (co // _LANE, n, oh, n_s)
    x_spec = pl.BlockSpec(
        (1, 1, wp, _LANE),
        lambda cc, nn, hh, ss: (nn, hh * sh + (ss // n_ci) * dh, 0,
                                ss % n_ci))
    w_spec = pl.BlockSpec(
        (1, kw_n, _LANE, _LANE),
        lambda cc, nn, hh, ss: (ss // n_ci, 0, ss % n_ci, cc))
    o_spec = pl.BlockSpec((1, 1, ow, _LANE),
                          lambda cc, nn, hh, ss: (nn, hh, 0, cc))
    vec_spec = pl.BlockSpec((1, _LANE), lambda cc, nn, hh, ss: (0, cc))
    kernel = functools.partial(_fwd_stats_kernel, kw_n=kw_n, dw=dw, sw=sw,
                               ow=ow, n_s=n_s, n_n=n, n_oh=oh)
    return pl.pallas_call(
        kernel, name="conv2d_stats", grid=grid, in_specs=[x_spec, w_spec],
        out_specs=[o_spec, vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((n, oh, ow, co), out_dtype),
                   jax.ShapeDtypeStruct((1, co), jnp.float32),
                   jax.ShapeDtypeStruct((1, co), jnp.float32)],
        scratch_shapes=[_scratch((ow, _LANE)), _scratch((1, _LANE)),
                        _scratch((1, _LANE))],
        interpret=_interpret(),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary", "arbitrary")),
    )(xp, w_hwio)


def conv2d(x, w, strides, paddings, dilations, out_dtype=None):
    """x [N, H, W, Ci] bf16, w [Co, Ci, KH, KW] bf16 -> y [N, OH, OW, Co].
    Caller must have passed the `ineligible` gate."""
    ph, pw = paddings
    return _conv_call(x, jnp.transpose(w, (2, 3, 1, 0)), strides, dilations,
                      ((ph, ph), (pw, pw)), out_dtype=out_dtype)


def conv2d_stats(x, w, strides, paddings, dilations, out_dtype=None):
    """conv2d plus per-channel (sum, sum-of-squares) of the rounded
    output: (y, csum [Co], csq [Co]) — the fused conv->bn->act window's
    statistics come for free from VMEM."""
    ph, pw = paddings
    y, csum, csq = _conv_call(
        x, jnp.transpose(w, (2, 3, 1, 0)), strides, dilations,
        ((ph, ph), (pw, pw)), out_dtype=out_dtype, stats=True)
    return y, csum.reshape(-1), csq.reshape(-1)


def conv2d_q8(x, w, strides, paddings, dilations, dq, out_dtype=None):
    """Quantized forward: x [N, H, W, Ci] int8, w [Co, Ci, KH, KW] int8,
    dq f32 [Co] the combined activation*weight dequant scales
    (quant.qconv2d builds them). int32 VMEM accumulation, dequantized to
    `out_dtype` (default bf16) on the output row. Caller must have
    passed quant.ineligible_conv, which requires the `ineligible` gate
    here."""
    ph, pw = paddings
    return _conv_call(x, jnp.transpose(w, (2, 3, 1, 0)), strides,
                      dilations, ((ph, ph), (pw, pw)),
                      out_dtype=out_dtype or jnp.bfloat16,
                      dq=jnp.asarray(dq, jnp.float32).reshape(1, -1))


def conv2d_grad_input(dout, w, x_hw, strides, paddings, dilations,
                      out_dtype=None):
    """dL/dx as a transposed conv through the forward kernel: dilate the
    cotangent by the stride, flip the filter spatially and swap its
    channel axes, pad lo=(K-1)*d-p / hi=H-Hd+p (both non-negative by the
    shared gate), then run the stride-1 forward."""
    sh, sw = strides
    ph, pw = paddings
    dh, dw = dilations
    co, ci, kh, kw = w.shape
    h, wdim = x_hw
    n, oh, ow, _ = dout.shape
    hd, wd = (oh - 1) * sh + 1, (ow - 1) * sw + 1
    if sh > 1 or sw > 1:
        dd = jnp.zeros((n, hd, wd, co), dout.dtype)
        dd = dd.at[:, ::sh, ::sw, :].set(dout)
    else:
        dd = dout
    keh, kew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    w_t = jnp.transpose(jnp.flip(w, (2, 3)), (2, 3, 0, 1))  # [KH,KW,Co,Ci]
    return _conv_call(
        dd, w_t, (1, 1), dilations,
        ((keh - 1 - ph, h - hd + ph), (kew - 1 - pw, wdim - wd + pw)),
        out_dtype=out_dtype)


def conv2d_grad_filter(x, dout, kernel_hw, strides, paddings, dilations,
                       out_dtype=None):
    """dL/dw [Co, Ci, KH, KW]: per-(kh, ci, co) tiles accumulated over the
    N*OH row pairs in f32 scratch, rounded once at the end."""
    import jax.experimental.pallas as pl
    n, _, _, ci = x.shape
    _, oh, ow, co = dout.shape
    kh, kw_n = kernel_hw
    sh, sw = strides
    ph, pw = paddings
    dh, dw = dilations
    xp = _deinterleave(jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0))),
                       kw_n, dw, sw)
    wp = xp.shape[2]
    m_n = n * oh
    grid = (kh, ci // _LANE, co // _LANE, m_n)
    x_spec = pl.BlockSpec(
        (1, 1, wp, _LANE),
        lambda kk, ii, cc, mm: (mm // oh, (mm % oh) * sh + kk * dh, 0, ii))
    do_spec = pl.BlockSpec(
        (1, 1, ow, _LANE), lambda kk, ii, cc, mm: (mm // oh, mm % oh, 0, cc))
    o_spec = pl.BlockSpec((1, kw_n, _LANE, _LANE),
                          lambda kk, ii, cc, mm: (kk, 0, ii, cc))
    kernel = functools.partial(_wgrad_kernel, kw_n=kw_n, dw=dw, sw=sw,
                               ow=ow, m_n=m_n)
    g_hwio = pl.pallas_call(
        kernel, name="conv2d_grad_filter", grid=grid,
        in_specs=[x_spec, do_spec], out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((kh, kw_n, ci, co),
                                       out_dtype or x.dtype),
        scratch_shapes=[_scratch((kw_n, _LANE, _LANE))],
        interpret=_interpret(),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
    )(xp, dout)
    return jnp.transpose(g_hwio, (3, 2, 0, 1))


def bn_apply(x2, scale, bias, mean, var, eps, act_fn):
    """x2 [M, C] bf16 (C % 128 == 0, M % 8 == 0); scale/bias/mean/var f32
    [C]. Returns (ybn, yact) with yact None when act_fn is: normalize
    (+ activation), statistics supplied by conv2d_stats."""
    import jax.experimental.pallas as pl
    m_total, c = x2.shape
    bc = _LANE
    bm = next(b for b in (512, 256, 128, 64, 32, 16, 8) if m_total % b == 0)
    grid = (c // bc, m_total // bm)
    x_spec = pl.BlockSpec((bm, bc), lambda cc, mm: (mm, cc))
    vec_spec = pl.BlockSpec((1, bc), lambda cc, mm: (0, cc))
    out_specs = [x_spec] + ([x_spec] if act_fn is not None else [])
    out_shape = [jax.ShapeDtypeStruct((m_total, c), x2.dtype)]
    if act_fn is not None:
        out_shape.append(jax.ShapeDtypeStruct((m_total, c), x2.dtype))
    kernel = functools.partial(_bn_apply_kernel, eps=eps, act=act_fn)
    outs = pl.pallas_call(
        kernel, name="bn_apply", grid=grid,
        in_specs=[x_spec, vec_spec, vec_spec, vec_spec, vec_spec],
        out_specs=out_specs, out_shape=out_shape,
        interpret=_interpret(),
        compiler_params=_compiler_params(("parallel", "parallel")),
    )(x2, scale.reshape(1, c), bias.reshape(1, c), mean.reshape(1, c),
      var.reshape(1, c))
    if act_fn is not None:
        return outs[0], outs[1]
    return outs[0], None


# Dispatch table: the registered op types whose lowering can reach a
# kernel of this suite, and which. check_pallas_table pins it against
# ops/registry.py and quant.QUANT_OPS: a conv op that quantizes but is
# not listed (or the reverse) is a route nobody audits, and a `_grad`
# entry would claim a backward kernel where conv2d_grad only transposes
# the lax conv.
KERNELS = {
    "conv2d": (conv2d_q8,),
    "depthwise_conv2d": (conv2d_q8,),     # groups gate: always declines
}
