"""The int8 conv of AMP O3: `conv2d_q8`, a tiled MXU Pallas kernel, and
its tiling gate `ineligible`. It is the repo's only conv kernel and
`amp.decorate(level="O3")`'s only int8 conv: quant.qconv2d dispatches it
behind quant.ineligible_conv, which asks `ineligible` here and reports a
miss as reason "kernel". Every float conv and every conv backward is
XLA's convolution (ops/nn_ops.py::_lax_conv): bf16 kernels on this grid
ran ResNet-50 eleven times slower than it on a v5e (PERF.md section 6,
PR 25; deleted at PR 45), and no pair on a chip has priced this one
(ROADMAP.md Design, `int8 conv verdict`).

Tiling: NHWC int8 operands, int32 VMEM accumulation, channels in
128-lane tiles, the per-channel dequantization vector applied to the
output row while it is still in VMEM. The grid walks one output row per
step with an H *input* block of size 1: at block size 1 the BlockSpec
index map addresses *rows*, so strided/dilated input-row selection
(`oh*stride + kh*dilation`) happens in the index map and no halo
exchange or revisit is needed. Inside the kernel the kw taps unroll as a
Python loop of unit-stride row slices (a strided conv's row is
de-interleaved into its width phases first, `_deinterleave`) feeding
[OW, Ci] x [Ci, Co] MXU dots into an accumulator that carries across the
sequential (innermost) reduction dim of the grid:

  grid (N, OH/BH, Co/128, KH*Ci/128 * BH), acc [BH, OW, 128]

BH is the multi-row pipelining factor: the filter tile is by far the
heaviest HBM stream of the row-walk, so the reduction dim is extended by
BH output rows with the row index *innermost*. Consecutive grid steps
then keep the same filter block index and Pallas skips the copy: filter
traffic divides by BH while the accumulator grows to [BH, OW, 128] rows
of VMEM. BH is the largest of {8, 4, 2, 1} that divides OH and fits the
VMEM row budget.

KERNELS lists the dispatch for tools/check_registry.py; the gate's
reasons are kernel_choice.REASONS["conv2d"]. On CPU the kernel runs
under the Pallas interpreter, so its parity tests are tier-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel_cost
from .pallas_attention import _compiler_params, _interpret

__all__ = ["KERNELS", "PALLAS_CONV", "conv2d_q8", "ineligible"]

# Read by nothing; tests/benchmark/test_run_cpu.py (the benchmark's file) setattr's it.
PALLAS_CONV = True

_LANE = 128

# VMEM width budget: each grid step keeps a [Wp, 128] input row, an
# [OW, 128] 32-bit accumulator and an [OW, 128] output row resident
# (double buffered by the pipeline). 2048 lanes bounds that resident set
# around 3 MB, comfortably inside the ~16 MB/core VMEM of current TPUs,
# so wider shapes fall back to lax.conv instead of failing Mosaic
# compilation at run time.
_MAX_W = 2048


def ineligible(x, w, strides, paddings, dilations, groups=1, mesh=None):
    """None when the kernel's tiling applies, else the reason
    (kernel_choice.REASONS["conv2d"]).

    `x` is the NHWC operand *post mxu_cast* and before quantization (AMP
    convs are bf16 by here; a plain f32 conv reads "dtype"), `w` the
    OIHW filter. Padding past (K-1)*d per spatial dim declines: the
    bound is the deleted grad-input kernel's, and the route is not
    widened before a chip has priced it. `mesh` is the program's SPMD
    mesh: XLA cannot partition a Mosaic custom call (it would gather
    every operand and run the whole conv on each device), and the kernel
    is not wrapped in shard_map, so a step partitioned over more than
    one device declines.
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
        # the padded width or the accumulator row would overflow the
        # VMEM row budget (W + KWe - 1: the deleted grad-input re-pad's
        # width, kept with the bound above)
        return "geometry"
    return None


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


def _fwd_kernel(x_ref, w_ref, dq_ref, o_ref, acc, *, kw_n, dw, sw, ow, n_s,
                bh):
    """Grid (N, OH/BH, Co/128, KH*Ci/128 * BH): one output row [OW, 128]
    per (n, oh, co), reduction taps streamed innermost with the H-block
    row index `hb` cycling fastest, so the filter block index is
    unchanged for BH consecutive steps and its copy is skipped (module
    docstring). int8 operands, int32 accumulator, per-channel dequant
    vector applied on the way out."""
    import jax.experimental.pallas as pl
    ss2 = pl.program_id(3)
    ss = ss2 // bh                 # reduction step: kh * n_ci + ci tile
    hb = ss2 % bh                  # output row within the H block

    @pl.when(ss == 0)
    def _zero():
        acc[pl.ds(hb, 1)] = jnp.zeros((1,) + acc.shape[1:], acc.dtype)

    x_row = x_ref[0, 0]            # [Wp, 128] one padded input row
    wt = w_ref[0]                  # [KW, 128, 128] one kh tap
    total = None
    for kw, xs in enumerate(_taps(x_row, kw_n, dw, sw, ow)):
        t = _dot_i32(xs, wt[kw], ((1,), (0,)))
        total = t if total is None else total + t
    acc[pl.ds(hb, 1)] += total[None]

    @pl.when(ss == n_s - 1)
    def _finish():
        row = acc[pl.ds(hb, 1)].astype(jnp.float32) * dq_ref[...]
        o_ref[0, pl.ds(hb, 1)] = row.astype(o_ref.dtype)


# --- pallas_call wrappers -----------------------------------------------

def _block_h(oh: int, ow: int) -> int:
    """Pipelining factor: the largest H block that divides OH and keeps
    the [BH, OW, 128] accumulator + the output block inside a ~3 MB
    VMEM slice of the row budget (4+2 bytes per element, x2 pipeline)."""
    return next(b for b in (8, 4, 2, 1) if oh % b == 0 and b * ow <= 4096)


def _conv_call(x, w_hwio, strides, paddings, dilations, dq, out_dtype):
    """The conv driver. `x` NHWC int8 (unpadded), `w_hwio` int8 [KH, KW,
    Ci, Co], `dq` f32 [1, Co]: int32 accumulation, dequantized on the
    output row in VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, _, _, ci = x.shape
    kh, kw_n, _, co = w_hwio.shape
    sh, sw = strides
    ph, pw = paddings
    dh, dw = dilations
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    oh = (xp.shape[1] - ((kh - 1) * dh + 1)) // sh + 1
    ow = (xp.shape[2] - ((kw_n - 1) * dw + 1)) // sw + 1
    xp = _deinterleave(xp, kw_n, dw, sw)
    wp = xp.shape[2]
    n_ci = ci // _LANE
    n_s = kh * n_ci
    bh = _block_h(oh, ow)
    grid = (n, oh // bh, co // _LANE, n_s * bh)
    x_spec = pl.BlockSpec(
        (1, 1, wp, _LANE),
        lambda nn, hh, cc, ss: (
            nn, (hh * bh + ss % bh) * sh + (ss // bh // n_ci) * dh, 0,
            (ss // bh) % n_ci))
    w_spec = pl.BlockSpec(
        (1, kw_n, _LANE, _LANE),
        lambda nn, hh, cc, ss: (ss // bh // n_ci, 0, (ss // bh) % n_ci, cc))
    dq_spec = pl.BlockSpec((1, _LANE), lambda nn, hh, cc, ss: (0, cc))
    o_spec = pl.BlockSpec((1, bh, ow, _LANE),
                          lambda nn, hh, cc, ss: (nn, hh, 0, cc))
    kernel = functools.partial(_fwd_kernel, kw_n=kw_n, dw=dw, sw=sw,
                               ow=ow, n_s=n_s, bh=bh)
    out_shape = jax.ShapeDtypeStruct((n, oh, ow, co), out_dtype)
    # The work as implemented (ops/kernel_cost.py): KW products [OW, 128]
    # by [128, 128] a grid step, int8 on the MXU's doubled rate, so
    # counted at half a bf16 pass each (a floor against the bf16 peak
    # must not pass what runs); an input row and a filter tap times the
    # steps that fetch them (the tap held over an H block), the result
    # once.
    steps = grid[0] * grid[1] * grid[2] * grid[3]
    cost = kernel_cost.estimate(
        steps * kw_n * ow * _LANE * _LANE, 0,
        kernel_cost.fetched_bytes(grid, [x_spec, w_spec, dq_spec],
                                  (xp, w_hwio, dq))
        + kernel_cost.array_bytes(out_shape))
    return pl.pallas_call(
        kernel, name="conv2d_q8", cost_estimate=cost,
        grid=grid, in_specs=[x_spec, w_spec, dq_spec], out_specs=o_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bh, ow, _LANE), jnp.int32)],
        interpret=_interpret(),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
    )(xp, w_hwio, dq)


def conv2d_q8(x, w, strides, paddings, dilations, dq, out_dtype=None):
    """Quantized forward: x [N, H, W, Ci] int8, w [Co, Ci, KH, KW] int8,
    dq f32 [Co] the combined activation*weight dequant scales
    (quant.qconv2d builds them). int32 VMEM accumulation, dequantized to
    `out_dtype` (default bf16) on the output row. Caller must have
    passed quant.ineligible_conv, which requires the `ineligible` gate
    here."""
    return _conv_call(x, jnp.transpose(w, (2, 3, 1, 0)), strides, paddings,
                      dilations, jnp.asarray(dq, jnp.float32).reshape(1, -1),
                      out_dtype or jnp.bfloat16)


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
