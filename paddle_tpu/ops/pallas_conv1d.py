"""The short causal convolution of the state-space and delta-rule mixers
as two Pallas TPU kernels, forward and gradient, that read the
projection's rows once in the dtype they arrive in and write once in the
same dtype (hybrid_ops.causal_conv1d_reference states the op in plain
jax.numpy, stays the path for shapes the gate declines and is what the
tests hold the kernels to).

    pre[t] = Bias + sum_j Filter[:, j] * X[t - (K-1) + j]      zeros before t = 0
    Out    = silu(pre)
    dpre   = dOut * s * (1 + pre * (1 - s)),  s = sigmoid(pre)
    dX[t]  = sum_j Filter[:, j] * dpre[t + (K-1) - j]          zeros past T
    dFilter[:, j] = sum_t dpre[t] * X[t - (K-1) + j],  dBias = sum_t dpre

Why kernels: the arithmetic is K multiply-adds and a silu an element, but
XLA materialises jnp.pad(float32(X)) as a float32 [1, T, C] copy and its
gradient keeps and reads more of the same (788.8 MB a gradient op at the
hybrid cell's [1, 4096, 6144] where X, dOut and dX in bf16 are 151 MB:
PERF_TABLES.md, PR 40). Here nothing of [T, C] in float32 reaches HBM:
X (and dOut) are widened in VMEM, pre and its sigmoid are recomputed by
the gradient's kernel, and the gradient reads X, Filter, Bias and dOut
and nothing the forward kept, so a replayed segment replays one kernel.

One body, two orientations. A grid step owns a [time, channels] block of
one sequence and walks it in chunks that fit the vector registers (a
fori_loop over groups of channels, inside it one over chunks of time),
so the K shifted copies, pre, its sigmoid and the products of one chunk
never leave them. The time axis is a static parameter, `axis`:
  axis 0, time on the sublanes: X is [B, T, C] as the delta rule's
    kernels read q, k and v (ops/pallas_kda.py). Chunks of [128, 128].
  axis 1, time on the lanes: X is [B, C, T] behind a swapaxes, which is
    how the scan's kernels take their operands (ops/pallas_scan.py: at one
    sequence a step XLA lays the Mamba cells' [1, T, C] out with T minor,
    so the swapaxes are bitcasts there and a kernel that wanted rows
    would pay a relayout an operand). Chunks of [16, 1024].
The LAYER says which it builds for (`time_on_lanes`, an attribute
mamba2_mixer writes because its scan reads time along the lanes;
kda_mixer writes none). The taps shift along the time axis with
pltpu.roll over the chunk extended by the `unit` steps before it (16
rows or 128 lanes: whole tiles, so the extension and the slice back cost
no relayout); before a block's first chunk those steps are the tail of
the previous time block, carried in VMEM scratch along the sequential
time axis (zeros before t = 0). The gradient walks time DOWNWARD, blocks
and chunks: dX needs dpre of the K-1 steps AFTER a chunk, which is the
head of the chunk it has just done (a loop carry, and scratch across
blocks; zeros past T), and pre needs X of the K-1 steps BEFORE the
block, a second narrow BlockSpec on X. dFilter and dBias are summed in
float32 a tile-wide partial a tap (8 rows or 128 lanes: vector adds
alone) in the output block, which stays in VMEM along the time axis and
is written once a channel block; jax.numpy folds the partials behind the
kernel.

Precision: float32 inside, the K additions in the statement's order
(Bias first), so the interpreted forward equals the statement bit for
bit; Out and dX leave in X's dtype, dFilter and dBias in float32.

The op alone on a v5e (tools/conv1d_sweep.py, my chip run, PR 60, call 6,
the committed files), ms a call, forward | forward + gradient, bf16
operands, K = 4, sixteen calls chained in one executable; a form is the
block / the chunk, each time x channels:
                          [1, 8192, 4096]  [1, 8192, 4352]  [1, 4096, 6144]
                          no Bias (Kimi)   Bias (granite)   Bias (hybrid)
  XLA's statement          0.953 | 3.936    1.161 | 4.456    0.238 | 1.808
  time on the sublanes
    512x512 / 128x128      0.409 | 0.885    0.441 | 1.040    0.258 | 0.585
    512x512 / 64x128       0.448 | 0.914    0.483 | 1.062    0.288 | 0.630
    1024x512 / 128x128     0.406 | 0.858    0.430 | 0.969    0.251 | 0.576
    512x512 / 32x128       0.533 | 1.099    (call 1)
  time on the lanes
    512x512 / 512x16       0.786 | 1.979    0.840 | 2.141    0.542 | 1.455
    1024x512 / 512x16      0.812 | 2.067    0.868 | 2.232    0.564 | 1.523
    2048x256 / 512x16      0.810 | 2.046    0.862 | 2.200    0.559 | 1.504
    512x512 / 256x16       1.281 | 3.357    1.369 | 3.613    0.915 | 2.491
    1024x256 / 1024x16     0.598 | 1.451    0.631 | 1.557    0.400 | 1.049
Largest difference from the statement on the same operands, over the
largest entry: Out 0 (the same bits on the chip too), dX 1.6e-3 (one
rounding of a bf16 result), dFilter and dBias 3e-7. The block hardly
matters once it is half a megabyte; the chunk does: a chunk is one pass
of a loop whose fixed cost is about 20 cycles beside 10 a float32 vreg
(sublanes: 102 cycles a [64, 128] chunk, 61 a [32, 128], 188 a [128,
128]), and with time on the lanes the steps before a chunk are a whole
lane block, a quarter of a 512-step chunk's rolls and an eighth of a
1024-step one's. What the time is made of (call 2, the kernels with
parts taken out, [1, 8192, 4096], forward | gradient): sublanes 0.41 |
0.47 of which the rolls 0.03 | 0.06 and silu / sigmoid 0.10 | 0.03 (the
exact reciprocal; 0.5 (1 + tanh(x / 2)) would be 0.07 | 0.02 less and
not the statement's bits), the rest 0.30 | 0.37 loads, the bf16 round
trip and the multiply-adds at about 7 cycles a vreg; lanes (512x16)
0.88 | 1.25 before the taps were widened once a block (_widen) and the
gradient's rolls shortened, 0.79 | 1.19 after. Inside a cell's step the
kernels read faster than chained here: 0.25 to 0.27 | 0.385 ms a call in
the Kimi-Linear cell (sublanes); with time on the lanes 0.65 | 1.20 in
granite's and 0.46 | 0.85 in the hybrid cell's at 512x512 / 512x16 (call
6), 0.44 | 0.83 and 0.31 | 0.59 at 1024x256 / 1024x16 (call 7), which
is the table's below: the longest chunk a [16, .] group's values still
fit the registers at, and a block of one chunk a group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel_cost

__all__ = ["causal_conv1d_bwd", "causal_conv1d_fwd", "ineligible"]

_F32 = jnp.float32
# most taps a kernel takes: the K-1 steps before a chunk come from one
# `unit` of time, and a sublane tile of float32 is 8 rows
_MAX_TAPS = 8
# by time axis: (time, channels) of a grid step's block, (time, channels)
# of a chunk, the steps read before a chunk (whole packed bf16 rows, or a
# lane block) and the width along time of a partial sum a tap
_TILES = {0: dict(tile=(512, 512), chunk=(128, 128), unit=16, fold=8),
          1: dict(tile=(1024, 256), chunk=(1024, 16), unit=128, fold=128)}


def _largest(n: int, most: int, step: int):
    """The largest multiple of `step` that divides n and is at most
    `most`, or None."""
    return next((d for d in range(min(most, n) // step * step, 0, -step)
                 if n % d == 0), None)


def ineligible(t: int, c: int, k: int, dtype):
    """None when the kernels take X [., `t`, `c`] of `dtype` under `k`
    taps, in either orientation, else the reason
    hybrid_ops.causal_conv1d_reference keeps the op
    (kernel_choice.REASONS["causal_conv1d"]): time and channels must be
    whole 128-wide blocks (`time`, `channels`: a lane block one way, whole
    chunks the other), the operands bf16 or float32 (`dtype`), and the
    taps' reach inside one sublane tile (`taps`)."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return "dtype"
    if not 1 <= k <= _MAX_TAPS:
        return "taps"
    if t % 128:
        return "time"
    if c % 128:
        return "channels"
    return None


def _oriented(axis, time, channels):
    return (time, channels) if axis == 0 else (channels, time)


def _window(axis, t0, tlen, c0, clen):
    import jax.experimental.pallas as pl
    return _oriented(axis, pl.ds(t0, tlen), pl.ds(c0, clen))


def _widen(w_ref, wide_ref):
    """Time on the lanes: each tap's column of the [cc, K (+1)] block
    broadcast over one lane block of `wide_ref`, once a channel block (at
    its first time step), so a chunk reads its taps as whole tiles; a
    column broadcast a chunk was a third of the forward's time (PR 60,
    call 2)."""
    cc, rows = w_ref.shape
    for j in range(rows):
        wide_ref[:, j * 128:(j + 1) * 128] = jnp.broadcast_to(
            w_ref[:, j:j + 1], (cc, 128))


def _taps_of(ref, axis, k, bias, c0, co, ct):
    """The K taps of a group of channels, and the bias or None, each
    shaped to multiply a chunk: rows of the [K (+1), cc] block, which
    broadcast down the sublanes, or the widened columns (_widen) set side
    by side along the chunk's lanes."""
    import jax.experimental.pallas as pl
    rows = k + bool(bias)
    if axis == 0:
        w = ref[:, pl.ds(c0, co)]
        taps = [w[j:j + 1] for j in range(rows)]
    else:
        taps = [jnp.concatenate(
            [ref[pl.ds(c0, co), j * 128:(j + 1) * 128]] * (ct // 128), axis=1)
            for j in range(rows)]
    return taps[:k], (taps[k] if bias else None)


def _delayed(x, before, k, axis):
    """[X[t - (K-1) + j] for j in range(K)] over a chunk: `x` behind the
    `before` steps, rolled forward along time and cut back."""
    from jax.experimental.pallas import tpu as pltpu
    unit = before.shape[axis]
    ext = jnp.concatenate([before, x], axis)
    return [x if j == k - 1 else lax.slice_in_dim(
        pltpu.roll(ext, k - 1 - j, axis), unit, None, axis=axis)
        for j in range(k)]


def _chunk(x_ref, p_ref, win, r, ct, unit, c0, co, before_block):
    """Chunk `r` of a group of channels in float32: (its first step, X,
    the gate ahead or None, U = PreGate * X: what the taps read, and U
    of the `unit` steps before the chunk: the block's own, or ahead of
    its first chunk `before_block`, the previous time block's tail)."""
    import jax.experimental.pallas as pl
    t0 = pl.multiple_of(r * ct, ct)
    at = win(t0, ct, c0, co)
    x = x_ref[at].astype(_F32)
    earlier = win(pl.multiple_of(jnp.maximum(t0 - unit, 0), unit), unit, c0,
                  co)
    before = x_ref[earlier].astype(_F32)
    p, u = None, x
    if p_ref is not None:
        p = p_ref[at].astype(_F32)
        u, before = x * p, before * p_ref[earlier].astype(_F32)
    return t0, x, p, u, jnp.where(r == 0, before_block, before)


def _pre(delayed, taps, bias):
    """Bias + sum_j tap_j * X[t - (K-1) + j], added in the statement's
    order (0.0 + a is a: no Bias starts from the first product)."""
    pre = bias
    for xs, w in zip(delayed, taps):
        pre = xs * w if pre is None else pre + xs * w
    return pre


def _fwd_kernel(*refs, axis, k, bias, chunk, unit, pre=False, post=False,
                act="silu"):
    import jax.experimental.pallas as pl

    it = iter(refs)
    x_ref = next(it)
    p_ref = next(it) if pre else None
    q_ref = next(it) if post else None
    w_ref, o_ref, halo_ref, *wide = it
    tt, cc = x_ref.shape[axis], x_ref.shape[1 - axis]
    ct, co = chunk
    win = functools.partial(_window, axis)
    taps_ref, = wide or (w_ref,)

    @pl.when(pl.program_id(2) == 0)
    def _():
        halo_ref[...] = jnp.zeros_like(halo_ref)
        if wide:
            _widen(w_ref, taps_ref)

    def group(g, _):
        c0 = pl.multiple_of(g * co, co)
        taps, b = _taps_of(taps_ref, axis, k, bias, c0, co, ct)

        def one(r, _):
            t0, _, _, u, before = _chunk(x_ref, p_ref, win, r, ct, unit, c0,
                                         co, halo_ref[win(0, unit, c0, co)])
            out = _pre(_delayed(u, before, k, axis), taps, b)
            if act == "silu":
                out = jax.nn.silu(out)
            if q_ref is not None:
                out = out * q_ref[win(t0, ct, c0, co)].astype(_F32)
            o_ref[win(t0, ct, c0, co)] = out.astype(o_ref.dtype)

        lax.fori_loop(0, tt // ct, one, None)

    lax.fori_loop(0, cc // co, group, None)
    tail = win(tt - unit, unit, 0, cc)
    halo_ref[...] = x_ref[tail].astype(_F32) if p_ref is None \
        else x_ref[tail].astype(_F32) * p_ref[tail].astype(_F32)


def _folded(p, axis, fold):
    """p summed along time down to `fold` steps: whole tiles added."""
    n = p.shape[axis] // fold
    total = lax.slice_in_dim(p, 0, fold, axis=axis)
    for i in range(1, n):
        total = total + lax.slice_in_dim(p, i * fold, (i + 1) * fold,
                                         axis=axis)
    return total


def _bwd_kernel(*refs, axis, k, bias, chunk, unit, fold, pre=False,
                post=False, act="silu"):
    """Operands: X and the `unit` steps of X before the block, the gate
    ahead and its steps before likewise, the gate behind, Out's
    cotangent, the taps; results: dX, the gates' gradients, the taps'
    partial sums; scratch as _scratch gives it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(refs)
    x_ref, prev_ref = next(it), next(it)
    p_ref, prev_p_ref = (next(it), next(it)) if pre else (None, None)
    q_ref = next(it) if post else None
    do_ref, w_ref, dx_ref = next(it), next(it), next(it)
    dp_ref = next(it) if pre else None
    dq_ref = next(it) if post else None
    dw_ref, next_ref, *wide = it
    tt, cc = x_ref.shape[axis], x_ref.shape[1 - axis]
    ct, co = chunk
    chunks = tt // ct
    win = functools.partial(_window, axis)
    i = pl.program_id(2)
    first_block = i == pl.num_programs(2) - 1       # time runs downward
    taps_ref, = wide or (w_ref,)

    @pl.when(i == 0)
    def _():
        next_ref[...] = jnp.zeros_like(next_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if wide:
            _widen(w_ref, taps_ref)

    def group(g, _):
        c0 = pl.multiple_of(g * co, co)
        taps, b = _taps_of(taps_ref, axis, k, bias, c0, co, ct)
        before_block = prev_ref[win(0, unit, c0, co)].astype(_F32)
        if prev_p_ref is not None:
            before_block = before_block * prev_p_ref[
                win(0, unit, c0, co)].astype(_F32)
        before_block = jnp.where(first_block, 0.0, before_block)

        def one(q, carry):
            after, sums = carry
            t0, x, p, u, before = _chunk(x_ref, p_ref, win, chunks - 1 - q,
                                         ct, unit, c0, co, before_block)
            at = win(t0, ct, c0, co)
            delayed = _delayed(u, before, k, axis)
            if act == "silu" or q_ref is not None:
                pre = _pre(delayed, taps, b)
            if act == "silu":
                s = jax.nn.sigmoid(pre)
            dpre = do_ref[at].astype(_F32)
            if q_ref is not None:   # Out = PostGate * act(pre)
                dq_ref[at] = (dpre * (pre * s if act == "silu" else pre)
                              ).astype(dq_ref.dtype)
                dpre = dpre * q_ref[at].astype(_F32)
            if act == "silu":
                dpre = dpre * (s * (1.0 + pre * (1.0 - s)))
            # dX[t] = sum_j tap_j * dpre[t + (K-1) - j]: the chunk ahead
            # of the `after` steps, rolled on by a unit less the reach (a
            # roll back by the reach, past the array's end, was three
            # times as dear along the lanes) and read a unit later
            ext = jnp.concatenate([dpre, after], axis)
            ahead = [dpre if j == k - 1 else lax.slice_in_dim(
                pltpu.roll(ext, unit - (k - 1 - j), axis), unit, unit + ct,
                axis=axis) for j in range(k)]
            d_u = _pre(ahead, taps, None)
            if p_ref is None:
                dx_ref[at] = d_u.astype(dx_ref.dtype)
            else:                   # U = PreGate * X
                dx_ref[at] = (d_u * p).astype(dx_ref.dtype)
                dp_ref[at] = (d_u * x).astype(dp_ref.dtype)
            terms = [dpre * xs for xs in delayed] + [dpre] * bool(bias)
            sums = tuple(acc + _folded(p, axis, fold)
                         for acc, p in zip(sums, terms))
            return lax.slice_in_dim(dpre, 0, unit, axis=axis), sums

        zero = jnp.zeros(_oriented(axis, fold, co), _F32)
        after, sums = lax.fori_loop(
            0, chunks, one,
            (next_ref[win(0, unit, c0, co)], (zero,) * (k + bool(bias))))
        next_ref[win(0, unit, c0, co)] = after
        for j, total in enumerate(sums):
            at = win(j * fold, fold, c0, co)
            dw_ref[at] = dw_ref[at] + total

    lax.fori_loop(0, cc // co, group, None)


def _forms(axis, t, c, tile, chunk):
    """(block, chunk, unit, fold) of a call over [t, c]: the table's, the
    block cut to the largest whole 128-wide part of each axis (the gate
    holds both to 128) and the chunk to a whole part of the block."""
    form = _TILES[axis]
    tile, chunk = tile or form["tile"], chunk or form["chunk"]
    tt, cc = _largest(t, tile[0], 128), _largest(c, tile[1], 128)
    ct = _largest(tt, chunk[0], max(form["unit"], form["fold"]))
    co = _largest(cc, chunk[1], 16 if axis else 128)
    assert ct and co, (axis, t, c, tile, chunk)
    return (tt, cc), (ct, co), form["unit"], form["fold"]


def _scratch(axis, unit, cc, rows):
    """The `unit` steps carried from one time block to the next, and with
    time on the lanes the taps widened to a lane block each (_widen)."""
    from jax.experimental.pallas import tpu as pltpu
    carried = pltpu.VMEM(_oriented(axis, unit, cc), _F32)
    return [carried, pltpu.VMEM((cc, rows * 128), _F32)] if axis else [carried]


def _form(pre, post, act):
    """The kernels' keywords of the gated form; none for the plain one,
    whose calls are traced as they were."""
    assert act in ("silu", "identity"), act
    return dict(pre=pre, post=post, act=act) \
        if pre or post or act != "silu" else {}


def _declared(grid, in_specs, out_shape, dtype, k, bias, pre, post, act,
              backward):
    """A call's cost_estimate (ops/kernel_cost.py). The kernels issue no
    product: their arithmetic is the vector unit's, counted here as the
    statement counts it (a tap a multiply and an add an element, silu
    four and its derivative five, a gate one, and in the gradient the
    taps three times: the pre-activation again, dX and the taps' sums),
    which no floor is made of at these shapes: the bytes are. Bytes: X,
    the gates and Out's cotangent once a block, the `unit` steps before
    a block beside them in the gradient, the taps once a channel block,
    and each result once. One transcendental an element under silu."""
    elements = math.prod(out_shape[0].shape)
    silu = act == "silu"
    if backward:
        a_element = 3 * 2 * k + bias + pre * 3 + post * 2 + silu * 8
    else:
        a_element = 2 * k - (not bias) + pre + post + silu * 4
    operands = [jax.ShapeDtypeStruct((), dtype)] * (len(in_specs) - 1) \
        + [jax.ShapeDtypeStruct((), _F32)]
    return kernel_cost.estimate(
        elements * a_element, elements * silu,
        kernel_cost.fetched_bytes(grid, in_specs, operands)
        + kernel_cost.array_bytes(*out_shape))


@functools.lru_cache(maxsize=None)
def _fwd_call(axis, bsz, t, c, k, bias, dtype, tile, chunk, interpret,
              pre=False, post=False, act="silu"):
    """One traced forward a shape: a model's layers, a replayed segment's
    forwards and q, k and v lower the same jitted function. `pre`,
    `post`: whether the gates are operands, behind X in that order, each
    in X's blocks; a call with a gate goes by a name of its own."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (tt, cc), chunk, unit, _ = _forms(axis, t, c, tile, chunk)
    rows = k + bool(bias)
    block = pl.BlockSpec((None,) + _oriented(axis, tt, cc),
                         lambda b, j, i: (b,) + _oriented(axis, i, j))
    taps = pl.BlockSpec(_oriented(axis, rows, cc),
                        lambda b, j, i: _oriented(axis, 0, j))
    grid, in_specs = (bsz, c // cc, t // tt), [block] * (1 + pre + post) + [taps]
    out_shape = jax.ShapeDtypeStruct((bsz,) + _oriented(axis, t, c), dtype)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, axis=axis, k=k, bias=bias,
                          chunk=chunk, unit=unit, **_form(pre, post, act)),
        name=("gated_conv1d" if pre or post else "causal_conv1d") + "_fwd",
        grid=grid, in_specs=in_specs, out_specs=block, out_shape=out_shape,
        scratch_shapes=_scratch(axis, unit, cc, rows),
        interpret=interpret,
        cost_estimate=_declared(grid, in_specs, [out_shape], dtype, k, bias,
                                pre, post, act, False),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    return jax.jit(call)


@functools.lru_cache(maxsize=None)
def _bwd_call(axis, bsz, t, c, k, bias, dtype, tile, chunk, interpret,
              pre=False, post=False, act="silu"):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (tt, cc), chunk, unit, fold = _forms(axis, t, c, tile, chunk)
    rows, blocks = k + bool(bias), t // tt

    def down(i):
        return blocks - 1 - i

    block = pl.BlockSpec((None,) + _oriented(axis, tt, cc),
                         lambda b, j, i: (b,) + _oriented(axis, down(i), j))
    # the `unit` steps of X before the block (block 0 reads its own first
    # ones, which the kernel takes as zeros)
    prev = pl.BlockSpec(
        (None,) + _oriented(axis, unit, cc),
        lambda b, j, i: (b,) + _oriented(
            axis, kernel_cost.maximum(down(i) * (tt // unit) - 1, 0), j))
    taps = pl.BlockSpec(_oriented(axis, rows, cc),
                        lambda b, j, i: _oriented(axis, 0, j))
    sums = pl.BlockSpec((None,) + _oriented(axis, rows * fold, cc),
                        lambda b, j, i: (b,) + _oriented(axis, 0, j))
    grid = (bsz, c // cc, blocks)
    in_specs = [block, prev] * (1 + pre) + [block] * (1 + post) + [taps]
    out_shape = [jax.ShapeDtypeStruct((bsz,) + _oriented(axis, t, c),
                                      dtype)] * (1 + pre + post) + [
        jax.ShapeDtypeStruct((bsz,) + _oriented(axis, rows * fold, c), _F32)]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, axis=axis, k=k, bias=bias,
                          chunk=chunk, unit=unit, fold=fold,
                          **_form(pre, post, act)),
        name=("gated_conv1d" if pre or post else "causal_conv1d") + "_bwd",
        grid=grid, in_specs=in_specs,
        out_specs=[block] * (1 + pre + post) + [sums], out_shape=out_shape,
        scratch_shapes=_scratch(axis, unit, cc, rows),
        interpret=interpret,
        cost_estimate=_declared(grid, in_specs, out_shape, dtype, k, bias,
                                pre, post, act, True),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    return jax.jit(call)


def _packed_taps(w, bias, axis):
    """Filter [C, K] and Bias [C] or None as one float32 operand: [C, K
    (+1)] with time on the lanes, its transpose with time on the
    sublanes, so a tap broadcasts along time."""
    taps = w.astype(_F32)
    if bias is not None:
        taps = jnp.concatenate([taps, bias.astype(_F32)[:, None]], axis=1)
    return taps.T if axis == 0 else taps


def _gates(pre_gate, post_gate, activation, like):
    """The gated form's operands (those that are there, in the kernels'
    order) and the calls' keywords."""
    gates = [g for g in (pre_gate, post_gate) if g is not None]
    assert all(g.shape == like.shape and g.dtype == like.dtype
               for g in gates), [g.shape for g in gates]
    return gates, (pre_gate is not None, post_gate is not None, activation)


def causal_conv1d_fwd(x, w, bias=None, *, pre_gate=None, post_gate=None,
                      activation="silu", lanes=False, tile=None, chunk=None,
                      interpret=False):
    """PostGate * act(Bias + the causal depthwise conv of PreGate * X
    [B, T, C] under Filter [C, K]) in X's dtype, on the forward kernel;
    the gates are optional and X's shape and dtype, `activation` "silu"
    or "identity"; `lanes`: time along the lanes (the kernel sees
    [B, C, T])."""
    bsz, t, c = x.shape
    axis = int(lanes)
    assert ineligible(t, c, w.shape[1], x.dtype) is None
    gates, form = _gates(pre_gate, post_gate, activation, x)
    call = _fwd_call(axis, bsz, t, c, w.shape[1], bias is not None,
                     jnp.dtype(x.dtype), tile, chunk, interpret, *form)
    taps = _packed_taps(w, bias, axis)
    if lanes:
        return jnp.swapaxes(call(*(jnp.swapaxes(a, 1, 2)
                                   for a in [x] + gates), taps), 1, 2)
    return call(x, *gates, taps)


def causal_conv1d_bwd(x, w, bias, d_out, *, pre_gate=None, post_gate=None,
                      activation="silu", lanes=False, tile=None, chunk=None,
                      interpret=False):
    """(dX in X's dtype, dFilter [C, K] float32, dBias [C] float32 or
    None, dPreGate and dPostGate in X's dtype or None) of
    causal_conv1d_fwd from its operands and Out's cotangent, on the
    gradient's kernel: U = PreGate * X, the pre-activation and its
    sigmoid are computed again."""
    bsz, t, c = x.shape
    axis, k = int(lanes), w.shape[1]
    assert ineligible(t, c, k, x.dtype) is None and d_out.shape == x.shape
    gates, form = _gates(pre_gate, post_gate, activation, x)
    call = _bwd_call(axis, bsz, t, c, k, bias is not None,
                     jnp.dtype(x.dtype), tile, chunk, interpret, *form)
    taps = _packed_taps(w, bias, axis)
    if lanes:
        x, d_out = jnp.swapaxes(x, 1, 2), jnp.swapaxes(d_out, 1, 2)
        gates = [jnp.swapaxes(g, 1, 2) for g in gates]
    # X and the gate ahead twice: the block, and the steps before it
    *grads, sums = call(x, x, *gates[:1] * 2 * form[0], *gates[form[0]:],
                        d_out, taps)
    if lanes:
        grads = [jnp.swapaxes(g, 1, 2) for g in grads]
        sums = jnp.swapaxes(sums, 1, 2)
    # [B, rows x fold, C] -> [rows, C]: the partials folded
    sums = sums.reshape(bsz, k + (bias is not None), -1, c).sum((0, 2))
    grads = iter(grads)
    return (next(grads), sums[:k].T, (sums[k] if bias is not None else None),
            next(grads) if form[0] else None,
            next(grads) if form[1] else None)
