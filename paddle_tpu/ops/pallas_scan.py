"""Mamba-2's chunked scan (SSD) as two Pallas TPU kernels: the forward and
its gradient, with the [chunk, chunk] decay and score blocks in VMEM.

ops/hybrid_ops.py::ssd_scan_chunked states the algorithm in plain
jax.numpy and stays the path for shapes that do not tile
(hybrid_ops.ssd_scan_ineligible). At the hybrid cell's shape its mask
and decayed scores are [32, 8, 8, 128, 128] arrays, 134 MB each in
float32, written to HBM and read back between the products, and its
gradient recomputes them and writes their cotangents too: 2.0 GB a layer
where the scan's operands and results are 0.22 GB. Here one grid step
owns one (batch, group, chunk): the group's scores are one
[chunk, chunk] block, each head's mask exp(cum_i - cum_j) and decayed
scores are built from it on the VPU and the EUP and consumed by the MXU
without leaving VMEM.

Grid (batch, head block, chunk), the chunk axis sequential. A head block
is the R heads of a group that one step owns (heads_a_step): the whole
group where it has at most 8 heads (the hybrid cell's 8 groups of 8: the
grid is (batch, group, chunk) as it was), else a divisor of it, so one
group of 64 heads (granite-4.0-h-micro) is 2 head blocks of 32 at chunk
128 that read the same B and C; the recurrence is a head's own, so a
block's state needs no other's, and what the heads of a group share, dB
and dC, leaves the gradient's kernel as one share a head block
([B, blocks x N, T]) that jax.numpy adds up behind it (at 8192 tokens 2
shares of 2 MB each way, against 64 MB of x a pass). The running state
of a block's R heads, [R x P, N] float32 (256 KB at R 8, P 64, N 128),
is VMEM scratch, so the recurrence across chunks is in the kernel. The
forward walks the chunks upward and writes the state ENTERING each
chunk, in the compute dtype, beside y: the gradient's one residual
beyond the op's inputs ([chunks, H x P, N]; 33.5 MB a layer in bf16 at
the cell's shape). The gradient walks the chunks downward with the
state's cotangent in the same scratch.

Orientation: TIME RUNS ALONG THE LANES. x, y and their cotangents are
[B, H x P, T], blocks (R x P, chunk); B and C [B, G x N, T], blocks
(N, chunk), and once more with time on the sublanes ([B, T, G x N],
8 MB each); the cumulative log-decays [B, G, R, T] float32, blocks
(R, chunk). A head is P sublanes of its group's block, a slice that
costs nothing; a position's factors exp(cum), exp(cum_end - cum) are
[1, chunk] rows that multiply a head's [P, chunk] by a sublane
broadcast; the sums over a head's P that the log-decays' gradient needs
run down the sublanes and arrive lane-dense; and the per-head products
stream P rows through the MXU, y_r^T = x_r^T W_r^T. For the masks the
kernel turns one [128, chunk] tile of cum a step on the XLU to have it
as columns too (why R <= 128: heads_a_step stays under it). This is also how the hybrid cell's step
holds its activations: at one sequence a step XLA lays [1, T, C] out
with T minor, so the swapaxes around the kernels are bitcasts there. A
first version with time on the sublanes ([chunk, R x P] blocks, two
64-lane heads a lane block behind a lane mask) ran the op alone at the
same speed and the cell 3.0 ms a step slower (busy 157.3 against 154.3
ms, x dt still formed outside both): XLA relaid x, y, dy and dx out
around it, 0.1 ms each a layer (PERF.md section 6, PR 40).

The gradient (Dao & Gu 2024, section 6, pulled back by hand). With
W = (C B^T) o M the decayed scores of a head, E = exp(cum),
F = exp(cum_end - cum) and h, dh the entering state and the cotangent of
the leaving one:
    dX = W^T dY + (B dh) o F            dW = dY X^T     dS = sum_r dW o M
    dC = dS B + (dY o E) h^T            dB = dS^T C + (X o F) dh^T
    dh_in = exp(cum_end) dh + C^T (dY o E)
and the mask's cotangent reaches the log-decays without a stored
[chunk, chunk] array: cum_i multiplies all of y_i, and -cum_i everything
that x_i feeds, so d cum_i = dY_i . Y_i - dX_i . X_i (the row sums of
dM o M less its column sums, written through the products that are
there anyway), plus at a chunk's last position dh . h of the state it
leaves. Y is recomputed. The cumulative sum and its transpose stay
jax.numpy around the kernels ([B, T, H] float32, 1 MB). The kernels
read x and dt and form x dt where they read it (rounded to the compute
dtype as ssd_scan_chunked rounds it), and the gradient's kernel hands
back x's and dt's shares of x dt's cotangent: as jax.numpy around the
kernels the product was a pass over [T, H x P] each way, and XLA wrote
dt's broadcast over P out as 67 MB of float32 beside it.

Precision: cumulative sums, masks, both running states and every
accumulation float32; the operands of the products in the dtype they
arrive in (bf16 under AMP, float32 without), as ssd_scan_chunked's.

On CPU the kernels run under the Pallas interpreter (`interpret`).

The op alone on a v5e (tools/scan_sweep.py, my chip run, PR 40), ms a
call at [1, 4096, 64, 64], G 8, N 128, chunk 128, bf16 operands, x and y
row-major outside (so the kernels' path pays four transposes of
[4096, 4096] there that the cell's step does not):
                          forward   forward + gradient
    ssd_scan_chunked        1.24         5.78
    the kernels             0.47         1.47
    ssd_scan_fwd alone      0.42   (0.23-0.31 in the cell's step)
    ssd_scan_bwd alone      0.71   (0.50 in the cell's step)
Largest error against the chunked form in float32, over the largest
value: y 3.5e-3 (chunked in bf16 3.1e-3), dx 3.2e-3 (3.2e-3), d dt
2.4e-3 (2.7e-3), d a 4.3e-3 (1.2e-3), dB 4.1e-3 (4.9e-3), dC 3.0e-3
(5.2e-3). With x dt formed outside the kernels the pair read 0.54 and
1.83; the first version (time on the sublanes) 0.63 and 1.84.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel_cost

__all__ = ["heads_a_step", "ssd_scan_kernels"]

_LANES = 128
_F32 = jnp.float32


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _dot_tn(a, b):
    """a.T @ b"""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _columns(cum):
    """cum [R, L] -> [L, 128]: column r is cum[r]; one tile turned on the
    XLU."""
    r, l = cum.shape
    return jnp.concatenate(
        [cum, jnp.zeros((_LANES - r, l), _F32)], axis=0).T


def _rows_of(cum):
    """cum [R, L] -> exp(cum), exp(cum_end - cum) [R, L] and exp(cum_end)
    [R, 128]: a head's factors as rows, which multiply its [P, .] rows
    by a sublane broadcast. (exp after the lane broadcast: a [1, 1] value
    broadcast both ways at once is not something Mosaic lowers.)"""
    l = cum.shape[1]
    end = cum[:, l - 1:l]
    return (jnp.exp(cum), jnp.exp(end - cum),
            jnp.exp(jnp.broadcast_to(end, (cum.shape[0], _LANES))))


def _decayed(state, decay):
    """state [P, N] * decay [1, 128], a lane block at a time (a row of a
    wider [R, N] crashed Mosaic's compile at N = 256)."""
    return jnp.concatenate(
        [state[:, j:j + _LANES] * decay
         for j in range(0, state.shape[1], _LANES)], axis=1)


def _fwd_kernel(cum_ref, dt_ref, x_ref, b_ref, ct_ref, y_ref, hin_ref, h_sc,
                *, r, p):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_sc[...] = jnp.zeros_like(h_sc)

    cum, dt = cum_ref[0, 0], dt_ref[0, 0]             # [R, L] float32
    b, ct = b_ref[0], ct_ref[0]                       # [L, N], [N, L]
    dtype = b.dtype
    l, n = b.shape
    cols = _columns(cum)
    grow, to_end, decay = _rows_of(cum)
    # time runs along the lanes: S^T[j, i] = B_j . C_i, once a group, and a
    # head's M^T[j, i] = exp(cum_i - cum_j) for i >= j
    upper = lax.broadcasted_iota(jnp.int32, (l, l), 0) \
        <= lax.broadcasted_iota(jnp.int32, (l, l), 1)
    scores_t = _dot(b, ct)
    h = h_sc[...]                                     # [R x P, N] float32
    entering = h.astype(dtype)
    hin_ref[0, 0] = entering
    carried = _dot(entering, ct)                      # [R x P, L]
    weighted = []
    for k in range(r):
        rows = slice(k * p, (k + 1) * p)
        x = (x_ref[0, rows, :].astype(_F32) * dt[k:k + 1]).astype(dtype)
        m_t = jnp.where(upper, jnp.exp(cum[k:k + 1, :] - cols[:, k:k + 1]),
                        0.0)
        y_ref[0, rows, :] = _dot(x, (scores_t * m_t).astype(dtype)) \
            + carried[rows] * grow[k:k + 1]                   # x dt, [P, L]
        weighted.append((x.astype(_F32) * to_end[k:k + 1]).astype(dtype))
        h_sc[rows, :] = _decayed(h[rows], decay[k:k + 1])
    h_sc[...] += _dot(jnp.concatenate(weighted, axis=0), b)


def _bwd_kernel(cum_ref, dt_ref, x_ref, dy_ref, b_ref, bt_ref, c_ref, ct_ref,
                hin_ref, dx_ref, dcum_ref, ddt_ref, dbt_ref, dct_ref, dtot_ref,
                dh_sc, *, r, p):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dh_sc[...] = jnp.zeros_like(dh_sc)

    cum, dt = cum_ref[0, 0], dt_ref[0, 0]
    b, bt, c, ct = b_ref[0], bt_ref[0], c_ref[0], ct_ref[0]
    dtype = b.dtype
    l, n = b.shape
    cols = _columns(cum)
    grow, to_end, decay = _rows_of(cum)
    row = lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = lax.broadcasted_iota(jnp.int32, (l, l), 1)
    lower, upper = row >= col, row <= col
    scores, scores_t = _dot(c, bt), _dot(b, ct)       # [i, j] and [j, i]
    h = hin_ref[0, 0]                                 # entering, [R x P, N]
    dh = dh_sc[...]                                   # of the leaving, f32
    carried = _dot(h, ct)                             # [R x P, L]
    from_dh = _dot(dh.astype(dtype), bt)              # (B dh)^T
    d_scores = jnp.zeros((l, l), _F32)
    d_cum, d_dt, dy_grown, weighted = [], [], [], []
    for k in range(r):
        rows = slice(k * p, (k + 1) * p)
        x_in = x_ref[0, rows, :].astype(_F32)          # [P, L]
        x = (x_in * dt[k:k + 1]).astype(dtype)         # x dt
        dy = dy_ref[0, rows, :]
        diff = cols[:, k:k + 1] - cum[k:k + 1, :]      # cum_row - cum_lane
        m = jnp.where(lower, jnp.exp(diff), 0.0)
        m_t = jnp.where(upper, jnp.exp(-diff), 0.0)
        y = _dot(x, (scores_t * m_t).astype(dtype)) \
            + carried[rows] * grow[k:k + 1]
        d_x = _dot(dy, (scores * m).astype(dtype)) \
            + from_dh[rows] * to_end[k:k + 1]
        d_scores = d_scores + _dot_tn(dy, x) * m
        # d_x is x dt's: x gets it times dt, dt its sum with x over the
        # head's P sublanes, lane-dense as it is; and likewise
        # d cum_i = dY_i . Y_i - dX_i . X_i
        dx_ref[0, rows, :] = (d_x * dt[k:k + 1]).astype(dx_ref.dtype)
        d_dt.append(jnp.sum(d_x * x_in, axis=0, keepdims=True))
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        d_cum.append(jnp.sum(dy32 * y - d_x * x32, axis=0, keepdims=True))
        dy_grown.append((dy32 * grow[k:k + 1]).astype(dtype))
        weighted.append((x32 * to_end[k:k + 1]).astype(dtype))
        dh_sc[rows, :] = _decayed(dh[rows], decay[k:k + 1])
    dy_grown = jnp.concatenate(dy_grown, axis=0)      # [R x P, L]
    weighted = jnp.concatenate(weighted, axis=0)
    dh_sc[...] += _dot(dy_grown, c)
    dh_in = dh_sc[...]
    left = dh_in * h.astype(_F32)
    dtot_ref[0, 0, 0] = jnp.concatenate(
        [jnp.sum(left[k * p:(k + 1) * p], axis=0, keepdims=True)
         for k in range(r)], axis=0)
    dcum_ref[0, 0] = jnp.concatenate(d_cum, axis=0)
    ddt_ref[0, 0] = jnp.concatenate(d_dt, axis=0)
    dct_ref[0] = (_dot_tn(h, dy_grown)
                  + _dot(bt, d_scores.T.astype(dtype))).astype(dct_ref.dtype)
    dbt_ref[0] = (_dot_tn(dh.astype(dtype), weighted)
                  + _dot(ct, d_scores.astype(dtype))).astype(dbt_ref.dtype)


def _declared(grid, in_specs, operands, out_shape, scratch, backward):
    """A call's cost_estimate (ops/kernel_cost.py), the work as
    implemented, a grid step of R heads of P rows, a chunk of L positions
    and a state N wide: forward, the group's scores 2 L^2 N once, the
    entering state's read-out and the state's update 2 RP N L each, and a
    head's masked product 2 P L^2; the gradient, both orientations of the
    scores, Y again, dX and dW a head (three times 2 P L^2), the
    read-outs of h and dh, dh's update, and dB's and dC's two products
    each. One pass each: the operands arrive in the compute dtype. A head
    narrower than 128 lanes is still P rows of a product L deep here, so
    nothing is padded in this orientation. Transcendentals: a head's
    [L, L] mask (two in the gradient) and its rows' factors. Bytes: each
    block times the steps that fetch it (B and C once a head block and
    chunk), each result once."""
    (rp, n), l = scratch, in_specs[0].block_shape[-1]
    r = in_specs[0].block_shape[2]
    if backward:
        flops = 2 * 2 * l * l * n + 3 * 2 * rp * l * l + 5 * 2 * rp * n * l \
            + 2 * 2 * n * l * l
    else:
        flops = 2 * l * l * n + 2 * rp * l * l + 2 * 2 * rp * n * l
    exps = (1 + backward) * r * l * l + 2 * r * l + r * _LANES
    steps = grid[0] * grid[1] * grid[2]
    return kernel_cost.estimate(
        steps * flops, steps * exps,
        kernel_cost.fetched_bytes(grid, in_specs, operands)
        + kernel_cost.array_bytes(*out_shape))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, *operands):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, _F32)], interpret=interpret,
        compiler_params=params,
        cost_estimate=_declared(grid, in_specs, operands, out_shape, scratch,
                                name.endswith("_bwd")))(*operands)


# The most positions x heads one grid step owns, chunk x heads of a group.
# A step's work and its VMEM grow with both: 64 heads of one group
# (granite-4.0-h-micro) at the published chunk of 256 would be x, y, dy
# and dx blocks of [4096, 256], 2-4 MB each and double-buffered, a 2 MB
# state and 64 unrolled masks a step, and Mosaic refuses it for VMEM; so
# does 32 heads at 256. On the chip (tools/scan_sweep.py at
# [1, 8192, 64, 64], G 1, N 128, bf16; my chip run, PR 49), forward +
# gradient in ms by chunk and heads a step: 128 x 8 2.80, x 16 2.43,
# x 32 2.30; 256 x 8 2.74, x 16 2.60 (ssd_scan_chunked 11.10): more heads
# a step are fewer steps, and B, C and the scores are fetched and formed
# once a step. A group of at most 8 heads is one step whatever the chunk
# (the hybrid cell's 8 groups of 8: the shape the kernels were written
# and measured at).
_STEP_ROWS = 4096
_WHOLE_GROUP = 8


def heads_a_step(heads_a_group: int, chunk: int = 128,
                 itemsize: int = 2) -> int:
    """The heads of a group that one grid step owns: all of a group of at
    most _WHOLE_GROUP, else its largest divisor r with chunk x r within
    _STEP_ROWS of bf16 operands, half that of float32 ones (a head
    block; module docstring): 32 of 64 at chunk 128, 16 at chunk 256;
    without AMP 16 and 8 (Mosaic refuses 16 float32 heads at 256)."""
    if heads_a_group <= _WHOLE_GROUP:
        return heads_a_group
    most = max(_WHOLE_GROUP, _STEP_ROWS * 2 // (chunk * itemsize))
    return max(k for k in range(1, min(heads_a_group, most) + 1)
               if heads_a_group % k == 0)


def _grid(x, b, chunk, r, p, groups, up: bool):
    """(grid, N, BlockSpecs) over x [B, H x P, T] and b [B, T, G x N]:
    grid (batch, head block, chunk step), the step walking the chunks
    upward, or downward for the gradient. A head block is `r` heads of
    one of the `groups` groups and reads that group's B and C."""
    import jax.experimental.pallas as pl
    bsz, hp, t = x.shape
    blocks, chunks = hp // (r * p), t // chunk
    n, l, rp = b.shape[2] // groups, chunk, r * p
    per = blocks // groups                     # head blocks a group

    def z(s):
        return s if up else chunks - 1 - s

    return (bsz, blocks, chunks), n, dict(
        cum=pl.BlockSpec((1, 1, r, l), lambda i, g, s: (i, g, 0, z(s))),
        x=pl.BlockSpec((1, rp, l), lambda i, g, s: (i, g, z(s))),
        b=pl.BlockSpec((1, l, n), lambda i, g, s: (i, z(s), g // per)),
        bt=pl.BlockSpec((1, n, l), lambda i, g, s: (i, g // per, z(s))),
        # a head block's share of dB and dC, [B, blocks x N, T]
        dbt=pl.BlockSpec((1, n, l), lambda i, g, s: (i, g, z(s))),
        h=pl.BlockSpec((1, 1, rp, n), lambda i, g, s: (i, z(s), g, 0)),
        tot=pl.BlockSpec((1, 1, 1, r, n), lambda i, g, s: (i, z(s), g, 0, 0)))


@functools.partial(jax.jit, static_argnames=("chunk", "r", "p", "groups",
                                             "interpret"))
def _forward(cum, dt, x, b, ct, *, chunk, r, p, groups, interpret):
    grid, n, sp = _grid(x, b, chunk, r, p, groups, True)
    return _call(
        functools.partial(_fwd_kernel, r=r, p=p), "ssd_scan_fwd", grid,
        [sp["cum"], sp["cum"], sp["x"], sp["b"], sp["bt"]],
        [sp["x"], sp["h"]],
        [jax.ShapeDtypeStruct(x.shape, _F32),
         jax.ShapeDtypeStruct((grid[0], grid[2], x.shape[1], n), b.dtype)],
        (r * p, n), interpret, cum, dt, x, b, ct)


@functools.partial(jax.jit, static_argnames=("chunk", "r", "p", "groups",
                                             "interpret"))
def _backward(cum, dt, x, dy, b, bt, c, ct, entering, *, chunk, r, p, groups,
              interpret):
    grid, n, sp = _grid(x, b, chunk, r, p, groups, False)
    shares = (grid[0], grid[1] * n, x.shape[2])
    out = _call(
        functools.partial(_bwd_kernel, r=r, p=p), "ssd_scan_bwd", grid,
        [sp["cum"], sp["cum"], sp["x"], sp["x"], sp["b"], sp["bt"], sp["b"],
         sp["bt"], sp["h"]],
        [sp["x"], sp["cum"], sp["cum"], sp["dbt"], sp["dbt"], sp["tot"]],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(cum.shape, _F32),
         jax.ShapeDtypeStruct(cum.shape, _F32),
         jax.ShapeDtypeStruct(shares, b.dtype),
         jax.ShapeDtypeStruct(shares, c.dtype),
         jax.ShapeDtypeStruct((grid[0], grid[2], grid[1], r, n), _F32)],
        (r * p, n), interpret, cum, dt, x, dy, b, bt, c, ct, entering)
    if grid[1] == groups:
        return out
    # B and C are their group's: the head blocks' shares of dB and dC add up
    d_x, d_cum, d_dt, d_b, d_c, d_tot = out
    d_b, d_c = (v.astype(_F32).reshape(grid[0], groups, -1, n, v.shape[2])
                .sum(2).reshape(bt.shape).astype(v.dtype) for v in (d_b, d_c))
    return d_x, d_cum, d_dt, d_b, d_c, d_tot


def _head_rows(v, g):
    """[B, T, H] -> [B, G, R, T]: heads on sublanes, time on lanes."""
    bsz, t, h = v.shape
    return v.reshape(bsz, t, g, h // g).transpose(0, 2, 3, 1)


def _of_head_rows(v):
    """[B, G, R, T] -> [B, T, H]"""
    bsz, g, r, t = v.shape
    return v.transpose(0, 3, 1, 2).reshape(bsz, t, g * r)


def _cum_rows(da, chunk, g):
    """da [B, T, H] float32 -> its cumulative sum inside each chunk,
    [B, G, R, T]."""
    bsz, t, h = da.shape
    cum = jnp.cumsum(da.reshape(bsz, t // chunk, chunk, h), axis=2)
    return _head_rows(cum.reshape(bsz, t, h), g)


def _flat(v):
    """[B, T, a, b] -> [B, T, a x b] and the same with time last."""
    rows = v.reshape(v.shape[:2] + (-1,))
    return rows, rows.swapaxes(1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, da, b, c, chunk, r, interpret):
    return _scan_fwd(x, dt, da, b, c, chunk, r, interpret)[0]


def _scan_fwd(x, dt, da, b, c, chunk, r, interpret):
    bsz, t, h, p = x.shape
    blocks = h // r                            # head blocks, group-major
    y, entering = _forward(
        _cum_rows(da, chunk, blocks), _head_rows(dt, blocks), _flat(x)[1],
        _flat(b)[0], _flat(c)[1], chunk=chunk, r=r, p=p, groups=b.shape[2],
        interpret=interpret)
    return y.swapaxes(1, 2).reshape(x.shape), (x, dt, da, b, c, entering)


def _scan_bwd(chunk, r, interpret, res, dy):
    x, dt, da, b, c, entering = res
    bsz, t, h, p = x.shape
    blocks = h // r
    d_x, d_cum, d_dt, d_b, d_c, d_tot = _backward(
        _cum_rows(da, chunk, blocks), _head_rows(dt, blocks), _flat(x)[1],
        _flat(dy.astype(b.dtype))[1], *_flat(b), *_flat(c), entering,
        chunk=chunk, r=r, p=p, groups=b.shape[2], interpret=interpret)
    # the state a chunk leaves is the next one's entering state: its
    # dh . h, summed over the head, lands on the chunk's last position;
    # then the cumulative sum's transpose inside each chunk
    d_cum = _of_head_rows(d_cum).reshape(bsz, t // chunk, chunk, h)
    left = d_tot.sum(-1).reshape(bsz, t // chunk, h)
    left = jnp.pad(left[:, 1:], ((0, 0), (0, 1), (0, 0)))
    d_cum = d_cum.at[:, :, -1].add(left)
    d_da = jnp.cumsum(d_cum[:, :, ::-1], axis=2)[:, :, ::-1]
    return (d_x.swapaxes(1, 2).reshape(x.shape), _of_head_rows(d_dt),
            d_da.reshape(da.shape), d_b.swapaxes(1, 2).reshape(b.shape),
            d_c.swapaxes(1, 2).reshape(c.shape))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_kernels(x, dt, a, b, c, chunk, dtype=jnp.float32,
                     interpret=False, heads=None):
    """ssd_scan_chunked's recurrence, arguments and precision on the two
    kernels, for shapes hybrid_ops.ssd_scan_ineligible admits. dt * a and
    the padding of T to a multiple of `chunk` (dt = 0: such a step
    neither decays nor feeds the state) stay jax.numpy, so autodiff
    carries them; the kernels and their rule see x, dt, the log-decays,
    B and C, and multiply x by dt where they read it. `heads`: the heads
    of a group one grid step owns (default heads_a_step of the group's;
    tools/scan_sweep.py passes others)."""
    t = x.shape[1]
    pad = (-t) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    dt = dt.astype(_F32)
    per_group = x.shape[2] // b.shape[2]
    heads = heads or heads_a_step(per_group, chunk,
                                  jnp.dtype(dtype).itemsize)
    assert per_group % heads == 0, (per_group, heads)
    y = _scan(x, dt, dt * a.astype(_F32), b.astype(dtype), c.astype(dtype),
              chunk, heads, interpret)
    return y[:, :t]
