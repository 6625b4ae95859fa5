"""Kimi Delta Attention's chunked delta rule as two Pallas TPU kernels: the
forward and its gradient, with a head's [K, V] state in VMEM across the
walk over the chunks.

ops/hybrid_ops.py::kda_chunked states the algorithm in plain jax.numpy
(`_kda_chunk` has the algebra) and stays the path for shapes that do not
tile (hybrid_ops.kda_scan_ineligible) and the tests' independent
statement. There a pass is one lax.scan over the chunks of about twenty
small fusions a chunk, each bound by the fixed cost of an instruction, and
q, k, v and the gate are made float32 and turned to [chunk, head, row,
channel] in HBM on the way in. Here one grid step owns one (batch, head
block, chunk): everything of a chunk is formed in VMEM and consumed there.

Grid (batch, head block, chunk), the chunk axis sequential. A head block
is the R heads one step owns (heads_a_step): a grid step costs about 0.35
us whatever it does, and the heads of a block are independent chains of
products for the scheduler to interleave. The operands are read AS THE
PROJECTIONS WROTE THEM: the op's [B, T, H, K] is a bitcast of [B, T, H x
K], a head block's chunk is the block (chunk, R x K) at (b, c, block),
time on the sublanes and a head one lane block wide, in the dtype it
arrives in (bf16 under AMP). The kernels form what the op's lowering
formed in jax.numpy around kda_chunked: the L2 norms of q and k, g = a x
softplus(gate + dt_bias) with a = -exp(A_log) a head, the running sum of
g down the chunk (one full-precision product with the triangle of ones
for the whole block), and the scaling of the output by K^-1/2; the
gradient's kernel pulls all of that back, so what crosses HBM a pass is
the bf16 operands and results and nothing in float32 of [T, H x K].
beta's sigmoid stays jax.numpy ([B, T, H], 1 MB) and reaches the kernels
as [B, blocks, T, R]: a head's beta is a column that multiplies its rows
by a lane broadcast. a and dt_bias arrive as rows [1, H x K].

The state is kept TRANSPOSED, [V, K] float32 a head in VMEM scratch: the
decay a channel, exp(G_last), is then a row that multiplies it by a
sublane broadcast, w S and (q exp(G)) S contract the last dims of both
operands, and the update is u'^T (k exp(G_last - G)).

The [C, C] system and its inverse are most of what a chunk costs: with
every level of hybrid_ops._unit_lower_inverse as two full-precision
products a head, the forward read 12.7 ms a layer on the chip, 8.9 of it
the inverse (my chip runs, PR 56). Three things took that to 5.9: two
heads' blocks side by side in one [64, 128] array (`_diag_of_packed`),
so a product serves two heads and no vector register is half empty; the
first level, whose products are by the identity, not formed; the second,
whose operands hold one entry a row, written out on the VPU (`_inverse`).

The gradient. The forward kernel writes, beside o, the state ENTERING
each chunk (float32 [B, chunks, H, V, K], which is what the scan kept as
its carries) and each chunk's inverse M (float32, 16 KB a head and chunk
against the eight full-precision products that form it), and the backward
kernel reads both. In a program they are the op's outputs Entering and
Inverse (hybrid_ops._kda_scan calls kda_scan_forward) and its gradient op
runs the backward kernel alone (kda_scan_backward): one forward kernel a
layer and step. WHAT OUTLIVES THE FORWARD where a checkpointed segment
replays the op (PR 65): the op's inputs' checkpoint and the INVERSES
(registry: kept_in_replay); the replayed op is handed them and runs the
forward kernel that READS them in place of _inverse (`given`: another
call, `kda_scan_fwd_given` / `gdn_scan_fwd_given`, nothing else of the
body differs) and writes o and the entering states again. The inverse
hangs on k, beta and the decays alone and is the dear part of a chunk to
form; the entering states are four times its bytes (537 against 134 MB a
layer at 16,384 tokens and 32 heads) and are not kept. On a v5e the
given-inverse call reads 2.05 ms where the plain one reads 5.68 at [1,
8192, 32, 128, 128], 5.13 against 11.97 under a decay a head at 16,384
tokens (tools/kda_sweep.py --path forward,given_inverse; my chip run, PR
65), and 1.52 | 3.40 ms inside the two cells' steps. kda_scan_kernels
is the same pair under one jax.custom_vjp, for callers outside a program
(the tests, tools/kda_sweep.py, a raw op built without the two outputs):
its rule's forward keeps the two for the backward kernel, and a gradient
that re-traces it (registry.generic_grad_lower) runs the forward kernel a
second time as its state pass. The backward kernel
walks the chunks downward with the state's cotangent in the same
scratch; each chunk's system and decayed copies are formed again in VMEM
ahead of their pull-back. With do the output's cotangent and dS' that of
the state a chunk leaves (`_kda_chunk`'s names):
    o = qd S + QK u'            S' = S exp(G_last) + te^T u'
    d qd = do S^T    dQK = do u'^T    du' = QK^T do + te dS'
    d te = u' dS'^T  dw = -du' S^T    dS = dS' exp(G_last) + qd^T do - w^T du'
    dM = dw kb^T + du' vb^T     d[kb vb] = M^T [dw du']
    dA = -M^T dM M^T below the diagonal (float32, full precision)
and the scores' cotangents reach q, k and the summed decays G through
the same sub-block forms that made them, the sub-block's reference row
with them (0 in exact arithmetic, and what cancels the roundings of the
two sides under the running sum's transpose: left out, dt_bias' gradient
read 0.021 from float32 where XLA's form reads 0.005). dG runs back
through the running sum (one product with the triangle's transpose) to
g, the gate, a and dt_bias; a's and dt_bias' shares are summed over the
chunks in the kernel, a row a (batch, head block), and over the batch
and a head's channels in jax.numpy behind it.

Precision (hybrid_ops._kda_chunk's): the state, the running sums, every
exponent, the [C, C] system, its inverse's products (Precision.HIGHEST:
Mosaic's fp32 contraction) and every accumulation are float32; every
other product takes operands in the compute dtype (bf16 under AMP).
Every exponent is a difference referred to a sub-block of _SUB rows.

A decay a HEAD and key heads under groups of value heads (Gated DeltaNet,
arXiv:2412.06464; PR 64) share the kernels' bodies under other names
(`gdn_scan_fwd` / `gdn_scan_bwd`; the channel form's calls are what they
were, jaxpr for jaxpr): g [B, T, H] and its running sum inside each chunk
are formed in jax.numpy inside the rule (`_running_sums`) and reach the
kernels as beta does, [B, blocks, T, R]; the exponents are taken once a
row of all R heads and parked in VMEM scratch, a column a head
(`_decays_a_head`); q and k are read at their own head count, a step's R
/ ratio key heads, their unit rows formed once a key head; and a key
head's dq and dk are summed over its group ahead of the norm's pull-back.
On a v5e at [1, 16384, 16 | 32 heads, 128, 128], chunk 64, bf16, 8 heads
a step (tools/kda_sweep.py --decay head --key-heads 16; my chip run, PR
64), forward | forward + gradient ms: these kernels 12.38 | 21.01; the
channel form's behind a gate broadcast to [B, T, H, K] and q, k repeated
to 32 heads, widening and pull-back included, 15.11 | 27.25.

On CPU the kernels run under the Pallas interpreter (`interpret`).

The op alone on a v5e (tools/kda_sweep.py, my chip runs, PR 56), ms a
call at [1, 8192, 32, 128, 128], chunk 64, bf16 operands, 8 heads a step:
                          forward   forward + gradient
    kda_scan_chunked       10.35         27.79
    the kernels             5.93         10.80
    (in the cell's step: kda_scan_fwd 5.16, kda_scan_bwd 4.51)
Largest error against XLA's form with float32 operands, over the largest
entry, the kernels | XLA's form in bf16: o 3.9e-3 | 4.0e-3, dq 3.8e-3 |
4.7e-3, dk 3.7e-3 | 4.9e-3, dv 8.3e-3 | 8.3e-3, d gate 9.3e-3 | 1.8e-2,
dA_log 1.2e-3 | 4.3e-3, d dt_bias 1.4e-3 | 4.8e-3, d beta 3.1e-3 |
4.2e-3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel_cost

__all__ = ["heads_a_step", "kda_scan_backward", "kda_scan_forward",
           "kda_scan_kernels"]

_LANES = 128
_F32 = jnp.float32
# rows of one sub-block of a chunk: hybrid_ops._KDA_SUB
_SUB = 16
# The most heads one grid step owns, and the most rows x heads of bf16
# operands (half that of float32 ones). A step's VMEM grows with both: a
# head brings q, k, v, gate, o (and in the gradient do and four results
# more) blocks of [chunk, 128], double-buffered, and 64 KB each of state
# scratch and of entering state, the latter double-buffered too. On the
# chip (tools/kda_sweep.py at [1, 8192, 32, 128, 128], chunk 64, bf16; my
# chip runs, PR 56), forward | forward + gradient ms by heads a step: 2
# 7.21 | -, 4 6.11 | 11.13, 8 5.93 | 10.80 (XLA's form 10.35 | 27.79); 16
# heads are refused for VMEM when the gradient's kernel is loaded.
_HEADS = 8
_STEP_ROWS = 512


def heads_a_step(heads: int, chunk: int = 64, itemsize: int = 2,
                 ratio: int = 1) -> int:
    """The heads one grid step owns: the largest divisor of `heads` up to
    _HEADS with chunk x heads within _STEP_ROWS of bf16 operands, half
    that of float32 ones (8 at the published chunk of 64 under AMP).
    Where `ratio` value heads read one key head a step owns whole
    groups (one at least, whatever the rows: the gate admits groups of
    up to _HEADS), so a key head's dq and dk are summed inside it."""
    most = max(ratio, min(_HEADS, _STEP_ROWS * 2 // (chunk * itemsize)))
    return max(r for r in range(1, min(heads, most) + 1)
               if heads % r == 0 and r % ratio == 0)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _dot_nt(a, b):
    """a @ b.T"""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _dot_tn(a, b):
    """a.T @ b"""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _full(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at full precision."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _cat(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


# Two heads' [C, C] blocks (scores, the system, its inverse) stand side by
# side in one [C, 2 C] array where that fills a lane block (C <= 64): a
# float32 [64, 64] alone leaves half of every vector register and half of
# the MXU's columns idle, and the inverse's full-precision products are
# most of what a chunk costs. [X0 | X1] times the block diagonal of (Y0,
# Y1) is [X0 Y0 | X1 Y1]; the transpose of a packed array times another
# holds (X0^T Y0, X1^T Y1) on its block diagonal. With one head a pack
# every function below is the identity.

def _head_of_lane(shape, c):
    """Which head of a pack a lane of a [., pack c] array belongs to."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) >> (c.bit_length() - 1)


def _diag_of_packed(p, c):
    """[X0 | X1] [c, pack c] -> the block diagonal of (X0, X1)."""
    pack = p.shape[1] // c
    if pack == 1:
        return p
    head = _head_of_lane(p.shape, c)
    return jnp.concatenate(
        [jnp.where(head == i, p, jnp.zeros_like(p)) for i in range(pack)],
        axis=0)


def _packed_of_diag(d, c):
    """The diagonal blocks of a [pack c, pack c] array, side by side."""
    pack = d.shape[1] // c
    out = d[:c]
    for i in range(1, pack):
        out = jnp.where(_head_of_lane(out.shape, c) == i,
                        d[i * c:(i + 1) * c], out)
    return out


def _diag_of_heads(xs):
    """[n, w] a head (w whole lane blocks) -> their block diagonal."""
    if len(xs) == 1:
        return xs[0]
    return jnp.concatenate([
        jnp.concatenate([x if j == i else jnp.zeros_like(x)
                         for j in range(len(xs))], axis=1)
        for i, x in enumerate(xs)], axis=0)


def _sum_a_head(x, head, i):
    """[c, pack c] -> [c, 1]: each row's sum over head i's lanes."""
    return jnp.sum(jnp.where(head == i, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _block(x, i, rows, cols):
    """Block (i, i) of an array of [rows, cols] blocks."""
    return x[i * rows:(i + 1) * rows, i * cols:(i + 1) * cols]


def _triangles(c, pack):
    """(row >= col, row > col, row, col) of a pack of [c, c] blocks."""
    row = lax.broadcasted_iota(jnp.int32, (c, pack * c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, pack * c), 1) & (c - 1)
    return row >= col, row > col, row, col


def _inverse(a, row, col, head):
    """(I + a)^-1 of a pack of strictly lower [C, C] float32 blocks by
    block forward substitution: hybrid_ops._unit_lower_inverse's levels.
    The first level's products are by the identity, and the second's
    operands hold one entry a row or a column beside the diagonal, so the
    two are written out on the VPU: with a0 the entries between the rows
    of a pair and a1 those under a pair of pairs, X = I - a0, a1 X is a1
    less its right neighbour times a0's entry in that column, and X (a1
    X) is that less its upper neighbour times a0's entry in that row."""
    from jax.experimental.pallas import tpu as pltpu
    c, width = a.shape

    def under(level):       # rows in the second block of a pair, columns
        return ((row >> level) & 1 == 1) & (        # in the first
            (row >> level) - (col >> level) == 1)

    zero = jnp.zeros_like(a)
    first = jnp.where(under(0), a, zero)
    inverse = jnp.where(row == col, 1.0, 0.0) - first
    along = jnp.sum(first, axis=0, keepdims=True)     # a[c + 1, c]
    down = sum(jnp.where(head == i, _sum_a_head(first, head, i), zero)
               for i in range(width // c))            # a[r, r - 1]
    step = jnp.where(under(1), a, zero)
    step = step - pltpu.roll(step, width - 1, 1) * along
    inverse = inverse - (step - down * pltpu.roll(step, 1, 0))
    for level in range(2, c.bit_length() - 1):
        step = _full(jnp.where(under(level), a, zero),
                     _diag_of_packed(inverse, c))
        inverse = inverse - _full(inverse, _diag_of_packed(step, c))
    return inverse


def _decays(gate, a_row, bias_row, lower):
    """What the gate gives every head of a block at once, [C, R K] each:
    z = gate + dt_bias and its softplus, the summed decays G down the
    chunk, the rows G_ahead ahead of each sub-block's first row, and the
    exponents exp(G - G_ahead) of a row's own sub-block, exp(G) and
    exp(G_last - G), with exp(G_last) as a row; and for each sub-block i
    exp(G_ahead_i - G) over the keys it may see, those up to its own last
    row, 0 over the rest (`rises`)."""
    c, width = gate.shape
    z = gate.astype(_F32) + bias_row
    soft = jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))
    cum = _full(lower, a_row * soft)                  # the running sum
    ahead = [jnp.zeros((1, width), _F32)] + [
        cum[i * _SUB - 1:i * _SUB] for i in range(1, c // _SUB)]
    own = jnp.concatenate(
        [jnp.broadcast_to(x, (_SUB, width)) for x in ahead], axis=0)
    last = cum[c - 1:c]
    at = lax.broadcasted_iota(jnp.int32, cum.shape, 0)
    rises = []
    for i, row in enumerate(ahead):
        seen = at < (i + 1) * _SUB
        rises.append(jnp.where(
            seen, jnp.exp(jnp.where(seen, row - cum, 0.0)), 0.0))
    return dict(z=z, soft=soft, at=at, rises=rises,
                shrunk=jnp.exp(cum - own), decayed=jnp.exp(cum),
                to_end=jnp.exp(last - cum), last=jnp.exp(last))


class _Plane:
    """Plane i of a [n, C, R] scratch, indexed as the array it holds: [rows,
    lanes] is a load from the ref (Mosaic takes no view, `.at`, of a ref
    whose lanes are not whole blocks)."""

    def __init__(self, ref, i):
        self.ref, self.i = ref, i

    def __getitem__(self, at):
        return self.ref[(self.i,) + at]


def _decays_a_head(cum, e_sc, last_sc, kd):
    """_decays where the decay is a head's and not a channel's. `cum` [C,
    R]: the running sum of g down the chunk, a column a head (formed in
    jax.numpy ahead of the call: g is [B, T, H]). Every exponent is taken
    ONCE A ROW of all R heads, R lanes of a vector register where the
    channel form fills 128 a head, and parked in VMEM scratch, whose
    columns the heads read back as [C, 1] and broadcast over their lanes
    as they do beta's (a ref gives a column at a lane offset; a loaded
    value does not). exp(G_last) is parked as [V, R]: a column of it
    multiplies the transposed state's rows."""
    c, r = cum.shape
    ahead = [jnp.zeros((1, r), _F32)] + [
        cum[i * _SUB - 1:i * _SUB] for i in range(1, c // _SUB)]
    own = jnp.concatenate(
        [jnp.broadcast_to(x, (_SUB, r)) for x in ahead], axis=0)
    last = cum[c - 1:c]
    at = lax.broadcasted_iota(jnp.int32, cum.shape, 0)
    e_sc[0] = jnp.exp(cum - own)
    e_sc[1] = jnp.exp(cum)
    e_sc[2] = jnp.exp(last - cum)
    for i, row in enumerate(ahead):
        seen = at < (i + 1) * _SUB
        e_sc[3 + i] = jnp.where(
            seen, jnp.exp(jnp.where(seen, row - cum, 0.0)), 0.0)
    last_sc[...] = jnp.broadcast_to(jnp.exp(last), last_sc.shape)
    return dict(at=lax.broadcasted_iota(jnp.int32, (c, kd), 0),
                rises=[_Plane(e_sc, 3 + i) for i in range(len(ahead))],
                shrunk=_Plane(e_sc, 0), decayed=_Plane(e_sc, 1),
                to_end=_Plane(e_sc, 2), last=last_sc)


def _unit(x, eps):
    """A head's rows over their L2 norm, and the factor."""
    x = x.astype(_F32)
    factor = lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
    return x * factor, factor


def _pack_forward(heads, d, tri, *, c, dtype):
    """What both kernels form of one pack of heads ahead of the state.
    `heads`: a dict a head of its unit q and k, v, beta [C, 1] and the
    head's lanes of `d` (_decays). -> the sub-blocks' row and key forms,
    the masked scores QK and KK [C, pack C], beta over them, and each
    head's [k exp(G) beta | v beta]."""
    lower, below, _, _ = tri
    pack = len(heads)
    for h in heads:
        shrunk = d["shrunk"][:, h["own"]]
        h["qs"], h["ks"] = ((h[x] * shrunk).astype(dtype) for x in "qk")
    rows, keys, qk, kk = [], [], [], []
    for i, rise in enumerate(d["rises"]):
        own = slice(i * _SUB, (i + 1) * _SUB)
        rows.append(_cat([jnp.concatenate([h["qs"][own], h["ks"][own]], axis=0)
                          for h in heads], axis=1))     # [2 sub, pack K]
        keys.append([(h["k"] * rise[:, h["own"]]).astype(dtype)
                     for h in heads])
        scores = _dot_nt(rows[i], _diag_of_heads(keys[i]))  # [2 sub, pack C]
        qk.append(scores[:_SUB])
        kk.append(scores[_SUB:])
    head = _head_of_lane((c, pack * c), c)
    beta = heads[0]["beta"]
    for i in range(1, pack):
        beta = jnp.where(head == i, heads[i]["beta"], beta)
    fed = [jnp.concatenate(
        [(h["k"] * d["decayed"][:, h["own"]] * h["beta"]).astype(dtype),
         (h["v"] * h["beta"]).astype(dtype)], axis=1) for h in heads]
    return dict(rows=rows, keys=keys, fed=fed, head=head,
                beta=jnp.broadcast_to(beta, (c, pack * c)),
                qk=jnp.where(lower, jnp.concatenate(qk, axis=0), 0.0),
                kk=jnp.where(below, jnp.concatenate(kk, axis=0), 0.0))


def _heads_of(refs, j, units, *, kd, vd, eps, ratio, per_head):
    """Value head j of a step: `wide` its key head's lanes of q and k (j
    // ratio: the unit rows are formed once a key head, in `units`),
    `tall` its own of v and o, `own` its lanes of _decays' arrays (its K
    channels, or its one column where the decay is a head's)."""
    q_ref, k_ref, v_ref, beta_ref = refs
    i = j // ratio
    wide, tall = slice(i * kd, (i + 1) * kd), slice(j * vd, (j + 1) * vd)
    if i not in units:
        units[i] = _unit(q_ref[0, :, wide], eps) \
            + _unit(k_ref[0, :, wide], eps)
    q, rq, k, rk = units[i]
    own = slice(j, j + 1) if per_head else slice(j * kd, (j + 1) * kd)
    return dict(j=j, wide=wide, tall=tall, own=own, q=q, k=k, rq=rq, rk=rk,
                v=v_ref[0, :, tall].astype(_F32),
                beta=beta_ref[0, 0, :, j:j + 1])        # [C, 1] float32


def _fwd_kernel(q_ref, k_ref, v_ref, gate_ref, beta_ref, *rest,
                r, pack, kd, vd, eps, scale, dtype, ratio=1, per_head=False,
                given=False):
    """One chunk of a head block: o, and for the gradient the state
    entering the chunk and the chunk's inverse a pack. `rest`: a's and
    dt_bias' rows, the three results and the state's scratch; where the
    decay is a head's (`per_head`: `gate_ref` then holds the running sums
    G [1, 1, C, R]) no rows, and _decays_a_head's two scratches behind
    the state's. `given`: the inverses are the last operand, those an
    earlier run of this kernel wrote over the same k, beta and decays,
    and not a result: none is formed."""
    import jax.experimental.pallas as pl
    if not per_head:
        a_ref, bias_ref, *rest = rest
    if given:
        inverse_ref, o_ref, enter_ref, s_sc, *rest = rest
    else:
        o_ref, enter_ref, inverse_ref, s_sc, *rest = rest
    if per_head:
        e_sc, last_sc = rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_sc[...] = jnp.zeros_like(s_sc)

    c = q_ref.shape[1]
    tri = _triangles(c, pack)
    if per_head:
        d = _decays_a_head(gate_ref[0, 0], e_sc, last_sc, kd)
    else:
        d = _decays(gate_ref[0], a_ref[...], bias_ref[...],
                    _triangles(c, 1)[0].astype(_F32))
    units = {}
    for g in range(r // pack):
        heads = [_heads_of((q_ref, k_ref, v_ref, beta_ref), g * pack + i,
                           units, kd=kd, vd=vd, eps=eps, ratio=ratio,
                           per_head=per_head) for i in range(pack)]
        f = _pack_forward(heads, d, tri, c=c, dtype=dtype)
        if given:
            inverse = inverse_ref[0, 0, g]              # [C, pack C] float32
        else:
            inverse = _inverse(f["kk"] * f["beta"], tri[2], tri[3], f["head"])
            inverse_ref[0, 0, g] = inverse
        wu = _dot(inverse.astype(dtype), _diag_of_heads(f["fed"]))
        fresh, carried = [], []
        for i, h in enumerate(heads):
            j, wide = h["j"], h["own"]
            state = s_sc[j]                             # [V, K] float32
            enter_ref[0, 0, j] = state
            entering = state.astype(dtype)
            w = wu[:, i * (kd + vd):i * (kd + vd) + kd].astype(dtype)
            u = wu[:, i * (kd + vd) + kd:(i + 1) * (kd + vd)]
            fresh.append((u - _dot_nt(w, entering)).astype(dtype))
            s_sc[j] = state * d["last"][:, wide] + _dot_tn(
                fresh[i], (h["k"] * d["to_end"][:, wide]).astype(dtype))
            carried.append(_dot_nt(
                (h["q"] * d["decayed"][:, wide]).astype(dtype), entering))
        inner = _dot(f["qk"].astype(dtype), _diag_of_heads(fresh))
        for i, h in enumerate(heads):
            o_ref[0, :, h["tall"]] = (
                (carried[i] + inner[:, i * vd:(i + 1) * vd]) * scale
            ).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, gate_ref, beta_ref, *rest,
                r, pack, kd, vd, eps, scale, dtype, ratio=1, per_head=False):
    """One chunk of a head block, walked downward. `rest` as
    _fwd_kernel's: where the decay is a head's the kernel hands back the
    running sums' cotangent [1, 1, C, R], a head's channels summed, where
    the channel form pulls it back to the gate, a and dt_bias itself."""
    import jax.experimental.pallas as pl
    if per_head:
        (do_ref, enter_ref, inverse_ref, dq_ref, dk_ref, dv_ref, dcum_ref,
         dbeta_ref, ds_sc, e_sc, last_sc) = rest
    else:
        (a_ref, bias_ref, do_ref, enter_ref, inverse_ref, dq_ref, dk_ref,
         dv_ref, dgate_ref, dbeta_ref, da_ref, dbias_ref, ds_sc) = rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_sc[...] = jnp.zeros_like(ds_sc)
        if not per_head:
            da_ref[...] = jnp.zeros_like(da_ref)
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    c = q_ref.shape[1]
    n = c // _SUB
    tri = _triangles(c, pack)
    lower, below = tri[:2]
    if per_head:
        d = _decays_a_head(gate_ref[0, 0], e_sc, last_sc, kd)
    else:
        running = _triangles(c, 1)[0].astype(_F32)
        a_row = a_ref[...]
        d = _decays(gate_ref[0], a_row, bias_ref[...], running)
    turned = ((0,), (0,)), ((), ())
    both = kd + vd
    d_cum, d_beta = [None] * r, [None] * r
    units, group = {}, None     # a key head's dq and dk over its group
    for g in range(r // pack):
        heads = [_heads_of((q_ref, k_ref, v_ref, beta_ref), g * pack + i,
                           units, kd=kd, vd=vd, eps=eps, ratio=ratio,
                           per_head=per_head) for i in range(pack)]
        f = _pack_forward(heads, d, tri, c=c, dtype=dtype)
        inverse = inverse_ref[0, 0, g]                  # [C, pack C] float32
        inv = inverse.astype(dtype)
        wu = _dot(inv, _diag_of_heads(f["fed"]))
        for i, h in enumerate(heads):
            wide = h["own"]
            h["state"] = enter_ref[0, 0, h["j"]]        # [V, K] float32
            h["entering"] = h["state"].astype(dtype)
            h["w"] = wu[:, i * both:i * both + kd].astype(dtype)
            h["fresh"] = (wu[:, i * both + kd:(i + 1) * both]
                          - _dot_nt(h["w"], h["entering"])).astype(dtype)
            h["decayed"], h["to_end"] = (d[x][:, wide]
                                         for x in ("decayed", "to_end"))
            h["te"] = (h["k"] * h["to_end"]).astype(dtype)
            h["qd"] = (h["q"] * h["decayed"]).astype(dtype)
            h["d_out"] = (do_ref[0, :, h["tall"]].astype(_F32)
                          * scale).astype(dtype)
            h["d_left"] = ds_sc[h["j"]]                 # of S', [V, K]
        # o = qd S + QK u';  S' = S exp(G_last) + te^T u'
        d_out = _cat([h["d_out"] for h in heads], axis=1)
        d_qk = jnp.where(lower, _dot_nt(
            d_out, _diag_of_heads([h["fresh"] for h in heads])), 0.0)
        from_qk = _dot_tn(f["qk"].astype(dtype), d_out)  # [pack C, pack V]
        d_wu = []
        for i, h in enumerate(heads):
            d_left = h["d_left"].astype(dtype)
            h["d_qd"] = _dot(h["d_out"], h["entering"])             # [C, K]
            d_fresh = (_block(from_qk, i, c, vd)
                       + _dot_nt(h["te"], d_left)).astype(dtype)    # [C, V]
            h["d_te"] = _dot(h["fresh"], d_left)                    # [C, K]
            # u' = u - w S;  [w u] = M [kb vb]
            d_wu.append(jnp.concatenate(
                [-_dot(d_fresh, h["entering"]), d_fresh.astype(_F32)],
                axis=1).astype(dtype))                  # [C, K + V]
            ds_sc[h["j"]] = h["d_left"] * d["last"][:, h["own"]] \
                + _dot_tn(h["d_out"], h["qd"]) - _dot_tn(d_fresh, h["w"])
        d_wu = _cat(d_wu, axis=1)
        d_fed = _dot_tn(inv, d_wu)                      # [pack C, pack (K+V)]
        d_inverse = _dot_nt(d_wu, _diag_of_heads(f["fed"]))  # [C, pack C]
        # M = (I + A)^-1: dA = -M^T dM M^T below the diagonal
        d_a = jnp.where(below, -_packed_of_diag(_full(
            inverse, _full(d_inverse, _diag_of_packed(inverse, c),
                           (((1,), (1,)), ((), ()))), turned), c), 0.0)
        d_kk = d_a * f["beta"]
        # What each decayed copy hands the summed decays G is its
        # cotangent times itself: a row's own (the rows, qd, kb) to its G,
        # a key's (the keys, te) from its G, and both to the row they are
        # referred to (the sub-block's G_ahead, the chunk's last). The
        # reference's share is 0 in exact arithmetic and is formed all the
        # same: under the running sum's transpose it takes off, to the
        # last bit, the part of the two that cancels, where their
        # roundings would stay
        for i, h in enumerate(heads):
            h["d_kb"] = _block(d_fed, i, c, both)[:, :kd]
            h["d_vb"] = _block(d_fed, i, c, both)[:, kd:]
            fed_k = h["d_kb"] * h["decayed"] * h["beta"]
            h["d_q"] = h["d_qd"] * h["decayed"]
            h["d_k"] = h["d_te"] * h["to_end"] + fed_k
            last = jnp.sum(h["d_left"] * h["state"] * d["last"][:, h["own"]],
                           axis=0, keepdims=True) + jnp.sum(
                h["d_te"] * h["to_end"] * h["k"], axis=0, keepdims=True)
            h["d_cum"] = h["d_q"] * h["q"] \
                + (fed_k - h["d_te"] * h["to_end"]) * h["k"] \
                + jnp.where(d["at"][:, :kd] == c - 1, last, 0.0)
            h["d_rows"] = []
        for s in range(n):          # the scores, a sub-block of rows a time
            own = slice(s * _SUB, (s + 1) * _SUB)
            rise = d["rises"][s]
            d_scores = jnp.concatenate([d_qk[own], d_kk[own]],
                                       axis=0).astype(dtype)
            d_row = _dot(d_scores, _diag_of_heads(f["keys"][s]))
            d_key = _dot_tn(d_scores, f["rows"][s])     # [pack C, pack K]
            for i, h in enumerate(heads):
                shrunk = d["shrunk"][own, h["own"]]
                mine = d_row[:, i * kd:(i + 1) * kd]
                mine = jnp.concatenate(
                    [mine[:_SUB] * shrunk, mine[_SUB:] * shrunk], axis=0)
                h["d_rows"].append(mine)
                of_key = _block(d_key, i, c, kd) * rise[:, h["own"]]
                h["d_k"] = h["d_k"] + of_key
                h["d_cum"] = h["d_cum"] - of_key * h["k"]
                if s:       # the first sub-block is referred to 0
                    ahead = jnp.sum(of_key * h["k"], axis=0, keepdims=True) \
                        - jnp.sum(mine[:_SUB] * h["q"][own]
                                  + mine[_SUB:] * h["k"][own],
                                  axis=0, keepdims=True)
                    h["d_cum"] = h["d_cum"] + jnp.where(
                        d["at"][:, :kd] == s * _SUB - 1, ahead, 0.0)
        of_a = d_a * f["kk"]
        for i, h in enumerate(heads):
            d_qs = jnp.concatenate([x[:_SUB] for x in h["d_rows"]], axis=0)
            d_ks = jnp.concatenate([x[_SUB:] for x in h["d_rows"]], axis=0)
            d_q, d_k = h["d_q"] + d_qs, h["d_k"] + d_ks
            d_cum[h["j"]] = h["d_cum"] + d_qs * h["q"] + d_ks * h["k"]
            if ratio > 1:   # a key head's dq and dk, summed over its group
                if h["j"] % ratio:  # ahead of the norm's pull-back (linear)
                    d_q, d_k = d_q + group[0], d_k + group[1]
                group = (d_q, d_k)
            if (h["j"] + 1) % ratio == 0:   # the group's last member writes
                # the unit rows: x = x~ r, d x~ = r (dx - x (dx . x))
                dq_ref[0, :, h["wide"]] = (h["rq"] * (d_q - h["q"] * jnp.sum(
                    d_q * h["q"], -1, keepdims=True))).astype(dq_ref.dtype)
                dk_ref[0, :, h["wide"]] = (h["rk"] * (d_k - h["k"] * jnp.sum(
                    d_k * h["k"], -1, keepdims=True))).astype(dk_ref.dtype)
            dv_ref[0, :, h["tall"]] = (h["d_vb"] * h["beta"]).astype(
                dv_ref.dtype)
            d_beta[h["j"]] = (
                jnp.sum(h["d_kb"] * h["k"] * h["decayed"], -1, keepdims=True)
                + jnp.sum(h["d_vb"] * h["v"], -1, keepdims=True)
                + _sum_a_head(of_a, f["head"], i))
    dbeta_ref[0, 0] = _cat(d_beta, axis=1)
    if per_head:    # the running sums' own: a head's channels read one G
        dcum_ref[0, 0] = _cat([jnp.sum(x, -1, keepdims=True) for x in d_cum],
                              axis=1)
        return
    # the running sum, g = a softplus(z), z = gate + dt_bias
    d_g = _full(running, _cat(d_cum, axis=1), turned)
    d_z = d_g * a_row * jax.nn.sigmoid(d["z"])
    dgate_ref[0] = d_z.astype(dgate_ref.dtype)
    da_ref[0] += jnp.sum(d_g * d["soft"], axis=0, keepdims=True)
    dbias_ref[0] += jnp.sum(d_z, axis=0, keepdims=True)


def _packed(chunk, r):
    """The heads whose [C, C] blocks share one array: two where they fill
    a lane block together and the step's heads pair up."""
    return 2 if 2 * chunk <= _LANES and r % 2 == 0 else 1


def _specs(bsz, t, heads, kd, vd, chunk, r, up: bool, ratio: int = 1):
    """(grid, heads a pack, BlockSpecs): grid (batch, head block, chunk
    step), the step walking the chunks upward, or downward for the
    gradient. `keys`: q's and k's block where `ratio` value heads read
    one key head, the step's r / ratio key heads."""
    import jax.experimental.pallas as pl
    chunks = t // chunk
    pack = _packed(chunk, r)

    def z(s):
        return s if up else chunks - 1 - s

    return (bsz, heads // r, chunks), pack, dict(
        wide=pl.BlockSpec((1, chunk, r * kd), lambda b, h, s: (b, z(s), h)),
        tall=pl.BlockSpec((1, chunk, r * vd), lambda b, h, s: (b, z(s), h)),
        keys=pl.BlockSpec((1, chunk, r // ratio * kd),
                          lambda b, h, s: (b, z(s), h)),
        beta=pl.BlockSpec((1, 1, chunk, r), lambda b, h, s: (b, h, z(s), 0)),
        row=pl.BlockSpec((1, r * kd), lambda b, h, s: (0, h)),
        # a (batch, head block)'s share of a row's gradient, summed over
        # the chunks where it stands
        share=pl.BlockSpec((1, 1, r * kd), lambda b, h, s: (b, 0, h)),
        state=pl.BlockSpec((1, 1, r, vd, kd),
                           lambda b, h, s: (b, z(s), h, 0, 0)),
        inverse=pl.BlockSpec((1, 1, r // pack, chunk, pack * chunk),
                             lambda b, h, s: (b, z(s), h, 0, 0)))


def _declared(grid, in_specs, operands, out_shape, *, r, pack, kd, vd,
              per_head, backward=False, given=False):
    """A call's cost_estimate (ops/kernel_cost.py), the work as
    implemented: the MXU FLOPs of every product of `_fwd_kernel` /
    `_bwd_kernel`, a float32 product at Precision.HIGHEST (`_full`: the
    inverse's, the running sum's) at the 6 bf16 passes it runs as, every
    other at one (Mosaic rounds float32 operands at default precision).
    A chunk of a pack of p heads, C rows, K and V wide: the sub-blocks'
    scores 4 C^2 p^2 K; the inverse, two full-precision products of [C,
    pC] by [pC, pC] a level from the third on (none where the inverses
    are `given`, or in the gradient, which reads them); [w u] = M [kb vb]
    2 C pC p(K+V); three state products a head forward, seven in the
    gradient; the within-chunk output; and in the gradient the pull-backs
    of each, dA's two full-precision products and the scores' 8 C^2 p^2
    K. A decay a channel adds the running sum, [C, C] by [C, R K] at
    full precision (and its transpose in the gradient). The decays'
    multiplies and every other pass on the vector unit are time and not
    floor: left out. Transcendentals: the exponents `_decays` /
    `_decays_a_head` take. Bytes: each block times the steps that fetch
    it, the entering states and the inverses among them where read, and
    each result once, those two among them where written."""
    c, p, both = in_specs[0].block_shape[1], pack, kd + vd
    full = kernel_cost.passes(_F32, highest=True)
    square = 2 * c * (p * c) ** 2                       # [C, pC] by [pC, pC]
    scores, wu, state = 4 * c * c * p * p * kd, 2 * c * p * c * p * both, \
        2 * c * kd * vd
    within = 2 * c * p * c * p * vd
    if backward:
        a_pack = scores + wu + 7 * p * state + 2 * within + 2 * wu \
            + 2 * full * square + 2 * scores
    else:
        levels = 0 if given else max(c.bit_length() - 3, 0)
        a_pack = scores + levels * 2 * full * square + wu + 3 * p * state \
            + within
    sub = c // _SUB
    if per_head:
        running, exps = 0, (3 + sub) * c * r
    else:
        running = (1 + backward) * full * 2 * c * c * r * kd
        exps = (5 + backward + sub) * c * r * kd
    steps = grid[0] * grid[1] * grid[2]
    return kernel_cost.estimate(
        steps * (r // p * a_pack + running), steps * exps,
        kernel_cost.fetched_bytes(grid, in_specs, operands)
        + kernel_cost.array_bytes(*out_shape))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, *operands, work):
    """`scratch`: the float32 VMEM scratch's shape, or a list of them;
    `work`: what `_declared` needs of the kernel's statics."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    shapes = scratch if isinstance(scratch, list) else [scratch]
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in shapes],
        interpret=interpret, compiler_params=params,
        cost_estimate=_declared(grid, in_specs, operands, out_shape,
                                **work))(*operands)


_STATIC = ("heads", "chunk", "r", "eps", "dtype", "interpret")


def _rows(a_log, dt_bias, kd):
    """a = -exp(A_log) a head, over its channels, and dt_bias: [1, H K]."""
    return (jnp.repeat(-jnp.exp(a_log.astype(_F32)), kd)[None],
            dt_bias.astype(_F32).reshape(1, -1))


def _forward_call(kernel, name, grid, sp, in_specs, result, scratch,
                  interpret, operands, inverse):
    """A forward kernel's call -> (o, the entering states, the inverses).
    `result`: (o's, the entering states', the inverses') ShapeDtypeStructs.
    Handed `inverse`, the inverses an earlier call wrote over the same k,
    beta and decays, the kernel reads them where it would form them
    (`given`: another kernel under `name`_given) and they are returned as
    they came."""
    work = {x: kernel.keywords.get(x, False)
            for x in ("r", "pack", "kd", "vd", "per_head")}
    if inverse is None:
        return _call(kernel, name, grid, in_specs,
                     [sp["tall"], sp["state"], sp["inverse"]], list(result),
                     scratch, interpret, *operands, work=work)
    out, entering = _call(
        functools.partial(kernel, given=True), name + "_given", grid,
        in_specs + [sp["inverse"]], [sp["tall"], sp["state"]],
        list(result[:2]), scratch, interpret, *operands, inverse,
        work=dict(work, given=True))
    return out, entering, inverse


def _results(v, bsz, chunks, heads, vd, kd, chunk, pack):
    return (jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bsz, chunks, heads, vd, kd), _F32),
            jax.ShapeDtypeStruct(
                (bsz, chunks, heads // pack, chunk, pack * chunk), _F32))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, gate, beta, a_log, dt_bias, inverse=None, *, heads,
             chunk, r, eps, dtype, interpret):
    """(o [B, T, H x V] in v's dtype, the state entering each chunk [B,
    chunks, H, V, K], each chunk's inverse [B, chunks, H / pack, C, pack
    C]), the two the gradient reads in float32. One call for the forward
    and for the custom_vjp's state pass, over the op's own operands: where
    the compiler finds both it runs one. `inverse`: _forward_call's."""
    bsz, t = q.shape[:2]
    kd, vd = q.shape[2] // heads, v.shape[2] // heads
    grid, pack, sp = _specs(bsz, t, heads, kd, vd, chunk, r, True)
    return _forward_call(
        functools.partial(_fwd_kernel, r=r, pack=pack, kd=kd, vd=vd, eps=eps,
                          scale=kd ** -0.5, dtype=dtype),
        "kda_scan_fwd", grid, sp,
        [sp["wide"], sp["wide"], sp["tall"], sp["wide"], sp["beta"],
         sp["row"], sp["row"]],
        _results(v, bsz, grid[2], heads, vd, kd, chunk, pack),
        (r, vd, kd), interpret,
        (q, k, v, gate, beta, *_rows(a_log, dt_bias, kd)), inverse)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, gate, beta, a_log, dt_bias, d_out, entering, inverse,
              *, heads, chunk, r, eps, dtype, interpret):
    """The gradients of q, k, v, the gate and beta as the kernel writes
    them, and a_log's and dt_bias' summed behind it: the kernel leaves a
    row a (batch, head block) of g's cotangent times the softplus, a's
    own a channel, and of z's."""
    bsz, t = q.shape[:2]
    kd, vd = q.shape[2] // heads, v.shape[2] // heads
    grid, pack, sp = _specs(bsz, t, heads, kd, vd, chunk, r, False)
    share = jax.ShapeDtypeStruct((bsz, 1, heads * kd), _F32)
    *grads, d_a, d_bias = _call(
        functools.partial(_bwd_kernel, r=r, pack=pack, kd=kd, vd=vd, eps=eps,
                          scale=kd ** -0.5, dtype=dtype),
        "kda_scan_bwd", grid,
        [sp["wide"], sp["wide"], sp["tall"], sp["wide"], sp["beta"],
         sp["row"], sp["row"], sp["tall"], sp["state"], sp["inverse"]],
        [sp["wide"], sp["wide"], sp["tall"], sp["wide"], sp["beta"],
         sp["share"], sp["share"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct(gate.shape, gate.dtype),
         jax.ShapeDtypeStruct(beta.shape, _F32), share, share],
        (r, vd, kd), interpret, q, k, v, gate, beta,
        *_rows(a_log, dt_bias, kd), d_out, entering, inverse,
        work=dict(r=r, pack=pack, kd=kd, vd=vd, per_head=False,
                  backward=True))
    d_a = d_a.sum((0, 1)).reshape(heads, kd).sum(1)
    return (*grads, (-jnp.exp(a_log.astype(_F32)) * d_a).astype(a_log.dtype),
            d_bias.sum((0, 1)).reshape(dt_bias.shape).astype(dt_bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(q, k, v, gate, beta, a_log, dt_bias, static):
    return _forward(q, k, v, gate, beta, a_log, dt_bias, **dict(static))[0]


def _scan_fwd(q, k, v, gate, beta, a_log, dt_bias, static):
    """The rule's forward is the op's own call with all three results in
    use, so the call a gradient op's re-trace makes here is the one a
    replayed forward op made just ahead of it, and the compiler runs one
    (a call with a result pruned is another kernel and is not merged).
    The entering states and the inverses live from here to the backward
    kernel: inside the gradient op, whose re-trace runs this."""
    operands = (q, k, v, gate, beta, a_log, dt_bias)
    out, entering, inverse = _forward(*operands, **dict(static))
    return out, operands + (entering, inverse)


def _scan_bwd(static, kept, d_out):
    return _backward(*kept[:7], d_out, *kept[7:], **dict(static))


_scan.defvjp(_scan_fwd, _scan_bwd)


# --- a decay a head, key heads under groups of value heads -------------------

_STATIC_A_HEAD = _STATIC + ("ratio",)


def _scratch_a_head(r, chunk, vd, kd):
    """The state's scratch and _decays_a_head's two."""
    return [(r, vd, kd), (3 + chunk // _SUB, chunk, r), (vd, r)]


@functools.partial(jax.jit, static_argnames=_STATIC_A_HEAD)
def _forward_a_head(q, k, v, cum, beta, inverse=None, *, heads, chunk, r,
                    eps, dtype, interpret, ratio):
    """_forward where the decay is a head's and `ratio` value heads read
    one key head: q, k [B, T, H / ratio x K] as their projections wrote
    them (a step's block is its r / ratio key heads: nothing is repeated
    ahead of the call), `cum` [B, blocks, T, R] the running sums of g
    inside each chunk, laid out as beta is. Another kernel under another
    name: the channel form's call is the one it was."""
    bsz, t = q.shape[:2]
    kd, vd = q.shape[2] * ratio // heads, v.shape[2] // heads
    grid, pack, sp = _specs(bsz, t, heads, kd, vd, chunk, r, True, ratio)
    return _forward_call(
        functools.partial(_fwd_kernel, r=r, pack=pack, kd=kd, vd=vd, eps=eps,
                          scale=kd ** -0.5, dtype=dtype, ratio=ratio,
                          per_head=True),
        "gdn_scan_fwd", grid, sp,
        [sp["keys"], sp["keys"], sp["tall"], sp["beta"], sp["beta"]],
        _results(v, bsz, grid[2], heads, vd, kd, chunk, pack),
        _scratch_a_head(r, chunk, vd, kd), interpret, (q, k, v, cum, beta),
        inverse)


@functools.partial(jax.jit, static_argnames=_STATIC_A_HEAD)
def _backward_a_head(q, k, v, cum, beta, d_out, entering, inverse, *, heads,
                     chunk, r, eps, dtype, interpret, ratio):
    """The gradients of q and k (a key head's summed over its group
    inside the kernel), v, the running sums and beta."""
    bsz, t = q.shape[:2]
    kd, vd = q.shape[2] * ratio // heads, v.shape[2] // heads
    grid, pack, sp = _specs(bsz, t, heads, kd, vd, chunk, r, False, ratio)
    return _call(
        functools.partial(_bwd_kernel, r=r, pack=pack, kd=kd, vd=vd, eps=eps,
                          scale=kd ** -0.5, dtype=dtype, ratio=ratio,
                          per_head=True),
        "gdn_scan_bwd", grid,
        [sp["keys"], sp["keys"], sp["tall"], sp["beta"], sp["beta"],
         sp["tall"], sp["state"], sp["inverse"]],
        [sp["keys"], sp["keys"], sp["tall"], sp["beta"], sp["beta"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct(cum.shape, _F32),
         jax.ShapeDtypeStruct(beta.shape, _F32)],
        _scratch_a_head(r, chunk, vd, kd), interpret, q, k, v, cum, beta,
        d_out, entering, inverse,
        work=dict(r=r, pack=pack, kd=kd, vd=vd, per_head=True,
                  backward=True))


def _running_sums(gate, a_log, dt_bias, *, live, chunk, r):
    """gate [B, T, H] raw -> the running sums inside each chunk of g =
    -exp(a_log) softplus(gate + dt_bias), float32 [B, blocks, T, R] (a
    head's a column of its block, as beta's are): 4 B a head and token.
    Rows from `live` on are padding and decay nothing."""
    bsz, t, h = gate.shape
    g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
        gate.astype(_F32) + dt_bias.astype(_F32))
    if live < t:
        g = g * (jnp.arange(t) < live)[None, :, None]
    cum = jnp.cumsum(g.reshape(bsz, -1, chunk, h), axis=2)
    return cum.reshape(bsz, t, h // r, r).transpose(0, 2, 1, 3)


def _split(static):
    """(what _running_sums reads, what the kernels' calls read) of the
    rule's static arguments."""
    static = dict(static)
    sums = dict(live=static.pop("live"), chunk=static["chunk"],
                r=static["r"])
    return sums, static


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan_a_head(q, k, v, gate, beta, a_log, dt_bias, static):
    return _scan_a_head_fwd(q, k, v, gate, beta, a_log, dt_bias, static)[0]


def _scan_a_head_fwd(q, k, v, gate, beta, a_log, dt_bias, static):
    """As _scan_fwd: all three results in use, so a replayed forward op's
    call and the gradient op's re-traced one are one to the compiler. The
    running sums are formed INSIDE the rule for the same reason: traced
    under the gradient op's jax.vjp they were other instructions than
    the forward op's (the softplus and the cumsum linearised), the two
    calls' operands differed and the chip ran the forward kernel a third
    time a layer (PR 64: 9 runs a step of 10.3 ms where 6 were meant)."""
    sums, static_ = _split(static)
    cum = _running_sums(gate, a_log, dt_bias, **sums)
    out, entering, inverse = _forward_a_head(q, k, v, cum, beta, **static_)
    return out, (q, k, v, gate, beta, a_log, dt_bias, entering, inverse)


def _scan_a_head_bwd(static, kept, d_out):
    q, k, v, gate, beta, a_log, dt_bias, entering, inverse = kept
    sums, static = _split(static)
    cum, pull = jax.vjp(functools.partial(_running_sums, **sums), gate,
                        a_log, dt_bias)
    d_q, d_k, d_v, d_cum, d_beta = _backward_a_head(
        q, k, v, cum, beta, d_out, entering, inverse, **static)
    d_gate, d_a, d_bias = pull(d_cum)
    return d_q, d_k, d_v, d_gate, d_beta, d_a, d_bias


_scan_a_head.defvjp(_scan_a_head_fwd, _scan_a_head_bwd)


def _laid_out(q, k, v, gate, beta, *, chunk, r):
    """The op's q, k, v and gate as [B, T', H x K] rows and its raw beta
    as sigmoid(beta) [B, blocks, T', R], a head's a column of its block,
    T' = T padded to whole chunks (beta = 0 there: such a row writes
    nothing, it comes behind every row that is read, and what it decays is
    the state the last chunk leaves, which nobody reads). jax.numpy, so
    autodiff carries it."""
    bsz, t, h, _ = v.shape
    pad = (-t) % chunk
    beta = jax.nn.sigmoid(beta.astype(_F32))
    flat = [x.reshape(bsz, t, -1) for x in (q, k, v, gate)]
    if pad:
        flat = [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in flat]
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    return (*flat,
            beta.reshape(bsz, t + pad, h // r, r).transpose(0, 2, 1, 3))


def _static(q, v, gate, chunk, eps, dtype, interpret, heads):
    """(the heads a step owns, whether the decay is a head's, the rule's
    static arguments as _scan / _scan_a_head take them)."""
    t, h = v.shape[1:3]
    ratio = h // q.shape[2]
    r = heads or heads_a_step(h, chunk, jnp.dtype(dtype).itemsize, ratio)
    assert h % r == 0 and r % ratio == 0, (h, r, ratio)
    static = (("heads", h), ("chunk", chunk), ("r", r), ("eps", float(eps)),
              ("dtype", jnp.dtype(dtype)), ("interpret", bool(interpret)))
    if gate.ndim == 4:
        assert ratio == 1, "a decay a channel reads its own key head"
        return r, False, static
    return r, True, static + (("ratio", ratio), ("live", t))


def kda_scan_kernels(q, k, v, gate, a_log, dt_bias, beta, chunk, eps,
                     dtype=jnp.float32, interpret=False, heads=None):
    """The op kda_scan (hybrid_ops._kda_scan's equations, arguments as
    its slots hold them: q, k, gate [B, T, H, K] and v [B, T, H, V] raw,
    a_log [H], dt_bias [H x K], beta [B, T, H] raw; or, a decay a head,
    gate [B, T, H], dt_bias [H] and q, k [B, T, H / ratio, K]: value
    head j reads key head j // ratio) on the kernels, for
    shapes hybrid_ops.kda_scan_ineligible admits; the result [B, T, H, V]
    in v's dtype, differentiable (one jax.custom_vjp whose gradient runs
    the forward kernel again for its state pass: for callers outside a
    program, whose op keeps the inverses and has a gradient op of its
    own, kda_scan_forward and kda_scan_backward). `heads`: the heads one
    grid step owns (default heads_a_step; tools/kda_sweep.py passes
    others)."""
    r, per_head, static = _static(q, v, gate, chunk, eps, dtype, interpret,
                                  heads)
    laid = _laid_out(q, k, v, gate, beta, chunk=chunk, r=r)
    out = (_scan_a_head if per_head else _scan)(*laid, a_log, dt_bias,
                                                 static)
    return out[:, :v.shape[1]].reshape(v.shape)


def kda_scan_forward(q, k, v, gate, a_log, dt_bias, beta, chunk, eps,
                     dtype=jnp.float32, interpret=False, heads=None,
                     inverse=None):
    """kda_scan_kernels' forward alone -> (the result [B, T, H, V], the
    state entering each chunk [B, chunks, H, V, K], each chunk's inverse
    [B, chunks, H / pack, C, pack C]), the two float32, which
    kda_scan_backward reads. `inverse`: the inverses an earlier call
    wrote from the same k, gate, a_log, dt_bias and beta; the kernel then
    reads them and forms none (they are the dear part of a chunk: eight
    full-precision products a pack of heads), and they come back as they
    came."""
    r, per_head, static = _static(q, v, gate, chunk, eps, dtype, interpret,
                                  heads)
    *rows, gate, beta = _laid_out(q, k, v, gate, beta, chunk=chunk, r=r)
    if per_head:
        sums, static = _split(static)
        out, entering, inverse = _forward_a_head(
            *rows, _running_sums(gate, a_log, dt_bias, **sums), beta, inverse,
            **static)
    else:
        out, entering, inverse = _forward(*rows, gate, beta, a_log, dt_bias,
                                          inverse, **dict(static))
    return out[:, :v.shape[1]].reshape(v.shape), entering, inverse


def kda_scan_backward(q, k, v, gate, a_log, dt_bias, beta, entering, inverse,
                      d_out, chunk, eps, dtype=jnp.float32, interpret=False,
                      heads=None):
    """The gradients of kda_scan_forward's first seven arguments, in
    their order, from its two float32 results and its first's cotangent:
    the backward kernel alone, and the pull-back of the jax.numpy around
    it (_laid_out; the running sums of a decay a head)."""
    r, per_head, static = _static(q, v, gate, chunk, eps, dtype, interpret,
                                  heads)
    laid, pull = jax.vjp(functools.partial(_laid_out, chunk=chunk, r=r),
                         q, k, v, gate, beta)
    bsz, t = v.shape[:2]
    d_out = jnp.pad(d_out.astype(v.dtype).reshape(bsz, t, -1),
                    ((0, 0), (0, laid[0].shape[1] - t), (0, 0)))
    *d_laid, d_a, d_bias = (_scan_a_head_bwd if per_head else _scan_bwd)(
        static, (*laid, a_log, dt_bias, entering, inverse), d_out)
    d_q, d_k, d_v, d_gate, d_beta = pull(tuple(d_laid))
    return d_q, d_k, d_v, d_gate, d_a, d_bias, d_beta
