"""Flash attention as a Pallas TPU kernel (the "pallas for the hot ops"
tier of the compute path; /opt/skills/guides/pallas_guide.md patterns).

Forward (`flash_fwd`): online softmax. A tile of Q rows stays resident
in VMEM, K/V are walked block by block with the running max, denominator
and output in VMEM scratch, so the [T, T] score matrix never reaches
HBM. The only residual saved for the backward is the per-row logsumexp
(m + log l).

Backward: the standard flash backward (FlashAttention-2 style), two
kernels that recompute the score blocks in VMEM from (Q, K, LSE):
`flash_dq` (Q/dO tile resident, K/V walked) and `flash_dkv` (K/V tile
resident, Q/dO walked). All three kernels take global (q_off, k_off)
position offsets, so the same code serves the single-device path
(offsets 0) and the per-shard blocks of the ring composition
(parallel/ring_attention.py).

Orientation: every kernel computes the TRANSPOSED block S^T = K Q^T
[rows of K, rows of Q]. The softmax statistics (running max and sum,
LSE, the backward's delta) are then [1, rows of Q] rows: lane-dense,
reduced along sublanes by the VPU and broadcast along sublanes for
free. With S as [rows of Q, rows of K] they are cross-lane reductions
and [rows, 1] columns that use one lane of 128, and the forward took
1.5x as long on a v5e (PERF.md section 6, PR 29). The forward and dq
accumulate their outputs transposed too (O^T = V^T P^T, dQ^T = K^T dS^T,
one head's D sublanes at a time; the walked V or K block is turned once
on the XLU) and turn the tile back when they store it.

Layout (PR 29): operands stay in the model's [B, T, H, D], viewed as
[B, T, H*D] (free: the model reshapes a [B, T, H*D] projection into
heads and back). Heads are a GRID axis: one grid step owns one block of
128 lanes = 128/D whole heads (two of 64, four of 32, one of 128; a D
that is a multiple of 128 is its own block; an (H, D) extent of at most
128 lanes is one full-width block), so the head count only has to fill
lane blocks: 12 and 10 heads of 64 are admitted, 3 heads of 64 are not
("heads"), D=96 is not ("head_dim"). Inside a block the heads are told
apart by a lane mask on the RESIDENT operand: Q (or K, V, dO) with the
other heads' lanes zeroed contracts over 128 lanes to exactly one
head's scores, at the MXU cost of the D-wide contraction (a 128-deep
systolic pass either way) and with no lane slicing or shuffling; dkv's
128-lane products P^T dO and dS^T Q are picked per head by the same
mask. The walked side is a VMEM-resident "major" tile (the whole
sequence up to `_MAJOR` rows, else a grid axis with the carries in
scratch) stepped through by an in-kernel loop whose bounds come from the
causal geometry: blocks past the diagonal are never visited, blocks
below it skip the mask, and a causally dead major tile is clamped in the
`index_map`, so its DMA is not issued either. Row statistics (LSE, the
ring's m and l, the backward's delta) travel lane-dense as
[B, H, T/rows, 1, rows].

Precision: dots take the input dtype (bf16 rides the MXU's half-precision
datapath) with f32 ACCUMULATION via preferred_element_type; softmax
statistics and scaling run in f32; P/dS are cast back to the input dtype
for their matmuls (the FlashAttention-2 recipe). A power-of-two
1/sqrt(D) is folded into the resident operand (exact in any binary
float); any other scale multiplies the f32 scores.

On CPU (the test mesh) the kernels run under the Pallas interpreter
(interpret=True): same code path, no Mosaic compile. Shapes must tile: T
divisible by 128, or T itself when at most 128 (sublane-aligned,
T % 8 == 0); callers fall back to attention_reference otherwise
(ops/nn_ops.py wiring) and book the reason (`count_fallback`), as they
book a lowering that took the kernels (`count_hit`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["FALLBACK_REASONS", "count_fallback", "count_hit",
           "flash_attention", "ineligible", "supports"]

_NEG = -1e30
_LANES = 128

# Every reason `ineligible` can return.
FALLBACK_REASONS = frozenset({"shape", "seq", "heads", "head_dim"})

# Rows of the walked operand that stay in VMEM at once. Up to here the
# whole sequence is one tile and the walk is the in-kernel loop alone;
# past it the major tiles are a grid axis (K/V at 2048 rows x 128 lanes
# are 0.5 MB each, double-buffered).
_MAJOR = 2048

# (resident rows, walked block rows) of all three kernels, from the sweep
# on a v5e (tools/flash_sweep.py; PERF.md section 6, PR 29). At
# (16, 1024, 12, 64) causal bf16, ms a call over 128..1024 rows a side:
# fwd 0.93 at (512, 512) of 0.93-1.86, dq 1.05 at (512, 512) of
# 1.05-2.61, dkv 1.38 at (512, 512) of 1.38-2.35. Resident tiles of 1024
# rows lose the causal skip, walked blocks of 128 pay the loop 8 times.
# One shape was swept and one answer came back: a table keyed by (T, D)
# is for the day a second shape measures differently. The wrappers take
# the tiles (and `major`) as static arguments, so the sweep and the tests
# vary them per call.
_TILE = (512, 512)

_OP = "scaled_dot_product_attention"


def _lane_block(h: int, d: int):
    """(lanes, heads) of one grid step's block of the [.., H*D] view, or
    the reason no block fits."""
    if h * d <= _LANES:
        return h * d, h
    if d % _LANES == 0:
        return d, 1
    if _LANES % d:
        return "head_dim"
    if (h * d) % _LANES:
        return "heads"
    return _LANES, _LANES // d


def _heads_ineligible(h: int, d: int):
    """The head-count / head-dim half of the gate, shared with the ring
    path's per-shard check (block_supports)."""
    block = _lane_block(h, d)
    return block if isinstance(block, str) else None


def _seq_ok(t: int) -> bool:
    return t >= 8 and t % 8 == 0 and (t <= 128 or t % 128 == 0)


def ineligible(q, k, v):
    """None when the flash kernels apply to [B, T, H, D] operands, else
    the reason the caller keeps the einsum path: T must tile and be
    sublane-aligned (T % 8 == 0: Mosaic tiles (8, 128) for f32); the
    heads must fill whole 128-lane blocks of the [B, T, H*D] view
    ("heads": 3 heads of 64), and D must divide 128 or be a multiple of
    it ("head_dim": 96). The kernels read as many K/V heads as Q heads:
    the attention op repeats a K/V of fewer heads (grouped-query
    attention) to the query's count before it asks here
    (ops/nn_ops._repeat_kv), so such a shape is gated, and its hit or
    fallback reason booked, as full attention of the query's heads; a
    K/V that reaches this gate with another head count than Q is
    "shape"."""
    if q.ndim != 4 or q.shape != k.shape or q.shape != v.shape:
        return "shape"
    _, t, h, d = q.shape
    if not _seq_ok(t):
        return "seq"
    return _heads_ineligible(h, d)


def supports(q, k, v) -> bool:
    return ineligible(q, k, v) is None


def block_supports(q, k) -> bool:
    """The ring path's per-shard gate: Q and the visiting K/V shard may
    differ in length; each must tile."""
    return (q.ndim == 4 and _seq_ok(q.shape[1]) and _seq_ok(k.shape[1])
            and _heads_ineligible(q.shape[2], q.shape[3]) is None)


def count_fallback(reason: str):
    """pallas_fallback_total{op="scaled_dot_product_attention", reason}:
    flash was asked for (use_flash True, or 'auto' on a shape the rule
    gives to the kernels) and the gate kept the einsum path."""
    from . import pallas_conv
    pallas_conv.count_fallback(_OP, reason)


def count_hit():
    """pallas_kernel_total{op="scaled_dot_product_attention"}: one per
    lowering of a forward op that took the flash kernels (a step of 12
    layers traced once reads 12)."""
    from . import pallas_conv
    pallas_conv.count_hit(_OP)


def _fit(t: int, want: int) -> int:
    """The largest power-of-two multiple of 128 that divides t and is at
    most `want`; t itself when it does not tile by 128."""
    if t % 128:
        return t
    b = 128
    while b * 2 <= want and t % (b * 2) == 0:
        b *= 2
    return b


def _major(t: int, b: int, major: int) -> int:
    """Rows of one VMEM-resident tile of the walked side: a multiple of
    its block b that divides t, at most `major`."""
    if t <= major:
        return t
    m = max(major // b, 1) * b
    while t % m:
        m -= b
    return m


def _interpret() -> bool:
    """Mosaic-compile only when actually lowering for TPU. The executor
    targets its place's device via jax.default_device — which
    jax.default_backend() ignores — so a CPUPlace run in a TPU-default
    process must still take the interpreter."""
    dev = jax.config.jax_default_device
    if dev is not None:
        platform = getattr(dev, "platform", None)
        if platform is not None:
            return platform != "tpu"
    return jax.default_backend() != "tpu"


def _compiler_params(semantics):
    """Declare grid-dimension semantics so Mosaic can overlap tile DMA
    with compute: "parallel" dims carry nothing across iterations;
    "arbitrary" marks a dim whose scratch accumulators DO carry.
    vmem_limit raised past the 16 MB default (v5e has 128 MB physical
    VMEM; 64 MB leaves headroom for double-buffered DMA). Shared with
    ops/pallas_conv.py and fusion's bn+act kernel."""
    if _interpret():
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=64 * 1024 * 1024)


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# Grid (batch, lane block, resident tile, major tile): the first three
# carry nothing across iterations; the major tiles of the walked side
# carry the scratch accumulators.
_SEM = ("parallel", "parallel", "parallel", "arbitrary")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _folds(scale: float) -> bool:
    """A power-of-two scale multiplies any binary float exactly, so it
    goes into the resident operand once instead of into every score."""
    return math.frexp(scale)[0] == 0.5


def _head_masks(rows: int, lanes: int, d: int, hpb: int):
    """Per head of the block, the [rows, lanes] mask of its lanes (None
    for a one-head block)."""
    if hpb == 1:
        return [None]
    head = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) // d
    return [head == hh for hh in range(hpb)]


def _only(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _by_head(masks, vals):
    """The block-wide [rows, lanes] value whose lanes of head hh come
    from vals[hh] ([rows, lanes], or [rows, 1] broadcast)."""
    out = vals[-1]
    for mask, val in zip(masks[-2::-1], vals[-2::-1]):
        out = jnp.where(mask, val, out)
    return out


def _floordiv(x, b: int):
    return jnp.floor_divide(x, jnp.int32(b))


def _clip(x, hi: int):
    return jnp.clip(x, 0, hi)


def _walk(full, masked, block):
    """Run block(j, masked) over the (lo, hi) range of walked blocks that
    need no mask and over the range the diagonal crosses (None: no causal
    mask)."""
    def run(bounds, mask):
        def body(j, carry):
            block(j, mask)
            return carry
        lax.fori_loop(*bounds, body, 0)
    run(full, False)
    if masked is not None:
        run(masked, True)


def _kv_ranges(q_first, k_base, bq: int, bk: int, per: int, causal: bool):
    """_walk's ranges over the `per` K/V blocks of a major tile that
    starts at position k_base, for a resident Q tile of bq rows at
    q_first: the blocks wholly at or below the diagonal, then those it
    crosses; blocks past it are in neither."""
    if not causal:
        return (0, per), None
    n_full = _clip(_floordiv(q_first - k_base + 1, bk), per)
    n_live = _clip(_floordiv(q_first + bq - 1 - k_base, bk) + 1, per)
    return (0, n_full), (n_full, n_live)


def _kv_major_index(bq: int, mk: int, n_maj: int, causal: bool):
    """index_map half of _kv_ranges: the K/V major tile to fetch for Q
    tile i at grid step kk; past the last live one the index stays put,
    so no DMA is issued for a tile the loop will not walk."""
    def index(i, kk, offs):
        if not causal:
            return kk
        last = _floordiv(offs[0] + (i + 1) * bq - 1 - offs[1], mk)
        return jnp.minimum(kk, _clip(last, n_maj - 1))
    return index


def _q_minus_k(bk: int, bq: int):
    """Column minus row of a transposed [bk, bq] block: position of the
    Q row less that of the K row, up to the blocks' offsets. The causal
    mask keeps where it is >= k_first - q_first."""
    return (lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            - lax.broadcasted_iota(jnp.int32, (bk, bq), 0))


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *rest, bq: int,
                bk: int, mk: int, n_maj: int, d: int, hpb: int,
                scale: float, causal: bool, normalize: bool):
    """Grid (B, lane blocks, Tq/bq, Tk/mk): Q tile [bq, L] resident, the
    K/V major tile [mk, L] in VMEM, walked in blocks of bk rows by the
    loop; (acc, m, l) carry in scratch across major tiles. Works on the
    transposed blocks S^T = K Q^T [bk, bq]: the running max and sum are
    [1, bq] rows reduced along sublanes (no cross-lane reduction, no
    lane-sparse column), and the output accumulates transposed,
    O^T = V^T P^T, one head's D rows at a time. normalize=True emits
    (softmax(S)V, LSE), the single-device forward; normalize=False emits
    the raw (acc, m, l), the per-shard block the ring merge consumes."""
    import jax.experimental.pallas as pl

    stat_refs, (acc_sc, m_sc, l_sc) = rest[:-3], rest[-3:]
    i = pl.program_id(2)
    kk = pl.program_id(3)
    q_first = off_ref[0] + i * bq
    k_base = off_ref[1] + kk * mk
    per = mk // bk
    fold = _folds(scale)

    @pl.when(kk == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    masks = _head_masks(bq, q_ref.shape[-1], d, hpb)
    qt = q_ref[0]
    if fold:
        qt = qt * jnp.asarray(scale, qt.dtype)
    qs = [_only(mask, qt) for mask in masks]
    if causal:
        diff = _q_minus_k(bk, bq)

    def block(j, masked):
        start = pl.multiple_of(j * bk, bk)
        kb = k_ref[0, pl.ds(start, bk), :]
        vt = _transpose(v_ref[0, pl.ds(start, bk), :])      # [L, bk]
        for hh in range(hpb):
            st = _dot(kb, qs[hh], _NT)
            if not fold:
                st = st * scale
            if masked:
                st = jnp.where(diff >= k_base + start - q_first, st, _NEG)
            m_prev = m_sc[hh]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_sc[hh] = l_sc[hh] * alpha + jnp.sum(pt, axis=0, keepdims=True)
            m_sc[hh] = m_new
            rows = slice(hh * d, (hh + 1) * d)
            acc_sc[rows, :] = acc_sc[rows, :] * alpha + _dot(
                vt[rows, :], pt.astype(vt.dtype), _NN)

    _walk(*_kv_ranges(q_first, k_base, bq, bk, per, causal), block)

    @pl.when(kk == n_maj - 1)
    def _finalize():
        for hh in range(hpb):
            l = jnp.maximum(l_sc[hh], 1e-30)
            if normalize:
                rows = slice(hh * d, (hh + 1) * d)
                acc_sc[rows, :] = acc_sc[rows, :] * (1.0 / l)
                # per-row logsumexp of the scaled scores: the only
                # residual the flash backward needs beyond (q, k, v, o)
                stat_refs[0][0, hh, 0] = m_sc[hh] + jnp.log(l)
            else:
                stat_refs[0][0, hh, 0] = m_sc[hh]
                stat_refs[1][0, hh, 0] = l_sc[hh]
        o_ref[0] = _transpose(acc_sc[...]).astype(o_ref.dtype)


def _transpose(x):
    """A 2-D tile turned on the XLU, in f32 (Mosaic transposes 32-bit
    tiles)."""
    return x.astype(jnp.float32).T.astype(x.dtype)


def _vma_struct(like):
    """ShapeDtypeStruct factory whose outputs vary over the same mesh
    axes as `like` (a shard_map operand; empty outside one)."""
    vma = getattr(getattr(like, "aval", None), "vma", None) or frozenset()
    return functools.partial(jax.ShapeDtypeStruct, vma=vma)


def _offsets(q_off, k_off):
    return jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])


def _flat(x):
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          *operands):
    """One pallas_call of the family: the offsets ride as the scalar
    prefetch, so the index maps can clamp a causally dead tile."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[_scratch(s) for s in scratch]),
        out_shape=out_shape, interpret=_interpret(),
        compiler_params=_compiler_params(_SEM))(*operands)


def _specs(lanes, hpb, b_res, b_walk, m_walk, walk_index):
    """BlockSpecs of the family's grid (batch, lane block, resident tile,
    major tile): an operand tile and a row-statistic tile, on the
    resident and on the walked side. walk_index(i, kk, offs) is the major
    tile to fetch (clamped where the causal mask kills one)."""
    import jax.experimental.pallas as pl

    def res(bb, g, i, kk, offs):
        return bb, i, g

    def walk(bb, g, i, kk, offs):
        return bb, walk_index(i, kk, offs), g

    def res_stat(bb, g, i, kk, offs):
        return bb, g, i, 0, 0

    def walk_stat(bb, g, i, kk, offs):
        return bb, g, walk_index(i, kk, offs), 0, 0

    return (pl.BlockSpec((1, b_res, lanes), res),
            pl.BlockSpec((1, m_walk, lanes), walk),
            pl.BlockSpec((1, hpb, 1, 1, b_res), res_stat),
            pl.BlockSpec((1, hpb, m_walk // b_walk, 1, b_walk), walk_stat))


# The wrappers are jitted so that a step with one attention per layer
# traces and lowers each kernel once: jax lowers an inner jit of the same
# shapes to one function and calls it per layer (36 pallas_calls lowered
# one by one cost GPT-2's first step 5 s a lowering, PERF.md section 6).
@functools.partial(jax.jit, static_argnames=("scale", "causal", "normalize",
                                             "tile", "major"))
def _fwd_call(q, k, v, q_off, k_off, scale, causal, normalize, tile=_TILE,
              major=_MAJOR):
    """Returns (out [B,Tq,H,D], stats): stats = (lse,) when normalizing,
    else (m, l); each [B, H, Tq] f32."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    lanes, hpb = _lane_block(h, d)
    bq, bk = _fit(tq, tile[0]), _fit(tk, tile[1])
    mk = _major(tk, bk, major)
    n_maj = tk // mk

    res, walk, res_stat, _ = _specs(
        lanes, hpb, bq, bk, mk, _kv_major_index(bq, mk, n_maj, causal))
    struct = _vma_struct(q)
    n_stat = 1 if normalize else 2
    out, *stats = _call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, mk=mk, n_maj=n_maj,
                          d=d, hpb=hpb, scale=float(scale), causal=causal,
                          normalize=normalize),
        "flash_fwd", (b, h * d // lanes, tq // bq, n_maj),
        [res, walk, walk], [res] + [res_stat] * n_stat,
        [struct((b, tq, h * d), q.dtype if normalize else jnp.float32)]
        + [struct((b, h, tq // bq, 1, bq), jnp.float32)] * n_stat,
        [(lanes, bq), (hpb, 1, bq), (hpb, 1, bq)],
        _offsets(q_off, k_off), _flat(q), _flat(k), _flat(v))
    return out.reshape(b, tq, h, d), [s.reshape(b, h, tq) for s in stats]


def _forward(q, k, v, causal, return_lse=False):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, (lse,) = _fwd_call(q, k, v, 0, 0, scale, causal, normalize=True)
    return (out, lse) if return_lse else out


def flash_attention_block(q, k, v, q_off, k_off, scale, causal):
    """Per-shard flash block for ring attention: q [B,Tq,H,D] resident,
    k/v [B,Tk,H,D] visiting, global offsets as traced scalars. Returns
    (acc [B,Tq,H,D] unnormalized, l [B,H,Tq], m [B,H,Tq]) in f32 carries,
    matching parallel.ring_attention._block_attn's online-softmax form."""
    acc, (m, l) = _fwd_call(q, k, v, q_off, k_off, scale, causal,
                            normalize=False)
    return acc, l, m


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_sc, *, bq: int, bk: int, mk: int, n_maj: int,
               d: int, hpb: int, scale: float, causal: bool):
    """Grid and walk of the forward: Q/dO tile resident, K/V walked, dQ^T
    carried in scratch. Recomputes P^T = exp(S^T - LSE) per block;
    dS^T = P^T * (V dO^T - delta); dQ^T = (sum_k K^T dS^T) * scale."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    kk = pl.program_id(3)
    q_first = off_ref[0] + i * bq
    k_base = off_ref[1] + kk * mk
    per = mk // bk
    fold = _folds(scale)

    @pl.when(kk == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    masks = _head_masks(bq, q_ref.shape[-1], d, hpb)
    qt = q_ref[0]
    if fold:
        qt = qt * jnp.asarray(scale, qt.dtype)
    dot_ = do_ref[0]
    qs = [_only(mask, qt) for mask in masks]
    dos = [_only(mask, dot_) for mask in masks]
    if causal:
        diff = _q_minus_k(bk, bq)

    def block(j, masked):
        start = pl.multiple_of(j * bk, bk)
        kb = k_ref[0, pl.ds(start, bk), :]
        vb = v_ref[0, pl.ds(start, bk), :]
        kt = _transpose(kb)                                  # [L, bk]
        for hh in range(hpb):
            st = _dot(kb, qs[hh], _NT)
            if not fold:
                st = st * scale
            if masked:
                st = jnp.where(diff >= k_base + start - q_first, st, _NEG)
            pt = jnp.exp(st - lse_ref[0, hh, 0])
            dpt = _dot(vb, dos[hh], _NT)
            dst = (pt * (dpt - dl_ref[0, hh, 0])).astype(kb.dtype)
            rows = slice(hh * d, (hh + 1) * d)
            dq_sc[rows, :] = dq_sc[rows, :] + _dot(kt[rows, :], dst, _NN)

    _walk(*_kv_ranges(q_first, k_base, bq, bk, per, causal), block)

    @pl.when(kk == n_maj - 1)
    def _finalize():
        dq_ref[0] = _transpose(dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, bq: int, bk: int,
                mq: int, n_maj: int, d: int, hpb: int, scale: float,
                causal: bool):
    """Grid (B, lane blocks, Tk/bk, Tq/mq): K/V tile resident, the
    Q/dO/LSE/delta major tile walked in blocks of bq rows, dK/dV carried
    in scratch. Works on the transposed blocks S^T = K Q^T [bk, bq], so
    LSE and delta are [1, bq] rows broadcast along sublanes:
    dV = sum_q P^T dO; dK = (sum_q dS^T Q) * scale."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)    # k tile
    kk = pl.program_id(3)   # q major tile
    k_first = off_ref[1] + i * bk
    q_base = off_ref[0] + kk * mq
    per = mq // bq
    fold = _folds(scale)

    @pl.when(kk == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    masks = _head_masks(bk, k_ref.shape[-1], d, hpb)
    kt = k_ref[0]
    if fold:
        kt = kt * jnp.asarray(scale, kt.dtype)
    vt = v_ref[0]
    ks = [_only(mask, kt) for mask in masks]
    vs = [_only(mask, vt) for mask in masks]
    if causal:
        diff = _q_minus_k(bk, bq)

    def block(j, masked):
        start = pl.multiple_of(j * bq, bq)
        qb = q_ref[0, pl.ds(start, bq), :]
        dob = do_ref[0, pl.ds(start, bq), :]
        dvs, dks = [], []
        for hh in range(hpb):
            st = _dot(ks[hh], qb, _NT)
            if not fold:
                st = st * scale
            if masked:
                st = jnp.where(diff >= k_first - q_base - start, st, _NEG)
            pt = jnp.exp(st - lse_ref[0, hh, j])
            dvs.append(_dot(pt.astype(dob.dtype), dob, _NN))
            dpt = _dot(vs[hh], dob, _NT)
            dst = (pt * (dpt - dl_ref[0, hh, j])).astype(qb.dtype)
            dks.append(_dot(dst, qb, _NN))
        dv_sc[...] = dv_sc[...] + _by_head(masks, dvs)
        dk_sc[...] = dk_sc[...] + _by_head(masks, dks)

    if causal:
        # Q blocks the diagonal crosses, then those wholly below it
        live0 = _clip(_floordiv(k_first - q_base, bq), per)
        full0 = _clip(-_floordiv(q_base - (k_first + bk - 1), bq), per)
        full0 = jnp.maximum(full0, live0)
        _walk((full0, per), (live0, full0), block)
    else:
        _walk((0, per), None, block)

    @pl.when(kk == n_maj - 1)
    def _finalize():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "dq_tile",
                                             "dkv_tile", "major"))
def flash_attention_bwd_block(q, k, v, do, lse, delta, q_off, k_off, scale,
                              causal, dq_tile=_TILE, dkv_tile=_TILE,
                              major=_MAJOR):
    """Flash backward for one (Q shard, K/V shard) pair with global position
    offsets: q/do [B,Tq,H,D], k/v [B,Tk,H,D], lse/delta [B,H,Tq] (scaled-
    score logsumexp from the forward; delta = rowsum(dO*O)). Returns
    (dq, dk, dv) in the inputs' dtypes. Offsets (0, 0) with Tq == Tk == T
    is exactly the single-device flash backward; the ring backward calls it
    per visiting shard (parallel/ring_attention.py). `dq_tile` and
    `dkv_tile` are each kernel's (resident rows, walked block rows)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    assert block_supports(q, k), (
        f"flash_attention_bwd_block needs tileable shapes (tq={tq}, "
        f"tk={tk}, h={h}, d={d}); gate callers with block_supports()")
    lanes, hpb = _lane_block(h, d)
    # rows no shard ever validated carry lse = -inf (possible only for
    # non-causal corner cases); push them to +big so exp(s - lse) == 0 and
    # they contribute nothing to any gradient.
    lse = jnp.where(jnp.isfinite(lse), lse, 1e30).astype(jnp.float32)
    delta = delta.astype(jnp.float32)
    offs = _offsets(q_off, k_off)
    struct = _vma_struct(q)
    q2, k2, v2, do2 = _flat(q), _flat(k), _flat(v), _flat(do)
    statics = dict(d=d, hpb=hpb, scale=float(scale), causal=causal)

    def stat(x, rows):
        return x.reshape(b, h, tq // rows, 1, rows)

    bq, bk = _fit(tq, dq_tile[0]), _fit(tk, dq_tile[1])
    mk = _major(tk, bk, major)
    n_maj = tk // mk

    res, walk, res_stat, _ = _specs(
        lanes, hpb, bq, bk, mk, _kv_major_index(bq, mk, n_maj, causal))
    dq = _call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, mk=mk, n_maj=n_maj,
                          **statics),
        "flash_dq", (b, h * d // lanes, tq // bq, n_maj),
        [res, walk, walk, res, res_stat, res_stat], res,
        struct((b, tq, h * d), q.dtype), [(lanes, bq)],
        offs, q2, k2, v2, do2, stat(lse, bq), stat(delta, bq))

    bk, bq = _fit(tk, dkv_tile[0]), _fit(tq, dkv_tile[1])
    mq = _major(tq, bq, major)
    n_maj = tq // mq

    def q_index(i, kk, offs):
        if not causal:
            return kk
        first = _floordiv(offs[1] + i * bk - offs[0], mq)
        return jnp.maximum(kk, _clip(first, n_maj - 1))

    res, walk, _, walk_stat = _specs(lanes, hpb, bk, bq, mq, q_index)
    dk, dv = _call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, mq=mq, n_maj=n_maj,
                          **statics),
        "flash_dkv", (b, h * d // lanes, tk // bk, n_maj),
        [walk, res, res, walk, walk_stat, walk_stat], [res, res],
        [struct((b, tk, h * d), k.dtype), struct((b, tk, h * d), v.dtype)],
        [(bk, lanes), (bk, lanes)],
        offs, q2, k2, v2, do2, stat(lse, bq), stat(delta, bq))

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal=False):
    """softmax(QK^T/sqrt(D) [+causal mask]) V over [B, T, H, D]."""
    return _forward(q, k, v, causal)


def _fwd(q, k, v, causal):
    o, lse = _forward(q, k, v, causal, return_lse=True)
    return o, (q, k, v, o, lse)


def _bwd(causal, res, g):
    q, k, v, o, lse = res
    scale = 1.0 / (q.shape[-1] ** 0.5)
    # delta_i = dO_i . O_i  — the softmax-jacobian row correction
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)       # [B, H, T]
    return flash_attention_bwd_block(q, k, v, g, lse, delta, 0, 0, scale,
                                     causal)


flash_attention.defvjp(_fwd, _bwd)
