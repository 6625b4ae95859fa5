"""Flash attention as a Pallas TPU kernel (the "pallas for the hot ops"
tier of the compute path; /opt/skills/guides/pallas_guide.md patterns).

Forward: online-softmax blocks — Q tiles stay resident in VMEM while K/V
tiles stream through as the innermost (sequential) grid dim, carrying the
running max/denominator in VMEM scratch, so the [T, T] score matrix never
materializes in HBM and VMEM use is O(tile) — T is unbounded (memory
O(T) end to end, same contract as parallel/ring_attention.py across chips
but within one core's VMEM).

Backward: the standard flash backward (FlashAttention-2 style) — the
forward saves only the per-row logsumexp (m + log l); the backward
recomputes score blocks in VMEM from (Q, K, LSE) and accumulates
dQ (one kernel, Q tiles resident, K/V streaming) and dK/dV (a second
kernel, K/V tiles resident, Q/dO streaming). Both kernels take global
(q_off, k_off) position offsets so the same code serves the single-device
path (offsets 0) and the per-shard blocks of the ring composition
(parallel/ring_attention.py flash_ring backward).

Layout: operands stay in the model's [B, T, H, D] — tiles span a head
GROUP of the trailing (H, D) dims and the kernels loop the group's heads
in an unrolled Python loop, so no head-major transpose copies bracket the
kernels (they dominated wall time in transformer training, where T is
moderate and attention is called per layer). The group is all H heads up
to 8 and groups of 8 walked by a grid dim above that (`_head_block`): the
unrolled loop keeps every head's [bq, bk] score tiles live, so VMEM grows
with the group, not with H — 16 heads in one group is refused by the v5e
compiler at T >= 2048. A head count past 8 that is not a multiple of 8
cannot be grouped (Mosaic wants the second-minor block dim % 8 or full)
and is declined by the gate (`ineligible`, reason "heads"); so is a
group whose heads x D outgrow the tile the compiler accepted (reason
"head_dim": 8 heads of D=256, where 4 heads pass).
Precision: dots take the input dtype (bf16 rides the MXU's half-precision
datapath) with f32 ACCUMULATION via preferred_element_type; softmax
statistics and scaling run in f32; P/dS are cast back to the input dtype
for their matmuls — the FlashAttention-2 recipe.

On CPU (the test mesh) the kernels run under the Pallas interpreter
(interpret=True) — same code path, no Mosaic compile. Shapes must tile:
T divisible by the block (128, or T itself when smaller; sublane-aligned
T % 8 == 0); callers fall back to attention_reference otherwise
(ops/nn_ops.py wiring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["FALLBACK_REASONS", "count_fallback", "flash_attention",
           "ineligible", "supports"]

_NEG = -1e30


# Every reason `ineligible` can return.
FALLBACK_REASONS = frozenset({"shape", "seq", "heads", "head_dim"})

# What the v5e compiler fits in VMEM at T >= 2048, as it was asked
# (tests/test_tpu_compile.py): 8 heads in one unrolled loop, and a tile of
# heads x head_dim = 1024 lanes (8 x 128, 4 x 256, 2 x 512 compile;
# 16 x 64 and 8 x 256 are refused).
_MAX_HEADS = 8
_MAX_TILE_LANES = 1024


def _head_block(h: int) -> int:
    """Heads per grid step: all of them up to _MAX_HEADS, else groups of
    _MAX_HEADS walked by the grid (the gate admits only multiples)."""
    return h if h <= _MAX_HEADS else _MAX_HEADS


def _heads_ineligible(h: int, d: int):
    """The head-count / head-dim half of the gate, shared with the ring
    path's per-shard check (block_supports)."""
    if h > _MAX_HEADS and h % _MAX_HEADS:
        return "heads"
    if _head_block(h) * d > _MAX_TILE_LANES:
        return "head_dim"
    return None


def ineligible(q, k, v):
    """None when the flash kernels apply to [B, T, H, D] operands, else
    the reason the caller keeps the einsum path: T must tile and be
    sublane-aligned (T % 8 == 0 — Mosaic tiles (8, 128) for f32); H must
    fit one unrolled head loop or split into groups of 8; a group's heads
    x D must fit the tile the chip's compiler accepted (a group below 8
    heads cannot be split further: Mosaic wants the block's second-minor
    dim % 8 or full)."""
    if q.ndim != 4 or q.shape != k.shape or q.shape != v.shape:
        return "shape"
    _, t, h, d = q.shape
    if not (t >= 8 and t % 8 == 0 and (t <= 128 or t % 128 == 0)):
        return "seq"
    return _heads_ineligible(h, d)


def supports(q, k, v) -> bool:
    return ineligible(q, k, v) is None


def count_fallback(reason: str):
    """pallas_fallback_total{op="scaled_dot_product_attention", reason}:
    flash was asked for (use_flash True, or 'auto' at long T) and the
    gate kept the einsum path."""
    from . import pallas_conv
    pallas_conv.count_fallback("scaled_dot_product_attention", reason)


def _block(t: int) -> int:
    """Resident-side (Q in fwd/dq, K in dkv) tile rows. Default 128; the
    env knob grows it (power-of-two, must divide t) — larger resident
    tiles amortize per-block softmax-state updates and halve grid steps,
    at the cost of more VMEM per tile."""
    import os
    if t % 128 != 0:
        return t
    b = 128
    # 512 at long T, 128 below T=2048: chosen by a sweep that predates
    # PR 1 and has not been re-measured on today's code (1024 exceeds the
    # VMEM budget and fails to compile).
    default = "512" if t >= 2048 else "128"
    want = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_Q", default))
    while b * 2 <= want and t % (b * 2) == 0:
        b *= 2
    return b


def _block_k(t: int) -> int:
    """Streamed-side (K or Q) tile rows: larger tiles amortize MXU matmul
    setup — the per-block dots contract over D (= 64 typically), so the
    streamed dimension is the only one free to grow. Capped by an env
    knob for tuning; must divide t. 1024 at long T (2048 fails the VMEM
    budget), 512 below T=2048 — same unrepeated sweep as _block."""
    import os
    default = "1024" if t >= 2048 else "512"
    cap = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK_K", default))
    b = _block(t)
    while b * 2 <= cap and t % (b * 2) == 0:
        b *= 2
    return b


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
    "arbitrary" marks the streamed innermost dim whose scratch
    accumulators DO carry. vmem_limit raised past the 16 MB default: the
    unrolled head loop keeps H tiles' intermediates live (v5e has 128 MB
    physical VMEM; 64 MB leaves headroom for double-buffered DMA)."""
    if _interpret():
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=64 * 1024 * 1024)


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


_SEM = ("parallel", "parallel", "parallel", "arbitrary")


def _dot(a, b, dims):
    from jax import lax
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _causal_mask(s, q_first, k_first, bq, bk):
    from jax import lax
    qpos = q_first + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_first + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, _NEG)


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, bq: int, bk: int, n_h: int,
                n_k: int, scale: float, causal: bool, normalize: bool):
    """Grid (B, H/n_h, n_q, n_k): Q tile [bq, n_h, D] resident, K/V
    tiles [bk, n_h, D] streamed innermost; unrolled loop over the n_h
    heads of the group; (acc, m, l) carry in scratch with a leading head
    axis. normalize=True emits
    (softmax(S)V, LSE) — the single-device forward; normalize=False emits
    the raw (acc, m, l) — the per-shard block the ring merge consumes."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    def compute():
        # full-tile loads + value-level head slices: Mosaic's bf16 layout
        # inference rejects (1, rows, 1, d) ref-slice reshapes, and whole
        # tiles give it freedom to keep the packed layout
        qt = q_ref[0]                                 # [bq, H, D]
        kt = k_ref[0]
        vt = v_ref[0]
        for hh in range(n_h):
            q = qt[:, hh, :]                          # [bq, D]
            s = _dot(q, kt[:, hh, :], ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, q_off + i * bq, k_off + j * bk, bq, bk)
            m_prev = m_sc[hh, :, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_sc[hh, :, 0] = l_sc[hh, :, 0] * corr + jnp.sum(p, axis=-1)
            acc_sc[hh] = acc_sc[hh] * corr[:, None] + _dot(
                p.astype(q.dtype), vt[:, hh, :], ((1,), (0,)))
            m_sc[hh, :, 0] = m_new

    if causal:
        # K tiles strictly past this Q tile's last row are dead: skip the
        # MXU work (the tile DMA still streams — grids are static)
        pl.when(q_off + i * bq + (bq - 1) >= k_off + j * bk)(compute)
    else:
        compute()

    @pl.when(j == n_k - 1)
    def _finalize():
        outs, stats = [], []
        for hh in range(n_h):
            if normalize:
                l = l_sc[hh, :, 0]
                outs.append((acc_sc[hh] /
                             jnp.maximum(l, 1e-30)[:, None]))
                # per-row logsumexp of the scaled scores — the only
                # residual the flash backward needs beyond (q, k, v, o)
                stats.append((m_sc[hh, :, 0] +
                              jnp.log(jnp.maximum(l, 1e-30)))[:, None])
            else:
                outs.append(acc_sc[hh])
                stats.append(jnp.stack([m_sc[hh, :, 0], l_sc[hh, :, 0]],
                                       axis=1))
        o_ref[0] = jnp.stack(outs, axis=1).astype(o_ref.dtype)
        lse_ref[0] = jnp.stack(stats, axis=1)


def _vma_struct(like):
    """ShapeDtypeStruct factory whose outputs vary over the same mesh
    axes as `like` (a shard_map operand; empty outside one)."""
    vma = getattr(getattr(like, "aval", None), "vma", None) or frozenset()
    return functools.partial(jax.ShapeDtypeStruct, vma=vma)


def _fwd_call(q, k, v, q_off, k_off, scale, causal, normalize):
    import jax.experimental.pallas as pl

    b, tq, h, d = q.shape
    tk = k.shape[1]
    hg = _head_block(h)
    bq = _block(min(tq, tk))
    bk = _block_k(tk)
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    out_struct = _vma_struct(q)
    stat_last = 1 if normalize else 2

    def res_spec(rows, d_):
        return pl.BlockSpec((1, rows, hg, d_),
                            lambda bb, g, j, kk: (bb, j, g, 0))

    def stream_spec(rows, d_):
        return pl.BlockSpec((1, rows, hg, d_),
                            lambda bb, g, j, kk: (bb, kk, g, 0))

    out, stats = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, n_h=hg, n_k=tk // bk,
                          scale=float(scale), causal=causal,
                          normalize=normalize),
        name="flash_fwd",
        grid=(b, h // hg, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((2,), lambda bb, g, j, kk: (0,)),
            res_spec(bq, d), stream_spec(bk, d), stream_spec(bk, d),
        ],
        out_specs=[res_spec(bq, d), res_spec(bq, stat_last)],
        out_shape=[
            out_struct((b, tq, h, d),
                       q.dtype if normalize else jnp.float32),
            out_struct((b, tq, h, stat_last), jnp.float32),
        ],
        scratch_shapes=[_scratch((hg, bq, d)), _scratch((hg, bq, 1)),
                        _scratch((hg, bq, 1))],
        interpret=_interpret(),
        compiler_params=_compiler_params(_SEM),
    )(offs, q, k, v)
    return out, stats


def _forward(q, k, v, causal, return_lse=False):
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    out, lse = _fwd_call(q, k, v, 0, 0, scale, causal, normalize=True)
    if return_lse:
        # [B, T, H, 1] -> [B, H, T]: tiny (no D axis) transpose
        return out, lse[..., 0].transpose(0, 2, 1)
    return out


def flash_attention_block(q, k, v, q_off, k_off, scale, causal):
    """Per-shard flash block for ring attention: q [B,Tq,H,D] resident,
    k/v [B,Tk,H,D] visiting, global offsets as traced scalars. Returns
    (acc [B,Tq,H,D] unnormalized, l [B,H,Tq], m [B,H,Tq]) in f32 carries,
    matching parallel.ring_attention._block_attn's online-softmax form."""
    acc, stats = _fwd_call(q, k, v, q_off, k_off, scale, causal,
                           normalize=False)
    m = stats[..., 0].transpose(0, 2, 1)
    l = stats[..., 1].transpose(0, 2, 1)
    return acc, l, m


def block_supports(q, k) -> bool:
    tq, tk = q.shape[1], k.shape[1]
    blk = _block(min(tq, tk))
    return (q.ndim == 4 and tq % blk == 0 and tk % blk == 0
            and min(tq, tk) >= 8 and tq % 8 == 0 and tk % 8 == 0
            and _heads_ineligible(q.shape[2], q.shape[3]) is None)


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_sc, *, bq: int, bk: int, n_h: int, n_k: int,
               scale: float, causal: bool):
    """Grid (B, H/n_h, n_q, n_k), K/V STREAMED innermost (wide bk tiles) with a
    per-head dQ scratch carry. Recomputes P = exp(S - LSE) per block;
    dS = P*(dO V^T - delta); dQ = (sum_k dS K) * scale. Causal: K blocks
    fully past the Q tile's last row skip their MXU work."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def compute():
        qt = q_ref[0]
        kt = k_ref[0]
        vt = v_ref[0]
        dot_ = do_ref[0]
        lset = lse_ref[0].astype(jnp.float32)
        dlt = dl_ref[0].astype(jnp.float32)
        for hh in range(n_h):
            q = qt[:, hh, :]
            kb = kt[:, hh, :]
            s = _dot(q, kb, ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, q_off + i * bq, k_off + j * bk, bq, bk)
            p = jnp.exp(s - lset[:, hh, :])
            dp = _dot(dot_[:, hh, :], vt[:, hh, :], ((1,), (1,)))
            ds = (p * (dp - dlt[:, hh, :])).astype(q.dtype)
            dq_sc[hh] = dq_sc[hh] + _dot(ds, kb, ((1,), (0,)))

    if causal:
        pl.when(q_off + i * bq + (bq - 1) >= k_off + j * bk)(compute)
    else:
        compute()

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = jnp.stack([dq_sc[hh] * scale for hh in range(n_h)],
                              axis=1).astype(dq_ref.dtype)


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, bq: int, bk: int,
                n_h: int, n_q: int, scale: float, causal: bool):
    """Grid (B, H/n_h, n_k, n_q), Q/dO/LSE/delta STREAMED innermost (wide bq
    tiles) with per-head dK/dV scratch carries. dV = sum_q P^T dO;
    dK = (sum_q dS^T Q) * scale. Causal: Q blocks fully before the K
    tile's first column skip their MXU work."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)   # k tile
    j = pl.program_id(3)   # q tile (streamed)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def compute():
        kt = k_ref[0]
        vt = v_ref[0]
        qt = q_ref[0]
        dot_ = do_ref[0]
        lset = lse_ref[0].astype(jnp.float32)
        dlt = dl_ref[0].astype(jnp.float32)
        for hh in range(n_h):
            kb = kt[:, hh, :]
            qb = qt[:, hh, :]
            dob = dot_[:, hh, :]
            s = _dot(qb, kb, ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, q_off + j * bq, k_off + i * bk, bq, bk)
            p = jnp.exp(s - lset[:, hh, :])
            dv_sc[hh] = dv_sc[hh] + _dot(p.astype(kb.dtype), dob,
                                         ((0,), (0,)))
            dp = _dot(dob, vt[:, hh, :], ((1,), (1,)))
            ds = (p * (dp - dlt[:, hh, :])).astype(kb.dtype)
            dk_sc[hh] = dk_sc[hh] + _dot(ds, qb, ((0,), (0,)))

    if causal:
        pl.when(q_off + j * bq + (bq - 1) >= k_off + i * bk)(compute)
    else:
        compute()

    @pl.when(j == n_q - 1)
    def _finalize():
        dk_ref[0] = jnp.stack([dk_sc[hh] * scale for hh in range(n_h)],
                              axis=1).astype(dk_ref.dtype)
        dv_ref[0] = jnp.stack([dv_sc[hh] for hh in range(n_h)],
                              axis=1).astype(dv_ref.dtype)


def flash_attention_bwd_block(q, k, v, do, lse, delta, q_off, k_off, scale,
                              causal):
    """Flash backward for one (Q shard, K/V shard) pair with global position
    offsets: q/do [B,Tq,H,D], k/v [B,Tk,H,D], lse/delta [B,H,Tq] (scaled-
    score logsumexp from the forward; delta = rowsum(dO*O)). Returns
    (dq, dk, dv) in the inputs' dtypes. Offsets (0, 0) with Tq == Tk == T
    is exactly the single-device flash backward; the ring backward calls it
    per visiting shard (parallel/ring_attention.py)."""
    import jax.experimental.pallas as pl

    b, tq, h, d = q.shape
    tk = k.shape[1]
    hg = _head_block(h)
    block = _block(min(tq, tk))
    assert tq % block == 0 and tk % block == 0, (
        f"flash_attention_bwd_block needs tileable shapes (tq={tq}, "
        f"tk={tk}, block={block}); gate callers with block_supports()")
    # resident tiles stay at `block`; the STREAMED side gets wide tiles
    # (dq streams K, dkv streams Q — see _block_k)
    bq_w = _block_k(tq)
    bk_w = _block_k(tk)
    # rows no shard ever validated carry lse = -inf (possible only for
    # non-causal corner cases); push them to +big so exp(s - lse) == 0 and
    # they contribute nothing to any gradient. Operands stay [B,T,H,D];
    # the row stats become [B,T,H,1] (tiny transposes — no D axis).
    lseh = jnp.where(jnp.isfinite(lse), lse, 1e30).astype(
        jnp.float32).transpose(0, 2, 1)[..., None]
    dlh = delta.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])

    interpret = _interpret()
    out_struct = _vma_struct(q)

    off_spec = pl.BlockSpec((2,), lambda bb, g, j, kk: (0,))

    def res_spec(rows, d_):
        return pl.BlockSpec((1, rows, hg, d_),
                            lambda bb, g, j, kk: (bb, j, g, 0))

    def stream_spec(rows, d_):
        return pl.BlockSpec((1, rows, hg, d_),
                            lambda bb, g, j, kk: (bb, kk, g, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=block, bk=bk_w, n_h=hg,
                          n_k=tk // bk_w, scale=float(scale),
                          causal=causal),
        name="flash_dq",
        grid=(b, h // hg, tq // block, tk // bk_w),
        in_specs=[off_spec, res_spec(block, d), stream_spec(bk_w, d),
                  stream_spec(bk_w, d), res_spec(block, d),
                  res_spec(block, 1), res_spec(block, 1)],
        out_specs=res_spec(block, d),
        out_shape=out_struct((b, tq, h, d), q.dtype),
        scratch_shapes=[_scratch((hg, block, d))],
        interpret=interpret,
        compiler_params=_compiler_params(_SEM),
    )(offs, q, k, v, do, lseh, dlh)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq_w, bk=block, n_h=hg,
                          n_q=tq // bq_w, scale=float(scale),
                          causal=causal),
        name="flash_dkv",
        grid=(b, h // hg, tk // block, tq // bq_w),
        in_specs=[off_spec, stream_spec(bq_w, d), res_spec(block, d),
                  res_spec(block, d), stream_spec(bq_w, d),
                  stream_spec(bq_w, 1), stream_spec(bq_w, 1)],
        out_specs=[res_spec(block, d), res_spec(block, d)],
        out_shape=[out_struct((b, tk, h, d), k.dtype),
                   out_struct((b, tk, h, d), v.dtype)],
        scratch_shapes=[_scratch((hg, block, d)),
                        _scratch((hg, block, d))],
        interpret=interpret,
        compiler_params=_compiler_params(_SEM),
    )(offs, q, k, v, do, lseh, dlh)

    return dq, dk, dv


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
