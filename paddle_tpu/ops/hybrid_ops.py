"""Ops of hybrid state-space / mixture-of-experts language models (no
2018-reference analogue): RMSNorm with its gated, grouped form, a causal
depthwise conv over time, the Mamba-2 selective scan in its chunked (SSD)
form, a sigmoid top-k router and a dropless expert layer that is told
which experts it holds. models/nemotron_h.py builds a model from them.

Precision under AMP: norm statistics, the router, `dt`, `A`, the scan's
decays and its state stay float32; the scan's four products and the
expert products take bf16 operands with float32 accumulation.

Gradients: all generic (registry.generic_grad_lower: jax.vjp of the
lowering, whose re-traced forward XLA merges with the original). The
scan's core is a jax.checkpoint, so its backward keeps the op's inputs
and the chunk states, and recomputes the [chunk, chunk] decay and score
blocks instead of holding them per layer; the expert layer's grouped
products are Pallas calls, which XLA does not merge, so its backward
runs the forward's two products once more (routed rows are 6 * held /
experts of a token's, a few per cent of the step's arithmetic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import in_var, same_as_input, set_out
from .registry import op

__all__ = ["GMM_FALLBACK_REASONS", "gmm_ineligible", "ssd_scan_chunked"]


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _compute_dtype(ctx):
    """bf16 under AMP, else float32: the operand type of an MXU product
    whose inputs this file made in float32."""
    amp = getattr(ctx, "amp_dtype", None)
    return jnp.dtype(amp) if amp else jnp.dtype(jnp.float32)


# --- RMSNorm -----------------------------------------------------------------

@op("rms_norm", infer_shape=same_as_input())
def _rms_norm(ctx, op_, ins):
    """Out = Scale * n(X), n(x) = x / sqrt(mean(x^2) + epsilon) over the
    last axis, or over each of `groups` equal slices of it. With Gate the
    input is X * silu(Gate) first (gate before norm, as nemotron_h's
    MambaRMSNormGated). Statistics in float32; Out has X's dtype."""
    x = jnp.asarray(ins["X"][0])
    h = _f32(x)
    if ins.get("Gate") and ins["Gate"][0] is not None:
        h = h * jax.nn.silu(_f32(ins["Gate"][0]))
    groups = op_.attr("groups", 1)
    g = h.reshape(h.shape[:-1] + (groups, h.shape[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                      + op_.attr("epsilon", 1e-5))
    out = g.reshape(h.shape) * _f32(ins["Scale"][0])
    return {"Out": [out.astype(x.dtype)]}


# --- causal depthwise conv over time -----------------------------------------

@op("causal_conv1d", infer_shape=same_as_input())
def _causal_conv1d(ctx, op_, ins):
    """X [B, T, C], Filter [C, K], Bias [C]: Out[t] = silu(Bias + sum_j
    Filter[:, j] * X[t - (K-1) + j]) with zeros before t = 0 (a depthwise
    conv1d, left pad K-1, then Mamba's activation). K shifted
    multiply-adds on the VPU; float32 inside, X's dtype out."""
    x = jnp.asarray(ins["X"][0])
    w = _f32(ins["Filter"][0])
    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(_f32(x), ((0, 0), (k - 1, 0), (0, 0)))
    out = _f32(ins["Bias"][0])
    for j in range(k):
        out = out + padded[:, j:j + t] * w[:, j]
    return {"Out": [jax.nn.silu(out).astype(x.dtype)]}


# --- Mamba-2 selective scan, chunked (SSD) -----------------------------------

def _segsum_exp(a):
    """a [..., l] of log-decays -> [..., l, l]: exp(sum of a over
    (j, i]) for i >= j, 0 above the diagonal."""
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    n = a.shape[-1]
    lower = jnp.tril(jnp.ones((n, n), bool))
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def ssd_scan_chunked(x, dt, a, b, c, chunk, dtype=jnp.float32):
    """The state-space recurrence h_t = exp(dt_t a) h_{t-1} + dt_t x_t
    b_t^T, y_t = h_t c_t per head, in chunks (Dao & Gu 2024, section 6):
    inside a chunk a masked [chunk, chunk] product, across chunks a
    recurrence over the T/chunk states written as one small product.
    x [B, T, H, P]; dt [B, T, H] (after softplus); a [H] (negative);
    b, c [B, T, G, N], head h reading group h // (H/G). Decays, the
    masks' weights and the states are float32; the four products take
    `dtype` operands and accumulate in float32. T need not be a multiple
    of `chunk`: the tail is padded with dt = 0, which neither decays nor
    feeds the state."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = (-t) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    dt = _f32(dt)
    xdt = (_f32(x) * dt[..., None]).astype(dtype).reshape(
        bsz, nc, chunk, g, r, p)
    b = b.astype(dtype).reshape(bsz, nc, chunk, g, n)
    c = c.astype(dtype).reshape(bsz, nc, chunk, g, n)
    # log-decay of each step, [B, chunks, G, R, chunk]
    da = (dt * _f32(a)).reshape(bsz, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(da, axis=-1)

    def dot(spec, *operands):
        return jnp.einsum(spec, *operands,
                          preferred_element_type=jnp.float32)

    # 1. inside each chunk: scores C B^T per group, decayed per head
    scores = dot("bzlgn,bzsgn->bzgls", c, b)
    masked = (scores[:, :, :, None] * _segsum_exp(da)).astype(dtype)
    y = dot("bzgrls,bzsgrp->bzlgrp", masked, xdt)
    # 2. the state each chunk leaves behind, had it started from zero
    to_end = jnp.exp(cum[..., -1:] - cum)                 # [B, z, G, R, l]
    weighted = (xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None]
                .astype(dtype))
    states = dot("bzlgn,bzlgrp->bzgrpn", b, weighted)     # float32
    # 3. across chunks: state entering chunk z = sum over earlier chunks
    # of their state, decayed by the chunks in between
    total = jnp.pad(cum[..., -1], ((0, 0), (1, 0), (0, 0), (0, 0)))
    across = _segsum_exp(total.transpose(0, 2, 3, 1))     # [B, G, R, z+1, z+1]
    entering = jnp.einsum("bgrzy,bygrpn->bzgrpn", across[..., :-1, 1:],
                          states, precision=lax.Precision.HIGHEST)
    # 4. what the entering state adds to each position of the chunk
    carried = dot("bzlgn,bzgrpn->bzlgrp", c, entering.astype(dtype))
    y = y + carried * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(bsz, t + pad, h, p)[:, :t]


@op("ssd_scan", infer_shape=same_as_input())
def _ssd_scan(ctx, op_, ins):
    """Mamba-2's mixer between its conv and its gated norm. X [B, T, H, P],
    Dt [B, T, H] (raw), DtBias, ALog, D [H], B and C [B, T, G, N]:
    dt = softplus(Dt + DtBias), A = -exp(ALog), the recurrence of
    ssd_scan_chunked with `chunk_size`, plus the skip D * X. Out has X's
    dtype."""
    x = jnp.asarray(ins["X"][0])
    dt = jax.nn.softplus(_f32(ins["Dt"][0]) + _f32(ins["DtBias"][0]))
    a = -jnp.exp(_f32(ins["ALog"][0]))
    core = jax.checkpoint(functools.partial(
        ssd_scan_chunked, chunk=op_.attr("chunk_size", 128),
        dtype=_compute_dtype(ctx)))
    y = core(x, dt, a, jnp.asarray(ins["B"][0]), jnp.asarray(ins["C"][0]))
    y = y + _f32(x) * _f32(ins["D"][0])[:, None]
    return {"Out": [y.astype(x.dtype)]}


# --- router ------------------------------------------------------------------

def _router_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is None or xv.shape is None:
        return
    shape = [xv.shape[0], op_.attr("top_k", 1)]
    set_out(op_, block, "TopkIdx", shape, "int32")
    set_out(op_, block, "TopkWeight", shape, "float32")


@op("moe_router", infer_shape=_router_infer, non_diff_inputs=("Bias",))
def _moe_router(ctx, op_, ins):
    """X [N, D], W [D, E], Bias [E] (a buffer: no gradient) -> TopkIdx
    [N, k] int32 and TopkWeight [N, k] float32. Scores s = sigmoid(X W) in
    float32 at full precision; the k experts with the largest s + Bias are
    chosen; their weights are `scaling` * s_i / (sum of the chosen s +
    1e-20) when `norm_topk_prob`, else `scaling` * s_i."""
    s = jax.nn.sigmoid(jnp.matmul(_f32(ins["X"][0]), _f32(ins["W"][0]),
                                  precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(_f32(ins["Bias"][0])),
                       op_.attr("top_k", 1))
    w = jnp.take_along_axis(s, idx, axis=-1)
    if op_.attr("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return {"TopkIdx": [idx.astype(jnp.int32)],
            "TopkWeight": [w * op_.attr("scaling", 1.0)]}


# --- dropless expert layer ---------------------------------------------------

_GMM_OP = "moe_experts"
_GMM_ROWS = 128

# Every reason gmm_ineligible can return.
GMM_FALLBACK_REASONS = frozenset({"rows", "width"})


def gmm_ineligible(rows: int, d: int, f: int):
    """None when Pallas' grouped matmul (megablox gmm) takes [rows, d] x
    [held, d, f] and back, else the reason lax.ragged_dot keeps the
    product: `rows` = tokens x top_k must be a multiple of the row tile,
    d and f at least one lane block wide."""
    if rows % _GMM_ROWS:
        return "rows"
    if d < 128 or f < 128:
        return "width"
    return None


def _gmm_tiling(d: int, f: int, dtype):
    """(rows, contraction, columns) of one grid step of gmm and tgmm, from
    the sweep on a v5e (PERF.md section 6, PR 30): at [24576, 2688] x
    [8, 2688, 1856] and back, forward + backward, ms with 1536 / 24576 of
    the rows routed: (128, 128, 128) 14.5 / 122; (128, 512, 512) 4.6 /
    21.6; (128, 1024, 1024) 3.9 / 15.1; (256, 1024, 1024) 4.0 / 13.3;
    (128, 2688, 512) 3.8 / 12.7; lax.ragged_dot 10.2 / 45.7. Small tiles
    pay the grid step thousands of times; the whole contraction in one
    step needs no accumulation pass. Row tiles of 128, because a held
    expert sees about 192 rows a sequence here. The contraction tile is
    capped at 4096 for bf16 operands and at 1024 for float32 ones (no
    AMP), whose [2688, 512] blocks, twice buffered, were refused by the
    chip's 16 MB of scoped VMEM."""
    cap = 4096 if jnp.dtype(dtype).itemsize <= 2 else 1024
    return (_GMM_ROWS, min(max(d, f), cap), min(d, f, 512))


def _grouped_products(rows, w1, w2, sizes, use_gmm):
    """relu(rows W1[e])^2 W2[e] for the rows of each group e (`sizes` rows
    each, in order; rows past their sum come back undefined)."""
    if use_gmm:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        from .pallas_attention import _interpret
        dot = functools.partial(
            megablox.gmm, group_sizes=sizes, preferred_element_type=rows.dtype,
            tiling=_gmm_tiling(w1.shape[1], w1.shape[2], rows.dtype),
            interpret=_interpret())
    else:
        dot = functools.partial(lax.ragged_dot, group_sizes=sizes)
    with jax.named_scope("moe_up"):
        h = dot(rows, w1)
    h = jnp.square(jax.nn.relu(h))
    with jax.named_scope("moe_down"):
        return dot(h, w2)


def _experts_infer(op_, block):
    same_as_input()(op_, block)
    set_out(op_, block, "RowsRouted", [1], "float32")
    set_out(op_, block, "RowsCombined", [1], "float32")
    set_out(op_, block, "LoadMaxOverMean", [1], "float32")


@op("moe_experts", infer_shape=_experts_infer, non_diff_inputs=("TopkIdx",))
def _moe_experts(ctx, op_, ins):
    """The routed experts' part of a mixture-of-experts layer, for the
    `experts_held` experts from `expert_offset` on of `num_experts`:
    Out[n] = sum over the chosen (i of top_k) with TopkIdx[n, i] held of
    TopkWeight[n, i] * f_e(X[n]), f_e(x) = relu(x W1[e])^2 W2[e]. What
    the experts held elsewhere would add is left out; with experts_held =
    num_experts this is the whole routed layer.

    No token is dropped and every shape is static: the N x top_k (token,
    slot) pairs are sorted by held expert, pairs of absent experts last,
    the tokens' rows gathered in that order, and one grouped product runs
    over the rows of the held experts alone (Pallas' megablox gmm visits
    only the row tiles its group sizes cover; lax.ragged_dot where the
    shape does not tile, booked with the reason). The rows then return to
    their tokens weighted, by a scatter-add.

    RowsRouted [1]: the pairs the router sent to held experts, counted
    on its indices; RowsCombined [1]: the rows the grouped product was
    given and the scatter-add returned, counted where they are combined
    (the two differ only if a row is lost between them); LoadMaxOverMean
    [1]: the busiest held expert's rows over the held experts' mean."""
    from . import pallas_conv
    from .. import quant

    x = jnp.asarray(ins["X"][0])
    idx = jnp.asarray(ins["TopkIdx"][0])
    weight = _f32(ins["TopkWeight"][0])
    dtype = _compute_dtype(ctx)
    w1 = jnp.asarray(ins["W1"][0]).astype(dtype)
    w2 = jnp.asarray(ins["W2"][0]).astype(dtype)
    held = op_.attr("experts_held", w1.shape[0])
    n, k = idx.shape
    assert w1.shape[0] == held and k == op_.attr("top_k", k)

    local = idx.reshape(-1) - op_.attr("expert_offset", 0)
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)           # held experts first
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    routed = sizes.sum()
    live = (jnp.arange(n * k) < routed)[:, None]
    token = order // k

    reason = gmm_ineligible(n * k, x.shape[-1], w1.shape[-1])
    if quant.counters_suppressed():   # the grad op's re-trace books nothing
        pass
    elif reason is None:
        pallas_conv.count_hit(_GMM_OP)
    else:
        pallas_conv.count_fallback(_GMM_OP, reason)
    rows = jnp.where(live, x[token].astype(dtype), 0)
    out = _grouped_products(rows, w1, w2, sizes, reason is None)
    # the kernel leaves rows past the routed ones undefined: select before
    # weighting, so that no gradient is a product with them either
    out = jnp.where(live, _f32(out), 0) * weight.reshape(-1)[order][:, None]
    out = jnp.zeros(x.shape, jnp.float32).at[token].add(out)

    load = sizes.max() / jnp.maximum(routed / held, 1.0)
    return {"Out": [out.astype(x.dtype)],
            "RowsRouted": [_f32((group < held).sum()).reshape(1)],
            "RowsCombined": [_f32(live.sum()).reshape(1)],
            "LoadMaxOverMean": [_f32(load).reshape(1)]}
