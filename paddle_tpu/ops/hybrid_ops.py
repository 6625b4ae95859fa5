"""Ops of hybrid state-space / mixture-of-experts language models (no
2018-reference analogue): RMSNorm with its gated, grouped forms (the
gate ahead of the norm or behind it, a weight of 1 + w), a causal
depthwise conv over time, the Mamba-2 selective scan in its chunked (SSD)
form, a top-k router (sigmoid or softmax scores), the rule that moves its
selection bias against the load (moe_balance_bias) and a dropless expert
layer that is told which experts it holds (squared-ReLU experts, or gated
SiLU ones when it is given the third matrix), and the rotary position
embedding (latent attention's decoupled part; every dim of a head in the
block-diffusion decoder; YaRN's blended frequencies and factor over half
a head in the gated window decoder's full layers; the first quarter of a
head in the Gated DeltaNet decoder's). models/nemotron_h.py,
models/mla_moe.py, models/block_diffusion_moe.py, models/window_moe.py,
models/gated_window_moe.py and models/gdn_moe.py build models from them.

Precision under AMP: norm statistics, the router, `dt`, `A`, the scan's
decays and its state stay float32; the scan's four products and the
expert products take bf16 operands with float32 accumulation; the rotary
angles, their sines and cosines and the rotation itself are float32.

Gradients: generic but the expert layer's, the short convolution's and,
on the kernels, the delta rule's
(registry.generic_grad_lower: jax.vjp of the lowering, whose re-traced
forward XLA merges with the original). The convolution and its explicit
gradient op run, where time and channels are whole 128-wide blocks
(pallas_conv1d.ineligible), on the two kernels of ops/pallas_conv1d.py,
which read X (and Out's cotangent) once in the dtype they arrive in and
write once, the gradient's computing the pre-activation again from the
op's inputs alone (PERF.md section 6, PR 60); elsewhere
causal_conv1d_reference and autodiff's gradient of it. The scan runs, where its shape tiles (ssd_scan_ineligible),
on the two Pallas kernels of ops/pallas_scan.py, whose [chunk, chunk]
decay and score blocks never leave VMEM: a jax.custom_vjp inside the
lowering that keeps the op's inputs and the state entering each chunk,
and recomputes the blocks in the gradient's kernel (PERF.md section 6,
PR 40); elsewhere its core is ssd_scan_chunked under a jax.checkpoint,
which keeps the op's inputs and recomputes the blocks as XLA arrays; the
chip's compiler merges a re-traced forward kernel with the original (4
ssd_scan_fwd a step of the hybrid cell, not 8). The delta rule (kda_scan),
where a head is a lane block (kda_scan_ineligible), is the second
exception: the kernels of ops/pallas_kda.py write the state entering each
chunk and each chunk's inverse as the op's outputs Entering and Inverse,
and an explicit gradient op reads them and runs the backward kernel alone
(_kda_grad). Under checkpoints the INVERSES outlive the forward
(kept_in_replay): a replayed op is handed them and runs the forward
kernel that reads them and forms none, writing Out and the entering
states again, which are four times the inverses' bytes and are not kept
(PERF.md section 6, PR 56 and PR 65); elsewhere kda_chunked and
autodiff's gradient of it, the generic rule. The expert layer is the
first exception: moe_experts writes the up product's rows
(and the gate's) as outputs and an explicit gradient op reads them
(_experts_grad, as nn_ops._sdpa_grad reads LSE), so the gradient runs the
pulled-back products alone. A layer that holds an eighth of the experts
or less handles its rows inside a capacity chosen on the device
(_capacity_ladder: twice a uniform router's share, then, for a sixteenth
or less, four times it, then every pair): one lax.switch in the op, a
branch a rung, and one in its gradient op,
which takes the same rung from the same routed count; a conditional is a
wall to XLA's merging, which is why nothing is traced twice across it
(a re-traced forward inside the gradient's branch ran its products a
second time: PERF.md section 6, PR 36 and PR 58). Inside a branch, and
in the layer without one, the two maps between tokens and sorted rows
are gathers both ways, forward and pulled back (_rows_of_tokens,
_pairs_summed: autodiff would zero-fill [N, D] and scatter-add). The map
back to the tokens, forward and pulled back (_sum_of_pairs), runs on the
kernel of ops/pallas_pair_sum.py wherever its gate takes the shape: one
pass over the live rows (PR 47). The rotation is linear in X and its
generic gradient is the rotation by the opposite angle.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..framework.desc import OpDesc
from ..framework.framework import grad_var_name
from . import kernel_choice, pallas_pair_sum
from .common import in_var, same_as_input, set_out
from .registry import (NO_GRAD, generic_grad_lower, generic_grad_op_descs,
                       handed_on, op)

__all__ = ["causal_conv1d_reference", "gmm_ineligible", "kda_chunked",
           "ssd_scan_chunked", "ssd_scan_ineligible"]


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
    MambaRMSNormGated), or with `gate_behind` the result is times
    silu(Gate): Scale * n(X) * silu(Gate), the norm ahead of the gate
    (qwen3_next's gated norm a head). `unit_offset`: the weight is 1 +
    Scale (Scale from zeros; qwen3_next's and Gemma's form). Statistics
    in float32; Out has X's dtype."""
    x = jnp.asarray(ins["X"][0])
    h = _f32(x)
    gate = ins["Gate"][0] if ins.get("Gate") else None
    behind = op_.attr("gate_behind", False)
    if gate is not None and not behind:
        h = h * jax.nn.silu(_f32(gate))
    groups = op_.attr("groups", 1)
    g = h.reshape(h.shape[:-1] + (groups, h.shape[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                      + op_.attr("epsilon", 1e-5))
    out = g.reshape(h.shape)    # (traced in the order it always was)
    scale = _f32(ins["Scale"][0])
    if op_.attr("unit_offset", False):
        scale = 1.0 + scale
    out = out * scale
    if gate is not None and behind:
        out = out * jax.nn.silu(_f32(gate))
    return {"Out": [out.astype(x.dtype)]}


# --- rotary position embedding -----------------------------------------------

def _yarn_frequencies(inv, dims: int, theta: float, yarn):
    """YaRN's per-pair frequencies (arXiv:2309.00071, as transformers'
    _compute_yarn_parameters): pair j keeps theta^(-2j/dims) below `low`
    (it turns more than beta_fast times over the original context),
    takes it divided by `factor` from `high` on (fewer than beta_slow
    turns), and a linear blend between; low and high are the floor and
    the ceiling of dims * ln(original / (2 pi beta)) / (2 ln theta).
    `yarn`: (factor, original positions, beta_fast, beta_slow)."""
    factor, original, beta_fast, beta_slow = yarn

    def pair_that_turns(times):
        return dims * np.log(original / (times * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(pair_that_turns(beta_fast)), 0)
    high = min(np.ceil(pair_that_turns(beta_slow)), dims - 1)
    ramp = np.clip((np.arange(dims // 2) - low)
                   / (high - low if high != low else 0.001), 0.0, 1.0)
    return inv * (1 - ramp) + inv / factor * ramp


def _rotary_angles(positions, dims: int, theta: float, yarn=None):
    """[positions, dims / 2] float32 angles t * theta^(-2 i / dims), the
    frequencies blended by _yarn_frequencies where `yarn` is given."""
    inv = np.float64(theta) ** (-np.arange(0, dims, 2, dtype=np.float64)
                                / dims)
    if yarn is not None:
        inv = _yarn_frequencies(inv, dims, float(theta), yarn)
    return jnp.arange(positions, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)


_YARN_ATTRS = ("yarn_factor", "yarn_original_positions", "yarn_beta_fast",
               "yarn_beta_slow")


@op("rotary_embedding", infer_shape=same_as_input())
def _rotary_embedding(ctx, op_, ins):
    """X [B, T, H, D]: the last `rotary_dims` of every head (default all
    D) are rotated by the position t of axis 1, the pair (i, i + r/2) of
    those r dims by the angle t * theta^(-2i/r) (the rotate-half
    pairing); the leading D - r dims pass through. With `rotate_first`
    the FIRST r dims are the rotated ones and the trailing D - r pass
    through (qwen3_next's partial rotary). With `yarn_factor`
    (and the three attributes beside it) the per-pair frequencies are
    YaRN's blend (_yarn_frequencies); `attention_factor` multiplies the
    cosines and sines, so the rotated dims come out scaled by it and the
    others do not. Angles, sines and cosines and the rotation are float32
    whatever X is; Out has X's dtype."""
    x = jnp.asarray(ins["X"][0])
    d = x.shape[-1]
    r = op_.attr("rotary_dims", 0) or d
    assert r % 2 == 0 and r <= d, (r, d)
    yarn = None
    if op_.attr("yarn_factor", 0.0):
        yarn = tuple(float(op_.attr(name)) for name in _YARN_ATTRS)
    angle = _rotary_angles(x.shape[1], r, op_.attr("theta", 10000.0), yarn)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    factor = op_.attr("attention_factor", 1.0)
    if factor != 1.0:   # traced only when set: every other step's HLO,
        cos, sin = factor * cos, factor * sin   # and cache key, as it was
    h = _f32(x)
    if op_.attr("rotate_first", False):
        first, second, keep = h[..., :r // 2], h[..., r // 2:r], h[..., r:]
        out = jnp.concatenate([first * cos - second * sin,
                               second * cos + first * sin, keep], axis=-1)
        return {"Out": [out.astype(x.dtype)]}
    keep, first, second = h[..., :d - r], h[..., d - r:d - r // 2], \
        h[..., d - r // 2:]
    out = jnp.concatenate([keep, first * cos - second * sin,
                           second * cos + first * sin], axis=-1)
    return {"Out": [out.astype(x.dtype)]}


# --- causal depthwise conv over time -----------------------------------------

def causal_conv1d_reference(x, w, bias=None, pre_gate=None, post_gate=None,
                            activation="silu"):
    """The op causal_conv1d in plain jax.numpy, its whole form:

        U = PreGate * X                                 (no gate: X)
        pre[t] = Bias + sum_j Filter[:, j] * U[t - (K-1) + j]
        Out = PostGate * act(pre)                       (no gate: act(pre))

    K shifted multiply-adds over a zero-padded float32 copy of U, then
    `activation` ("silu", Mamba's, or "identity"); float32 inside, X's
    dtype out. The statement the kernels of ops/pallas_conv1d.py are held
    to, and the path (with autodiff's gradient of it) for shapes their
    gate declines."""
    assert activation in ("silu", "identity"), activation
    w = _f32(w)
    k, t = w.shape[1], x.shape[1]
    u = _f32(x) if pre_gate is None else _f32(x) * _f32(pre_gate)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = _f32(bias) if bias is not None else 0.0
    for j in range(k):
        out = out + padded[:, j:j + t] * w[:, j]
    if activation == "silu":
        out = jax.nn.silu(out)
    if post_gate is not None:
        out = out * _f32(post_gate)
    return out.astype(x.dtype)


_CONV1D_OP = "causal_conv1d"
# the op's tensor inputs behind X, and the reference's / the kernels'
# keyword each arrives under
_CONV1D_SLOTS = {"Filter": "w", "Bias": "bias", "PreGate": "pre_gate",
                 "PostGate": "post_gate"}


def _conv1d_operands(op_, ins):
    """(X, {keyword: Filter, Bias, the gates: those that are there, and
    `activation` where it is not the default}, the kernels' keywords or
    None where their gate declines, the gate's reason) of the op or its
    gradient op."""
    from . import pallas_conv1d
    from .pallas_attention import _interpret

    x = jnp.asarray(ins["X"][0])
    form = {key: jnp.asarray(ins[slot][0])
            for slot, key in _CONV1D_SLOTS.items()
            if ins.get(slot) and ins[slot][0] is not None}
    if op_.attr("activation", "silu") != "silu":
        form["activation"] = op_.attr("activation")
    reason = pallas_conv1d.ineligible(x.shape[1], x.shape[2],
                                      form["w"].shape[1], x.dtype)
    kernel = None if reason else dict(
        lanes=bool(op_.attr("time_on_lanes", False)), interpret=_interpret())
    return x, form, kernel, reason


def _conv1d_grad(fwd, no_grad_set):
    """causal_conv1d_grad reads the op's inputs and Out's cotangent and
    nothing the forward made (the kernel computes the pre-activation
    again): the generic maker's op would trace the forward inside the
    gradient op and keep its float32 copies for the pull-back."""
    wanted = [s for s in ("X",) + tuple(_CONV1D_SLOTS)
              if fwd.inputs.get(s) and fwd.input(s)[0] not in no_grad_set]
    if not wanted:
        return []
    return [OpDesc(
        type=fwd.type + "_grad",
        inputs={**{s: list(names) for s, names in fwd.inputs.items()},
                "Out@GRAD": [grad_var_name(fwd.output("Out")[0])]},
        outputs={s + "@GRAD": [grad_var_name(fwd.input(s)[0])]
                 for s in wanted},
        attrs=dict(fwd.attrs))]


@op("causal_conv1d", infer_shape=same_as_input(), grad=_conv1d_grad)
def _causal_conv1d(ctx, op_, ins):
    """X [B, T, C], Filter [C, K], Bias [C] (may be absent: Kimi Delta
    Attention's short convolutions have none), and two optional gates of
    X's shape, PreGate ahead of the taps and PostGate behind them (LFM2's
    operator, C * conv(B * x), with `activation` "identity"):

        Out[t] = PostGate[t] * act(Bias + sum_j Filter[:, j]
                                   * (PreGate * X)[t - (K-1) + j])

    with zeros before t = 0 (a depthwise conv1d, left pad K-1, then
    `activation`: "silu", Mamba's and the default, or "identity"). K
    shifted multiply-adds on the VPU; float32 inside, X's dtype out.
    Without gates and attribute the program and the lowering are what
    they were.

    What runs is chosen from the shapes (pallas_conv1d.ineligible): the
    forward kernel of ops/pallas_conv1d.py, which reads X and the gates
    once in the dtype they have and writes Out once (interpreted off the
    chip; pallas_kernel_total{op="causal_conv1d"}), or
    causal_conv1d_reference, XLA's, booked with the reason
    (pallas_fallback_total). Every forward lowering books, a replayed
    segment's too (12 + 9 in the Kimi-Linear cell); the gradient op books
    nothing. `time_on_lanes` (an attribute the layer writes: mamba2_mixer,
    whose scan reads time along the lanes) turns the kernel's blocks, not
    the result."""
    from . import pallas_conv1d

    x, form, kernel, reason = _conv1d_operands(op_, ins)
    kernel_choice.book(_CONV1D_OP, reason)
    if kernel is None:
        return {"Out": [causal_conv1d_reference(x, **form)]}
    return {"Out": [pallas_conv1d.causal_conv1d_fwd(
        x, form.pop("w"), form.pop("bias", None), **form, **kernel)]}


@op("causal_conv1d_grad", grad=NO_GRAD)
def _causal_conv1d_grad(ctx, op_, ins):
    """dX and the gates' gradients (X's dtype), dFilter and dBias (their
    parameters' dtypes) from the op's inputs and Out's cotangent: the
    gradient's kernel of ops/pallas_conv1d.py where the forward took its
    kernel (the same gate), else autodiff's gradient of
    causal_conv1d_reference."""
    from . import pallas_conv1d

    x, form, kernel, _ = _conv1d_operands(op_, ins)
    d_out = jnp.asarray(ins["Out@GRAD"][0])
    primals = {"X": x, **{slot: form[key]
                          for slot, key in _CONV1D_SLOTS.items()
                          if key in form}}
    if kernel is None:
        def statement(tensors):
            return causal_conv1d_reference(
                tensors["X"], activation=form.get("activation", "silu"),
                **{_CONV1D_SLOTS[s]: v for s, v in tensors.items()
                   if s != "X"})
        grads, = jax.vjp(statement, primals)[1](d_out.astype(x.dtype))
    else:
        grads = dict(zip(
            ("X", "Filter", "Bias", "PreGate", "PostGate"),
            pallas_conv1d.causal_conv1d_bwd(
                x, form.pop("w"), form.pop("bias", None), d_out, **form,
                **kernel)))
    return {slot + "@GRAD": [grads[slot].astype(like.dtype)]
            for slot, like in primals.items()
            if slot + "@GRAD" in op_.desc.outputs}


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
    The statement of the algorithm in plain jax.numpy, and the path for
    shapes that do not tile (ssd_scan_ineligible: the tiny test models);
    where they do, ops/pallas_scan.py::ssd_scan_kernels computes the same
    from the same arguments with the [chunk, chunk] blocks in VMEM. Here
    the mask and the decayed scores are [B, chunks, G, R, chunk, chunk]
    arrays that XLA writes and reads back (134 MB each in float32 at the
    hybrid cell's shape).
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


_SCAN_OP = "ssd_scan"


def ssd_scan_ineligible(chunk: int, heads_a_group: int, p: int, n: int):
    """None when the scan kernels (ops/pallas_scan.py) take chunks of
    `chunk` steps, groups of `heads_a_group` heads of P = `p` and states
    of N = `n`, else the reason ssd_scan_chunked keeps the scan
    (kernel_choice.REASONS["ssd_scan"]). Time
    runs along the kernels' lanes, so a chunk must fill 128-lane blocks
    (`chunk`), as must the states' N where B and C stand with time on the
    sublanes (`state`); a head is P sublanes of its block, whole packed
    bf16 rows of 16 (`heads`). Any number of heads a group tiles: a step
    owns pallas_scan.heads_a_step of them at the op's chunk."""
    del heads_a_group
    if chunk % 128:
        return "chunk"
    if n % 128:
        return "state"
    if p % 16:
        return "heads"
    return None


@op("ssd_scan", infer_shape=same_as_input())
def _ssd_scan(ctx, op_, ins):
    """Mamba-2's mixer between its conv and its gated norm. X [B, T, H, P],
    Dt [B, T, H] (raw), DtBias, ALog, D [H], B and C [B, T, G, N]:
    dt = softplus(Dt + DtBias), A = -exp(ALog), the recurrence of
    ssd_scan_chunked with `chunk_size`, plus the skip D * X. Out has X's
    dtype.

    What runs is chosen from the shapes (ssd_scan_ineligible): the two
    Pallas kernels of ops/pallas_scan.py, forward and gradient, whose
    [chunk, chunk] blocks never leave VMEM (interpreted off the chip;
    pallas_kernel_total{op="ssd_scan"}), or ssd_scan_chunked under a
    jax.checkpoint, booked with the reason (pallas_fallback_total). Both
    take the same operands at the same precision; softplus, dt * A and
    the skip are jax.numpy around either, so autodiff carries DtBias,
    ALog and D."""
    from .pallas_attention import _interpret
    from .pallas_scan import ssd_scan_kernels

    x = jnp.asarray(ins["X"][0])
    b, c = jnp.asarray(ins["B"][0]), jnp.asarray(ins["C"][0])
    dt = jax.nn.softplus(_f32(ins["Dt"][0]) + _f32(ins["DtBias"][0]))
    a = -jnp.exp(_f32(ins["ALog"][0]))
    chunk = op_.attr("chunk_size", 128)
    reason = ssd_scan_ineligible(chunk, x.shape[2] // b.shape[2], x.shape[3],
                                 b.shape[3])
    kernel_choice.book(_SCAN_OP, reason)
    shared = dict(chunk=chunk, dtype=_compute_dtype(ctx))
    if reason is None:
        core = functools.partial(ssd_scan_kernels, interpret=_interpret(),
                                 **shared)
    else:
        core = jax.checkpoint(functools.partial(ssd_scan_chunked, **shared))
    y = core(x, dt, a, b, c)
    y = y + _f32(x) * _f32(ins["D"][0])[:, None]
    return {"Out": [y.astype(x.dtype)]}


# --- the gated delta rule, chunked: Kimi Delta Attention's decay a channel, ---
# --- and Gated DeltaNet's a head with key heads under groups of value heads ---

# rows of one sub-block of a chunk. Every exponent of kda_chunked is a
# difference of summed log-decays referred to a sub-block's first row, so
# the largest it takes is what ONE sub-block's tokens decay by: 16 tokens
# of the published strongest initial decay (-1.6 a token) are e^25.6,
# where float32 ends at e^88.7.
_KDA_SUB = 16


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for a [..., C, C] strictly lower triangular, C a power
    of two, in float32 at full precision: block forward substitution by
    doubling. X holds the inverse of every diagonal block of b rows (b =
    1: the identity); with a's entries under the first and beside the
    second of each pair of neighbouring blocks, a_b (the pair's A21, all
    else zero), X - X a_b X is the same of 2 b rows: [[M11, 0], [-M22 A21
    M11, M22]] a pair. log2(C) levels of two batched [C, C] products
    each, every one exact forward substitution: no power of `a` is formed
    (the Neumann product (I - a)(I + a^2)(I + a^4)... forms them, and
    cancels catastrophically where keys repeat), no loop over rows
    reaches the compiled step, and every operand keeps [C, C] as its
    minor dims (a [b, b] block a pair pads sixty-four-fold in a v5e's
    tiles at b = 2). Its gradient is the inverse's own, da = -M^T dM M^T
    below the diagonal: two products and M alone kept, where autodiff
    through the levels keeps two [C, C] arrays a level."""
    c = a.shape[-1]
    assert c & (c - 1) == 0, f"a chunk of {c} rows is no power of two"
    at = np.arange(c)
    inverse = jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), a.shape)
    b = 1
    while b < c:
        # rows in the second block of a pair, columns in the first
        under = ((at[:, None] // b) % 2 == 1) & (
            at[:, None] // b - at[None, :] // b == 1)
        step = jnp.matmul(jnp.where(under, a, 0.0), inverse,
                          precision=lax.Precision.HIGHEST)
        inverse = inverse - jnp.matmul(inverse, step,
                                       precision=lax.Precision.HIGHEST)
        b *= 2
    return inverse


def _unit_lower_inverse_fwd(a):
    inverse = _unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    turned = jnp.swapaxes(inverse, -1, -2)
    d_a = -jnp.matmul(
        jnp.matmul(turned, d_inverse, precision=lax.Precision.HIGHEST),
        turned, precision=lax.Precision.HIGHEST)
    at = np.arange(inverse.shape[-1])
    return (jnp.where(at[:, None] > at[None, :], d_a, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def kda_chunked(q, k, v, g, beta, chunk, dtype=jnp.float32):
    """The gated delta rule with a decay a channel (Kimi Delta Attention,
    arXiv:2510.26692), a head at a time:

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    in chunks of `chunk` tokens (the WY form): one lax.scan over the
    chunks with the state [B, H, K, V] as the carry, each chunk
    (_kda_chunk, which has the algebra) a jax.checkpoint. The gradient
    keeps a state a chunk and computes a chunk's [C, C] system, inverse
    and decayed copies again ahead of their gradient, so nothing of a
    chunk outlives it. tools/kda_sweep.py on a v5e at the Kimi-Linear
    cell's [1, 8192, 32, 128, 128], forward | forward + gradient ms (PR
    55): 8.9 | 25.5 at chunks of 64 (9.1 | 27.0 at 32); held over more
    than its own chunk the gradient's working set leaves the chip: 9.6 |
    27.4 with four chunks of 64 kept at once, 10.0 | 38.0 with sixteen,
    16.4 | 53.2 with sixty-four. The whole op around it (kda_scan_chunked
    from bf16 operands) 10.35 | 27.79 at chunks of 64, where the kernels
    of ops/pallas_kda.py read 5.93 | 10.80 (PR 56).

    q, k [B, T, H, K] (unit rows: the caller's L2 norm), v [B, T, H, V],
    g [B, T, H, K] (<= 0) and beta [B, T, H] in float32. T need not be a
    multiple of `chunk`: the tail is padded with beta = 0 and g = 0,
    which neither writes nor decays."""
    bsz, t, h, kd = q.shape
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))

    def chunks(x):
        return jnp.moveaxis(
            x.reshape((bsz, (t + pad) // chunk, chunk) + x.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_chunk(state, chunk_):
        return _kda_chunk(state, *chunk_, dtype)

    _, out = lax.scan(one_chunk,
                      jnp.zeros((bsz, h, kd, v.shape[-1]), jnp.float32),
                      tuple(map(chunks, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).reshape(bsz, t + pad, h, -1)[:, :t]


def _kda_chunk(state, q, k, v, g, beta, dtype):
    """(the state left, o [B, C, H, V]) of one chunk of C tokens entered
    with `state` [B, H, K, V] in float32. With G_r the summed g of the
    chunk's rows up to and with r, A_rs = beta_r sum_c k_rc k_sc exp(G_rc
    - G_sc) below the diagonal, M = (I + A)^-1 (_unit_lower_inverse), w =
    M (beta k exp(G)) and u = M (beta v): u' = u - w S, o = (q exp(G)) S
    + tril(q k^T decayed) u', S <- Diag(exp(G_C)) S + (k exp(G_C - G))^T
    u'.

    G, A, the inverse and the state are float32; the products take
    `dtype` operands and accumulate in float32. Every exponent is a
    DIFFERENCE referred to the first row of a sub-block of _KDA_SUB rows
    (a row's own sub-block for the row side, the row's sub-block for
    every key it may see), at most 0 across sub-blocks and at most one
    sub-block's decay inside one; exp(-G) alone, which passes float32 at
    a chunk's summed decay of -88.7, is never taken."""
    bsz, chunk, h, kd = q.shape
    sub = _KDA_SUB if chunk % _KDA_SUB == 0 else chunk
    n = chunk // sub
    # [B, C, H, ...] -> [B, H, C, ...]: a head's chunk is a matrix, and
    # every product below is batched over b and h
    q, k, v, g, beta = (jnp.moveaxis(_f32(x), 2, 1)
                        for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=2)                         # [B, H, l, K]
    # the summed decay ahead of each sub-block's first row: [B, H, n, K]
    ahead = jnp.concatenate(
        [jnp.zeros_like(cum[:, :, :1]), cum[:, :, sub - 1:-1:sub]], axis=2)
    own = jnp.repeat(ahead, sub, axis=2)                # a row's own sub-block
    shrunk, decayed = jnp.exp(cum - own), jnp.exp(cum)

    def by_sub(x):
        return x.astype(dtype).reshape(bsz, h, n, sub, kd)

    # q's and k's rows of a sub-block, one under the other: [.., n, 2 sub, K]
    rows = jnp.concatenate([by_sub(q * shrunk), by_sub(k * shrunk)], axis=3)
    # the keys as row sub-block i reads them: those up to its own last row
    at = jnp.arange(chunk)
    seen = at[None, :] < (jnp.arange(n)[:, None] + 1) * sub     # [n, l]
    rise = ahead[:, :, :, None] - cum[:, :, None]       # [B, H, n, l, K]
    keys = (k[:, :, None] * jnp.exp(
        jnp.where(seen[:, :, None], rise, -jnp.inf))).astype(dtype)

    def dot(spec, *operands):
        return jnp.einsum(spec, *operands,
                          preferred_element_type=jnp.float32)

    scores = dot("bhnik,bhnsk->bhnis", rows, keys)
    qk, kk = (scores[:, :, :, part].reshape(bsz, h, chunk, chunk)
              for part in (slice(0, sub), slice(sub, None)))
    below = at[:, None] > at[None, :]
    qk = jnp.where(below | (at[:, None] == at[None, :]), qk, 0.0)
    a = jnp.where(below, kk, 0.0) * beta[..., None]     # [B, H, l, s]
    inverse = _unit_lower_inverse(a).astype(dtype)
    fed = beta[..., None]
    w = dot("bhls,bhsk->bhlk", inverse,
            (k * decayed * fed).astype(dtype)).astype(dtype)
    u = dot("bhls,bhsv->bhlv", inverse, (v * fed).astype(dtype))
    entering = state.astype(dtype)
    fresh = (u - dot("bhlk,bhkv->bhlv", w, entering)).astype(dtype)
    last = cum[:, :, -1]                                # [B, H, K]
    to_end = (k * jnp.exp(last[:, :, None] - cum)).astype(dtype)
    left = state * jnp.exp(last)[..., None] + dot("bhlk,bhlv->bhkv", to_end,
                                                  fresh)
    out = dot("bhlk,bhkv->bhlv", (q * decayed).astype(dtype), entering) \
        + dot("bhls,bhsv->bhlv", qk.astype(dtype), fresh)
    return left, jnp.moveaxis(out, 1, 2)


def _kda_infer(op_, block):
    v, q = in_var(op_, block, "V"), in_var(op_, block, "Q")
    if v is None or v.shape is None:
        return
    set_out(op_, block, "Out", list(v.shape), v.dtype)
    if q is None or q.shape is None:
        return
    chunk = op_.attr("chunk_size", 64)
    b, t, h, width = v.shape
    chunks = -(-t // chunk) if t >= 0 else -1
    set_out(op_, block, "Entering", [b, chunks, h, width, q.shape[3]],
            "float32")
    # how many heads' blocks share a pack is the lowering's to know (the
    # heads a step owns follow the compute dtype)
    set_out(op_, block, "Inverse", [b, chunks, -1, chunk, -1], "float32")


_KDA_OP = "kda_scan"
# the two outputs the kernels write beside Out and the gradient op reads
# (pallas_kda.kda_scan_forward's, in its order): the state entering each
# chunk, which a replayed op writes again, and each chunk's inverse, kept
# across a replayed segment (the op's registry entry). An op built
# without them (XLA's path: kda_scan_outputs) has the generic gradient.
_KDA_KEPT = ("Entering", "Inverse")


def kda_scan_ineligible(chunk: int, k: int, v: int, ratio: int = 1,
                        per_head: bool = True):
    """None when the delta rule's kernels (ops/pallas_kda.py) take chunks
    of `chunk` tokens and heads of K = `k` and V = `v` channels, `ratio`
    value heads reading one key head under a decay a head (`per_head`) or
    a channel, else the reason kda_chunked keeps the op
    (kernel_choice.REASONS["kda_scan"]).
    A head is one lane block of its projection's [T, H x K] rows: K must
    be 128 (Mosaic aborts on a row of a [., 256] array, as it did for
    the scan kernels' N: PR 40) and V whole lane blocks (`width`); the
    chunk's system is solved by doubling from sub-blocks of _KDA_SUB
    rows, a power of two of them (`chunk`). Any number of heads and any
    T tile: a step owns pallas_kda.heads_a_step of the heads, and a tail
    is padded."""
    if k != 128 or v % 128:
        return "width"
    if chunk % _KDA_SUB or chunk & (chunk - 1):
        return "chunk"
    # a key head's dq and dk are summed inside one grid step, which owns
    # at most _HEADS heads; the channel form's kernels (Kimi-Linear's,
    # held to what they were) read q, k and the gate through one block
    from .pallas_kda import _HEADS
    if ratio > 1 and (ratio > _HEADS or not per_head):
        return "group"
    return None


def kda_scan_outputs(chunk: int, k: int, v: int, ratio: int = 1,
                     per_head: bool = True):
    """The output slots a kda_scan op of these shapes is built with beside
    Out (layers.kda_mixer, layers.gdn_mixer): _KDA_KEPT where the kernels
    take the shapes (kda_scan_ineligible's arguments), none on XLA's
    path."""
    return () if kda_scan_ineligible(chunk, k, v, ratio, per_head) \
        else _KDA_KEPT


def kda_scan_chunked(q, k, v, gate, a_log, dt_bias, beta, chunk, eps,
                     dtype=jnp.float32):
    """The op kda_scan in jax.numpy around kda_chunked (the op's
    docstring has the equations): the norm, g and beta in float32
    whatever the inputs are, so autodiff carries a_log and dt_bias. A
    decay a head is broadcast over the head's channels and a key head
    repeated to its value heads here: the statement, and the path of
    shapes the kernels do not take, not the cell's."""
    q, k = (x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
            for x in (_f32(q), _f32(k)))
    heads, width = v.shape[2], q.shape[3]
    if heads != q.shape[2]:     # value head j reads key head j // ratio
        q, k = (jnp.repeat(x, heads // x.shape[2], axis=2) for x in (q, k))
    if gate.ndim == 3:          # a decay a head, over its channels
        g = jnp.broadcast_to((-jnp.exp(_f32(a_log)) * jax.nn.softplus(
            _f32(gate) + _f32(dt_bias)))[..., None], q.shape)
    else:
        g = -jnp.exp(_f32(a_log))[:, None] * jax.nn.softplus(
            _f32(gate) + _f32(dt_bias).reshape(heads, width))
    out = kda_chunked(q, k, v, g, jax.nn.sigmoid(_f32(beta)), chunk,
                      dtype=dtype)
    return (out * width ** -0.5).astype(v.dtype)


def _kda_operands(op_, ins):
    """(the op's seven operands in the kernels' order, its chunk length,
    whether the decay is a head's, the value heads a key head, the reason
    the kernels decline the shapes or None)."""
    operands = [jnp.asarray(ins[slot][0]) for slot in (
        "Q", "K", "V", "Gate", "ALog", "DtBias", "Beta")]
    chunk = op_.attr("chunk_size", 64)
    per_head = operands[3].ndim == 3
    ratio = operands[2].shape[2] // operands[0].shape[2]
    return operands, chunk, per_head, ratio, kda_scan_ineligible(
        chunk, operands[0].shape[3], operands[2].shape[3], ratio, per_head)


def _kda_grad(fwd, no_grad_set):
    """kda_scan_grad reads the op's inputs, Out's cotangent and the two
    float32 outputs the forward kernel wrote (_KDA_KEPT), as
    nn_ops._sdpa_grad reads LSE, and runs the backward kernel alone: the
    generic maker's op traces the forward kernel again for them. An op
    built without them (XLA's path) has the generic maker's."""
    if not all(fwd.outputs.get(s) for s in _KDA_KEPT):
        return generic_grad_op_descs(fwd, no_grad_set)
    wanted = [s for s, names in fwd.inputs.items()
              if names[0] not in no_grad_set]
    if not wanted:
        return []
    return [OpDesc(
        type=fwd.type + "_grad",
        inputs={**{s: list(names) for s, names in fwd.inputs.items()},
                **{s: fwd.output(s) for s in _KDA_KEPT},
                "Out@GRAD": [grad_var_name(fwd.output("Out")[0])]},
        outputs={s + "@GRAD": [grad_var_name(fwd.input(s)[0])]
                 for s in wanted},
        attrs=dict(fwd.attrs))]


@op("kda_scan", infer_shape=_kda_infer, grad=_kda_grad,
    kept_in_replay=("Inverse",))
def _kda_scan(ctx, op_, ins):
    """Kimi Delta Attention between its short convolutions and its gated
    norm. Q, K [B, T, H, K] and V [B, T, H, V] (behind conv and silu),
    Gate [B, T, H, K] (the low-rank map's output, raw), ALog [H], DtBias
    [H * K], Beta [B, T, H] (raw):

        q, k <- q / sqrt(sum q^2 + `epsilon`), k likewise    (a head's rows)
        g = -exp(ALog) * softplus(Gate + DtBias)             a channel, <= 0
        beta = sigmoid(Beta)
        Out = kda_chunked(q, k, V, g, beta, `chunk_size`) / sqrt(K)

    The norm, g and beta are float32 whatever the inputs are; Out has V's
    dtype. What runs is chosen from the shapes (kda_scan_ineligible): the
    Pallas kernels of ops/pallas_kda.py, forward and gradient, which read
    Q, K, V and Gate as [B, T, H x K] in the dtype they arrive in, form
    the norms, g and each chunk's system in VMEM and keep a head's state
    there across the walk (interpreted off the chip;
    pallas_kernel_total{op="kda_scan"}), or kda_scan_chunked, XLA's (the
    chunked form and autodiff's gradient of it, each chunk computed again
    ahead of its pull-back; the triangular inverse's gradient its own),
    booked with the reason (pallas_fallback_total). Both take the same
    operands at the same precision.

    Inverse [B, chunks, H / pack, C, pack C] and Entering [B, chunks, H,
    V, K], float32, of an op built with them (kda_scan_outputs: the
    kernels' shapes): each chunk's inverse and the state entering it, as
    the forward kernel writes them and kda_scan_grad's backward kernel
    reads them. Inverse is kept across a replayed segment (the registry
    entry's kept_in_replay): the replayed op is handed it as KeptInverse,
    and its forward kernel reads it where it would form it again from the
    same K, Beta and decays, and writes Out and Entering (the inverse is
    two fifths of the kernel to form and 16 KB a head and chunk to hold;
    the entering states, 64 KB a head and chunk, are not kept). An op
    built without the two (XLA's path, a raw op) has the generic
    gradient, which on the kernels' shapes runs them under
    pallas_kda.kda_scan_kernels' custom_vjp.

    kda_scan_total{chunk, path} books each forward lowering: `kernel` or
    `chunked` a first forward's; of an op a recomputed segment runs
    again, `kernel_given_inverse` where it was handed the inverses,
    `kernel_replay` or `chunked_replay` where it forms all again (a
    gradient's re-trace books nothing).

    The Gated DeltaNet form (arXiv:2412.06464), told by the shapes alone:
    Gate [B, T, H] and DtBias [H], a decay a HEAD, g = -exp(ALog) *
    softplus(Gate + DtBias) read by all of a head's channels; and Q, K
    [B, T, H / r, K] under V's H heads, value head j reading key head j
    // r. The kernels then take one exponent a row and head where the
    channel form takes K, read Q and K at their own head count and sum a
    key head's dQ and dK over its group inside the backward walk (other
    calls, `gdn_scan_fwd` / `gdn_scan_bwd`: the channel form's are what
    they were); kda_scan_head_decay_total{path, groups} books such a
    lowering beside kda_scan_total."""
    from .pallas_attention import _interpret
    from .pallas_kda import kda_scan_forward, kda_scan_kernels

    kept = handed_on(ctx, op_, ins)
    operands, chunk, per_head, ratio, reason = _kda_operands(op_, ins)
    keeps = all(s in op_.desc.outputs for s in _KDA_KEPT)
    if keeps and reason:
        raise ValueError(
            f"kda_scan (Out {op_.desc.output('Out')}) was built with the "
            f"outputs {_KDA_KEPT}, which the kernels alone write, at shapes "
            f"they decline ({reason}): build it with kda_scan_outputs'")
    kernel_choice.book(_KDA_OP, reason)
    if not kernel_choice.in_retrace():
        from .. import telemetry
        from ..backward import RECOMPUTE_ATTR
        path = "kernel" if reason is None else "chunked"
        if kept is not None:
            path += "_given_inverse"
        elif RECOMPUTE_ATTR in op_.desc.attrs:
            path += "_replay"
        telemetry.counter(
            "kda_scan_total",
            "lowerings of a forward kda_scan op, by its chunk length and "
            "the path taken (`kernel`: ops/pallas_kda.py's; `chunked`: "
            "XLA's; `kernel_replay`, `chunked_replay`: the same, run "
            "again by a recomputed segment; `kernel_given_inverse`: the "
            "kernels' forward run again from the chunk inverses the first "
            "run kept)",
            labels=("chunk", "path")).labels(chunk=str(chunk),
                                             path=path).inc()
        if per_head:
            telemetry.counter(
                "kda_scan_head_decay_total",
                "of kda_scan_total's lowerings, those with a decay a head "
                "(Gate [B, T, H]), by path and by the value heads that "
                "read one key head",
                labels=("path", "groups")).labels(
                    path=path, groups=str(ratio)).inc()
    shared = dict(chunk=chunk, eps=op_.attr("epsilon", 1e-6),
                  dtype=_compute_dtype(ctx))
    if keeps:
        out, entering, inverse = kda_scan_forward(
            *operands, interpret=_interpret(), **shared,
            inverse=kept and jnp.asarray(kept["Inverse"][0]))
        return {"Out": [out], "Entering": [entering], "Inverse": [inverse]}
    if reason is None:
        out = kda_scan_kernels(*operands, interpret=_interpret(), **shared)
    else:
        out = kda_scan_chunked(*operands, **shared)
    return {"Out": [out]}


@op("kda_scan_grad", grad=NO_GRAD)
def _kda_scan_grad(ctx, op_, ins):
    """The gradients of kda_scan's seven inputs from Out's cotangent, the
    inverses and the entering states the forward kernel wrote: the
    backward kernel alone (pallas_kda.kda_scan_backward), no forward one.
    The desc of an op built without the two is the generic maker's
    (_kda_grad) and is lowered as such."""
    if "__fwd_type__" in op_.desc.attrs:
        return generic_grad_lower(ctx, op_, ins)
    from .pallas_attention import _interpret
    from .pallas_kda import kda_scan_backward

    operands, chunk, _, _, _ = _kda_operands(op_, ins)
    grads = kda_scan_backward(
        *operands, *(jnp.asarray(ins[s][0]) for s in _KDA_KEPT),
        jnp.asarray(ins["Out@GRAD"][0]), chunk=chunk,
        eps=op_.attr("epsilon", 1e-6), dtype=_compute_dtype(ctx),
        interpret=_interpret())
    slots = ("Q", "K", "V", "Gate", "ALog", "DtBias", "Beta")
    return {slot + "@GRAD": [g] for slot, g in zip(slots, grads)
            if slot + "@GRAD" in op_.desc.outputs}


# --- router ------------------------------------------------------------------

def _router_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is None or xv.shape is None:
        return
    shape = [xv.shape[0], op_.attr("top_k", 1)]
    set_out(op_, block, "TopkIdx", shape, "int32")
    set_out(op_, block, "TopkWeight", shape, "float32")


_SCORINGS = {"sigmoid": jax.nn.sigmoid,
             "softmax": functools.partial(jax.nn.softmax, axis=-1)}
# how the selection bias b enters the choice: added to a sigmoid's score;
# a softmax's probability is scaled by exp(b) (b added to the logit: the
# experts' order is the logits' whatever the softmax's temperature), and
# s * exp(0) is s itself, bit for bit
_BIASED = {"sigmoid": lambda s, b: s + b,
           "softmax": lambda s, b: s * jnp.exp(b)}


@op("moe_router", infer_shape=_router_infer, non_diff_inputs=("Bias",))
def _moe_router(ctx, op_, ins):
    """X [N, D], W [D, E], Bias [E] (a buffer: no gradient) -> TopkIdx
    [N, k] int32 and TopkWeight [N, k] float32. Scores s = sigmoid(X W)
    (`scoring` "sigmoid", the default: nemotron_h's and glm4_moe_lite's
    routers) or softmax(X W) over all E experts ("softmax": sdar_moe's),
    in float32 at full precision; the k experts with the largest s + Bias
    (sigmoid) or s * exp(Bias) (softmax) are chosen (the bias moves the
    choice only); their weights are `scaling` * s_i / (sum of the chosen
    s + `norm_epsilon`, default 1e-20; lfm2_moe publishes 1e-6) when
    `norm_topk_prob`, else `scaling` * s_i."""
    scoring = op_.attr("scoring", "sigmoid")
    assert scoring in _SCORINGS, scoring
    s = _SCORINGS[scoring](jnp.matmul(_f32(ins["X"][0]), _f32(ins["W"][0]),
                                      precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(
        _BIASED[scoring](s, lax.stop_gradient(_f32(ins["Bias"][0]))),
        op_.attr("top_k", 1))
    w = jnp.take_along_axis(s, idx, axis=-1)
    if op_.attr("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + op_.attr("norm_epsilon", 1e-20))
    return {"TopkIdx": [idx.astype(jnp.int32)],
            "TopkWeight": [w * op_.attr("scaling", 1.0)]}


def _balance_infer(op_, block):
    bias = in_var(op_, block, "Bias")
    if bias is not None and bias.shape is not None:
        set_out(op_, block, "BiasOut", list(bias.shape), bias.dtype)


@op("moe_balance_bias", infer_shape=_balance_infer, grad=NO_GRAD)
def _moe_balance_bias(ctx, op_, ins):
    """The balancing rule that needs no loss (arXiv:2408.15664, in its
    form proportional to the error, held to one `rate` a step): TopkIdx
    [N, k] (the step's choices over all E experts, held here or not) and
    Bias [E] -> BiasOut [E] = Bias + `rate` * clip((mean - count_e) /
    mean, -1, 1), count_e the (token, slot) pairs that chose expert e
    and mean = N k / E: an expert chosen too often is chosen less at the
    next step, and one at its share is left alone. BiasOut is Bias' own
    variable: the executor writes it back with the rest of the state,
    and no gradient passes; the op belongs behind the backward
    (models.balance_routers), whose router gradient reads Bias again.
    The counts are a compare-and-sum, as
    moe_experts' sizes are (a scatter-add of N k ones costs 0.2 ms a
    layer on a v5e: PR 38)."""
    idx, bias = ins["TopkIdx"][0], ins["Bias"][0]
    experts = bias.shape[0]
    count = (idx.reshape(-1, 1) == jnp.arange(experts)).sum(
        0, dtype=jnp.float32)
    mean = idx.size / experts
    step = op_.attr("rate", 0.0) * jnp.clip((mean - count) / mean, -1.0, 1.0)
    return {"BiasOut": [(_f32(bias) + step).astype(bias.dtype)]}


# --- dropless expert layer ---------------------------------------------------

_GMM_OP = "moe_experts"
_GMM_ROWS = 128


def gmm_ineligible(rows: int, d: int, f: int):
    """None when Pallas' grouped matmul (megablox gmm) takes [rows, d] x
    [held, d, f] and back, else the reason lax.ragged_dot keeps the
    product (kernel_choice.REASONS["moe_experts"]): `rows` = tokens x
    top_k must be a multiple of the row tile, d and f at least one lane
    block wide."""
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


# the gated form's activations, by the op's `gate_act`
_GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _grouped_dots(sizes, kernel, d, f, dtype):
    """The three grouped products over rows sorted by group, `sizes` rows
    each, in order -> (dot, dot_t, dot_w): dot(a, w)[p] = a[p] w[e of p]
    and dot_t(a, w)[p] = a[p] w[e of p]^T (rows past the sizes' sum come
    back undefined from both), dot_w(a, b)[e] = the sum over e's rows of
    a[p]^T b[p] (rows past the sum add nothing). `kernel`: None for
    lax.ragged_dot and its transposes, else megablox gmm and tgmm with
    `interpret=kernel` at _gmm_tiling(d, f, dtype), results in `dtype`."""
    if kernel is None:
        dot = functools.partial(lax.ragged_dot, group_sizes=sizes)
        by_group = lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
        return (dot, lambda a, w: dot(a, w.swapaxes(1, 2)),
                lambda a, b: lax.ragged_dot_general(a, b, sizes, by_group))
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
    tiling = _gmm_tiling(d, f, dtype)
    dot = functools.partial(
        megablox.gmm, group_sizes=sizes, preferred_element_type=dtype,
        tiling=tiling, interpret=kernel)
    return (dot, functools.partial(dot, transpose_rhs=True),
            lambda a, b: tgmm(a.swapaxes(0, 1), b, sizes, dtype, tiling,
                              None, sizes.shape[0], interpret=kernel))


def _hidden(up, gate_up=None, gate_act="silu"):
    """The experts' hidden rows, float32, from the up product's rows and
    the gate product's: relu(up)^2, or act(gate_up) * up, act = `gate_act`
    (silu, or relu: ReGLU)."""
    if gate_up is None:
        return jnp.square(jax.nn.relu(_f32(up)))
    return _GATE_ACTS[gate_act](_f32(gate_up)) * _f32(up)


def _grouped_products(rows, w1, w2, sizes, kernel, gate=None,
                      gate_act="silu"):
    """relu(rows W1[e])^2 W2[e] for the rows of each group e (`sizes` rows
    each, in order; rows past their sum come back undefined); with `gate`
    (act(rows Gate[e]) * (rows W1[e])) W2[e], the activation in float32
    (_hidden). `kernel` as _grouped_dots takes it. -> (the down product,
    (the up product,) or (the up product, the gate's): what the gradient
    reads in place of running either again)."""
    dot, _, _ = _grouped_dots(sizes, kernel, w1.shape[1], w1.shape[2],
                              rows.dtype)
    with jax.named_scope("moe_up"):
        kept = (dot(rows, w1),)
    if gate is not None:
        with jax.named_scope("moe_gate"):
            kept += (dot(rows, gate),)
    with jax.named_scope("moe_down"):
        return dot(_hidden(*kept, gate_act=gate_act).astype(rows.dtype),
                   w2), kept


def _experts_infer(op_, block):
    same_as_input()(op_, block)
    for slot in ("RowsRouted", "RowsCombined", "LoadMaxOverMean",
                 "RowsHandled"):
        set_out(op_, block, slot, [1], "float32")
    xv, wv, idx = (in_var(op_, block, s) for s in ("X", "W1", "TopkIdx"))
    if None in (xv, wv, idx) or None in (xv.shape, wv.shape, idx.shape):
        return
    pairs = xv.shape[0] * idx.shape[1] if xv.shape[0] >= 0 else -1
    for slot in _KEPT:      # the compute dtype is the lowering's to know
        set_out(op_, block, slot, [pairs, wv.shape[2]], xv.dtype)


def _capacity_ladder(pairs: int, held: int, num_experts: int):
    """The row counts the expert layer may handle, ascending, three at
    most: `pairs` = N x top_k last (every pair: nothing is ever dropped)
    and before it r2 and r4, each kept if it is at most a quarter of the
    pairs. r2 is the smallest halving of `pairs` that tiles as `pairs`
    does (so gmm_ineligible answers the same for every rung) and still
    holds twice the share a uniform router sends here, pairs * held /
    num_experts; r4 the same for four times the share. A layer that holds
    more than an eighth of the experts has the one rung and no
    conditional; an eighth, (pairs / 4, pairs): r4 is half the pairs and
    falls away; a sixteenth or less, (r2, r4, pairs), or (r4, pairs)
    where r2 would not tile and the halving stops at r4.

    The quarter is what the chip allowed (PERF.md section 6, PR 36):
    every rung is a branch of the forward and of the gradient to trace,
    lower and load, seconds of a cell's set-up each, so a rung before the
    last has to save more than half of the rows' handling when it is
    taken. Twice the share first (PR 69): routers that train under a
    balancing rule send the held experts within a few percent of the
    uniform share (their busiest single expert runs 1.14 to 1.20 times
    its mean), so every settled layer-step fits r2 and handles half of
    r4's rows, which is the case the quarter was written for; an eighth
    has taken twice the share since the gradient's branch reads the
    forward's products and runs none again (PERF.md section 6, PR 58:
    the last rung behind a switch cost a fifth more than the layer
    without one while it did). Four times the share stays behind it as
    the second rung: a router that has not settled (PR 36's ran two or
    three times off balance in their first steps; a step 0 under a
    balancing rule reads up to a tenth over r2) or that trains under no
    rule and drifts lands there, at what it cost before r2 was there,
    and not on the last rung at the price of the layer without a ladder.
    A conditional's buffers are its largest branch's, so the rungs before
    the last cost set-up (PR 69: 4 to 5 s of the head-decay delta-rule
    cell's 110) and tens of MB either way, no more."""
    def smallest_halving(times):
        rung = pairs
        while (rung % 2 == 0
               and rung // 2 * num_experts >= times * pairs * held
               and (rung // 2 % _GMM_ROWS == 0) == (pairs % _GMM_ROWS == 0)):
            rung //= 2
        return rung
    rungs = sorted({smallest_halving(2), smallest_halving(4), pairs})
    return tuple(r for r in rungs[:-1] if 4 * r <= pairs) + (pairs,)


# The two maps between the tokens' rows [N, D] and the C rows the grouped
# products see. Sorted row p is pair order[p]'s token's row, and a token
# gets back the sum of its top_k pairs' rows. `order` is a permutation of
# the N x top_k pairs and `pos` [N, top_k] its inverse, so every sorted
# row belongs to exactly one pair: each map is the other's transpose, and
# read through the other index both are gathers, forward and pulled back.
# Autodiff cannot know that and writes each transpose as a zero-fill of
# [N, D] and a scatter-add of C rows (1.25-1.34 ms each at 14 % of their
# bytes on a v5e, ten a step of the latent-attention cell, PR 38); the
# op's gradient (_pull_rows_back) is written by hand with both.

def _sum_of_pairs(rows, pos, live_rows, weight=None):
    """[C, D] -> [N, D] float32: sum over a token's top_k pairs of
    (weight[n, j] *) rows[pos[n, j]], a pair whose place in the order is
    at or past `live_rows` adding nothing (an absent expert's, or one past
    the rung: its place may lie past C, so it reads row 0 and is selected
    away, never multiplied). One gather of N rows a slot, added: the other
    way, one gather of [N, top_k, D], XLA writes, relays out for the
    reshape and reads back. The layer alone, forward + gradient on a v5e,
    a gather a slot | one gather | the scatter-add: 6.11 | 6.82 | 8.45 ms
    at [4096 x 4, 2048] with every pair handled, 3.15 | 6.27 | 3.74 at
    [4096 x 6, 2688] with 6144 handled (PERF.md section 6, PR 38). In a
    step's trace the forward's gathers, weights and sum are one pass only
    behind a shared expert, whose down product takes them into its
    epilogue (+0.03 ms in the latent-attention cell); without one, and
    pulled back everywhere, each slot's gather is a pass of its own that
    writes [N, D] in float32 and the add reads all top_k of them (537 MB
    at [8192, 2048], top 8). Since PR 47 this is the path of the shapes
    ops/pallas_pair_sum.py declines, and the statement its kernel is
    tested against (_pairs_summed chooses)."""
    total = 0
    for j in range(pos.shape[1]):
        ok = pos[:, j] < live_rows
        picked = rows.at[jnp.where(ok, pos[:, j], 0)].get(
            mode="promise_in_bounds")
        term = jnp.where(ok[:, None], _f32(picked), 0)
        total = total + (term if weight is None else term * weight[:, j, None])
    return total


_PAIR_SUM_OP = "pair_sum"


def _pairs_summed(rows, pos, windows, live_rows, weight=None,
                  out_dtype=jnp.float32):
    """_sum_of_pairs as `out_dtype`: on the kernel of
    ops/pallas_pair_sum.py where the op's gate took it (`windows` is then
    the sort's pair_windows), as written above where it declined (None).
    Forward (weighted, the down product's rows back to their tokens) and
    pulled back (the rows' cotangents summed into dX) take the one path:
    XLA fuses the gathers into no consumer when pulled back, and forward
    only behind a shared expert's down product, which a lowering cannot
    see."""
    if windows is None:
        return _sum_of_pairs(rows, pos, live_rows, weight).astype(out_dtype)
    from .pallas_attention import _interpret
    return pallas_pair_sum.pair_sum(rows, pos, live_rows, windows, weight,
                                    out_dtype=out_dtype,
                                    interpret=_interpret())


def _rows_of_tokens(x, token, live_rows, dtype):
    """x[token] as `dtype`, zero from `live_rows` on: [N, D] -> [C, D],
    the cast and the select in the gather's own pass."""
    live = (jnp.arange(token.shape[0]) < live_rows)[:, None]
    return jnp.where(live, x.at[token].get(
        mode="promise_in_bounds").astype(dtype), 0)


# the output slots that carry _grouped_products' kept results from the op
# to its gradient op, in that order (the second only when gated)
_KEPT = ("Up", "GateUp")


def _handle_rows(capacity, kernel, gate_act, sort, sizes, x, weight, w1, w2,
                 gate=None):
    """The layer over the first `capacity` pairs of the sorted order (the
    routed ones, which the caller knows to be no more, then dead ones):
    their tokens' rows gathered, the grouped products, and each token's
    top_k rows gathered back through the inverse of the sort, weighted
    and summed in float32. `sort` = (order, its inverse [N, top_k], the
    kernel's pair_windows or None: _pairs_summed).
    -> (Out, the pairs it combined, _grouped_products' kept results as
    [N x top_k, F], the rung's rows first: a switch's branches return one
    shape (_over_all_pairs)."""
    order, pos, windows = sort
    head = order[:capacity]
    live_rows = jnp.minimum(sizes.sum(), capacity)
    rows = _rows_of_tokens(x, head // weight.shape[1], live_rows, w1.dtype)
    out, kept = _grouped_products(rows, w1, w2, sizes, kernel, gate, gate_act)
    out = _pairs_summed(out, pos, windows, live_rows, weight)
    return (out.astype(x.dtype), (pos < live_rows).sum(),
            tuple(_over_all_pairs(a, order.shape[0], kernel) for a in kept))


def _over_all_pairs(rows, pairs, kernel):
    """[C, F] -> [pairs, F]: `rows` first, the rest never read (the
    gradient op slices the rung's rows back out). Beside the kernels the
    rest is not written either: the rows are put at the head of a buffer
    that a Pallas call with an empty body leaves as the allocator gave it,
    a pass over C rows; a fill with zeros is a write of pairs - C rows of
    each kept product a layer and step (2.8 ms a step in the Kimi-Linear
    cell, whose small rung is an eighth of the pairs: PERF.md section 6,
    PR 58). Without the kernels (`kernel` None) the rest is zero."""
    if rows.shape[0] == pairs:
        return rows
    if kernel is None:
        return jnp.pad(rows, ((0, pairs - rows.shape[0]), (0, 0)))
    from jax.experimental import pallas as pl
    from . import kernel_cost
    # an empty body: no work and no bytes, and the call says so
    unwritten = pl.pallas_call(
        lambda out: None, name="unwritten_rows", interpret=kernel,
        cost_estimate=kernel_cost.estimate(),
        out_shape=jax.ShapeDtypeStruct((pairs,) + rows.shape[1:], rows.dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY))()
    return lax.dynamic_update_slice(unwritten, rows, (0, 0))


def _pull_rows_back(capacity, kernel, gate_act, sort, sizes, ct, kept, x,
                    weight, w1, w2, gate=None):
    """_handle_rows pulled back over the same `capacity` pairs, from Out's
    cotangent `ct` [N, D] and the forward's `kept` products: no grouped
    product of the forward runs again. In sorted order one gather of the
    cotangent's rows serves every gradient: with g[p] = ct[token p] and
    t = g W2[e]^T (the down product pulled back per unit of weight),
    dWeight[pair p] = hidden[p] . t[p] (what y[p] . g[p] is, without y),
    dW2[e] = (weight * hidden)^T g over e's rows, and weight * t goes back
    through the activation (in float32) to the up product's rows and the
    gate's, whose transposes give dW1, dWGate and the rows' cotangent;
    dX[n] sums that over the token's live pairs (_pairs_summed). The
    tokens' rows are gathered again (a pass of the rung's size; kept, they
    would be [N x top_k, D] a layer). Rows from `live_rows` on hold what
    the kernels left there and reach no result: the products over groups
    skip them and both maps back select them away.
    -> (dX, dWeight, dW1, dW2[, dWGate]), the matrices' in their dtype."""
    order, pos, windows = sort
    head = order[:capacity]
    token = head // weight.shape[1]
    live_rows = jnp.minimum(sizes.sum(), capacity)
    _, dot_t, dot_w = _grouped_dots(sizes, kernel, w1.shape[1], w1.shape[2],
                                    w1.dtype)
    g = _rows_of_tokens(ct, token, live_rows, w1.dtype)
    by_row = weight.reshape(-1).at[head].get(
        mode="promise_in_bounds")[:, None]
    hidden, back = jax.vjp(functools.partial(_hidden, gate_act=gate_act),
                           *(a[:capacity] for a in kept))
    with jax.named_scope("moe_down"):
        t = _f32(dot_t(g, w2))
        d_w2 = dot_w((hidden * by_row).astype(w1.dtype), g)
    d_by_row = (hidden * t).sum(-1)
    d_kept = back(t * by_row)
    rows = _rows_of_tokens(x, token, live_rows, w1.dtype)
    with jax.named_scope("moe_up"):
        d_rows, d_w1 = dot_t(d_kept[0], w1), dot_w(rows, d_kept[0])
    d_mats = (d_w1, d_w2)
    if gate is not None:
        with jax.named_scope("moe_gate"):
            d_rows = d_rows + dot_t(d_kept[1], gate)
            d_mats += (dot_w(rows, d_kept[1]),)
    ok = pos < live_rows
    d_weight = jnp.where(ok, d_by_row.at[jnp.where(ok, pos, 0)].get(
        mode="promise_in_bounds"), 0)
    return (_pairs_summed(d_rows, pos, windows, live_rows,
                          out_dtype=x.dtype), d_weight) + d_mats


# One function object a (function, capacity, kernel, gate_act): jax keeps
# a switch branch's jaxpr by the function traced and its avals, so a
# model's expert layers trace a rung once (the hybrid cell's step holds 12
# such switches of one shape).
@functools.lru_cache(maxsize=None)
def _rung(handle, capacity, kernel, gate_act):
    return functools.partial(handle, capacity, kernel, gate_act)


def _on_the_rung(handle, r, *operands):
    """handle (_handle_rows or _pull_rows_back) at the capacity the step's
    routed pairs fit, r.rungs[r.rung]: one lax.switch branch a rung, and
    no conditional where the ladder has one. The matrices' casts to the
    compute dtype are operands of the switch, once, not work of each
    branch."""
    branches = [_rung(handle, c, r.kernel, r.gate_act) for c in r.rungs]
    operands = (r.sort, r.sizes) + operands + (r.x, r.weight) + tuple(
        m.astype(r.dtype) for m in r.mats)
    if len(branches) == 1:
        return branches[0](*operands)
    return lax.switch(r.rung, branches, *operands)


def _routed(ctx, op_, ins):
    """What moe_experts and its gradient op both make of the op's inputs
    (XLA merges the two where no replay's barrier stands between them):
    the pairs sorted by held expert, the ladder and the step's rung, and
    which kernels take the shapes."""
    from .pallas_attention import _interpret

    x = jnp.asarray(ins["X"][0])
    idx = jnp.asarray(ins["TopkIdx"][0])
    mats = [jnp.asarray(ins[slot][0]) for slot in ("W1", "W2", "WGate")
            if ins.get(slot) and ins[slot][0] is not None]
    held = op_.attr("experts_held", mats[0].shape[0])
    n, k = idx.shape
    assert mats[0].shape[0] == held and k == op_.attr("top_k", k)

    local = idx.reshape(-1) - op_.attr("expert_offset", 0)
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)           # held experts first
    sizes = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)

    reason = gmm_ineligible(n * k, x.shape[-1], mats[0].shape[-1])
    rungs = _capacity_ladder(n * k, held, op_.attr("num_experts", held))
    # the token side's kernel, if it takes every rung (_pairs_summed)
    declined = next(filter(None, (pallas_pair_sum.ineligible(
        n, c, x.shape[-1], held) for c in rungs)), None)
    return types.SimpleNamespace(
        x=x, weight=_f32(ins["TopkWeight"][0]), mats=mats,
        dtype=_compute_dtype(ctx), gate_act=op_.attr("gate_act", "silu"),
        held=held, group=group, sizes=sizes, reason=reason, declined=declined,
        kernel=_interpret() if reason is None else None, rungs=rungs,
        rung=(sizes.sum() > jnp.asarray(rungs[:-1], jnp.int32)).sum(),
        sort=(order, jnp.argsort(order).astype(jnp.int32).reshape(n, k),
              None if declined else pallas_pair_sum.pair_windows(
                  group, held, k)))


def _experts_grad(fwd, no_grad_set):
    """moe_experts_grad reads the op's inputs, Out's cotangent and the
    products the forward kept (_KEPT), as nn_ops._sdpa_grad reads LSE:
    the generic maker's op would trace the forward again inside the
    gradient op, and behind a conditional XLA merges none of it with the
    original."""
    wanted = [s for s in ("X", "TopkWeight", "W1", "W2", "WGate")
              if fwd.inputs.get(s) and fwd.input(s)[0] not in no_grad_set]
    if not wanted:
        return []
    kept = _KEPT if fwd.inputs.get("WGate") else _KEPT[:1]
    missing = [s for s in kept if not fwd.outputs.get(s)]
    if missing:
        raise ValueError(
            f"moe_experts (Out {fwd.output('Out')}): its gradient reads the "
            f"output slots {missing}, which the op was built without")
    return [OpDesc(
        type=fwd.type + "_grad",
        inputs={**{s: list(names) for s, names in fwd.inputs.items()},
                **{s: fwd.output(s) for s in kept},
                "Out@GRAD": [grad_var_name(fwd.output("Out")[0])]},
        outputs={s + "@GRAD": [grad_var_name(fwd.input(s)[0])]
                 for s in wanted},
        attrs=dict(fwd.attrs))]


@op("moe_experts", infer_shape=_experts_infer, grad=_experts_grad,
    non_diff_inputs=("TopkIdx",))
def _moe_experts(ctx, op_, ins):
    """The routed experts' part of a mixture-of-experts layer, for the
    `experts_held` experts from `expert_offset` on of `num_experts`:
    Out[n] = sum over the chosen (i of top_k) with TopkIdx[n, i] held of
    TopkWeight[n, i] * f_e(X[n]), f_e(x) = relu(x W1[e])^2 W2[e], or with
    the optional third matrix WGate [held, D, F] the gated form f_e(x) =
    (act(x WGate[e]) * (x W1[e])) W2[e], act the attr `gate_act` ("silu",
    the default, or "relu"). What the experts held elsewhere
    would add is left out; with experts_held = num_experts this is the
    whole routed layer.

    No token is dropped and every shape is static: the N x top_k (token,
    slot) pairs are sorted by held expert, pairs of absent experts last,
    the tokens' rows gathered in that order, and one grouped product runs
    over the rows of the held experts alone (Pallas' megablox gmm visits
    only the row tiles its group sizes cover; lax.ragged_dot where the
    shape does not tile, booked with the reason). The rows then return to
    their tokens by a second gather, through the inverse of the sort: a
    token reads the rows of its top_k pairs, weights and adds them in
    float32. The gradient op gathers both ways too (_pull_rows_back): no
    [N, D] is zero-filled and nothing is scatter-added, forward or
    backward.

    The rows gathered, multiplied and gathered back are the first C pairs
    of that order, C the smallest rung of _capacity_ladder that holds the
    step's routed pairs, chosen on the device (lax.switch, one branch a
    rung, in the gradient op too): twice the share a uniform router
    sends here, or, a sixteenth of the experts held or less, four times
    it, where a router that has not settled lands; the last rung is all
    N x top_k, so no step drops a row, and a layer that holds more than
    an eighth of the experts has that rung alone and no conditional.

    Up, and GateUp of the gated form [N x top_k, F] in the compute dtype:
    the up product's rows and the gate's in sorted order, undefined past
    the rung taken, which the gradient op reads where it would run the
    forward's products again (dear to compute, F wide to hold; a replayed
    segment's forward hands them on, and the first forward's are dead).
    RowsRouted [1]: the pairs the router sent to held experts, counted
    on its indices; RowsCombined [1]: the pairs whose rows the grouped
    product was given and their tokens read back, counted where they are
    combined (the two differ only if a row is lost between them);
    LoadMaxOverMean [1]: the busiest held expert's rows over the held
    experts' mean; RowsHandled [1]: the rung taken."""
    r = _routed(ctx, op_, ins)
    kernel_choice.book(_GMM_OP, r.reason)
    kernel_choice.book(_PAIR_SUM_OP, r.declined)
    out, combined, kept = _on_the_rung(_handle_rows, r)
    routed = r.sizes.sum()
    load = r.sizes.max() / jnp.maximum(routed / r.held, 1.0)
    return {"Out": [out],
            **{slot: [a] for slot, a in zip(_KEPT, kept)},
            "RowsRouted": [_f32((r.group < r.held).sum()).reshape(1)],
            "RowsCombined": [_f32(combined).reshape(1)],
            "LoadMaxOverMean": [_f32(load).reshape(1)],
            "RowsHandled": [_f32(jnp.asarray(r.rungs)[r.rung]).reshape(1)]}


@op("moe_experts_grad", grad=NO_GRAD)
def _moe_experts_grad(ctx, op_, ins):
    """dX, dTopkWeight and the matrices' gradients from Out's cotangent
    and the forward's kept products, on the rung the forward took (the
    same routed count picks it): _pull_rows_back, which runs the pulled-
    back grouped products and no forward one."""
    r = _routed(ctx, op_, ins)
    kept = tuple(jnp.asarray(ins[slot][0])      # one a matrix but W2
                 for slot in _KEPT[:len(r.mats) - 1])
    d_x, d_weight, *d_mats = _on_the_rung(
        _pull_rows_back, r, jnp.asarray(ins["Out@GRAD"][0]), kept)
    # barrier: the matrices' gradients leave the switch in the compute
    # dtype and wait for the optimizer at the step's end. Unpinned, XLA
    # moves their casts to the parameters' float32 into every branch, and
    # five gated layers then hold 1.5 GB of float32 gradients where the
    # layer without a switch held half
    d_mats = lax.optimization_barrier(d_mats)
    grads = {"X": d_x, "TopkWeight": d_weight.astype(
        jnp.asarray(ins["TopkWeight"][0]).dtype)}
    for slot, m, d in zip(("W1", "W2", "WGate"), r.mats, d_mats):
        grads[slot] = d.astype(m.dtype)
    return {slot + "@GRAD": [g] for slot, g in grads.items()
            if slot + "@GRAD" in op_.desc.outputs}
