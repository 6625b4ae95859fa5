"""Ops of manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606): the residual path of a decoder as n
streams a token, X [B, T, n, C]. Every sublayer F reads a learned mix of
the streams and writes back through three maps made from the token's own
state (layers.hyper_connection_maps builds the four ops in this order;
models/mla_moe.py builds decoders from them):

    hyper_connection_maps   u = vec(X) / sqrt(mean(vec(X)^2) + epsilon)
                            [p | q | r] = u Phi           widths n, n, n^2
                            Pre  = sigmoid(alpha_pre p + b_pre)
                            Post = 2 sigmoid(alpha_post q + b_post)
                            ResLogits = alpha_res mat(r) + b_res
    sinkhorn_knopp          M_0 = exp(clamp(ResLogits)); `iters` sweeps,
                            each the columns over (their sums + eps) and
                            then the rows likewise: a doubly stochastic
                            [n, n] map a token
    hc_pre_mix              x_in = sum_j Pre[j] X[j]
    hc_post_res_mix         X'[i] = sum_j Res[i, j] X[j] + Post[i] y

Precision under AMP: the streams, x_in and y are in the activations'
dtype (bf16 under O2); the norm's statistics, the three maps, the sweeps
and both mixes' sums are float32. The projection's operands are the
streams as they arrive and Phi in their dtype, accumulated in float32.
Every stream-sized operand is read behind a barrier (_as_stored), so that
what crosses HBM between two ops is the bf16 array the program names and
not a float32 copy XLA keeps for the reader's sake.

Layout: a token's maps are n or n^2 numbers. The mixes read them entry
by entry, each entry an array over the tokens; the sweeps run on
[n, n, tokens], the tokens and not the 4 or 16 entries along the lanes,
and a sum over columns or rows is a sum over a leading axis: n - 1 adds
of whole vectors. (Written entry by entry with no reduction at all, the
forty half-sweeps are one elementwise graph whose every node has four
readers: XLA fuses none of it, 450 kernels a forward on the described
v5e, and its CPU backend does not finish compiling it.)

Gradients: all four have explicit gradient ops that read the forward
op's inputs and the cotangents alone. sinkhorn_knopp_grad runs the sweeps
again from M_0 under jax.vjp and keeps nothing from the forward; the
mixes' and the maps' are written out (each cotangent one pass over X and
the incoming cotangent; X's cotangent is written once in X's dtype,
where the maps' generic gradient wrote three float32 arrays of X's size
a sublayer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.desc import OpDesc
from ..framework.framework import grad_var_name
from .common import in_var, set_out
from .registry import NO_GRAD, op

__all__ = ["hyper_connection_maps_reference", "sinkhorn_knopp_reference",
           "hc_pre_mix_reference", "hc_post_res_mix_reference"]


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _activation_dtype(ctx, like):
    """What a mix writes: under AMP O2 / O3 the activations' dtype (the
    embedding is a float32 master table's rows, so X_0 arrives float32
    and the first write-back is where the streams become bf16), else
    `like`'s own."""
    amp = getattr(ctx, "amp_dtype", None)
    if amp and getattr(ctx, "amp_level", "O1") in ("O2", "O3") \
            and like.dtype == jnp.float32:
        return jnp.dtype(amp)
    return like.dtype


def _as_stored(x):
    """A stream-sized operand as its producer stored it. Every lowering
    here reads a bf16 operand in float32, and where the producer's last
    step was the rounding to bf16 XLA drops the pair (its
    `xla_allow_excess_precision`) and has the producer write the float32
    array beside or in place of the bf16 one: 235 MB for 117 a [4096, 4,
    3584] state, on every edge of the residual path (the first chipless
    compile of the cell: `add_convert_fusion` writing (bf16, f32) pairs
    and the cotangents' sum kept in float32 between sublayers). Behind a
    barrier the operand is what the program says it is. A float32
    operand (no AMP) passes untouched."""
    x = jnp.asarray(x)
    return x if x.dtype == jnp.float32 else lax.optimization_barrier(x)


def _matrix(rows):
    """n rows of n arrays [...], an entry each -> [..., n, n]."""
    return jnp.stack([jnp.stack(row, axis=-1) for row in rows], axis=-2)


def _streams(x):
    """X [..., n, C] -> its n streams [..., C] in float32."""
    return [_f32(x[..., j, :]) for j in range(x.shape[-2])]


def _explicit_grad(slots, outs=("Out",)):
    """The maker of an explicit `<type>_grad` that reads the op's inputs
    `slots` and the cotangents of its outputs `outs`, nothing the forward
    made, and writes the cotangent of each input that wants one."""
    def maker(fwd, no_grad_set):
        wanted = [s for s in slots if fwd.input(s)[0] not in no_grad_set]
        if not wanted:
            return []
        return [OpDesc(
            type=fwd.type + "_grad",
            inputs={**{s: list(fwd.input(s)) for s in slots},
                    **{s + "@GRAD": [grad_var_name(fwd.output(s)[0])]
                       for s in outs}},
            outputs={s + "@GRAD": [grad_var_name(fwd.input(s)[0])]
                     for s in wanted},
            attrs=dict(fwd.attrs))]
    return maker


# --- the three maps ----------------------------------------------------------

def _projected(x, phi, epsilon):
    """(vec(X) Phi [B, T, 2 n + n^2], the token's 1 / rms [B, T, 1]),
    float32, and (vec(X), Phi) as the product read them: the streams as
    they arrive and Phi in their dtype, the MXU's operands under AMP,
    summed in float32. The norm has no weight (the paper folds it into
    Phi), so u Phi is the first times the second: the projection reads X
    once as it is."""
    b, t, n, c = x.shape
    flat = x.reshape(b, t, n * c)
    phi = jnp.asarray(phi)
    if flat.dtype != jnp.float32:
        phi = phi.astype(flat.dtype)
    proj = jnp.einsum("btk,km->btm", flat, phi,
                      preferred_element_type=jnp.float32)
    over_rms = lax.rsqrt(jnp.mean(jnp.square(_f32(flat)), axis=-1,
                                  keepdims=True) + epsilon)
    return proj, over_rms, flat, phi


def _maps_of(z, bias, alpha, n):
    """The three maps from z = u Phi [B, T, 2 n + n^2], all float32."""
    alpha, bias = _f32(alpha), _f32(bias)
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    logits = alpha[2] * z[..., 2 * n:] + bias[2 * n:]
    return pre, post, logits.reshape(z.shape[:-1] + (n, n))


def hyper_connection_maps_reference(x, phi, bias, alpha, epsilon):
    """(Pre [B, T, n], Post [B, T, n], ResLogits [B, T, n, n]), float32,
    of the streams x [B, T, n, C], Phi [n C, 2 n + n^2], Bias [2 n + n^2]
    and Alpha [3]: the module docstring's first block."""
    proj, over_rms, _, _ = _projected(x, phi, epsilon)
    return _maps_of(proj * over_rms, bias, alpha, x.shape[2])


def _maps_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is None or x.shape is None:
        return
    lead, n = list(x.shape[:-2]), x.shape[-2]
    set_out(op_, block, "Pre", lead + [n], "float32")
    set_out(op_, block, "Post", lead + [n], "float32")
    set_out(op_, block, "ResLogits", lead + [n, n], "float32")


_MAPS_SLOTS, _MAPS_OUTS = ("X", "Phi", "Bias", "Alpha"), (
    "Pre", "Post", "ResLogits")


@op("hyper_connection_maps", infer_shape=_maps_infer,
    grad=_explicit_grad(_MAPS_SLOTS, _MAPS_OUTS))
def _hyper_connection_maps(ctx, op_, ins):
    """X [B, T, n, C], Phi [n C, 2 n + n^2], Bias [2 n + n^2], Alpha [3]
    -> Pre, Post [B, T, n] and ResLogits [B, T, n, n], float32 whatever X
    is (hyper_connection_maps_reference)."""
    pre, post, logits = hyper_connection_maps_reference(
        _as_stored(ins["X"][0]), ins["Phi"][0], ins["Bias"][0],
        ins["Alpha"][0], op_.attr("epsilon", 1e-6))
    return {"Pre": [pre], "Post": [post], "ResLogits": [logits]}


@op("hyper_connection_maps_grad", grad=NO_GRAD)
def _hyper_connection_maps_grad(ctx, op_, ins):
    """The cotangents of X (its dtype), Phi, Bias and Alpha from the op's
    inputs and the maps' cotangents (one that did not arrive is zeros).
    With P = vec(X) Phi, r = 1 / rms and dz the cotangent of z = r P
    (pulled back through the three small maps by jax.vjp):

        dvec(X) = (r dz) Phi^T - (sum_m dz_m P_m) r^3 / (n C) vec(X)
        dPhi    = vec(X)^T (r dz)

    The first product's operands are r dz and Phi in X's dtype, summed in
    float32 and written in X's dtype; the norm's term is elementwise and
    fuses with the sum of X's cotangents behind the op: no float32 array
    of X's size is written (the generic gradient wrote three)."""
    x = _as_stored(ins["X"][0])
    n, width = x.shape[2], x.shape[2] * x.shape[3]
    bias, alpha = (jnp.asarray(ins[s][0]) for s in ("Bias", "Alpha"))
    proj, over_rms, flat, phi = _projected(x, ins["Phi"][0],
                                           op_.attr("epsilon", 1e-6))
    maps, pull = jax.vjp(lambda z, b, a: _maps_of(z, b, a, n),
                         proj * over_rms, bias, alpha)
    arrived = [ins.get(s + "@GRAD") or [None] for s in _MAPS_OUTS]
    d_z, d_bias, d_alpha = pull(tuple(
        jnp.zeros_like(m) if got[0] is None else _f32(got[0]).reshape(m.shape)
        for m, got in zip(maps, arrived)))
    d_proj = (d_z * over_rms).astype(flat.dtype)
    norm_term = jnp.sum(d_z * proj, axis=-1, keepdims=True) \
        * over_rms ** 3 / width
    # the product leaves the MXU in X's dtype (summed in float32 inside):
    # left in float32 it is written whole, 235 MB a sublayer, ahead of
    # the elementwise pass that adds the norm's term and X's other two
    # cotangents
    d_flat = jnp.einsum("btm,km->btk", d_proj, phi,
                        preferred_element_type=flat.dtype) \
        - (norm_term * _f32(flat)).astype(flat.dtype)
    d_phi = jnp.einsum("btk,btm->km", flat, d_proj,
                       preferred_element_type=jnp.float32)
    return _wanted(op_, {
        "X": d_flat.astype(x.dtype).reshape(x.shape),
        "Phi": d_phi.astype(jnp.asarray(ins["Phi"][0]).dtype),
        "Bias": d_bias.astype(bias.dtype),
        "Alpha": d_alpha.astype(alpha.dtype)})


# --- Sinkhorn-Knopp ----------------------------------------------------------

def _swept(logits, iters, eps, clamp):
    """[n, n, tokens] float32: `iters` sweeps from M_0 = exp(clamp(logits
    [..., n, n])), every sweep the columns over (their sums + eps), then
    the rows likewise (arXiv:2512.24880 eq. 9); a division is one
    reciprocal a column or row."""
    n = logits.shape[-1]
    m = jnp.exp(jnp.clip(_f32(logits), clamp[0], clamp[1]))
    m = m.reshape(-1, n, n).transpose(1, 2, 0)
    for _ in range(iters):
        m = m * (1.0 / (m.sum(0, keepdims=True) + eps))
        m = m * (1.0 / (m.sum(1, keepdims=True) + eps))
    return m


def sinkhorn_knopp_reference(logits, iters, eps, clamp):
    """H [..., n, n] float32 = `iters` Sinkhorn-Knopp sweeps from M_0 =
    exp(clamp(logits)): near doubly stochastic (rows exactly, up to eps;
    columns as far as the sweeps have converged)."""
    return _swept(logits, iters, eps, clamp).transpose(2, 0, 1).reshape(
        logits.shape)


def _sinkhorn_attrs(op_):
    return (int(op_.attr("iters", 20)), float(op_.attr("eps", 1e-6)),
            (float(op_.attr("clamp_min", -30.0)),
             float(op_.attr("clamp_max", 30.0))))


def _sinkhorn_infer(op_, block):
    x = in_var(op_, block, "Logits")
    if x is not None:
        set_out(op_, block, "Out", x.shape, "float32")
    for slot in ("SumError", "DiagonalMass"):
        set_out(op_, block, slot, [1], "float32")


@op("sinkhorn_knopp", infer_shape=_sinkhorn_infer,
    grad=_explicit_grad(("Logits",)))
def _sinkhorn_knopp(ctx, op_, ins):
    """Logits [..., n, n] -> Out [..., n, n] float32
    (sinkhorn_knopp_reference; attributes `iters`, `eps`, `clamp_min`,
    `clamp_max`), and two float32 scalars of the result for the model's
    gauges: SumError [1], the largest |row sum - 1| or |column sum - 1|
    of any token's map, and DiagonalMass [1], the mean of trace(H) / n.
    A first lowering books hyper_connection_sublayers_total{program}, a
    replayed segment's hyper_connection_replays_total{program} (a kept
    segment's is not lowered): their ratio is the share of the sublayers
    that run twice."""
    from .. import telemetry
    from ..backward import RECOMPUTE_ATTR

    iters, eps, clamp = _sinkhorn_attrs(op_)
    logits = jnp.asarray(ins["Logits"][0])
    m = _swept(logits, iters, eps, clamp)
    n = m.shape[0]
    error = jnp.maximum(jnp.max(jnp.abs(m.sum(0) - 1.0)),
                        jnp.max(jnp.abs(m.sum(1) - 1.0)))
    mass = jnp.mean(sum(m[i, i] for i in range(n))) / n
    if RECOMPUTE_ATTR in op_.desc.attrs:
        booked = telemetry.counter(
            "hyper_connection_replays_total",
            "sublayers behind hyper-connections whose forward the backward "
            "runs again (a replayed sinkhorn_knopp op each), a trace",
            labels=("program",))
    else:
        booked = telemetry.counter(
            "hyper_connection_sublayers_total",
            "sublayers behind hyper-connections (a sinkhorn_knopp op each), "
            "a trace", labels=("program",))
    booked.labels(program=telemetry.program_label(ctx.program)).inc()
    return {"Out": [m.transpose(2, 0, 1).reshape(logits.shape)],
            "SumError": [error.reshape(1)],
            "DiagonalMass": [mass.reshape(1)]}


@op("sinkhorn_knopp_grad", grad=NO_GRAD)
def _sinkhorn_knopp_grad(ctx, op_, ins):
    """Logits' cotangent through the sweeps, run again from M_0."""
    iters, eps, clamp = _sinkhorn_attrs(op_)
    logits = jnp.asarray(ins["Logits"][0])
    _, pull = jax.vjp(
        lambda x: sinkhorn_knopp_reference(x, iters, eps, clamp), logits)
    grad, = pull(_f32(ins["Out@GRAD"][0]))
    return {"Logits@GRAD": [grad.astype(logits.dtype)]}


# --- the two mixes -----------------------------------------------------------

def hc_pre_mix_reference(x, pre, dtype=None):
    """x_in [..., C] = sum_j pre[..., j] x[..., j, :], summed in float32,
    in `dtype` (default x's)."""
    pre = _f32(pre)
    return sum(pre[..., j, None] * stream
               for j, stream in enumerate(_streams(x))).astype(
                   dtype or x.dtype)


def hc_post_res_mix_reference(x, y, res, post, dtype=None):
    """X' [..., n, C]: X'[i] = sum_j res[..., i, j] x[..., j, :] +
    post[..., i] y, summed in float32, in `dtype` (default x's)."""
    res, post, y32 = _f32(res), _f32(post), _f32(y)
    streams = _streams(x)
    return jnp.stack(
        [sum(res[..., i, j, None] * stream
             for j, stream in enumerate(streams)) + post[..., i, None] * y32
         for i in range(len(streams))], axis=-2).astype(dtype or x.dtype)


def _wanted(op_, grads):
    return {slot + "@GRAD": [value] for slot, value in grads.items()
            if slot + "@GRAD" in op_.desc.outputs}


def _pre_mix_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is not None and x.shape is not None:
        set_out(op_, block, "Out", list(x.shape[:-2]) + [x.shape[-1]],
                x.dtype)


@op("hc_pre_mix", infer_shape=_pre_mix_infer, grad=_explicit_grad(("X", "Pre")))
def _hc_pre_mix(ctx, op_, ins):
    """X [B, T, n, C], Pre [B, T, n] -> Out [B, T, C]
    (hc_pre_mix_reference): one read of X, one write of x_in."""
    x = _as_stored(ins["X"][0])
    return {"Out": [hc_pre_mix_reference(x, ins["Pre"][0],
                                         _activation_dtype(ctx, x))]}


@op("hc_pre_mix_grad", grad=NO_GRAD)
def _hc_pre_mix_grad(ctx, op_, ins):
    """dX[j] = Pre[j] dOut (X's dtype); dPre[j] = sum_c X[j, c] dOut[c]
    (float32)."""
    x, pre = _as_stored(ins["X"][0]), _f32(ins["Pre"][0])
    d_out = _f32(_as_stored(ins["Out@GRAD"][0]))
    n = x.shape[-2]
    return _wanted(op_, {
        "X": jnp.stack([pre[..., j, None] * d_out for j in range(n)],
                       axis=-2).astype(x.dtype),
        "Pre": jnp.stack([jnp.sum(stream * d_out, axis=-1)
                          for stream in _streams(x)],
                         axis=-1).astype(jnp.asarray(ins["Pre"][0]).dtype)})


def _post_res_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is not None:
        set_out(op_, block, "Out", x.shape, x.dtype)


_POST_RES_SLOTS = ("X", "Y", "Res", "Post")


@op("hc_post_res_mix", infer_shape=_post_res_infer,
    grad=_explicit_grad(_POST_RES_SLOTS))
def _hc_post_res_mix(ctx, op_, ins):
    """X [B, T, n, C], Y [B, T, C], Res [B, T, n, n], Post [B, T, n] ->
    Out [B, T, n, C] (hc_post_res_mix_reference): one read of X and of
    Y, one write of X'."""
    x = _as_stored(ins["X"][0])
    return {"Out": [hc_post_res_mix_reference(
        x, _as_stored(ins["Y"][0]), ins["Res"][0], ins["Post"][0],
        _activation_dtype(ctx, x))]}


@op("hc_post_res_mix_grad", grad=NO_GRAD)
def _hc_post_res_mix_grad(ctx, op_, ins):
    """With G = Out's cotangent [B, T, n, C]: dX[j] = sum_i Res[i, j]
    G[i], dY = sum_i Post[i] G[i] (the dtypes of X and Y); dRes[i, j] =
    sum_c G[i, c] X[j, c], dPost[i] = sum_c G[i, c] Y[c] (float32)."""
    x, y = _as_stored(ins["X"][0]), _as_stored(ins["Y"][0])
    res, post = (jnp.asarray(ins[s][0]) for s in ("Res", "Post"))
    res32, post32, y32 = _f32(res), _f32(post), _f32(y)
    streams, cots = _streams(x), _streams(_as_stored(ins["Out@GRAD"][0]))
    n = len(streams)
    return _wanted(op_, {
        "X": jnp.stack([sum(res32[..., i, j, None] * cots[i]
                            for i in range(n)) for j in range(n)],
                       axis=-2).astype(x.dtype),
        "Y": sum(post32[..., i, None] * cots[i]
                 for i in range(n)).astype(y.dtype),
        "Res": _matrix([[jnp.sum(cots[i] * streams[j], axis=-1)
                         for j in range(n)]
                        for i in range(n)]).astype(res.dtype),
        "Post": jnp.stack([jnp.sum(cot * y32, axis=-1) for cot in cots],
                          axis=-1).astype(post.dtype)})
