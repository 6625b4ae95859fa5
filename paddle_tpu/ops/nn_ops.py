"""NN ops: conv/pool/norm/softmax/losses/embedding/dropout/metrics.

TPU-native lowerings of the reference ops (conv_op.cc + conv_cudnn_op.cu.cc,
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, lookup_table_op.cc,
dropout_op.cc, lrn_op.cc, accuracy_op.cc, auc_op.cc, loss ops…). Layout is
NCHW to match the reference's user-visible semantics; XLA relayouts for the
MXU internally, so no data_layout_transform pass is needed (reference
framework/data_layout_transform.cc becomes a compiler concern).

The hard-label softmax_with_cross_entropy carries its own gradient rule
(`_hard_label_nll`, a jax.custom_vjp inside the lowering, so the generic
gradient op differentiates through it and XLA merges the re-traced
forward). Left to autodiff, `log_softmax` + `take_along_axis` on bf16
logits made the whole GPT-2 step keep a float32 copy of the
[16384, 50257] logits from the loss to the head's gradient products and
write the log-probabilities out for a 16384-entry gather: 3.3 GB written
and read twice, 17 ms of a 169.6 ms step, though head and loss alone
fuse well (PERF.md section 6, PR 31). The rule reads log-sum-exp and the
label's logit from the logits as they arrive, in one reduce, and gives
the gradient in their dtype. Its one `optimization_barrier` pins the
residual: without it XLA shares the backward's convert with the
forward's and hoists it into the head's relayout copy, which brings the
float32 buffer back. The gradient is left unpinned on purpose: XLA
recomputes it where it is consumed, which measured faster than writing
it out once (same section).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.desc import OpDesc
from ..framework.framework import grad_var_name
from . import kernel_choice
from .registry import (NO_GRAD, handed_on, infer_grad_shapes, op,
                       register)
from .common import (SelectedRowsVal, in_var, mxu_cast, out_var,
                     same_as_input, set_out, to_np_dtype)


# --- softmax ----------------------------------------------------------------

@op("softmax", infer_shape=same_as_input())
def _softmax(ctx, op_, ins):
    return {"Out": [jax.nn.softmax(jnp.asarray(ins["X"][0]), axis=-1)]}


def _ce_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None and xv.shape is not None:
        set_out(op_, block, "Y", list(xv.shape[:-1]) + [1], xv.dtype)


@op("cross_entropy", infer_shape=_ce_infer, non_diff_inputs=("Label",))
def _cross_entropy(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    label = jnp.asarray(ins["Label"][0])
    if op_.attr("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.clip(x, 1e-12, None)),
                        axis=-1, keepdims=True)
    else:
        idx = label.reshape(label.shape[0], -1)[:, :1].astype(jnp.int32)
        picked = jnp.take_along_axis(x, idx, axis=-1)
        loss = -jnp.log(jnp.clip(picked, 1e-12, None))
    return {"Y": [loss]}


def _swce_infer(op_, block):
    xv = in_var(op_, block, "Logits")
    if xv is not None and xv.shape is not None:
        set_out(op_, block, "Softmax", xv.shape, xv.dtype)
        set_out(op_, block, "Loss", list(xv.shape[:-1]) + [1], xv.dtype)


def _hit(logits, idx):
    """Where the last axis' position is the row's label: the one-hot as
    an iota compare, which a reduce or an elementwise fusion takes in
    without an array of the logits' size (and which GSPMD partitions
    where a gather over a split vocabulary would not)."""
    return jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == idx


def _nll_and_lse(logits, idx):
    """(lse - logits[label], lse), both [..., 1] float32, read from the
    logits in the dtype they arrive in: each convert sits inside the
    reduction that consumes it."""
    l32 = logits.astype(jnp.float32)
    # the max of bf16 values is exact in bf16
    m = jnp.max(logits, axis=-1, keepdims=True).astype(jnp.float32)
    lse = m + jnp.log(jnp.sum(jnp.exp(l32 - m), axis=-1, keepdims=True))
    picked = jnp.sum(jnp.where(_hit(logits, idx), l32, 0.0), axis=-1,
                     keepdims=True)
    return lse - picked, lse


@jax.custom_vjp
def _hard_label_nll(logits, idx):
    """Hard-label cross-entropy and the rows' log-sum-exp; `idx` is the
    int32 label with a trailing axis of 1. The rule is the op's own
    (module docstring): nothing of the logits' size exists in float32,
    forward or backward."""
    return _nll_and_lse(logits, idx)


def _hard_label_nll_fwd(logits, idx):
    loss, lse = _nll_and_lse(logits, idx)
    # barrier: the residual is the logits buffer the head's matmul wrote
    # (bf16 under AMP). Unpinned, XLA merges the backward's convert with
    # the forward's and hoists it into the relayout copy, and that one
    # float32 copy then lives from the loss to the head's gradient matmuls
    return (loss, lse), (jax.lax.optimization_barrier(logits), idx, lse)


def _hard_label_nll_bwd(res, cts):
    logits, idx, lse = res
    g_loss, g_lse = cts
    # d lse / d logits is the softmax, so a cotangent on lse (the Softmax
    # output's, when something reads it) scales the same exponential
    p = jnp.exp(logits.astype(jnp.float32) - lse)
    d = p * (g_loss + g_lse) - jnp.where(_hit(logits, idx), g_loss, 0.0)
    # no barrier here: XLA recomputes this in the prologues of the head's
    # two gradient products and of its bias sum, one read of the logits
    # each; pinned, it is written and read back (2.1 % of GPT-2's step)
    return d.astype(logits.dtype), None


_hard_label_nll.defvjp(_hard_label_nll_fwd, _hard_label_nll_bwd)


@op("softmax_with_cross_entropy", infer_shape=_swce_infer,
    non_diff_inputs=("Label",))
def _softmax_with_cross_entropy(ctx, op_, ins):
    logits = jnp.asarray(ins["Logits"][0])
    label = jnp.asarray(ins["Label"][0])
    if op_.attr("soft_label", False):
        # a soft label weighs the whole row: the dense path. logsumexp in
        # f32 for stability with bf16 logits (AMP O2); the astype is
        # inside the trace so its vjp casts the cotangent back to bf16
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
        softmax = jnp.exp(logp)
    else:
        idx = label.astype(jnp.int32)
        if idx.ndim < logits.ndim:
            idx = idx[..., None]
        elif idx.shape[-1] != 1:
            idx = idx[..., :1]
        loss, lse = _hard_label_nll(logits, idx)
        # outside the rule: a step that reads no Softmax drops it; one
        # that does differentiates it through the logits and through lse
        softmax = jnp.exp(logits.astype(jnp.float32) - lse)
    # padded sequence logits [B,T,V]: zero the padded positions' losses
    lengths = ctx.seq_len(op_.desc.inputs["Logits"][0])
    if lengths is not None and logits.ndim >= 3:
        t = logits.shape[1]
        mask = (jnp.arange(t)[None, :] <
                jnp.asarray(lengths)[:, None]).astype(loss.dtype)
        loss = loss * mask.reshape(mask.shape + (1,) * (loss.ndim - 2))
    return {"Softmax": [softmax], "Loss": [loss]}


@op("sigmoid_cross_entropy_with_logits", infer_shape=same_as_input(),
    non_diff_inputs=("Label",))
def _sigmoid_ce(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    label = jnp.asarray(ins["Label"][0])
    # max(x,0) - x*z + log(1+exp(-|x|)) — stable form
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": [loss]}


# --- simple losses ----------------------------------------------------------

@op("smooth_l1_loss", non_diff_inputs=("Y",))
def _smooth_l1(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    sigma2 = op_.attr("sigma", 1.0) ** 2
    d = x - y
    if ins.get("InsideWeight") and ins["InsideWeight"][0] is not None:
        d = d * jnp.asarray(ins["InsideWeight"][0])
    ad = jnp.abs(d)
    diff = jnp.where(ad < 1.0 / sigma2, 0.5 * d * d * sigma2, ad - 0.5 / sigma2)
    if ins.get("OutsideWeight") and ins["OutsideWeight"][0] is not None:
        diff = diff * jnp.asarray(ins["OutsideWeight"][0])
    out = jnp.sum(diff.reshape(diff.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [d]}


@op("log_loss", non_diff_inputs=("Labels",))
def _log_loss(ctx, op_, ins):
    p = jnp.asarray(ins["Predicted"][0])
    y = jnp.asarray(ins["Labels"][0])
    eps = op_.attr("epsilon", 1e-4)
    out = -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)
    return {"Loss": [out]}


@op("hinge_loss", non_diff_inputs=("Labels",))
def _hinge_loss(ctx, op_, ins):
    pred = jnp.asarray(ins["Logits"][0])
    label = jnp.asarray(ins["Labels"][0])
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2 * label - 1) * pred)]}


@op("huber_loss", non_diff_inputs=("Y",))
def _huber_loss(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])  # predictions
    y = jnp.asarray(ins["Y"][0])
    delta = op_.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@op("rank_loss", non_diff_inputs=("Label",))
def _rank_loss(ctx, op_, ins):
    label = jnp.asarray(ins["Label"][0])
    left = jnp.asarray(ins["Left"][0])
    right = jnp.asarray(ins["Right"][0])
    d = left - right
    return {"Out": [jnp.log1p(jnp.exp(d)) - label * d]}


@op("margin_rank_loss", non_diff_inputs=("Label",))
def _margin_rank_loss(ctx, op_, ins):
    label = jnp.asarray(ins["Label"][0])
    x1 = jnp.asarray(ins["X1"][0])
    x2 = jnp.asarray(ins["X2"][0])
    margin = op_.attr("margin", 0.0)
    act = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [act], "Activated": [(act > 0).astype(x1.dtype)]}


@op("squared_l2_norm")
def _squared_l2_norm(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    return {"Out": [jnp.sum(x * x).reshape(1)]}


@op("squared_l2_distance", non_diff_inputs=())
def _squared_l2_distance(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    sub = x - y
    return {"Out": [jnp.sum(sub * sub, axis=1, keepdims=True)], "sub_result": [sub]}


# --- embedding --------------------------------------------------------------

def _lookup_infer(op_, block):
    wv, iv = in_var(op_, block, "W"), in_var(op_, block, "Ids")
    if wv is None or iv is None or wv.shape is None or iv.shape is None:
        return
    shape = list(iv.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    set_out(op_, block, "Out", shape + [wv.shape[1]], wv.dtype)


@op("lookup_table", infer_shape=_lookup_infer, non_diff_inputs=("Ids",))
def _lookup_table(ctx, op_, ins):
    from . import sparse_ops
    w = jnp.asarray(ins["W"][0])
    ids = jnp.asarray(ins["Ids"][0])
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids.reshape(ids.shape[:-1])
    pad = op_.attr("padding_idx", -1)
    ids32 = ids.astype(jnp.int32)
    wname = (op_.input("W") or [None])[0]
    if wname and sparse_ops.table_axes(ctx.program, wname) is not None:
        # row-sharded table: pin + gather under pd.coll.emb_lookup so
        # GSPMD mod-shard-routes the ids instead of all-gathering rows
        out = sparse_ops.sharded_lookup(ctx.program, wname, w, ids32)
    else:
        out = jnp.take(w, ids32, axis=0)
    if pad is not None and pad >= 0:
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return {"Out": [out]}


@op("lookup_table_grad", grad=NO_GRAD)
def _lookup_table_grad(ctx, op_, ins):
    """Embedding gradient (reference lookup_table_op.cc LookupTableGradKernel).
    is_sparse=True returns a SelectedRowsVal — ids + per-lookup cotangent
    rows, duplicates unmerged exactly like the reference — so the sgd
    update is a scatter-add touching only the looked-up rows instead of a
    dense table-sized gradient (reference selected_rows_functor.cc).
    Dense path scatter-adds into a full zeros table."""
    w = jnp.asarray(ins["W"][0])
    ids = jnp.asarray(ins["Ids"][0])
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    g = jnp.asarray(ins["Out@GRAD"][0])
    pad = op_.attr("padding_idx", -1)
    flat_ids = ids.reshape(-1).astype(jnp.int32)
    flat_g = g.reshape(-1, g.shape[-1]).astype(w.dtype)
    if pad is not None and pad >= 0:
        flat_g = jnp.where((flat_ids == pad)[:, None], 0.0, flat_g)
    from . import sparse_ops
    wname = (op_.input("W") or [None])[0]
    sharded = (wname is not None
               and sparse_ops.table_axes(ctx.program, wname) is not None)
    if op_.attr("is_sparse", False) or sharded:
        # sharded tables force the sparse grad even without is_sparse: a
        # dense [V, D] cotangent would materialize the whole table per
        # device before the optimizer ever saw it
        if sharded and not op_.attr("is_sparse", False):
            sparse_ops.note_once(
                f"forced_sparse:{wname}",
                f"lookup_table_grad for row-sharded table '{wname}' "
                f"emits a SelectedRows gradient (is_sparse forced on): "
                f"a dense gradient would materialize the full table.")
        return {"W@GRAD": [SelectedRowsVal(flat_ids, flat_g, w.shape[0])]}
    dense = jnp.zeros_like(w).at[flat_ids].add(flat_g)
    return {"W@GRAD": [dense]}


# --- conv / pool ------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n

def _conv_out_dim(i, k, p, s, d=1):
    if i is None or i < 0:
        return None
    ke = d * (k - 1) + 1
    return (i + 2 * p - ke) // s + 1


def _conv2d_infer(op_, block):
    xv, fv = in_var(op_, block, "Input"), in_var(op_, block, "Filter")
    if xv is None or fv is None or xv.shape is None or fv.shape is None:
        return
    s, p, d = (_pair(op_.attr("strides", [1, 1])), _pair(op_.attr("paddings", [0, 0])),
               _pair(op_.attr("dilations", [1, 1])))
    n, _, h, w = xv.shape
    co, _, kh, kw = fv.shape
    set_out(op_, block, "Output",
            [n, co, _conv_out_dim(h, kh, p[0], s[0], d[0]),
             _conv_out_dim(w, kw, p[1], s[1], d[1])], xv.dtype)


def _lax_conv(x, w, s, p, d, groups):
    """The one conv call every route's arithmetic is defined by: NHWC
    activation, OIHW filter handed over as HWIO."""
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)),
        window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d, feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@op("conv2d", infer_shape=_conv2d_infer)
def _conv2d(ctx, op_, ins):
    """Computes in NHWC — the TPU-preferred conv layout (channels on the
    minor axis feed the MXU directly). Under the trace-time layout
    convention (ops/layout.py) the NHWC result is kept and tagged so the
    whole conv/bn/pool stack runs NHWC with one transpose at each end;
    with the convention off, the user-visible NCHW layout is restored
    per conv.

    Every float conv is XLA's convolution (`_lax_conv`), on one chip as
    under a mesh: on a v5e it ran ResNet-50 eleven times faster than
    row-per-grid-step Pallas kernels did (PERF.md §6, PR 25; deleted at
    PR 45). Only AMP O3 has a second route: the int8 kernel
    pallas_conv.conv2d_q8, behind quant.ineligible_conv."""
    from . import layout as layout_mod
    from .. import quant
    x = jnp.asarray(ins["Input"][0])
    w = jnp.asarray(ins["Filter"][0])
    s = _pair(op_.attr("strides", [1, 1]))
    p = _pair(op_.attr("paddings", [0, 0]))
    d = _pair(op_.attr("dilations", [1, 1]))
    groups = op_.attr("groups", 1) or 1
    nhwc_in = ctx.layout_of(op_.desc.inputs["Input"][0]) == layout_mod.NHWC
    (x, w), restore = mxu_cast(ctx, x, w)
    if not nhwc_in:
        x = jnp.transpose(x, (0, 2, 3, 1))
    qmode = getattr(ctx, "quant_mode", None)
    out = None
    if qmode:
        qreason = quant.ineligible_conv(
            x, w, s, p, d, groups, qmode,
            mesh=getattr(ctx.program, "_mesh", None))
        if qreason is None:
            # booked under the kernel's op, whichever conv op lowers
            kernel_choice.book("conv2d", None)
            quant.count_hit(op_.type)
            out = quant.qconv2d(
                x, w, s, p, d, qmode,
                pre=quant.prequantized(ctx, op_.desc.inputs["Filter"][0]))
        else:
            quant.count_fallback(op_.type, qreason)
    if out is None:
        out = _lax_conv(x, w, s, p, d, groups)
    if restore is not None:
        out = out.astype(restore)
    if ctx.layout_opt:
        ctx.set_layout(op_.desc.outputs["Output"][0], layout_mod.NHWC)
    else:
        out = jnp.transpose(out, (0, 3, 1, 2))
    return {"Output": [out]}


@op("depthwise_conv2d", infer_shape=_conv2d_infer)
def _depthwise_conv2d(ctx, op_, ins):
    return _conv2d(ctx, op_, ins)


@op("conv2d_grad", infer_shape=infer_grad_shapes, grad=NO_GRAD)
def _conv2d_grad(ctx, op_, ins):
    """Explicit conv backward: the input and filter gradients are the
    two transposes of the forward's `_lax_conv` call on the mxu-cast
    NHWC operands (conv is linear in each operand, so no forward is
    computed again), whatever route the forward took. Under AMP O3 that is
    the straight-through estimator: the int8 forward has no transpose
    rule, so this lowering cannot defer to generic_grad_lower there.

    Layout contract (matches the generic path's tag bookkeeping): the
    Output@GRAD cotangent arrives NHWC-tagged when the layout convention
    is on (layout.align_cotangents' prepass) and NCHW otherwise;
    Input@GRAD must be produced in Input's current layout because
    tag_outputs re-tags it from the forward var; Filter@GRAD is always
    canonical OIHW."""
    from . import layout as layout_mod
    douts = ins.get("Output@GRAD")
    if not douts or douts[0] is None:
        # Zero cotangent (output unused by the loss): explicit zeros,
        # each in its forward var's current layout and dtype as the
        # contract above asks, and no conv in the step for them.
        outs = {}
        for slot, names in op_.desc.outputs.items():
            base = slot[: -len("@GRAD")]
            srcs = ins.get(base, [])
            outs[slot] = [
                jnp.zeros_like(jnp.asarray(srcs[i]))
                if i < len(srcs) and srcs[i] is not None else None
                for i in range(len(names))]
        return outs
    x = jnp.asarray(ins["Input"][0])
    w = jnp.asarray(ins["Filter"][0])
    s = _pair(op_.attr("strides", [1, 1]))
    p = _pair(op_.attr("paddings", [0, 0]))
    d = _pair(op_.attr("dilations", [1, 1]))
    groups = op_.attr("groups", 1) or 1
    x_nhwc_in = ctx.layout_of(op_.desc.inputs["Input"][0]) == layout_mod.NHWC
    (xc, wc), _ = mxu_cast(ctx, x, w)
    x_nhwc = xc if x_nhwc_in else jnp.transpose(xc, (0, 2, 3, 1))
    dout = jnp.asarray(douts[0])
    if ctx.layout_of(op_.desc.inputs["Output@GRAD"][0]) != layout_mod.NHWC:
        dout = jnp.transpose(dout, (0, 2, 3, 1))
    dout = dout.astype(x_nhwc.dtype)   # the conv's own output dtype
    outs = {}
    if "Input@GRAD" in op_.desc.outputs:
        dx, = jax.linear_transpose(
            lambda a: _lax_conv(a, wc, s, p, d, groups), x_nhwc)(dout)
        dx = dx.astype(x.dtype)
        if not x_nhwc_in:
            dx = jnp.transpose(dx, (0, 3, 1, 2))
        outs["Input@GRAD"] = [dx]
    if "Filter@GRAD" in op_.desc.outputs:
        # rounded to the operands' dtype, then cast to the filter's. A
        # transpose written out with preferred_element_type=f32 gave
        # bit-equal gradients and updates at the same rate on the v5e
        # (XLA keeps the excess precision across the cast; PERF.md §6,
        # PR 25), so the shorter form stays
        dw, = jax.linear_transpose(
            lambda b: _lax_conv(x_nhwc, b, s, p, d, groups), wc)(dout)
        outs["Filter@GRAD"] = [dw.astype(w.dtype)]
    return outs


@op("depthwise_conv2d_grad", infer_shape=infer_grad_shapes, grad=NO_GRAD)
def _depthwise_conv2d_grad(ctx, op_, ins):
    return _conv2d_grad(ctx, op_, ins)


def _conv3d_infer(op_, block):
    xv, fv = in_var(op_, block, "Input"), in_var(op_, block, "Filter")
    if xv is None or fv is None or xv.shape is None or fv.shape is None:
        return
    s = _pair(op_.attr("strides", [1, 1, 1]), 3)
    p = _pair(op_.attr("paddings", [0, 0, 0]), 3)
    d = _pair(op_.attr("dilations", [1, 1, 1]), 3)
    n = xv.shape[0]
    co = fv.shape[0]
    dims = [_conv_out_dim(xv.shape[2 + i], fv.shape[2 + i], p[i], s[i], d[i])
            for i in range(3)]
    set_out(op_, block, "Output", [n, co] + dims, xv.dtype)


@op("conv3d", infer_shape=_conv3d_infer)
def _conv3d(ctx, op_, ins):
    """NDHWC compute for the MXU, same layout convention as conv2d."""
    from . import layout as layout_mod
    x = jnp.asarray(ins["Input"][0])
    w = jnp.asarray(ins["Filter"][0])
    s = _pair(op_.attr("strides", [1, 1, 1]), 3)
    p = _pair(op_.attr("paddings", [0, 0, 0]), 3)
    d = _pair(op_.attr("dilations", [1, 1, 1]), 3)
    groups = op_.attr("groups", 1) or 1
    ndhwc_in = ctx.layout_of(op_.desc.inputs["Input"][0]) == layout_mod.NDHWC
    (x, w), restore = mxu_cast(ctx, x, w)
    if not ndhwc_in:
        x = jnp.transpose(x, (0, 2, 3, 4, 1))
    out = jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 4, 1, 0)),
        window_strides=s, padding=[(pi, pi) for pi in p],
        rhs_dilation=d, feature_group_count=groups,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    if restore is not None:
        out = out.astype(restore)
    if ctx.layout_opt:
        ctx.set_layout(op_.desc.outputs["Output"][0], layout_mod.NDHWC)
    else:
        out = jnp.transpose(out, (0, 4, 1, 2, 3))
    return {"Output": [out]}


def _convt2d_infer(op_, block):
    xv, fv = in_var(op_, block, "Input"), in_var(op_, block, "Filter")
    if xv is None or fv is None or xv.shape is None or fv.shape is None:
        return
    s = _pair(op_.attr("strides", [1, 1]))
    p = _pair(op_.attr("paddings", [0, 0]))
    d = _pair(op_.attr("dilations", [1, 1]))
    n, _, h, w = xv.shape
    _, co, kh, kw = fv.shape

    def odim(i, k, pp, ss, dd):
        if i is None or i < 0:
            return None
        return (i - 1) * ss - 2 * pp + dd * (k - 1) + 1
    set_out(op_, block, "Output",
            [n, co, odim(h, kh, p[0], s[0], d[0]), odim(w, kw, p[1], s[1], d[1])],
            xv.dtype)


@op("conv2d_transpose", infer_shape=_convt2d_infer)
def _conv2d_transpose(ctx, op_, ins):
    """Gradient-of-conv formulation (dilate the input by stride, pad by
    k-1-p), computed in NHWC for the MXU like conv2d."""
    from . import layout as layout_mod
    x = jnp.asarray(ins["Input"][0])
    w = jnp.asarray(ins["Filter"][0])   # (Cin, Cout, kh, kw) = IOHW
    s = _pair(op_.attr("strides", [1, 1]))
    p = _pair(op_.attr("paddings", [0, 0]))
    d = _pair(op_.attr("dilations", [1, 1]))
    kh = d[0] * (w.shape[2] - 1) + 1
    kw = d[1] * (w.shape[3] - 1) + 1
    nhwc_in = ctx.layout_of(op_.desc.inputs["Input"][0]) == layout_mod.NHWC
    (x, w), restore = mxu_cast(ctx, x, w)
    if not nhwc_in:
        x = jnp.transpose(x, (0, 2, 3, 1))
    # (Cin, Cout, kh, kw) flipped spatially -> HWIO with I=Cin, O=Cout
    out = jax.lax.conv_general_dilated(
        x, jnp.transpose(jnp.flip(w, (2, 3)), (2, 3, 0, 1)),
        window_strides=(1, 1),
        padding=[(kh - 1 - p[0], kh - 1 - p[0]), (kw - 1 - p[1], kw - 1 - p[1])],
        lhs_dilation=s, rhs_dilation=d,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if restore is not None:
        out = out.astype(restore)
    if ctx.layout_opt:
        ctx.set_layout(op_.desc.outputs["Output"][0], layout_mod.NHWC)
    else:
        out = jnp.transpose(out, (0, 3, 1, 2))
    return {"Output": [out]}


def _pool2d_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is None or xv.shape is None:
        return
    if op_.attr("global_pooling", False):
        set_out(op_, block, "Out", [xv.shape[0], xv.shape[1], 1, 1], xv.dtype)
        return
    k = _pair(op_.attr("ksize"))
    s = _pair(op_.attr("strides", [1, 1]))
    p = _pair(op_.attr("paddings", [0, 0]))
    n, c, h, w = xv.shape

    def odim(i, kk, pp, ss):
        if i is None or i < 0:
            return None
        if op_.attr("ceil_mode", False):
            return (i - kk + 2 * pp + ss - 1) // ss + 1
        return (i - kk + 2 * pp) // ss + 1
    set_out(op_, block, "Out",
            [n, c, odim(h, k[0], p[0], s[0]), odim(w, k[1], p[1], s[1])], xv.dtype)


@op("pool2d", infer_shape=_pool2d_infer)
def _pool2d(ctx, op_, ins):
    from . import layout as layout_mod
    x = jnp.asarray(ins["X"][0])
    nhwc = ctx.layout_of(op_.desc.inputs["X"][0]) == layout_mod.NHWC
    sp = (1, 2) if nhwc else (2, 3)   # spatial dims in the live layout
    ptype = op_.attr("pooling_type", "max")
    if op_.attr("global_pooling", False):
        k = [x.shape[sp[0]], x.shape[sp[1]]]
        s, p = k, [0, 0]
    else:
        k = _pair(op_.attr("ksize"))
        s = _pair(op_.attr("strides", [1, 1]))
        p = _pair(op_.attr("paddings", [0, 0]))
    if nhwc:
        window = (1, k[0], k[1], 1)
        strides = (1, s[0], s[1], 1)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    else:
        window = (1, 1, k[0], k[1])
        strides = (1, 1, s[0], s[1])
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    else:
        out = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
        if op_.attr("exclusive", True):
            ones = jnp.ones((x.shape[sp[0]], x.shape[sp[1]]), dtype=x.dtype)
            ones = ones[None, :, :, None] if nhwc else ones[None, None]
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides, pads)
            out = out / cnt
        else:
            out = out / (k[0] * k[1])
    if nhwc:
        ctx.set_layout(op_.desc.outputs["Out"][0], layout_mod.NHWC)
    return {"Out": [out]}


# --- normalization ----------------------------------------------------------

def _bn_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is None or xv.shape is None:
        return
    set_out(op_, block, "Y", xv.shape, xv.dtype)
    c = xv.shape[1] if len(xv.shape) > 1 else xv.shape[0]
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        set_out(op_, block, slot, [c], "float32")


@op("batch_norm", infer_shape=_bn_infer,
    non_diff_inputs=("Mean", "Variance"))
def _batch_norm(ctx, op_, ins):
    from . import layout as layout_mod
    x = jnp.asarray(ins["X"][0])
    scale = jnp.asarray(ins["Scale"][0])
    bias = jnp.asarray(ins["Bias"][0])
    mean = jnp.asarray(ins["Mean"][0])
    var = jnp.asarray(ins["Variance"][0])
    eps = op_.attr("epsilon", 1e-5)
    momentum = op_.attr("momentum", 0.9)
    is_test = op_.attr("is_test", False)
    tag = ctx.layout_of(op_.desc.inputs["X"][0])
    # channel axis: minor under the internal NHWC/NDHWC convention
    ch = (x.ndim - 1) if tag in (layout_mod.NHWC, layout_mod.NDHWC) else 1
    axes = tuple(i for i in range(x.ndim) if i != ch)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]

    # statistics always in f32 — bf16 inputs (AMP O2) would lose too many
    # mantissa bits in the mean/var reductions; output returns to x's dtype
    # so bf16 activations stay bf16 downstream
    xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        use_mean = jnp.mean(xf, axis=axes)
        if x.dtype == jnp.bfloat16:
            # one-pass statistics: E[x] and E[x^2] are sibling reductions
            # over the same input, which XLA multi-output-fuses into a
            # single sweep of x — one fewer full HBM read per BN (+12%
            # ResNet-50 step throughput). Safe only for bf16 activations:
            # their 8-bit mantissa already bounds the relative error, so
            # the E[x^2]-E[x]^2 cancellation adds nothing beyond the
            # input quantization. f32 inputs with large mean/std ratio
            # would catastrophically cancel, so they take the centered
            # two-pass form below.
            use_var = jnp.maximum(
                jnp.mean(jnp.square(xf), axis=axes) - jnp.square(use_mean),
                0.0)
        else:
            use_var = jnp.mean(jnp.square(xf - use_mean.reshape(shape)),
                               axis=axes)
        mean_out = mean * momentum + use_mean * (1.0 - momentum)
        var_out = var * momentum + use_var * (1.0 - momentum)
        saved_mean = use_mean
        saved_var = use_var
    inv = jax.lax.rsqrt(use_var + eps)
    y = (xf - use_mean.reshape(shape)) * (inv * scale).reshape(shape) \
        + bias.reshape(shape)
    y = y.astype(x.dtype)
    if tag in (layout_mod.NHWC, layout_mod.NDHWC):
        ctx.set_layout(op_.desc.outputs["Y"][0], tag)
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_mean], "SavedVariance": [saved_var]}


def _ln_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is None or xv.shape is None:
        return
    set_out(op_, block, "Y", xv.shape, xv.dtype)
    ax = op_.attr("begin_norm_axis", 1)
    left = int(np.prod([d for d in xv.shape[:ax]])) if all(
        d is not None and d > 0 for d in xv.shape[:ax]) else None
    set_out(op_, block, "Mean", [left] if left else None, "float32")
    set_out(op_, block, "Variance", [left] if left else None, "float32")


@op("layer_norm", infer_shape=_ln_infer)
def _layer_norm(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    ax = op_.attr("begin_norm_axis", 1)
    eps = op_.attr("epsilon", 1e-5)
    axes = tuple(range(ax, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    feat_shape = (1,) * ax + x.shape[ax:]
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * jnp.asarray(ins["Scale"][0]).reshape(feat_shape)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        y = y + jnp.asarray(ins["Bias"][0]).reshape(feat_shape)
    return {"Y": [y], "Mean": [mean.reshape(-1)], "Variance": [var.reshape(-1)]}


@op("lrn", infer_shape=same_as_input())
def _lrn(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    n = op_.attr("n", 5)
    k = op_.attr("k", 2.0)
    alpha = op_.attr("alpha", 1e-4)
    beta = op_.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    acc = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, n, 1, 1), (1, 1, 1, 1),
        ((0, 0), (half, n - 1 - half), (0, 0), (0, 0)))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


@op("label_smooth", non_diff_inputs=("PriorDist",))
def _label_smooth(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    eps = op_.attr("epsilon", 0.0)
    if ins.get("PriorDist") and ins["PriorDist"][0] is not None:
        prior = jnp.asarray(ins["PriorDist"][0])
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return {"Out": [out]}


# --- dropout ----------------------------------------------------------------

def _dropout_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None:
        set_out(op_, block, "Out", xv.shape, xv.dtype)
        set_out(op_, block, "Mask", xv.shape, "float32")


def _dropout_grad(fwd, no_grad_set):
    xname = fwd.input("X")[0]
    if xname in no_grad_set:
        return []
    return [OpDesc(
        type="dropout_grad",
        inputs={"Mask": fwd.output("Mask"),
                "Out@GRAD": [grad_var_name(fwd.output("Out")[0])]},
        outputs={"X@GRAD": [grad_var_name(xname)]},
        attrs=dict(fwd.attrs))]


@op("dropout", infer_shape=_dropout_infer, grad=_dropout_grad)
def _dropout(ctx, op_, ins):
    """Reference semantics (dropout_op.cc, 'downgrade_in_infer'): train
    multiplies by a bernoulli mask; inference scales by (1-p)."""
    x = jnp.asarray(ins["X"][0])
    p = op_.attr("dropout_prob", 0.5)
    if op_.attr("is_test", False):
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    key = ctx.next_rng(op_)
    mask = jax.random.bernoulli(key, 1.0 - p, x.shape).astype(x.dtype)
    return {"Out": [x * mask], "Mask": [mask]}


@op("dropout_grad", grad=NO_GRAD)
def _dropout_grad_kernel(ctx, op_, ins):
    dout = jnp.asarray(ins["Out@GRAD"][0])
    mask = jnp.asarray(ins["Mask"][0])
    return {"X@GRAD": [dout * mask]}


# --- metrics (no grad) ------------------------------------------------------

def _accuracy_infer(op_, block):
    set_out(op_, block, "Accuracy", [1], "float32")
    set_out(op_, block, "Correct", [1], "int32")
    set_out(op_, block, "Total", [1], "int32")


@op("accuracy", infer_shape=_accuracy_infer, grad=NO_GRAD)
def _accuracy(ctx, op_, ins):
    idx = jnp.asarray(ins["Indices"][0])
    label = jnp.asarray(ins["Label"][0]).reshape(-1, 1)
    hit = jnp.any(idx == label, axis=1)
    correct = jnp.sum(hit.astype(jnp.int32)).reshape(1)
    total = jnp.asarray([idx.shape[0]], dtype=jnp.int32)
    acc = correct.astype(jnp.float32) / idx.shape[0]
    return {"Accuracy": [acc], "Correct": [correct], "Total": [total]}


@op("auc", grad=NO_GRAD)
def _auc(ctx, op_, ins):
    """Streaming-free AUC over the batch via threshold buckets
    (reference auc_op.cc)."""
    pred = jnp.asarray(ins["Out"][0])
    label = jnp.asarray(ins["Label"][0]).reshape(-1)
    pos_score = pred[:, 1] if pred.ndim == 2 and pred.shape[1] >= 2 \
        else pred.reshape(-1)
    num_t = op_.attr("num_thresholds", 200)
    th = jnp.linspace(0.0, 1.0, num_t)
    is_pos = (label > 0)
    tp = jnp.sum((pos_score[None, :] >= th[:, None]) & is_pos[None, :], axis=1)
    fp = jnp.sum((pos_score[None, :] >= th[:, None]) & ~is_pos[None, :], axis=1)
    P = jnp.maximum(jnp.sum(is_pos), 1)
    N = jnp.maximum(jnp.sum(~is_pos), 1)
    tpr = tp / P
    fpr = fp / N
    auc = -jnp.trapezoid(tpr, fpr)
    return {"AUC": [auc.reshape(1)]}


# --- attention ---------------------------------------------------------------

def _sdpa_infer(op_, block):
    qv = in_var(op_, block, "Q")
    if qv is not None:
        set_out(op_, block, "Out", qv.shape, qv.dtype)
        if "LSE" in op_.desc.outputs:
            b, t, h = qv.shape[0], qv.shape[1], qv.shape[2]
            set_out(op_, block, "LSE", [b, h, t], "float32")


def _sdpa_grad(fwd, no_grad_set):
    """Explicit grad op consuming the forward's saved LSE (dropout-Mask
    pattern; reference batch_norm saves statistics the same way). The
    generic vjp maker would re-trace the forward INSIDE the grad op — for
    HLO einsums XLA CSEs the duplicate, but pallas custom calls are not
    CSE'd, so use_flash would pay the flash forward twice per step.
    Both attention ops' (`<forward type>_grad`)."""
    wanted = [s for s in ("Q", "K", "V")
              if fwd.input(s)[0] not in no_grad_set]
    if not wanted:
        return []
    return [OpDesc(
        type=fwd.type + "_grad",
        inputs={"Q": fwd.input("Q"), "K": fwd.input("K"),
                "V": fwd.input("V"), "Out": fwd.output("Out"),
                "LSE": fwd.output("LSE"),
                "Out@GRAD": [grad_var_name(fwd.output("Out")[0])]},
        outputs={s + "@GRAD": [grad_var_name(fwd.input(s)[0])]
                 for s in wanted},
        attrs=dict(fwd.attrs))]


# What both attention ops keep across a replayed segment
# (registry.OpDef.kept_in_replay, which says why these two and no other):
# the two outputs their explicit grad op reads. Between two checkpoints
# they are dead after the forward, so the replay would run the forward
# kernels a second time for them, behind a barrier that keeps XLA from
# merging the two runs: the cost that grad op was written to avoid.
_SDPA_KEPT = ("Out", "LSE")


# Shortest per-device sequence that 'auto' hands to the flash kernels.
# Attention alone on a v5e, causal bf16, 12 heads of 64, 16384 tokens a
# call, forward + backward, ms (tools/flash_sweep.py, PR 29; PERF.md
# section 6):
#     T      128    256    512    1024   2048   4096
#     einsum 0.64   1.81   3.20   6.62   12.25  22.73
#     flash  2.33   2.04   2.24   3.66   4.17   7.05
# The kernels' time follows the tokens, einsum's the T x T scores: they
# cross between 256 and 512. These pairs are attention ALONE, at one
# (H, D), read before the kernels' last layout (T=1024 reads 2.89 since).
# End to end only T=1024 is covered: in GPT-2's step the kernels took
# 28 ms where einsum took 81, and freed 3.3 GB; no benchmark cell runs
# T=512 or sits on the einsum side of this line.
_FLASH_AUTO_MIN_T = 512


def _flash_wins(q) -> bool:
    """The 'auto' rule, on the per-device [B, T, H, D] operand the
    lowering sees: the flash kernels from the sequence length at which
    they beat einsum attention on the chip, whose [B, H, T, T] scores
    and probabilities cost HBM traffic and memory that grow with T^2."""
    return q.shape[1] >= _FLASH_AUTO_MIN_T


def _ring_uses_flash(op_, q, mesh):
    """Whether the ring path runs Pallas flash blocks per shard: explicit
    use_flash=False forces the einsum ring; True or 'auto' takes flash
    whenever the shard shape tiles (long-context is exactly where flash
    pays). Static — the explicit grad op recomputes the same decision."""
    uf = op_.attr("use_flash", "auto")
    if uf is False:
        return False
    from ..parallel.ring_attention import flash_ring_eligible
    return flash_ring_eligible(q, mesh, "sp")


_FlashPlacement = collections.namedtuple("_FlashPlacement",
                                         "mesh qkv lse")


def _flash_partition(program, q):
    """How the flash kernels sit in a GSPMD-partitioned step: XLA cannot
    partition a Mosaic custom call (it gathers every operand and runs the
    whole call on each device), so under a mesh of more than one device
    they run inside shard_map — batch over the data and fsdp axes, heads
    over the tensor axis of the program's planner layout. Attention is
    independent per (example, head), so the shards need no collective.
    An axis whose size does not divide its dim stays replicated. Returns
    (placement, shard): placement None off-mesh, else the mesh with the
    specs of a [B, T, H, D] operand and of a [B, H, T] row statistic
    (LSE); shard(x) is the aval of x's per-device block, which is what
    the kernels' gate must pass, or None for an x (a K or V of fewer
    heads than Q) whose heads the tensor axis does not divide."""
    mesh = getattr(program, "_mesh", None)
    if mesh is None or mesh.size == 1 or q.ndim != 4:
        return None, lambda x: x
    from jax.sharding import PartitionSpec
    from ..parallel.planner import SpecLayout
    plan = getattr(program, "_sharding_plan", None)
    layout = plan.layout if plan is not None else SpecLayout()

    def fit(axes, dim):
        took, n = [], 1
        for a in axes:
            if a in mesh.axis_names and dim % (n * mesh.shape[a]) == 0:
                took.append(a)
                n *= mesh.shape[a]
        return (tuple(took) or None), n

    batch, nb = fit((layout.data_axis, layout.fsdp_axis), q.shape[0])
    heads, nh = fit((layout.tensor_axis,), q.shape[2])

    def shard(x):
        b, t, h, d = x.shape
        if h % nh:
            return None
        return jax.ShapeDtypeStruct((b // nb, t, h // nh, d), x.dtype)

    return _FlashPlacement(mesh, PartitionSpec(batch, None, heads, None),
                           PartitionSpec(batch, heads, None)), shard


def _kv_groups(q, k) -> int:
    """Query heads per K/V head of [B, T, H, D] operands: 1 for full
    multi-head attention, H / H_kv under grouped-query attention, where
    query head j reads K/V head j // (H / H_kv)."""
    hq, hkv = q.shape[2], k.shape[2]
    assert hq % hkv == 0, f"{hq} query heads over {hkv} K/V heads"
    return hq // hkv


def _repeat_kv(x, groups: int):
    """K or V of H_kv heads as the query's H: each head `groups` times in
    a row, for the paths that need equal head counts (`_repeated_kv`'s
    grounds); their grad ops sum dK and dV back over each group. The
    flash kernels read H_kv heads where one head is a lane block and pay
    none of it."""
    return x if groups == 1 else jnp.repeat(x, groups, axis=2)


def _sum_kv_groups(g, groups: int):
    """_repeat_kv pulled back: the gradient of a repeated K or V summed
    over each group of `groups` heads, in float32."""
    if groups == 1:
        return g
    b, t, h, d = g.shape
    return g.astype(jnp.float32).reshape(b, t, h // groups, groups, d) \
        .sum(3).astype(g.dtype)


def _repeated_kv(q, k, mode, shard=lambda x: x):
    """None where this lowering hands K and V on at their own head count
    (equal counts, or the flash kernels read the K/V head of a query head
    themselves: `pallas_attention.reads_kv_heads`), else the ground for
    the repeat: "path" (`mode` is einsum or ring), "mesh" (the tensor
    axis of a planned program does not divide H_kv: `shard` gives None)
    or "lanes" (a lane block of several heads: D = 64)."""
    from . import pallas_attention
    if _kv_groups(q, k) == 1:
        return None
    if mode != "flash":
        return "path"
    if shard(k) is None:
        return "mesh"
    return None if pallas_attention.reads_kv_heads(shard(q), shard(k)) \
        else "lanes"


def _count_kv_groups(op_type: str, groups: int, ground):
    """attention_kv_groups_total{op, groups, form, ground}: one per
    lowering of a forward attention op under grouped-query attention,
    form "kernel" (K and V read at their own heads, ground "") or
    "repeated" with `_repeated_kv`'s ground."""
    if groups > 1 and not kernel_choice.in_retrace():
        from .. import telemetry
        telemetry.counter(
            "attention_kv_groups_total",
            "lowerings of a forward attention op whose K and V have fewer "
            "heads than Q, by query heads a K/V head and by form: kernel "
            "(the flash kernels read K/V at their own head count) or "
            "repeated (K/V widened to Q's heads ahead of the path, dK/dV "
            "summed behind it) with the ground: lanes, path or mesh",
            labels=("op", "groups", "form", "ground")).labels(
                op=op_type, groups=str(groups),
                form="repeated" if ground else "kernel",
                ground=ground or "").inc()


def _window(op_) -> int:
    """The op's `window` (absent: 0, none): a causal query sees the last
    `window` keys up to and with its own. Every path takes it or says
    that it does not."""
    window = int(op_.attr("window", 0))
    if window < 0 or (window and not op_.attr("causal", False)):
        raise ValueError(
            f"{op_.type}: window={window} needs causal=True and a "
            f"positive number of keys")
    return window


def _count_window(window: int):
    """attention_window_total{window}: one per lowering of a forward
    attention op that carries a window."""
    if window and not kernel_choice.in_retrace():
        from .. import telemetry
        telemetry.counter(
            "attention_window_total",
            "lowerings of a forward attention op under a sliding window, "
            "by the window's keys",
            labels=("window",)).labels(window=str(window)).inc()


def _sdpa_paths(ctx, op_, q, k, v, count=False):
    """(mode, how, repeated): 'ring' under sequence_parallel with an sp
    mesh (how = the mesh; a window is refused there by name: the ring's
    shards know none), 'flash' when use_flash is True, or 'auto' and the
    rule (_flash_wins) gives the per-device shape to the kernels, and
    their gate passes that shape (how = _flash_partition's placement),
    else 'einsum'. `repeated` is `_repeated_kv`'s ground for widening a K
    and V of fewer heads to the query's count ahead of the path, None
    where they go on as they came; the gate is asked about the operands
    the kernels would get. Auto-selection: the default config gets
    whichever path is faster for its shape, no user flag. `count` books
    the decision of this lowering, as every Pallas gate does (the forward
    op passes it; the grad op recomputes the same static decision in
    silence): a lowering on the kernels under pallas_kernel_total, a
    declined flash request under pallas_fallback_total with the gate's
    reason. A step traced again (a new feed shape) lowers its ops again
    and counts them again, with the decision of that shape."""
    from . import pallas_attention
    mesh = getattr(ctx.program, "_mesh", None)
    if op_.attr("sequence_parallel", False) and mesh is not None and \
            "sp" in mesh.axis_names:
        window = _window(op_)
        if window:
            raise NotImplementedError(
                f"{op_.type}: window={window} under sequence_parallel: "
                f"ring attention takes no window")
        return "ring", mesh, _repeated_kv(q, k, "ring")
    einsum = "einsum", None, _repeated_kv(q, k, "einsum")
    uf = op_.attr("use_flash", "auto")
    if not uf:
        return einsum
    partition, shard = _flash_partition(ctx.program, q)
    if uf == "auto" and not _flash_wins(shard(q)):
        return einsum
    repeated = _repeated_kv(q, k, "flash", shard)

    def got(x):
        if repeated:
            x = jax.ShapeDtypeStruct(
                x.shape[:2] + q.shape[2:3] + x.shape[3:], x.dtype)
        return shard(x)

    reason = pallas_attention.ineligible(shard(q), got(k), got(v))
    if count:
        kernel_choice.book("scaled_dot_product_attention", reason)
    return ("flash", partition, repeated) if reason is None else einsum


@op("scaled_dot_product_attention", infer_shape=_sdpa_infer,
    grad=_sdpa_grad, kept_in_replay=_SDPA_KEPT)
def _scaled_dot_product_attention(ctx, op_, ins):
    """Fused softmax attention, Q/K/V [B, T, H, D] (no 2018-reference
    analogue — the capability the brief requires for long context). K and
    V may have fewer heads than Q (grouped-query attention: a divisor of
    Q's count; the flash kernels read them at their own count where one
    head is a lane block, every other path has them repeated:
    _repeated_kv). With
    sequence_parallel=True and a program mesh carrying an 'sp' axis, the
    computation runs as ring attention (parallel/ring_attention.py):
    sequence shards stay resident per device and K/V rotate over ICI via
    ppermute, so full-sequence scores never materialize.

    Also emits LSE, the per-row logsumexp of the scaled scores [B, H, T]
    (f32) — the residual the flash backward recomputes from. The einsum
    path derives it from the same logits XLA already CSEs; the ring path
    emits the real ring-merged LSE so its explicit backward can run the
    blockwise ring gradient directly, without re-executing the forward
    (Pallas custom calls are not CSE'd — ADVICE r4). Replayed in a
    recomputed segment it is handed both and runs nothing (_SDPA_KEPT)."""
    kept = handed_on(ctx, op_, ins)
    if kept is not None:
        return kept
    q = jnp.asarray(ins["Q"][0])
    k = jnp.asarray(ins["K"][0])
    v = jnp.asarray(ins["V"][0])
    causal, window = op_.attr("causal", False), _window(op_)
    (q, k, v), restore = mxu_cast(ctx, q, k, v)
    groups = _kv_groups(q, k)
    from ..parallel.ring_attention import (attention_reference,
                                           attention_reference_lse,
                                           ring_attention_sharded)
    mode, mesh, repeated = _sdpa_paths(ctx, op_, q, k, v, count=True)
    _count_window(window)
    _count_kv_groups(op_.type, groups, repeated)
    if repeated:
        k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    if mode == "ring":
        out, lse = ring_attention_sharded(
            q, k, v, mesh, axis="sp", causal=causal,
            use_flash=_ring_uses_flash(op_, q, mesh), return_lse=True)
    elif mode == "flash":
        # Pallas flash attention (ops/pallas_attention.py): O(T) memory
        # online-softmax VMEM kernel
        from . import pallas_attention

        def fwd(q, k, v):
            return pallas_attention._forward(q, k, v, causal,
                                             return_lse=True, window=window)

        if mesh is not None:        # a _FlashPlacement
            fwd = jax.shard_map(
                fwd, mesh=mesh.mesh, in_specs=(mesh.qkv,) * 3,
                out_specs=(mesh.qkv, mesh.lse), check_vma=False)
        out, lse = fwd(q, k, v)
    else:
        out = attention_reference(q, k, v, causal=causal, window=window)
        lse = attention_reference_lse(q, k, causal=causal, window=window)
    if restore is not None:
        out = out.astype(restore)
    return {"Out": [out], "LSE": [lse]}


@op("scaled_dot_product_attention_grad", grad=NO_GRAD,
    non_diff_inputs=("LSE",))
def _sdpa_grad_kernel(ctx, op_, ins):
    """dQ/dK/dV from the saved (Out, LSE): the flash path runs the Pallas
    backward kernels directly (ops/pallas_attention.flash_attention_bwd_
    block) — no forward re-execution; einsum and ring paths differentiate
    their forward under jax.vjp (XLA CSEs the duplicated einsum HLO)."""
    q = jnp.asarray(ins["Q"][0])
    k = jnp.asarray(ins["K"][0])
    v = jnp.asarray(ins["V"][0])
    do = jnp.asarray(ins["Out@GRAD"][0])
    causal, window = op_.attr("causal", False), _window(op_)
    (q, k, v, do), restore = mxu_cast(ctx, q, k, v, do)
    groups = _kv_groups(q, k)
    from ..parallel.ring_attention import (attention_reference,
                                           ring_attention_sharded)
    mode, mesh, repeated = _sdpa_paths(ctx, op_, q, k, v)
    if repeated:
        k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    if mode == "flash":
        from . import pallas_attention
        o = jnp.asarray(ins["Out"][0]).astype(q.dtype)
        lse = jnp.asarray(ins["LSE"][0])
        scale = 1.0 / (q.shape[-1] ** 0.5)

        def bwd(q, k, v, do, o, lse):
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1).transpose(0, 2, 1)
            return pallas_attention.flash_attention_bwd_block(
                q, k, v, do, lse, delta, 0, 0, scale, causal, window=window)

        if mesh is not None:        # a _FlashPlacement
            bwd = jax.shard_map(
                bwd, mesh=mesh.mesh, in_specs=(mesh.qkv,) * 5 + (mesh.lse,),
                out_specs=(mesh.qkv,) * 3, check_vma=False)
        dq, dk, dv = bwd(q, k, v, do, o, lse)
    elif mode == "ring":
        if _ring_uses_flash(op_, q, mesh):
            # direct blockwise ring backward from the saved (Out, LSE):
            # no forward re-execution (ADVICE r4 — a vjp re-trace would
            # pay the un-CSE-able flash forward twice per step)
            from ..parallel.ring_attention import ring_attention_bwd_sharded
            o = jnp.asarray(ins["Out"][0]).astype(q.dtype)
            lse = jnp.asarray(ins["LSE"][0])
            dq, dk, dv = ring_attention_bwd_sharded(
                q, k, v, do.astype(q.dtype), o, lse, mesh, axis="sp",
                causal=causal)
        else:
            _, vjp_fn = jax.vjp(
                lambda a, b, c: ring_attention_sharded(
                    a, b, c, mesh, axis="sp", causal=causal,
                    use_flash=False), q, k, v)
            dq, dk, dv = vjp_fn(do.astype(q.dtype))
    else:
        _, vjp_fn = jax.vjp(
            lambda a, b, c: attention_reference(a, b, c, causal=causal,
                                                window=window),
            q, k, v)
        dq, dk, dv = vjp_fn(do.astype(q.dtype))
    if repeated:
        dk, dv = _sum_kv_groups(dk, groups), _sum_kv_groups(dv, groups)
    if restore is not None:
        dq, dk, dv = (dq.astype(restore), dk.astype(restore),
                      dv.astype(restore))
    outs = {}
    for name, g in (("Q@GRAD", dq), ("K@GRAD", dk), ("V@GRAD", dv)):
        if name in op_.desc.outputs:
            outs[name] = [g]
    return outs


# --- block-diffusion attention -----------------------------------------------

_BD_OP = "block_diffusion_attention"


def _bd_masks(length: int, block: int):
    """The three parts of block-diffusion training's mask as [L, L] bools
    over (query position, key position), from the positions' blocks
    b(i) = i // block: a noisy query sees the clean keys of `earlier`
    blocks and the noisy keys of its `own`; a clean query sees the clean
    keys `upto` its own block (block-causal). No clean query sees a noisy
    key."""
    bid = jnp.arange(length) // block
    return {"earlier": bid[:, None] > bid[None, :],
            "own": bid[:, None] == bid[None, :],
            "upto": bid[:, None] >= bid[None, :]}


def _bd_einsum(q, k, v, block: int):
    """(Out [2B, L, H, D], LSE [2B, H, L]) of block-diffusion attention
    as masked einsum attention: the noisy half against [clean keys of
    earlier blocks ; noisy keys of its own block], the clean half
    block-causal against the clean keys. Scores [B, H, L, 2L] and
    [B, H, L, L] reach HBM: the path of shapes the kernels do not tile."""
    half, length = q.shape[0] // 2, q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    mask = _bd_masks(length, block)

    def scores(qs, ks, keep):
        logits = jnp.einsum("bqhd,bkhd->bhqk", qs, ks).astype(
            jnp.float32) * scale
        return jnp.where(keep[None, None], logits, -jnp.inf)

    (qn, qc), (kn, kc), (vn, vc) = ((x[:half], x[half:]) for x in (q, k, v))
    noisy = jnp.concatenate([scores(qn, kc, mask["earlier"]),
                             scores(qn, kn, mask["own"])], axis=-1)
    clean = scores(qc, kc, mask["upto"])
    out = jnp.concatenate([
        jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(noisy, -1).astype(v.dtype),
                   jnp.concatenate([vc, vn], axis=1)),
        jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(clean, -1).astype(v.dtype), vc)])
    lse = jnp.concatenate([jax.scipy.special.logsumexp(noisy, -1),
                           jax.scipy.special.logsumexp(clean, -1)])
    return out, lse


def _own_rows(x, block: int):
    """x [B, L, H, D] -> [block, B, L, H, D] float32, a broadcast: entry j
    holds, at every position, row j of the position's own block of
    `block` positions. The own-block part of block-diffusion attention is
    written over it as `block` elementwise passes at the operands' own
    [B, L, H, D] shape (multiply and reduce over D, or weigh and add),
    which XLA fuses without ever building it: L x block pairs a head as
    einsums over [.., block, block] put a dim of `block` = 4 on the lanes
    and pad it 32 times."""
    b, t, h, d = x.shape
    blocks = x.astype(jnp.float32).reshape(b, t // block, 1, block, h, d)
    rows = jnp.broadcast_to(blocks, (b, t // block, block, block, h, d))
    return jnp.moveaxis(rows, 3, 0).reshape(block, b, t, h, d)


def _own_block_scores(qn, kn, block: int):
    """Scaled scores of every noisy query against the `block` noisy keys
    of its own block, [block, B, L, H] float32."""
    scale = 1.0 / qn.shape[-1] ** 0.5
    return jnp.sum(qn.astype(jnp.float32) * _own_rows(kn, block),
                   axis=-1) * scale


def _to_own_rows(weight, x, block: int, groups: int = 1):
    """_own_rows pulled back: [B, L, H / groups, D] float32 whose row j of
    every block is the sum over the block's positions q, and over each
    group of `groups` heads (_repeat_kv pulled back in the same
    reduction), of weight[j] at q times x at q; weight [block, B, L, H]
    float32, x [B, L, H, D]."""
    b, t, h, d = x.shape
    n = t // block
    terms = weight.reshape(block, b, n, block, h // groups, groups, 1) \
        * x.astype(jnp.float32).reshape(1, b, n, block, h // groups, groups,
                                        d)
    return jnp.moveaxis(terms.sum((3, 5)), 0, 2).reshape(
        b, t, h // groups, d)


def _bd_flash(q, k, v, block: int):
    """(Out, LSE) of block-diffusion attention on the flash kernels, all
    three parts from the one block-causal geometry of
    ops/pallas_attention.py: the clean half is a block-causal forward;
    the noisy half's view of the clean keys is the same geometry with
    the query's position moved back by one block (q_off = -block:
    strictly earlier blocks), returned unnormalized as (acc, l, m); its
    own block is L x block pairs a head, elementwise (_own_rows), merged
    with that partial by their row statistics as ring attention merges
    its steps. K and V may have fewer heads than Q: the kernels take the
    clean ones as they came, the own-block passes a repeat of the noisy
    ones that XLA fuses into them. Dead tiles are neither fetched nor
    walked, so the score work is L^2 + O(L x tile) pairs a head, where
    the [2L, 2L] square has 4 L^2; nothing of [L, L] reaches HBM."""
    from . import pallas_attention
    half, groups = q.shape[0] // 2, _kv_groups(q, k)
    (qn, qc), (kn, kc), (vn, vc) = ((x[:half], x[half:]) for x in (q, k, v))
    kn, vn = _repeat_kv(kn, groups), _repeat_kv(vn, groups)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out_c, lse_c = pallas_attention._forward(qc, kc, vc, True,
                                             return_lse=True, block=block)
    acc1, l1, m1 = pallas_attention.flash_attention_block(
        qn, kc, vc, -block, 0, scale, True, block=block)
    l1, m1 = l1.transpose(0, 2, 1), m1.transpose(0, 2, 1)    # [B, L, H]
    s = _own_block_scores(qn, kn, block)                  # [block, B, L, H]
    m2 = s.max(0)
    m = jnp.maximum(m1, m2)
    p = jnp.exp(s - m)                  # already on the merged maximum
    a1 = jnp.exp(m1 - m)
    l = l1 * a1 + p.sum(0)
    acc2 = jnp.sum(p[..., None] * _own_rows(vn, block), axis=0)
    out_n = (acc1 * a1[..., None] + acc2) / l[..., None]
    lse_n = (m + jnp.log(l)).transpose(0, 2, 1)
    return (jnp.concatenate([out_n.astype(q.dtype), out_c]),
            jnp.concatenate([lse_n, lse_c]))


def _bd_flash_grad(q, k, v, o, lse, do, block: int):
    """(dQ, dK, dV) of _bd_flash from the saved (Out, LSE): the flash
    backward on each of the two kernel parts against the merged LSE, as
    the ring's backward runs it a shard, and the own-block part's few
    pairs elementwise; the clean keys' gradient is the sum of what the
    two halves sent them."""
    from . import pallas_attention
    half, groups = q.shape[0] // 2, _kv_groups(q, k)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    parts = ((x[:half], x[half:])
             for x in (q, k, v, do, lse, delta.transpose(0, 2, 1)))
    (qn, qc), (kn, kc), (vn, vc), (don, doc), (lse_n, lse_c), (dl_n, dl_c) \
        = parts
    kn, vn = _repeat_kv(kn, groups), _repeat_kv(vn, groups)
    bwd = functools.partial(pallas_attention.flash_attention_bwd_block,
                            k_off=0, scale=scale, causal=True, block=block)
    dq_c, dk_c, dv_c = bwd(qc, kc, vc, doc, lse_c, dl_c, q_off=0)
    dq_n, dk_c2, dv_c2 = bwd(qn, kc, vc, don, lse_n, dl_n, q_off=-block)

    p = jnp.exp(_own_block_scores(qn, kn, block)
                - lse_n.transpose(0, 2, 1))               # [block, B, L, H]
    dp = jnp.sum(don.astype(jnp.float32) * _own_rows(vn, block), axis=-1)
    ds = p * (dp - delta[:half]) * scale
    dq_own = jnp.sum(ds[..., None] * _own_rows(kn, block), axis=0)
    dk_n = _to_own_rows(ds, qn, block, groups)
    dv_n = _to_own_rows(p, don, block, groups)

    def add(x, y):
        return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(q.dtype)

    return (jnp.concatenate([add(dq_n, dq_own), dq_c]),
            jnp.concatenate([dk_n.astype(q.dtype), add(dk_c, dk_c2)]),
            jnp.concatenate([dv_n.astype(q.dtype), add(dv_c, dv_c2)]))


def _bd_takes_flash(ctx, op_, q, k, count=False):
    """(flash, repeated): whether this lowering runs on the flash
    kernels: use_flash True, or 'auto' and the rule of
    scaled_dot_product_attention (_flash_wins) on one stream's
    [B, L, H, D], and the kernels' gate passes that shape with the block
    length; and `_repeated_kv`'s ground for widening K and V to the
    query's heads first, None where they go on as they came. The op
    carries no shard_map of its own: a program planned over a mesh of
    several devices passes use_flash=False. `count` books the decision
    under op="block_diffusion_attention", a hit or the reason, as
    _sdpa_paths does: the forward op passes it."""
    from . import pallas_attention
    uf = op_.attr("use_flash", "auto")
    one = jax.ShapeDtypeStruct((q.shape[0] // 2,) + q.shape[1:], q.dtype)
    if not uf or (uf == "auto" and not _flash_wins(one)):
        return False, _repeated_kv(q, k, "einsum")
    repeated = _repeated_kv(q, k, "flash")
    kv = one if repeated else jax.ShapeDtypeStruct(
        one.shape[:2] + k.shape[2:], k.dtype)
    reason = pallas_attention.ineligible(one, kv, kv,
                                         block=op_.attr("block_length", 1))
    if count:
        kernel_choice.book(_BD_OP, reason)
    if reason is not None:
        return False, _repeated_kv(q, k, "einsum")
    return True, repeated


@op(_BD_OP, infer_shape=_sdpa_infer, grad=_sdpa_grad,
    kept_in_replay=_SDPA_KEPT)
def _block_diffusion_attention(ctx, op_, ins):
    """Attention of block-diffusion training (arXiv:2503.09573): every
    sequence runs as two streams of L positions, a noised copy and the
    clean one, Q/K/V [2B, L, H, D] with the B noisy streams first and
    their clean streams behind them (K and V may have fewer heads:
    grouped-query attention, as scaled_dot_product_attention, repeated
    or not by the same rule). With
    b(i) = i // block_length, a noisy query at i sees the noisy keys of
    its own block (both directions) and the clean keys of earlier blocks;
    a clean query sees the clean keys of blocks up to its own. One
    algorithm, two implementations chosen from the shapes
    (_bd_takes_flash): _bd_flash on the kernels, _bd_einsum elsewhere.
    Emits LSE [2B, H, L] float32 for the explicit backward; replayed in
    a recomputed segment it is handed both and runs nothing (_SDPA_KEPT)."""
    kept = handed_on(ctx, op_, ins)
    if kept is not None:
        return kept
    q, k, v = (jnp.asarray(ins[s][0]) for s in ("Q", "K", "V"))
    block = op_.attr("block_length", 1)
    assert q.shape[0] % 2 == 0 and q.shape[1] % block == 0, (q.shape, block)
    (q, k, v), restore = mxu_cast(ctx, q, k, v)
    groups = _kv_groups(q, k)
    flash, repeated = _bd_takes_flash(ctx, op_, q, k, count=True)
    _count_kv_groups(op_.type, groups, repeated)
    if repeated:
        k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    out, lse = (_bd_flash if flash else _bd_einsum)(q, k, v, block)
    if restore is not None:
        out = out.astype(restore)
    return {"Out": [out], "LSE": [lse]}


@op(_BD_OP + "_grad", grad=NO_GRAD, non_diff_inputs=("LSE",))
def _block_diffusion_attention_grad(ctx, op_, ins):
    """dQ/dK/dV of block_diffusion_attention: on the kernels from the
    saved (Out, LSE), no forward run again (_bd_flash_grad); the einsum
    path differentiates its forward under jax.vjp."""
    q, k, v, do = (jnp.asarray(ins[s][0]) for s in ("Q", "K", "V",
                                                    "Out@GRAD"))
    block = op_.attr("block_length", 1)
    (q, k, v, do), restore = mxu_cast(ctx, q, k, v, do)
    groups = _kv_groups(q, k)
    flash, repeated = _bd_takes_flash(ctx, op_, q, k)
    if repeated:
        k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    if flash:
        dq, dk, dv = _bd_flash_grad(
            q, k, v, jnp.asarray(ins["Out"][0]).astype(q.dtype),
            jnp.asarray(ins["LSE"][0]), do.astype(q.dtype), block)
    else:
        _, vjp_fn = jax.vjp(lambda a, b, c: _bd_einsum(a, b, c, block)[0],
                            q, k, v)
        dq, dk, dv = vjp_fn(do.astype(q.dtype))
    if repeated:
        dk, dv = _sum_kv_groups(dk, groups), _sum_kv_groups(dv, groups)
    if restore is not None:
        dq, dk, dv = (g.astype(restore) for g in (dq, dk, dv))
    return {name: [g] for name, g in (("Q@GRAD", dq), ("K@GRAD", dk),
                                      ("V@GRAD", dv))
            if name in op_.desc.outputs}


# --- mixture of experts ------------------------------------------------------

def _moe_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None:
        set_out(op_, block, "Out", xv.shape, xv.dtype)


@op("moe_ffn", infer_shape=_moe_infer)
def _moe_ffn(ctx, op_, ins):
    """Top-1 gated mixture-of-experts FFN in the GShard dispatch-einsum
    form (no 2018-reference analogue; the expert-parallel capability the
    brief requires). Tokens route to their top expert up to a fixed
    capacity C = ceil(N/E * capacity_factor); dispatch/combine are one-hot
    einsums, so when the expert weights W1 [E, D, F] / W2 [E, F, D] are
    sharded over an 'ep' mesh axis (parallel.shard_parameter), GSPMD
    partitions the expert matmuls and inserts the token all-to-all over
    ICI. Overflowed tokens pass through (residual), standard MoE practice.
    """
    x = jnp.asarray(ins["X"][0])              # [N, D]
    gw = jnp.asarray(ins["GateW"][0])         # [D, E]
    w1 = jnp.asarray(ins["W1"][0])            # [E, D, F]
    w2 = jnp.asarray(ins["W2"][0])            # [E, F, D]
    (x, gw, w1, w2), restore = mxu_cast(ctx, x, gw, w1, w2)
    n, d = x.shape
    e = w1.shape[0]
    cap_f = op_.attr("capacity_factor", 1.25)
    cap = max(int(np.ceil(n / e * cap_f)), 1)

    logits = x @ gw                            # [N, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top = jnp.argmax(probs, axis=-1)           # [N]
    top_p = jnp.max(probs, axis=-1)            # [N]
    onehot = jax.nn.one_hot(top, e, dtype=jnp.float32)   # [N, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # position in expert
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                            dtype=jnp.float32) * keep[..., None]
    dispatch = pos_oh                           # [N, E, C]

    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", expert_in, w1))
    expert_out = jnp.einsum("ecf,efd->ecd", h, w2)
    combine = dispatch * top_p[:, None, None].astype(jnp.float32)
    routed = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), expert_out)
    # overflowed / unrouted tokens pass through unchanged
    routed_mask = dispatch.sum(axis=(1, 2)).astype(x.dtype)[:, None]
    out = routed + x * (1.0 - routed_mask)
    if restore is not None:
        out = out.astype(restore)
    return {"Out": [out]}


def _hsigmoid_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None and xv.shape is not None:
        set_out(op_, block, "Cost", [xv.shape[0], 1], xv.dtype)


@op("hierarchical_sigmoid", infer_shape=_hsigmoid_infer,
    non_diff_inputs=("Label",))
def _hierarchical_sigmoid(ctx, op_, ins):
    """Hierarchical sigmoid over a complete binary code tree (reference
    gserver HierarchicalSigmoidLayer.cpp: codeLength = 1 + floor(log2(
    numClasses - 1)); per-class code bits walk the tree). Cost per sample =
    sum_j softplus(pre_j) - bit_j * pre_j over the label's path, which is
    -log P(label) under the tree factorization. Vectorized over a fixed
    max code length with a validity mask — no per-sample loops, MXU gemm
    for all path nodes at once."""
    x = jnp.asarray(ins["X"][0])                       # [B, F]
    w = jnp.asarray(ins["W"][0])                       # [C-1, F]
    label = jnp.asarray(ins["Label"][0]).reshape(-1)   # [B]
    bias = ins.get("Bias", [None])[0]
    num_classes = int(op_.attr("num_classes"))
    code_len = max(1, int(np.ceil(np.log2(max(num_classes, 2)))))

    c = (label + num_classes).astype(jnp.int32)        # SimpleCode basis
    js = jnp.arange(code_len)
    shifted = c[:, None] >> (js[None, :] + 1)          # [B, J]
    valid = (shifted >= 1).astype(x.dtype)
    idx = jnp.maximum(shifted - 1, 0)                  # node ids [B, J]
    bits = ((c[:, None] >> js[None, :]) & 1).astype(x.dtype)

    wn = w[idx]                                        # [B, J, F]
    pre = jnp.einsum("bf,bjf->bj", x, wn)
    if bias is not None:
        b = jnp.asarray(bias).reshape(-1)              # [C-1]
        pre = pre + b[idx]
    cost = (jax.nn.softplus(pre) - bits * pre) * valid
    return {"Cost": [jnp.sum(cost, axis=1, keepdims=True)]}
