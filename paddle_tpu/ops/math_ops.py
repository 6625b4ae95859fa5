"""Math ops: GEMM, elementwise+broadcast, activations, reductions.

TPU-native lowerings of the reference ops (mul_op.cc, matmul_op.cc,
elementwise_*_op.cc + elementwise_op_function.h, activation_op.cc — 20+
activations, reduce_op.cc, sum_op.cc, mean_op.cc, cumsum_op.cc, cos_sim_op.cc,
norm ops). Matmuls map straight onto the MXU via jnp.matmul/einsum; elementwise
ops fuse into neighbours under XLA, so there is no hand-written fusion layer
like the reference's math functors (operators/math/math_function.*).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.desc import OpDesc
from ..framework.framework import Operator
from . import kernel_choice, sibling_products
from .registry import (NO_GRAD, generic_grad_lower, infer_grad_shapes, op,
                       register)
from .common import (SelectedRowsVal, maybe_dense, broadcast_y_to_x, in_var, matmul_shape, mxu_cast, out_var,
                     same_as_input, set_out)


# --- GEMM family ------------------------------------------------------------

def _flat2(x, num_col_dims):
    """Flatten to 2-D the way mul_op does (reference mul_op.cc): leading
    num_col_dims dims become rows, the rest columns."""
    shape = x.shape
    rows = int(np.prod(shape[:num_col_dims])) if num_col_dims else 1
    cols = int(np.prod(shape[num_col_dims:])) if num_col_dims < len(shape) else 1
    return x.reshape(rows, cols)


def _mul_infer(op_, block):
    xv, yv = in_var(op_, block, "X"), in_var(op_, block, "Y")
    if xv is None or yv is None or xv.shape is None or yv.shape is None:
        return
    xn = op_.attr("x_num_col_dims", 1)
    yn = op_.attr("y_num_col_dims", 1)
    set_out(op_, block, "Out",
            list(xv.shape[:xn]) + list(yv.shape[yn:]), xv.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cotangent_at(v, sharding):
    """v, and its cotangent constrained to `sharding` where it arrives:
    every product of the gradient reads that one array."""
    return v


_cotangent_at.defvjp(
    lambda v, sharding: (v, None),
    lambda sharding, _, g: (jax.lax.with_sharding_constraint(g, sharding),))


def _gather_once(ctx, op_, x):
    """`tensor_parallel.gather_once` for this product in this trace:
    nothing for a weight that is no parameter of the program, on the
    quantised route, or run eagerly (there is no partitioner). Books
    tp_gather_pinned_total{program, side}, one a product and side, on
    the forward trace."""
    program = getattr(ctx, "program", None)
    if getattr(program, "_mesh", None) is None or \
            getattr(ctx, "quant_mode", None) or \
            not isinstance(x, jax.core.Tracer) or \
            op_.attr("y_num_col_dims", 1) != 1:
        return frozenset(), None
    from ..parallel import tensor_parallel
    sides, whole = tensor_parallel.gather_once(
        program, op_.desc.input("Y")[0], x.shape[0])
    if sides and not kernel_choice.in_retrace():
        from .. import telemetry
        pinned = telemetry.counter(
            "tp_gather_pinned_total",
            "products whose activation operand (column-parallel) or "
            "output cotangent (row-parallel) is constrained so that it "
            "crosses the model axis once (tensor_parallel.gather_once)",
            labels=("program", "side"))
        for side in sides:
            pinned.labels(program=telemetry.program_label(program),
                          side=side).inc()
    return sides, whole


@op("mul", infer_shape=_mul_infer)
def _mul(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    xn = op_.attr("x_num_col_dims", 1)
    yn = op_.attr("y_num_col_dims", 1)
    (xf, yf), restore = mxu_cast(ctx, _flat2(x, xn), _flat2(y, yn))
    # both constraints in the op's own shape: on the flattened value a
    # reshape stands between the pinned array and its neighbours, and XLA
    # then fuses neither way across it (PERF.md section 6, PR 52)
    once, whole = _gather_once(ctx, op_, x)
    if "operand" in once:
        # pinned as [rows, d] the product stays 2-D and gelu leaves the up
        # projection's epilogue for a pass of its own: 10.7 ms more busy
        # time a step of gpt2-large.train-fsdp2-tp2
        xf = jax.lax.with_sharding_constraint(
            xf.reshape(x.shape), whole(x.ndim)).reshape(xf.shape)
    qmode = getattr(ctx, "quant_mode", None)
    if qmode:
        from .. import quant
        reason = quant.ineligible_matmul(xf, yf, qmode)
        if reason is None:
            quant.count_hit(op_.type)
            pre = quant.prequantized(ctx, op_.desc.inputs["Y"][0])
            out2d = quant.qmatmul(xf, yf, qmode, pre=pre)
        else:
            quant.count_fallback(op_.type, reason)
            out2d = jnp.matmul(xf, yf)
    else:
        out2d = jnp.matmul(xf, yf)
    out_shape = x.shape[:xn] + y.shape[yn:]
    if "cotangent" in once:
        # pinned as [rows, n] the grad-weight product no longer takes the
        # activation before it into its operand, and XLA keeps gelu's
        # output for it: +42 MB a layer
        out2d = _cotangent_at(out2d.reshape(out_shape), whole(len(out_shape)))
    if restore is not None:
        out2d = out2d.astype(restore)
    return {"Out": [out2d.reshape(out_shape)]}


def _mul_grad(ctx, op_, ins):
    """The generic vjp of `mul`, but for a product that
    `sibling_products.members` admits: its weight's gradient as ever, and
    in place of `dX` the pair (dOut, Y) left open, for the program's
    `sum` to fold with its siblings' and the first reader to contract
    once."""
    if not sibling_products.is_member(ctx, op_, ins):
        return generic_grad_lower(ctx, op_, ins)
    outs = {}
    rest = {s: ns for s, ns in op_.desc.outputs.items() if s != "X@GRAD"}
    if rest:
        view = Operator.__new__(Operator)
        view.block = getattr(op_, "block", None)
        view.desc = OpDesc(type=op_.type, inputs=dict(op_.desc.inputs),
                           outputs=rest, attrs=dict(op_.desc.attrs))
        outs = generic_grad_lower(ctx, view, ins)
    x, y = jnp.asarray(ins["X"][0]), jnp.asarray(ins["Y"][0])
    (xf, yf), _ = mxu_cast(ctx, _flat2(x, op_.attr("x_num_col_dims", 1)),
                           _flat2(y, 1))
    outs["X@GRAD"] = [sibling_products.open_pair(
        ctx, x, yf, ins["Out@GRAD"][0], jnp.result_type(xf, yf))]
    return outs


register("mul_grad", lower=_mul_grad, infer_shape=infer_grad_shapes,
         grad=NO_GRAD)


def _matmul_infer(op_, block):
    xv, yv = in_var(op_, block, "X"), in_var(op_, block, "Y")
    if xv is None or yv is None:
        return
    set_out(op_, block, "Out",
            matmul_shape(xv.shape and list(xv.shape), yv.shape and list(yv.shape),
                         op_.attr("transpose_X", False),
                         op_.attr("transpose_Y", False)),
            xv.dtype)


@op("matmul", infer_shape=_matmul_infer)
def _matmul(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    if op_.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if op_.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    (x, y), restore = mxu_cast(ctx, x, y)
    qmode = getattr(ctx, "quant_mode", None)
    if qmode:
        from .. import quant
        reason = quant.ineligible_matmul(x, y, qmode)
        if reason is None:
            quant.count_hit(op_.type)
            # the admission cache stores Y in [K, N] orientation, so a
            # transposed Y quantizes dynamically (prequantize skips it)
            pre = None if op_.attr("transpose_Y", False) else \
                quant.prequantized(ctx, op_.desc.inputs["Y"][0])
            out = quant.qmatmul(x, y, qmode, pre=pre)
        else:
            quant.count_fallback(op_.type, reason)
            out = jnp.matmul(x, y)
    else:
        out = jnp.matmul(x, y)
    if restore is not None:
        out = out.astype(restore)
    alpha = op_.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


def _bilinear_infer(op_, block):
    xv = in_var(op_, block, "X")
    wv = in_var(op_, block, "Weight")
    if xv is not None and xv.shape is not None and wv is not None \
            and wv.shape is not None:
        set_out(op_, block, "Out", [xv.shape[0], wv.shape[0]], xv.dtype)


@op("bilinear_tensor_product", infer_shape=_bilinear_infer)
def _bilinear_tensor_product(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])      # (B, M)
    y = jnp.asarray(ins["Y"][0])      # (B, N)
    w = jnp.asarray(ins["Weight"][0])  # (O, M, N)
    (x, y, w), restore = mxu_cast(ctx, x, y, w)
    out = jnp.einsum("bm,omn,bn->bo", x, w, y)
    if restore is not None:
        out = out.astype(restore)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out = out + jnp.asarray(ins["Bias"][0]).astype(out.dtype)
    return {"Out": [out]}


# --- elementwise with axis broadcast ---------------------------------------

_elementwise_fns = {
    "elementwise_add": jnp.add,
    "elementwise_sub": jnp.subtract,
    "elementwise_mul": jnp.multiply,
    "elementwise_div": jnp.divide,
    "elementwise_max": jnp.maximum,
    "elementwise_min": jnp.minimum,
    "elementwise_pow": jnp.power,
}


def _ew_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None:
        set_out(op_, block, "Out", xv.shape, xv.dtype)


def _make_ew(fn):
    def lower(ctx, op_, ins):
        x = jnp.asarray(ins["X"][0])
        axis = op_.attr("axis", -1)
        # channel-bias form (axis==1, 1-D Y) under the internal NHWC
        # convention (ops/layout.py): the channel axis is minor, so the
        # broadcast target moves to the last dim
        if axis == 1 and getattr(ins["Y"][0], "ndim", 0) == 1 and \
                ctx.layout_of(op_.desc.inputs["X"][0]) is not None:
            axis = x.ndim - 1
        y = broadcast_y_to_x(x, ins["Y"][0], axis)
        # AMP O2: an f32 operand (e.g. a master-weight bias) must not
        # promote a bf16 activation back to f32 — that would silently
        # re-materialize f32 tensors at every fc/conv bias add and forfeit
        # the halved HBM traffic. The cast is in-trace, so the bias grad
        # flows back to the f32 master copy through the astype vjp.
        if getattr(ctx, "amp_level", "O1") in ("O2", "O3") and \
                x.dtype == jnp.bfloat16 and y.dtype == jnp.float32:
            y = y.astype(x.dtype)
        return {"Out": [fn(x, y)]}
    return lower


for _name, _fn in _elementwise_fns.items():
    register(_name, lower=_make_ew(_fn), infer_shape=_ew_infer)


# --- activations (reference activation_op.cc) -------------------------------

def _softshrink(x, lam=0.5):
    return jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam, 0.0))


_activations = {
    "sigmoid": lambda x, a: jax.nn.sigmoid(x),
    "logsigmoid": lambda x, a: jax.nn.log_sigmoid(x),
    "exp": lambda x, a: jnp.exp(x),
    "relu": lambda x, a: jax.nn.relu(x),
    "tanh": lambda x, a: jnp.tanh(x),
    "tanh_shrink": lambda x, a: x - jnp.tanh(x),
    "softshrink": lambda x, a: _softshrink(x, a.attr("lambda", 0.5)),
    "hard_shrink": lambda x, a: jnp.where(
        jnp.abs(x) > a.attr("threshold", 0.5), x, 0.0),
    "sqrt": lambda x, a: jnp.sqrt(x),
    "abs": lambda x, a: jnp.abs(x),
    "ceil": lambda x, a: jnp.ceil(x),
    "floor": lambda x, a: jnp.floor(x),
    "round": lambda x, a: jnp.round(x),
    "reciprocal": lambda x, a: 1.0 / x,
    "log": lambda x, a: jnp.log(x),
    "square": lambda x, a: jnp.square(x),
    "softplus": lambda x, a: jax.nn.softplus(x),
    "softsign": lambda x, a: x / (1.0 + jnp.abs(x)),
    "brelu": lambda x, a: jnp.clip(x, a.attr("t_min", 0.0), a.attr("t_max", 24.0)),
    "leaky_relu": lambda x, a: jnp.where(x >= 0, x, a.attr("alpha", 0.02) * x),
    "soft_relu": lambda x, a: jnp.log1p(jnp.exp(
        jnp.clip(x, -a.attr("threshold", 40.0), a.attr("threshold", 40.0)))),
    "elu": lambda x, a: jnp.where(x >= 0, x, a.attr("alpha", 1.0)
                                  * (jnp.exp(x) - 1.0)),
    "relu6": lambda x, a: jnp.clip(x, 0.0, a.attr("threshold", 6.0)),
    "pow": lambda x, a: jnp.power(x, a.attr("factor", 1.0)),
    "stanh": lambda x, a: a.attr("scale_b", 1.7159) * jnp.tanh(
        a.attr("scale_a", 2.0 / 3.0) * x),
    "hard_sigmoid": lambda x, a: jnp.clip(
        a.attr("slope", 0.2) * x + a.attr("offset", 0.5), 0.0, 1.0),
    "swish": lambda x, a: x * jax.nn.sigmoid(a.attr("beta", 1.0) * x),
    "thresholded_relu": lambda x, a: jnp.where(
        x > a.attr("threshold", 1.0), x, 0.0),
    "gelu": lambda x, a: jax.nn.gelu(x, approximate=False),
    "silu": lambda x, a: jax.nn.silu(x),
    "relu2": lambda x, a: jnp.square(jax.nn.relu(x)),   # squared ReLU
}


@jax.custom_vjp
def _pinned(v):
    """v behind an optimization barrier: every reader reads the array and
    none can take its evaluation into an operand. The cotangent passes
    freely (a barrier's own transpose would pin it too, and the gradient
    product would write it out for a pass of its own: +7.6 % where this
    form reads +11.4 %, PERF.md section 6, PR 44)."""
    return jax.lax.optimization_barrier(v)


_pinned.defvjp(lambda v: (_pinned(v), None), lambda _, g: (g,))


def _erf_gelu(x, a):
    """jax.nn.gelu(x, approximate=False) term for term, its erfc pinned:
    the polynomial is evaluated once a layer and direction, in the dtype
    the op's arithmetic has it in, and 0.5 * x * erfc (two multiplies) is
    what a reader recomputes; the gradient reads the same array."""
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        return jax.nn.gelu(x, approximate=False)
    sqrt_half = np.sqrt(0.5).astype(x.dtype)
    return jnp.array(0.5 * x * _pinned(jax.lax.erfc(-x * sqrt_half)),
                     dtype=x.dtype)


# The activations dear enough to be evaluated once and kept, each as the
# same arithmetic with its dear term pinned: those whose evaluation costs
# more vector time an element than a product that takes it into an
# operand costs MXU time. Erf gelu is 144 float32 instructions an element
# on a v5e (no bf16 VPU), all but a few of them erfc's polynomial; left
# free, XLA clones it into the operand of every product that reads gelu's
# output and into the epilogue of the one that makes its gradient, and
# each of those runs at the vector unit's pace, 38-42 % of the MXU's
# (gpt2.train-t1024). What is kept is erfc and not gelu's output: that is
# what XLA keeps by itself under GSPMD's plan (gpt2-large.train-fsdp2-tp2,
# whose compiled step this leaves as it was), one array a layer beside
# the pre-activation where the output would be a second. A dozen vector
# operations an element (relu, relu2, silu, swish, tanh) recomputed in an
# operand are cheaper than 2 B written and read: not here.
KEPT_ACTS = {"gelu": _erf_gelu}


def _count_kept(name):
    """activation_kept_total{act}: one a lowering of a forward op (the
    gradient op's re-trace is silent, as for the kernels' counters)."""
    from .. import telemetry
    if not kernel_choice.in_retrace():
        telemetry.counter(
            "activation_kept_total",
            "lowerings of an activation whose dear term is pinned behind "
            "an optimization barrier, so it is evaluated once and not "
            "inside the products that read it",
            labels=("act",)).labels(act=name).inc()


def _make_act(name, fn):
    def lower(ctx, op_, ins):
        x = jnp.asarray(ins["X"][0])
        kept = KEPT_ACTS.get(name)
        if kept is not None:
            _count_kept(name)
            return {"Out": [kept(x, op_)]}
        return {"Out": [fn(x, op_)]}
    return lower


for _name, _fn in _activations.items():
    register(_name, lower=_make_act(_name, _fn), infer_shape=same_as_input())


# --- reductions -------------------------------------------------------------

def _reduce_dims(op_, ndim):
    if op_.attr("reduce_all", False):
        return tuple(range(ndim))
    dim = op_.attr("dim", [0])
    if isinstance(dim, int):
        dim = [dim]
    return tuple(d % ndim for d in dim)


def _reduce_infer(op_, block):
    iv = in_var(op_, block, "X")
    if iv is None or iv.shape is None:
        return
    nd = len(iv.shape)
    dims = _reduce_dims(op_, nd)
    keep = op_.attr("keep_dim", False)
    if op_.attr("reduce_all", False):
        shape = [1] * nd if keep else [1]
    else:
        shape = [1 if i in dims else d for i, d in enumerate(iv.shape)] if keep \
            else [d for i, d in enumerate(iv.shape) if i not in dims]
        shape = shape or [1]
    set_out(op_, block, "Out", shape, iv.dtype)


_reduce_fns = {
    "reduce_sum": jnp.sum, "reduce_mean": jnp.mean, "reduce_max": jnp.max,
    "reduce_min": jnp.min, "reduce_prod": jnp.prod,
}


def _make_reduce(fn):
    def lower(ctx, op_, ins):
        x = jnp.asarray(ins["X"][0])
        dims = _reduce_dims(op_, x.ndim)
        keep = op_.attr("keep_dim", False)
        out = fn(x, axis=dims, keepdims=keep)
        if out.ndim == 0:
            out = out.reshape(1)
        return {"Out": [out]}
    return lower


for _name, _fn in _reduce_fns.items():
    register(_name, lower=_make_reduce(_fn), infer_shape=_reduce_infer)


def _mean_infer(op_, block):
    iv = in_var(op_, block, "X")
    set_out(op_, block, "Out", [1], iv.dtype if iv else "float32")


@op("mean", infer_shape=_mean_infer)
def _mean(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    lengths = ctx.seq_len(op_.desc.inputs["X"][0])
    if lengths is not None and x.ndim >= 2:
        # padded sequence: mean over valid positions only — matches the
        # reference's mean over packed [sum_len, ...] rows
        t = x.shape[1]
        mask = (jnp.arange(t)[None, :] <
                jnp.asarray(lengths)[:, None]).astype(x.dtype)
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
        feat = 1
        for d in x.shape[2:]:
            feat *= d
        denom = jnp.maximum(mask.sum() * feat, 1.0)
        return {"Out": [(jnp.sum(x * m) / denom).reshape(1)]}
    return {"Out": [jnp.mean(x).reshape(1)]}


def _sum_infer(op_, block):
    iv = in_var(op_, block, "X", 0)
    if iv is not None:
        set_out(op_, block, "Out", iv.shape, iv.dtype)


@op("sum", infer_shape=_sum_infer)
def _sum(ctx, op_, ins):
    """Element sum with SelectedRows support (reference sum_op.cc handles
    dense+sparse mixes): all-sparse inputs concatenate rows/values (rows may
    repeat, like the reference's unmerged SelectedRows), a mix densifies."""
    raw = [x for x in ins["X"] if x is not None]
    if any(isinstance(x, sibling_products.OpenProducts) for x in raw):
        return {"Out": [sibling_products.fold(raw)]}
    if raw and all(isinstance(x, SelectedRowsVal) for x in raw):
        if len(raw) == 1:
            return {"Out": [raw[0]]}
        rows = jnp.concatenate([x.rows for x in raw])
        vals = jnp.concatenate([x.values for x in raw])
        return {"Out": [SelectedRowsVal(rows, vals, raw[0].height)]}
    xs = [jnp.asarray(maybe_dense(x)) for x in raw]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


@op("cumsum", infer_shape=same_as_input())
def _cumsum(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    axis = op_.attr("axis", -1)
    if op_.attr("reverse", False):
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if op_.attr("exclusive", False):
        # shift by one along axis: out[i] = sum of x[:i]
        pad = [(0, 0)] * x.ndim
        pad[axis] = (1, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, x.shape[axis])
        out = jnp.pad(out, pad)[tuple(sl)]
    if op_.attr("reverse", False):
        out = jnp.flip(out, axis)
    return {"Out": [out]}


# --- similarity / norms -----------------------------------------------------

def _cos_sim_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None and xv.shape is not None:
        set_out(op_, block, "Out", [xv.shape[0], 1], xv.dtype)
        set_out(op_, block, "XNorm", [xv.shape[0], 1], xv.dtype)
    yv = in_var(op_, block, "Y")
    if yv is not None and yv.shape is not None:
        set_out(op_, block, "YNorm", [yv.shape[0], 1], yv.dtype)


@op("cos_sim", infer_shape=_cos_sim_infer)
def _cos_sim(ctx, op_, ins):
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    xn = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=1, keepdims=True))
    out = jnp.sum(x * y, axis=1, keepdims=True) / (xn * yn)
    return {"Out": [out], "XNorm": [xn], "YNorm": [yn]}


@op("norm", infer_shape=same_as_input())
def _norm(ctx, op_, ins):
    # l2-normalize along axis (reference norm_op.cc used by l2_normalize)
    x = jnp.asarray(ins["X"][0])
    axis = op_.attr("axis", -1)
    eps = op_.attr("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / n], "Norm": [n]}
