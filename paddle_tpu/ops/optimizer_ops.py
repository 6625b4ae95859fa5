"""Optimizer update ops (reference: sgd_op.cc, momentum_op.cc, adam_op.cc,
adamax_op.cc, adagrad_op.cc, decayed_adagrad_op.cc, adadelta_op.cc,
rmsprop_op.cc, ftrl_op.cc, proximal_gd_op.cc, proximal_adagrad_op.cc).

Like the reference, optimizer updates are ops in the program: outputs alias
the parameter/accumulator input names, so under the jitted whole-block
executor the updates fuse with the backward pass and parameters stay resident
in HBM (buffer donation in executor.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from .registry import NO_GRAD, op
from . import sparse_ops
from .common import SelectedRowsVal, maybe_dense, in_var, set_out


def _param_out_infer(*pairs):
    def infer(op_, block):
        for in_slot, out_slot in pairs:
            iv = in_var(op_, block, in_slot)
            if iv is not None:
                set_out(op_, block, out_slot, iv.shape, iv.dtype)
    return infer


def _lr(ins):
    return jnp.asarray(ins["LearningRate"][0]).reshape(())


def _param_grad(ins, op_type=None):
    """(param, grad) with the grad upcast to the param dtype: fp32
    master-weight updates under AMP O2 receive bf16 grads, which must be
    upcast before any arithmetic so lr*g and accumulators stay full
    precision. SelectedRows grads densify here, COUNTED: pass the op type
    so `sparse_densify_fallback_total{op,reason}` attributes the cliff —
    `no_sparse_kernel` for optimizers outside sparse_ops.SPARSE_APPLY_OPS
    (the reference registers SelectedRows kernels only for sgd/momentum/
    adam), `gated_off` when PADDLE_TPU_SPARSE_APPLY=0 disabled a capable
    one."""
    p = jnp.asarray(ins["Param"][0])
    g0 = ins["Grad"][0]
    if isinstance(g0, SelectedRowsVal) and op_type is not None:
        reason = ("gated_off" if op_type in sparse_ops.SPARSE_APPLY_OPS
                  else "no_sparse_kernel")
        sparse_ops.count_densify(op_type, reason)
    return p, jnp.asarray(maybe_dense(g0)).astype(p.dtype)


def _sparse_ready(ins):
    return (isinstance(ins["Grad"][0], SelectedRowsVal)
            and sparse_ops.sparse_apply_enabled())


def _pname(op_):
    names = op_.input("Param")
    return names[0] if names else None


# Dense update math, shared by the per-param lowerings below and the
# bucketed fused apply (ops/fusion.py), which calls it once per member
# tensor — the same expression on the same shapes, so the fused and the
# per-op traces agree bit for bit (the fused optimizer parity tests).
# Each output takes its input's dtype (the f32 learning rate would
# promote a bf16 parameter's update to f32), so it aliases the donated
# state and the next step sees the dtypes this one was compiled for.

# Beside each rule stands the step it takes (`p_out = p - step`), written
# over what the update leaves behind: the gradient the rule was given, the
# new moments, the scalars the rule read. The rule subtracts it, and the
# dynamics table (dynamics.py, layer 1) takes the update's norm from it,
# so nothing reads a parameter's old value behind its update.

def sgd_step(g, lr):
    return lr * g


def sgd_dense(p, g, lr):
    return (p - sgd_step(g, lr)).astype(p.dtype)


def momentum_step(g, v_new, lr, mu, use_nesterov):
    if use_nesterov:
        return lr * (g + mu * v_new)
    return lr * v_new


def momentum_dense(p, g, v, lr, mu, use_nesterov):
    v_out = mu * v + g
    p_out = p - momentum_step(g, v_out, lr, mu, use_nesterov)
    return p_out.astype(p.dtype), v_out.astype(v.dtype)


def adam_step(m1_new, m2_new, lr, eps, b1p, b2p):
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    return lr_t * m1_new / (jnp.sqrt(m2_new) + eps)


def adam_dense(p, g, m1, m2, lr, b1, b2, eps, b1p, b2p):
    m1o = b1 * m1 + (1 - b1) * g
    m2o = b2 * m2 + (1 - b2) * g * g
    po = p - adam_step(m1o, m2o, lr, eps, b1p, b2p)
    return po.astype(p.dtype), m1o.astype(m1.dtype), m2o.astype(m2.dtype)


def step_reads(op_):
    """What `dense_step` reads for the rule `op_`: (output slots whose new
    values it takes, whether it takes the rule's `Grad`, the scalar input
    slots in the order `dense_step` wants them); None for a rule with no
    step of its own above."""
    if op_.type == "sgd":
        return (), True, ("LearningRate",)
    if op_.type == "momentum":
        return (("VelocityOut",), bool(op_.attr("use_nesterov", False)),
                ("LearningRate",))
    if op_.type == "adam":
        return (("Moment1Out", "Moment2Out"), False,
                ("LearningRate", "Beta1Pow", "Beta2Pow"))
    return None


def dense_step(op_, g, new, scalars):
    """The step the dense rule `op_` took, from the values `step_reads`
    names: `g` (None where not read), `new` and `scalars` in its order."""
    lr, *pows = (jnp.asarray(s).reshape(()) for s in scalars)
    if op_.type == "sgd":
        return sgd_step(g, lr)
    if op_.type == "momentum":
        return momentum_step(g, new[0], lr, op_.attr("mu"),
                             op_.attr("use_nesterov", False))
    return adam_step(new[0], new[1], lr, op_.attr("epsilon", 1e-8), *pows)


@op("sgd", grad=NO_GRAD, infer_shape=_param_out_infer(("Param", "ParamOut")))
def _sgd(ctx, op_, ins):
    if _sparse_ready(ins):
        # scatter-apply kernel (reference sgd_op.h SelectedRows branch /
        # selected_rows_functor.cc), merge-first so duplicate ids sum
        # exactly like the dense accumulation
        p = jnp.asarray(ins["Param"][0])
        po = sparse_ops.sgd_apply(p, _lr(ins), ins["Grad"][0])
        po = sparse_ops.pin_table(ctx.program, _pname(op_), po)
        return {"ParamOut": [po]}
    p, g = _param_grad(ins, "sgd")
    return {"ParamOut": [sgd_dense(p, g, _lr(ins))]}


@op("momentum", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"),
                                 ("Velocity", "VelocityOut")))
def _momentum(ctx, op_, ins):
    mu = op_.attr("mu")
    if _sparse_ready(ins):
        # scatter-apply kernel: velocity decays + param moves only on
        # the gradient's rows (lazy semantics matching sparse adam below)
        p = jnp.asarray(ins["Param"][0])
        v = jnp.asarray(ins["Velocity"][0])
        po, vo = sparse_ops.momentum_apply(
            p, v, _lr(ins), mu, op_.attr("use_nesterov", False),
            ins["Grad"][0])
        po, vo = sparse_ops.pin_table(ctx.program, _pname(op_), po, vo)
        return {"ParamOut": [po], "VelocityOut": [vo]}
    p, g = _param_grad(ins, "momentum")
    v = jnp.asarray(ins["Velocity"][0])
    p_out, v_out = momentum_dense(p, g, v, _lr(ins), mu,
                                  op_.attr("use_nesterov", False))
    return {"ParamOut": [p_out], "VelocityOut": [v_out]}


@op("adam", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment1", "Moment1Out"),
                                 ("Moment2", "Moment2Out")))
def _adam(ctx, op_, ins):
    b1 = op_.attr("beta1", 0.9)
    b2 = op_.attr("beta2", 0.999)
    eps = op_.attr("epsilon", 1e-8)
    b1p = jnp.asarray(ins["Beta1Pow"][0]).reshape(())
    b2p = jnp.asarray(ins["Beta2Pow"][0]).reshape(())
    if _sparse_ready(ins):
        # scatter-apply kernel (reference adam_op.h SparseAdamFunctor):
        # moments/param update only the gradient's rows; untouched rows
        # keep stale moments, exactly like the reference. O(K*D) instead
        # of the O(V*D) densified update — the difference between an
        # embedding model training at batch cost vs vocab cost.
        p = jnp.asarray(ins["Param"][0])
        m1 = jnp.asarray(ins["Moment1"][0])
        m2 = jnp.asarray(ins["Moment2"][0])
        po, m1o, m2o = sparse_ops.adam_apply(
            p, m1, m2, _lr(ins), b1, b2, eps, b1p, b2p, ins["Grad"][0])
        po, m1o, m2o = sparse_ops.pin_table(
            ctx.program, _pname(op_), po, m1o, m2o)
        return {"ParamOut": [po], "Moment1Out": [m1o],
                "Moment2Out": [m2o]}
    p, g = _param_grad(ins, "adam")
    m1 = jnp.asarray(ins["Moment1"][0])
    m2 = jnp.asarray(ins["Moment2"][0])
    po, m1o, m2o = adam_dense(p, g, m1, m2, _lr(ins), b1, b2, eps,
                              b1p, b2p)
    return {"ParamOut": [po], "Moment1Out": [m1o], "Moment2Out": [m2o]}


@op("adamax", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment", "MomentOut"),
                                 ("InfNorm", "InfNormOut")))
def _adamax(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    m = jnp.asarray(ins["Moment"][0])
    u = jnp.asarray(ins["InfNorm"][0])
    b1p = jnp.asarray(ins["Beta1Pow"][0]).reshape(())
    b1 = op_.attr("beta1", 0.9)
    b2 = op_.attr("beta2", 0.999)
    eps = op_.attr("epsilon", 1e-8)
    mo = b1 * m + (1 - b1) * g
    uo = jnp.maximum(b2 * u, jnp.abs(g))
    po = p - (_lr(ins) / (1 - b1p)) * mo / (uo + eps)
    return {"ParamOut": [po], "MomentOut": [mo], "InfNormOut": [uo]}


@op("adagrad", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment", "MomentOut")))
def _adagrad(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    m = jnp.asarray(ins["Moment"][0])
    eps = op_.attr("epsilon", 1e-6)
    mo = m + g * g
    po = p - _lr(ins) * g / (jnp.sqrt(mo) + eps)
    return {"ParamOut": [po], "MomentOut": [mo]}


@op("decayed_adagrad", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment", "MomentOut")))
def _decayed_adagrad(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    m = jnp.asarray(ins["Moment"][0])
    decay = op_.attr("decay", 0.95)
    eps = op_.attr("epsilon", 1e-6)
    mo = decay * m + (1 - decay) * g * g
    po = p - _lr(ins) * g / (jnp.sqrt(mo) + eps)
    return {"ParamOut": [po], "MomentOut": [mo]}


@op("adadelta", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"),
                                 ("AvgSquaredGrad", "AvgSquaredGradOut"),
                                 ("AvgSquaredUpdate", "AvgSquaredUpdateOut")))
def _adadelta(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    ag = jnp.asarray(ins["AvgSquaredGrad"][0])
    au = jnp.asarray(ins["AvgSquaredUpdate"][0])
    rho = op_.attr("rho", 0.95)
    eps = op_.attr("epsilon", 1e-6)
    ago = rho * ag + (1 - rho) * g * g
    upd = -jnp.sqrt((au + eps) / (ago + eps)) * g
    auo = rho * au + (1 - rho) * upd * upd
    return {"ParamOut": [p + upd], "AvgSquaredGradOut": [ago],
            "AvgSquaredUpdateOut": [auo]}


@op("rmsprop", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment", "MomentOut"),
                                 ("MeanSquare", "MeanSquareOut")))
def _rmsprop(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    mom = jnp.asarray(ins["Moment"][0])
    ms = jnp.asarray(ins["MeanSquare"][0])
    rho = op_.attr("decay", 0.9)
    eps = op_.attr("epsilon", 1e-10)
    mu = op_.attr("momentum", 0.0)
    mso = rho * ms + (1 - rho) * g * g
    momo = mu * mom + _lr(ins) * g / jnp.sqrt(mso + eps)
    return {"ParamOut": [p - momo], "MomentOut": [momo], "MeanSquareOut": [mso]}


@op("ftrl", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"),
                                 ("SquaredAccumulator", "SquaredAccumOut"),
                                 ("LinearAccumulator", "LinearAccumOut")))
def _ftrl(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    sq = jnp.asarray(ins["SquaredAccumulator"][0])
    lin = jnp.asarray(ins["LinearAccumulator"][0])
    l1 = op_.attr("l1", 0.0)
    l2 = op_.attr("l2", 0.0)
    power = op_.attr("lr_power", -0.5)
    lr = _lr(ins)
    new_sq = sq + g * g
    if power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -power) - jnp.power(sq, -power)) / lr
    new_lin = lin + g - sigma * p
    if power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -power) / lr + 2 * l2
    pre = jnp.clip(new_lin, -l1, l1) - new_lin
    po = pre / denom
    return {"ParamOut": [po], "SquaredAccumOut": [new_sq],
            "LinearAccumOut": [new_lin]}


@op("proximal_gd", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut")))
def _proximal_gd(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    l1 = op_.attr("l1", 0.0)
    l2 = op_.attr("l2", 0.0)
    lr = _lr(ins)
    prox = p - lr * g
    po = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) \
        / (1.0 + lr * l2)
    return {"ParamOut": [po]}


@op("proximal_adagrad", grad=NO_GRAD,
    infer_shape=_param_out_infer(("Param", "ParamOut"), ("Moment", "MomentOut")))
def _proximal_adagrad(ctx, op_, ins):
    p, g = _param_grad(ins, op_.type)
    m = jnp.asarray(ins["Moment"][0])
    l1 = op_.attr("l1", 0.0)
    l2 = op_.attr("l2", 0.0)
    mo = m + g * g
    lr = _lr(ins) / jnp.sqrt(mo + 1e-12)
    prox = p - lr * g
    po = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) \
        / (1.0 + lr * l2)
    return {"ParamOut": [po], "MomentOut": [mo]}
