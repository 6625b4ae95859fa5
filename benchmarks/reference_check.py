"""The comparison that decides `correct` for a training cell: the
program's first step against the family's plain float32 reference, on the
same weights and the same batch, outside the measured window.

What is compared, each to a tolerance the cell's file gives under
`reference` beside the measured deviation it was set from (a tolerance of
null is not held, and the file says why):

- `loss_rtol`: the first loss against the reference's forward pass;
- `grad_rtol`: the gradient the optimizer applied, read back from its
  accumulators after the step, against jax.grad of the reference's loss:
  the L2 norm of the difference over all parameters, over the norm of the
  reference's gradient. A wrong backward pass, a kernel computing
  something else or a lower compute precision moves this most;
- `grad_norm_rtol`: the norm of that gradient over all parameters against
  the reference's (its size, whatever its direction);
- `grad_tail_rtol`: as `grad_rtol`, over the last TAIL_TENSORS parameters
  alone: the head's weight and bias, nearest the loss, whose gradient
  says how far the features the head sees are off;
- `update_rtol`: the parameters after the step against the optimizer's
  rule (`optimizers/<name>.py`) applied in float32 to that gradient: the
  norm of the difference over the norm of the update.
"""

import importlib

import numpy as np


def rule_of(config):
    """The optimizer's plain rule, `optimizers/<config["optimizer"]>.py`."""
    return importlib.import_module(
        "benchmarks.optimizers." + config["optimizer"])


def state_names(program, rule):
    """{parameter: {slot: accumulator's name}} from the optimizer ops
    (`rule.OP_TYPE`) of the train program."""
    return {op.input("Param")[0]: {s: op.input(s)[0] for s in rule.SLOTS}
            for op in program.global_block().ops if op.type == rule.OP_TYPE}


def reference_step(family, config, params, feed):
    """(loss, [gradients]) of the family's float32 reference as host
    numpy. Compiled ahead and dropped with its buffers before the
    program's step is loaded, so the two never share the chip."""
    import functools
    import jax

    fn = jax.jit(jax.value_and_grad(
        functools.partial(family.reference_loss, config)))
    compiled = fn.lower(params, feed).compile()
    loss, grads = compiled(params, feed)
    return float(loss), [np.asarray(g) for g in grads]


TAIL_TENSORS = 2
HELD = {"loss_rtol": "loss_rel_diff", "grad_rtol": "grad_rel_err",
        "grad_norm_rtol": "grad_norm_rel_diff",
        "grad_tail_rtol": "grad_tail_rel_err",
        "update_rtol": "update_rel_err"}


def _norm2(x):
    x = np.asarray(x, np.float32).ravel()
    return float(np.dot(x, x))


def compare(rule, config, names, before, after, state, loss, ref_loss,
            ref_grads, tol):
    """The verdict on one first step. `before`/`after`: the parameters
    (host arrays, in `names` order) around the step; `state`:
    {name: {slot: array}} after it; `tol`: the cell's `reference`."""
    diff2, ref2, got2, upd_diff2, upd2 = [], [], [], 0.0, 0.0
    for name, p0, p1, g_ref in zip(names, before, after, ref_grads):
        g = np.asarray(rule.applied_gradient(config, state[name]), np.float32)
        diff2.append(_norm2(g - g_ref))
        ref2.append(_norm2(g_ref))
        got2.append(_norm2(g))
        want = rule.first_update(config, p0, g)
        upd_diff2 += _norm2(p1 - want)
        upd2 += _norm2(want - p0)

    def ratio(num, den):
        return (num / den) ** 0.5 if den > 0 else float("inf")

    most = int(np.argmax(diff2))    # where most of the gradient's error is
    found = {
        "first_loss": loss, "reference_loss": ref_loss,
        "loss_rel_diff": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel_err": ratio(sum(diff2), sum(ref2)),
        "grad_norm_rel_diff": abs(ratio(sum(got2), sum(ref2)) - 1.0),
        "grad_tail_rel_err": ratio(sum(diff2[-TAIL_TENSORS:]),
                                   sum(ref2[-TAIL_TENSORS:])),
        "grad_err_mostly_in": [names[most], ratio(diff2[most], sum(diff2))],
        "update_rel_err": ratio(upd_diff2, upd2),
        "tolerances": {k: tol[k] for k in HELD}}
    found["ok"] = all(found[measured] <= tol[k]
                      for k, measured in HELD.items() if tol[k] is not None)
    return found
