"""An activation dear enough to be evaluated once and kept
(`math_ops.KEPT_ACTS`: erf gelu): its lowering pins its dear term, erfc,
behind an optimization barrier, so the products that read the op's
output read an array and two multiplies, and XLA cannot clone the
polynomial into their operands (PERF.md section 6, PR 44). The pin
changes no value, leaves the cheap activations' programs as they were,
and books activation_kept_total{act} once a lowering of a forward op."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import telemetry
from paddle_tpu.ops import math_ops


def _act_program(act, dtype, shape):
    """x -> cast -> act -> a weighted sum: (main, startup, loss, the
    activation's output, its input's gradient name)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=list(shape), dtype="float32",
                              append_batch_size=False)
        w = fluid.layers.data(name="w", shape=list(shape), dtype="float32",
                              append_batch_size=False)
        x.stop_gradient = False
        h = fluid.layers.cast(x, dtype)
        out = _unary(act, h)
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(fluid.layers.cast(out, "float32"), w))
        fluid.backward.append_backward(loss)
    return main, startup, loss, out, h.name + "@GRAD"


def _unary(op_type, x):
    from paddle_tpu.layer_helper import LayerHelper
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _feed(shape, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": (3.0 * rng.standard_normal(shape)).astype(np.float32),
            "w": rng.standard_normal(shape).astype(np.float32)}


def _run(act, dtype, shape):
    main, startup, loss, out, grad = _act_program(act, dtype, shape)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return [np.asarray(v) for v in exe.run(
        main, feed=_feed(shape), fetch_list=[out.name, grad, loss.name],
        return_numpy=False)]


@pytest.mark.parametrize("shape", [(2, 8, 3072), (3, 7, 333)],
                         ids=["ffn-width", "ragged"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_gelu_and_its_gradient_are_bit_equal_with_and_without_the_pin(
        dtype, shape, monkeypatch):
    """gelu and gelu_grad through the executor: the pinned lowering and
    the plain one (the rule switched off) give the same bits, in the
    cell's dtype and in float32, at the cell's FFN width and at a shape
    no tile divides."""
    kept = _run("gelu", dtype, shape)
    monkeypatch.setattr(math_ops, "KEPT_ACTS", {})
    plain = _run("gelu", dtype, shape)
    assert kept[0].dtype == plain[0].dtype and str(kept[0].dtype) == dtype
    # the op's output and its input's gradient. (The loss is not held to
    # this: a consumer XLA fuses the plain gelu into may read it before
    # its rounding to bf16, `xla_allow_excess_precision`; behind the pin
    # every reader sees the bf16 the op returns.)
    for a, b in zip(kept[:2], plain[:2]):
        assert a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert np.abs(kept[1].astype(np.float32)).max() > 0
    if dtype == "float32":
        assert kept[2].tobytes() == plain[2].tobytes()


@pytest.mark.parametrize("act", ["silu", "relu2", "gelu"])
def test_only_a_kept_activation_compiles_to_another_program(act, monkeypatch):
    """A cheap activation (a dozen vector operations an element: cheaper
    recomputed in an operand than 2 B written and read) compiles to the
    text it compiled to before the rule; gelu's step differs, by its
    barrier."""
    def lowered():
        with fluid.unique_name.guard():
            main, _, loss, _, _ = _act_program(act, "bfloat16", (2, 8, 384))
        exe = fluid.Executor(fluid.CPUPlace())
        fn = exe._make_step_fn(main, [loss.name],
                               exe._persistable_outputs(main), {})
        feed = _feed((2, 8, 384))
        return str(jax.make_jaxpr(fn)(feed, {}, np.uint32(0)))

    with_rule = lowered()
    monkeypatch.setattr(math_ops, "KEPT_ACTS", {})
    without = lowered()
    assert "optimization_barrier" not in without
    if act in ("silu", "relu2"):
        assert act not in math_ops.KEPT_ACTS
        assert with_rule == without
    else:
        assert "optimization_barrier" in with_rule
        assert with_rule != without


def _kept_counts():
    return dict(telemetry.read_series("activation_kept_total"))


def test_a_compiled_gelu_books_itself_once_and_other_programs_nothing():
    """activation_kept_total{act="gelu"}: one a lowering of a forward op;
    the gradient op's re-trace of the forward is silent, and a program
    without the op books nothing."""
    before = _kept_counts()
    _run("silu", "bfloat16", (2, 8, 128))
    _run("relu2", "bfloat16", (2, 8, 128))
    assert _kept_counts() == before
    _run("gelu", "bfloat16", (2, 8, 128))
    after = _kept_counts()
    assert after.pop("act=gelu") == before.get("act=gelu", 0) + 1
    assert after == {k: v for k, v in before.items() if k != "act=gelu"}


def test_the_rule_names_a_cost_class_of_registered_activations():
    """KEPT_ACTS is a subset of the activation table (a typo would switch
    the mechanism off in silence), and the cheap ones the expert cells
    and ResNet-50 run are not in it."""
    assert set(math_ops.KEPT_ACTS) <= set(math_ops._activations)
    assert not set(math_ops.KEPT_ACTS) & {"relu", "relu2", "silu", "swish", "tanh"}
    assert telemetry.METRIC_CATALOG["activation_kept_total"]["labels"] == (
        "act",)
