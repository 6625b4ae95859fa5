"""Sibling column-parallel products of one activation give their input
gradient as one contraction (ops/sibling_products.py, ISSUE 48): on four
of the harness's virtual devices, what GSPMD then reduces over the model
axis, what the step computes, and where the rule stays out."""

import re

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import models, telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import sibling_products
from paddle_tpu.parallel import planner, shard_feed, tensor_parallel
from paddle_tpu.parallel.mesh import make_mesh

B, T, D, VOCAB, LAYERS = 4, 32, 64, 101, 2
pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four (virtual) devices")


def _attention(amp=None):
    """A small transformer_lm: q, k and v read one layer_norm output."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, T], dtype="int64",
                                append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, T], dtype="int64",
                                append_batch_size=False)
        loss = models.transformer_lm(tok, lab, vocab_size=VOCAB, d_model=D,
                                     n_head=2, n_layer=LAYERS,
                                     use_flash=False)
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        if amp:
            opt = fluid.amp.decorate(opt, level=amp)
        opt.minimize(loss, startup_program=startup)
    rng = np.random.default_rng(7)
    feed = {"tok": rng.integers(0, VOCAB, (B, T)).astype(np.int32),
            "lab": rng.integers(0, VOCAB, (B, T)).astype(np.int32)}
    return main, startup, loss, feed


def _gated_ffn(amp=None):
    """Blocks of x += down(swish(gate(h)) * up(h)), h = layer_norm(x):
    gate and up read one activation, and no attention is near."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[-1, T, D], dtype="float32",
                              append_batch_size=False)
        y = fluid.layers.data(name="y", shape=[-1, T, D], dtype="float32",
                              append_batch_size=False)
        for _ in range(LAYERS):
            h = fluid.layers.layer_norm(x, begin_norm_axis=2)
            gate = fluid.layers.fc(h, 2 * D, num_flatten_dims=2, act="swish",
                                   bias_attr=False)
            up = fluid.layers.fc(h, 2 * D, num_flatten_dims=2,
                                 bias_attr=False)
            x = fluid.layers.elementwise_add(x, fluid.layers.fc(
                fluid.layers.elementwise_mul(gate, up), D,
                num_flatten_dims=2, bias_attr=False))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(x, y))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        if amp:
            opt = fluid.amp.decorate(opt, level=amp)
        opt.minimize(loss, startup_program=startup)
    rng = np.random.default_rng(7)
    feed = {"x": rng.standard_normal((B, T, D)).astype(np.float32),
            "y": rng.standard_normal((B, T, D)).astype(np.float32)}
    return main, startup, loss, feed


def _plan_gated(main, startup, mesh):
    """Megatron's specs written by hand (the planner's roles know no
    gated FFN): gate and up column-parallel, down row-parallel."""
    main._mesh = mesh
    names = set(mesh.axis_names)
    column = tuple(a if a in names else None for a in ("fsdp", "tp"))
    for p in main.global_block().all_parameters():
        if len(p.shape) == 2:
            tensor_parallel.shard_parameter(
                main, p.name, column if p.shape[1] == 2 * D else column[::-1])
    if "fsdp" in names:
        for n in ("x", "y"):
            shard_feed(main, n, ("fsdp", None, None))


def _plan_attention(main, startup, mesh):
    planner.plan(main, mesh, startup=startup)


# builder, how it is laid over a mesh, whole-activation tp all-reduces a
# layer with the rule (and without), those of the rest of the model
MODELS = {
    # out and down forward, up's and the merged q/k/v's input gradients
    # backward (q, k and v's apart: 6); the head's 101 columns stay whole
    "attention": (_attention, _plan_attention, 4, 6, 0),
    # down forward, the merged gate/up's input gradient backward (apart: 3)
    "gated_ffn": (_gated_ffn, _plan_gated, 2, 3, 0),
}


def _mesh(shape, axes):
    n = int(np.prod(shape))
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def _merged():
    return sum(v for k, v in telemetry.read_series(
        "sibling_products_merged_total").items() if "grad_input" in k)


def _step(build, lay, mesh, amp=None, fetch_grads=False):
    """One step in a scope of its own: (loss, {gradient name: array},
    the executor's account of the compiled step)."""
    main, startup, loss, feed = build(amp)
    if mesh is not None:
        lay(main, startup, mesh)
    grads = [p.name + "@GRAD" for p in main.global_block().all_parameters()] \
        if fetch_grads else []
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        out = exe.run(main, feed=feed, fetch_list=[loss] + grads)
        account = exe.step_account(main)
    return float(np.ravel(out[0])[0]), dict(zip(grads, out[1:])), account


def _whole_activations(account, rows):
    """How many arrays of a whole [B * T / fsdp, D] activation each tp
    all-reduce of the step carries."""
    whole = re.compile(r"\[(%d,%d|%d,%d,%d)\]" % (rows, D, rows // T, T, D))
    return [len(whole.findall(i.shape)) for i in account
            if i.kind == "all-reduce" and i.axis == "tp"]


@pytest.mark.parametrize("model", sorted(MODELS))
def test_input_gradients_of_siblings_cross_the_tp_axis_once(model):
    """The parent's step reduced 6 whole activations a layer over tp (3
    with a gated FFN): one for each sibling's input gradient, in a tuple
    of three (two). One contraction leaves 4 (2), none in a tuple, and
    the counter reads one a layer."""
    build, lay, per_layer, parent_per_layer, rest = MODELS[model]
    before = _merged()
    _, _, account = _step(build, lay, _mesh((2, 2), ("fsdp", "tp")))
    carried = _whole_activations(account, B * T // 2)
    assert sum(carried) == per_layer * LAYERS + rest
    assert sum(carried) < parent_per_layer * LAYERS + rest
    assert max(carried) == 1
    assert _merged() - before == LAYERS


@pytest.mark.parametrize("amp", [None, "O2"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_planned_step_computes_what_one_device_does(model, amp):
    """Loss and every parameter's gradient after one step: the planned
    program with the merged contraction against the same program on one
    device. In float32 element by element, to the planned-step tests'
    tolerance (tests/test_planner.py); under AMP O2 the products round to
    bf16 in another order, and the gradient is held as one vector."""
    build, lay, *_ = MODELS[model]
    want_loss, want, _ = _step(build, lay, None, amp, fetch_grads=True)
    before = _merged()
    got_loss, got, _ = _step(build, lay, _mesh((2, 2), ("fsdp", "tp")), amp,
                             fetch_grads=True)
    assert _merged() - before == LAYERS
    np.testing.assert_allclose(got_loss, want_loss,
                               rtol=2e-3 if amp else 2e-4)
    assert sorted(got) == sorted(want) and got
    if amp:
        # (the benchmark's grad_rel_err: k's bias has no gradient but
        # rounding's, so tensor by tensor says nothing)
        flat = np.concatenate([np.ravel(got[n] - g) for n, g in want.items()])
        ref = np.concatenate([np.ravel(g) for g in want.values()])
        assert np.linalg.norm(flat) < 0.02 * np.linalg.norm(ref)
        return
    for name, g in want.items():
        np.testing.assert_allclose(got[name], g, rtol=2e-3, atol=2e-4,
                                   err_msg=name)


def _lowered(build, lay, mesh):
    main, startup, loss, feed = build()
    if mesh is not None:
        lay(main, startup, mesh)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        compiled, feed_vals, state_vals, rng = exe._aot_block(
            main, feed, [loss], scope)
    return compiled.fn.lower(feed_vals, state_vals, np.uint32(rng)).as_text()


@pytest.mark.parametrize("mesh", ["none", "fsdp_alone", "tp_of_one"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_rule_stays_out_where_no_model_axis_splits_the_weights(
        model, mesh, monkeypatch):
    """No mesh, fsdp alone, a tp axis of size 1: nothing is merged and
    the lowered step is, text for text, the one traced with the matcher's
    table emptied."""
    build, lay, *_ = MODELS[model]
    on = {"none": lambda: None,
          "fsdp_alone": lambda: _mesh((2,), ("fsdp",)),
          "tp_of_one": lambda: _mesh((2, 1), ("fsdp", "tp"))}[mesh]
    before = _merged()
    with_rule = _lowered(build, lay, on())
    assert _merged() == before
    monkeypatch.setattr(sibling_products, "SIBLING_OPS", frozenset())
    assert _lowered(build, lay, on()) == with_rule


def test_emptied_table_traces_the_parents_step(monkeypatch):
    """The control of the test above: on fsdp x tp the two texts differ,
    and with the table emptied nothing is counted."""
    build, lay, *_ = MODELS["attention"]
    with_rule = _lowered(build, lay, _mesh((2, 2), ("fsdp", "tp")))
    monkeypatch.setattr(sibling_products, "SIBLING_OPS", frozenset())
    before = _merged()
    assert _lowered(build, lay, _mesh((2, 2), ("fsdp", "tp"))) != with_rule
    assert _merged() == before


def test_open_value_read_by_anything_but_sum_is_the_array():
    """fold and close by hand: pairs of two shapes and a dense term. One
    contraction a class of equal shapes, the same number as the separate
    products added up."""
    rng = np.random.default_rng(0)
    like = jax.ShapeDtypeStruct((2, 3, 8), np.float32)

    def pair(n, plus=None):
        dy = rng.standard_normal((6, n)).astype(np.float32)
        w = rng.standard_normal((8, n)).astype(np.float32)
        return sibling_products.OpenProducts(((dy, w),), like, "p", plus), \
            (dy @ w.T).reshape(like.shape)

    dense = rng.standard_normal(like.shape).astype(np.float32)
    (a, da), (b, db), (c, dc) = pair(4), pair(4), pair(5)
    series = "program=p,direction=grad_input"
    before = telemetry.read_series("sibling_products_merged_total").get(
        series, 0)
    folded = sibling_products.fold([a, dense, b, c])
    assert folded.shape == like.shape and folded.dtype == like.dtype
    np.testing.assert_allclose(folded.close(), da + db + dc + dense,
                               rtol=1e-5, atol=1e-5)
    assert telemetry.read_series("sibling_products_merged_total")[
        series] == before + 1       # the class of two; c's is a product
