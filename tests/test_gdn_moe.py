"""The layers of a decoder of Gated DeltaNet mixers three to one with
gated grouped-query attention, softmax top-k experts and a gated shared
expert (models.gdn_moe_lm: Qwen3-Next-80B-A3B-Instruct's block), at tiny
sizes on the CPU: the whole tiny model, loss, every gradient and one Adam
step, against benchmarks/families/qwen3_next.py::reference_loss, replayed
or not; the program the builder writes from `full_attention_interval`;
the scopes and the counters; rotary_embedding's two ends, rms_norm's two
new forms and moe_block's shared gate against their equations; the
sixteen shares of an expert layer; and that calls without the new
arguments build the ops they built."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward
from paddle_tpu import executor as executor_mod
from paddle_tpu import layers, telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import NAME_SCOPE_ATTR, grad_var_name

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-qwen3-next"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the whole tiny model against the reference ---------------------------

VARIANTS = {"as_published": {}, "nothing_replayed": {"recompute": False},
            "weights_not_normalised": {"norm_topk_prob": False},
            "the_first_share": {"expert_offset": 0},
            "the_second_period": {"layers_held": [4, 5, 6, 7]},
            "attention_first": {"layers_held": [3, 4],
                                "num_hidden_layers": 2}}


@functools.lru_cache(maxsize=None)
def loss_and_gradients(variant):
    """(names, the program's loss and gradients, the reference's) of the
    tiny model under VARIANTS[variant], one float32 step from a fixed
    start; the norm weights (zeros as published: 1 + w) are moved off
    zero first, so that the unit offset is told from a plain weight."""
    config, family = tiny(**VARIANTS[variant])
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    rng = np.random.default_rng(11)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 4242)
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()
                 if p.trainable]
        for n in names:
            if n.startswith("rms_norm"):
                scope.set_var(n, jnp.asarray(
                    np.asarray(scope.find_var(n))
                    + 0.3 * rng.standard_normal(
                        np.asarray(scope.find_var(n)).shape), jnp.float32))
        params = [jnp.asarray(scope.find_var(n)) for n in names]
        got, *grads = exe.run(
            main, feed=feed,
            fetch_list=[loss] + [grad_var_name(n) for n in names])
        exe.close()
    want = jax.value_and_grad(
        lambda p: family.reference_loss(config, p, feed))(params)
    return names, (float(np.ravel(got)[0]), grads), want


@pytest.mark.parametrize("variant", VARIANTS)
def test_tiny_model_against_the_reference_in_float32(variant):
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry, the program's fetched gradients against jax.grad of
    the reference on the same weights and batch, at T = 64, 2 key heads
    under 4 value heads of 16, 4 query heads over 2 key/value heads, 4 of
    16 experts held under top 4."""
    names, (got, grads), (want, want_grads) = loss_and_gradients(variant)
    config, _ = tiny(**VARIANTS[variant])
    linear = sum((l + 1) % 4 != 0 for l in config["layers_held"])
    full = len(config["layers_held"]) - linear
    # the embedding; a Gated DeltaNet layer 1 + 13 + 1 + 8, the attention
    # layer 1 + 7 + 1 + 8 (no router bias among them); the final norm and
    # the head
    assert len(names) == 1 + linear * 23 + full * 17 + 2
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_a_replayed_layer_changes_no_value():
    """With and without checkpoints the loss and every gradient are the
    same to float32's rounding (the compiler fuses the forward's ops with
    other neighbours where a barrier stands behind them)."""
    (_, with_, _), (_, without, _) = (
        loss_and_gradients(v) for v in ("as_published", "nothing_replayed"))
    assert abs(with_[0] - without[0]) <= 2e-7 * without[0]
    for a, b in zip(with_[1], without[1]):
        close(a, b, tol=1e-5)


@pytest.mark.parametrize("amp_level,loss_tol,grad_tol", [
    (None, 1e-6, 1e-5), ("O2", 2e-4, 0.03)])
def test_first_step_and_one_adam_step(amp_level, loss_tol, grad_tol):
    """reference_check.compare on the first step: the loss, the gradient
    Adam applied (read back from its accumulators), its norm, the tail
    and the parameters it left, float32 to rounding, AMP O2 to bf16 (CPU,
    measured: loss 1.7e-5, gradient 1.3e-2, its norm 1.1e-4, tail 5.8e-3,
    update 1.0e-5)."""
    found, _, names = first_step(amp_level, TINY)
    assert len(names) == 1 + 3 * 23 + 17 + 2
    assert found["loss_rel_diff"] <= loss_tol
    assert found["grad_rel_err"] <= grad_tol
    assert found["grad_tail_rel_err"] <= grad_tol
    assert found["grad_norm_rel_diff"] <= max(grad_tol / 3, 1e-5)
    assert found["update_rel_err"] <= (1e-3 if amp_level else 2e-5)


# --- 2. the program -----------------------------------------------------------

def test_the_model_is_built_from_the_interval():
    """A held layer's mixer is its published index's: every fourth
    softmax attention, the rest Gated DeltaNet. The delta rule's op reads
    a gate a HEAD and q, k at the key heads' count; the head norm has its
    gate behind it and a plain weight, every other norm the unit offset;
    the attention layer rotates the FIRST quarter of a head at theta 1e7
    and gates its output; every router is a softmax with a balancing
    rule; three layers replayed."""
    from paddle_tpu.models import gdn_moe as model
    assert model.mixer_kinds(range(8), 4) == [model.LINEAR] * 3 \
        + [model.FULL] + [model.LINEAR] * 3 + [model.FULL]
    config, family = tiny()
    main, startup, loss = family.build(config)
    block = main.global_block()
    forward = [op for op in block.ops if not op.type.endswith("_grad")
               and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    kinds = [op.type for op in forward
             if op.type in ("kda_scan", "scaled_dot_product_attention")]
    assert kinds == ["kda_scan"] * 3 + ["scaled_dot_product_attention"]

    def shape(op, slot):
        return tuple(block.var(op.input(slot)[0]).shape[1:])

    for op in forward:
        if op.type == "kda_scan":
            assert shape(op, "Q") == shape(op, "K") == (64, 2, 16)
            assert shape(op, "V") == (64, 4, 16)
            assert shape(op, "Gate") == shape(op, "Beta") == (64, 4)
            assert shape(op, "DtBias") == shape(op, "ALog") == ()
            assert block.var(op.input("DtBias")[0]).shape == (4,)
            assert op.attr("chunk_size") == 16
        if op.type == "causal_conv1d":
            assert sorted(op.desc.inputs) == ["Filter", "X"]
        if op.type == "rotary_embedding":
            assert (op.attr("rotate_first"), op.attr("rotary_dims"),
                    op.attr("theta")) == (True, 4, 1e7)
        if op.type == "moe_router":
            assert op.attr("scoring") == "softmax" and op.attr("top_k") == 4
    norms = [op for op in forward if op.type == "rms_norm"]
    behind = [op for op in norms if op.desc.attrs.get("gate_behind")]
    assert len(behind) == 3 and all(
        "unit_offset" not in op.desc.attrs and op.input("Gate")
        for op in behind)
    # two a layer, the query's and the key's, and the final one
    assert len([op for op in norms if op.desc.attrs.get("unit_offset")]) \
        == 2 * 4 + 2 + 1 == len(norms) - 3
    attention, = [op for op in forward
                  if op.type == "scaled_dot_product_attention"]
    assert shape(attention, "Q") == (64, 4, 16)
    assert shape(attention, "K") == shape(attention, "V") == (64, 2, 16)
    assert attention.attr("causal") is True
    # segment 0 is the embedding's; the last layer's runs once
    assert sorted(backward.replayed_ops(main)) == [1, 2, 3]
    rules = [op for op in block.ops if op.type == "moe_balance_bias"]
    assert len(rules) == 4
    marks = main._telemetry_fetch_extra
    assert set(marks) == {"loss", "moe_rows_routed", "moe_rows_combined",
                          "moe_load_max_over_mean", "moe_rows_handled"}


def test_the_scopes_hold_the_mixers_and_the_counters_book_the_form():
    """Every op of a Gated DeltaNet mixer and its gradient is under
    `gdn_mixer`, the attention's norms, rotations, op and gate under
    `gated_attention`, the expert layer under `moe_block`; a compile
    books one head-decay lowering a layer with the group ratio and one a
    replayed layer, and the gradient's re-trace none."""
    from paddle_tpu.models import gdn_moe as model
    config, family = tiny()
    main, startup, loss = family.build(config)
    scopes = {}
    for op in main.global_block().ops:
        scopes.setdefault(op.type, set()).add(
            op.desc.attrs.get(NAME_SCOPE_ATTR))
    assert scopes["kda_scan"] == scopes["kda_scan_grad"] \
        == scopes["causal_conv1d"] == {"/gdn_mixer/"}
    inside = f"/{model.ATTENTION_SCOPE}/"
    assert scopes["scaled_dot_product_attention"] == {inside}
    assert scopes["rotary_embedding"] == {inside + "rotary_embedding/"}
    assert inside in scopes["sigmoid"] and inside in scopes["elementwise_mul"]
    assert scopes["moe_experts"] == {"/moe_block/"}

    def booked(name):
        return dict(telemetry.snapshot()["counters"].get(name, {}))

    before = {n: booked(n) for n in ("kda_scan_total",
                                     "kda_scan_head_decay_total")}
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        exe.run(main, feed=family.make_batch(
            config, 2, np.random.default_rng(0)), fetch_list=[loss])

    def delta(name):
        return {k: v - before[name].get(k, 0)
                for k, v in booked(name).items() if v - before[name].get(k, 0)}

    assert delta("kda_scan_total") == {
        "chunk=16,path=chunked": 3, "chunk=16,path=chunked_replay": 3}
    assert delta("kda_scan_head_decay_total") == {
        "groups=2,path=chunked": 3, "groups=2,path=chunked_replay": 3}


# --- 3. the ops' new forms against their equations ----------------------------

@pytest.mark.parametrize("first", [False, True], ids=["last", "first"])
def test_rotary_embedding_rotates_either_end(first):
    """16 of 64 dims at theta 1e7: the pair (i, i + 8) of the rotated
    dims by t * theta^(-i/8), the FIRST 16 with `rotate_first`, else the
    LAST; the others pass. Out and the gradient (the rotation by the
    opposite angle)."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 12, 3, 64)).astype(np.float32)
    attrs = {"theta": 1e7, "rotary_dims": 16}
    if first:
        attrs["rotate_first"] = True
    outs, grads, cot = run_op("rotary_embedding", {"X": x},
                              {"Out": "float32"}, attrs, wrt=("X",))

    def rotate(x, sign=1.0):
        angle = np.arange(12)[:, None] * 1e7 ** (-np.arange(8) / 8.0)
        cos, sin = (f(angle)[None, :, None, :] for f in (np.cos, np.sin))
        lo = 0 if first else 48
        a, b = x[..., lo:lo + 8], x[..., lo + 8:lo + 16]
        out = x.copy()
        out[..., lo:lo + 8] = a * cos - sign * b * sin
        out[..., lo + 8:lo + 16] = b * cos + sign * a * sin
        return out

    close(outs["Out"], rotate(x), tol=1e-5)
    close(grads["X"], rotate(np.asarray(cot), -1.0), tol=1e-5)
    passes = slice(16, None) if first else slice(0, 48)
    np.testing.assert_array_equal(outs["Out"][..., passes], x[..., passes])


@pytest.mark.parametrize("unit_offset,behind", [
    (True, False), (False, True), (True, True), (False, False)])
def test_rms_norm_forms(unit_offset, behind):
    """(1 + w) n(x); w n(x) silu(g) with the gate BEHIND the norm; both;
    and the gate ahead as before (n(x silu(g))): out and the gradients
    of x, w and g against jax.grad of the equation."""
    rng = np.random.default_rng(2)
    x, g = (rng.standard_normal((2, 6, 4, 16)).astype(np.float32)
            for _ in range(2))
    w = (0.3 * rng.standard_normal(16)).astype(np.float32)
    attrs = {"epsilon": 1e-6, "groups": 1}
    if unit_offset:
        attrs["unit_offset"] = True
    if behind:
        attrs["gate_behind"] = True
    outs, grads, cot = run_op(
        "rms_norm", {"X": x, "Scale": w, "Gate": g}, {"Out": "float32"},
        attrs, wrt=("X", "Scale", "Gate"))

    def equation(x, w, g):
        scale = 1.0 + w if unit_offset else w
        if not behind:
            x = x * jax.nn.silu(g)
        out = scale * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                        + 1e-6)
        return out * jax.nn.silu(g) if behind else out

    want, vjp = jax.vjp(equation, *map(jnp.asarray, (x, w, g)))
    close(outs["Out"], want, tol=1e-5)
    for slot, g_ref in zip(("X", "Scale", "Gate"), vjp(jnp.asarray(cot))):
        close(grads[slot], g_ref, tol=2e-5)


def block_through_the_executor(x, **kwargs):
    """layers.moe_block over x [B, T, D] in float32 -> (out, {parameter:
    value} in creation order)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), fluid.program_guard(main, startup):
        var = fluid.layers.data(name="x", shape=list(x.shape),
                                dtype="float32", append_batch_size=False)
        out = layers.moe_block(var, **kwargs)
        names = [p.name for p in main.global_block().all_parameters()
                 if p.trainable]
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        got = [np.asarray(v) for v in exe.run(main, feed={"x": x},
                                               fetch_list=[out] + names)]
    return got[0], dict(zip(names, got[1:])), main


def uncut_layer(x, w_r, gate, up, down, s_g, s_u, s_d, w_sg, k):
    """The expert layer of ISSUE 64 from its equations, every expert
    held: p = softmax(x W_r), the k largest, w_i = p_i / sum of the
    chosen, y = sum_i w_i E_i(x) + sigmoid(x w_sg) E_s(x)."""
    def expert(x, g, u, d):
        return (jax.nn.silu(x @ g) * (x @ u)) @ d
    prob = jax.nn.softmax(jnp.asarray(x) @ w_r, axis=-1)
    top, idx = jax.lax.top_k(prob, k)
    weight = top / top.sum(-1, keepdims=True)
    routed = sum((weight * (idx == e)).sum(-1, keepdims=True)
                 * expert(x, gate[e], up[e], down[e])
                 for e in range(w_r.shape[1]))
    return routed, jax.nn.sigmoid(x @ w_sg) * expert(x, s_g, s_u, s_d)


def test_the_shared_experts_gate():
    """moe_block(shared_gate=True): the shared expert's output times
    sigmoid(x w_sg), one scalar a token, w_sg [D, 1] created behind the
    shared expert's three maps; without the argument no such map."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 24, 16)).astype(np.float32)
    kwargs = dict(num_experts=8, top_k=2, expert_width=12, shared_width=12,
                  gated=True, scoring="softmax")
    out, params, main = block_through_the_executor(x, shared_gate=True,
                                                   **kwargs)
    values = list(params.values())
    assert [v.shape for v in values] == [
        (16, 8), (8, 16, 12), (8, 16, 12), (8, 12, 16), (16, 12), (16, 12),
        (12, 16), (16, 1)]
    routed, shared = uncut_layer(x.reshape(-1, 16), *values, 2)
    close(out.reshape(-1, 16), routed + shared, tol=1e-5)
    plain, params, _ = block_through_the_executor(x, **kwargs)
    assert len(params) == 7


def test_shares_add_up_to_the_uncut_layer():
    """16 gated experts under a softmax router, top 4 normalised over the
    chosen, in SIXTEEN shares of one (the cell's sixteen ranks hold 32 of
    512 each; the arithmetic is the same) and in four of four: the
    shares' routed parts, with the shared expert and its gate counted
    once, are the uncut layer written from the equations; each share
    routes its own pairs only."""
    rng = np.random.default_rng(9)
    n, d, f, k = 48, 16, 24, 4
    x = rng.standard_normal((n, d)).astype(np.float32)
    w_r = rng.standard_normal((d, 16)).astype(np.float32)
    routed, _, _ = run_op(
        "moe_router", {"X": x, "W": w_r, "Bias": np.zeros(16, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"top_k": k, "scoring": "softmax", "norm_topk_prob": True}, ())
    idx, weight = routed["TopkIdx"], routed["TopkWeight"]
    gate, up = (rng.standard_normal((16, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((16, f, d)).astype(np.float32) * 0.3
    s_g, s_u = (rng.standard_normal((d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    s_d = rng.standard_normal((f, d)).astype(np.float32) * 0.3
    w_sg = rng.standard_normal((d, 1)).astype(np.float32)

    def share(offset, held):
        cut = slice(offset, offset + held)
        return run_op(
            "moe_experts",
            {"X": x, "TopkIdx": idx, "TopkWeight": weight,
             "WGate": gate[cut], "W1": up[cut], "W2": down[cut]},
            dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                           "LoadMaxOverMean", "RowsHandled"), "float32"),
            {"num_experts": 16, "experts_held": held,
             "expert_offset": offset, "top_k": k}, ())[0]

    want_routed, shared = uncut_layer(x, w_r, gate, up, down, s_g, s_u, s_d,
                                      w_sg, k)
    for held in (1, 4):
        parts = [share(offset, held) for offset in range(0, 16, held)]
        assert sum(p["RowsRouted"][0] for p in parts) == n * k
        close(sum(p["Out"] for p in parts) + shared, want_routed + shared,
              tol=1e-5)
    close(share(0, 16)["Out"] + shared, want_routed + shared, tol=1e-5)
    assert np.asarray(weight).sum(-1) == pytest.approx(1.0, rel=1e-5)


def test_a_sixteenth_held_has_two_rungs():
    """32 of 512 under top 10 at 16,384 tokens: the capacity ladder's
    20,480 | 40,960 | 163,840 since PR 69, the first rung twice a uniform
    router's 10,240 rows and the first rung of before, four times them,
    behind it (the name is the parent's: the test is the same test); 32 x
    16 lanes are pallas_pair_sum's limit exactly, at every rung."""
    from paddle_tpu.ops import hybrid_ops, pallas_pair_sum
    pairs = 16384 * 10
    assert hybrid_ops._capacity_ladder(pairs, 32, 512) == (
        20480, 40960, 163840)
    for rung in (20480, 40960, 163840):
        assert pallas_pair_sum.ineligible(16384, rung, 2048, 32) is None


# --- 4. what asks for none of it ----------------------------------------------

def test_calls_without_the_new_arguments_build_the_ops_of_before():
    main = fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data(name="x", shape=[2, 8, 4, 16], dtype="float32",
                              append_batch_size=False)
        layers.rms_norm(x, gate=x)
        layers.rotary_embedding(x, rotary_dims=8)
    norm, rotary = (op for op in main.global_block().ops
                    if op.type in ("rms_norm", "rotary_embedding"))
    assert set(norm.desc.attrs) - {NAME_SCOPE_ATTR} == {"epsilon", "groups"}
    assert "rotate_first" not in rotary.desc.attrs
    scale = main.global_block().var(norm.input("Scale")[0])
    assert scale.shape == (16,)
