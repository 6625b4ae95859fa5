"""The layers of a decoder of gated short convolutions and grouped-query
attention with a top-4 router and no shared expert (models.conv_moe_lm:
LFM2-24B-A2B's block), at tiny sizes on the CPU with the published
pattern (layers 0, 2, 3, 4, 5: conv + dense, attention, conv, conv,
conv): the whole tiny model, loss and every gradient, against
benchmarks/families/lfm2_moe.py::reference_loss, replayed or not; the
program the builder writes from the published lists; the mixer against
its equations, the thirds in the published order; QK-norm ahead of the
rotation; the router's weights; the eight shares of an expert layer; and
that a program which asks for none of it is the one the parent built."""

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward
from paddle_tpu import executor as executor_mod
from paddle_tpu import layers, telemetry
from paddle_tpu.framework.framework import NAME_SCOPE_ATTR, grad_var_name

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-lfm2-moe"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the whole tiny model against the reference ---------------------------

VARIANTS = {"as_published": {}, "nothing_replayed": {"recompute": False},
            "weights_not_normalised": {"norm_topk_prob": False},
            "the_first_share": {"expert_offset": 0}}


@functools.lru_cache(maxsize=None)
def loss_and_gradients(variant):
    """(names, the program's loss and gradients, the reference's) of the
    tiny model under VARIANTS[variant], one float32 step from a fixed
    start."""
    config, family = tiny(**VARIANTS[variant])
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 4242)
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()
                 if p.trainable]
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
    the reference on the same weights and batch, at T = 64, 4 query heads
    over 2 key/value heads, 4 of 16 experts held under top 4."""
    names, (got, grads), (want, want_grads) = loss_and_gradients(variant)
    # the tied embedding; a conv layer 1 + 5 + 1, the attention layer
    # 1 + 6 + 1; the dense feed-forward 3, an expert layer 4 (no router
    # bias among them); the embedding norm; no head
    assert len(names) == 1 + (7 + 3) + (8 + 4) + 3 * (7 + 4) + 1
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_a_replayed_layer_changes_no_value():
    """With and without checkpoints the loss is the same to the last bit
    and every gradient to float32's rounding (the compiler fuses a
    replayed op with other neighbours than the first forward's)."""
    (_, with_, _), (_, without, _) = (
        loss_and_gradients(v) for v in ("as_published", "nothing_replayed"))
    assert with_[0] == without[0]
    for a, b in zip(with_[1], without[1]):
        close(a, b, tol=2e-6)


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU (measured: loss 5.8e-6, gradient
    3.5e-3, its norm 1.1e-5, tail 3.9e-3, update 9.8e-6)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.01
    assert found["update_rel_err"] <= 1e-3


# --- 2. the program -----------------------------------------------------------

def test_the_model_is_built_from_the_published_lists():
    """A held layer's mixer is `layer_types` at its published index and
    its feed-forward dense under `num_dense_layers`: one gated
    causal_conv1d (both gates, three taps, no bias, no activation) a conv
    layer, QK-norm and rotation at theta 1e6 in the attention layer, one
    dense layer and four expert layers whose routers carry the published
    epsilon; four layers replayed, one balancing rule a router; the head
    is the embedding; the loss and the routing reach telemetry."""
    from paddle_tpu.models import conv_moe as model
    config, family = tiny()
    main, startup, loss = family.build(config)
    block = main.global_block()
    forward = [op for op in block.ops if not op.type.endswith("_grad")
               and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    kinds = [op.type for op in forward
             if op.type in ("causal_conv1d", "scaled_dot_product_attention")]
    assert kinds == ["causal_conv1d", "scaled_dot_product_attention"] \
        + ["causal_conv1d"] * 3
    for op in forward:
        if op.type == "causal_conv1d":
            assert sorted(op.desc.inputs) == ["Filter", "PostGate",
                                              "PreGate", "X"]
            assert op.desc.attrs["activation"] == "identity"
            assert "time_on_lanes" not in op.desc.attrs
            assert block.var(op.input("Filter")[0]).shape == (64, 3)
    attention, = [op for op in forward
                  if op.type == "scaled_dot_product_attention"]
    assert [block.var(attention.input(s)[0]).shape[2:]
            for s in ("Q", "K", "V")] == [(4, 16), (2, 16), (2, 16)]
    rotations = [op for op in forward if op.type == "rotary_embedding"]
    assert [(op.attr("theta"), op.attr("rotary_dims"))
            for op in rotations] == [(1e6, 16)] * 2
    routers = [op for op in forward if op.type == "moe_router"]
    assert len(routers) == 4
    for router in routers:
        assert "scoring" not in router.desc.attrs       # the sigmoid default
        assert router.attr("norm_epsilon") == model.ROUTER_NORM_EPSILON == 1e-6
        assert (router.attr("top_k"), router.attr("scaling"),
                router.attr("norm_topk_prob")) == (4, 1, True)
    experts = [op for op in forward if op.type == "moe_experts"]
    assert [(op.attr("num_experts"), op.attr("experts_held"),
             op.attr("expert_offset")) for op in experts] == [(16, 4, 4)] * 4
    assert all(op.input("WGate") for op in experts)     # gated experts
    assert [op.type for op in forward].count("silu") == 1    # the dense layer
    head, = [op for op in forward if op.type == "matmul"]
    assert head.input("Y") == [model.EMBEDDING] and head.attr("transpose_Y")
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4]     # the last layer is not
    assert [(types.count("causal_conv1d"),
             types.count("scaled_dot_product_attention"),
             types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [
        (1, 0, 0), (0, 1, 1), (1, 0, 1), (1, 0, 1)]
    # the attention op is handed what its first run kept (PR 54); the
    # conv's gradient op reads the op's inputs alone
    assert backward.replayed_ops(main, handed_on=True) == {
        1: [], 2: ["scaled_dot_product_attention"], 3: [], 4: []}
    grads = [op for op in block.ops if op.type == "causal_conv1d_grad"]
    assert len(grads) == 4
    for op in grads:
        assert sorted(op.desc.inputs) == ["Filter", "Out@GRAD", "PostGate",
                                          "PreGate", "X"]
        assert sorted(op.desc.outputs) == ["Filter@GRAD", "PostGate@GRAD",
                                           "PreGate@GRAD", "X@GRAD"]
    rules = [op for op in block.ops if op.type == "moe_balance_bias"]
    assert len(rules) == 4 and all(op.attr("rate") == 0.02 for op in rules)

    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(0))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for _ in range(2):
            out, = exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()     # a side-fetch still in flight is published here
    label = telemetry.program_label(main)
    assert telemetry.read_gauge(model.LOSS_METRIC, program=label) == \
        pytest.approx(float(np.ravel(out)[0]), rel=1e-6)
    for layer in ("0", "3"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        assert rows["count"] >= 1 and rows["sum"] > 0


def test_the_scopes_hold_the_mixers_and_the_attention():
    """`pd_scope.short_conv_mixer`: the three maps, the op and the output
    map; `pd_scope.gqa_attention`: a norm and a rotation a side and the
    attention op, the four maps outside."""
    from paddle_tpu.models import conv_moe as model
    config, family = tiny()
    main, _, _ = family.build(config)
    under = {"short_conv_mixer": [], model.ATTENTION_SCOPE: []}
    for op in main.global_block().ops:
        scope = (op.desc.attrs.get(NAME_SCOPE_ATTR) or "").split("/")
        for kind in under:
            if kind in scope and not op.type.endswith("_grad") \
                    and backward.RECOMPUTE_ATTR not in op.desc.attrs:
                under[kind].append(op.type)
    assert under["short_conv_mixer"] == ["mul"] * 3 + ["causal_conv1d",
                                                       "mul"] * 1 \
        + (["mul"] * 3 + ["causal_conv1d", "mul"]) * 3
    assert under[model.ATTENTION_SCOPE] == [
        "rms_norm", "rotary_embedding", "rms_norm", "rotary_embedding",
        "scaled_dot_product_attention"]


# --- 3. the mixer, the attention and the router against their equations -------

def _a_layer(build, feeds, seed=7, given=()):
    """One layer built by `build(*data vars)` over `feeds` {name: array}
    -> (its output, its parameters by creation order); `given`: values
    for its first parameters in place of the initial ones."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        data = [layers.data(name=n, shape=list(v.shape), dtype=str(v.dtype),
                            append_batch_size=False)
                for n, v in feeds.items()]
        out = build(*data)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()]
        for name, value in zip(names, given):
            scope.set_var(name, jnp.asarray(value))
        params = [np.asarray(scope.find_var(name)) for name in names]
        got, = exe.run(main, feed=feeds, fetch_list=[out])
    return np.asarray(got), params


def test_the_thirds_are_read_in_the_published_order():
    """y = (C * conv_3(B * x)) W_out with [B | C | x] = u W_in, the
    thirds in THIS order: the layer's three maps, in the order it creates
    them, are the gate ahead of the taps, the gate behind them and the
    convolved third; no activation, zeros before t = 0. B and x enter as
    a product, so swapping them changes nothing; an order that puts
    another third in C's place gives another result."""
    rng = np.random.default_rng(2)
    t, d = 24, 16
    u = rng.standard_normal((2, t, d)).astype(np.float32)
    got, (w_b, w_c, w_x, taps, w_out) = _a_layer(
        lambda x: layers.short_conv_mixer(x, conv_kernel=3, out_scale=0.3),
        {"u": u})
    assert taps.shape == (d, 3) and w_out.shape == (d, d)

    def published(order):
        w_in = np.concatenate([w_b, w_c, w_x], axis=1)         # [D, 3 D]
        proj = u @ w_in
        thirds = dict(zip(order, (proj[..., :d], proj[..., d:2 * d],
                                  proj[..., 2 * d:])))
        bx = thirds["B"] * thirds["x"]
        z = np.zeros_like(bx)
        for j in range(3):          # taps[:, 2] reads the present step
            shift = 2 - j
            z[:, shift:] += bx[:, :t - shift] * taps[:, j]
        return (thirds["C"] * z) @ w_out

    close(got, published("BCx"), tol=1e-5)
    close(got, published("xCB"), tol=1e-5)
    for wrong in ("CBx", "BxC", "CxB"):
        assert np.abs(got - published(wrong)).max() > 0.1 * np.abs(got).max()


def test_qk_norm_is_applied_before_the_rotation():
    """q and k are normed a head (one weight of head_dim for all heads)
    and THEN rotated; the other order is another value, since the
    rotation mixes the dims the norm's weight scales one by one."""
    rng = np.random.default_rng(4)
    t, heads, hd = 12, 2, 8
    x = rng.standard_normal((1, t, heads, hd)).astype(np.float32)
    weight = (1.0 + 0.5 * rng.standard_normal(hd)).astype(np.float32)

    got, _ = _a_layer(lambda v: layers.rotary_embedding(
        layers.rms_norm(v, epsilon=1e-5), theta=1e6), {"x": x},
        given=[weight])

    def rms(v):
        return weight * v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)

    def rotate(v):
        half = hd // 2
        angle = np.arange(t)[:, None] * 1e6 ** (-np.arange(half) / half)
        cos, sin = (f(angle)[None, :, None, :] for f in (np.cos, np.sin))
        a, b = v[..., :half], v[..., half:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    close(got, rotate(rms(x)), tol=1e-5)
    assert np.abs(got - rms(rotate(x))).max() > 1e-2
    # and that is the order the model's attention layer builds them in
    config, family = tiny()
    main, _, _ = family.build(config)
    types = [op.type for op in main.global_block().ops]
    first = types.index("rotary_embedding")
    assert types[first - 1] == "rms_norm"


def test_the_routers_weights_are_the_unbiased_scores_over_their_sum():
    """chosen = top 4 of (s + b); g_e = scaling * s_e / (sum of the chosen
    s + 1e-6): the bias moves the choice and takes no part in the
    weights, and the epsilon is the published 1e-6, not the other
    routers' 1e-20 (whose attribute is written only where it differs)."""
    rng = np.random.default_rng(6)
    n, d, e, k = 40, 16, 16, 4
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = (0.25 * rng.standard_normal((d, e))).astype(np.float32)
    # scores near zero, where the epsilon shows
    faint = x @ w - 14.0
    bias = np.linspace(-0.5, 0.5, e).astype(np.float32)

    def route(attrs, shift):
        return run_op(
            "moe_router", {"X": np.concatenate([x, np.ones((n, 1), "f4")], 1),
                           "W": np.concatenate([w, shift * np.ones((1, e),
                                                                   "f4")]),
                           "Bias": bias},
            {"TopkIdx": "int32", "TopkWeight": "float32"},
            dict({"top_k": k, "scaling": 1.0, "norm_topk_prob": True},
                 **attrs), ())[0]

    for shift in (0.0, -14.0):
        s = 1.0 / (1.0 + np.exp(-np.float64(x @ w + shift)))
        chosen = np.argsort(-(s + bias), axis=-1)[:, :k]
        picked = np.take_along_axis(s, chosen, -1)
        got = route({"norm_epsilon": 1e-6}, shift)
        np.testing.assert_array_equal(np.sort(got["TopkIdx"], -1),
                                      np.sort(chosen, -1))
        order = np.argsort(got["TopkIdx"], -1)
        mine = np.take_along_axis(got["TopkWeight"], order, -1)
        want = np.take_along_axis(
            picked / (picked.sum(-1, keepdims=True) + 1e-6),
            np.argsort(chosen, -1), -1)
        close(mine, want, tol=1e-5)
    # at faint scores the two epsilons part: the sum of the weights is
    # sum / (sum + eps)
    assert faint.max() < -8
    lfm2, other = (route(attrs, -14.0)["TopkWeight"].sum(-1)
                   for attrs in ({"norm_epsilon": 1e-6}, {}))
    assert np.all(other > 0.9999) and np.all(lfm2 < 0.999)
    # the layer writes the attribute only where it differs
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        v = layers.data(name="v", shape=[1, 8, 16], dtype="float32",
                        append_batch_size=False)
        layers.moe_block(v, 8, 2, 8, gated=True)
        layers.moe_block(v, 8, 2, 8, gated=True, norm_epsilon=1e-6)
    written = [op.desc.attrs.get("norm_epsilon")
               for op in main.global_block().ops if op.type == "moe_router"]
    assert written == [None, 1e-6]


# --- 4. the shares ------------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """64 gated experts under top 4 of a sigmoid router normalised over
    the chosen with 1e-6, no shared expert, in eight shares of eight
    (expert_offset 0, 8, ..., 56): what the shares give adds up to the
    uncut layer written from the equations (nothing is counted once: no
    chip computes a part alike); each share routes its own pairs only,
    and a token none of whose four choices is held gets EXACTLY zero from
    a share."""
    rng = np.random.default_rng(9)
    n, d, f, k, experts, held = 96, 16, 24, 4, 64, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    w_r = rng.standard_normal((d, experts)).astype(np.float32)
    routed, _, _ = run_op(
        "moe_router",
        {"X": x, "W": w_r, "Bias": np.zeros(experts, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"top_k": k, "scaling": 1.0, "norm_topk_prob": True,
         "norm_epsilon": 1e-6}, ())
    idx, weight = routed["TopkIdx"], routed["TopkWeight"]
    w1, w3 = (rng.standard_normal((experts, d, f)).astype(np.float32) * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((experts, f, d)).astype(np.float32) * 0.3

    def gated(x, g, u, dn):
        return (jax.nn.silu(x @ g) * (x @ u)) @ dn

    def share(offset, held):
        cut = slice(offset, offset + held)
        return run_op(
            "moe_experts",
            {"X": x, "TopkIdx": idx, "TopkWeight": weight,
             "WGate": w1[cut], "W1": w3[cut], "W2": w2[cut]},
            dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                           "LoadMaxOverMean", "RowsHandled"), "float32"),
            {"num_experts": experts, "experts_held": held,
             "expert_offset": offset, "top_k": k}, ())[0]

    parts = [share(offset, held) for offset in range(0, experts, held)]
    assert sum(p["RowsRouted"][0] for p in parts) == n * k
    s = jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(w_r))
    top, ref_idx = jax.lax.top_k(s, k)
    ref_weight = top / (top.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(ref_idx), -1),
                                  np.sort(idx, -1))
    want = sum((ref_weight * (ref_idx == e)).sum(-1, keepdims=True)
               * gated(x, w1[e], w3[e], w2[e]) for e in range(experts))
    close(sum(p["Out"] for p in parts), want, tol=1e-5)
    close(share(0, experts)["Out"], want, tol=1e-5)
    # tokens with no held choice: about 58 % of them a share, C(56, 4) /
    # C(64, 4), and exactly zero
    for number, part in enumerate(parts):
        away = ~((idx >= number * held) & (idx < (number + 1) * held)).any(-1)
        assert 0.3 * n < away.sum() < 0.8 * n
        assert not part["Out"][away].any()
        assert np.abs(part["Out"][~away]).max() > 0


# --- 5. what asks for none of it -----------------------------------------------

# (main, startup) of the three accepted configurations that build
# causal_conv1d, as the parent commit (PR 61) built them: the first 16 hex
# digits of sha256 over Program.to_json(). No gate, no attribute: their
# cells' programs are the parent's (the eight programs without the op are
# held by tests/test_causal_conv1d_kernels.py; Kimi-Linear's main program
# as PR 65 builds its kda_scan ops, with Inverse and Entering beside Out).
PARENT_PROGRAMS = {
    "kimi-linear-48b-a3b-instruct": ("2c1c4f892d9bd452", "f56ab18f9f796b0c"),
    "granite-4.0-h-micro": ("cbaa1f07490aec02", "aef2a12bd424d1b5"),
    "nemotron3-nano-30b-a3b": ("6ac48d33c64fc359", "2cd691daa316a1c1"),
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_program_that_asks_for_none_of_it_is_the_one_it_was(name):
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    for op in main.global_block().ops:
        assert not {"activation", "norm_epsilon"} & set(op.desc.attrs), op.type
        assert not {"PreGate", "PostGate"} & set(op.desc.inputs), op.type
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]
