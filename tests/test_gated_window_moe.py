"""The layers of a decoder whose attention layers differ in kind, head
count and positions, with a gate a head on the attention's output
(models.gated_window_moe_lm: Laguna-XS.2's block), at tiny sizes on the
CPU with the published ratios (6 and 8 query heads a key/value head, a
window shorter than two tiles): the whole tiny model, loss and every
gradient, against benchmarks/families/laguna.py::reference_loss; the
rotary op's YaRN frequencies and factor against a table written from the
formula; the flash kernels interpreted under a window both edges of which
cross a tile, K/V repeated 6 times, against the einsum path; the shares
of the expert layer; and that a program which asks for none of it is the
one the parent built."""

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
from paddle_tpu import telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.framework.framework import NAME_SCOPE_ATTR
from paddle_tpu.ops import pallas_attention

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-laguna"
PUBLISHED = run.load_json("configs", "laguna-xs.2")
NEW_ATTRS = {"yarn_factor", "yarn_original_positions", "yarn_beta_fast",
             "yarn_beta_slow", "attention_factor"}


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the whole tiny model against the reference ---------------------------

VARIANTS = {
    "as_published": {}, "nothing_replayed": {"recompute": False},
    "a_gate_an_element": {"gating_granularity": "per-element"},
    "no_gate": {"gating": False},
    "a_softmax_router": {"router_scoring": "softmax"}}


@functools.lru_cache(maxsize=None)
def loss_and_gradients(variant):
    """(names, the program's loss and gradients, the reference's) of the
    tiny model under VARIANTS[variant], run once a variant."""
    config, family = tiny(**VARIANTS[variant])
    main, names, params, feed, got = one_step(config, family)
    want = jax.value_and_grad(
        lambda p: family.reference_loss(config, p, feed))(params)
    return names, got, want


def one_step(config, family):
    """One float32 step of the tiny model from a fixed start. -> (main,
    the trainable parameters' names, their values before the step, the
    batch, (loss, every parameter's gradient))."""
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
    return main, names, params, feed, (float(np.ravel(got)[0]), grads)


@pytest.mark.parametrize("variant", VARIANTS)
def test_tiny_model_against_the_reference_in_float32(variant):
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry, the program's fetched gradients against jax.grad of
    the reference on the same weights and batch, over [full + dense,
    sliding, sliding, sliding, full] at T = 64 under a window of 16, 6
    and 8 query heads over one key/value head; and the same for the other
    value of each `assumed` key that the builder and the reference take."""
    names, (got, grads), (want, want_grads) = loss_and_gradients(variant)
    # embedding; 7 + 3 the dense layer, 7 + 7 an expert layer (6 without
    # a gate); the final norm and the head: no router bias
    gate = VARIANTS[variant].get("gating", True)
    assert len(names) == 1 + (9 + gate) + 4 * (13 + gate) + 2
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_a_replayed_layer_changes_no_value():
    """With and without checkpoints the loss is the same to the last bit
    and every gradient to float32's rounding: the replayed ops are the
    forward's own, and an expert layer's gradient op reads the products
    the replayed forward kept, which the compiler fuses with other
    neighbours than the first forward's (5.6e-9 beside 1e-2 on the CPU,
    PR 58)."""
    (_, with_, _), (_, without, _) = (
        loss_and_gradients(v) for v in ("as_published", "nothing_replayed"))
    assert with_[0] == without[0]
    for a, b in zip(with_[1], without[1]):
        close(a, b, tol=2e-6)


# the tiny model as an eighth: 4 of 32 experts held, 128 tokens x top 4 =
# 512 pairs, so the ladder is 128 | 512. `as_it_is`: a router near uniform
# sends about 64 and the steps take the small rung; `overflowed`: a small
# rung of 16, which they overflow, so the full rung is taken behind the
# switch; `one_rung`: every pair and no switch
EIGHTH_LADDERS = {"as_it_is": None,
                  "overflowed": lambda pairs, held, experts: (16, pairs),
                  "one_rung": lambda pairs, held, experts: (pairs,)}


@functools.lru_cache(maxsize=None)
def an_eighths_loss_and_gradients(ladder, recompute):
    """(loss, every parameter's gradient, {histogram: its reading at the
    first expert layer}) of one step of the tiny model as an eighth under
    EIGHTH_LADDERS[ladder], in float32."""
    from paddle_tpu.ops import hybrid_ops
    config, family = tiny(num_experts_published=32, recompute=recompute)
    published = hybrid_ops._capacity_ladder
    assert published(512, 4, 32) == (128, 512)
    hybrid_ops._capacity_ladder = EIGHTH_LADDERS[ladder] or published
    try:
        main, _, _, _, (loss, grads) = one_step(config, family)
    finally:
        hybrid_ops._capacity_ladder = published
    rows = {name: telemetry.read_histogram(
        "moe_rows_" + name, program=telemetry.program_label(main), layer="0")
        for name in ("routed", "combined", "handled")}
    return loss, grads, rows


@pytest.mark.parametrize("recompute", [True, False],
                         ids=["checkpoints", "nothing_replayed"])
@pytest.mark.parametrize("ladder", ["as_it_is", "overflowed"])
def test_an_eighths_rungs_give_what_the_one_rung_gives(ladder, recompute):
    """The gated layer as an eighth, with a checkpoint a layer and
    without: the loss and every gradient on the small rung, and on the
    full rung taken by overflow, are those of the layer with one rung and
    no switch; no routed row is lost on either."""
    loss, grads, rows = an_eighths_loss_and_gradients(ladder, recompute)
    want, want_grads, whole = an_eighths_loss_and_gradients("one_rung",
                                                            recompute)
    assert rows["routed"] == rows["combined"] == whole["routed"]
    assert 16 < rows["routed"]["sum"] <= 128
    assert rows["handled"]["sum"] == (128 if ladder == "as_it_is" else 512)
    assert whole["handled"]["sum"] == 512
    assert abs(loss - want) <= 1e-6 * want
    for g, g_ref in zip(grads, want_grads):
        close(g, g_ref, tol=2e-6)


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU (measured: loss 1.0e-6, gradient
    5.5e-3, its norm 1.8e-4, tail 3.5e-3, update 1.0e-5)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.01
    assert found["update_rel_err"] <= 1e-3


def test_the_model_is_built_from_the_published_lists():
    """A layer's kind, head count and feed-forward are its entries of the
    three lists: the scope its rotations, attention op and gate are built
    under, a window on the op or none, YaRN on the full layers' rotations
    alone, a gate of H_l columns, one dense layer and four expert layers
    with a sigmoid router scaled by 2.5; four layers replayed, one
    balancing rule a router; the loss and the routing reach telemetry."""
    from paddle_tpu.models import gated_window_moe as model
    config, family = tiny()
    main, startup, loss = family.build(config)
    ops = main.global_block().ops
    forward = [op for op in ops if not op.type.endswith("_grad")
               and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    attention = [op for op in forward
                 if op.type == "scaled_dot_product_attention"]
    assert [op.desc.attrs.get("window", 0) for op in attention] == \
        [0, 16, 16, 16, 0]
    heads = [main.global_block().var(op.input("Q")[0]).shape[2]
             for op in attention]
    assert heads == [6, 8, 8, 8, 6]
    assert all(main.global_block().var(op.input("K")[0]).shape[2] == 1
               for op in attention)
    rotations = [op for op in forward if op.type == "rotary_embedding"]
    assert len(rotations) == 10
    for i, op in enumerate(rotations):
        full = i // 2 in (0, 4)
        assert (NEW_ATTRS <= set(op.desc.attrs)) == full
        assert not full or op.attr("attention_factor") == pytest.approx(
            0.1 * np.log(8) + 1)
        assert op.attr("rotary_dims") == (8 if full else 16)
        assert op.attr("theta") == (500000.0 if full else 10000.0)
    gates = [op for op in forward if op.type == "sigmoid"]
    assert [main.global_block().var(op.output("Out")[0]).shape[-1]
            for op in gates] == heads
    routers = [op for op in forward if op.type == "moe_router"]
    assert len(routers) == 4
    for router in routers:
        assert "scoring" not in router.desc.attrs       # the sigmoid default
        assert router.attr("scaling") == 2.5 and router.attr("top_k") == 4
    assert [op.type for op in forward].count("silu") == 1 + 4  # dense, shared
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4]     # the last layer is not
    assert all(types.count("scaled_dot_product_attention") == 1
               for types in replayed.values())
    # each stands in its segment and is handed the Out and LSE its first
    # run kept, behind the segment's barrier; nothing else is handed on
    assert backward.replayed_ops(main, handed_on=True) == {
        seg: ["scaled_dot_product_attention"] for seg in replayed}
    again = [op for op in ops if op.type == "scaled_dot_product_attention"
             and backward.RECOMPUTE_ATTR in op.desc.attrs]
    assert [(op.input("KeptOut"), op.input("KeptLSE"))
            for op in reversed(again)] == [
        ([f"{first.output('Out')[0]}@RECOMPUTE.{seg}"],
         [f"{first.output('LSE')[0]}@RECOMPUTE.{seg}"])
        for seg, first in zip(sorted(replayed), attention)]
    assert [types.count("moe_router") for _, types in
            sorted(replayed.items())] == [0, 1, 1, 1]
    rules = [op for op in ops if op.type == "moe_balance_bias"]
    assert len(rules) == 4 and all(op.attr("rate") == 0.2 for op in rules)

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
    series = f"program={label},type=scaled_dot_product_attention"
    assert telemetry.read_series("recompute_kept_total")[series] == 4
    assert series not in telemetry.read_series("recompute_ops_total")
    # float32 here: Out [2, T, H_l, hd] and LSE [2, H_l, T] of four layers
    t, hd = config["sequence_length"], config["head_dim"]
    assert telemetry.read_series("recompute_kept_bytes")[
        f"program={label}"] == 4 * 2 * t * (6 + 3 * 8) * (hd + 1)
    for layer in ("0", "3"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        assert rows["count"] >= 1 and rows["sum"] > 0


def test_the_scopes_hold_the_rotations_the_op_and_the_gate():
    from paddle_tpu.models import window_moe
    config, family = tiny()
    main, _, _ = family.build(config)
    under = {window_moe.WINDOW_SCOPE: [], window_moe.GLOBAL_SCOPE: []}
    for op in main.global_block().ops:
        scope = (op.desc.attrs.get(NAME_SCOPE_ATTR) or "").split("/")
        for kind in under:
            if kind in scope and not op.type.endswith("_grad") \
                    and backward.RECOMPUTE_ATTR not in op.desc.attrs:
                under[kind].append(op.type)
    a_layer = ["rotary_embedding", "rotary_embedding",
               "scaled_dot_product_attention", "mul", "sigmoid",
               "elementwise_mul"]
    assert under[window_moe.WINDOW_SCOPE] == a_layer * 3
    assert under[window_moe.GLOBAL_SCOPE] == a_layer * 2


# --- 2. the rotation ---------------------------------------------------------

def yarn_table(dims, theta, factor, original, beta_fast, beta_slow):
    """omega_j from the formula, in float64 numpy, and (low, high)."""
    j = np.arange(dims // 2)
    f = theta ** (-2.0 * j / dims)

    def c(n):
        return dims * np.log(original / (2 * np.pi * n)) / (2 * np.log(theta))

    low, high = np.floor(c(beta_fast)), np.ceil(c(beta_slow))
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return f * (1 - ramp) + f / factor * ramp, (low, high)


def test_yarn_angles_and_factor_at_the_published_keys():
    """The full layers' rotation at the published group: 64 of 128 dims
    in 32 pairs, low 5 and high 16, pairs below 5 at 500000^(-2j/64),
    pairs from 16 on at a 64th of it, cos and sin times 1.4158883: the
    op's output against the rotation written out, the still half
    untouched, the turned half's norm scaled by the factor."""
    group = PUBLISHED["rope_parameters"]["full_attention"]
    omega, (low, high) = yarn_table(
        64, group["rope_theta"], group["factor"],
        group["original_max_position_embeddings"], group["beta_fast"],
        group["beta_slow"])
    assert (low, high) == (5, 16)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(omega[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(omega[16:], plain[16:] / 64, rtol=1e-12)
    assert np.all((omega[6:16] < plain[6:16])
                  & (omega[6:16] > plain[6:16] / 64))
    factor = group["attention_factor"]
    assert factor == pytest.approx(0.1 * np.log(64) + 1, rel=1e-12)
    family = run.load_module("families", PUBLISHED["family"])
    np.testing.assert_allclose(family.yarn_frequencies(group, 64), omega,
                               rtol=1e-12)

    t = 96
    x = np.random.default_rng(5).standard_normal((1, t, 2, 128)).astype(
        np.float32)
    main = fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
        xv = fluid.layers.data(name="x", shape=list(x.shape),
                               dtype="float32", append_batch_size=False)
        out = fluid.layers.rotary_embedding(
            xv, theta=group["rope_theta"], rotary_dims=64, scaling=group)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        got, = exe.run(main, feed={"x": x}, fetch_list=[out])
    angle = np.arange(t)[:, None] * omega                    # [t, 32]
    cos, sin = (factor * fn(angle)[:, None, :] for fn in (np.cos, np.sin))
    a, b = x[..., 64:96], x[..., 96:]
    want = np.concatenate([x[..., :64], a * cos - b * sin,
                           b * cos + a * sin], -1)
    # float32 angles at positions up to 95: the sines agree to 1e-5
    close(got, want, tol=2e-5)
    np.testing.assert_array_equal(got[..., :64], x[..., :64])
    np.testing.assert_allclose(
        np.linalg.norm(got[..., 64:], axis=-1),
        factor * np.linalg.norm(x[..., 64:], axis=-1), rtol=1e-5)


def test_a_rotation_without_scaling_is_the_op_it_was():
    """No group, or the default group: theta and rotary_dims alone, and
    the same numbers; an unknown rope_type is refused."""
    x = np.random.default_rng(6).standard_normal((1, 16, 2, 32)).astype(
        np.float32)
    main = fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
        xv = fluid.layers.data(name="x", shape=list(x.shape),
                               dtype="float32", append_batch_size=False)
        outs = [fluid.layers.rotary_embedding(xv, theta=1e4),
                fluid.layers.rotary_embedding(
                    xv, theta=1e4, scaling=PUBLISHED["rope_parameters"][
                        "sliding_attention"])]
        with pytest.raises(ValueError, match="rope_type"):
            fluid.layers.rotary_embedding(xv, scaling={"rope_type": "llama3"})
    for op in main.global_block().ops:
        assert set(op.desc.attrs) - {NAME_SCOPE_ATTR, "op_role"} == {
            "theta", "rotary_dims"}, op.desc.attrs
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        plain, default = exe.run(main, feed={"x": x}, fetch_list=outs)
    np.testing.assert_array_equal(plain, default)


# --- 3. the kernels under a window shorter than two tiles --------------------

@pytest.mark.parametrize("window", [512, 200, 1023],
                         ids=["a_tile", "odd", "nearly_two_tiles"])
def test_kernels_under_a_short_window_with_six_queries_a_key_head(window):
    """1024 positions in tiles of 512 rows under a window below
    bq + bk = 1024 keys, so the diagonal's tiles cross the far edge too
    (_crosses_both) and at 512 keys no walked tile is open; 6 query heads
    of 64 over one key/value head, repeated 6 times ahead of the kernels
    and summed over the group behind them: the op on the kernels
    (interpreted), output and three gradients, against the op on the
    einsum path, and the kernels booked as taken."""
    assert pallas_attention._crosses_both(window, *pallas_attention._TILE)
    rng = np.random.default_rng(window)
    q = rng.standard_normal((1, 1024, 6, 64)).astype(np.float32)
    k, v = (rng.standard_normal((1, 1024, 1, 64)).astype(np.float32)
            for _ in range(2))

    def op(use_flash):
        return run_op(
            "scaled_dot_product_attention", {"Q": q, "K": k, "V": v},
            {"Out": "float32", "LSE": "float32"},
            {"causal": True, "use_flash": use_flash, "window": window},
            ("Q", "K", "V"))

    def hits():
        return telemetry.read_series("pallas_kernel_total").get(
            "op=scaled_dot_product_attention", 0)

    before = hits()
    (outs, grads, _), (want, want_grads, _) = op(True), op(False)
    assert hits() - before >= 1
    close(outs["Out"], want["Out"], tol=1e-5)
    for slot in ("Q", "K", "V"):
        close(grads[slot], want_grads[slot], tol=2e-5)


def test_every_walked_tile_is_masked_at_the_published_window():
    """8192 positions under 512 keys in tiles of 512: a Q tile walks its
    own K block, which both edges cross, and the one before it, which the
    far edge crosses, and no open block; the first walks its own alone."""
    def ranges(q_first):
        blocks = [pallas_attention._kv_ranges(
            jnp.int32(q_first), jnp.int32(base), 512, 512, 4, True, 1, 512)
            for base in range(0, 8192, 2048)]
        return [sum(int(r[kind][1]) - int(r[kind][0]) for r in blocks
                    if r[kind] is not None) for kind in range(3)]

    assert ranges(7680) == ranges(4096) == ranges(512) == [0, 1, 1]
    assert ranges(0) == [0, 1, 0]


# --- 4. the shares -----------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """16 gated experts top-4 under a sigmoid router normalised over the
    chosen and scaled by 2.5, in eight shares of two: what the shares
    give, with the shared expert counted once, is the uncut layer written
    from the equations; and each share routes its own pairs only."""
    rng = np.random.default_rng(9)
    n, d, f, k, scaling = 48, 16, 24, 4, 2.5
    x = rng.standard_normal((n, d)).astype(np.float32)
    w_r = rng.standard_normal((d, 16)).astype(np.float32)
    routed, _, _ = run_op(
        "moe_router", {"X": x, "W": w_r, "Bias": np.zeros(16, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"top_k": k, "scaling": scaling, "norm_topk_prob": True}, ())
    idx, weight = routed["TopkIdx"], routed["TopkWeight"]
    gate, up = (rng.standard_normal((16, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((16, f, d)).astype(np.float32) * 0.3
    s_g, s_u = (rng.standard_normal((d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    s_d = rng.standard_normal((f, d)).astype(np.float32) * 0.3

    def gated(x, g, u, dn):
        return (jax.nn.silu(x @ g) * (x @ u)) @ dn

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

    parts = [share(offset, 2) for offset in range(0, 16, 2)]
    shared = gated(x, s_g, s_u, s_d)
    assert sum(p["RowsRouted"][0] for p in parts) == n * k
    # the uncut layer from the equations: s = sigmoid(x W_r), the top 4,
    # w_e = 2.5 s_e / sum of the chosen s
    s = jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(w_r))
    top, ref_idx = jax.lax.top_k(s, k)
    ref_weight = scaling * top / top.sum(-1, keepdims=True)
    np.testing.assert_array_equal(np.sort(np.asarray(ref_idx), -1),
                                  np.sort(idx, -1))
    want = shared + sum(
        (ref_weight * (ref_idx == e)).sum(-1, keepdims=True)
        * gated(x, gate[e], up[e], down[e]) for e in range(16))
    close(sum(p["Out"] for p in parts) + shared, want, tol=1e-5)
    close(share(0, 16)["Out"] + shared, want, tol=1e-5)
    assert np.asarray(weight).sum(-1) == pytest.approx(scaling, rel=1e-5)


# --- 5. what asks for none of it ---------------------------------------------

PARENT_PROGRAMS = {
    # sha256 of main.to_json() | startup.to_json() at PR 52's tree
    # (the programs with expert layers: at PR 58's, whose moe_experts ops
    # write Up / GateUp for an explicit gradient op)
    "smallthinker-21b-a3b-instruct": ("dc6827508c9e3acb", "33caad39b72bf4c9"),
}


@pytest.mark.parametrize("name", PARENT_PROGRAMS)
def test_a_program_that_asks_for_none_of_it_is_the_one_it_was(name):
    """window_moe_lm rotates through the same layer with no `scaling`: no
    op of its program carries a new attribute, and the serialised
    programs equal the parent's (PR 52), by their hashes. (The latent,
    block-diffusion and hybrid programs are held to PR 45's hashes by
    tests/test_window_moe.py.)"""
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    for op in main.global_block().ops:
        assert not NEW_ATTRS & set(op.desc.attrs), op.type
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]


def test_balance_routers_gives_a_replayed_router_no_second_rule():
    from paddle_tpu import models
    config, family = tiny(router_balance_rate=0)
    main, _, _ = family.build(config)
    routers = [op for op in main.global_block().ops
               if op.type == "moe_router"]
    assert len(routers) == 4 + 3        # three of them replayed
    assert len(models.balance_routers(main, 0.1)) == 4
