"""The layers of a decoder with two kinds of attention layer
(models.window_moe_lm: sliding-window layers with rotary positions, full
attention layers with none; a router that reads the attention's input;
ReGLU experts), at tiny sizes on the CPU: the whole tiny model against
benchmarks/families/smallthinker.py::reference_loss; the window alone, on
the flash kernels interpreted over several tiles and major tiles and on
the einsum path, against a dense mask on positions; what has positions
and what has none; what the router reads; the two orders of its softmax;
the ReLU gate; the shares of the expert layer; and that the programs
which ask for none of it are the ones they were. (The cell's step
compiled for a described v5e is in tests/test_tpu_compile.py: the one
file that describes a chip.)"""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.ops import pallas_attention

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-smallthinker"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the whole tiny model against the reference ---------------------------

def test_tiny_model_against_the_reference_in_float32():
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry, the program's fetched gradients against jax.grad of
    the reference on the same weights and batch, over two periods of
    [full, window, window, window] at T = 64 under a window of 16."""
    config, family = tiny()
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
        want, want_grads = jax.value_and_grad(
            lambda p: family.reference_loss(config, p, feed))(params)
        got, *grads = exe.run(
            main, feed=feed,
            fetch_list=[loss] + [grad_var_name(n) for n in names])
    # embedding; 10 a block; the final norm and the head: no router bias
    assert len(names) == 1 + 10 * config["num_hidden_layers"] + 2
    assert abs(float(np.ravel(got)[0]) - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU (measured: loss 1.5e-5, gradient
    5.4e-3, its norm 5.3e-4, tail 3.5e-3, update 1.0e-5; in float32 the
    loss is exact and the gradient 3.0e-7 off)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.01
    assert found["update_rel_err"] <= 1e-3


def test_the_model_is_built_from_the_layouts():
    """A layer's kind is its entry of the two layouts: the scope its
    rotations and attention op are built under, a `window` on the op or
    none, two rotations or none; every router reads the attention's
    normed input and every expert op gates by relu; the loss and the
    routing reach telemetry."""
    from paddle_tpu.models import window_moe as model
    config, family = tiny()
    main, startup, loss = family.build(config)
    ops = main.global_block().ops
    forward = [op for op in ops if not op.type.endswith("_grad")]
    attention = [op for op in forward
                 if op.type == "scaled_dot_product_attention"]
    assert [op.desc.attrs.get("window", 0) for op in attention] == \
        [0, 16, 16, 16] * 2
    assert all(op.attr("causal") for op in attention)
    assert [op.type for op in forward].count("rotary_embedding") == 2 * 6
    norms = [op for op in forward if op.type == "rms_norm"]
    routers = [op for op in forward if op.type == "moe_router"]
    experts = [op for op in forward if op.type == "moe_experts"]
    assert len(routers) == len(experts) == 8
    for i, (router, expert) in enumerate(zip(routers, experts)):
        a, b = norms[2 * i].output("Out")[0], norms[2 * i + 1].output("Out")[0]
        assert reads(main, router.input("X")[0]) == a
        assert reads(main, expert.input("X")[0]) == b
        assert expert.attr("gate_act") == "relu"
        assert router.attr("scoring") == "softmax"
    grads = [op for op in ops
             if op.type == "scaled_dot_product_attention_grad"]
    assert sorted(op.desc.attrs.get("window", 0) for op in grads) == \
        [0, 0] + [16] * 6

    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(0))
    exe = fluid.Executor(fluid.CPUPlace())
    before = counted("attention_window_total")
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for _ in range(2):
            out, = exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()     # a side-fetch still in flight is published here
    # one a forward lowering that carries a window, none for a gradient's
    assert counted("attention_window_total") - before == 6
    label = telemetry.program_label(main)
    assert telemetry.read_gauge(model.LOSS_METRIC, program=label) == \
        pytest.approx(float(np.ravel(out)[0]), rel=1e-6)
    for layer in ("0", "7"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        assert rows["count"] >= 1 and rows["sum"] > 0


def reads(program, name):
    """The variable a reshape2 of `name` was made from (moe_block flattens
    its inputs to rows)."""
    for op in program.global_block().ops:
        if name in op.output("Out") and op.type.startswith("reshape"):
            return op.input("X")[0]
    return name


def counted(name):
    return sum(telemetry.read_series(name).values())


# --- 2. the window alone -----------------------------------------------------

def dense_window(q, k, v, window, scale):
    """softmax over an explicit mask on positions: key <= query, and
    under a window query - key < window; K/V heads repeated to Q's."""
    t, groups = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    pos = np.arange(t)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[:, None] - pos[None, :] < window
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    prob = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v)


WINDOWS = {"a_tile": 128, "two_tiles": 256, "a_tile_and_a_half": 192,
           "one_key": 1, "an_odd_count": 37, "past_the_sequence": 600}


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("window", WINDOWS.values(), ids=WINDOWS.keys())
def test_kernels_under_a_window_over_several_tiles_and_major_tiles(window,
                                                                   fused):
    """The window's geometry over 4 x 4 tiles of 128 rows in two major
    tiles of 256: the forward and the backward, as one call (dQ
    accumulated over the four K tiles beside dK and dV) and as two,
    against a dense mask on positions. A window that is no multiple of
    the tile makes the edge tile's predicate partial; one that is shorter
    than two tiles has blocks both edges cross."""
    t, tiles = 512, dict(tile=(128, 128), major=256)
    rng = np.random.default_rng(window)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, t, 1, 128)),
                               jnp.float32) for _ in range(4))
    scale = 1.0 / np.sqrt(128)
    want, vjp = jax.vjp(
        lambda q, k, v: dense_window(q, k, v, window, scale), q, k, v)
    out, (lse,) = pallas_attention._fwd_call(
        q, k, v, 0, 0, scale, True, normalize=True, window=window, **tiles)
    close(out, want, tol=1e-5)
    delta = jnp.sum(do * out, axis=-1).transpose(0, 2, 1)
    grads = pallas_attention._bwd_call(
        q, k, v, do, lse, delta, 0, 0, scale, True, dq_tile=tiles["tile"],
        dkv_tile=tiles["tile"], major=tiles["major"], fused=fused,
        window=window)
    for g, g_ref in zip(grads, vjp(do)):
        # under one key dQ and dK are zero: held to the cotangent's size
        assert float(np.abs(g - g_ref).max()) <= 2e-5 * max(
            1.0, float(np.abs(g_ref).max()))


def test_a_window_past_the_sequence_is_the_causal_mask_bit_for_bit():
    t, tiles = 512, dict(tile=(128, 128), major=256)
    rng = np.random.default_rng(1)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, t, 2, 64)),
                               jnp.float32) for _ in range(4))
    scale = 1.0 / 8

    def both(window):
        out, (lse,) = pallas_attention._fwd_call(
            q, k, v, 0, 0, scale, True, normalize=True, window=window,
            **tiles)
        delta = jnp.sum(do * out, axis=-1).transpose(0, 2, 1)
        return (out, lse) + tuple(pallas_attention._bwd_call(
            q, k, v, do, lse, delta, 0, 0, scale, True, major=256,
            dq_tile=tiles["tile"], dkv_tile=tiles["tile"], window=window))

    for got, want in zip(both(t), both(0)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dead_tiles_are_neither_walked_nor_fetched():
    """At 8192 positions under 4096 keys, tiles of 512 in major tiles of
    2048: the last Q tile walks 9 of the 16 K blocks (7 open, the
    diagonal's and the far edge's) and fetches three of the four major
    tiles; the first K tile is walked by 9 Q blocks and fetches the first
    three major tiles of Q. Without a window the ranges are the causal
    ones."""
    def ranges(q_first, window):
        blocks = [pallas_attention._kv_ranges(
            jnp.int32(q_first), jnp.int32(base), 512, 512, 4, True, 1,
            window) for base in range(0, 8192, 2048)]
        return [sum(int(r[kind][1]) - int(r[kind][0]) for r in blocks
                    if r[kind] is not None) for kind in range(3)]

    assert ranges(7680, 4096) == [7, 1, 1]
    assert ranges(7680, 0) == [15, 1, 0]
    assert ranges(0, 4096) == [0, 1, 0]
    assert ranges(4096, 4096) == [7, 1, 1]
    assert ranges(4096, 4000) == [6, 1, 2]      # a partial edge: two blocks
    index = pallas_attention._kv_major_index(512, 2048, 4, True, 1, 4096)
    offs = jnp.zeros(2, jnp.int32)
    assert [int(index(15, kk, offs)) for kk in range(4)] == [1, 1, 2, 3]
    assert [int(index(0, kk, offs)) for kk in range(4)] == [0, 0, 0, 0]

    def seers(k_first, window):
        blocks = [pallas_attention._q_ranges(
            jnp.int32(k_first), jnp.int32(base), 512, 512, 4, 1, window)
            for base in range(0, 8192, 2048)]
        return [sum(int(r[kind][1]) - int(r[kind][0]) for r in blocks
                    if r[kind] is not None) for kind in range(3)]

    assert seers(0, 4096) == [7, 1, 1]
    assert seers(0, 0) == [15, 1, 0]
    assert seers(7680, 4096) == [0, 1, 0]
    q_index = pallas_attention._q_major_index(512, 512, 2048, 4, True, 1,
                                              4096)
    assert [int(q_index(0, kk, offs)) for kk in range(4)] == [0, 1, 2, 2]
    assert [int(q_index(15, kk, offs)) for kk in range(4)] == [3, 3, 3, 3]


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("window", [128, 200, 1, 700],
                         ids=["a_tile", "odd", "one_key", "past"])
@pytest.mark.parametrize("use_flash", [True, False],
                         ids=["kernels", "einsum"])
def test_the_op_under_a_window_against_the_dense_mask(use_flash, window,
                                                      kv_heads):
    """The op through the executor, on the kernels (interpreted; T = 512
    in tiles of 512 here) and on the einsum path, with and without
    grouped-query heads: output and the three gradients."""
    t = 512 if use_flash else 96
    rng = np.random.default_rng(window + kv_heads)
    q = rng.standard_normal((1, t, 4, 32)).astype(np.float32)
    k, v = (rng.standard_normal((1, t, kv_heads, 32)).astype(np.float32)
            for _ in range(2))
    outs, grads, cot = run_op(
        "scaled_dot_product_attention", {"Q": q, "K": k, "V": v},
        {"Out": "float32", "LSE": "float32"},
        {"causal": True, "use_flash": use_flash, "window": window},
        ("Q", "K", "V"))
    want, vjp = jax.vjp(
        lambda q, k, v: dense_window(q, k, v, window, 1 / np.sqrt(32)),
        *map(jnp.asarray, (q, k, v)))
    close(outs["Out"], want, tol=1e-5)
    for slot, g_ref in zip(("Q", "K", "V"), vjp(jnp.asarray(cot))):
        close(grads[slot], g_ref, tol=2e-5)


def test_a_window_needs_a_causal_mask_and_no_ring():
    q = np.zeros((1, 64, 2, 16), np.float32)
    with pytest.raises(Exception, match="window=8 needs causal"):
        run_op("scaled_dot_product_attention", {"Q": q, "K": q, "V": q},
               {"Out": "float32", "LSE": "float32"},
               {"causal": False, "window": 8}, ())
    with pytest.raises(ValueError, match="window of 8 keys"):
        pallas_attention._fwd_call(q, q, q, 0, 0, 0.25, True, True,
                                   block=4, window=8)
    from paddle_tpu.parallel import mesh as mesh_mod
    from test_nemotron_h import _run_op
    main = fluid.Program()
    main._mesh = mesh_mod.make_mesh((2,), ("sp",))
    with fluid.program_guard(main, fluid.Program()):
        with pytest.raises(Exception, match="ring attention takes no "
                                            "window"):
            _run_op(main, "scaled_dot_product_attention",
                    {"Q": q, "K": q, "V": q},
                    {"Out": "float32", "LSE": "float32"},
                    {"causal": True, "window": 8,
                     "sequence_parallel": True}, ())


def test_fused_attention_writes_the_window_only_when_it_is_set():
    with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                  fluid.Program()):
        q = fluid.layers.data(name="q", shape=[1, 64, 2, 16],
                              dtype="float32", append_batch_size=False)
        fluid.layers.fused_attention(q, q, q, causal=True)
        fluid.layers.fused_attention(q, q, q, causal=True, window=24)
        plain, windowed = fluid.default_main_program().global_block().ops
    assert "window" not in plain.desc.attrs
    assert windowed.attr("window") == 24


# --- 3. what has positions ---------------------------------------------------

@pytest.mark.parametrize("rotated", [0, 1], ids=["no_positions", "rotary"])
def test_a_layer_without_rotation_knows_no_position(rotated):
    """A full-attention layer's last query over the same keys and values
    in another order: without the rotation the order of the earlier
    tokens cannot matter (no positional encoding at all: causal attention
    over a set), with it the output moves. (ISSUE 46 asks for every
    position index shifted; a uniform shift leaves a rotary layer's
    scores unchanged too, RoPE being relative, so the order is what tells
    the two kinds apart.)"""
    rng = np.random.default_rng(8)
    t = 32
    q, k, v = (rng.standard_normal((1, t, 2, 16)).astype(np.float32)
               for _ in range(3))
    order = np.concatenate([rng.permutation(t - 1), [t - 1]])

    def last_row(k, v):
        main = fluid.Program()
        with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
            qv, kv, vv = (fluid.layers.data(
                name=n, shape=list(q.shape), dtype="float32",
                append_batch_size=False) for n in "qkv")
            if rotated:
                qv, kv = (fluid.layers.rotary_embedding(x, theta=1.5e6)
                          for x in (qv, kv))
            out = fluid.layers.fused_attention(qv, kv, vv, causal=True)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            return exe.run(main, feed={"q": q, "k": k, "v": v},
                           fetch_list=[out])[0][0, -1]

    moved = np.abs(last_row(k[:, order], v[:, order]) - last_row(k, v)).max()
    assert moved <= 1e-6 if not rotated else moved >= 1e-2


# --- 4. the router -----------------------------------------------------------

def block_outputs(x, a, seed=2):
    """moe_block(x, router_input=a) over arrays: (Out, TopkIdx,
    TopkWeight), the weights from a fixed seed."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with unique_name.guard(), fluid.program_guard(main, startup):
        xv, av = (fluid.layers.data(name=n, shape=list(x.shape),
                                    dtype="float32", append_batch_size=False)
                  for n in "xa")
        out = fluid.layers.moe_block(xv, 8, 3, 32, gated=True,
                                     scoring="softmax", router_input=av,
                                     gate_act="relu")
        router, = [op for op in main.global_block().ops
                   if op.type == "moe_router"]
        fetch = [out, router.output("TopkIdx")[0],
                 router.output("TopkWeight")[0]]
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        return exe.run(main, feed={"x": x, "a": a}, fetch_list=fetch)


def test_the_router_reads_its_own_input():
    """Moving what the experts read leaves the chosen experts and their
    weights as they were, and moves the output; moving what the router
    reads moves the choice."""
    rng = np.random.default_rng(5)
    a, b, b2, a2 = (rng.standard_normal((2, 16, 24)).astype(np.float32)
                    for _ in range(4))
    out, idx, weight = block_outputs(b, a)
    out_b, idx_b, weight_b = block_outputs(b2, a)
    np.testing.assert_array_equal(idx_b, idx)
    np.testing.assert_array_equal(weight_b, weight)
    assert np.abs(out_b - out).max() > 1e-3
    _, idx_a, _ = block_outputs(b, a2)
    assert (idx_a != idx).any()


def test_the_default_router_input_is_what_the_experts_read():
    with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                  fluid.Program()):
        x = fluid.layers.data(name="x", shape=[2, 8, 16], dtype="float32",
                              append_batch_size=False)
        fluid.layers.moe_block(x, 8, 2, 12, gated=True)
        ops = fluid.default_main_program().global_block().ops
    router, = [op for op in ops if op.type == "moe_router"]
    experts, = [op for op in ops if op.type == "moe_experts"]
    assert router.input("X") == experts.input("X")
    assert "gate_act" not in experts.desc.attrs


def test_a_softmax_over_the_chosen_is_the_softmax_over_all_renormalised():
    """The published order (top-k of the logits, a softmax over the six)
    against the op's (a softmax over all, the top-k renormalised): the
    same experts and, to rounding, the same weights; on the op and on the
    reference's route()."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    outs, _, _ = run_op(
        "moe_router", {"X": x, "W": w, "Bias": np.zeros(8, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"scoring": "softmax", "top_k": 3, "norm_topk_prob": True}, ())
    logits = x.astype(np.float64) @ w.astype(np.float64)
    want_idx = np.argsort(-logits, -1)[:, :3]
    top = np.take_along_axis(logits, want_idx, -1)
    over_chosen = np.exp(top - top.max(-1, keepdims=True))
    over_chosen /= over_chosen.sum(-1, keepdims=True)
    np.testing.assert_array_equal(outs["TopkIdx"], want_idx)
    close(outs["TopkWeight"], over_chosen, tol=1e-6)
    ref_top, ref_idx = jax.lax.top_k(jnp.asarray(x) @ jnp.asarray(w), 3)
    np.testing.assert_array_equal(np.asarray(ref_idx), want_idx)
    close(jax.nn.softmax(ref_top, -1), over_chosen, tol=1e-6)


# --- 5. the gate -------------------------------------------------------------

def experts_op(x, idx, weight, gate, up, down, offset=0, held=None,
               num_experts=8, wrt=(), **attrs):
    held = gate.shape[0] if held is None else held
    return run_op(
        "moe_experts",
        {"X": x, "TopkIdx": idx, "TopkWeight": weight, "WGate": gate,
         "W1": up, "W2": down},
        dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                       "LoadMaxOverMean", "RowsHandled", "Up", "GateUp"),
                      "float32"),
        dict(attrs, num_experts=num_experts, experts_held=held,
             expert_offset=offset, top_k=idx.shape[1]), wrt)


def test_the_relu_gate_against_the_formula_and_shut_it_passes_nothing():
    """relu(x G) * (x U) D a chosen expert; where the gate is closed
    (x G < 0 in every column: G = -|.| on a positive x) the output and
    every gradient through it are exactly zero, which a silu gate's are
    not."""
    rng = np.random.default_rng(11)
    n, d, f = 24, 16, 20
    x = np.abs(rng.standard_normal((n, d))).astype(np.float32)
    idx = np.stack([rng.permutation(4)[:2] for _ in range(n)]).astype(
        np.int32)
    weight = rng.random((n, 2)).astype(np.float32)
    gate, up = (rng.standard_normal((4, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((4, f, d)).astype(np.float32) * 0.3
    outs, _, _ = experts_op(x, idx, weight, gate, up, down, num_experts=4,
                            gate_act="relu")
    want = np.zeros((n, d))
    for j in range(2):
        for e in range(4):
            rows = idx[:, j] == e
            h = np.maximum(x[rows] @ gate[e], 0) * (x[rows] @ up[e])
            want[rows] += weight[rows, j, None] * (h @ down[e])
    close(outs["Out"], want, tol=1e-5)

    shut = -np.abs(gate)
    wrt = ("X", "WGate", "W1", "W2", "TopkWeight")
    outs, grads, _ = experts_op(x, idx, weight, shut, up, down,
                                num_experts=4, wrt=wrt, gate_act="relu")
    assert np.all(outs["Out"] == 0.0)
    for slot in wrt:
        assert np.all(np.asarray(grads[slot]) == 0.0), slot
    outs, grads, _ = experts_op(x, idx, weight, shut, up, down,
                                num_experts=4, wrt=wrt)
    assert np.abs(outs["Out"]).max() > 0 and np.abs(grads["W1"]).max() > 0


PARENT_PROGRAMS = {
    # sha256 of main.to_json() | startup.to_json() at PR 45's tree
    # (the programs with expert layers: at PR 58's, whose moe_experts ops
    # write Up / GateUp for an explicit gradient op; the hybrid program
    # at PR 60's, whose causal_conv1d ops carry `time_on_lanes` and an
    # explicit gradient op)
    "glm-4.7-flash": ("c6c56c119b5b8e5c", "57465f9570324186"),
    "sdar-30b-a3b-chat": ("ae3fbd7051acd413", "d87f315e59443b2d"),
    "nemotron3-nano-30b-a3b": ("6ac48d33c64fc359", "2cd691daa316a1c1"),
    "gpt2": ("32530ba784525f48", "6cae3670f852b823"),
}


@pytest.mark.parametrize("name", PARENT_PROGRAMS)
def test_a_program_that_asks_for_none_of_it_is_the_one_it_was(name):
    """No `window` on an attention op, no `gate_act` on an expert op, and
    the serialised programs of the accepted language configurations equal
    to the parent's (PR 45), by their hashes: a PR that changes one of
    these programs on purpose pins its own."""
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    for op in main.global_block().ops:
        assert not {"window", "gate_act"} & set(op.desc.attrs), op.type
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]


# --- 6. the shares -----------------------------------------------------------

def test_shares_add_up_and_an_unheld_token_gets_zero():
    """8 ReGLU experts top-3 in eight shares of one under the router's
    softmax over the chosen, no shared expert: what the shares give adds
    up to the uncut layer and to the reference's experts() over all
    eight, and in each share a token none of whose choices is held there
    gets exactly zero."""
    rng = np.random.default_rng(9)
    n, d, f = 48, 16, 24
    x, a = (rng.standard_normal((n, d)).astype(np.float32) for _ in range(2))
    w_r = rng.standard_normal((d, 8)).astype(np.float32)
    routed, _, _ = run_op(
        "moe_router", {"X": a, "W": w_r, "Bias": np.zeros(8, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"scoring": "softmax", "top_k": 3}, ())
    idx, weight = routed["TopkIdx"], routed["TopkWeight"]
    gate, up = (rng.standard_normal((8, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((8, f, d)).astype(np.float32) * 0.3

    def share(offset, held):
        cut = slice(offset, offset + held)
        return experts_op(x, idx, weight, gate[cut], up[cut], down[cut],
                          offset=offset, gate_act="relu")[0]

    whole = share(0, 8)
    parts = [share(offset, 1) for offset in range(8)]
    close(sum(p["Out"] for p in parts), whole["Out"], tol=1e-5)
    assert sum(p["RowsRouted"][0] for p in parts) == n * 3
    for offset, part in enumerate(parts):
        unheld = ~(idx == offset).any(-1)
        assert unheld.any() and (~unheld).any()
        assert np.all(part["Out"][unheld] == 0.0)
        assert np.abs(part["Out"][~unheld]).min(-1).max() > 0
    # the reference's layer over all eight, from the same router input
    top, ref_idx = jax.lax.top_k(jnp.asarray(a) @ jnp.asarray(w_r), 3)
    ref_weight = jax.nn.softmax(top, -1)
    want = sum(
        (ref_weight * (ref_idx == e)).sum(-1, keepdims=True)
        * ((jax.nn.relu(x @ gate[e]) * (x @ up[e])) @ down[e])
        for e in range(8))
    close(whole["Out"], want, tol=1e-5)
