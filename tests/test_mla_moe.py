"""Latent attention, the rotary embedding, gated experts, the
multi-token-prediction module and the parameters it shares with the main
model (layers/nn.py, ops/hybrid_ops.py, models.mla_moe_lm), at tiny sizes
on the CPU: the rotation's two properties; the assembled attention
against the equations written head by head; the share test of the gated
expert layer; a parameter read by two layers (one variable, one gradient
that is the sum, one Adam slot); the whole tiny model against
benchmarks/families/glm4_moe_lite.py::reference_loss; the flash kernels
at head size 256; and the tables that must know every new op."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name

from benchmarks import run
from test_nemotron_h import close, first_step, routed, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-glm-moe-lite"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- the rotation ------------------------------------------------------------

def rotate(x, theta=1e6, dims=None):
    outs, _, _ = run_op("rotary_embedding", {"X": x}, {"Out": "float32"},
                        {"theta": theta, "rotary_dims": dims or x.shape[-1]})
    return outs["Out"]


def test_rotation_keeps_norms_and_the_leading_dims():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 24, 3, 32)).astype(np.float32)
    out = rotate(x, dims=8)
    np.testing.assert_array_equal(out[..., :24], x[..., :24])
    close(np.linalg.norm(out[..., 24:], axis=-1),
          np.linalg.norm(x[..., 24:], axis=-1), tol=1e-6)
    np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-7)   # t = 0
    assert np.abs(out[:, 1:, :, 24:] - x[:, 1:, :, 24:]).max() > 0.1


def test_rotation_is_relative_and_pairs_i_with_i_plus_half():
    """<R_s q, R_t k> depends on s - t alone; and the angle of the pair
    (i, i + r/2) at position t is t * theta^(-2i/r)."""
    rng = np.random.default_rng(1)
    t, r = 16, 8
    q = np.broadcast_to(rng.standard_normal((1, 1, 1, r)), (1, t, 1, r))
    k = np.broadcast_to(rng.standard_normal((1, 1, 1, r)), (1, t, 1, r))
    rq = rotate(q.astype(np.float32), theta=100.0)[0, :, 0]
    rk = rotate(k.astype(np.float32), theta=100.0)[0, :, 0]
    dots = rq @ rk.T                                    # [s, t]
    for lag in range(-3, 4):
        diagonal = np.diagonal(dots, offset=lag)
        close(diagonal, np.full_like(diagonal, diagonal[0]), tol=1e-5)
    assert np.ptp(dots[0]) > 0.1                        # and on nothing less
    one = np.zeros((1, t, 1, r), np.float32)
    one[..., 1] = 1.0                                   # e_1, paired with 5
    turned = rotate(one, theta=100.0)[0, :, 0]
    angle = np.arange(t) * 100.0 ** (-2.0 / r)
    close(turned[:, 1], np.cos(angle), tol=1e-5)
    close(turned[:, 1 + r // 2], np.sin(angle), tol=1e-5)


# --- the assembled attention -------------------------------------------------

SIZES = dict(num_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32)


def mla_by_head(x, w, theta, eps=1e-5):
    """ISSUE 33 section 1, one head and one position pair at a time."""
    w_qa, qn, w_qb, w_kva, kvn, w_kvb, w_o = w
    heads, nope, rope, vd = 4, 24, 8, 32
    t = x.shape[0]

    def rms(v, g):
        return g * v / np.sqrt((v ** 2).mean(-1, keepdims=True) + eps)

    def turn(v, pos):
        out = v.copy()
        for i in range(rope // 2):
            a = pos * theta ** (-2.0 * i / rope)
            out[i] = v[i] * np.cos(a) - v[i + rope // 2] * np.sin(a)
            out[i + rope // 2] = v[i + rope // 2] * np.cos(a) \
                + v[i] * np.sin(a)
        return out

    q = (rms(x @ w_qa, qn) @ w_qb).reshape(t, heads, nope + rope)
    kva = x @ w_kva
    kv = (rms(kva[:, :16], kvn) @ w_kvb).reshape(t, heads, nope + vd)
    k_rope = np.stack([turn(kva[p, 16:], p) for p in range(t)])
    out = np.zeros((t, heads, vd))
    for j in range(heads):
        q_j = np.stack([np.concatenate([q[p, j, :nope],
                                        turn(q[p, j, nope:], p)])
                        for p in range(t)])
        k_j = np.concatenate([kv[:, j, :nope], k_rope], axis=1)
        for p in range(t):
            s = q_j[p] @ k_j[:p + 1].T / np.sqrt(nope + rope)
            prob = np.exp(s - s.max())
            out[p, j] = (prob / prob.sum()) @ kv[:p + 1, j, nope:]
    return out.reshape(t, heads * vd) @ w_o


def test_latent_attention_against_the_equations_head_by_head():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1, 12, 20)).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.layers.data(name="x", shape=[1, 12, 20], dtype="float32",
                               append_batch_size=False)
        out = fluid.layers.latent_attention(xv, rope_theta=50.0, **SIZES)
    names = [p.name for p in main.global_block().all_parameters()]
    assert [tuple(p.shape) for p in main.global_block().all_parameters()] \
        == [(20, 24), (24,), (24, 128), (20, 24), (16,), (16, 224), (128, 20)]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        for n in names:      # norms away from 1, maps away from 0.02
            v = np.asarray(scope.find_var(n))
            scope.set_var(n, (rng.standard_normal(v.shape) * 0.3
                              + (v.ndim == 1)).astype(np.float32))
        weights = [np.asarray(scope.find_var(n), np.float64) for n in names]
        got, = exe.run(main, feed={"x": x}, fetch_list=[out])
    close(got[0], mla_by_head(x[0].astype(np.float64), weights, 50.0),
          tol=2e-5)


# --- gated experts -----------------------------------------------------------

def gated_loop(x, idx, weight, gate, up, down, offset=0):
    silu = lambda v: v / (1.0 + np.exp(-v))             # noqa: E731
    out = np.zeros_like(x)
    for e in range(gate.shape[0]):
        mine = (weight * (idx == offset + e)).sum(-1, keepdims=True)
        out += mine * ((silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


def test_gated_shares_add_up_to_the_uncut_layer():
    """8 gated experts in the two shares 0-3 and 4-7: what the shares
    give, with the shared expert counted once, is the uncut layer; and
    the op's gradients are those of the plain loop."""
    rng = np.random.default_rng(7)
    n, d, f, k = 40, 16, 24, 3
    x = rng.standard_normal((n, d)).astype(np.float32)
    gate, up = (rng.standard_normal((8, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((8, f, d)).astype(np.float32) * 0.3
    s_g, s_u = (rng.standard_normal((d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    s_d = rng.standard_normal((f, d)).astype(np.float32) * 0.3
    shared = ((x @ s_g) / (1 + np.exp(-(x @ s_g))) * (x @ s_u)) @ s_d
    idx, weight = routed(rng, n, k, 8)

    def share(offset, held, wrt=()):
        return run_op(
            "moe_experts",
            {"X": x, "TopkIdx": idx, "TopkWeight": weight,
             "WGate": gate[offset:offset + held],
             "W1": up[offset:offset + held],
             "W2": down[offset:offset + held]},
            {"Out": "float32", "RowsRouted": "float32",
             "RowsCombined": "float32", "LoadMaxOverMean": "float32",
             "Up": "float32", "GateUp": "float32"},
            {"num_experts": 8, "experts_held": held,
             "expert_offset": offset, "top_k": k}, wrt)

    parts = [share(0, 4)[0], share(4, 4)[0]]
    wrt = ("X", "TopkWeight", "WGate", "W1", "W2")
    whole, grads, cot = share(0, 8, wrt)
    close(sum(p["Out"] for p in parts) + shared, whole["Out"] + shared,
          tol=1e-5)
    close(whole["Out"] + shared,
          gated_loop(x, idx, weight, gate, up, down) + shared, tol=1e-4)
    assert sum(p["RowsRouted"][0] for p in parts) == n * k \
        == whole["RowsRouted"][0] == whole["RowsCombined"][0]

    def plain(x, wt, g, u, dn):
        out = 0.0
        for e in range(8):
            mine = (wt * (idx == e)).sum(-1, keepdims=True)
            out = out + mine * ((jax.nn.silu(x @ g[e]) * (x @ u[e])) @ dn[e])
        return (out * cot).sum()

    want = jax.grad(plain, argnums=range(5))(x, weight, gate, up, down)
    for slot, g in zip(wrt, want):
        close(grads[slot], g, tol=2e-4)


def program_text(*programs):
    return "\n".join(
        "%s %s %s %s" % (op.type, sorted(op.desc.inputs.items()),
                         sorted(op.desc.outputs.items()),
                         sorted(op.desc.attrs.items()))
        for program in programs for op in program.global_block().ops)


def test_moe_block_without_gated_is_the_program_it_was():
    """The hybrid cell's layer: its ops, slots, names and attributes,
    forward, backward and startup, hash to what the tree before the gated
    form gave (tools: the same lines on commit 6312135) with the output
    slot `RowsHandled` that PR 36 added to `moe_experts` and the slot `Up`
    that PR 58 added for its gradient op, which since then is the op's
    own (the inputs, Up and Out's cotangent) and not the generic one."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2, 8, 16], dtype="float32",
                              append_batch_size=False)
        out = fluid.layers.moe_block(x, 8, 2, 12, shared_width=24,
                                     experts_held=4, expert_offset=4,
                                     scaling=2.5)
        fluid.backward.append_backward(fluid.layers.mean(out))
    text = program_text(main, startup)
    assert "WGate" not in text and "silu" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "90bbd9d550472d245975d9750e31e151731af045309abf4018343c6e855f4904"
    # and the same call's gated form has the third matrix
    with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                  fluid.Program()):
        x = fluid.layers.data(name="x", shape=[2, 8, 16], dtype="float32",
                              append_batch_size=False)
        fluid.layers.moe_block(x, 8, 2, 12, shared_width=24, experts_held=4,
                               gated=True)
        ops = fluid.default_main_program().global_block().ops
    experts, = [op for op in ops if op.type == "moe_experts"]
    assert experts.input("WGate") and [op.type for op in ops].count("mul") == 3


# --- one parameter, two readers ----------------------------------------------

def two_readers(shared):
    """Two lookups in a table and two products with a head, summed into
    one loss: with `shared` both pairs name one parameter, else each
    reader has a copy (set equal after startup). -> {parameter: gradient}"""
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 11, (2, 6)).astype(np.int64)
    nxt = rng.integers(0, 11, (2, 6)).astype(np.int64)
    names = ("emb", "emb", "head", "head") if shared else \
        ("emb", "emb_b", "head", "head_b")
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        a, b = (fluid.layers.data(name=n, shape=[2, 6], dtype="int64",
                                  append_batch_size=False) for n in "ab")
        attr = lambda n: fluid.ParamAttr(name=n)        # noqa: E731
        x = fluid.layers.embedding(a, size=[11, 8], param_attr=attr(names[0]))
        y = fluid.layers.tanh(fluid.layers.elementwise_add(
            x, fluid.layers.embedding(b, size=[11, 8],
                                      param_attr=attr(names[1]))))
        first = fluid.layers.fc(x, 11, num_flatten_dims=2, bias_attr=False,
                                param_attr=attr(names[2]))
        second = fluid.layers.fc(y, 11, num_flatten_dims=2, bias_attr=False,
                                 param_attr=attr(names[3]))
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(
            fluid.layers.tanh(first), second))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        if not shared:
            for src in ("emb", "head"):
                scope.set_var(src + "_b", np.asarray(scope.find_var(src)))
        got = exe.run(main, feed={"a": ids, "b": nxt},
                      fetch_list=[grad_var_name(n) for n in set(names)])
    return dict(zip(set(names), (np.asarray(g) for g in got))), main, startup


def test_a_shared_parameter_gets_the_sum_of_its_readers_gradients():
    one, main, startup = two_readers(shared=True)
    two, _, _ = two_readers(shared=False)
    close(one["emb"], two["emb"] + two["emb_b"], tol=1e-6)
    close(one["head"], two["head"] + two["head_b"], tol=1e-6)
    assert np.abs(two["emb_b"]).max() > 0 and np.abs(two["head_b"]).max() > 0
    # the program holds each once: one variable, one init op, one Adam op
    params = [p.name for p in main.global_block().all_parameters()]
    assert sorted(params) == ["emb", "head"]
    inits = [op.output_arg_names[0] for op in startup.global_block().ops]
    assert inits.count("emb") == inits.count("head") == 1
    adam = [op.input("Param")[0] for op in main.global_block().ops
            if op.type == "adam"]
    assert sorted(adam) == ["emb", "head"]


def test_sharing_at_another_shape_is_refused():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        a = fluid.layers.data(name="a", shape=[2, 6], dtype="int64",
                              append_batch_size=False)
        fluid.layers.embedding(a, size=[11, 8],
                               param_attr=fluid.ParamAttr(name="emb"))
        with pytest.raises(ValueError, match="shared at shape"):
            fluid.layers.embedding(a, size=[11, 4],
                                   param_attr=fluid.ParamAttr(name="emb"))


# --- the whole tiny model against the reference ------------------------------

@pytest.fixture(scope="module")
def o2_step():
    return first_step("O2", TINY)


def test_tiny_model_against_the_reference_in_float32():
    """Loss, every gradient (the shared embedding's and head's are sums
    over both readers in program and reference alike), its norm, the tail
    and one Adam step, to float32 rounding: measured 0, 2.6e-7, 2.6e-8,
    3.6e-7 and 1.1e-5 (the update's own arithmetic)."""
    found, main, names = first_step(None, TINY)
    assert len(names) == 67        # embedding and head once, no router bias
    assert found["loss_rel_diff"] <= 1e-6
    assert found["grad_rel_err"] <= 1e-5
    assert found["grad_tail_rel_err"] <= 1e-5
    assert found["grad_norm_rel_diff"] <= 1e-5
    assert found["update_rel_err"] <= 1e-3


def test_tiny_model_against_the_reference_under_amp(o2_step):
    found, _, names = o2_step
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.01
    # the tail is the module's last two tensors: it is built after the head
    assert names.index("mla_moe_lm.head") < len(names) - 2


def test_one_master_and_one_adam_slot_for_each_shared_table(o2_step):
    """(The one bf16 cast of the shared head is the compiler's doing: it
    merges the two readers' converts; tests/test_tpu_compile.py reads it
    in the step compiled for the chip.)"""
    from paddle_tpu.models import mla_moe
    _, main, names = o2_step
    block = main.global_block()
    readers = {name: [op.type for op in block.ops
                      if name in op.input_arg_names
                      and op.desc.attrs.get("op_role") != "backward"
                      and op.type != "adam"]
               for name in (mla_moe.EMBEDDING, mla_moe.HEAD)}
    assert readers == {mla_moe.EMBEDDING: ["lookup_table"] * 2,
                       mla_moe.HEAD: ["mul"] * 2}
    for name in readers:
        assert names.count(name) == 1
        adam = [op for op in block.ops if op.type == "adam"
                and op.input("Param") == [name]]
        assert len(adam) == 1
        moments = [n for n in block.vars
                   if name in n and "moment" in n.lower()]
        assert len(moments) == 2, moments              # m and v, once
        sums = [op for op in block.ops if op.type == "sum"
                and op.output("Out") == [grad_var_name(name)]]
        assert len(sums) == 1                          # two readers, one sum


def test_batches_hold_the_ids_one_and_two_on_and_no_loss_ignores_any():
    config, family = tiny()
    feed = family.make_batch(config, 3, np.random.default_rng(2 ** 31 + 5))
    t = config["sequence_length"]
    assert {k: v.shape for k, v in feed.items()} == \
        {"tok": (3, t), "lab": (3, t), "lab2": (3, t)}
    np.testing.assert_array_equal(feed["lab"][:, :-1], feed["tok"][:, 1:])
    np.testing.assert_array_equal(feed["lab2"][:, :-1], feed["lab"][:, 1:])
    np.testing.assert_array_equal(feed["lab2"][:, :-2], feed["tok"][:, 2:])
    assert family.items_per_batch(feed) == 3 * t
    # the module's loss is a mean over all B x T positions: changing the
    # last position's second label alone moves it
    params = [jnp.asarray(np.random.default_rng(i).standard_normal(
        shape).astype(np.float32) * 0.05 + (len(shape) == 1))
        for i, shape in enumerate(reference_shapes(config, family))]
    other = dict(feed, lab2=feed["lab2"].copy())
    other["lab2"][-1, -1] = (other["lab2"][-1, -1] + 1) % config["vocab_size"]
    only_mtp = dict(config, mtp_loss_weight=1.0)
    a = float(family.reference_loss(only_mtp, params, feed))
    b = float(family.reference_loss(only_mtp, params, other))
    assert abs(a - b) > 1e-7


def reference_shapes(config, family):
    main, _, _ = family.build(config)
    return [tuple(p.shape) for p in main.global_block().all_parameters()
            if p.trainable]


def test_the_two_losses_and_the_routing_reach_telemetry():
    """Side-fetches, no fetch by the user: a gauge for each loss whose
    weighted sum is the fetched loss, and a routing sample a step for
    each of the three expert layers (the module's block is the third)."""
    from paddle_tpu import telemetry
    config, family = tiny()
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(0))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for _ in range(2):
            out, = exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()     # a side-fetch still in flight is published here
    label = telemetry.program_label(main)
    main_loss = telemetry.read_gauge("loss_main", program=label)
    mtp_loss = telemetry.read_gauge("loss_mtp", program=label)
    assert main_loss > 1.0 and mtp_loss > 1.0
    assert float(np.ravel(out)[0]) == pytest.approx(
        main_loss + config["mtp_loss_weight"] * mtp_loss, rel=1e-6)
    for layer in ("0", "1", "2"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        combined = telemetry.read_histogram("moe_rows_combined",
                                            program=label, layer=layer)
        handled = telemetry.read_histogram("moe_rows_handled", program=label,
                                           layer=layer)
        assert rows["count"] == handled["count"] == 2 and combined == rows
        assert handled["sum"] >= rows["sum"]
    assert telemetry.read_histogram("moe_rows_routed", program=label,
                                    layer="3") is None


# --- the flash kernels at head size 256 --------------------------------------

def test_flash_kernels_at_head_size_256_against_einsum():
    """[1, 256, 3, 256] causal float32, interpreted: one head a lane
    block of 256 lanes, forward and the three gradients; tiles of 128 so
    that the walk, the diagonal and the skip are all taken."""
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.parallel.ring_attention import attention_reference
    rng = np.random.default_rng(4)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, 256, 3, 256)),
                               jnp.float32) for _ in range(4))
    assert pa._lane_block(3, 256) == (256, 1)
    assert pa.ineligible(q, k, v) is None
    want, vjp = jax.vjp(lambda *a: attention_reference(*a, causal=True),
                        q, k, v)
    out, (lse,) = pa._fwd_call(q, k, v, 0, 0, 1 / 16.0, True,
                               normalize=True, tile=(128, 128))
    close(out, want, tol=1e-5)
    delta = jnp.sum(do * out, axis=-1).transpose(0, 2, 1)
    grads = pa.flash_attention_bwd_block(
        q, k, v, do, lse, delta, 0, 0, 1 / 16.0, True, dq_tile=(128, 128),
        dkv_tile=(128, 128))
    for got, wanted in zip(grads, vjp(do)):
        close(got, wanted, tol=1e-4)


def test_the_model_takes_the_flash_kernels_where_they_tile():
    """T = 512 (the 'auto' rule's floor) at the tiny widths: each of the
    four attention ops books a hit and none a fallback."""
    from paddle_tpu import telemetry
    config, family = tiny(sequence_length=512, num_hidden_layers=1,
                          first_k_dense_replace=1)
    main, startup, loss = family.build(config)
    key = "op=scaled_dot_product_attention"
    hits = telemetry.read_series("pallas_kernel_total").get(key, 0)
    falls = sum(v for k, v in telemetry.read_series(
        "pallas_fallback_total").items() if k.startswith(key))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        out, = exe.run(main, feed=family.make_batch(
            config, 1, np.random.default_rng(0)), fetch_list=[loss])
    assert np.isfinite(out).all()
    assert telemetry.read_series("pallas_kernel_total")[key] - hits == 2
    assert sum(v for k, v in telemetry.read_series(
        "pallas_fallback_total").items() if k.startswith(key)) == falls


# --- a trace can book every op of a layer to it -----------------------------

@pytest.fixture(scope="module")
def tiny_program():
    config, family = tiny()
    main, _, loss = family.build(config)
    return main, loss


def test_the_layers_build_every_op_under_their_name(tiny_program):
    """What mla_time_pct.train and mtp_time_pct.train read."""
    main, _ = tiny_program
    under = {}
    for op in main.global_block().ops:
        under.setdefault(op.desc.attrs.get("op_namescope"), []).append(op.type)
    mla = under["/latent_attention/"]
    for op_type, count in (("mul", 5 * 3), ("rms_norm", 2 * 3),
                           ("scaled_dot_product_attention", 3),
                           ("concat", 3), ("expand", 3)):
        assert mla.count(op_type) == mla.count(op_type + "_grad") == count
    rotary = under["/latent_attention/rotary_embedding/"]
    assert rotary.count("rotary_embedding") == 6 == \
        rotary.count("rotary_embedding_grad")
    assert under["/mtp_block/latent_attention/"].count("mul") == 5
    assert under["/mtp_block/moe_block/"].count("moe_experts") == 1
    assert under["/mtp_block/"].count("lookup_table") == 1
    assert under["/mtp_block/"].count("mul") == 2        # eh_proj, the head
    assert under["/gated_mlp/"].count("mul") == 3
    assert under["/moe_block/gated_mlp/"].count("silu") == 2
    assert not {"rotary_embedding", "scaled_dot_product_attention",
                "moe_experts"} & set(under[None])


def test_every_table_knows_the_new_ops(tiny_program):
    from paddle_tpu import roofline
    from paddle_tpu.analysis import infer
    main, _ = tiny_program
    assert "rotary_embedding" in {op.type for op in main.global_block().ops}
    assert infer.rule_kind("rotary_embedding") == "registry"
    assert infer.rule_kind("rotary_embedding_grad") == "grad"

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    ins, outs = {"X": [aval(2, 6, 4, 8)]}, {"Out": [aval(2, 6, 4, 8)]}
    assert roofline.op_cost("rotary_embedding", ins, outs, {})[0] == \
        pytest.approx(6.0 * 384)
    ungated = {"X": [aval(10, 8)], "W1": [aval(4, 8, 12)]}
    attrs = {"top_k": 3, "experts_held": 4, "num_experts": 16}
    outs = {"Out": [aval(10, 8)]}
    two = roofline.op_cost("moe_experts", ungated, outs, attrs)[0]
    three = roofline.op_cost(
        "moe_experts", dict(ungated, WGate=[aval(4, 8, 12)]), outs, attrs)[0]
    assert two == pytest.approx(4.0 * 10 * 3 * 0.25 * 8 * 12)
    assert three == pytest.approx(1.5 * two)


def test_preflight_of_the_tiny_model_is_clean(tiny_program):
    from paddle_tpu import analysis
    main, loss = tiny_program
    report = analysis.analyze_program(main, feeds=["tok", "lab", "lab2"],
                                      fetches=[loss.name])
    bad = [d.format() for d in report.diagnostics if d.severity != "info"]
    assert not bad, bad


def test_family_arithmetic_at_the_published_sizes():
    """ISSUE 33 section 6's count: 85.4M a latent-attention block, 126M
    the dense feed-forward, 28.6M an expert layer, 2.87 GFLOP a token;
    six attention ops of 2.62 ms at the v5e's peak."""
    config = run.load_json("configs", "glm-4.7-flash")
    family = run.load_module("families", config["family"])
    per = family.part_flops_per_item(config)
    assert per["mla"] == pytest.approx(85.4e6, rel=2e-3)
    assert per["dense"] == pytest.approx(125.8e6, rel=1e-3)
    assert per["experts"] == pytest.approx(28.6e6, rel=2e-3)
    assert per["eh_proj"] + 2 * per["head"] == pytest.approx(175.4e6, rel=1e-3)
    assert family.required_flops_per_item(config) == pytest.approx(
        2.87e9, rel=3e-3)
    flops, bytes_ = family.attention_kernel_cost(config)
    assert family.attention_ops_per_step(config) == 6
    assert flops / 197e12 == pytest.approx(2.62e-3, rel=3e-3)
    assert bytes_ == 9 * 2 * 4096 * 20 * 256
