"""Manifold-constrained hyper-connections around latent attention and
experts (ops/hyper_connection_ops.py, layers.hyper_connection_maps /
hc_pre_mix / hc_post_res_mix, models.mla_moe_lm with `hc_mult`), at tiny
sizes on the CPU in float32: the sweeps and both mixes and their explicit
gradient ops against `jax.grad` of their plain forms; YaRN in the
DeepSeek keys; the whole tiny model against
benchmarks/families/xing4.py::reference_loss on the loss and every
gradient with phi, alpha and b at non-trivial values; a replayed block;
the share test; `hc_mult=None` leaves the program it was; the parameter
count; scopes, counters and gauges; the tables that must know every new
op."""

import functools
import hashlib
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod, telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.ops import hyper_connection_ops as hc_ops

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-xing4"
CELL_CONFIG = "xing4.0-29b-a4b"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the ops against their plain forms ------------------------------------

def plain_sinkhorn(logits, iters=20, eps=1e-6, clamp=(-30.0, 30.0)):
    """The equations on whole [.., n, n] arrays, sums along an axis."""
    m = jnp.exp(jnp.clip(logits, *clamp))
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


SINKHORN_OUTS = {"Out": "float32", "SumError": "float32",
                 "DiagonalMass": "float32"}


@pytest.mark.parametrize("spread,iters", [(1.0, 20), (4.0, 20), (1.0, 3)])
def test_sinkhorn_knopp_and_its_gradient_op(spread, iters):
    """The op (entry by entry, reciprocals) against the plain sweeps, and
    its explicit gradient op (the sweeps run again from M_0) against
    jax.grad of them; rows sum to 1, columns as far as converged; the two
    scalars it reports are the result's own."""
    rng = np.random.default_rng(int(10 * spread) + iters)
    logits = (spread * rng.standard_normal((2, 12, 4, 4))).astype(np.float32)
    attrs = {"iters": iters, "eps": 1e-6, "clamp_min": -30.0,
             "clamp_max": 30.0}
    outs, grads, cot = run_op("sinkhorn_knopp", {"Logits": logits},
                              SINKHORN_OUTS, attrs, ("Logits",))
    want = plain_sinkhorn(jnp.asarray(logits), iters)
    close(outs["Out"], want, tol=1e-5)
    close(outs["Out"].sum(-1), np.ones((2, 12, 4)), tol=1e-5)
    error = max(np.abs(np.asarray(want).sum(axis) - 1).max()
                for axis in (-1, -2))
    assert abs(float(outs["SumError"][0]) - error) <= 1e-5 + 0.02 * error
    assert float(outs["DiagonalMass"][0]) == pytest.approx(
        float(np.trace(np.asarray(want), axis1=-2, axis2=-1).mean() / 4),
        rel=1e-4)
    want_grad = jax.grad(lambda x: (plain_sinkhorn(x, iters)
                                    * cot).sum())(jnp.asarray(logits))
    close(grads["Logits"], want_grad, tol=2e-5)


def test_sinkhorn_clamps_its_logits():
    """Logits of 80 and -80 are those of 30 and -30: no overflow, and no
    gradient through a clamped entry."""
    logits = np.zeros((1, 2, 4, 4), np.float32)
    logits[0, 0, 0, 0], logits[0, 0, 1, 2] = 80.0, -80.0
    clamped = np.clip(logits, -30.0, 30.0)
    outs, grads, _ = run_op(
        "sinkhorn_knopp", {"Logits": logits}, SINKHORN_OUTS,
        {"iters": 20, "eps": 1e-6, "clamp_min": -30.0, "clamp_max": 30.0},
        ("Logits",))
    assert np.isfinite(outs["Out"]).all()
    close(outs["Out"], plain_sinkhorn(jnp.asarray(clamped)), tol=1e-5)
    assert grads["Logits"][0, 0, 0, 0] == 0 == grads["Logits"][0, 0, 1, 2]
    assert np.abs(grads["Logits"][0, 1]).max() > 0


def streams_and_maps(rng, b=2, t=10, n=4, c=24):
    x = rng.standard_normal((b, t, n, c)).astype(np.float32)
    y = rng.standard_normal((b, t, c)).astype(np.float32)
    pre = rng.random((b, t, n)).astype(np.float32)
    post = 2 * rng.random((b, t, n)).astype(np.float32)
    res = rng.random((b, t, n, n)).astype(np.float32)
    return x, y, pre, post, res


def test_pre_mix_and_its_gradient_op():
    x, _, pre, _, _ = streams_and_maps(np.random.default_rng(0))
    outs, grads, cot = run_op("hc_pre_mix", {"X": x, "Pre": pre},
                              {"Out": "float32"}, {}, ("X", "Pre"))

    def plain(x, pre):
        return jnp.einsum("btj,btjc->btc", pre, x)

    close(outs["Out"], plain(x, pre), tol=1e-6)
    want = jax.grad(lambda *a: (plain(*a) * cot).sum(), argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(pre))
    close(grads["X"], want[0], tol=1e-6)
    close(grads["Pre"], want[1], tol=1e-5)


def test_post_res_mix_and_its_gradient_op():
    x, y, _, post, res = streams_and_maps(np.random.default_rng(1))
    ins = {"X": x, "Y": y, "Res": res, "Post": post}
    outs, grads, cot = run_op("hc_post_res_mix", ins, {"Out": "float32"}, {},
                              tuple(ins))

    def plain(x, y, res, post):
        return jnp.einsum("btij,btjc->btic", res, x) \
            + post[..., None] * y[:, :, None, :]

    close(outs["Out"], plain(x, y, res, post), tol=1e-6)
    want = jax.grad(lambda *a: (plain(*a) * cot).sum(),
                    argnums=(0, 1, 2, 3))(*(jnp.asarray(v)
                                            for v in ins.values()))
    for slot, g in zip(ins, want):
        close(grads[slot], g, tol=1e-5)


def test_a_mix_that_wants_one_cotangent_writes_one():
    """The gradient ops write the cotangents asked for and no other."""
    x, y, _, post, res = streams_and_maps(np.random.default_rng(2))
    _, grads, _ = run_op("hc_post_res_mix",
                         {"X": x, "Y": y, "Res": res, "Post": post},
                         {"Out": "float32"}, {}, ("Y",))
    assert set(grads) == {"Y"} and np.abs(grads["Y"]).max() > 0


def test_the_maps_against_the_equations():
    """hyper_connection_maps: the norm over n C without a weight, the
    projection, the two sigmoids and the residual logits, and its
    explicit gradient op (one map's cotangent arriving, the others
    zeros), against the equations written out."""
    rng = np.random.default_rng(3)
    b, t, n, c = 2, 6, 4, 16
    x = rng.standard_normal((b, t, n, c)).astype(np.float32)
    phi = (0.3 * rng.standard_normal((n * c, 24))).astype(np.float32)
    bias = rng.standard_normal(24).astype(np.float32)
    alpha = np.asarray([0.7, -0.4, 1.3], np.float32)
    ins = {"X": x, "Phi": phi, "Bias": bias, "Alpha": alpha}
    outs, grads, cot = run_op(
        "hyper_connection_maps", ins,
        {"Pre": "float32", "Post": "float32", "ResLogits": "float32"},
        {"epsilon": 1e-6}, tuple(ins))

    def plain(x, phi, bias, alpha):
        flat = x.reshape(b, t, n * c)
        u = flat / jnp.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
        z = u @ phi
        return (jax.nn.sigmoid(alpha[0] * z[..., :4] + bias[:4]),
                2 * jax.nn.sigmoid(alpha[1] * z[..., 4:8] + bias[4:8]),
                (alpha[2] * z[..., 8:] + bias[8:]).reshape(b, t, n, n))

    want = plain(*ins.values())
    for slot, w in zip(("Pre", "Post", "ResLogits"), want):
        close(outs[slot], w, tol=1e-5)
    want_grads = jax.grad(lambda *a: (plain(*a)[0] * cot).sum(),
                          argnums=(0, 1, 2, 3))(
        *(jnp.asarray(v) for v in ins.values()))
    for slot, g in zip(ins, want_grads):
        close(grads[slot], g, tol=2e-5)


def test_the_maps_start_where_the_configuration_says():
    """H_pre 1 / n, H_post 1 and H_res near the identity (0.948 on the
    diagonal at n = 4) at alpha 0.01 and a small phi: the layer's initial
    values."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2, 8, 4, 16], dtype="float32",
                              append_batch_size=False)
        stats = []
        maps = fluid.layers.hyper_connection_maps(x, stats=stats)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        pre, post, res, error, mass = exe.run(
            main, feed={"x": np.random.default_rng(4).standard_normal(
                (2, 8, 4, 16)).astype(np.float32)},
            fetch_list=list(maps) + list(stats[0]))
    assert np.abs(pre - 0.25).max() < 2e-3 and np.abs(post - 1).max() < 5e-3
    diagonal = np.diagonal(res, axis1=-2, axis2=-1)
    assert np.abs(diagonal - 0.948).max() < 5e-3
    assert float(mass[0]) == pytest.approx(diagonal.mean(), rel=1e-5)
    assert float(error[0]) < 1e-4
    shapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    assert shapes == [(64, 24), (24,), (3,)]


# --- 2. YaRN in the DeepSeek keys --------------------------------------------

DEEPSEEK_YARN = {"type": "yarn", "factor": 64, "beta_fast": 32,
                 "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                 "original_max_position_embeddings": 4096}


def rotary_attrs(scaling, **kwargs):
    with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                  fluid.Program()):
        x = fluid.layers.data(name="x", shape=[1, 8, 2, 64], dtype="float32",
                              append_batch_size=False)
        fluid.layers.rotary_embedding(x, scaling=scaling, **kwargs)
        op, = [o for o in fluid.default_main_program().global_block().ops
               if o.type == "rotary_embedding"]
    return dict(op.desc.attrs)


def test_rotary_embedding_reads_the_deepseek_keys():
    """`type` where `rope_type` is absent; cosines and sines times
    m(mscale) / m(mscale_all_dim): 1 as published, and the ratio where
    the two differ; the plain YaRN group keeps its 0.1 ln(factor) + 1."""
    attrs = rotary_attrs(DEEPSEEK_YARN)
    assert attrs["yarn_factor"] == 64 and attrs["attention_factor"] == 1.0
    assert attrs["yarn_original_positions"] == 4096
    uneven = rotary_attrs(dict(DEEPSEEK_YARN, mscale=0.707))
    m = lambda s: 0.1 * s * math.log(64) + 1            # noqa: E731
    assert uneven["attention_factor"] == pytest.approx(m(0.707) / m(1))
    plain = rotary_attrs({"rope_type": "yarn", "factor": 64,
                          "original_max_position_embeddings": 4096})
    assert plain["attention_factor"] == pytest.approx(m(1))
    assert "yarn_factor" not in rotary_attrs(None)
    with pytest.raises(ValueError):
        rotary_attrs({"type": "linear", "factor": 2})


def latent_ops(rope_scaling, v_head_dim):
    with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                  fluid.Program()):
        x = fluid.layers.data(name="x", shape=[1, 16, 32], dtype="float32",
                              append_batch_size=False)
        kwargs = {} if rope_scaling is None else {
            "rope_scaling": rope_scaling}
        fluid.layers.latent_attention(x, 2, 12, 8, 16, 8, v_head_dim,
                                      **kwargs)
        return list(fluid.default_main_program().global_block().ops)


@pytest.mark.parametrize("v_head_dim,lanes", [(16, 24), (24, 24)])
def test_latent_attention_folds_yarns_score_factor_into_q(v_head_dim, lanes):
    """Scores times m(mscale_all_dim)^2 = 2.0047 at factor 64, carried by
    q beside the lanes' own sqrt(lanes / 24); both rotations take the
    group; without `rope_scaling` no op carries any of it."""
    ops = latent_ops(DEEPSEEK_YARN, v_head_dim)
    scales = [op.attr("scale") for op in ops if op.type == "scale"]
    assert scales == [pytest.approx((0.1 * math.log(64) + 1) ** 2)]
    assert scales[0] == pytest.approx(2.0047, rel=1e-4)
    turns = [op for op in ops if op.type == "rotary_embedding"]
    assert len(turns) == 2 and all(op.attr("yarn_factor") == 64
                                   for op in turns)
    plain = latent_ops(None, v_head_dim)
    assert [op.type for op in plain if op.type == "scale"] == []
    assert not any("yarn_factor" in op.desc.attrs for op in plain)


# --- 3. the whole tiny model against the reference ---------------------------

VARIANTS = {"as_published": {}, "nothing_replayed": {"recompute": False}}


def stir(names, scope, rng):
    """phi, alpha and b away from their initial values, so that a wrong
    map shows: phi N(0, 0.3), alpha N(0.5, 0.3), b moved by N(0, 0.5)."""
    for name in names:
        if "hyper_connection_maps" not in name:
            continue
        value = np.asarray(scope.find_var(name))
        if value.shape == (3,):
            value = rng.normal(0.5, 0.3, 3)
        elif value.ndim == 1:
            value = value + rng.normal(0, 0.5, value.shape)
        else:
            value = rng.normal(0, 0.3, value.shape)
        scope.set_var(name, jnp.asarray(value.astype(np.float32)))


@functools.lru_cache(maxsize=None)
def loss_and_gradients(variant):
    """(names, the program's loss and gradients, the reference's, the
    two gauges) of the tiny model under VARIANTS[variant]."""
    config, family = tiny(**VARIANTS[variant])
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 4242)
        exe.run(startup)
        names = tuple(p.name for p in main.global_block().all_parameters()
                      if p.trainable)
        stir(names, scope, np.random.default_rng(5))
        params = [jnp.asarray(scope.find_var(n)) for n in names]
        got, *grads = exe.run(
            main, feed=feed,
            fetch_list=[loss] + [grad_var_name(n) for n in names])
        exe.close()     # the step's side-fetches
    gauges = {name: telemetry.read_series(name)[
        "program=" + telemetry.program_label(main)] for name in (
            "hc_res_sum_error", "hc_res_diagonal_mass")}
    want = jax.value_and_grad(
        lambda p: family.reference_loss(config, p, feed))(params)
    return names, (float(np.ravel(got)[0]), grads), want, gauges


@pytest.mark.parametrize("variant", VARIANTS)
def test_tiny_model_against_the_reference_in_float32(variant):
    """Loss to 1e-6 and EVERY parameter's gradient to 2e-5 of its own
    largest entry, phi's, alpha's and b's among them, the program's
    fetched gradients against jax.grad of the reference on the same
    weights and batch, over [dense, expert, expert] at T = 32, four
    streams, keys 24 beside values 16 under YaRN."""
    names, (got, grads), (want, want_grads), _ = loss_and_gradients(variant)
    # embedding; a block: (phi, b, alpha, norm) + 7 and (phi, b, alpha,
    # norm) + 3 (dense) or 7 (experts); the final norm and the head
    assert len(names) == 1 + (11 + 7) + 2 * (11 + 11) + 2
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=2e-5)
    total = sum(float(np.sum((np.asarray(g) - np.asarray(r)) ** 2))
                for g, r in zip(grads, want_grads))
    norm = sum(float(np.sum(np.asarray(r) ** 2)) for r in want_grads)
    assert (total / norm) ** 0.5 <= 5e-6


def test_a_replayed_block_changes_no_value():
    (_, with_, _, _), (_, without, _, _) = (
        loss_and_gradients(v) for v in VARIANTS)
    assert abs(with_[0] - without[0]) <= 2e-6 * abs(without[0])
    for a, b in zip(with_[1], without[1]):
        close(a, b, tol=1e-5)


def test_the_gauges_say_what_the_maps_are():
    """hc_res_sum_error and hc_res_diagonal_mass reach telemetry as one
    float32 scalar a step: with the maps stirred the columns have not
    converged (an error well above rounding) and the diagonal holds less
    than the 0.948 it starts from."""
    _, _, _, gauges = loss_and_gradients("as_published")
    assert 1e-6 < gauges["hc_res_sum_error"] < 0.5
    assert 0.25 < gauges["hc_res_diagonal_mass"] < 0.948
    for name in gauges:
        assert telemetry.METRIC_CATALOG[name]["kind"] == "gauge"


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU at the initial maps (read: loss
    6e-6, gradient 0.004, tail 0.003, update 1e-5)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["update_rel_err"] <= 1e-3


# --- 4. the shares -----------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer_behind_a_hyper_connection():
    """The guide's section 4: eight shares of 8 of 64 experts, top 4
    under a sigmoid router renormalised and scaled by 2. A share's expert
    layer gives its held experts' part; the parts of all eight, with the
    shared expert AND the residual path (H_res X, and H_post times the
    shared expert's output) counted once, add up to what the uncut
    reference gives for the whole sublayer X' = H_res X + H_post (x) F(x_in),
    because the write-back is linear in F's output."""
    rng = np.random.default_rng(11)
    n_tok, d, f, k, streams = 40, 16, 24, 4, 4
    x, _, pre, post, res = streams_and_maps(rng, 1, n_tok, streams, d)
    res = np.asarray(plain_sinkhorn(jnp.asarray(4 * res)))
    x_in = np.asarray(hc_ops.hc_pre_mix_reference(x, pre))[0]
    w_r = rng.standard_normal((d, 64)).astype(np.float32)
    routed, _, _ = run_op(
        "moe_router", {"X": x_in, "W": w_r, "Bias": np.zeros(64, np.float32)},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"top_k": k, "scaling": 2.0, "norm_topk_prob": True}, ())
    gate, up = (rng.standard_normal((64, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((64, f, d)).astype(np.float32) * 0.3
    s_g, s_u = (rng.standard_normal((d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    s_d = rng.standard_normal((f, d)).astype(np.float32) * 0.3

    def gated(x, g, u, dn):
        return (jax.nn.silu(x @ g) * (x @ u)) @ dn

    def share(offset):
        cut = slice(offset, offset + 8)
        return run_op(
            "moe_experts",
            {"X": x_in, "TopkIdx": routed["TopkIdx"],
             "TopkWeight": routed["TopkWeight"], "WGate": gate[cut],
             "W1": up[cut], "W2": down[cut]},
            dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                           "LoadMaxOverMean", "RowsHandled"), "float32"),
            {"num_experts": 64, "experts_held": 8, "expert_offset": offset,
             "top_k": k}, ())[0]

    parts = [share(offset) for offset in range(0, 64, 8)]
    assert sum(p["RowsRouted"][0] for p in parts) == n_tok * k
    shared = gated(x_in, s_g, s_u, s_d)

    def written_back(y, with_residual):
        """H_post (x) y, and H_res X beside it where asked."""
        full = hc_ops.hc_post_res_mix_reference(x, y[None], res, post)
        if with_residual:
            return full
        return full - hc_ops.hc_post_res_mix_reference(
            x, jnp.zeros_like(y)[None], res, post)

    got = written_back(shared, True) + sum(
        written_back(jnp.asarray(p["Out"]), False) for p in parts)
    s = jax.nn.sigmoid(jnp.asarray(x_in) @ jnp.asarray(w_r))
    top, idx = jax.lax.top_k(s, k)
    weight = 2.0 * top / top.sum(-1, keepdims=True)
    whole = shared + sum(
        (weight * (idx == e)).sum(-1, keepdims=True)
        * gated(x_in, gate[e], up[e], down[e]) for e in range(64))
    want = jnp.einsum("btij,btjc->btic", res, x) \
        + post[..., None] * whole[None, :, None, :]
    close(got, want, tol=2e-5)


# --- 5. what asks for none of it, and the count ------------------------------

def test_hc_mult_none_leaves_the_program_it_was():
    """With the new arguments at their defaults mla_moe_lm's program and
    startup program are the GLM cell's, by the hashes
    tests/test_kda_moe.py pins; and the tiny GLM model's text is the
    same with every new argument spelt at its default."""
    from test_kda_moe import PARENT_PROGRAMS
    config = run.load_json("configs", "glm-4.7-flash")
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS["glm-4.7-flash"]
    types = {op.type for op in main.global_block().ops}
    assert not types & {"hyper_connection_maps", "sinkhorn_knopp",
                        "hc_pre_mix", "hc_post_res_mix", "reduce_sum"}

    def text(**kwargs):
        from paddle_tpu import models
        main = fluid.Program()
        with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
            tok, lab, lab2 = (fluid.layers.data(
                name=n, shape=[-1, 16], dtype="int64",
                append_batch_size=False) for n in ("tok", "lab", "lab2"))
            models.mla_moe_lm(
                tok, lab, lab2, vocab_size=32, hidden_size=32,
                num_hidden_layers=2, first_k_dense_replace=1,
                num_attention_heads=2, q_lora_rank=12, kv_lora_rank=8,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
                intermediate_size=48, n_routed_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=16, **kwargs)
        return main.to_json()

    assert text() == text(hc_mult=None, hc_sinkhorn_iters=20, hc_eps=1e-6,
                          hc_res_clamp=(-30.0, 30.0), rope_scaling=None,
                          recompute=False)


def test_the_cells_parameter_count_is_the_files():
    """The program built from the cell's configuration holds
    `parameters_here` trainable parameters (759,346,190: ISSUE 68's
    table), the family's arithmetic says the same, the routers' biases
    are buffers, and no prediction module is built."""
    config = run.load_json("configs", CELL_CONFIG)
    family = run.load_module("families", config["family"])
    main, _, _ = family.build(config)
    params = main.global_block().all_parameters()
    count = sum(int(np.prod(p.shape)) for p in params if p.trainable)
    assert count == config["parameters_here"] == 759_346_190
    assert count == family.parameters_here(config)
    assert [tuple(p.shape) for p in params if not p.trainable] == [(64,)] * 4
    hyper = [p for p in params if "hyper_connection_maps" in p.name]
    assert sum(int(np.prod(p.shape)) for p in hyper) == 5 * 688_182
    types = [op.type for op in main.global_block().ops]
    # ten, and eight replayed: the last block lies behind the last
    # checkpoint, where nothing is replayed
    assert types.count("sinkhorn_knopp") == 18
    assert "mtp_block" not in main.to_json()


def test_hyper_connections_take_no_prediction_module():
    config, family = tiny(num_nextn_predict_layers=1)
    with pytest.raises(AssertionError):
        family.build(config)


# --- 6. scopes, counters, tables ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_program():
    config, family = tiny()
    return family.build(config)


def test_the_layers_build_every_op_under_their_name(tiny_program):
    """hyper_connection_maps (the sweeps nested in it), hc_pre_mix and
    hc_post_res_mix are name scopes, forward, replayed and gradient
    alike, so a trace books their time to the residual path."""
    main, _, _ = tiny_program
    scopes = {}
    for op in main.global_block().ops:
        scope = op.desc.attrs.get("op_namescope", "")
        scopes.setdefault(op.type, set()).add(scope.strip("/"))
    for op_type in ("hyper_connection_maps", "hyper_connection_maps_grad"):
        assert scopes[op_type] == {"hyper_connection_maps"}
    for op_type in ("sinkhorn_knopp", "sinkhorn_knopp_grad"):
        assert scopes[op_type] == {"hyper_connection_maps/sinkhorn_knopp"}
    for op_type in ("hc_pre_mix", "hc_pre_mix_grad", "hc_post_res_mix",
                    "hc_post_res_mix_grad"):
        assert scopes[op_type] == {op_type.replace("_grad", "")}
    replayed = [op for op in main.global_block().ops
                if op.type == "sinkhorn_knopp"
                and "recompute_segment" in op.desc.attrs]
    assert len(replayed) == 4       # the last block's two are not


def test_the_trace_counts_sublayers_and_replays():
    """hyper_connection_sublayers_total books a first lowering,
    hyper_connection_replays_total a replayed one (the CPU reports no
    memory limit, so every segment is replayed; the last block lies
    behind the last checkpoint and is no segment): six and four."""
    before = {name: sum(telemetry.read_series(name).values())
              for name in ("hyper_connection_sublayers_total",
                           "hyper_connection_replays_total")}
    config, family = tiny()
    main, startup, loss = family.build(config)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        exe.run(main, feed=family.make_batch(
            config, 1, np.random.default_rng(0)), fetch_list=[loss])
    after = {name: sum(telemetry.read_series(name).values())
             for name in before}
    first, again = (after[n] - before[n] for n in before)
    assert first > 0 and first % 6 == 0 and again == first * 4 // 6
    for name in before:
        assert telemetry.METRIC_CATALOG[name]["kind"] == "counter"


def test_every_table_knows_the_new_ops(tiny_program):
    """The roofline's cost table prices the maps' projection and both
    mixes from their shapes; the registry holds the four explicit
    gradient ops with no gradient of their own."""
    from paddle_tpu import roofline
    from paddle_tpu.ops import registry
    for op_type in ("hyper_connection_maps_grad", "sinkhorn_knopp_grad",
                    "hc_pre_mix_grad", "hc_post_res_mix_grad"):
        assert registry.get(op_type).grad is registry.NO_GRAD
    x = jax.ShapeDtypeStruct((1, 32, 4, 64), jnp.float32)
    phi = jax.ShapeDtypeStruct((256, 24), jnp.float32)
    flops, _ = roofline.op_cost("hyper_connection_maps",
                                {"X": [x], "Phi": [phi]}, {}, {})
    assert flops == 2.0 * 32 * 256 * 25
    small = jax.ShapeDtypeStruct((1, 32, 4), jnp.float32)
    flops, _ = roofline.op_cost("hc_pre_mix", {"X": [x], "Pre": [small]},
                                {}, {})
    assert flops == 2.0 * 32 * 4 * 64
    flops, _ = roofline.op_cost("hc_post_res_mix",
                                {"X": [x], "Post": [small]}, {}, {})
    assert flops == 2.0 * 32 * 4 * 64 * 5


def test_family_arithmetic_at_the_published_sizes():
    """ISSUE 68's table, part by part, and the bytes floor: about 100 KB
    a token and sublayer a pass in bf16."""
    config = run.load_json("configs", CELL_CONFIG)
    family = run.load_module("families", config["family"])
    assert family.hyper_connection_sublayers(config) == 10
    a_pass = (3 * 4 + 2) * 3584 * 2
    assert a_pass == 100_352
    with_replay = family.hyper_connection_cost(config)
    assert with_replay == family.hyper_connection_cost(
        config, replayed_share=0.8)
    without = family.hyper_connection_cost(config, replayed_share=0.0)
    assert with_replay - without == pytest.approx(
        8 * (4096 * (a_pass + 2 * 24 * 4) + 14336 * 24 * 4))
    assert 12.5e9 < with_replay < 13.5e9
    assert family.hyper_connection_cost(config, tokens=2048) \
        < 0.51 * with_replay
    flops, bytes_ = family.attention_kernel_cost(config)
    assert flops == 2.0 * (4096 * 4097 / 2) * 32 * 3 * (192 + 128)
    assert bytes_ == 2.0 * 4096 * 32 * (4 * 192 + 5 * 128)
    assert family.expert_layers(config) == 4
    assert family.attention_ops_per_step(config) == 5
    per = family.part_flops_per_item(config)
    assert per["hyper"] == 2 * 14336 * 24 + 2 * 3584 * 24
    assert family.required_flops_per_item(config) == 3.0 * (
        5 * (per["mla"] + 2 * per["hyper"]) + per["dense"]
        + 4 * per["experts"] + per["head"])
    inv = family.yarn_frequencies(64, 10000, config["rope_scaling"])
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], plain[:11])
    np.testing.assert_allclose(inv[23:], plain[23:] / 64)
    assert (inv[11:23] < plain[11:23]).all() \
        and (inv[11:23] > plain[11:23] / 64).all()
    assert family.yarn_magnitude(config["rope_scaling"],
                                 "mscale_all_dim") ** 2 \
        == pytest.approx(2.0047, rel=1e-4)
