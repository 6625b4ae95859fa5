"""Block-diffusion training's layers (layers.block_diffusion_attention,
the softmax router, models.block_diffusion_moe_lm), at tiny sizes on the
CPU: the whole tiny model against
benchmarks/families/sdar_moe.py::reference_loss; the mask against a dense
masked softmax; the flash kernels under the block mask, interpreted, at
shapes that tile, against the einsum path, their walk ranges over several
tiles and major tiles, and what may leak where; the shares of the expert
layer under the softmax router; the router's two scorings; and the
tables that must know the new op. (The sixth test of ISSUE 42, the cell's
step compiled for a described v5e, is in tests/test_tpu_compile.py: the
one file that describes a chip.)"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.ops import kernel_choice, nn_ops, pallas_attention

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-sdar-moe"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the whole tiny model against the reference ---------------------------

def test_tiny_model_against_the_reference_in_float32():
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry, the program's fetched gradients against jax.grad of
    the reference on the same weights and batch (measured: loss 0, the
    gradient over all parameters 2.6e-7)."""
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
    # embedding; 12 a block; the final norm and the head: no router bias
    assert len(names) == 1 + 12 * config["num_hidden_layers"] + 2
    assert abs(float(np.ravel(got)[0]) - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_tiny_model_against_the_reference_under_amp():
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.03
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.01
    assert found["update_rel_err"] <= 1e-3


def test_batches_carry_their_noise():
    """tok from the slice less its last row; noisy = tok with the masked
    ids replaced by the mask token; weight = m / p of the block, so zero
    exactly where the id was kept and at least 1 where it was not; about
    half the positions masked under U(0, 1) rates."""
    config, family = tiny(sequence_length=4096, vocab_size=1000)
    feed = family.make_batch(config, 3, np.random.default_rng(2 ** 31 + 5))
    tok, noisy, weight = feed["tok"], feed["noisy"], feed["weight"]
    assert tok.shape == noisy.shape == weight.shape == (3, 4096)
    assert tok.dtype == noisy.dtype == np.int32 and weight.dtype == np.float32
    assert tok.max() == 998 and tok.min() == 0
    masked = noisy != tok
    assert set(np.unique(noisy[masked])) == {999}
    np.testing.assert_array_equal(weight > 0, masked)
    assert weight[masked].min() >= 1.0
    assert weight.max() <= 1.0 / config["mask_epsilon"]
    assert 0.45 < masked.mean() < 0.55
    # one rate a block: the weights of a block's masked positions agree
    by_block = weight.reshape(3, -1, config["block_length"])
    top = by_block.max(-1, keepdims=True)
    assert np.all((by_block == 0) | (by_block == top))
    assert family.items_per_batch(feed) == 3 * 4096


def test_the_loss_and_the_masked_share_reach_telemetry():
    from paddle_tpu import telemetry
    from paddle_tpu.models import block_diffusion_moe as model
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
    assert telemetry.read_gauge(model.LOSS_METRIC, program=label) == \
        pytest.approx(float(np.ravel(out)[0]), rel=1e-6)
    assert telemetry.read_gauge(model.MASKED_METRIC, program=label) == \
        pytest.approx((feed["noisy"] != feed["tok"]).mean(), rel=1e-6)
    for layer in ("0", "1"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        assert rows["count"] >= 1 and rows["sum"] > 0


# --- 2. the mask -------------------------------------------------------------

def dense_block_diffusion(q, k, v, block):
    """softmax over an explicit [2L, 2L] mask, one sequence at a time:
    rows and columns [noisy ; clean], K/V heads repeated to Q's."""
    half, length, heads = q.shape[0] // 2, q.shape[1], q.shape[2]
    groups = heads // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    bid = np.arange(length) // block
    keep = np.zeros((2 * length, 2 * length), bool)
    keep[:length, :length] = bid[:, None] == bid[None, :]    # noisy, own
    keep[:length, length:] = bid[:, None] > bid[None, :]     # noisy, clean
    keep[length:, length:] = bid[:, None] >= bid[None, :]    # clean, clean
    both = lambda x: jnp.concatenate([x[:half], x[half:]], axis=1)  # noqa
    scores = jnp.einsum("bqhd,bkhd->bhqk", both(q), both(k)) \
        / np.sqrt(q.shape[-1])
    prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob, both(v))
    return jnp.concatenate([out[:, :length], out[:, length:]], axis=0)


def attention_op(q, k, v, block, use_flash="auto", wrt=("Q", "K", "V")):
    return run_op("block_diffusion_attention", {"Q": q, "K": k, "V": v},
                  {"Out": "float32", "LSE": "float32"},
                  {"block_length": block, "use_flash": use_flash}, wrt)


def operands(seed, shape, kv_heads=None):
    rng = np.random.default_rng(seed)
    kv = shape[:2] + (kv_heads or shape[2],) + shape[3:]
    return [rng.standard_normal(s).astype(np.float32)
            for s in (shape, kv, kv)]


def against_dense(outs, grads, cot, q, k, v, block, tol):
    want, vjp = jax.vjp(lambda *a: dense_block_diffusion(*a, block), q, k, v)
    close(outs["Out"], want, tol=tol)
    for slot, g in zip(("Q", "K", "V"), vjp(jnp.asarray(cot))):
        close(grads[slot], g, tol=tol)


@pytest.mark.parametrize("block", [1, 4, 16])
def test_attention_against_a_dense_masked_softmax(block):
    """The einsum path (L = 32 tiles no kernel) with grouped-query heads,
    forward and the three gradients."""
    q, k, v = operands(block, (4, 32, 4, 16), kv_heads=2)
    outs, grads, cot = attention_op(q, k, v, block)
    against_dense(outs, grads, cot, q, k, v, block, tol=1e-5)
    assert outs["LSE"].shape == (4, 4, 32)


def test_block_length_one_is_causal_attention_on_the_clean_stream():
    q, k, v = operands(7, (2, 32, 4, 16), kv_heads=2)
    outs, _, _ = attention_op(q, k, v, 1, wrt=())
    causal, _, _ = run_op(
        "scaled_dot_product_attention",
        {"Q": q[1:], "K": k[1:], "V": v[1:]},
        {"Out": "float32", "LSE": "float32"},
        {"causal": True, "use_flash": False}, ())
    close(outs["Out"][1:], causal["Out"], tol=1e-6)
    close(outs["LSE"][1:], causal["LSE"], tol=1e-6)


# --- 3. the kernels, interpreted ---------------------------------------------

@pytest.mark.parametrize("block", [4, 32])
def test_kernels_against_the_einsum_path_and_the_dense_mask(block):
    """L 256, D 128: the op on the flash kernels (booked as a hit under
    its own label) against the einsum path and against the dense mask,
    forward, LSE and the three gradients."""
    from paddle_tpu import telemetry
    q, k, v = operands(block, (2, 256, 2, 128), kv_heads=1)
    hits = dict(telemetry.read_series("pallas_kernel_total"))
    backward = dict(telemetry.read_series("flash_backward_total"))
    key = "op=block_diffusion_attention"
    outs, grads, cot = attention_op(q, k, v, block, use_flash=True)
    assert dict(telemetry.read_series("pallas_kernel_total"))[key] == \
        hits.get(key, 0) + 2   # run_op lowers the forward twice (alone for
    # its shape, then with the backward); the grad op books nothing there,
    # and each of its two kernel parts one fused backward (PR 43)
    booked = dict(telemetry.read_series("flash_backward_total"))
    assert booked["form=fused,reason="] == \
        backward.get("form=fused,reason=", 0) + 2
    assert {k: n for k, n in booked.items() if "split" in k} == \
        {k: n for k, n in backward.items() if "split" in k}
    plain, plain_grads, _ = attention_op(q, k, v, block, use_flash=False)
    close(outs["Out"], plain["Out"], tol=1e-5)
    close(outs["LSE"], plain["LSE"], tol=1e-5)
    for slot in ("Q", "K", "V"):
        close(grads[slot], plain_grads[slot], tol=2e-5)
    against_dense(outs, grads, cot, q, k, v, block, tol=2e-5)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("q_off", [0, -8], ids=["upto", "earlier"])
def test_walk_ranges_over_several_tiles_and_major_tiles(q_off, fused):
    """The block-causal geometry over 4 x 4 tiles of 128 rows in two
    major tiles of 256, at block 8, with the query's position moved back
    by one block (strictly earlier blocks) and not: the three kernels
    against a dense mask on positions, the backward as one call (dQ
    accumulated over the four K tiles beside dK and dV) and as two. Rows
    that see no key (the first block under q_off = -block) are left out
    of the forward's comparison and carry a zero cotangent."""
    block, t, tiles = 8, 512, dict(tile=(128, 128), major=256)
    rng = np.random.default_rng(5)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, t, 1, 128)),
                               jnp.float32) for _ in range(4))
    scale = 1.0 / np.sqrt(128)
    pos = np.arange(t)
    keep = (pos[:, None] + q_off) // block >= pos[None, :] // block
    seen = keep.any(-1)
    do = do * seen[None, :, None, None]

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(keep, s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    want, vjp = jax.vjp(dense, q, k, v)
    out, (lse,) = pallas_attention._fwd_call(
        q, k, v, q_off, 0, scale, True, normalize=True, block=block, **tiles)
    close(out[:, seen], want[:, seen], tol=1e-5)
    delta = jnp.sum(do * out, axis=-1).transpose(0, 2, 1)
    grads = pallas_attention._bwd_call(
        q, k, v, do, lse, delta, q_off, 0, scale, True, dq_tile=tiles["tile"],
        dkv_tile=tiles["tile"], major=tiles["major"], block=block,
        fused=fused)
    for g, g_ref in zip(grads, vjp(do)):
        close(g, g_ref, tol=2e-5)


def test_what_may_leak_where():
    """On the kernels, exactly (a masked score's weight is exp(-1e30) =
    0): perturbing a noisy position changes no clean output and no noisy
    output of another block; perturbing a clean position of block b
    changes no output of a block before b in either stream and no noisy
    output of block b."""
    block, length = 32, 256
    q, k, v = (jnp.asarray(x) for x in operands(11, (2, length, 1, 128)))
    base, _ = nn_ops._bd_flash(q, k, v, block)
    at = 3 * block + 5                                   # in block 3
    blocks = np.arange(length) // block

    def perturbed(stream):
        bump = lambda x: x.at[stream, at].add(1.0)       # noqa: E731
        out, _ = nn_ops._bd_flash(bump(q), bump(k), bump(v), block)
        return np.asarray(out != base).any((-1, -2))     # [2, L] rows moved

    moved = perturbed(0)                                 # a noisy position
    assert not moved[1].any()
    assert moved[0][blocks == 3].all() and not moved[0][blocks != 3].any()
    moved = perturbed(1)                                 # a clean position
    assert not moved[0][blocks <= 3].any() and moved[0][blocks > 3].all()
    assert not moved[1][blocks < 3].any() and moved[1][blocks >= 3].all()


@pytest.mark.parametrize("shape,block,reason", [
    ((1, 512, 2, 64), 4, None), ((1, 512, 2, 64), 128, None),
    ((1, 512, 2, 64), 3, "block"), ((1, 512, 2, 64), 256, "block"),
    ((1, 64, 2, 64), 48, "block"), ((1, 520, 2, 64), 4, "seq")])
def test_the_gate_knows_the_block_length(shape, block, reason):
    q = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert pallas_attention.ineligible(q, q, q, block=block) == reason
    assert reason is None or \
        reason in kernel_choice.REASONS["block_diffusion_attention"]


def test_a_declined_shape_is_booked_with_its_reason():
    """use_flash=True at L = 48 in blocks of 12: the einsum path, and
    pallas_fallback_total{op="block_diffusion_attention", reason="block"}
    once a lowering."""
    from paddle_tpu import telemetry
    key = "op=block_diffusion_attention,reason=block"
    before = dict(telemetry.read_series("pallas_fallback_total")).get(key, 0)
    q, k, v = operands(2, (2, 48, 2, 16))
    outs, grads, cot = attention_op(q, k, v, 12, use_flash=True)
    against_dense(outs, grads, cot, q, k, v, 12, tol=1e-5)
    assert dict(telemetry.read_series("pallas_fallback_total"))[key] == \
        before + 2             # run_op's two lowerings of the forward


# --- 4. the shares under the softmax router ----------------------------------

def router(x, w, scoring=None, top_k=2, **attrs):
    if scoring is not None:
        attrs["scoring"] = scoring
    outs, _, _ = run_op(
        "moe_router", {"X": x, "W": w, "Bias": np.zeros(w.shape[1], "f4")},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        dict(attrs, top_k=top_k), ())
    return outs["TopkIdx"], outs["TopkWeight"]


def test_shares_add_up_under_the_softmax_router_and_an_unheld_token_gets_zero():
    """8 gated experts top-2 in four shares of 2, no shared expert: what
    the shares give adds up to the uncut layer, and in each share a token
    none of whose choices is held there gets exactly zero."""
    rng = np.random.default_rng(9)
    n, d, f = 48, 16, 24
    x = rng.standard_normal((n, d)).astype(np.float32)
    idx, weight = router(x, rng.standard_normal((d, 8)).astype(np.float32),
                         "softmax")
    gate, up = (rng.standard_normal((8, d, f)).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.standard_normal((8, f, d)).astype(np.float32) * 0.3

    def share(offset, held):
        outs, _, _ = run_op(
            "moe_experts",
            {"X": x, "TopkIdx": idx, "TopkWeight": weight,
             "WGate": gate[offset:offset + held],
             "W1": up[offset:offset + held],
             "W2": down[offset:offset + held]},
            dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                           "LoadMaxOverMean", "RowsHandled"), "float32"),
            {"num_experts": 8, "experts_held": held,
             "expert_offset": offset, "top_k": 2}, ())
        return outs

    whole = share(0, 8)
    parts = [share(offset, 2) for offset in (0, 2, 4, 6)]
    close(sum(p["Out"] for p in parts), whole["Out"], tol=1e-5)
    assert sum(p["RowsRouted"][0] for p in parts) == n * 2
    for offset, part in zip((0, 2, 4, 6), parts):
        unheld = ~((idx >= offset) & (idx < offset + 2)).any(-1)
        assert unheld.any() and (~unheld).any()
        assert np.all(part["Out"][unheld] == 0.0)
        assert np.abs(part["Out"][~unheld]).min(-1).max() > 0


# --- 5. the router's two scorings --------------------------------------------

def test_softmax_scoring_against_the_formula():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want_idx = np.argsort(-prob, -1)[:, :3]
    top = np.take_along_axis(prob, want_idx, -1)
    idx, weight = router(x, w, "softmax", top_k=3)
    np.testing.assert_array_equal(idx, want_idx)
    close(weight, top / top.sum(-1, keepdims=True), tol=1e-6)
    close(weight.sum(-1), np.ones(40), tol=1e-6)
    _, raw = router(x, w, "softmax", top_k=3, norm_topk_prob=False,
                    scaling=2.0)
    close(raw, 2.0 * top, tol=1e-6)


def test_sigmoid_scoring_is_the_default_and_what_it_was():
    """No `scoring` attribute (a program built before it existed) and
    "sigmoid" give the same bits; both the formula's choices."""
    rng = np.random.default_rng(6)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    old = router(x, w, None, top_k=3, scaling=1.8)
    new = router(x, w, "sigmoid", top_k=3, scaling=1.8)
    np.testing.assert_array_equal(old[0], new[0])
    np.testing.assert_array_equal(old[1], new[1])
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w.astype(np.float64))))
    want_idx = np.argsort(-s, -1)[:, :3]
    top = np.take_along_axis(s, want_idx, -1)
    np.testing.assert_array_equal(old[0], want_idx)
    close(old[1], 1.8 * top / top.sum(-1, keepdims=True), tol=1e-6)
    with pytest.raises(Exception, match="scoring|tanh"):
        router(x, w, "tanh")


def biased(x, w, bias, scoring, top_k=3):
    outs, _, _ = run_op(
        "moe_router", {"X": x, "W": w, "Bias": bias},
        {"TopkIdx": "int32", "TopkWeight": "float32"},
        {"scoring": scoring, "top_k": top_k}, ())
    return outs["TopkIdx"], outs["TopkWeight"]


def test_the_selection_bias_moves_a_softmax_router_s_choice_by_the_logit():
    """Under the softmax the bias scales the probability by exp(b): the
    choice is the top of logit + b, whatever the logits' scale; the
    weights are the unbiased probabilities of the chosen; a zero bias is
    the unbiased router bit for bit. The sigmoid router adds it to the
    score, as it did."""
    rng = np.random.default_rng(14)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    w = (rng.standard_normal((16, 8)) * 3.0).astype(np.float32)
    bias = rng.standard_normal(8).astype(np.float32)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    idx, weight = biased(x, w, bias, "softmax")
    want_idx = np.argsort(-(logits + bias), -1)[:, :3]
    np.testing.assert_array_equal(idx, want_idx)
    assert (want_idx != np.argsort(-logits, -1)[:, :3]).any()
    top = np.take_along_axis(prob, want_idx, -1)
    close(weight, top / top.sum(-1, keepdims=True), tol=1e-6)
    zero = biased(x, w, np.zeros(8, "f4"), "softmax")
    plain = router(x, w, "softmax", top_k=3)
    np.testing.assert_array_equal(zero[0], plain[0])
    np.testing.assert_array_equal(zero[1], plain[1])
    s = 1.0 / (1.0 + np.exp(-logits))
    np.testing.assert_array_equal(biased(x, w, bias, "sigmoid")[0],
                                  np.argsort(-(s + bias), -1)[:, :3])


def balance(idx, bias, rate):
    outs, _, _ = run_op("moe_balance_bias", {"TopkIdx": idx, "Bias": bias},
                        {"BiasOut": "float32"}, {"rate": rate}, ())
    return outs["BiasOut"]


def test_balance_bias_against_the_rule():
    """b_e += rate * clip(1 - count_e / mean, -1, 1) over ALL experts: an
    expert nobody chose rises by `rate`, one chosen twice its share or
    more falls by `rate`, one at its share stays."""
    idx = np.array([[0, 1], [0, 1], [0, 2], [0, 3], [0, 4], [0, 5],
                    [1, 2], [1, 3]], np.int32)      # 16 pairs, 8 experts
    bias = np.linspace(-1, 1, 8).astype(np.float32)
    count = np.bincount(idx.ravel(), minlength=8)   # 6 4 2 2 1 1 0 0
    want = bias + 0.25 * np.clip(1.0 - count / 2.0, -1, 1)
    got = balance(idx, bias, 0.25)
    close(got, want, tol=1e-7)
    assert got[0] == bias[0] - 0.25 and got[1] == bias[1] - 0.25
    assert got[2] == bias[2] and got[7] == bias[7] + 0.25
    np.testing.assert_array_equal(balance(idx, bias, 0.0), bias)


def test_the_rule_levels_a_router_that_prefers_a_few_experts():
    """Router and rule in a closed loop on fixed logits whose offsets by
    expert (spread 1.5) exceed their spread by token (0.5): the busiest
    expert falls from over four times its share to under 1.3 times in 40
    steps at rate 0.2, and every expert is chosen."""
    rng = np.random.default_rng(21)
    n, experts, top_k = 2048, 32, 4
    x = np.concatenate([np.ones((n, 1)), rng.standard_normal((n, 15)) * 0.5
                        / np.sqrt(15)], 1).astype(np.float32)
    w = np.concatenate([rng.standard_normal((1, experts)) * 1.5,
                        rng.standard_normal((15, experts)) * np.sqrt(15)
                        ]).astype(np.float32)
    bias = np.zeros(experts, np.float32)
    mean = n * top_k / experts
    load = []
    for _ in range(40):
        idx, _ = biased(x, w, bias, "softmax", top_k)
        count = np.bincount(idx.ravel(), minlength=experts)
        load.append(count.max() / mean)
        bias = balance(idx, bias, 0.2)
    assert load[0] > 4.0 and max(load[-5:]) < 1.3, (load[0], load[-5:])
    assert count.min() > 0.5 * mean


def test_the_model_s_rule_moves_the_buffers_and_not_the_first_step():
    """With `router_balance_rate` the family appends one moe_balance_bias
    a router BEHIND the optimizer's ops: every block's bias buffer is
    what the rule makes of the step's choices, no optimizer touches it,
    and the first step (zero bias) gives the loss AND every gradient of
    the program without the rule, bit for bit (placed right after its
    router the rule changed the bias the router's gradient op reads:
    the chip's first gradient moved with the rate, PR 42, call E)."""
    rate = 0.125
    first = []
    for config, family in (tiny(router_balance_rate=0.0),
                           tiny(router_balance_rate=rate)):
        main, startup, loss = family.build(config)
        fluid.amp.disable(main)
        ops = main.global_block().ops
        routers = [op for op in ops if op.type == "moe_router"]
        rules = [op for op in ops if op.type == "moe_balance_bias"]
        assert len(rules) == (config["num_hidden_layers"]
                              if config["router_balance_rate"] else 0)
        assert ops[-len(rules):] == rules or not rules
        assert all(op.attr("op_role") == "optimize" for op in rules)
        params = [p.name for p in main.global_block().all_parameters()
                  if p.trainable]
        feed = family.make_batch(config, 2, np.random.default_rng(3))
        exe = fluid.Executor(fluid.CPUPlace())
        scope = executor_mod.Scope()
        with executor_mod.scope_guard(scope):
            scope.set_var("__rng_counter__", 4242)
            exe.run(startup)
            got = exe.run(
                main, feed=feed,
                fetch_list=[loss] + [op.output("TopkIdx")[0]
                                     for op in routers]
                + [p + "@GRAD" for p in params])
            after = [np.asarray(scope.find_var(op.input("Bias")[0]))
                     for op in routers]
        idx = got[1:1 + len(routers)]
        first.append([np.asarray(got[0])]
                     + [np.asarray(g) for g in got[1 + len(routers):]])
        experts = config["num_experts_published"]
        for rule, router_op, chosen, b in zip(rules, routers, idx, after):
            assert rule.input("Bias") == rule.output("BiasOut") \
                == router_op.input("Bias")
            count = np.bincount(np.asarray(chosen).ravel(),
                                minlength=experts)
            mean = np.asarray(chosen).size / experts
            close(b, rate * np.clip(1.0 - count / mean, -1, 1), tol=1e-7)
            assert np.abs(b).max() > 0
        if not rules:
            assert all(np.all(b == 0) for b in after)
        held = {n for op in ops if op.type == "adam"
                for n in op.input("Param")}
        assert not held & {op.input("Bias")[0] for op in routers}
    assert len(first[0]) == len(first[1]) > 10
    for without, with_rule in zip(*first):
        np.testing.assert_array_equal(without, with_rule)


def test_moe_block_builds_the_router_it_is_asked_for():
    from paddle_tpu.framework import unique_name
    for scoring, shared in (("softmax", 0), ("sigmoid", 24)):
        with unique_name.guard(), fluid.program_guard(fluid.Program(),
                                                      fluid.Program()):
            x = fluid.layers.data(name="x", shape=[2, 8, 16],
                                  dtype="float32", append_batch_size=False)
            fluid.layers.moe_block(x, 8, 2, 12, shared_width=shared,
                                   experts_held=2, gated=True,
                                   scoring=scoring)
            ops = fluid.default_main_program().global_block().ops
        router_op, = [op for op in ops if op.type == "moe_router"]
        # a sigmoid router's op carries no attribute: the program it was
        assert router_op.desc.attrs.get("scoring", "sigmoid") == scoring
        assert ("scoring" in router_op.desc.attrs) == (scoring == "softmax")
        # no shared expert: no product but the experts' own
        assert [op.type for op in ops].count("mul") == (3 if shared else 0)
