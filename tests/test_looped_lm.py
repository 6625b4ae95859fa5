"""A looped decoder (models.looped_lm: Ouro's block) at a tiny size on the
CPU: 2 layers of 4 heads of 16 at d 64 run 3 times over one set of
weights, an exit a pass. The whole model, loss and every gradient,
against benchmarks/families/ouro.py::reference_loss with and without
checkpoints; one set of parameters whatever the number of passes; a
shared weight's gradient against the sum over an untied twin's copies;
one pass as the plain cross-entropy; the exit masses and the entropy
term; what each segment replays; what stays float32 under bf16
activations; and that the decoders the benchmark already had are built as
they were."""

import functools
import hashlib
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward
from paddle_tpu import executor as executor_mod
from paddle_tpu import layer_helper
from paddle_tpu.framework.framework import NAME_SCOPE_ATTR, grad_var_name

from benchmarks import run
from test_nemotron_h import close, first_step

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-ouro"
# the module: `models.looped_lm` is the builder of the same name
looped = importlib.import_module("paddle_tpu.models.looped_lm")
LAYERS, PASSES = 2, 3


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


def trainable(main):
    return tuple(p.name for p in main.global_block().all_parameters()
                 if p.trainable)


def run_once(main, startup, fetch, feed, weights=None):
    """One run of `main` on the weights the startup program makes from a
    fixed counter (or on `weights`, {name: array}, laid over them) ->
    ({name: the weights it ran on}, the fetched arrays)."""
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 4242)
        exe.run(startup)
        for name, value in (weights or {}).items():
            scope.set_var(name, jnp.asarray(value))
        held = {n: np.asarray(scope.find_var(n)) for n in trainable(main)}
        return held, exe.run(main, feed=feed, fetch_list=fetch)


def random_gate(weights):
    """The weights with a gate that takes sides (its bias starts at 0 and
    its map small: every exit mass would start near its uniform share)."""
    rng = np.random.default_rng(11)
    return dict(weights, **{
        "looped_lm.gate.w": rng.standard_normal(
            weights["looped_lm.gate.w"].shape).astype(np.float32),
        "looped_lm.gate.b": np.array([0.7], np.float32)})


@functools.lru_cache(maxsize=None)
def loss_and_gradients(**over):
    """(names, the weights, the program's loss and gradients) of the tiny
    model in float32 on a gate that takes sides, run once a variant."""
    config, family = tiny(**over)
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    names = trainable(main)
    first, _ = run_once(main, startup, [loss], feed)
    weights, (got, *grads) = run_once(
        main, startup, [loss] + [grad_var_name(n) for n in names], feed,
        random_gate(first))
    return names, weights, float(np.ravel(got)[0]), grads, feed


@functools.lru_cache(maxsize=None)
def reference(**over):
    config, family = tiny(**over)
    names, weights, _, _, feed = loss_and_gradients(**over)
    return jax.value_and_grad(
        lambda p: family.reference_loss(config, p, feed))(
            [jnp.asarray(weights[n]) for n in names])


# --- 1. the whole tiny model against the reference ---------------------------

@pytest.mark.parametrize("recompute", [True, False])
def test_tiny_model_against_the_reference_in_float32(recompute):
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry: the program's fetched gradients (each shared weight's
    summed over its three readers by append_backward's `sum` ops) against
    jax.grad of the reference (two Python loops over one dict)."""
    names, _, got, grads, _ = loss_and_gradients(recompute=recompute)
    want, want_grads = reference(recompute=recompute)
    # the embedding, the gate's two, eleven a layer, the final norm, the
    # head
    assert len(names) == 3 + 11 * LAYERS + 2
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_a_replayed_application_changes_no_value():
    names, weights, with_, grads, _ = loss_and_gradients(recompute=True)
    _, same, without, plain, _ = loss_and_gradients(recompute=False)
    for n in names:
        np.testing.assert_array_equal(weights[n], same[n])
    assert abs(with_ - without) <= 2e-6 * abs(without)
    for a, b in zip(grads, plain):
        close(a, b, tol=1e-5)


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU (read: loss 1.1e-5, gradient
    0.011, its norm 1.4e-3, tail 0.0077, update 1.2e-5)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 2e-4
    assert found["grad_rel_err"] <= 0.04
    assert found["grad_tail_rel_err"] <= 0.03
    assert found["grad_norm_rel_diff"] <= 0.005
    assert found["update_rel_err"] <= 1e-3


# --- 2. one set of weights ---------------------------------------------------

def test_the_program_holds_one_set_of_weights_whatever_the_passes():
    """The same parameters, by name and shape, for 1, 2 and 4 passes: a
    layer is built once as parameters and `total_ut_steps` times as ops;
    the gate's two are the model's where no exit reads them (one pass)."""
    found = {}
    for passes in (1, 2, 4):
        config, family = tiny(total_ut_steps=passes)
        main, startup, _ = family.build(config)
        found[passes] = [(p.name, tuple(p.shape))
                         for p in main.global_block().all_parameters()]
        assert [(p.name, tuple(p.shape)) for p in
                startup.global_block().all_parameters()] == found[passes]
        products = [op for op in main.global_block().ops
                    if op.type == "scaled_dot_product_attention"
                    and backward.RECOMPUTE_ATTR not in op.desc.attrs]
        assert len(products) == passes * LAYERS
    assert found[1] == found[2] == found[4]
    count = sum(int(np.prod(shape)) for _, shape in found[4])
    layer = 4 * 64 * 64 + 3 * 64 * 96 + 4 * 64
    assert count == LAYERS * layer + 2 * 128 * 64 + 64 + 65
    # a weight's readers: every pass's op and, behind a checkpoint, its
    # replay
    config, family = tiny(total_ut_steps=4)
    main, _, _ = family.build(config)
    readers = [op for op in main.global_block().ops if op.type == "mul"
               and "looped_lm.layer_0.q" in op.input("Y")]
    assert len(readers) == 4 + 4
    assert sum(backward.RECOMPUTE_ATTR in op.desc.attrs
               for op in readers) == 4


def untied_twin(config, family):
    """The tiny model built with every parameter that a second op reads
    through LayerHelper.create_parameter under a name of that reader's
    own, `<name>#<reader>`: the same ops over passes x layers separately
    named copies (and a final norm and a head an exit). The gate's two
    are made once and read as variables, so they stay shared."""
    made = {}
    create = layer_helper.LayerHelper.create_parameter

    def create_a_copy(self, attr, *args, **kwargs):
        name = getattr(attr, "name", None)
        if name and name.startswith(looped.PREFIX + ".") \
                and ".gate." not in name and "embedding" not in name:
            made[name] = made.get(name, 0) + 1
            attr.name = f"{name}#{made[name] - 1}"
        return create(self, attr, *args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(layer_helper.LayerHelper, "create_parameter",
                      create_a_copy)
        main, startup, loss = family.build(config)
    return main, startup, loss, made


@pytest.mark.parametrize("recompute", [True, False])
def test_a_shared_weights_gradient_is_the_sum_over_its_readers(recompute):
    """The tied program's gradient of every layer tensor, the final norm
    and the head equals the sum of the gradients of an untied twin's
    copies initialised alike, under `checkpoints=` too (where each reader
    is also replayed)."""
    names, weights, loss_tied, grads, feed = loss_and_gradients(
        recompute=recompute)
    config, family = tiny(recompute=recompute)
    main, startup, loss, made = untied_twin(config, family)
    fluid.amp.disable(main)
    assert set(made.values()) == {PASSES} and len(made) == 11 * LAYERS + 2
    copies = trainable(main)
    assert len(copies) == 3 + PASSES * (11 * LAYERS + 2)
    alike = {c: weights[c.split("#")[0]] for c in copies}
    _, (got, *twin) = run_once(
        main, startup, [loss] + [grad_var_name(c) for c in copies], feed,
        alike)
    assert abs(float(np.ravel(got)[0]) - loss_tied) <= 1e-6 * loss_tied
    summed = {}
    for c, g in zip(copies, twin):
        base = c.split("#")[0]
        summed[base] = summed.get(base, 0.0) + np.asarray(g, np.float64)
    for name, g in zip(names, grads):
        close(g, summed[name], tol=1e-5)
    # and no copy's share is nothing: every reader counts
    for c, g in zip(copies, twin):
        assert np.abs(np.asarray(g)).max() > 0, c


# --- 3. the exits and their mix ----------------------------------------------

def exit_readings(config, family, weights=None):
    """(loss, each exit's mean cross-entropy, each exit's mean mass, the
    weights) of one float32 run, the exits' from the side-fetch marks."""
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    marks = main._telemetry_fetch_extra
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    fetch = [loss] + [marks[m] for m in (looped.EXIT_LOSS_METRIC,
                                         looped.EXIT_MASS_METRIC)
                      if m in marks]
    held, out = run_once(main, startup, fetch, feed, weights)
    return [np.asarray(o, np.float64).ravel() for o in out], held


def test_one_pass_is_the_plain_cross_entropy():
    """p_1 = 1 and H = 0: a plain sandwich-norm decoder, no gate op, no
    exit series; the reference with one pass agrees."""
    config, family = tiny(total_ut_steps=1)
    main, startup, loss = family.build(config)
    fluid.amp.disable(main)
    types = [op.type for op in main.global_block().ops]
    assert "softplus" not in types and "exp" not in types
    assert set(main._telemetry_fetch_extra) == {looped.LOSS_METRIC}
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    weights, (got,) = run_once(main, startup, [loss], feed)
    names = trainable(main)
    want = family.reference_loss(
        config, [jnp.asarray(weights[n]) for n in names], feed)
    assert abs(float(np.ravel(got)[0]) - float(want)) <= 1e-6 * float(want)
    # and it is a cross-entropy over 128 ids at small weights
    assert abs(float(want) - np.log(128)) < 0.2
    # the gate's two are held and no op reads them: no gradient, no Adam
    grads = {op.input("Param")[0] for op in main.global_block().ops
             if op.type == "adam"}
    assert set(names) - grads == {"looped_lm.gate.w", "looped_lm.gate.b"}


def test_the_masses_sum_to_one_and_beta_moves_the_loss_by_the_entropy():
    """sum_t p_t = 1 a token, so the means sum to 1; at beta 0 the loss
    is sum_t p_t CE^(t); beta moves it by -beta H(p) and nothing else."""
    first, held = exit_readings(*tiny())
    weights = random_gate(held)
    by_beta = {}
    for beta in (0.0, 0.1, 0.3):
        (loss, ce, mass), _ = exit_readings(
            *tiny(exit_entropy_weight=beta), weights=weights)
        assert ce.shape == mass.shape == (PASSES,)
        assert abs(mass.sum() - 1.0) <= 1e-6
        assert mass.min() > 0.02 and mass.max() - mass.min() > 0.05
        by_beta[beta] = (float(loss[0]), ce, mass)
    for beta in (0.1, 0.3):
        np.testing.assert_allclose(by_beta[beta][1], by_beta[0.0][1],
                                   rtol=1e-6)
        np.testing.assert_allclose(by_beta[beta][2], by_beta[0.0][2],
                                   rtol=1e-6)
    entropy = (by_beta[0.0][0] - by_beta[0.1][0]) / 0.1
    # the mean over tokens of H(p) lies under log 3, and under the
    # entropy of the mean masses (H is concave)
    mass = by_beta[0.0][2]
    assert 0 < entropy <= -(mass * np.log(mass)).sum() + 1e-6 <= np.log(3)
    assert abs(by_beta[0.0][0] - by_beta[0.3][0] - 0.3 * entropy) \
        <= 1e-5 * by_beta[0.0][0]


def test_the_exits_reach_telemetry_by_exit():
    """A sample a step and exit of the exit's mean cross-entropy and of
    the mean mass the gate gives it, without a fetch by the user, and one
    `side_fetch` event a step of each, a vector over the exits."""
    from paddle_tpu import telemetry
    config, family = tiny()
    main, startup, loss = family.build(config)
    feed = family.make_batch(config, 2, np.random.default_rng(0))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()     # a side-fetch still in flight is published here
    label = telemetry.program_label(main)
    mass, ce = ([telemetry.read_histogram(metric, program=label,
                                          exit=str(t)) for t in range(PASSES)]
                for metric in (looped.EXIT_MASS_METRIC,
                               looped.EXIT_LOSS_METRIC))
    assert [h["count"] for h in mass + ce] == [3] * (2 * PASSES)
    assert abs(sum(h["sum"] for h in mass) - 3.0) <= 1e-5
    # a small gate at the start: about 1/2, 1/4 and the 1/4 that is left
    for h, share in zip(mass, (0.5, 0.25, 0.25)):
        assert abs(h["sum"] / 3 - share) < 0.05
    assert all(abs(h["sum"] / 3 - np.log(128)) < 0.3 for h in ce)
    events = [e for e in telemetry.recent_events(kind="side_fetch")
              if e.get("program") == label
              and e.get("metric") == looped.EXIT_MASS_METRIC]
    assert len(events) == 3
    assert all(len(e["values"]) == PASSES
               and abs(sum(e["values"]) - 1.0) <= 1e-5 for e in events)


# --- 4. what the backward replays, and where the exits fall ------------------

def test_each_segment_replays_one_application_and_an_exit_falls_in_the_next():
    """3 x 2 applications, a checkpoint at each one's input: segment 0 is
    the embedding's lookup (nothing to replay), segments 1 to 5 an
    application each with one attention op (handed on) and one
    feed-forward; exits 1 and 2 are emitted where their pass ends, behind
    the final norm that writes the next checkpoint, so each is replayed
    with the next pass's first application; the third exit and the last
    application follow the last checkpoint and are not replayed."""
    config, family = tiny()
    main, _, _ = family.build(config)
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4, 5]
    for segment, types in replayed.items():
        assert types.count("scaled_dot_product_attention") == 1
        assert types.count("silu") == 1 and types.count("rotary_embedding") \
            == 2
        with_exit = segment in (3, 5)
        assert types.count("softmax_with_cross_entropy") == with_exit
        assert types.count("softplus") == 2 * with_exit
        # q, k, v, o, gate, up, down, and the head's product with an exit
        assert types.count("mul") == 7 + with_exit
    handed = backward.replayed_ops(main, handed_on=True)
    assert all(types == ["scaled_dot_product_attention"]
               for types in handed.values())
    block = main.global_block()
    losses = [i for i, op in enumerate(block.ops)
              if op.type == "softmax_with_cross_entropy"]
    # three forward, two replayed
    assert len(losses) == PASSES + (PASSES - 1)
    assert [backward.RECOMPUTE_ATTR in block.ops[i].desc.attrs
            for i in losses] == [False] * 3 + [True] * 2
    # no [N, V] array of an exit is a checkpoint's: the checkpoints are
    # the residual stream
    _, kept = looped_checkpoints(config)
    assert len(kept) == PASSES * LAYERS
    assert all(tuple(v.shape)[1:] == (64, 64) for v in kept)


def looped_checkpoints(config):
    from paddle_tpu.framework import unique_name

    main = fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, fluid.Program()):
        tok, lab = (fluid.layers.data(name=n, shape=[-1, 64], dtype="int64",
                                      append_batch_size=False)
                    for n in ("tok", "lab"))
        return looped.looped_lm(
            tok, lab, vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            intermediate_size=96, total_ut_steps=config["total_ut_steps"],
            recompute=True)


def test_the_scopes_hold_the_attention_and_the_exits():
    config, family = tiny()
    main, _, _ = family.build(config)
    scopes = {}
    for op in main.global_block().ops:
        scopes.setdefault(op.type, set()).add(
            op.desc.attrs.get(NAME_SCOPE_ATTR))
    attention, exits = (f"/{s}/" for s in (looped.ATTENTION_SCOPE,
                                           looped.EXIT_SCOPE))
    for kind in ("scaled_dot_product_attention",
                 "scaled_dot_product_attention_grad"):
        assert scopes[kind] == {attention}, kind
    # layers.rotary_embedding builds under its own name, nested
    assert scopes["rotary_embedding"] == {
        f"/{looped.ATTENTION_SCOPE}/rotary_embedding/"}
    for kind in ("softmax_with_cross_entropy", "softplus", "exp",
                 "reduce_sum", "mean", "softmax_with_cross_entropy_grad",
                 "softplus_grad", "exp_grad", "reduce_sum_grad"):
        assert scopes[kind] == {exits}, kind
    # the maps are the model's: q, k, v, o under no scope, the
    # feed-forward's under its own, the head's under the exit's
    assert scopes["mul"] == {None, "/gated_mlp/", exits}


# --- 5. what stays float32 ---------------------------------------------------

def test_the_gate_and_the_mix_stay_float32_under_bf16_activations():
    """Under AMP O2 the maps' outputs, the attention's and the head's
    logits are bf16 (the residual stream and the norms of it are float32,
    as in every decoder here: the embedding's rows are the master
    table's); every other value an exit computes (the gate's logit from
    its float32 multiply and sum, both softplus terms, the log-survival
    sums, log p, p, the cross-entropy, the terms, their sum and the loss)
    is float32."""
    config, family = tiny()
    main, startup, loss = family.build(config)
    assert (main._amp_dtype, main._amp_level) == ("bfloat16", "O2")
    block = main.global_block()
    forward = [op for op in block.ops
               if op.desc.attrs.get("op_role") != "backward"]
    exits = [op for op in forward if op.desc.attrs.get(NAME_SCOPE_ATTR)
             == f"/{looped.EXIT_SCOPE}/"]
    attended = [op.output("Out")[0] for op in forward
                if op.type == "scaled_dot_product_attention"]
    logits = {n for op in exits if op.type == "mul"
              for n in op.output("Out")}
    logits |= {n for op in exits if op.type == "reshape"
               and op.input("X")[0] in logits for n in op.output("Out")}
    assert len(attended) == PASSES * LAYERS and len(logits) == 2 * PASSES
    rest = [n for op in exits for n in op.output_arg_names
            if n not in logits and block.var(n).dtype == "float32"]
    kinds = {op.type for op in exits}
    assert {"cast", "reduce_sum", "softplus", "exp", "sum", "mean",
            "elementwise_mul", "elementwise_add"} <= kinds
    assert len(rest) > 12 * (PASSES - 1)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    low = attended + sorted(logits)
    _, out = run_once(main, startup, low + rest, feed)
    out = [np.asarray(o) for o in out]
    assert {str(o.dtype) for o in out[:len(low)]} == {"bfloat16"}
    assert {str(o.dtype) for o in out[len(low):]} == {"float32"}, [
        (n, o.dtype) for n, o in zip(rest, out[len(low):])
        if o.dtype != np.float32]


# --- 6. the decoders that were there ------------------------------------------

# (main, startup) of each accepted language configuration's programs as
# the parent commit (PR 58) built them, by the first 16 hex digits of
# sha256 over Program.to_json(): layers.gated_mlp's `name` names nothing
# unless asked (the three that build causal_conv1d as PR 60 builds them:
# an explicit gradient op, and `time_on_lanes` under mamba2_mixer; their
# startup programs are the parent's; Kimi-Linear's kda_scan ops as PR 65
# builds them, with Inverse and Entering beside Out)
PARENT_PROGRAMS = {
    "gpt2": ("32530ba784525f48", "6cae3670f852b823"),
    "gpt2-large": ("90b85e99fedb4110", "0abfed69b2161959"),
    "nemotron3-nano-30b-a3b": ("6ac48d33c64fc359", "2cd691daa316a1c1"),
    "glm-4.7-flash": ("c6c56c119b5b8e5c", "57465f9570324186"),
    "sdar-30b-a3b-chat": ("ae3fbd7051acd413", "d87f315e59443b2d"),
    "smallthinker-21b-a3b-instruct": ("dc6827508c9e3acb", "33caad39b72bf4c9"),
    "granite-4.0-h-micro": ("cbaa1f07490aec02", "aef2a12bd424d1b5"),
    "laguna-xs.2": ("d9e2dffcce8f0f43", "2bfd552d47366495"),
    "kimi-linear-48b-a3b-instruct": ("2c1c4f892d9bd452", "f56ab18f9f796b0c"),
}


def program_hashes(name):
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    return tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup))


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_an_accepted_decoder_is_built_as_it_was(name):
    assert program_hashes(name) == PARENT_PROGRAMS[name]
