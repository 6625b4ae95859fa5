"""models.granite_hybrid_lm and what it brought, at tiny sizes on the CPU:
the program against benchmarks/families/granite_hybrid.py::reference_loss
on the loss and every gradient; each of the four multipliers and the tied
head's two gradients shown to matter; recomputation by segments
(backward.append_backward(checkpoints=)) against the same program without
it, to the last bit, and what it leaves in the IR; every model's program
without checkpoints against the hash the parent commit gave; and the scan
kernels at one group of 64 heads under the interpreter."""

import hashlib
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward, executor as executor_mod, models
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name

from benchmarks import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
CONFIG = run.load_json("configs", "tiny-granite-hybrid", DATA)
FAMILY = run.load_module("families", CONFIG["family"])
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


class Step(NamedTuple):
    main: object
    names: list          # the trainable parameters, in creation order
    params: list
    feed: dict
    loss: float
    grads: dict          # {parameter: gradient}
    extra: list          # the `extra` variables asked for
    account: list        # exe.step_account(main)


def first_step(config, extra=()):
    """The float32 program of `config` run once on seeded weights and one
    seeded batch."""
    main, startup, loss = FAMILY.build(config)
    fluid.amp.disable(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 7)
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()
                 if p.trainable]
        params = [np.asarray(scope.find_var(n)) for n in names]
        feed = FAMILY.make_batch(config, 2, np.random.default_rng(0))
        grads = dict(main._grad_param_pairs)
        out = exe.run(main, feed=feed, fetch_list=[loss] + [
            grads[n] for n in names] + list(extra))
        account = exe.step_account(main)
    return Step(main, names, params, feed, float(out[0][0]),
                dict(zip(names, out[1:1 + len(names)])),
                out[1 + len(names):], account)


@pytest.fixture(scope="module")
def step():
    return first_step(CONFIG)


def rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def reference(config, params, feed):
    value, grads = jax.value_and_grad(
        lambda p: FAMILY.reference_loss(config, p, feed))(
            [jnp.asarray(p) for p in params])
    return float(value), [np.asarray(g) for g in grads]


def test_program_matches_the_reference_on_loss_and_every_gradient(step):
    ref_loss, ref_grads = reference(CONFIG, step.params, step.feed)
    assert step.loss == pytest.approx(ref_loss, rel=2e-6)
    assert len(step.names) == 1 + 2 * (2 + 8 + 3) + (2 + 4 + 3) + 1
    for name, want in zip(step.names, ref_grads):
        assert rel(step.grads[name], want) < 2e-5, name


@pytest.mark.parametrize("key", MULTIPLIERS)
def test_no_multiplier_is_decorative(step, key):
    """The same comparison with one multiplier set to 1 in the reference
    alone: it fails, on the loss or on some tensor's gradient."""
    ref_loss, ref_grads = reference(dict(CONFIG, **{key: 1}), step.params,
                                    step.feed)
    worst = max(rel(step.grads[n], want)
                for n, want in zip(step.names, ref_grads))
    assert abs(step.loss - ref_loss) / ref_loss > 1e-3 or worst > 0.05, key


def test_the_tied_gradient_is_the_lookups_and_the_heads_summed():
    """One parameter read by lookup_table and, transposed, by the head's
    product: append_backward's fan-in rule sums the two gradients, each
    against jax.grad of the reference with the head untied."""
    table = models.granite_hybrid.EMBEDDING
    share = grad_var_name(table) + "@RENAME@1"
    step = first_step(CONFIG, extra=(share,))
    (lookups,), grads, params, feed = (step.extra, step.grads, step.params,
                                       step.feed)
    ops = step.main.global_block().ops
    writers = {n: op.type for op in ops for n in op.output_arg_names
               if n.startswith(grad_var_name(table)) and op.type != "sum"}
    assert writers == {grad_var_name(table): "matmul_grad",
                       share: "lookup_table_grad"}
    assert any(op.type == "sum" and set(op.input("X")) == set(writers)
               for op in ops)
    assert step.names[0] == table
    rest = [jnp.asarray(p) for p in params[1:]]
    g_lookup, g_head = jax.grad(
        lambda e, h: FAMILY.reference_loss(CONFIG, [e] + rest, feed, head=h),
        argnums=(0, 1))(jnp.asarray(params[0]), jnp.asarray(params[0]))
    assert rel(lookups, np.asarray(g_lookup)) < 2e-5
    assert rel(grads[table] - lookups, np.asarray(g_head)) < 2e-5
    assert rel(grads[table], np.asarray(g_lookup + g_head)) < 2e-5
    # neither share is small beside the other
    assert 0.05 < np.linalg.norm(g_lookup) / np.linalg.norm(g_head) < 20


def test_recomputation_changes_no_bit_and_is_in_the_ir(step):
    """With checkpoints the loss and every gradient are the ones without,
    to the last bit (CPU, float32); the replayed forward ops stand in the
    IR in the backward's role under their segment, behind one barrier a
    segment; no grad op of a replayed segment reads a forward activation
    that is not a checkpoint; and the compiled step's instructions carry
    the segment (xplane.Instr.recompute)."""
    main, account = step.main, step.account
    plain = first_step(dict(CONFIG, recompute=False))
    assert not backward.replayed_ops(plain.main)
    assert step.loss == plain.loss
    for name in step.names:
        assert np.array_equal(step.grads[name], plain.grads[name]), name

    block = main.global_block()
    replayed = backward.replayed_ops(main)
    layers = CONFIG["num_hidden_layers"]
    # the last layer and the head follow the last checkpoint: not replayed
    assert sorted(replayed) == list(range(layers))
    assert replayed[1].count("ssd_scan") == 1    # the first mixer
    # the checkpoints: a segment's barrier waits for its end's cotangent
    kept = {n[:-len(grad_var_name(""))] for op in block.ops
            if op.type == "recompute_barrier" for n in op.input("Dep")}
    assert len(kept) == layers
    first_forward = {n for op in block.ops if not op.attr("op_role")
                     for n in op.output_arg_names}
    last_cut = max(i for i, op in enumerate(block.ops)
                   if kept & set(op.output_arg_names))
    tail = {n for op in block.ops[last_cut + 1:] if not op.attr("op_role")
            for n in op.output_arg_names}
    barriers = 0
    for op in block.ops:
        segment = op.attr(backward.RECOMPUTE_ATTR)
        if segment is not None:
            assert op.attr("op_role") == "backward"
            barriers += op.type == "recompute_barrier"
        elif op.attr("op_role") == "backward":
            read = {n for n in op.input_arg_names if n in first_forward
                    and not block.var(n).persistable}
            assert read <= kept | tail, (op.type, read - kept - tail)
    assert barriers == len(replayed)
    # the program the inference slicer leaves holds none of it
    sliced = fluid.io.get_inference_program([block.var(main._loss_names[0])])
    assert not backward.replayed_ops(sliced)

    under = [i for i in account if i.recompute is not None]
    assert under and all(i.role == "backward" for i in under)
    assert {i.recompute for i in under} <= set(replayed)
    assert any(i.scope == "mamba2_mixer" for i in under)


def test_a_segment_that_would_differ_is_refused_by_name():
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=8)
        mid = fluid.layers.fc(input=fluid.layers.dropout(h, 0.5), size=8)
        loss = fluid.layers.mean(fluid.layers.fc(input=mid, size=1))
        with pytest.raises(ValueError, match="'dropout'.*random"):
            fluid.optimizer.SGD(0.1).minimize(loss, checkpoints=[mid])


def test_counters_of_a_compile_with_checkpoints(step):
    """recompute_ops_total counts what runs again: replayed_ops less the
    attention op, which is handed the outputs its first run kept and is
    counted under recompute_kept_total."""
    import collections
    from paddle_tpu import telemetry
    replayed = backward.replayed_ops(step.main)
    label = f"program={telemetry.program_label(step.main)}"
    # the CPU reports no limit: every segment is replayed
    assert telemetry.read_series("recompute_segments_total")[
        f"{label},decision=replayed,reason=no_limit"] == len(replayed)
    ran_again = collections.Counter(
        t for types in backward.replayed_ops(
            step.main, handed_on=False).values() for t in types)
    handed = collections.Counter(
        t for types in backward.replayed_ops(
            step.main, handed_on=True).values() for t in types)
    assert handed == {"scaled_dot_product_attention": 1}
    assert ran_again + handed == collections.Counter(
        t for types in replayed.values() for t in types)
    ops = {k.split("type=")[1]: v for k, v in
           telemetry.read_series("recompute_ops_total").items()
           if k.startswith(label + ",")}
    assert ops == dict(ran_again) and ops["ssd_scan"] >= 1
    assert telemetry.read_series("recompute_kept_total")[
        f"{label},type=scaled_dot_product_attention"] == 1
    assert telemetry.read_series("recompute_kept_bytes")[label] > 0


# sha256 of main.to_json() + startup.to_json() of every model of
# paddle_tpu/models/ built WITHOUT checkpoints, as the parent commit
# (c26b2bf) built them: append_backward emits what it emitted, op for op
# and name for name, so the accepted cells' compile cache keys hold
# (the four presets with expert layers: as PR 58 builds them, whose
# moe_experts ops write Up / GateUp for an explicit gradient op; the
# hybrid preset as PR 60 builds it: causal_conv1d's explicit gradient op)
PARENT_PROGRAMS = {
    "alexnet":
        "749347dae9d46259e5085d6cd6b8129e79f7488ac336064944ae999749fb20e9",
    "googlenet":
        "4a57081cff7016ec7b64367b7fd176f6abcbeff303f66fe07b229e36ce387775",
    "vgg16":
        "b0d8d937c288eddd179b4dbf3f7201a214c62010a1790c8562c825fea10ad928",
    "vgg19":
        "67cb1267cc766d372dc6304669cb5b1d3dd5fad0d75cd52442e31213dace8a26",
    "resnet50":
        "5752a45151f16a3545ecf56a96462c5d428178c301f27bd5da31c1340b685d06",
    "resnet_cifar10":
        "d51f2d782165e4aa9ae26d39670ab2702fc7214dffd86cb6d835d29a0ca2d917",
    "mnist_mlp":
        "6772ef3160fd1842864828d781261ecf943eb3d5e9974b65226483c4ca17cbba",
    "mnist_conv":
        "68819fc1599bfdb80dc1c0c53f2e04cd53cac0d71300d947b15a7db5a16136ec",
    "smallnet_mnist_cifar":
        "30d601b63ade10fd3ed6161ca4c1062207ec1ba00db0563bb7f01521cc39184d",
    "tiny-gpt2":
        "5fd176abaa4479ded067ac1cee7922fd9247f2a913e92ecba88ca58391eefb66",
    "tiny-nemotron-h":
        "530a4521428c6e73de3e9e41db9f7264798457c1ec1a37061bf05ed5c44fe001",
    "tiny-glm-moe-lite":
        "8c60a3136a68e77e3579afe2192aada0bc17dfa22f2825ad6204b6c2cd2ec0e2",
    "tiny-sdar-moe":
        "10a5b2fcf70aad8e569071f915639f68e238e123478e26afc40d604ac6ab28c9",
    "tiny-smallthinker":
        "fad93b11da3ca07191cb617ee7839fea7dbb336cac4cadffbd69b1cc7ce188ce",
    "tiny-resnet18":
        "6d2521accb00190531752814da7ba439e2f19c65f2ef784bf04996699d6c98a0",
}

IMAGE_MODELS = {
    "alexnet": (models.alexnet, [3, 224, 224], 1000),
    "googlenet": (models.googlenet, [3, 224, 224], 1000),
    "vgg16": (models.vgg16, [3, 224, 224], 1000),
    "vgg19": (models.vgg19, [3, 224, 224], 1000),
    "resnet50": (models.resnet50, [3, 224, 224], 1000),
    "resnet_cifar10": (models.resnet_cifar10, [3, 32, 32], 10),
    "mnist_mlp": (models.mnist_mlp, [1, 28, 28], 10),
    "mnist_conv": (models.mnist_conv, [1, 28, 28], 10),
    "smallnet_mnist_cifar": (models.smallnet_mnist_cifar, [3, 32, 32], 10),
}
# transformer_lm, nemotron_h_lm, mla_moe_lm, block_diffusion_moe_lm,
# window_moe_lm and resnet_imagenet through their families' tiny presets
FAMILY_PRESETS = ("tiny-gpt2", "tiny-nemotron-h", "tiny-glm-moe-lite",
                  "tiny-sdar-moe", "tiny-smallthinker", "tiny-resnet18")


def build_model(name):
    if name in FAMILY_PRESETS:
        config = run.load_json("configs", name, DATA)
        return run.load_module("families", config["family"]).build(config)[:2]
    fn, shape, classes = IMAGE_MODELS[name]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=shape, dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
        cost, _, _ = models.build_image_classifier(fn, img, lab,
                                                   class_dim=classes)
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
            cost, startup_program=startup)
    return main, startup


@pytest.mark.parametrize("name", list(IMAGE_MODELS) + list(FAMILY_PRESETS))
def test_without_checkpoints_every_model_serializes_as_before(name):
    main, startup = build_model(name)
    assert not backward.replayed_ops(main)
    assert hashlib.sha256((main.to_json() + startup.to_json()).encode()) \
        .hexdigest() == PARENT_PROGRAMS[name]


@pytest.mark.parametrize("chunk,heads_a_step", [(256, 8), (128, 16)])
def test_scan_kernels_at_one_group_of_64_heads(chunk, heads_a_step):
    """The cell's scan shape but for its length: ONE group of 64 heads of
    64, state 128, at the published chunk of 256 and at the 128 the cell
    is lowered with, which the kernels take in float32 as 8 head blocks of
    8 or 4 of 16 (under AMP 4 of 16 or 2 of 32) that read the same B and
    C and whose shares of dB and dC are summed behind the gradient's
    kernel. The op's lowering, interpreted, against ssd_scan_chunked: Out
    and the gradients to all seven inputs."""
    import types
    from paddle_tpu.ops import hybrid_ops, pallas_scan

    heads, p, n, t = 64, 64, 128, 512
    assert hybrid_ops.ssd_scan_ineligible(chunk, heads, p, n) is None
    assert pallas_scan.heads_a_step(heads, chunk, 4) == heads_a_step
    assert pallas_scan.heads_a_step(heads, chunk, 2) == 2 * heads_a_step
    assert pallas_scan.heads_a_step(8, chunk) == 8      # the hybrid cell's
    rng = np.random.default_rng(64)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    order = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")
    args = [f(1, t, heads, p), f(1, t, heads), f(heads) * 0.5 - 3, f(heads),
            f(1, t, 1, n) * 0.3, f(1, t, 1, n) * 0.3, f(heads)]
    cot = f(1, t, heads, p)

    class Attrs:
        def attr(self, name, default=None):
            return {"chunk_size": chunk}.get(name, default)

    def kernels(*arrays):
        return hybrid_ops._ssd_scan(
            types.SimpleNamespace(amp_dtype=None), Attrs(),
            {s: [a] for s, a in zip(order, arrays)})["Out"][0]

    def chunked(x, dt_raw, dt_bias, a_log, b, c, skip):
        y = hybrid_ops.ssd_scan_chunked(
            x, jax.nn.softplus(dt_raw + dt_bias), -jnp.exp(a_log), b, c,
            chunk)
        return y + skip[:, None] * x

    def value_and_grads(fn):
        return fn(*args), jax.grad(lambda *a: (fn(*a) * cot).sum(),
                                   argnums=range(7))(*args)

    got, got_grads = value_and_grads(kernels)
    want, want_grads = value_and_grads(chunked)
    for slot, g, w in zip(("Out",) + order, (got,) + got_grads,
                          (want,) + want_grads):
        assert g.shape == w.shape, slot
        assert float(jnp.max(jnp.abs(g - w))) \
            <= 5e-4 * float(jnp.max(jnp.abs(w))), slot
