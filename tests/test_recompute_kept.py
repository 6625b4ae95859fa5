"""An attention op's Out and LSE outlive the forward under
`append_backward(checkpoints=)` (registry.OpDef.kept_in_replay): the
replayed op stands in its segment, reads the first forward's two outputs
under KeptOut / KeptLSE, behind the segment's barrier, and runs nothing,
so a step holds as many forward kernels as attention layers. Tiny shapes
on the CPU, the kernels interpreted."""

import collections

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward, telemetry
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.ops import registry

LAYERS, T, HEADS, HEAD_DIM = 3, 128, 4, 32
SDPA, BLOCK_DIFFUSION = ("scaled_dot_product_attention",
                         "block_diffusion_attention")
KEPT_SLOTS = ["KeptLSE", "KeptOut"]

# kind: (the op's type, K/V heads, what the layer is called with, batch,
#        forward kernels an op)
KINDS = {
    "causal": (SDPA, HEADS, {"causal": True, "use_flash": True}, 1, 1),
    "window": (SDPA, HEADS,
               {"causal": True, "use_flash": True, "window": 48}, 1, 1),
    "two_kv_heads": (SDPA, 2, {"causal": True, "use_flash": True}, 1, 1),
    # the clean half's forward and the noisy half's view of the clean keys
    "block_diffusion": (BLOCK_DIFFUSION, HEADS,
                        {"block_length": 4, "use_flash": True}, 2, 2),
    # 8 sequence shards of 16 positions on the CPU mesh: the hops are a
    # loop around one kernel equation an op
    "ring": (SDPA, HEADS, {"causal": True, "sequence_parallel": True}, 1,
             1),
}


def build(kind, checkpoints=True):
    """(main, startup, loss, feed) of LAYERS residual attention layers, a
    checkpoint at each layer's input: the last layer follows the last
    checkpoint and is not replayed, the others are."""
    op_type, kv_heads, how, batch, _ = KINDS[kind]
    layer = fluid.layers.fused_attention if op_type == SDPA \
        else fluid.layers.block_diffusion_attention
    width = HEADS * HEAD_DIM
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[batch, T, width],
                              dtype="float32", append_batch_size=False)
        kept = []
        for _ in range(LAYERS):
            kept.append(x)
            q, k, v = (fluid.layers.reshape(
                fluid.layers.fc(x, n * HEAD_DIM, num_flatten_dims=2,
                                bias_attr=False), [batch, T, n, HEAD_DIM])
                for n in (HEADS, kv_heads, kv_heads))
            a = fluid.layers.reshape(layer(q, k, v, **how),
                                     [batch, T, width])
            x = fluid.layers.elementwise_add(x, fluid.layers.fc(
                a, width, num_flatten_dims=2, bias_attr=False))
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(x, x))
        fluid.optimizer.SGD(0.1).minimize(
            loss, startup_program=startup,
            checkpoints=kept if checkpoints else None)
    if kind == "ring":
        from paddle_tpu.parallel import mesh as mesh_mod
        main._mesh = mesh_mod.make_mesh((8,), ("sp",))
    feed = {"x": np.random.default_rng(7).standard_normal(
        (batch, T, width)).astype(np.float32)}
    return main, startup, loss, feed


def run(main, startup, feed, fetch):
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        return [np.asarray(v) for v in
                exe.run(main, feed=feed, fetch_list=fetch)]


def loss_and_grads(main, startup, loss, feed):
    names = [grad_var_name(p.name)
             for p in main.global_block().all_parameters()]
    out = run(main, startup, feed, [loss.name] + names)
    return out[0], dict(zip(names, out[1:]))


def without_the_declaration(monkeypatch):
    """The parent's replay: no op type declares kept outputs."""
    for op_type in (SDPA, BLOCK_DIFFUSION):
        monkeypatch.setattr(registry.get(op_type), "kept_in_replay", ())


def attention_ops(main, op_type):
    first, again = [], []
    for op in main.global_block().ops:
        if op.type == op_type:
            (again if backward.RECOMPUTE_ATTR in op.desc.attrs
             else first).append(op)
    return first, again


def kernels_in_the_step(main, startup, loss, feed):
    """{kernel name: pallas_call equations} of the step as the executor
    traces it, through every sub-jaxpr."""
    exe = fluid.Executor(fluid.CPUPlace())

    def step_fn(program, fetch):
        return exe._make_step_fn(program, fetch,
                                 exe._persistable_outputs(program), {})

    rng = np.uint32(0)
    state = jax.eval_shape(step_fn(startup, []), {}, {}, rng)[2]
    found = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params.get("name") or getattr(
                    eqn.params.get("name_and_src_info"), "name", None)
                found[name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(step_fn(main, [loss.name]))(feed, state, rng).jaxpr)
    return found


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_replayed_op_is_handed_the_first_forwards_outputs(kind):
    """In the IR: each replayed attention op stands in its segment, reads
    the first forward's Out and LSE under slots of their own and Q, K, V
    from the replay; the segment's readers read what it writes; on the
    device what it writes IS the first forward's array."""
    op_type = KINDS[kind][0]
    main, startup, loss, feed = build(kind)
    first, again = attention_ops(main, op_type)
    assert len(first) == LAYERS and len(again) == LAYERS - 1
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == list(range(LAYERS - 1))
    assert all(types.count(op_type) == 1 for types in replayed.values())
    assert backward.replayed_ops(main, handed_on=True) == \
        {seg: [op_type] for seg in replayed}
    block = main.global_block()
    fetch = []
    for fwd, op in zip(first[:-1], reversed(again)):
        assert sorted(s for s in op.desc.inputs if s.startswith("Kept")) \
            == KEPT_SLOTS
        # behind the segment's barrier, as the segment's inputs are
        barrier, = [b for b in block.ops if b.type == "recompute_barrier"
                    and b.attr(backward.RECOMPUTE_ATTR)
                    == op.attr(backward.RECOMPUTE_ATTR)]
        behind = dict(zip(barrier.input("X"), barrier.output("Out")))
        assert op.input("KeptOut") == [behind[fwd.output("Out")[0]]]
        assert op.input("KeptLSE") == [behind[fwd.output("LSE")[0]]]
        for slot in ("Q", "K", "V"):
            assert op.input(slot) == [
                fwd.input(slot)[0] + backward.RECOMPUTE_SUFFIX]
        for slot in ("Out", "LSE"):
            assert op.output(slot) == [
                fwd.output(slot)[0] + backward.RECOMPUTE_SUFFIX]
        grad, = [g for g in block.ops if g.type == op_type + "_grad"
                 and g.input("Out") == op.output("Out")]
        assert grad.input("LSE") == op.output("LSE")
        assert grad.input("Q") == op.input("Q")
        fetch += fwd.output("Out") + op.output("Out") \
            + fwd.output("LSE") + op.output("LSE")
    # no forward op's desc says anything of it
    for fwd in first:
        assert sorted(fwd.desc.inputs) == ["K", "Q", "V"]
        assert not any("kept" in a.lower() for a in fwd.desc.attrs)
    values = run(main, startup, feed, fetch)
    for kept, handed in zip(values[::2], values[1::2]):
        assert np.isfinite(kept).all() and np.array_equal(kept, handed)


@pytest.mark.parametrize("kind", list(KINDS))
def test_no_bit_moves_and_no_forward_kernel_runs_twice(kind, monkeypatch):
    """Loss and every gradient are those of the parent's replay (the
    declaration taken off) and of no checkpoints at all, to the last bit;
    the traced step holds a forward kernel an attention layer where the
    parent's replay holds one a layer and one a replayed layer."""
    op_type, _, _, _, kernels_an_op = KINDS[kind]
    built = build(kind)
    loss, grads = loss_and_grads(*built)
    plain = build(kind, checkpoints=False)
    assert not backward.replayed_ops(plain[0])
    with monkeypatch.context() as patch:
        without_the_declaration(patch)
        parents = build(kind)
    assert not any(backward.replayed_ops(parents[0],
                                         handed_on=True).values())
    assert backward.replayed_ops(parents[0]) == \
        backward.replayed_ops(built[0])
    for other in (parents, plain):
        other_loss, other_grads = loss_and_grads(*other)
        assert np.array_equal(loss, other_loss)
        assert sorted(grads) == sorted(other_grads) and len(grads) == \
            4 * LAYERS
        for name in grads:
            assert np.array_equal(grads[name], other_grads[name]), name

    counts = [kernels_in_the_step(*b).get("flash_fwd", 0)
              for b in (built, plain, parents)]
    assert counts == [kernels_an_op * LAYERS, kernels_an_op * LAYERS,
                      kernels_an_op * (2 * LAYERS - 1)]


@pytest.mark.parametrize("kind", ["causal", "block_diffusion"])
def test_without_checkpoints_the_program_is_the_one_it_was(kind,
                                                           monkeypatch):
    main, startup, _, _ = build(kind, checkpoints=False)
    with monkeypatch.context() as patch:
        without_the_declaration(patch)
        was_main, was_startup, _, _ = build(kind, checkpoints=False)
    assert main.to_json() == was_main.to_json()
    assert startup.to_json() == was_startup.to_json()
    assert "Kept" not in main.to_json()


def test_an_op_without_the_declaration_is_replayed_as_before(monkeypatch):
    """Every other op of a segment runs again from the replay's names and
    reads nothing of the first forward's but the segment's inputs behind
    the barrier; with the declaration off the attention op is one of
    them."""
    main, _, _, _ = build("causal")
    with monkeypatch.context() as patch:
        without_the_declaration(patch)
        was, _, _, _ = build("causal")

    def replay(program, skip=()):
        return [(op.type, dict(op.desc.inputs), dict(op.desc.outputs),
                 dict(op.desc.attrs))
                for op in program.global_block().ops
                if backward.RECOMPUTE_ATTR in op.desc.attrs
                and op.type not in skip]

    skip = (SDPA, "recompute_barrier")
    assert replay(main, skip=skip) == replay(was, skip=skip)
    assert len(replay(main)) == len(replay(was))
    # the barrier lets in what it let in, and the two kept values behind
    barriers = [[op for op in program.global_block().ops
                 if op.type == "recompute_barrier"] for program in (main, was)]
    for barrier, before in zip(*barriers):
        entering = before.input("X")
        assert barrier.input("X")[:len(entering)] == entering
        assert len(barrier.input("X")) == len(entering) + 2
        assert barrier.input("Dep") == before.input("Dep")
    ran_again = backward.replayed_ops(main, handed_on=False)
    assert all(SDPA not in types and {"mul", "reshape"} <= set(types)
               for types in ran_again.values())
    assert backward.replayed_ops(was, handed_on=False) == \
        backward.replayed_ops(was) == backward.replayed_ops(main)
    for op in was.global_block().ops:
        assert not any(s.startswith("Kept") for s in op.desc.inputs)
    assert registry.get("mul").kept_in_replay == ()
    assert [t for t in registry.registered_ops()
            if registry.get(t).kept_in_replay] == sorted(
                [BLOCK_DIFFUSION, "kda_scan", SDPA])


@pytest.mark.parametrize("kind", ["two_kv_heads", "block_diffusion"])
def test_counters_of_a_compile_that_hands_outputs_on(kind):
    """recompute_kept_total counts the replayed ops that were handed their
    outputs and recompute_kept_bytes those outputs' bytes as traced;
    recompute_ops_total counts what runs again and no longer them. All
    three are declared."""
    op_type, _, _, batch, _ = KINDS[kind]
    for name in ("recompute_kept_total", "recompute_kept_bytes",
                 "recompute_ops_total"):
        assert name in telemetry.METRIC_CATALOG
    main, startup, loss, feed = build(kind)
    run(main, startup, feed, [loss.name])
    label = f"program={telemetry.program_label(main)}"
    kept = {k: v for k, v in
            telemetry.read_series("recompute_kept_total").items()
            if label + "," in k}
    assert kept == {f"{label},type={op_type}": LAYERS - 1}
    out_and_lse = 4 * batch * T * HEADS * (HEAD_DIM + 1)
    assert telemetry.read_series("recompute_kept_bytes")[label] == \
        (LAYERS - 1) * out_and_lse
    ran_again = {k.split("type=")[1]: v for k, v in
                 telemetry.read_series("recompute_ops_total").items()
                 if label + "," in k}
    assert op_type not in ran_again
    types = [t for types in backward.replayed_ops(
        main, handed_on=False).values() for t in types]
    assert ran_again == dict(collections.Counter(types))
    # the CPU reports no limit: every segment is replayed
    assert telemetry.read_series("recompute_segments_total")[
        f"{label},decision=replayed,reason=no_limit"] == LAYERS - 1
