"""The delta rule's chunk inverses outlive the forward under
`append_backward(checkpoints=)` (kda_scan's registry entry keeps Inverse):
the replayed op is handed them as KeptInverse and its forward kernel reads
them where it would form them again, writing Out and the entering states;
kda_scan_grad reads both and runs the backward kernel alone. Tiny shapes on
the CPU at heads of one lane block, the kernels interpreted."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward, telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import hybrid_ops, nn_ops, pallas_kda, registry

from test_recompute_kept import kernels_in_the_step, loss_and_grads, run

EPS, CHUNK = 1e-6, 64
T, HEADS, WIDTH, D_MODEL, LAYERS = 128, 2, 128, 32, 2

# form: (Gate's and DtBias' trailing dims by head count and width, the
#        value heads a key head, the kernels' names)
FORMS = {
    "channel": (lambda h, k: ((h, k), (h * k,)), 1, "kda_scan"),
    "head_decay": (lambda h, k: ((h,), (h,)), 1, "gdn_scan"),
    "head_decay_key_groups": (lambda h, k: ((h,), (h,)), 2, "gdn_scan"),
}


def operands(form, seqlen=136, heads=4, dtype=jnp.float32):
    """kda_scan's seven operands in the kernels' order; decays of about
    -0.1 a token (tests/test_gdn_kernels.py says why no stronger)."""
    dims, ratio, _ = FORMS[form]
    gate_dims, bias_dims = dims(heads, WIDTH)
    rng = np.random.default_rng(11)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    return (normal(1, seqlen, heads // ratio, WIDTH),
            normal(1, seqlen, heads // ratio, WIDTH),
            normal(1, seqlen, heads, WIDTH),
            normal(1, seqlen, *gate_dims) - 2.0,
            jnp.asarray(np.log(rng.uniform(0.5, 1.5, heads)), jnp.float32),
            jnp.asarray(0.1 * rng.standard_normal(bias_dims), jnp.float32),
            normal(1, seqlen, heads))


def forward(args, dtype, inverse=None):
    return pallas_kda.kda_scan_forward(*args, CHUNK, EPS, dtype=dtype,
                                       interpret=True, inverse=inverse)


def calls_of(fn, *args):
    """The pallas_call equations of fn's jaxpr, through every sub-jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# --- 1. the kernel that is given the inverses --------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_given_inverse_forward_is_the_plain_forward(form, dtype):
    """Out and the entering states of a run handed the first run's
    inverses are the first run's to the last bit, over three chunks, the
    last a padded tail, and the inverses come back as they came; handed other
    inverses it gives another Out: they are read, none is formed."""
    args = operands(form, dtype=dtype)
    out, entering, inverse = forward(args, dtype)
    assert inverse.dtype == entering.dtype == jnp.float32
    pack = pallas_kda._packed(CHUNK, pallas_kda.heads_a_step(
        4, CHUNK, jnp.dtype(dtype).itemsize, FORMS[form][1]))
    assert inverse.shape == (1, 3, 4 // pack, CHUNK, pack * CHUNK)
    assert entering.shape == (1, 3, 4, WIDTH, WIDTH)
    again, entering_again, handed_back = forward(args, dtype, inverse)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.array_equal(out, again)
    assert np.array_equal(entering, entering_again)
    assert np.array_equal(inverse, handed_back)
    other, _, _ = forward(args, dtype, jnp.zeros_like(inverse))
    assert not np.array_equal(out, other)


@pytest.mark.parametrize("form", list(FORMS))
def test_the_given_inverse_kernel_forms_no_inverse(form):
    """The given-inverse call is another kernel under the forward's name
    + `_given`, reads the inverses as its last operand and writes two
    results; its body holds no full-precision product but the channel
    form's running sum (the plain one: that and two a level of the
    inverse above the second), and as many others as the plain one."""
    args = operands(form, seqlen=128, dtype=jnp.bfloat16)
    _, _, inverse = jax.eval_shape(
        lambda *a: forward(a, jnp.bfloat16), *args)
    plain, = calls_of(lambda *a: forward(a, jnp.bfloat16), *args)
    given, = calls_of(lambda *a: forward(a[:-1], jnp.bfloat16, a[-1]),
                      *args, inverse)
    name = FORMS[form][2]
    assert (plain.params["name"], given.params["name"]) == (
        name + "_fwd", name + "_fwd_given")
    assert len(given.invars) == len(plain.invars) + 1
    assert given.invars[-1].aval.shape == inverse.shape
    assert (len(plain.outvars), len(given.outvars)) == (3, 2)

    def products(call):
        seen = collections.Counter()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    seen["full" if "HIGHEST" in str(eqn.params["precision"])
                         else "other"] += 1
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(call.params["jaxpr"])
        return seen

    running_sum = int(form == "channel")
    # a step owns the four heads, two a pack; a chunk of 64 rows has six
    # levels, the first two written out on the VPU
    packs, levels = 4 // 2, CHUNK.bit_length() - 1 - 2
    assert products(given)["full"] == running_sum
    assert products(plain)["full"] == running_sum + packs * 2 * levels
    assert products(given)["other"] == products(plain)["other"] > 0


@pytest.mark.parametrize("form", list(FORMS))
def test_the_backward_alone_is_the_rules_gradient(form):
    """kda_scan_backward from kda_scan_forward's two float32 results gives
    the gradients jax.vjp of kda_scan_kernels (the custom_vjp, whose
    gradient runs the forward kernel again) gives, to the last bit, and
    traces the backward kernel alone."""
    args = operands(form)
    cot = jnp.asarray(np.random.default_rng(3).standard_normal(
        args[2].shape), jnp.float32)
    out, vjp = jax.vjp(lambda *a: pallas_kda.kda_scan_kernels(
        *a, CHUNK, EPS, interpret=True), *args)
    want = vjp(cot)
    got_out, entering, inverse = forward(args, jnp.float32)
    assert np.array_equal(out, got_out)

    def alone(*a):
        return pallas_kda.kda_scan_backward(*a, entering, inverse, cot,
                                            CHUNK, EPS, interpret=True)

    for g, g_ref in zip(alone(*args), want):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype
        assert float(jnp.abs(g_ref).max()) > 0
        assert np.array_equal(g, g_ref)
    assert [c.params["name"] for c in calls_of(alone, *args)] == [
        FORMS[form][2] + "_bwd"]


# --- 2. a program's replayed layer -------------------------------------------

def build(kind, checkpoints=True):
    """(main, startup, loss, feed) of LAYERS residual delta-rule mixers at
    heads of one lane block, a checkpoint at each layer's input: the last
    layer follows the last checkpoint and is not replayed, the first is."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[1, T, D_MODEL],
                              dtype="float32", append_batch_size=False)
        kept = []
        for _ in range(LAYERS):
            kept.append(x)
            if kind == "kda":
                y = fluid.layers.kda_mixer(x, HEADS, WIDTH, chunk_size=CHUNK)
            else:
                y = fluid.layers.gdn_mixer(x, HEADS // 2, HEADS, WIDTH,
                                           WIDTH, chunk_size=CHUNK)
            x = fluid.layers.elementwise_add(x, y)
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(x, x))
        fluid.optimizer.SGD(0.1).minimize(
            loss, startup_program=startup,
            checkpoints=kept if checkpoints else None)
    feed = {"x": np.random.default_rng(7).standard_normal(
        (1, T, D_MODEL)).astype(np.float32)}
    return main, startup, loss, feed


def built_as_the_parent(monkeypatch, kind):
    """The program of before the inverses were kept: kda_scan with Out
    alone, the generic gradient op (whose re-trace runs the custom_vjp's
    forward kernel), the replayed op forming everything again."""
    with monkeypatch.context() as patch:
        patch.setattr(hybrid_ops, "kda_scan_outputs", lambda *shape: ())
        return build(kind)


def scans(main):
    first, again = [], []
    for op in main.global_block().ops:
        if op.type == "kda_scan":
            (again if backward.RECOMPUTE_ATTR in op.desc.attrs
             else first).append(op)
    return first, again


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_the_replayed_op_is_handed_the_first_forwards_inverses(kind):
    """In the IR: the replayed kda_scan stands in its segment, reads the
    first forward's Inverse under KeptInverse behind the segment's barrier
    and everything else from the replay; kda_scan_grad reads the replay's
    Entering and Inverse; on the device the Inverse the replay writes IS
    the first forward's array and its Out and Entering equal the first
    forward's."""
    main, startup, loss, feed = build(kind)
    first, again = scans(main)
    assert len(first) == LAYERS and len(again) == LAYERS - 1
    assert backward.replayed_ops(main, handed_on=True) == {0: ["kda_scan"]}
    ran_again, = backward.replayed_ops(main, handed_on=False).values()
    assert "kda_scan" in ran_again and "rms_norm" in ran_again
    block = main.global_block()
    fwd, op = first[0], again[0]
    assert [s for s in op.desc.inputs if s.startswith("Kept")] == [
        "KeptInverse"]
    barrier, = [b for b in block.ops if b.type == "recompute_barrier"]
    behind = dict(zip(barrier.input("X"), barrier.output("Out")))
    assert op.input("KeptInverse") == [behind[fwd.output("Inverse")[0]]]
    assert fwd.output("Entering")[0] not in behind
    assert fwd.output("Out")[0] not in behind
    for slot in ("Q", "K", "V", "Gate", "Beta"):
        assert op.input(slot) == [
            fwd.input(slot)[0] + backward.RECOMPUTE_SUFFIX]
    for slot in ("Out", "Entering", "Inverse"):
        assert op.output(slot) == [
            fwd.output(slot)[0] + backward.RECOMPUTE_SUFFIX]
    grads = [g for g in block.ops if g.type == "kda_scan_grad"]
    assert len(grads) == LAYERS
    replayed_grad, = [g for g in grads
                      if g.input("Inverse") == op.output("Inverse")]
    assert replayed_grad.input("Entering") == op.output("Entering")
    assert replayed_grad.input("Q") == op.input("Q")
    for g in grads:
        assert "Out" not in g.desc.inputs
        assert "__fwd_type__" not in g.desc.attrs
    # no forward op's desc says anything of it
    for fwd_op in first:
        assert not any(s.startswith("Kept") for s in fwd_op.desc.inputs)
    fetch = [n for slot in ("Inverse", "Entering", "Out")
             for n in fwd.output(slot) + op.output(slot)]
    values = run(main, startup, feed, fetch)
    for kept, handed in zip(values[::2], values[1::2]):
        assert np.isfinite(kept).all() and np.abs(kept).max() > 0
        assert np.array_equal(kept, handed)


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_no_bit_moves_and_no_inverse_is_formed_twice(kind, monkeypatch):
    """Loss and every gradient are the parent's path's (Out alone, the
    generic gradient, everything formed again in the replay) and those
    of no checkpoints at all, to the last bit. The traced step holds one
    plain forward kernel a layer and a given-inverse one a replayed layer,
    where the parent's holds a plain one a layer, a replayed layer and a
    gradient op; without checkpoints one forward kernel a layer and no
    given-inverse one."""
    name = "kda_scan" if kind == "kda" else "gdn_scan"
    built = build(kind)
    loss, grads = loss_and_grads(*built)
    assert np.isfinite(loss).all() and len(grads) > 10 * LAYERS
    plain = build(kind, checkpoints=False)
    assert not backward.replayed_ops(plain[0])
    parents = built_as_the_parent(monkeypatch, kind)
    assert not any(backward.replayed_ops(parents[0],
                                         handed_on=True).values())
    assert "Inverse" not in parents[0].to_json()
    assert backward.replayed_ops(parents[0]) == \
        backward.replayed_ops(built[0])
    for other in (plain, parents):
        other_loss, other_grads = loss_and_grads(*other)
        assert np.array_equal(loss, other_loss)
        assert sorted(grads) == sorted(other_grads)
        for key in grads:
            assert np.abs(grads[key]).max() > 0, key
            assert np.array_equal(grads[key], other_grads[key]), key

    def scan_kernels(program):      # the short convolutions' beside them
        return {k: n for k, n in kernels_in_the_step(*program).items()
                if k.startswith(name)}

    fwd, given, bwd = (name + s for s in ("_fwd", "_fwd_given", "_bwd"))
    assert scan_kernels(built) == {
        fwd: LAYERS, given: LAYERS - 1, bwd: LAYERS}
    assert scan_kernels(plain) == {fwd: LAYERS, bwd: LAYERS}
    assert scan_kernels(parents) == {
        fwd: LAYERS + (LAYERS - 1) + LAYERS, bwd: LAYERS}


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_counters_of_a_compile_that_hands_the_inverses_on(kind):
    """kda_scan_total books a first forward `kernel` and the replayed op
    `kernel_given_inverse` (`kernel_replay` no more); recompute_kept_total
    counts the hand-over and recompute_kept_bytes the inverses' bytes as
    traced; the op runs again, so recompute_ops_total counts it too."""
    def booked(name):
        return dict(telemetry.read_series(name))

    before = booked("kda_scan_total")
    main, startup, loss, feed = build(kind)
    run(main, startup, feed, [loss.name])
    delta = {k: v - before.get(k, 0)
             for k, v in booked("kda_scan_total").items()}
    assert {k: v for k, v in delta.items() if v} == {
        f"chunk={CHUNK},path=kernel": LAYERS,
        f"chunk={CHUNK},path=kernel_given_inverse": LAYERS - 1}
    label = f"program={telemetry.program_label(main)}"
    kept = {k: v for k, v in booked("recompute_kept_total").items()
            if label + "," in k}
    assert kept == {f"{label},type=kda_scan": LAYERS - 1}
    # float32 [1, chunks, H / 2, C, 2 C]: 16 KB a head and chunk
    assert booked("recompute_kept_bytes")[label] == \
        (LAYERS - 1) * 4 * (T // CHUNK) * HEADS * CHUNK * CHUNK
    ran_again = {k.split("type=")[1]: v for k, v in
                 booked("recompute_ops_total").items() if label + "," in k}
    assert ran_again["kda_scan"] == LAYERS - 1
    assert "kernel_given_inverse" in telemetry.METRIC_CATALOG[
        "kda_scan_total"]["help"]


def test_an_op_built_without_the_outputs_keeps_nothing():
    """XLA's path (heads of 16 channels): the op is built with Out alone,
    its gradient op is the generic one and its replay reads no Kept slot,
    as before; an op built with the two at such shapes is refused where
    it is lowered."""
    assert hybrid_ops.kda_scan_outputs(64, 16, 16) == ()
    assert hybrid_ops.kda_scan_outputs(64, 128, 128, 1, False) == (
        "Entering", "Inverse")
    assert hybrid_ops.kda_scan_outputs(64, 128, 128, 2, False) == ()
    assert hybrid_ops.kda_scan_outputs(64, 128, 256, 2, True) == (
        "Entering", "Inverse")
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[1, 64, 32], dtype="float32",
                              append_batch_size=False)
        kept = [x]
        x = fluid.layers.elementwise_add(
            x, fluid.layers.kda_mixer(x, 2, 16, chunk_size=32))
        kept.append(x)
        x = fluid.layers.elementwise_add(
            x, fluid.layers.kda_mixer(x, 2, 16, chunk_size=32))
        loss = fluid.layers.mean(x)
        fluid.optimizer.SGD(0.1).minimize(loss, startup_program=startup,
                                          checkpoints=kept)
    first, again = scans(main)
    assert len(first) == 2 and len(again) == 1
    for op in first + again:
        assert sorted(op.desc.outputs) == ["Out"]
        assert not any(s.startswith("Kept") for s in op.desc.inputs)
    assert not any(backward.replayed_ops(main, handed_on=True).values())
    grads = [g for g in main.global_block().ops if g.type == "kda_scan_grad"]
    assert len(grads) == 2
    assert all(g.attr("__fwd_type__") == "kda_scan" for g in grads)

    from test_nemotron_h import run_op
    from test_kda_moe import SLOTS, scan_inputs
    with pytest.raises(Exception, match="kda_scan_outputs"):
        run_op("kda_scan", scan_inputs(False),
               {"Out": "float32", "Entering": "float32",
                "Inverse": "float32"},
               {"chunk_size": 32, "epsilon": EPS}, SLOTS)


# --- 3. the attention ops' hand-over is what it was ---------------------------

def test_the_declarations_and_what_handed_on_returns():
    """Three ops keep outputs across a replay: the two attention ops all
    they write, and their lowering returns the very values it was handed
    and reads nothing else; kda_scan its Inverse alone, which handed_on
    returns beside nothing: the lowering runs the rest."""
    assert {t: registry.get(t).kept_in_replay
            for t in registry.registered_ops()
            if registry.get(t).kept_in_replay} == {
        "scaled_dot_product_attention": ("Out", "LSE"),
        "block_diffusion_attention": ("Out", "LSE"),
        "kda_scan": ("Inverse",)}

    class Op:
        def __init__(self, op_type, outputs):
            self.type = op_type
            self.desc = type("Desc", (), {"outputs": outputs, "attrs": {}})()

    ctx = type("Ctx", (), {"program": fluid.Program()})()
    out, lse, inverse = (np.zeros(n, np.float32) for n in (1, 2, 3))
    for op_type in ("scaled_dot_product_attention",
                    "block_diffusion_attention"):
        op = Op(op_type, {"Out": ["o"], "LSE": ["l"]})
        assert registry.handed_on(ctx, op, {"Q": [None]}) is None
        # Q, K and V are not there to be read: nothing is lowered
        handed = registry.get(op_type).lower(
            ctx, op, {"KeptOut": [out], "KeptLSE": [lse]})
        assert set(handed) == {"Out", "LSE"}
        assert handed["Out"][0] is out and handed["LSE"][0] is lse
    op = Op("kda_scan", {"Out": ["o"], "Entering": ["e"], "Inverse": ["i"]})
    assert registry.handed_on(ctx, op, {"Q": [None]}) is None
    assert registry.handed_on(ctx, op, {"KeptInverse": [inverse]}) == {
        "Inverse": [inverse]}
    assert nn_ops._SDPA_KEPT == ("Out", "LSE")
