"""The short convolution's kernels (ops/pallas_conv1d.py, PR 60),
interpreted, against the jax.numpy statement
(hybrid_ops.causal_conv1d_reference): Out, dX, dFilter and dBias in both
orientations over three time blocks, so both halos are crossed, the plain
form and the gated one (PR 62: a gate ahead of the taps, a gate behind
them, silu or no activation; LFM2's operator is both gates, three taps,
no bias, no activation); an impulse across a block's edge each way; the
plain form's calls held to the jaxpr of before the gates; what the gate declines, booked
and equal to the statement; the op and its explicit gradient op through
the executor; and the eight accepted programs that build no
causal_conv1d, which serialise as the parent's.
"""

import re
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from paddle_tpu import telemetry
from paddle_tpu.ops import hybrid_ops, kernel_choice, pallas_conv1d
from tests.test_nemotron_h import run_op

BF16 = jnp.bfloat16
# three time blocks and two channel blocks of [128, 128], walked in
# chunks of [64, 128] (time on the sublanes) or [128, 16] (on the lanes)
T, C = 384, 256


def forms(lanes):
    return dict(lanes=lanes, tile=(128, 128),
                chunk=(128, 16) if lanes else (64, 128), interpret=True)


def operands(bsz, k, bias, dtype, seed=0, gates=""):
    """(X, Filter, Bias or None, Out's cotangent, {the gated form's
    keywords}); `gates`: which of "pre" and "post" are there."""
    rng = np.random.default_rng(seed)

    def rows():
        return jnp.asarray(rng.standard_normal((bsz, T, C)), dtype)

    x, w, b, d_out = (
        rows(),
        jnp.asarray(rng.standard_normal((C, k)) * k ** -0.5, jnp.float32),
        jnp.asarray(rng.standard_normal(C), jnp.float32) if bias else None,
        rows())
    return x, w, b, d_out, {side + "_gate": rows()
                            for side in ("pre", "post") if side in gates}


@functools.partial(jax.jit, static_argnames="activation")
def statement(x, w, b, d_out, pre_gate=None, post_gate=None,
              activation="silu"):
    """(Out, dX, dFilter, dBias, dPreGate, dPostGate: None where the
    operand is absent) of the statement, compiled as a program's step
    compiles it."""
    there = {key: v for key, v in dict(
        x=x, w=w, bias=b, pre_gate=pre_gate, post_gate=post_gate).items()
        if v is not None}
    out, pull = jax.vjp(lambda kw: hybrid_ops.causal_conv1d_reference(
        activation=activation, **kw), there)
    grads, = pull(d_out)
    return (out,) + tuple(grads.get(key) for key in (
        "x", "w", "bias", "pre_gate", "post_gate"))


def largest(a):
    return float(jnp.abs(a.astype(jnp.float32)).max())


def distance(got, want):
    return float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max())


# (taps, Bias, gates, activation): the plain form's four, then the gated
# form: LFM2's operator (both gates, three taps, no bias, no activation),
# the same under silu, and each gate alone
FORMS = [(4, True, "", "silu"), (4, False, "", "silu"),
         (2, True, "", "silu"), (2, False, "", "silu"),
         (3, False, "pre+post", "identity"), (3, False, "pre+post", "silu"),
         (4, True, "pre", "silu"), (2, False, "post", "identity")]


@pytest.mark.parametrize("bsz", [1, 2])
@pytest.mark.parametrize(
    "k,bias,gates,activation", FORMS,
    ids=[f"{k}-{bias}" + (f"-{gates}-{act}" if gates else "")
         for k, bias, gates, act in FORMS])
@pytest.mark.parametrize("dtype", [jnp.float32, BF16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("lanes", [False, True],
                         ids=["time_on_sublanes", "time_on_lanes"])
def test_kernels_equal_the_statement(lanes, dtype, k, bias, gates,
                                     activation, bsz):
    """Out equals the statement bit for bit (the same float32 operations
    in the same order, rounded once), gated or not; the gradient
    recomputes U = PreGate * X and the pre-activation and writes silu's
    derivative as s (1 + pre (1 - s)), so float32 holds to 1e-6 of the
    largest entry and a bf16 dX (and a gate's gradient, which leaves in
    X's dtype too) to one rounding of the statement's own bf16 result on
    the same operands; dFilter and dBias are float32 sums over B x T in
    another order."""
    x, w, b, d_out, gated = operands(bsz, k, bias, dtype, gates=gates)
    gated["activation"] = activation
    want = statement(x, w, b, d_out, **gated)
    out = pallas_conv1d.causal_conv1d_fwd(x, w, b, **gated, **forms(lanes))
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want[0], np.float32))
    d_x, d_w, d_b, d_pre, d_post = pallas_conv1d.causal_conv1d_bwd(
        x, w, b, d_out, **gated, **forms(lanes))
    assert d_w.dtype == jnp.float32
    # one rounding of a bf16 result: 2^-8 of it
    ulp = 2.0 ** -8 if dtype == BF16 else 0.0
    for got, ref, there in ((d_x, want[1], True),
                            (d_pre, want[4], "pre" in gates),
                            (d_post, want[5], "post" in gates)):
        assert (got is not None) == there
        if not there:
            continue
        assert got.dtype == x.dtype
        assert distance(got, ref) <= (1e-6 + ulp) * largest(ref)
        if dtype == BF16:       # and nearly every entry is the very same
            same = np.asarray(got, np.float32) == np.asarray(ref, np.float32)
            assert same.mean() > 0.999
    assert distance(d_w, want[2]) <= 1e-6 * largest(want[2])
    assert (d_b is None) == (not bias)
    if bias:
        assert distance(d_b, want[3]) <= 1e-6 * largest(want[3])


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("lanes", [False, True],
                         ids=["time_on_sublanes", "time_on_lanes"])
def test_an_impulse_crosses_a_time_blocks_edge_both_ways(lanes, k):
    """X is one impulse at the last step of the first time block under
    taps of ones: the pre-activation is 1 at that step and at the first
    K-1 steps of the NEXT block (the forward's halo) and 0 elsewhere. A
    cotangent that is one impulse at the first step of the second block
    reaches dX at that step and at the last K-1 steps of the block BEFORE
    (the gradient's halo), and nowhere else."""
    edge = 128
    w = jnp.ones((C, k), jnp.float32)
    x = jnp.zeros((1, T, C), jnp.float32).at[0, edge - 1, :].set(1.0)
    out = np.asarray(pallas_conv1d.causal_conv1d_fwd(x, w, None,
                                                     **forms(lanes)))
    hit = np.zeros(T, bool)
    hit[edge - 1:edge - 1 + k] = True
    one = float(jax.nn.silu(jnp.float32(1.0)))
    np.testing.assert_allclose(out[0, hit], one, rtol=1e-6)
    assert not out[0, ~hit].any()
    # silu'(0) = 1/2 wherever X is zero: dpre = dOut / 2 there
    d_out = jnp.zeros((1, T, C), jnp.float32).at[0, edge, :].set(2.0)
    d_x, d_w, *_ = pallas_conv1d.causal_conv1d_bwd(
        jnp.zeros_like(x), w, None, d_out, **forms(lanes))
    back = np.zeros(T, bool)
    back[edge - (k - 1):edge + 1] = True
    d_x = np.asarray(d_x)
    np.testing.assert_allclose(d_x[0, back], 1.0, rtol=1e-6)
    assert not d_x[0, ~back].any() and not np.asarray(d_w).any()


DECLINED = {
    "time": dict(t=96),
    "channels": dict(c=64),
    "dtype": dict(dtype="float16"),
    "taps": dict(k=pallas_conv1d._MAX_TAPS + 1),
}


def fallbacks():
    return {key: value for key, value in dict(
        telemetry.read_series("pallas_fallback_total")).items()
        if "causal_conv1d" in str(key)}


@pytest.mark.parametrize("reason", sorted(DECLINED))
def test_a_declined_shape_keeps_the_statement_and_is_booked(reason):
    """Each reason the gate declares, once: the op runs
    causal_conv1d_reference and autodiff's gradient of it, equals the
    statement, and books pallas_fallback_total{op, reason} once a forward
    lowering (run_op lowers the forward alone first, for the cotangent's
    shape; the gradient op books nothing)."""
    assert set(DECLINED) == kernel_choice.REASONS["causal_conv1d"]
    shape = dict(dict(t=128, c=128, k=4, dtype="float32"), **DECLINED[reason])
    assert pallas_conv1d.ineligible(
        shape["t"], shape["c"], shape["k"], shape["dtype"]) == reason
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, shape["t"], shape["c"])).astype(
        shape["dtype"])
    w = (0.5 * rng.standard_normal((shape["c"], shape["k"]))).astype(
        np.float32)
    before = fallbacks()
    outs, grads, cot = run_op("causal_conv1d", {"X": x, "Filter": w},
                              {"Out": shape["dtype"]}, {}, ("X", "Filter"))
    added = {key: value - before.get(key, 0)
             for key, value in fallbacks().items()
             if value != before.get(key, 0)}
    assert list(added.values()) == [2] and reason in str(list(added)[0])
    want = statement(jnp.asarray(x), jnp.asarray(w), None,
                     jnp.asarray(cot, x.dtype))
    tol = 1e-5 if x.dtype == np.float32 else 2e-3
    assert distance(jnp.asarray(outs["Out"]), want[0]) <= tol * largest(
        want[0])
    for slot, g in zip(("X", "Filter"), want[1:]):
        assert distance(jnp.asarray(grads[slot]), g) <= tol * largest(g)


@pytest.mark.parametrize("bias,gates,activation", [
    (True, "", "silu"), (False, "", "silu"),
    (False, "PreGate+PostGate", "identity"), (True, "PostGate", "silu")],
    ids=["bias", "no_bias", "gated_as_lfm2", "a_gate_behind"])
@pytest.mark.parametrize("lanes", [False, True],
                         ids=["time_on_sublanes", "time_on_lanes"])
def test_the_op_and_its_gradient_op_run_the_kernels(lanes, bias, gates,
                                                    activation):
    """Through the executor at a shape the gate takes: the forward books
    pallas_kernel_total{op="causal_conv1d"} once and no fallback, the
    explicit gradient op (causal_conv1d_grad: the op's inputs and Out's
    cotangent in, nothing the forward made) books nothing, and Out and
    every input's gradient, the gates' among them, are the statement's;
    `time_on_lanes`, the attribute mamba2_mixer writes, turns the blocks
    and not the result. The gated form is booked as the plain one."""
    rng = np.random.default_rng(5)
    inputs = {"X": rng.standard_normal((2, 256, 128)).astype(np.float32),
              "Filter": (0.5 * rng.standard_normal((128, 4))).astype(
                  np.float32)}
    if bias:
        inputs["Bias"] = rng.standard_normal(128).astype(np.float32)
    for slot in filter(None, gates.split("+")):
        inputs[slot] = rng.standard_normal((2, 256, 128)).astype(np.float32)
    attrs = {"time_on_lanes": lanes}
    if activation != "silu":
        attrs["activation"] = activation

    def hits():
        return sum(value for key, value in dict(
            telemetry.read_series("pallas_kernel_total")).items()
            if "causal_conv1d" in str(key))

    kernels, declined = hits(), fallbacks()
    outs, grads, cot = run_op("causal_conv1d", inputs, {"Out": "float32"},
                              attrs, tuple(inputs))
    # (run_op runs the forward alone once for the cotangent's shape)
    assert hits() == kernels + 2 and fallbacks() == declined
    want = statement(*(jnp.asarray(inputs.get(s)) if s in inputs else None
                       for s in ("X", "Filter", "Bias")), jnp.asarray(cot),
                     *(jnp.asarray(inputs.get(s)) if s in inputs else None
                       for s in ("PreGate", "PostGate")),
                     activation=activation)
    np.testing.assert_array_equal(outs["Out"], np.asarray(want[0]))
    for slot, g in zip(("X", "Filter", "Bias", "PreGate", "PostGate"),
                       want[1:]):
        if slot in inputs:
            assert grads[slot].dtype == np.float32
            assert distance(jnp.asarray(grads[slot]), g) <= 1e-6 * largest(g)


def test_the_gradient_op_reads_nothing_the_forward_made():
    """The gradient op's inputs are the op's own and Out's cotangent: a
    replayed segment has nothing to hand on and nothing is kept for it."""
    from paddle_tpu import layers
    import paddle_tpu as fluid
    from paddle_tpu.ops import registry

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[1, 256, 128], dtype="float32",
                        append_batch_size=False)
        y = layers.mamba2_mixer(x, num_heads=2, head_dim=64, n_groups=1,
                                state_size=128)
        fluid.backward.append_backward(layers.reduce_sum(y))
    ops = {op.type: op for op in main.global_block().ops}
    assert ops["causal_conv1d"].attr("time_on_lanes") is True
    grad = ops["causal_conv1d_grad"]
    assert sorted(grad.desc.inputs) == ["Bias", "Filter", "Out@GRAD", "X"]
    assert sorted(grad.desc.outputs) == ["Bias@GRAD", "Filter@GRAD",
                                         "X@GRAD"]
    assert grad.attr("time_on_lanes") is True
    assert not registry.get("causal_conv1d").kept_in_replay


def test_the_gated_layers_gradient_op_reads_nothing_the_forward_made():
    """layers.short_conv_mixer builds ONE causal_conv1d with both gates
    and no activation; its gradient op reads the op's four inputs and
    Out's cotangent and returns a gradient for each of the four."""
    from paddle_tpu import layers
    import paddle_tpu as fluid
    from paddle_tpu.ops import registry

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[1, 256, 128], dtype="float32",
                        append_batch_size=False)
        fluid.backward.append_backward(layers.reduce_sum(
            layers.short_conv_mixer(x)))
    ops = [op for op in main.global_block().ops
           if op.type.startswith("causal_conv1d")]
    assert [op.type for op in ops] == ["causal_conv1d", "causal_conv1d_grad"]
    op, grad = ops
    assert sorted(op.desc.inputs) == ["Filter", "PostGate", "PreGate", "X"]
    assert op.desc.attrs["activation"] == "identity"
    assert "time_on_lanes" not in op.desc.attrs
    assert sorted(grad.desc.inputs) == ["Filter", "Out@GRAD", "PostGate",
                                        "PreGate", "X"]
    assert sorted(grad.desc.outputs) == ["Filter@GRAD", "PostGate@GRAD",
                                         "PreGate@GRAD", "X@GRAD"]
    assert grad.attr("activation") == "identity"
    assert not registry.get("causal_conv1d").kept_in_replay


def call_digest(call, axis, shape, k, bias, form=()):
    """The first 16 hex digits of sha256 over the jaxpr of one of the
    kernels' calls and its BlockSpecs' index maps (a pallas_call prints
    its specs without them)."""
    bsz, t, c = shape
    fn = getattr(pallas_conv1d, call)(axis, bsz, t, c, k, bias,
                                      jnp.dtype(BF16), None, None, False,
                                      *form)
    x = jax.ShapeDtypeStruct((bsz, t, c) if axis == 0 else (bsz, c, t), BF16)
    rows = k + bias
    taps = jax.ShapeDtypeStruct((rows, c) if axis == 0 else (c, rows),
                                jnp.float32)
    pre, post = (form + (False, False))[:2]
    args = (x,) * (1 + pre + post) if call == "_fwd_call" \
        else (x,) * (2 + 2 * pre + post + 1)
    jaxpr = jax.make_jaxpr(fn)(*args, taps)
    # the parent's calls declared no work (PR 66): the digests hold the
    # rest of the call, the declaration set aside
    text = [re.sub(r"cost_estimate=CostEstimate\([^)]*\)",
                   "cost_estimate=None", str(jaxpr))]

    def index_maps(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                text.extend(str(m.index_map_jaxpr) for m in
                            eqn.params["grid_mapping"].block_mappings)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                index_maps(sub)

    index_maps(jaxpr.jaxpr)
    assert len(text) > 1
    return hashlib.sha256("\n".join(text).encode()).hexdigest()[:16]


# (call, time axis, [B, T, C], taps, Bias) of the three accepted cells'
# kernel calls and their digests at the parent commit (PR 61: `git
# archive 214c520`, the same function run there)
PARENTS_CALLS = [
    ("_fwd_call", 0, (1, 8192, 4096), 4, False, "acb297e9c2124c6c"),
    ("_fwd_call", 1, (1, 8192, 4352), 4, True, "435f2f814939f2ae"),
    ("_fwd_call", 1, (1, 4096, 6144), 4, True, "1a9a446e478d11cf"),
    ("_bwd_call", 0, (1, 8192, 4096), 4, False, "1361ff5549c57782"),
    ("_bwd_call", 1, (1, 8192, 4352), 4, True, "7521ad21471c928e"),
    ("_bwd_call", 1, (1, 4096, 6144), 4, True, "a3b429f736f4697f"),
]


@pytest.mark.parametrize("call,axis,shape,k,bias,digest", PARENTS_CALLS,
                         ids=[f"{c[0]}-{'x'.join(map(str, c[2]))}"
                              for c in PARENTS_CALLS])
def test_the_plain_forms_calls_are_the_calls_of_before(call, axis, shape, k,
                                                       bias, digest):
    """Without a gate and under silu every kernel call is traced to the
    jaxpr of before PR 62, index maps included: the Kimi-Linear, granite
    and hybrid cells' steps lower what they lowered (their programs are
    held to the parent's by tests/test_conv_moe.py). A gated call is
    another kernel under another name."""
    assert call_digest(call, axis, shape, k, bias) == digest
    assert call_digest(call, axis, shape, k, bias,
                       (True, True, "identity")) != digest


# (main, startup) of the eight accepted configurations that build no
# causal_conv1d, as the parent commit (PR 59) built them: the first 16
# hex digits of sha256 over Program.to_json(). Their cells' steps are the
# parent's executables.
PARENT_PROGRAMS = {
    "resnet50": ("794c807653f3c470", "729fcfb275892b3b"),
    "gpt2": ("32530ba784525f48", "6cae3670f852b823"),
    "gpt2-large": ("90b85e99fedb4110", "0abfed69b2161959"),
    "glm-4.7-flash": ("c6c56c119b5b8e5c", "57465f9570324186"),
    "sdar-30b-a3b-chat": ("ae3fbd7051acd413", "d87f315e59443b2d"),
    "smallthinker-21b-a3b-instruct": ("dc6827508c9e3acb", "33caad39b72bf4c9"),
    "laguna-xs.2": ("d9e2dffcce8f0f43", "2bfd552d47366495"),
    "ouro-2.6b": ("c032844e8897b75c", "fc8a5054f91ff386"),
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_program_without_the_convolution_is_built_as_it_was(name):
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    assert not [op.type for op in main.global_block().ops
                if op.type.startswith("causal_conv1d")]
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]
