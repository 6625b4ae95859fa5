"""The layers of a decoder whose mixers are Kimi Delta Attention, three
to one latent-attention layer without positions (models.kda_moe_lm:
Kimi-Linear's block), at tiny sizes on the CPU with the published ratios:
the op's chunked delta rule, forward and every input's gradient, against
the recurrence token by token, at two chunk lengths and at decays as
strong as the published initial values allow; the triangular inverse
where keys repeat; the whole tiny model, loss and every gradient, against
benchmarks/families/kimi_linear.py::reference_loss; latent attention with
one direct query map and no rotation against its equations, and at keys
192 wide beside values 128 wide on the interpreted flash kernels against
the einsum path; the shares of the expert layer; and that a program which
asks for none of it is the one the parent built."""

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
from paddle_tpu.framework.framework import NAME_SCOPE_ATTR, grad_var_name
from paddle_tpu.ops import hybrid_ops, pallas_kda

from benchmarks import run
from test_nemotron_h import close, first_step, run_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
TINY = "tiny-kimi-linear"


def tiny(**over):
    config = dict(run.load_json("configs", TINY, DATA), **over)
    return config, run.load_module("families", config["family"])


# --- 1. the op against the recurrence ----------------------------------------

def recurrence(q, k, v, gate, a_log, dt_bias, beta, eps):
    """kda_scan's equations a token at a time, in float32: the L2 norms,
    g = -exp(A_log) softplus(gate + dt_bias), beta's sigmoid, S_t = (I -
    beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T, o_t =
    S_t^T q_t / sqrt(K)."""
    heads, width = q.shape[2], q.shape[3]
    q, k = (x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)
            for x in (q, k))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        gate + dt_bias.reshape(heads, width))
    beta = jax.nn.sigmoid(beta)

    def token(state, now):
        q_t, k_t, v_t, g_t, b_t = now                   # [B, H, ...]
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + b_t[..., None, None] * k_t[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    start = jnp.zeros((q.shape[0], heads, width, v.shape[-1]))
    _, out = jax.lax.scan(token, start, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1) * width ** -0.5, g


SLOTS = ("Q", "K", "V", "Gate", "ALog", "DtBias", "Beta")


def scan_inputs(strong, bsz=2, seqlen=200, heads=3, width=16, value=8):
    """kda_scan's inputs. `strong`: the strongest decay the published
    initial values allow on every channel, A = 16 and a step of 0.1 (a
    token's log-decay -1.6, a chunk of 64 tokens' -102), beside raw gates
    near zero; else A in [1, 16] and steps in [0.001, 0.1]."""
    rng = np.random.default_rng(5)

    def normal(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    step = np.full(heads * width, 0.1) if strong else np.exp(
        rng.uniform(np.log(0.001), np.log(0.1), heads * width))
    return {
        "Q": normal(bsz, seqlen, heads, width),
        "K": normal(bsz, seqlen, heads, width),
        "V": normal(bsz, seqlen, heads, value),
        "Gate": normal(bsz, seqlen, heads, width,
                       scale=0.01 if strong else 0.5),
        "ALog": np.log(np.full(heads, 16.0) if strong
                       else rng.uniform(1, 16, heads)).astype(np.float32),
        "DtBias": np.log(np.expm1(step)).astype(np.float32),
        "Beta": normal(bsz, seqlen, heads)}


# the shapes that take each path of the op (hybrid_ops.kda_scan_ineligible):
# heads of 16 and 8 channels XLA's chunked form, a lane block each the
# kernels of ops/pallas_kda.py, interpreted here
PATHS = {"chunked": dict(), "kernel": dict(bsz=1, heads=2, width=128,
                                           value=128)}


def booked_paths():
    return dict(telemetry.read_series("kda_scan_total"))


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_delta_rule_matches_the_recurrence(chunk, strong, path):
    """Out to 2e-5 and the gradient of every one of the op's seven
    inputs to 1e-4 of its largest entry, at 200 tokens (several chunks
    and a padded tail), against jax.grad of the recurrence; at the strong
    decays a chunk of 64 sums to -102 and exp(-G) alone would be inf.
    By `path`: XLA's chunked form, and the kernels with their hand-written
    gradient (the float32 state, sums, exponents and inverse are what
    2e-5 holds: a state or a summed decay in bf16 reads 2.5e-3)."""
    ins = scan_inputs(strong, **PATHS[path])
    eps = 1e-6
    before = booked_paths()
    got, grads, cot = run_op("kda_scan", ins, {"Out": "float32"},
                             {"chunk_size": chunk, "epsilon": eps}, SLOTS)
    label = f"chunk={chunk},path={path}"
    assert booked_paths().get(label, 0) > before.get(label, 0)
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    want, g = recurrence(*args, eps)
    if strong:      # a chunk of 64 tokens' summed log-decay, a channel
        assert float(g[:, :64].sum(1).max()) < -90
    assert np.isfinite(got["Out"]).all()
    close(got["Out"], want)
    want_grads = jax.grad(
        lambda *a: (recurrence(*a, eps)[0] * cot).sum(),
        argnums=range(len(SLOTS)))(*args)
    for slot, g_ref in zip(SLOTS, want_grads):
        assert np.isfinite(grads[slot]).all(), slot
        assert float(jnp.abs(g_ref).max()) > 0, slot
        close(grads[slot], g_ref, tol=1e-4)


def kernels(q, k, v, gate, a_log, dt_bias, beta, chunk, dtype=jnp.float32,
            eps=1e-6, **how):
    return pallas_kda.kda_scan_kernels(q, k, v, gate, a_log, dt_bias, beta,
                                       chunk, eps, dtype=dtype,
                                       interpret=True, **how)


def with_gradients(fn, args, cot=None):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(jnp.ones_like(out) if cot is None else cot)


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_the_kernels_are_the_chunked_form(strong):
    """The op on the kernels against the op on kda_chunked (jax.vjp of
    XLA's form) on the same float32 inputs, 200 tokens in chunks of 64
    with a padded tail, two heads in one grid step and a head a step: Out
    and the gradients of q, k, v, the gate and beta to 1e-5 of the
    largest entry, A_log's and dt_bias', which sum a cotangent over every
    token, to 1e-4."""
    ins = scan_inputs(strong, **PATHS["kernel"])
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        ins["V"].shape), jnp.float32)
    want = with_gradients(lambda *a: hybrid_ops.kda_scan_chunked(
        *a, 64, 1e-6), args, cot)
    for heads in (2, 1):
        got = with_gradients(lambda *a: kernels(*a, 64, heads=heads), args,
                             cot)
        for name, g, w in zip(("Out",) + SLOTS, got, want):
            assert float(jnp.abs(w).max()) > 0, name
            close(g, w, tol=1e-4 if name in ("ALog", "DtBias") else 1e-5)


def test_the_kernels_carry_the_state():
    """200 tokens through the kernels in chunks of 32 (seven grid steps
    with the state in scratch, the tail padded) and as one chunk of 256
    that carries nothing: the same output and the same seven gradients
    to float32's rounding."""
    ins = scan_inputs(False, **PATHS["kernel"])
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    for got, want in zip(
            with_gradients(lambda *a: kernels(*a, 32), args),
            with_gradients(lambda *a: kernels(*a, 256), args)):
        close(got, want, tol=1e-5)


def test_chunks_carry_the_state():
    """200 tokens in chunks of 32 (a lax.scan over seven checkpointed
    chunks with the state as the carry, the tail padded to a chunk) and
    as one chunk of 256 that carries nothing: the same output and the
    same five gradients to float32's rounding."""
    ins = scan_inputs(False)
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in args[:2])
    g = -jax.nn.softplus(args[3])
    operands = (q, k, args[2], g, jax.nn.sigmoid(args[6]))

    def both(chunk):
        out, vjp = jax.vjp(
            lambda *a: hybrid_ops.kda_chunked(*a, chunk), *operands)
        return (out,) + vjp(jnp.ones_like(out))

    for got, want in zip(both(32), both(256)):
        close(got, want, tol=1e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (a scan's body, a checkpoint's, a custom_vjp's call)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for held in eqn.params.values():
            inner = getattr(held, "jaxpr", held)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


def test_the_state_and_the_decays_stay_float32_under_bf16_operands():
    """What the benchmark's comparison cannot see on the chip (PERF.md
    section 7: behind five layers of bf16 cotangents a state or a summed
    decay held in bf16 reads as a sound seed does), held here by the
    traced program itself: with bf16 operands the walk's carry is the
    float32 state, every exponent and every running sum is taken in
    float32, the triangular inverse's products have float32 operands, and
    every product accumulates in float32."""
    ins = scan_inputs(False, seqlen=64)
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    operands = (args[0], args[1], args[2], -jax.nn.softplus(args[3]),
                jax.nn.sigmoid(args[6]))
    traced = jax.make_jaxpr(lambda *a: hybrid_ops.kda_chunked(
        *a, 32, dtype=jnp.bfloat16))(*operands).jaxpr
    walk, = [e for e in traced.eqns if e.primitive.name == "scan"]
    body = walk.params["jaxpr"]
    carry = body.in_avals[walk.params["num_consts"]:][
        :walk.params["num_carry"]]
    assert [(a.shape, a.dtype) for a in carry] == [((2, 3, 16, 8),
                                                    jnp.float32)]
    seen = {"exp": 0, "cumsum": 0, "full": 0, "bf16": 0}
    for eqn in _equations(traced):
        name = eqn.primitive.name
        if name in ("exp", "cumsum"):
            assert eqn.invars[0].aval.dtype == jnp.float32, eqn
            seen[name] += 1
        elif name == "dot_general":
            assert eqn.outvars[0].aval.dtype == jnp.float32, eqn
            kinds = {v.aval.dtype for v in eqn.invars}
            seen["full" if kinds == {jnp.dtype("float32")} else "bf16"] += 1
            if kinds == {jnp.dtype("float32")}:   # the inverse's levels
                assert "HIGHEST" in str(eqn.params["precision"]), eqn
    # a chunk: five exponents, one running sum, two products a level of
    # the inverse (32 rows: five levels), seven with bf16 operands
    assert seen == {"exp": 5, "cumsum": 1, "full": 10, "bf16": 7}, seen


def test_the_kernels_keep_float32_where_the_chunked_form_does():
    """The same, read from the kernels' own traced programs (the
    pallas_calls of a forward and its gradient: the forward kernel, whose
    entering states and inverses the rule keeps, and the backward kernel)
    with bf16 operands: each call's scratch is the float32 [R, V, K]
    state or its cotangent; every exponent, logarithm and reciprocal root
    is taken of a float32; the running sum is a float32 product at full
    precision with the triangle of ones; the inverse's levels and its
    pull-back (-M^T dM M^T) are float32 x float32 at full precision;
    every other product takes bf16 operands and every product's result
    is float32; and the entering states and inverses cross from the forward
    kernel to the backward kernel in float32."""
    ins = scan_inputs(False, seqlen=64, **PATHS["kernel"])
    args = [jnp.asarray(ins[s], jnp.bfloat16 if s in ("Q", "K", "V", "Gate",
                                                      "Beta") else None)
            for s in SLOTS]
    traced = jax.make_jaxpr(lambda *a: with_gradients(
        lambda *b: kernels(*b, 32, dtype=jnp.bfloat16), a))(*args).jaxpr
    calls = [e for e in _equations(traced) if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["kda_scan_fwd",
                                                 "kda_scan_bwd"]
    # the two heads' systems of a chunk of 32 rows are one pack: the
    # running sum and the inverse's two products a level above the
    # second (the first two are written out on the VPU, in float32); in
    # the backward the running sum, its transpose and the inverse's
    # pull-back
    full = {"kda_scan_fwd": 1 + 2 * 3, "kda_scan_bwd": 1 + 1 + 2}
    for call in calls:
        name, body = call.params["name"], call.params["jaxpr"]
        scratch = body.invars[-1].aval
        assert (scratch.shape, scratch.dtype) == ((2, 128, 128), jnp.float32)
        seen = {"full": 0, "bf16": 0, "exp": 0}
        for eqn in _equations(body):
            prim = eqn.primitive.name
            if prim in ("exp", "log1p", "rsqrt", "logistic", "reduce_sum"):
                assert eqn.invars[0].aval.dtype == jnp.float32, eqn
                seen["exp"] += prim == "exp"
            elif prim == "dot_general":
                assert eqn.outvars[0].aval.dtype == jnp.float32, eqn
                kinds = {v.aval.dtype for v in eqn.invars}
                if kinds == {jnp.dtype("float32")}:
                    assert "HIGHEST" in str(eqn.params["precision"]), eqn
                    seen["full"] += 1
                else:
                    assert kinds == {jnp.dtype("bfloat16")}, eqn
                    seen["bf16"] += 1
        assert seen["full"] == full[name], (name, seen)
        assert seen["bf16"] >= 7 and seen["exp"] >= 5, (name, seen)
    kept = [(v.aval.shape, v.aval.dtype) for v in calls[0].outvars[1:]]
    assert kept == [((1, 2, 2, 128, 128), jnp.float32),
                    ((1, 2, 1, 32, 64), jnp.float32)]
    assert [(v.aval.shape, v.aval.dtype) for v in calls[1].invars[-2:]] == kept


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nothing_overflows_in_the_kernels_at_the_strongest_decay(dtype):
    """The kernels at chunks of 64 whose summed log-decay is -102 on
    every channel: every value and every gradient finite in float32 and
    with bf16 operands, and the bf16 result within 3 % of the float32
    one's largest entry."""
    ins = scan_inputs(True, seqlen=256, **PATHS["kernel"])
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    got = with_gradients(lambda *a: kernels(*a, 64, dtype=jnp.dtype(dtype)),
                         args)
    assert all(bool(jnp.isfinite(x).all()) for x in got)
    close(got[0], kernels(*args, 64), tol=3e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nothing_overflows_at_the_strongest_published_decay(dtype):
    """kda_chunked itself at chunks of 64 whose summed log-decay is -102
    on every channel: every value and every gradient finite in float32
    and with bf16 operands, and the bf16 result within 3 % of the float32
    one's largest entry."""
    ins = scan_inputs(True, seqlen=256)
    args = [jnp.asarray(ins[s]) for s in SLOTS]
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in args[:2])
    g = -16.0 * jax.nn.softplus(args[3] + args[5].reshape(3, 16))
    beta = jax.nn.sigmoid(args[6])
    assert float(g.reshape(2, 4, 64, 3, 16).sum(2).max()) < -90

    def total(q, k, v, g, beta, dtype):
        return hybrid_ops.kda_chunked(q, k, v, g, beta, 64,
                                      dtype=jnp.dtype(dtype))

    out = total(q, k, args[2], g, beta, dtype)
    grads = jax.grad(lambda *a: total(*a, dtype).sum(), argnums=range(5))(
        q, k, args[2], g, beta)
    assert all(bool(jnp.isfinite(x).all()) for x in (out,) + grads)
    close(out, total(q, k, args[2], g, beta, "float32"), tol=3e-2)


def test_the_triangular_inverse_where_every_key_is_the_same():
    """(I + A)^-1 by block forward substitution, against numpy's inverse:
    random strictly lower A, and A = 0.9 below the diagonal (every key
    the same, beta 0.9, no decay), where the powers of A that a Neumann
    product forms reach 1e13 and the inverse's entries stay under 1."""
    rng = np.random.default_rng(2)
    lower = np.tril(np.ones((64, 64), np.float32), -1)
    for a in (lower * rng.standard_normal((3, 64, 64)).astype(np.float32),
              0.9 * lower[None]):
        want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
        got = hybrid_ops._unit_lower_inverse(jnp.asarray(a))
        assert float(np.abs(np.asarray(got) - want).max()) \
            <= 1e-5 * max(1.0, float(np.abs(want).max()))


def kernels_inverse(a, interpret=True):
    """pallas_kda._inverse on [pack, C, C] strictly lower blocks, side by
    side in one [C, pack C] array as the kernels hold them."""
    from jax.experimental import pallas as pl
    pack, c, _ = a.shape
    packed = jnp.concatenate(list(jnp.asarray(a, jnp.float32)), axis=1)

    def body(a_ref, out_ref):
        _, _, row, col = pallas_kda._triangles(c, pack)
        out_ref[...] = pallas_kda._inverse(
            a_ref[...], row, col, pallas_kda._head_of_lane(packed.shape, c))

    out = pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(
        packed.shape, jnp.float32), interpret=interpret)(packed)
    return np.asarray(out).reshape(c, pack, c).transpose(1, 0, 2)


@pytest.mark.parametrize("pack,c,spread", [(2, 64, 1.0), (2, 32, 1.0),
                                           (1, 128, 0.25)])
def test_the_kernels_inverse_where_every_key_is_the_same(pack, c, spread):
    """The same two systems through the kernels' own inverse (two heads
    side by side in one array, the first level not formed, the second on
    the VPU, the rest two full-precision products a level), against
    numpy's inverse at 1e-5 (128 rows of N(0, 1) are a system no float32
    solves: its inverse reaches 1e14; a quarter of that spread there)."""
    rng = np.random.default_rng(2)
    lower = np.tril(np.ones((c, c), np.float32), -1)
    for a in (lower * (spread * rng.standard_normal((pack, c, c))).astype(
            np.float32), 0.9 * np.broadcast_to(lower, (pack, c, c))):
        want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
        assert float(np.abs(kernels_inverse(a) - want).max()) \
            <= 1e-5 * max(1.0, float(np.abs(want).max()))


def test_the_conv_takes_no_bias():
    """causal_conv1d without its Bias is the op with a zero Bias."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 12, 6)).astype(np.float32)
    taps = rng.standard_normal((6, 4)).astype(np.float32)
    bare, grads, _ = run_op("causal_conv1d", {"X": x, "Filter": taps},
                            {"Out": "float32"}, {}, ("X", "Filter"))
    full, full_grads, _ = run_op(
        "causal_conv1d",
        {"X": x, "Filter": taps, "Bias": np.zeros(6, np.float32)},
        {"Out": "float32"}, {}, ("X", "Filter"))
    close(bare["Out"], full["Out"], tol=1e-6)
    for slot in ("X", "Filter"):
        close(grads[slot], full_grads[slot], tol=1e-6)


# --- 2. the whole tiny model against the reference ---------------------------

VARIANTS = {"as_published": {}, "nothing_replayed": {"recompute": False},
            "another_chunk": {"kda_chunk_size": 32}}


# the reference's loss and gradients on the tiny model's weights and
# batch, computed by the first variant that runs: the variants share the
# startup program and its counter, so they share the weights (asserted)
_REFERENCE = {}


@functools.lru_cache(maxsize=None)
def loss_and_gradients(variant):
    """(names, the program's loss and gradients, the reference's) of the
    tiny model under VARIANTS[variant], run once a variant."""
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
        params = [np.asarray(scope.find_var(n)) for n in names]
        got, *grads = exe.run(
            main, feed=feed,
            fetch_list=[loss] + [grad_var_name(n) for n in names])
    if not _REFERENCE:
        _REFERENCE["weights"] = params
        _REFERENCE["want"] = jax.value_and_grad(
            lambda p: family.reference_loss(config, p, feed))(
                [jnp.asarray(p) for p in params])
    for a, b in zip(_REFERENCE["weights"], params):
        np.testing.assert_array_equal(a, b)
    return names, (float(np.ravel(got)[0]), grads), _REFERENCE["want"]


@pytest.mark.parametrize("variant", VARIANTS)
def test_tiny_model_against_the_reference_in_float32(variant):
    """Loss to 1e-6 and EVERY parameter's gradient to 1e-5 of its own
    largest entry, the program's fetched gradients (the chunked delta
    rule's among them) against jax.grad of the reference (the recurrence
    token by token) on the same weights and batch, over [KDA + dense,
    KDA, KDA, latent, KDA] at T = 64."""
    names, (got, grads), (want, want_grads) = loss_and_gradients(variant)
    # embedding; norm + 15 (KDA) or 5 (latent) + norm + 3 (dense) or 7
    # (experts); the final norm and the head: no router bias
    assert len(names) == 1 + (17 + 3) + 3 * (17 + 7) + (7 + 7) + 2
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for name, g, g_ref in zip(names, grads, want_grads):
        assert np.abs(np.asarray(g_ref)).max() > 0, name
        close(g, g_ref, tol=1e-5)


def test_a_replayed_layer_changes_no_value():
    """With and without checkpoints the loss and every gradient agree to
    float32's rounding (1e-5 of each tensor's largest entry: the replayed
    ops are the forward's own, and XLA fuses the replayed delta rule with
    other neighbours than the first one's)."""
    (_, with_, _), (_, without, _) = (
        loss_and_gradients(v) for v in ("as_published", "nothing_replayed"))
    assert abs(with_[0] - without[0]) <= 2e-6 * abs(without[0])
    for a, b in zip(with_[1], without[1]):
        close(a, b, tol=1e-5)


def test_tiny_model_against_the_reference_under_amp():
    """bf16 against float32 on the CPU (read: loss 1.4e-4, gradient
    0.033, its norm 1.9e-3, tail 0.019, update 1.5e-5: at d = 64 and 64
    tokens a rounding is a larger share than at the cell's size)."""
    found, _, _ = first_step("O2", TINY)
    assert found["loss_rel_diff"] <= 4e-4
    assert found["grad_rel_err"] <= 0.06
    assert found["grad_tail_rel_err"] <= 0.04
    assert found["grad_norm_rel_diff"] <= 0.01
    assert found["update_rel_err"] <= 1e-3


def test_the_model_is_built_from_the_published_lists():
    """Mixers by the two 1-based lists, the leading dense layer, expert
    layers behind it; a layer in neither list, or in both, is refused."""
    from paddle_tpu.models import kda_moe
    config, family = tiny()
    main, _, _ = family.build(dict(config, recompute=False))
    ops = [op.type for op in main.global_block().ops
           if backward.RECOMPUTE_ATTR not in op.desc.attrs]
    mixers = [t for t in ops if t in ("kda_scan",
                                      "scaled_dot_product_attention")]
    assert mixers == ["kda_scan"] * 3 + ["scaled_dot_product_attention",
                                         "kda_scan"]
    assert ops.count("moe_experts") == 4 and ops.count("moe_router") == 4
    assert "rotary_embedding" not in ops
    assert ops.count("causal_conv1d") == 12
    with pytest.raises(ValueError):
        kda_moe.mixer_kinds(3, [1, 2], [2, 3])
    with pytest.raises(ValueError):
        kda_moe.mixer_kinds(3, [1], [3])
    published = run.load_json("configs", "kimi-linear-48b-a3b-instruct")
    linear = published["linear_attn_config"]
    kinds = kda_moe.mixer_kinds(27, linear["kda_layers"],
                                linear["full_attn_layers"])
    assert kinds.count(kda_moe.KDA) == 20 and kinds.count(kda_moe.FULL) == 7
    assert kinds[:5] == [kda_moe.KDA] * 3 + [kda_moe.FULL, kda_moe.KDA]


def test_the_scopes_hold_the_mixers_and_the_counter_books_the_chunk():
    """Every op of a mixer and its gradient is under its layer's scope;
    a compile books one forward lowering a KDA layer under its chunk
    length and one a replayed layer (the last layer's segment runs once:
    its backward follows it), and the gradient's re-trace none."""
    config, family = tiny()
    main, startup, loss = family.build(config)
    scopes = {}
    for op in main.global_block().ops:
        scopes.setdefault(op.type, set()).add(
            op.desc.attrs.get(NAME_SCOPE_ATTR))
    assert scopes["kda_scan"] == scopes["kda_scan_grad"] \
        == scopes["causal_conv1d"] == {"/kda_mixer/"}
    assert scopes["scaled_dot_product_attention"] == {"/latent_attention/"}
    assert scopes["moe_experts"] == {"/moe_block/"}

    def booked():
        return dict(telemetry.snapshot()["counters"].get("kda_scan_total",
                                                          {}))

    before = booked()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        exe.run(main, feed=family.make_batch(
            config, 2, np.random.default_rng(0)), fetch_list=[loss])
    delta = {key: value - before.get(key, 0)
             for key, value in booked().items()}
    chunk = config["kda_chunk_size"]
    assert {key: value for key, value in delta.items() if value} == {
        f"chunk={chunk},path=chunked": 4,
        f"chunk={chunk},path=chunked_replay": 3}


# --- 3. latent attention without a latent query and without positions --------

def nope_mla(x, w_q, w_kva, norm_kv, w_kvb, w_o, heads, nope, rope, vd,
             eps=1e-5):
    """The layer's equations a head at a time, in float32: one direct
    query map, the one shared key head carried unturned."""
    t, kvr = x.shape[0], norm_kv.shape[0]
    q = (x @ w_q).reshape(t, heads, nope + rope)
    down = x @ w_kva
    c_kv, k_pe = down[:, :kvr], down[:, kvr:]
    c_kv = norm_kv * c_kv / np.sqrt((c_kv ** 2).mean(-1, keepdims=True) + eps)
    kv = (c_kv @ w_kvb).reshape(t, heads, nope + vd)
    mask = np.tril(np.ones((t, t), bool))
    out = []
    for j in range(heads):
        k_j = np.concatenate([kv[:, j, :nope], k_pe], -1)
        s = np.where(mask, q[:, j] @ k_j.T / np.sqrt(nope + rope), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append(p / p.sum(-1, keepdims=True) @ kv[:, j, nope:])
    return np.concatenate(out, -1) @ w_o


def latent_layer(x, use_flash, heads, nope, rope, vd, kvr, wrt_all=False):
    """layers.latent_attention(q_lora_rank=None, rotate=False) over x
    [B, T, D] through the executor in float32 -> (out, the parameters in
    creation order, the op types built[, every parameter's gradient of
    the summed output])."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), fluid.program_guard(main, startup):
        var = fluid.layers.data(name="x", shape=list(x.shape),
                                dtype="float32", append_batch_size=False)
        out = fluid.layers.latent_attention(
            var, heads, None, kvr, nope, rope, vd, use_flash=use_flash,
            rotate=False)
        names = [p.name for p in main.global_block().all_parameters()]
        fetch = [out] + names
        if wrt_all:
            fluid.backward.append_backward(fluid.layers.reduce_sum(out))
            fetch += [grad_var_name(n) for n in names]
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        got = [np.asarray(v) for v in exe.run(main, feed={"x": x},
                                               fetch_list=fetch)]
    types = [op.type for op in main.global_block().ops]
    n = len(names)
    return got[0], got[1:1 + n], types, got[1 + n:]


def test_latent_attention_with_a_direct_query_and_no_rotation():
    """Against the equations head by head at 24 (16 | 8) beside 16: five
    parameters (no query latent, no query norm), no rotary op, and under
    128 lanes only the values are widened, to the keys' 24."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((1, 32, 48)).astype(np.float32)
    out, params, types, _ = latent_layer(x, False, heads=3, nope=16, rope=8,
                                         vd=16, kvr=20)
    assert [p.shape for p in params] == [(48, 72), (48, 28), (20,),
                                         (20, 96), (48, 48)]
    assert "rotary_embedding" not in types
    assert types.count("pad") == 1          # v alone: q and k are 24 wide
    close(out[0], nope_mla(x[0].astype(np.float64),
                           *(p.astype(np.float64) for p in params),
                           heads=3, nope=16, rope=8, vd=16), tol=1e-5)


def test_kernels_at_keys_192_beside_values_128_against_einsum():
    """The published head: q and k 192 (128 | 64) wide, v 128, handed to
    the op with zero lanes up to 256. The interpreted flash kernels take
    it (a hit is booked, no fallback) and agree with the einsum path on
    the output and on every parameter's gradient; and the einsum path
    agrees with the equations at the unpadded widths."""
    rng = np.random.default_rng(12)
    x = rng.standard_normal((1, 256, 64)).astype(np.float32)
    sizes = dict(heads=2, nope=128, rope=64, vd=128, kvr=32)

    def hits():
        counters = telemetry.snapshot()["counters"]
        return sum(counters.get("pallas_kernel_total", {}).values()), \
            sum(counters.get("pallas_fallback_total", {}).values())

    before = hits()
    flash, params, types, flash_grads = latent_layer(x, True, wrt_all=True,
                                                     **sizes)
    after = hits()
    assert after[0] - before[0] == 1 and after[1] == before[1]
    assert types.count("pad") == 3 and "scale" in types
    plain, plain_params, _, plain_grads = latent_layer(x, False, wrt_all=True,
                                                       **sizes)
    for a, b in zip(params, plain_params):
        np.testing.assert_array_equal(a, b)
    close(flash, plain, tol=2e-5)
    for g, g_ref in zip(flash_grads, plain_grads):
        close(g, g_ref, tol=2e-4)
    sizes.pop("kvr")
    close(plain[0], nope_mla(x[0].astype(np.float64),
                             *(p.astype(np.float64) for p in params),
                             **sizes), tol=1e-5)


# --- 4. the shares -----------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """16 gated experts top-4 under a sigmoid router renormalised over
    the chosen and scaled by 2.446, in four shares of four (a rank of the
    cell holds a thirty-second; the arithmetic is the same): what the
    shares give, with the shared expert counted once, is the uncut layer
    written from the equations; each share routes its own pairs only."""
    rng = np.random.default_rng(9)
    n, d, f, k, scaling = 48, 16, 24, 4, 2.446
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

    parts = [share(offset, 4) for offset in range(0, 16, 4)]
    shared = gated(x, s_g, s_u, s_d)
    assert sum(p["RowsRouted"][0] for p in parts) == n * k
    s = jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(w_r))
    top, ref_idx = jax.lax.top_k(s, k)
    ref_weight = scaling * top / top.sum(-1, keepdims=True)
    want = shared + sum(
        (ref_weight * (ref_idx == e)).sum(-1, keepdims=True)
        * gated(x, gate[e], up[e], down[e]) for e in range(16))
    close(sum(p["Out"] for p in parts) + shared, want, tol=1e-5)
    close(share(0, 16)["Out"] + shared, want, tol=1e-5)
    assert np.asarray(weight).sum(-1) == pytest.approx(scaling, rel=1e-5)


@pytest.mark.parametrize("held,small", [(8, (4096, 8192)),
                                        (16, (8192, 16384)),
                                        (32, (16384,))],
                         ids=["a_thirty_second", "a_sixteenth", "an_eighth"])
def test_the_ladder_has_two_rungs_at_a_thirty_second(held, small):
    """8 held of 256 at 8192 tokens and top 8: the capacity ladder gives
    4096 | 8192 | 65536 pairs since PR 69 (two rungs before it: the name
    is the parent's), and a uniform router's 2048 rows take the first,
    twice their count; a share of 16 has twice and four times its 4096
    rows, and one of 32 (the gated window cell's eighth) twice its 8192
    alone: a quarter of the pairs, and four times would be half."""
    ladder = hybrid_ops._capacity_ladder(8192 * 8, held, 256)
    assert ladder == small + (65536,)
    uniform = 8192 * 8 * held // 256
    assert [c // uniform for c in small] == [2, 4][:len(small)]


# --- 5. what asks for none of it ---------------------------------------------

PARENT_PROGRAMS = {
    # sha256 of main.to_json() | startup.to_json() at PR 54's tree
    # (the programs with expert layers: at PR 58's, whose moe_experts ops
    # write Up / GateUp for an explicit gradient op; the two with Mamba
    # mixers at PR 60's, whose causal_conv1d ops carry `time_on_lanes`
    # and an explicit gradient op)
    "glm-4.7-flash": ("c6c56c119b5b8e5c", "57465f9570324186"),
    "nemotron3-nano-30b-a3b": ("6ac48d33c64fc359", "2cd691daa316a1c1"),
    "granite-4.0-h-micro": ("cbaa1f07490aec02", "aef2a12bd424d1b5"),
}


@pytest.mark.parametrize("name", PARENT_PROGRAMS)
def test_a_program_that_asks_for_none_of_it_is_the_one_it_was(name):
    """mla_moe_lm builds latent attention with a latent query, a
    rotation and equal widths; nemotron_h_lm and granite_hybrid_lm build
    mamba2_mixer, whose convolution carries its Bias: no op of their
    programs carries a pad or a scale the new arguments would add, and
    the serialised programs equal the parent's (PR 54), by their
    hashes."""
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    for op in main.global_block().ops:
        assert op.type != "pad" and op.type != "kda_scan"
        if op.type == "causal_conv1d":
            assert op.desc.inputs.get("Bias")
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]
