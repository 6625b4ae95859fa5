"""The delta rule under a decay a HEAD and key heads beneath groups of
value heads (Gated DeltaNet, arXiv:2412.06464: the op kda_scan told by
its shapes, PR 64), at small sizes on the CPU: the op's statement
(hybrid_ops.kda_scan_chunked) and the kernels of ops/pallas_kda.py,
interpreted, against the recurrence a token at a time, forward and every
gradient, over chunk lengths, a ragged tail and group ratios; the gate
(kda_scan_ineligible) and what it books; the counters; and that what
asks for none of it is what it was: the channel form's two kernel calls
by the digest of their jaxprs, and the eleven accepted configurations'
programs by their hashes."""

import re
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import telemetry
from paddle_tpu.ops import hybrid_ops, kernel_choice, pallas_kda

from benchmarks import run
from test_nemotron_h import close, run_op

EPS = 1e-6
SLOTS = ("Q", "K", "V", "Gate", "ALog", "DtBias", "Beta")


def recurrence(q, k, v, gate, a_log, dt_bias, beta):
    """S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - (e^{g_t} S_{t-1})^T
    k_t))^T, o_t = S_t^T q_t a value head, one token at a time; value head
    j reads key head j // ratio; g a head (gate [B, T, H]) or a channel
    (gate [B, T, H, K]: the decay then multiplies the state's rows)."""
    ratio = v.shape[2] // q.shape[2]
    width = q.shape[3]

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + EPS)

    q, k = (jnp.repeat(unit(x), ratio, axis=2) for x in (q, k))
    q = q / np.sqrt(width)
    per_head = gate.ndim == 3
    g = -jnp.exp(a_log if per_head else a_log[:, None]) * jax.nn.softplus(
        gate + (dt_bias if per_head else dt_bias.reshape(-1, width)))
    if per_head:
        g = g[..., None]
    b = jax.nn.sigmoid(beta)

    def one(q_, k_, v_, g_, b_):
        def token(state, now):
            q_t, k_t, v_t, g_t, b_t = now
            state = state * jnp.exp(g_t)[..., None]
            seen = jnp.einsum("hk,hkv->hv", k_t, state)
            state = state + k_t[..., None] * (
                b_t[:, None] * (v_t - seen))[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state)
        zero = jnp.zeros(v_.shape[1:2] + (width, v_.shape[-1]))
        return jax.lax.scan(token, zero, (q_, k_, v_, g_, b_))[1]

    return jax.vmap(one)(q, k, v, g, b)


def operands(bsz, t, key_heads, heads, k, v, seed=0, per_head=True):
    """q, k, v, gate, beta ~ N(0, 1); A in U(1, 8) and dt_bias about -4,
    so that a sub-block of 16 tokens decays by far less than e^88."""
    rng = np.random.default_rng(seed)
    decays = (heads,) if per_head else (heads, k)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(bsz, t, key_heads, k), normal(bsz, t, key_heads, k),
            normal(bsz, t, heads, v), normal(bsz, t, *decays),
            jnp.asarray(np.log(rng.uniform(1, 8, heads)), jnp.float32),
            0.5 * normal(int(np.prod(decays))) - 4.0, normal(bsz, t, heads))


def value_and_grads(fn, args, seed=5):
    weight = jnp.asarray(np.random.default_rng(seed).standard_normal(
        args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * weight).sum(), argnums=tuple(range(7)))(
                *args)


# --- 1. the statement and the kernels against the recurrence ------------------

@pytest.mark.parametrize("key_heads,heads", [(2, 2), (1, 2), (1, 4)],
                         ids=["r1", "r2", "r4"])
@pytest.mark.parametrize("t,chunk", [(48, 16), (75, 32), (130, 64)],
                         ids=["whole_chunks", "ragged_32", "ragged_64"])
def test_statement_matches_the_recurrence(t, chunk, key_heads, heads):
    """kda_scan_chunked at K = V = 16 (the shapes tier-1's tiny models
    run on XLA's form), a decay a head and grouped key heads, forward and
    all seven gradients to 2e-5 of the largest entry."""
    args = operands(2, t, key_heads, heads, 16, 16, seed=t)
    want, want_grads = value_and_grads(recurrence, args)
    got, grads = value_and_grads(
        lambda *a: hybrid_ops.kda_scan_chunked(*a, chunk, EPS), args)
    assert abs(float(got - want)) <= 2e-5 * abs(float(want))
    for g, g_ref in zip(grads, want_grads):
        assert g.shape == g_ref.shape
        close(g, g_ref, tol=1e-4)


@pytest.mark.parametrize("key_heads,heads,t,chunk", [
    (2, 4, 160, 32), (4, 4, 128, 64), (1, 4, 90, 16), (2, 4, 100, 64)],
    ids=["r2_chunk32", "r1_chunk64", "r4_ragged16", "r2_ragged64"])
def test_kernels_match_the_recurrence(key_heads, heads, t, chunk):
    """The head-decay kernels (`gdn_scan_fwd` / `gdn_scan_bwd`),
    interpreted, at K = V = 128: o and every gradient against the
    recurrence; dq and dk come back at the KEY heads' count, summed over
    each group inside the backward walk."""
    args = operands(1, t, key_heads, heads, 128, 128, seed=chunk + t)
    assert hybrid_ops.kda_scan_ineligible(chunk, 128, 128,
                                          heads // key_heads, True) is None
    want, want_grads = value_and_grads(recurrence, args)
    got, grads = value_and_grads(
        lambda *a: pallas_kda.kda_scan_kernels(*a, chunk, EPS,
                                               interpret=True), args)
    assert abs(float(got - want)) <= 2e-5 * abs(float(want))
    for g, g_ref in zip(grads, want_grads):
        assert g.shape == g_ref.shape
        close(g, g_ref, tol=1e-4)


def test_kernels_match_the_statement_under_bf16_operands():
    """Both paths take the same operands at the same precision: with bf16
    q, k, v and products the kernels and XLA's form agree to bf16's
    rounding, and each with float32 to a few times it."""
    args = operands(1, 128, 2, 4, 128, 128, seed=3)
    low = tuple(x.astype(jnp.bfloat16) if x.ndim >= 3 else x for x in args)
    exact = hybrid_ops.kda_scan_chunked(*args, 64, EPS)
    kernels = pallas_kda.kda_scan_kernels(
        *low, 64, EPS, dtype=jnp.bfloat16, interpret=True)
    statement = hybrid_ops.kda_scan_chunked(*low, 64, EPS,
                                            dtype=jnp.bfloat16)
    assert kernels.dtype == statement.dtype == jnp.bfloat16
    close(kernels.astype(jnp.float32), exact, tol=3e-2)
    close(statement.astype(jnp.float32), exact, tol=3e-2)
    close(kernels.astype(jnp.float32), statement.astype(jnp.float32),
          tol=3e-2)


def test_a_heads_decay_is_the_channel_form_with_the_gate_broadcast():
    """ROADMAP Reach 13 (b)'s way to the numbers, which the op no longer
    needs: Gate broadcast over a head's channels and q, k repeated to
    the value heads, through the CHANNEL form's kernels, gives the
    head-decay form's output."""
    q, k, v, gate, a_log, dt_bias, beta = operands(1, 128, 2, 4, 128, 128)
    direct = pallas_kda.kda_scan_kernels(q, k, v, gate, a_log, dt_bias, beta,
                                         64, EPS, interpret=True)
    widened = pallas_kda.kda_scan_kernels(
        jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
        jnp.broadcast_to(gate[..., None], v.shape), a_log,
        jnp.repeat(dt_bias, 128), beta, 64, EPS, interpret=True)
    close(direct, widened, tol=2e-5)


# --- 2. the op through the executor: shapes choose the form -------------------

def kda_op(args, chunk, wrt=()):
    return run_op("kda_scan", dict(zip(SLOTS, map(np.asarray, args))),
                  {"Out": "float32"},
                  {"chunk_size": chunk, "epsilon": EPS}, wrt=wrt)


def counted(name):
    series = telemetry.snapshot()["counters"].get(name, {})
    return {k: v for k, v in series.items() if v}


@pytest.mark.parametrize("width,path", [(16, "chunked"), (128, "kernel")])
def test_the_op_takes_the_form_from_its_shapes(width, path):
    """Gate [B, T, H] and Q at half of V's heads: the op runs the
    head-decay form on the path its gate gives, books kda_scan_total as
    ever and kda_scan_head_decay_total{path, groups} beside it, and its
    generic gradient gives all seven inputs theirs."""
    telemetry.reset()
    args = operands(1, 64, 2, 4, width, width, seed=1)
    outs, grads, cot = kda_op(args, 32, wrt=SLOTS)
    want = recurrence(*args)
    close(outs["Out"], want, tol=2e-5)
    _, vjp = jax.vjp(recurrence, *args)
    for slot, g_ref in zip(SLOTS, vjp(jnp.asarray(cot))):
        close(grads[slot], g_ref, tol=1e-4)
    # run_op lowers the forward twice (once for the output's shape)
    total = counted("kda_scan_total")
    assert all(f"path={path}" in k for k in total) and total
    decays = counted("kda_scan_head_decay_total")
    assert list(decays.values()) == [sum(total.values())]
    assert f"path={path}" in next(iter(decays)) \
        and "groups=2" in next(iter(decays))
    booked = counted("pallas_kernel_total" if path == "kernel"
                     else "pallas_fallback_total")
    assert any("op=kda_scan" in k for k in booked)


def test_a_channel_decay_books_no_head_decay():
    telemetry.reset()
    args = operands(1, 32, 2, 2, 16, 16, per_head=False)
    outs, _, _ = kda_op(args, 16)
    close(outs["Out"], recurrence(*args), tol=2e-5)
    assert counted("kda_scan_total")
    assert not counted("kda_scan_head_decay_total")


# --- 3. the gate --------------------------------------------------------------

@pytest.mark.parametrize("case,reason", [
    ((64, 128, 128), None), ((64, 128, 128, 1, False), None),
    ((64, 128, 128, 2, True), None), ((64, 128, 256, 8, True), None),
    ((64, 128, 128, 2, False), "group"), ((64, 128, 128, 16, True), "group"),
    ((64, 64, 128, 2, True), "width"), ((48, 128, 128, 2, True), "chunk")])
def test_the_gates_cases(case, reason):
    """Groups of up to eight value heads under a decay a head; grouped
    key heads under a decay a channel keep XLA's form (no model has
    them, and the channel form's calls are held to what they were); the
    width and the chunk as before, and ahead of the group."""
    assert hybrid_ops.kda_scan_ineligible(*case) == reason
    assert reason is None or reason in kernel_choice.REASONS["kda_scan"]


@pytest.mark.parametrize("heads,chunk,itemsize,ratio,want", [
    (32, 64, 2, 1, 8), (32, 64, 2, 2, 8), (32, 64, 4, 2, 4),
    (32, 128, 2, 4, 4), (12, 64, 2, 4, 4), (32, 128, 4, 8, 8)])
def test_a_step_owns_whole_groups(heads, chunk, itemsize, ratio, want):
    assert pallas_kda.heads_a_step(heads, chunk, itemsize, ratio) == want
    assert want % ratio == 0 and heads % want == 0


# --- 4. what asks for none of it ----------------------------------------------

def call_digest(call, shape, chunk, r, dtype):
    """The first 16 hex digits of sha256 over the jaxpr of one of the
    CHANNEL form's kernel calls and its BlockSpecs' index maps (a
    pallas_call prints its specs without them)."""
    bsz, t, h, kd, vd = shape
    wide = jax.ShapeDtypeStruct((bsz, t, h * kd), dtype)
    tall = jax.ShapeDtypeStruct((bsz, t, h * vd), dtype)
    args = [wide, wide, tall, wide,
            jax.ShapeDtypeStruct((bsz, h // r, t, r), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((h * kd,), jnp.float32)]
    if call == "_backward":
        pack = pallas_kda._packed(chunk, r)
        args += [tall, jax.ShapeDtypeStruct(
            (bsz, t // chunk, h, vd, kd), jnp.float32),
            jax.ShapeDtypeStruct(
                (bsz, t // chunk, h // pack, chunk, pack * chunk),
                jnp.float32)]
    static = dict(heads=h, chunk=chunk, r=r, eps=1e-6,
                  dtype=jnp.dtype(dtype), interpret=False)
    fn = getattr(pallas_kda, call)
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **static))(*args)
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


KIMI = (1, 8192, 32, 128, 128)
# (call, [B, T, H, K, V], chunk, heads a step, dtype) of the Kimi-Linear
# cell's two kernel calls and of a float32 pair two heads a pack, with
# their digests at the parent commit (PR 63: `git archive a357cdb`, the
# same function run there)
PARENTS_CALLS = [
    ("_forward", KIMI, 64, 8, jnp.bfloat16, "a73a12ca04d42ae1"),
    ("_backward", KIMI, 64, 8, jnp.bfloat16, "35226cf8d0fdeda5"),
    ("_forward", (1, 256, 4, 128, 128), 32, 4, jnp.float32,
     "866b96a10f2f5f30"),
    ("_backward", (1, 256, 4, 128, 128), 32, 4, jnp.float32,
     "985f63adee6f9cb3"),
]


@pytest.mark.parametrize("call,shape,chunk,r,dtype,digest", PARENTS_CALLS)
def test_the_channel_forms_calls_are_the_parents(call, shape, chunk, r,
                                                 dtype, digest):
    """`kda_scan_fwd` / `kda_scan_bwd` lower to the jaxprs they lowered
    to before the head-decay form shared their kernel bodies: the
    Kimi-Linear cell's Mosaic calls are the parent's."""
    assert call_digest(call, shape, chunk, r, dtype) == digest


# (main, startup) of the eleven accepted language configurations and the
# ResNet as the parent commit (PR 63) built them: the first 16 hex digits
# of sha256 over Program.to_json(). rms_norm, rotary_embedding, moe_block
# and kda_mixer without the new arguments build the ops of before
# (Kimi-Linear's main program as PR 65 builds it: its four kda_scan ops
# write Inverse and Entering beside Out, hybrid_ops.kda_scan_outputs).
PARENT_PROGRAMS = {
    "glm-4.7-flash": ("c6c56c119b5b8e5c", "57465f9570324186"),
    "gpt2-large": ("90b85e99fedb4110", "0abfed69b2161959"),
    "gpt2": ("32530ba784525f48", "6cae3670f852b823"),
    "granite-4.0-h-micro": ("cbaa1f07490aec02", "aef2a12bd424d1b5"),
    "kimi-linear-48b-a3b-instruct": ("2c1c4f892d9bd452", "f56ab18f9f796b0c"),
    "laguna-xs.2": ("d9e2dffcce8f0f43", "2bfd552d47366495"),
    "lfm2-24b-a2b": ("d27c6e5344c2f8dc", "816e981734df8260"),
    "nemotron3-nano-30b-a3b": ("6ac48d33c64fc359", "2cd691daa316a1c1"),
    "ouro-2.6b": ("c032844e8897b75c", "fc8a5054f91ff386"),
    "resnet50": ("794c807653f3c470", "729fcfb275892b3b"),
    "sdar-30b-a3b-chat": ("ae3fbd7051acd413", "d87f315e59443b2d"),
    "smallthinker-21b-a3b-instruct": ("dc6827508c9e3acb", "33caad39b72bf4c9"),
}
NEW_ATTRS = {"unit_offset", "gate_behind", "rotate_first"}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_program_that_asks_for_none_of_it_is_the_one_it_was(name):
    config = run.load_json("configs", name)
    main, startup, _ = run.load_module("families", config["family"]).build(
        config)
    for op in main.global_block().ops:
        assert not NEW_ATTRS & set(op.desc.attrs), op.type
    assert tuple(hashlib.sha256(p.to_json().encode()).hexdigest()[:16]
                 for p in (main, startup)) == PARENT_PROGRAMS[name]
