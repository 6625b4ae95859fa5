"""The hybrid Mamba-2 / mixture-of-experts / grouped-query-attention ops
(ops/hybrid_ops.py), their layers and models.nemotron_h_lm, at tiny sizes
on the CPU: each op through the executor against a plain form written
here (the scan against the step-by-step recurrence), forward and
gradient; the share test of the expert layer; the whole tiny model
against benchmarks/families/nemotron_h.py::reference_loss; and the tables
that must know every new op."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework.framework import grad_var_name
from paddle_tpu.layer_helper import LayerHelper

from benchmarks import reference_check, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark", "data")
NEW_OPS = ("rms_norm", "causal_conv1d", "ssd_scan", "moe_router",
           "moe_experts", "relu2")


def run_op(op_type, inputs, outputs, attrs, wrt=()):
    """One `op_type` op over data vars through the executor. `inputs`
    {slot: array}; `outputs` {slot: dtype}. The loss is the sum of the
    first output times a fixed random cotangent. -> ({slot: array},
    {slot of wrt: gradient}, cotangent)."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        return _run_op(main, op_type, inputs, outputs, attrs, wrt)


def _run_op(main, op_type, inputs, outputs, attrs, wrt):
    helper = LayerHelper(op_type)
    vars_ = {}
    for slot, value in inputs.items():
        var = fluid.layers.data(name=slot.lower(), shape=list(value.shape),
                                dtype=str(value.dtype),
                                append_batch_size=False)
        var.stop_gradient = var.desc.stop_gradient = slot not in wrt
        vars_[slot] = var
    outs = {slot: helper.create_tmp_variable(dtype)
            for slot, dtype in outputs.items()}
    helper.append_op(type=op_type,
                     inputs={s: [v] for s, v in vars_.items()},
                     outputs={s: [v] for s, v in outs.items()}, attrs=attrs)
    first = next(iter(outs))
    feed = {s.lower(): v for s, v in inputs.items()}
    fetch = list(outs.values())
    cot = None
    if wrt:
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            shape = np.asarray(exe.run(main, feed=feed, fetch_list=[outs[first]])[0]
                               ).shape
        cot = np.random.default_rng(1).standard_normal(shape).astype(
            np.float32)
        r = fluid.layers.data(name="cot", shape=list(shape), dtype="float32",
                              append_batch_size=False)
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(outs[first], r))
        fluid.backward.append_backward(loss)
        feed["cot"] = cot
        fetch += [grad_var_name(vars_[s].name) for s in wrt]
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        got = [np.asarray(v) for v in exe.run(main, feed=feed,
                                               fetch_list=fetch)]
    return (dict(zip(outs, got)), dict(zip(wrt, got[len(outs):])), cot)


def close(got, want, tol=2e-5):
    want = np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * scale


# --- the scan ----------------------------------------------------------------

def recurrence(x, dt_raw, dt_bias, a_log, b, c, skip):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t b_t^T, y_t = h_t c_t + D x_t,
    one step at a time."""
    heads, groups = x.shape[2], b.shape[2]
    bh = jnp.repeat(b, heads // groups, axis=2)
    ch = jnp.repeat(c, heads // groups, axis=2)
    dt = jax.nn.softplus(dt_raw + dt_bias)
    decay = jnp.exp(dt * -jnp.exp(a_log))

    def step(h, inp):
        x_t, dt_t, a_t, b_t, c_t = inp
        h = a_t[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[3:])
    _, y = jax.lax.scan(step, h0, tuple(
        v.swapaxes(0, 1) for v in (x, dt, decay, bh, ch)))
    return y.swapaxes(0, 1) + skip[:, None] * x


@pytest.mark.parametrize("seqlen,chunk", [(37, 16), (64, 16), (96, 32),
                                          (24, 32)])
def test_ssd_scan_matches_the_recurrence(seqlen, chunk):
    rng = np.random.default_rng(seqlen)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    ins = {"X": f(2, seqlen, 4, 8), "Dt": f(2, seqlen, 4), "DtBias": f(4),
           "ALog": f(4), "B": f(2, seqlen, 2, 16), "C": f(2, seqlen, 2, 16),
           "D": f(4)}
    wrt = tuple(ins)
    outs, grads, cot = run_op("ssd_scan", ins, {"Out": "float32"},
                              {"chunk_size": chunk}, wrt)
    order = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")
    want, want_grads = jax.value_and_grad(
        lambda *a: (recurrence(*a) * cot).sum(), argnums=range(7))(
            *(jnp.asarray(ins[s]) for s in order))
    close(outs["Out"], recurrence(*(jnp.asarray(ins[s]) for s in order)))
    for slot, g in zip(order, want_grads):
        close(grads[slot], g, tol=1e-4)


def test_scan_backward_keeps_no_per_step_state():
    """The gradient's residuals are per chunk, not per step: nothing of
    [T, H, P, N] is held."""
    from paddle_tpu.ops.hybrid_ops import ssd_scan_chunked
    t, h, p, n = 256, 4, 8, 16
    args = (jnp.zeros((1, t, h, p)), jnp.ones((1, t, h)), -jnp.ones(h),
            jnp.zeros((1, t, 2, n)), jnp.zeros((1, t, 2, n)))
    from jax._src.ad_checkpoint import saved_residuals
    saved = saved_residuals(
        lambda *a: jax.checkpoint(
            lambda *b: ssd_scan_chunked(*b, 32))(*a).sum(), *args)
    assert max(int(np.prod(aval.shape)) for aval, _ in saved) < t * h * p * n


# --- the scan on its kernels (ops/pallas_scan.py), interpreted -----------------

SCAN_ORDER = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")


def scan_inputs(rng, bsz, seqlen, heads, groups, p=64, n=128, a_log=None):
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    # dt = softplus(Dt + DtBias) about 0.05, as the published
    # initialisation leaves it ([0.001, 0.1]): at dt about 1 a chunk of
    # 128 steps sums log-decays to -1000 and float32 rounds the mask's
    # exponents to 1e-4, in either implementation
    return {"X": f(bsz, seqlen, heads, p), "Dt": f(bsz, seqlen, heads),
            "DtBias": f(heads) * 0.5 - 3,
            "ALog": f(heads) if a_log is None else np.float32(a_log),
            "B": f(bsz, seqlen, groups, n) * 0.3,
            "C": f(bsz, seqlen, groups, n) * 0.3, "D": f(heads)}


def scan_lowering(chunk, amp=None):
    """The op's lowering as the executor calls it, over SCAN_ORDER."""
    import types
    from paddle_tpu.ops import hybrid_ops

    def out(*arrays):
        return hybrid_ops._ssd_scan(
            types.SimpleNamespace(amp_dtype=amp), _Attrs(chunk_size=chunk),
            {s: [a] for s, a in zip(SCAN_ORDER, arrays)})["Out"][0]
    return out


def chunked_form(x, dt_raw, dt_bias, a_log, b, c, skip, chunk=128):
    from paddle_tpu.ops.hybrid_ops import ssd_scan_chunked
    y = ssd_scan_chunked(x, jax.nn.softplus(dt_raw + dt_bias),
                         -jnp.exp(a_log), b, c, chunk)
    return y + skip[:, None] * x


# (batch, T, heads, groups, AMP dtype, ALog or None for random)
SCAN_KERNEL_CASES = {
    "one_chunk": (1, 128, 2, 1, None, None),
    "many_chunks": (2, 384, 2, 1, None, None),
    "several_groups": (1, 256, 4, 2, None, None),
    "ragged_tail": (1, 200, 4, 2, None, None),
    "shorter_than_a_chunk": (1, 72, 2, 1, None, None),
    "bf16": (1, 256, 4, 2, "bfloat16", None),
    "bf16_ragged_tail": (2, 200, 2, 1, "bfloat16", None),
    # a = -e^4: a step keeps e^-3 and a chunk's dead triangle overflows
    # (exp(350), selected away); a = -e^-9: 512 steps keep 0.997 and the
    # mask is all ones
    "both_ends_of_the_exp": (1, 512, 2, 1, None, (4.0, -9.0)),
}


@pytest.mark.parametrize("case", SCAN_KERNEL_CASES)
def test_scan_kernels_match_the_chunked_form_and_the_recurrence(case):
    """The kernel path (the shape tiles: P 64, N 128, chunk 128) through
    the op's lowering: Out and the gradients to all seven inputs against
    ssd_scan_chunked in float32 and against the step-by-step recurrence;
    bf16 operands at the tiny model's O2 tolerance."""
    from paddle_tpu.ops import hybrid_ops
    bsz, seqlen, heads, groups, amp, a_log = SCAN_KERNEL_CASES[case]
    assert hybrid_ops.ssd_scan_ineligible(128, heads // groups, 64, 128) \
        is None
    rng = np.random.default_rng(seqlen + heads)
    ins = scan_inputs(rng, bsz, seqlen, heads, groups, a_log=a_log)
    args = [jnp.asarray(ins[s]) for s in SCAN_ORDER]
    cot = jnp.asarray(rng.standard_normal(ins["X"].shape), jnp.float32)

    def value_and_grads(fn):
        out = fn(*args)
        return out, jax.grad(lambda *a: (fn(*a) * cot).sum(),
                             argnums=range(7))(*args)

    got, got_grads = value_and_grads(scan_lowering(128, amp))
    assert got.dtype == jnp.float32
    # float32: the chunked form's own distance from the recurrence, which
    # ALog's gradient sets (2.6e-4 in the last case, the kernels' 4e-6)
    tol = 5e-4 if amp is None else 0.03
    for form in (chunked_form, recurrence):
        want, want_grads = value_and_grads(form)
        close(got, want, tol=tol)
        for slot, g, w in zip(SCAN_ORDER, got_grads, want_grads):
            assert g.shape == w.shape, slot
            close(g, w, tol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_kernels_give_a_padded_tail_zero_gradients(dtype):
    """Steps with dt = 0 behind the sequence (log-decay 0, x, B and C
    zero) whose output nobody reads: every gradient row of the tail is
    exactly zero, so the padding adds nothing to a gradient."""
    from paddle_tpu.ops import pallas_scan
    rng = np.random.default_rng(5)
    t, live, heads, groups, p, n = 256, 150, 4, 2, 64, 128
    tail = (np.arange(t) >= live)

    def f(*shape):
        v = rng.standard_normal((1, t) + shape).astype(np.float32)
        v[:, tail] = 0
        return v
    x, b, c, dy = f(heads, p), f(groups, n), f(groups, n), f(heads, p)
    dt = np.abs(f(heads))
    _, vjp = jax.vjp(
        lambda *a: pallas_scan._scan(*a, 128, heads // groups, True),
        jnp.asarray(x, dtype),
        jnp.asarray(dt), jnp.asarray(-1.5 * dt), jnp.asarray(b, dtype),
        jnp.asarray(c, dtype))
    for grad in vjp(jnp.asarray(dy)):
        assert np.abs(np.asarray(grad, np.float32)[:, :live]).max() > 0
        assert not np.asarray(grad, np.float32)[:, live:].any()


def test_the_scan_gate_is_a_function_of_shapes_and_names_its_reasons():
    from paddle_tpu.ops import hybrid_ops, kernel_choice
    gate = {  # (chunk, heads a group, P, N)
        (128, 8, 64, 128): None, (256, 8, 64, 128): None,
        (128, 2, 64, 256): None, (128, 16, 32, 128): None,
        (128, 1, 64, 128): None, (128, 8, 128, 128): None,
        (32, 2, 8, 16): "chunk", (64, 8, 64, 128): "chunk",
        (128, 8, 64, 16): "state", (128, 8, 64, 192): "state",
        (128, 8, 8, 128): "heads",        # half a packed bf16 row
        (128, 8, 40, 128): "heads",
        (128, 160, 64, 128): None,        # 20 head blocks of 8 (PR 49)
        (256, 64, 64, 128): None,         # one group of 64: 8 head blocks
    }
    for shape, reason in gate.items():
        assert hybrid_ops.ssd_scan_ineligible(*shape) == reason, shape
    assert {r for r in gate.values() if r} \
        == kernel_choice.REASONS["ssd_scan"]


def _scan_counts():
    from paddle_tpu import telemetry
    return (dict(telemetry.read_series("pallas_kernel_total")),
            dict(telemetry.read_series("pallas_fallback_total")))


def test_a_scan_that_does_not_tile_keeps_the_chunked_form_and_says_why():
    """chunk 64 at widths that would tile: booked as a fallback with the
    gate's reason, no hit, and the numbers of ssd_scan_chunked."""
    ins = scan_inputs(np.random.default_rng(9), 1, 128, 2, 1)
    before = _scan_counts()
    outs, grads, cot = run_op("ssd_scan", ins, {"Out": "float32"},
                              {"chunk_size": 64}, SCAN_ORDER)
    hits, falls = _scan_counts()
    key = "op=ssd_scan,reason=chunk"
    assert falls[key] > before[1].get(key, 0)
    assert hits.get("op=ssd_scan", 0) == before[0].get("op=ssd_scan", 0)
    args = [jnp.asarray(ins[s]) for s in SCAN_ORDER]
    close(outs["Out"], chunked_form(*args, chunk=64), tol=1e-6)
    want = jax.grad(lambda *a: (chunked_form(*a, chunk=64) * cot).sum(),
                    argnums=range(7))(*args)
    for slot, g in zip(SCAN_ORDER, want):
        close(grads[slot], g, tol=1e-5)


def test_the_scans_gradient_op_books_no_second_hit():
    """A program with the gradient op books as many lowerings on the
    kernels as the same program without it (generic_grad_lower traces
    the forward again inside kernel_choice.retrace()), and the kernels'
    gradient through the executor is the recurrence's."""
    ins = scan_inputs(np.random.default_rng(11), 1, 128, 2, 1)

    def hits_of(wrt):
        before = _scan_counts()
        out = run_op("ssd_scan", ins, {"Out": "float32"},
                     {"chunk_size": 128}, wrt)
        after = _scan_counts()
        assert after[1] == before[1]                 # and no fallback
        return after[0]["op=ssd_scan"] - before[0].get("op=ssd_scan", 0), out

    forward_only, _ = hits_of(())
    assert forward_only >= 1
    # run_op runs the forward program (for Out's shape), then the one
    # with the gradient ops
    both, (outs, grads, cot) = hits_of(SCAN_ORDER)
    assert both == 2 * forward_only
    args = [jnp.asarray(ins[s]) for s in SCAN_ORDER]
    close(outs["Out"], recurrence(*args), tol=2e-4)
    want = jax.grad(lambda *a: (recurrence(*a) * cot).sum(),
                    argnums=range(7))(*args)
    for slot, g in zip(SCAN_ORDER, want):
        close(grads[slot], g, tol=2e-4)


# --- conv, norm --------------------------------------------------------------

def test_causal_conv1d():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 19, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    b = rng.standard_normal(6).astype(np.float32)

    def plain(x, w, b):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        out = b + sum(padded[:, j:j + 19] * w[:, j] for j in range(4))
        return jax.nn.silu(out)

    outs, grads, cot = run_op(
        "causal_conv1d", {"X": x, "Filter": w, "Bias": b},
        {"Out": "float32"}, {}, ("X", "Filter", "Bias"))
    close(outs["Out"], plain(x, w, b))
    # position t reads nothing after t
    later = x.copy()
    later[:, 10:] += 1.0
    np.testing.assert_array_equal(np.asarray(plain(later, w, b))[:, :10],
                                  np.asarray(plain(x, w, b))[:, :10])
    want = jax.grad(lambda *a: (plain(*a) * cot).sum(), argnums=(0, 1, 2))(
        x, w, b)
    for slot, g in zip(("X", "Filter", "Bias"), want):
        close(grads[slot], g, tol=1e-4)


@pytest.mark.parametrize("groups,gated", [(1, False), (4, True), (2, False)])
def test_rms_norm_gated_and_grouped(groups, gated):
    rng = np.random.default_rng(groups)
    x = rng.standard_normal((3, 5, 16)).astype(np.float32)
    w = rng.standard_normal(16).astype(np.float32)
    z = rng.standard_normal((3, 5, 16)).astype(np.float32)

    def plain(x, w, z):
        h = x * jax.nn.silu(z) if gated else x
        g = h.reshape(3, 5, groups, -1)
        g = g / jnp.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
        return g.reshape(3, 5, 16) * w

    ins = {"X": x, "Scale": w}
    if gated:
        ins["Gate"] = z
    outs, grads, cot = run_op("rms_norm", ins, {"Out": "float32"},
                              {"epsilon": 1e-5, "groups": groups},
                              tuple(ins))
    close(outs["Out"], plain(x, w, z))
    want = jax.grad(lambda *a: (plain(*a) * cot).sum(), argnums=(0, 1, 2))(
        x, w, z)
    for slot, g in zip(("X", "Scale", "Gate"), want):
        if slot in ins:
            close(grads[slot], g, tol=1e-4)


# --- router ------------------------------------------------------------------

def test_router_choice_normalisation_and_scaling():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((40, 12)).astype(np.float32)
    w = rng.standard_normal((12, 16)).astype(np.float32)
    bias = np.zeros(16, np.float32)
    bias[3] = 10.0             # expert 3 is always chosen ...
    attrs = {"top_k": 3, "scaling": 2.5, "norm_topk_prob": True}
    outs, grads, cot = run_op(
        "moe_router", {"X": x, "W": w, "Bias": bias},
        {"TopkWeight": "float32", "TopkIdx": "int32"}, attrs, ("X", "W"))
    s = 1.0 / (1.0 + np.exp(-(x @ w)))
    want_idx = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.sort(outs["TopkIdx"], -1),
                                  np.sort(want_idx, -1))
    assert (outs["TopkIdx"] == 3).any(-1).all()
    # ... but weighs in with its score, not its score plus the bias
    chosen = np.take_along_axis(s, outs["TopkIdx"], -1)
    close(outs["TopkWeight"], 2.5 * chosen / chosen.sum(-1, keepdims=True))
    close(outs["TopkWeight"].sum(-1), np.full(40, 2.5))

    def plain(x, w):
        s = jax.nn.sigmoid(x @ w)
        c = jnp.take_along_axis(s, jnp.asarray(outs["TopkIdx"]), -1)
        return 2.5 * c / (c.sum(-1, keepdims=True) + 1e-20)

    want = jax.grad(lambda *a: (plain(*a) * cot).sum(), argnums=(0, 1))(x, w)
    close(grads["X"], want[0], tol=1e-4)
    close(grads["W"], want[1], tol=1e-4)
    unnormed, _, _ = run_op(
        "moe_router", {"X": x, "W": w, "Bias": bias},
        {"TopkWeight": "float32", "TopkIdx": "int32"},
        dict(attrs, norm_topk_prob=False, scaling=1.0))
    close(unnormed["TopkWeight"],
          np.take_along_axis(s, unnormed["TopkIdx"], -1))


# --- the dropless expert layer -----------------------------------------------

def expert_loop(x, idx, weight, w1, w2, offset=0):
    """sum over the held experts of (the token's weight for that expert)
    x relu(x W1[e])^2 W2[e], every token through every held expert."""
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        mine = (weight * (idx == offset + e)).sum(-1, keepdims=True)
        out = out + mine * (jnp.maximum(x @ w1[e], 0.0) ** 2 @ w2[e])
    return out


def routed(rng, n, k, experts, favour=None):
    """(idx [n, k] of distinct experts, weight [n, k]); `favour`: experts
    that draw most of the tokens."""
    scores = rng.random((n, experts))
    if favour is not None:
        scores[:, favour] += rng.random((n, len(favour))) * 3.0
    idx = np.argsort(-scores, -1)[:, :k].astype(np.int32)
    return idx, rng.random((n, k)).astype(np.float32) + 0.1


@pytest.mark.parametrize("n,d,f,path", [(64, 128, 128, "gmm"),
                                        (50, 24, 40, "rows"),
                                        (64, 24, 40, "width")])
def test_experts_drop_no_row_under_a_skewed_router(n, d, f, path):
    """Held experts 4..7 of 16, two of them drawing most tokens: the
    layer against a loop over the experts, forward and gradient; every
    routed pair counted; the grouped product on the kernel where it
    tiles, booked with the reason where not."""
    from paddle_tpu import telemetry
    from paddle_tpu.ops import hybrid_ops
    rng = np.random.default_rng(n + d)
    k, held, offset = 4, 4, 4
    x = rng.standard_normal((n, d)).astype(np.float32) * 0.5
    w1 = rng.standard_normal((held, d, f)).astype(np.float32) * 0.2
    w2 = rng.standard_normal((held, f, d)).astype(np.float32) * 0.2
    idx, weight = routed(rng, n, k, 16, favour=[5, 6])
    reason = hybrid_ops.gmm_ineligible(n * k, d, f)
    assert (reason or "gmm") == path
    before = dict(telemetry.read_series("pallas_kernel_total")), \
        dict(telemetry.read_series("pallas_fallback_total"))
    ins = {"X": x, "TopkIdx": idx, "TopkWeight": weight, "W1": w1, "W2": w2}
    outs, grads, cot = run_op(
        "moe_experts", ins,
        {"Out": "float32", "RowsRouted": "float32",
         "RowsCombined": "float32", "LoadMaxOverMean": "float32",
         "Up": "float32"},
        {"num_experts": 16, "experts_held": held, "expert_offset": offset,
         "top_k": k}, ("X", "TopkWeight", "W1", "W2"))
    close(outs["Out"], expert_loop(x, idx, weight, w1, w2, offset), tol=1e-4)
    counts = np.array([(idx == offset + e).sum() for e in range(held)])
    assert outs["RowsRouted"][0] == outs["RowsCombined"][0] \
        == counts.sum()                                  # no row lost
    assert counts.sum() > 0.4 * n * k                    # and it is skewed
    close(outs["LoadMaxOverMean"], [counts.max() / counts.mean()])
    want = jax.grad(lambda x, wt, a, b: (expert_loop(
        x, idx, wt, a, b, offset) * cot).sum(), argnums=(0, 1, 2, 3))(
            x, weight, w1, w2)
    for slot, g in zip(("X", "TopkWeight", "W1", "W2"), want):
        close(grads[slot], g, tol=2e-4)
    hits = telemetry.read_series("pallas_kernel_total")
    falls = telemetry.read_series("pallas_fallback_total")
    key = "op=moe_experts"
    if reason is None:
        assert hits[key] > before[0].get(key, 0)
    else:
        key = f"op=moe_experts,reason={reason}"
        assert falls[key] > before[1].get(key, 0)


def test_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: what the four shares give, with the
    shared expert counted once, is the uncut layer."""
    rng = np.random.default_rng(7)
    n, d, f, k = 48, 16, 24, 3
    x = rng.standard_normal((n, d)).astype(np.float32)
    w1 = rng.standard_normal((16, d, f)).astype(np.float32) * 0.3
    w2 = rng.standard_normal((16, f, d)).astype(np.float32) * 0.3
    s1 = rng.standard_normal((d, 2 * f)).astype(np.float32) * 0.3
    s2 = rng.standard_normal((2 * f, d)).astype(np.float32) * 0.3
    idx, weight = routed(rng, n, k, 16)
    shared = np.maximum(x @ s1, 0.0) ** 2 @ s2

    def share(offset, held):
        outs, _, _ = run_op(
            "moe_experts",
            {"X": x, "TopkIdx": idx, "TopkWeight": weight,
             "W1": w1[offset:offset + held], "W2": w2[offset:offset + held]},
            {"Out": "float32", "RowsRouted": "float32",
             "RowsCombined": "float32", "LoadMaxOverMean": "float32"},
            {"num_experts": 16, "experts_held": held,
             "expert_offset": offset, "top_k": k})
        return outs

    parts = [share(offset, 4) for offset in (0, 4, 8, 12)]
    whole = share(0, 16)
    close(sum(p["Out"] for p in parts) + shared, whole["Out"] + shared,
          tol=1e-5)
    close(whole["Out"] + shared,
          expert_loop(x, idx, weight, w1, w2) + shared, tol=1e-4)
    assert sum(p["RowsRouted"][0] for p in parts) == n * k \
        == whole["RowsRouted"][0]


# --- the capacity ladder -----------------------------------------------------

HELD, OFFSET, EXPERTS = 2, 4, 32


def routed_by_hand(rng, n, k, held_pairs, held=HELD):
    """(idx [n, k], weight [n, k]) with exactly `held_pairs` of the n * k
    (token, slot) pairs on the held experts OFFSET .. OFFSET + held - 1,
    scattered over tokens and slots, every other pair on an absent one."""
    absent = np.setdiff1d(np.arange(EXPERTS), OFFSET + np.arange(held))
    flat = rng.choice(absent, n * k)
    here = rng.permutation(n * k)[:held_pairs]
    flat[here] = OFFSET + rng.integers(0, held, held_pairs)
    return flat.reshape(n, k).astype(np.int32), \
        rng.random((n, k)).astype(np.float32) + 0.1


def experts_inputs(rng, n, d, f, k, held_pairs, gated, held=HELD,
                   experts=EXPERTS):
    """moe_experts' inputs for `held` of `experts` experts over `n`
    tokens: `held_pairs` pairs routed to the held ones by hand (`held`
    of EXPERTS from OFFSET on), or with None a random router's choice of
    `experts`."""
    ins = {"X": rng.standard_normal((n, d)).astype(np.float32) * 0.5}
    ins["TopkIdx"], ins["TopkWeight"] = routed(rng, n, k, experts) \
        if held_pairs is None else routed_by_hand(rng, n, k, held_pairs, held)
    for slot, shape in (("WGate", (held, d, f)), ("W1", (held, d, f)),
                        ("W2", (held, f, d))):
        if gated or slot != "WGate":
            ins[slot] = rng.standard_normal(shape).astype(np.float32) * 0.2
    return ins


def run_experts(ins, wrt=(), offset=OFFSET, experts=EXPERTS):
    """One moe_experts op over `ins`, holding W1's experts from `offset`
    on. -> run_op's triple."""
    return run_op(
        "moe_experts", ins,
        dict.fromkeys(("Out", "RowsRouted", "RowsCombined",
                       "LoadMaxOverMean", "RowsHandled", "Up")
                      + (("GateUp",) if "WGate" in ins else ()), "float32"),
        {"num_experts": experts, "experts_held": ins["W1"].shape[0],
         "expert_offset": offset, "top_k": ins["TopkIdx"].shape[1]},
        tuple(s for s in wrt if s in ins))


def experts_op(rng, n, d, f, k, held_pairs, gated, wrt=(), held=HELD):
    """One moe_experts op holding `held` of EXPERTS experts over `n`
    tokens with `held_pairs` pairs routed to them. -> run_op's triple."""
    return run_experts(experts_inputs(rng, n, d, f, k, held_pairs, gated,
                                      held), wrt)


LADDERS = {
    # a sixteenth of the experts or less: twice the uniform share, then
    # four times it (the first rung of before PR 69), then every pair
    "hybrid_cell": ((24576, 8, 128), (3072, 6144, 24576)),
    "a_sixty_fourth": ((24576, 2, 128), (768, 1536, 24576)),
    "a_sixteenth": ((16384, 4, 64), (2048, 4096, 16384)),
    "block_diffusion_cell": ((65536, 8, 128), (8192, 16384, 65536)),
    "delta_rule_cell": ((65536, 8, 256), (4096, 8192, 65536)),
    "head_decay_delta_rule_cell": ((163840, 32, 512),
                                   (20480, 40960, 163840)),
    "between_a_thirty_second_and_a_sixteenth": ((65536, 6, 128),
                                                (8192, 16384, 65536)),
    "ragged_dot": ((240, 2, 32), (30, 60, 240)),
    # twice the share for an eighth, a quarter of the pairs: four times
    # it is half of them and falls away
    "latent_cell": ((16384, 8, 64), (4096, 16384)),
    "gated_window_cell": ((65536, 32, 256), (16384, 65536)),
    "sliding_window_cell": ((49152, 8, 64), (12288, 49152)),
    "an_eighth_on_ragged_dot": ((240, 4, 32), (60, 240)),
    "between_a_sixteenth_and_an_eighth": ((65536, 12, 128), (16384, 65536)),
    # more than an eighth: the one rung
    "a_quarter": ((16384, 16, 64), (16384,)),
    "over_an_eighth": ((240, 5, 32), (240,)),
    "all_held": ((24576, 128, 128), (24576,)),
    # the rung tiles as the pairs do: where twice the share (64 of 512
    # at a sixteenth) would not and four times it does, the halving
    # stops there and the two are one rung, as before PR 69
    "kernel": ((512, 2, 32), (128, 512)),
    "64_would_not_tile": ((512, 1, 64), (128, 512)),
    "a_sixteenth_on_the_kernel": ((1024, 4, 64), (128, 256, 1024)),
    "125_has_no_half": ((250, 1, 128), (250,)),
}


@pytest.mark.parametrize("case", LADDERS)
def test_the_ladder_is_a_function_of_shapes_and_the_share(case):
    from paddle_tpu.ops.hybrid_ops import _capacity_ladder, gmm_ineligible
    (pairs, held, experts), rungs = LADDERS[case]
    assert _capacity_ladder(pairs, held, experts) == rungs
    assert rungs[-1] == pairs and len(rungs) <= 3   # no pair is dropped
    assert all(4 * c <= pairs for c in rungs[:-1])
    assert list(rungs) == sorted(set(rungs))
    # every rung holds twice a uniform router's share, or every pair
    assert rungs[0] * experts >= min(2 * held, experts) * pairs
    assert len({gmm_ineligible(c, 2688, 1856) for c in rungs}) == 1


# 60 tokens x 4 slots, 2 of 32 experts held (a sixteenth: rungs 30, 60,
# 240) or 4 (an eighth: 60, 240) on lax.ragged_dot; 128 x 4 at a lane
# block's widths: 128, 512 on the kernel for both (64 would not tile;
# the kernel's three rungs, 128 | 256 | 1024, are GATHER_CASES' and
# tests/test_pair_sum.py's)
LADDER_CASES = [(60, 24, 40, r) for r in (0, 29, 30, 31, 59, 60, 61, 240)] \
    + [(128, 128, 128, r) for r in (100, 129)]
LADDER_RUNGS = {(60, HELD): (30, 60, 240), (60, 4): (60, 240),
                (128, HELD): (128, 512), (128, 4): (128, 512)}


@pytest.mark.parametrize("held", [HELD, 4], ids=["a_sixteenth", "an_eighth"])
@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("n,d,f,held_pairs", LADDER_CASES)
def test_a_rung_gives_what_the_whole_layer_gives(n, d, f, held_pairs, gated,
                                                 held, monkeypatch):
    """Nothing routed, one under a rung, exactly a rung, one over it (the
    next rung taken by overflow: the second of three, or the full one)
    and every pair, under twice the share, between twice and four times
    it and over that: Out and every gradient of each rung equal those of
    the layer with the full size as its only rung and no switch, and no
    routed row is lost."""
    from paddle_tpu.ops import hybrid_ops
    wrt = ("X", "TopkWeight", "WGate", "W1", "W2")
    seed = 1000 * n + held_pairs
    outs, grads, _ = experts_op(np.random.default_rng(seed), n, d, f, 4,
                                held_pairs, gated, wrt, held)
    rungs = hybrid_ops._capacity_ladder(n * 4, held, EXPERTS)
    assert rungs == LADDER_RUNGS[n, held]
    assert outs["RowsRouted"][0] == outs["RowsCombined"][0] == held_pairs
    assert outs["RowsHandled"][0] == min(c for c in rungs if c >= held_pairs)
    monkeypatch.setattr(hybrid_ops, "_capacity_ladder",
                        lambda pairs, held, experts: (pairs,))
    whole, whole_grads, _ = experts_op(np.random.default_rng(seed), n, d, f,
                                       4, held_pairs, gated, wrt, held)
    assert whole["RowsHandled"][0] == n * 4
    assert whole["RowsCombined"][0] == held_pairs
    close(outs["Out"], whole["Out"], tol=1e-6)
    assert set(grads) == set(wrt) - (set() if gated else {"WGate"})
    for slot in grads:
        close(grads[slot], whole_grads[slot], tol=1e-6)
    if not held_pairs:
        assert not outs["Out"].any() and not grads["W1"].any()


class _Attrs:
    def __init__(self, **attrs):
        self.attrs = attrs

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


@pytest.mark.parametrize("held,rungs", [(EXPERTS, 1), (EXPERTS // 4, 1),
                                        (EXPERTS // 8, 2), (2, 3), (1, 3)])
def test_a_layer_that_holds_every_expert_has_no_conditional(held, rungs):
    """One rung (every expert held, or more than an eighth): the lowering
    and the gradient op's hold no `cond`; an eighth's forward (two rungs)
    or a sixteenth's (three) holds one and its gradient op one, its own,
    of a branch a rung: it reads the forward's kept products and traces
    no forward."""
    import types
    from paddle_tpu.ops import hybrid_ops
    rng = np.random.default_rng(held)
    n, d, f, k = 60, 24, 40, 4
    idx, weight = routed(rng, n, k, EXPERTS)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w1 = rng.standard_normal((held, d, f)).astype(np.float32)
    w2 = rng.standard_normal((held, f, d)).astype(np.float32)
    op_ = _Attrs(num_experts=EXPERTS, experts_held=held, expert_offset=0,
                 top_k=k)
    op_.desc = types.SimpleNamespace(outputs=dict.fromkeys(
        s + "@GRAD" for s in ("X", "TopkWeight", "W1", "W2")))
    ctx = types.SimpleNamespace(amp_dtype=None)

    def ins(x, weight, w1, w2):
        return {"X": [x], "TopkIdx": [idx], "TopkWeight": [weight],
                "W1": [w1], "W2": [w2]}

    def out(*operands):
        return hybrid_ops._moe_experts(ctx, op_, ins(*operands))

    def grads(up, ct, *operands):
        return hybrid_ops._moe_experts_grad(
            ctx, op_, dict(ins(*operands), **{"Up": [up], "Out@GRAD": [ct]}))

    assert len(hybrid_ops._capacity_ladder(n * k, held, EXPERTS)) == rungs
    conds = int(rungs > 1)
    forward = jax.make_jaxpr(out)(x, weight, w1, w2)
    assert str(forward).count("cond[") == conds
    outs = out(x, weight, w1, w2)
    assert outs["Up"][0].shape == (n * k, f) and "GateUp" not in outs
    pulled = jax.make_jaxpr(grads)(outs["Up"][0], outs["Out"][0], x, weight,
                                   w1, w2)
    assert str(pulled).count("cond[") == conds
    # a rung: the forward's two products; in the gradient op the two
    # pulled back to the rows and the two to the matrices, and no
    # forward one
    assert str(forward).count("ragged_dot_general[") == 2 * rungs
    assert str(pulled).count("ragged_dot_general[") == 4 * rungs


@pytest.mark.parametrize("held_pairs", [0, 1, 29, 30, 31, 59, 60, 61, 239,
                                        240])
def test_rows_handled_is_the_smallest_rung_that_holds_the_routed(held_pairs):
    outs, _, _ = experts_op(np.random.default_rng(held_pairs), 60, 24, 40, 4,
                            held_pairs, gated=False)
    assert outs["RowsRouted"][0] == outs["RowsCombined"][0] == held_pairs
    assert outs["RowsHandled"][0] == min(
        c for c in (30, 60, 240) if c >= held_pairs)


# --- rows back to their tokens ------------------------------------------------

def scatter_add_layer(x, idx, weight, w1, w2, gate, offset):
    """The layer as the op wrote it before its rows went back by a gather
    (PR 38): the pairs sorted by held expert, the tokens' rows gathered in
    that order, one ragged product over the held experts' rows, and the
    weighted rows added into a zero-filled [N, D] at their tokens. Its
    gradients are autodiff's: a scatter-add for each gather."""
    held, k = w1.shape[0], idx.shape[1]
    local = idx.reshape(-1) - offset
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    token = order // k
    live = (jnp.arange(order.shape[0]) < sizes.sum())[:, None]
    rows = jnp.where(live, x[token], 0)
    up = jax.lax.ragged_dot(rows, w1, sizes)
    if gate is None:
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.silu(jax.lax.ragged_dot(rows, gate, sizes)) * up
    out = jnp.where(live, jax.lax.ragged_dot(h, w2, sizes), 0) \
        * weight.reshape(-1)[order][:, None]
    return jnp.zeros_like(x).at[token].add(out)


# name: tokens, d, f, experts, held, offset, pairs routed here (None: a
# random router's), gated, rungs, why the kernel does not take the product
GATHER_CASES = {
    "every_expert_held": (64, 128, 128, 8, 8, 0, None, True, 1, None),
    "absent_first_and_last": (64, 128, 128, 16, 4, 6, None, False, 1, None),
    "small_rung_taken": (128, 128, 128, 32, 2, 4, 100, True, 2, None),
    "full_rung_taken": (128, 128, 128, 32, 2, 4, 300, False, 2, None),
    "rows_do_not_tile": (50, 24, 40, 16, 4, 6, None, True, 1, "rows"),
    # a sixteenth on ragged_dot: 30 | 60 | 240
    "rows_do_not_tile_first_rung": (60, 24, 40, 32, 2, 4, 25, True, 3,
                                    "rows"),
    "rows_do_not_tile_small_rung": (60, 24, 40, 32, 2, 4, 40, False, 3,
                                    "rows"),
    "rows_do_not_tile_full_rung": (60, 24, 40, 32, 2, 4, 61, True, 3, "rows"),
    "nothing_routed_here": (60, 24, 40, 32, 2, 4, 0, True, 3, "rows"),
    # a sixteenth on the kernel: 128 | 256 | 1024
    "first_of_three_rungs_taken": (256, 128, 128, 32, 2, 4, 120, True, 3,
                                   None),
    # an eighth held: twice the uniform share is the small rung
    "an_eighth_small_rung_taken": (128, 128, 128, 32, 4, 4, 120, True, 2,
                                   None),
    "an_eighth_full_rung_taken": (128, 128, 128, 32, 4, 4, 129, False, 2,
                                  None),
    "an_eighth_rows_do_not_tile_full_rung": (60, 24, 40, 32, 4, 4, 100, True,
                                             2, "rows"),
    "a_quarter_has_one_rung": (60, 24, 40, 32, 8, 4, 100, True, 1, "rows"),
}


@pytest.mark.parametrize("case", GATHER_CASES)
def test_rows_return_by_gather_as_by_the_scatter_add(case):
    """moe_experts' Out and its gradients to X, TopkWeight, W1, W2 and
    WGate against the scatter-add form written here, and a token none of
    whose experts is held gets exactly nothing, forward and pulled back."""
    from paddle_tpu.ops import hybrid_ops
    n, d, f, experts, held, offset, held_pairs, gated, n_rungs, reason = \
        GATHER_CASES[case]
    assert held_pairs is None or (offset, experts) == (OFFSET, EXPERTS)
    k = 4
    ins = experts_inputs(np.random.default_rng(len(case) + n), n, d, f, k,
                         held_pairs, gated, held, experts)
    wrt = tuple(s for s in ("X", "TopkWeight", "W1", "W2", "WGate")
                if s in ins)
    outs, grads, cot = run_experts(ins, wrt, offset, experts)

    here = (ins["TopkIdx"] >= offset) & (ins["TopkIdx"] < offset + held)
    rungs = hybrid_ops._capacity_ladder(n * k, held, experts)
    assert outs["RowsRouted"][0] == outs["RowsCombined"][0] == here.sum()
    assert outs["RowsHandled"][0] == min(c for c in rungs if c >= here.sum())
    assert (len(rungs), hybrid_ops.gmm_ineligible(n * k, d, f)) == \
        (n_rungs, reason)
    if "rung_taken" in case:
        assert (outs["RowsHandled"][0] == n * k) == ("full" in case)

    def plain(x, weight, w1, w2, gate=None):
        return scatter_add_layer(x, ins["TopkIdx"], weight, w1, w2, gate,
                                 offset)

    operands = [ins[s] for s in wrt]
    close(outs["Out"], plain(*operands), tol=1e-4)
    want = jax.grad(lambda *a: (plain(*a) * cot).sum(),
                    argnums=tuple(range(len(wrt))))(*operands)
    for slot, g in zip(wrt, want):
        close(grads[slot], g, tol=2e-4)
    elsewhere = ~here.any(-1)
    assert elsewhere.any() == (case != "every_expert_held")
    assert not outs["Out"][elsewhere].any()
    assert not grads["X"][elsewhere].any()
    assert not grads["TopkWeight"][~here].any()


@pytest.mark.parametrize("width,held", [(32, 16), (32, 1), (128, 16),
                                        (128, 1)],
                         ids=["ragged", "ragged_ladder", "gmm", "gmm_ladder"])
def test_no_scatter_is_left_in_an_expert_layers_step(width, held):
    """The compiled train step of one moe_block, every expert held (one
    rung) and a sixteenth (two, under a conditional): nothing lowered from
    moe_experts or its gradient is a scatter, but for the few integers of
    group metadata that megablox's own wrapper writes before its kernel
    (`jit(gmm)` / `jit(tgmm)` scopes: s32[tiles + groups])."""
    from paddle_tpu import xplane
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2, 32, width],
                              dtype="float32", append_batch_size=False)
        h = fluid.layers.moe_block(x, num_experts=16, top_k=4,
                                   expert_width=width, shared_width=width,
                                   experts_held=held)
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        text = exe.compiled_hlo(
            main, feed={"x": np.ones((2, 32, width), np.float32)},
            fetch_list=[loss])
    mine = [i for i in xplane.hlo_instructions(text)
            if i.op in ("moe_experts", "moe_experts_grad")]
    assert {i.op for i in mine} == {"moe_experts", "moe_experts_grad"}
    assert {i.heavy for i in mine} >= {"sort", "dot"}
    left = [(i.name, i.shape, i.op_name) for i in mine
            if i.heavy == "scatter" and "jit(gmm)" not in i.op_name
            and "jit(tgmm)" not in i.op_name]
    assert not left, left


# --- grouped-query attention -------------------------------------------------

def gqa_plain(q, k, v):
    t, groups = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, 2), jnp.repeat(v, groups, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("use_flash", [False, True])
def test_grouped_query_attention(use_flash):
    """4 query heads over 2 K/V heads of 32: einsum and the flash kernels
    (interpreted) against the plain form, forward and gradient; the
    kernels' gate books the hit as for full attention."""
    from paddle_tpu import telemetry
    rng = np.random.default_rng(3)
    shapes = {"q": (2, 128, 4, 32), "k": (2, 128, 2, 32),
              "v": (2, 128, 2, 32)}
    feed = {n: rng.standard_normal(s).astype(np.float32)
            for n, s in shapes.items()}
    vars_ = {}
    for n, s in shapes.items():
        var = fluid.layers.data(name=n, shape=list(s), dtype="float32",
                                append_batch_size=False)
        var.stop_gradient = var.desc.stop_gradient = False
        vars_[n] = var
    out = fluid.layers.fused_attention(vars_["q"], vars_["k"], vars_["v"],
                                       causal=True, use_flash=use_flash)
    loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, out))
    fluid.backward.append_backward(loss)
    key = "op=scaled_dot_product_attention"
    before = telemetry.read_series("pallas_kernel_total").get(key, 0)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        got = exe.run(feed=feed, fetch_list=[out] + [
            grad_var_name(vars_[n].name) for n in "qkv"])
    after = telemetry.read_series("pallas_kernel_total").get(key, 0)
    assert after - before == (1 if use_flash else 0)
    args = [jnp.asarray(feed[n]) for n in "qkv"]
    close(got[0], gqa_plain(*args), tol=1e-4)
    want = jax.grad(lambda *a: (gqa_plain(*a) ** 2).sum(),
                    argnums=(0, 1, 2))(*args)
    for g, w in zip(got[1:], want):
        assert np.asarray(g).shape == w.shape
        close(g, w, tol=2e-4)


def test_kv_heads_must_divide_query_heads():
    from paddle_tpu.ops import nn_ops
    with pytest.raises(AssertionError):
        nn_ops._kv_groups(jnp.zeros((1, 8, 4, 8)), jnp.zeros((1, 8, 3, 8)))


# --- the whole tiny model against the reference ------------------------------

def first_step(amp_level, name="tiny-nemotron-h"):
    """reference_check.compare() of the first step of the tiny model
    `name` (tests/test_mla_moe.py reads its own through this)."""
    config = run.load_json("configs", name, DATA)
    family = run.load_module("families", config["family"])
    main, startup, loss = family.build(config)
    if amp_level is None:
        fluid.amp.disable(main)
    feed = family.make_batch(config, 2, np.random.default_rng(3))
    rule = reference_check.rule_of(config)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        scope.set_var("__rng_counter__", 12345)
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()
                 if p.trainable]
        params = [scope.find_var(n) for n in names]
        start = [np.asarray(p) for p in params]
        ref_loss, ref_grads = reference_check.reference_step(
            family, config, params, feed)
        out, = exe.run(main, feed=feed, fetch_list=[loss])
        slots = reference_check.state_names(main, rule)
        found = reference_check.compare(
            rule, config, names, start,
            [np.asarray(scope.find_var(n)) for n in names],
            {n: {s: np.asarray(scope.find_var(v))
                 for s, v in slots[n].items()} for n in names},
            float(np.ravel(out)[0]), ref_loss, ref_grads,
            dict.fromkeys(reference_check.HELD))
    return found, main, names


@pytest.mark.parametrize("amp_level,loss_tol,grad_tol", [
    (None, 1e-6, 1e-5), ("O2", 2e-4, 0.03)])
def test_tiny_model_against_the_reference(amp_level, loss_tol, grad_tol):
    """Loss, every gradient, its norm, the tail (the final norm's weight
    and the head) and one Adam step: float32 to rounding, O2 to bf16."""
    found, main, names = first_step(amp_level)
    assert len(names) == 38        # the router's bias is not among them
    assert found["loss_rel_diff"] <= loss_tol
    assert found["grad_rel_err"] <= grad_tol
    assert found["grad_tail_rel_err"] <= grad_tol
    assert found["grad_norm_rel_diff"] <= grad_tol
    assert found["update_rel_err"] <= 1e-3


def test_published_initialisation_of_the_scan_parameters():
    config = run.load_json("configs", "tiny-nemotron-h", DATA)
    family = run.load_module("families", config["family"])
    main, startup, _ = family.build(config)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        mixer = [np.asarray(scope.find_var(f"mamba2_mixer_0.w_{i}"))
                 for i in (2, 3, 4)]
    dt0 = np.log1p(np.exp(mixer[0]))                   # softplus(dt_bias)
    assert (dt0 >= 0.999e-3).all() and (dt0 <= 0.1001).all()
    a = np.exp(mixer[1])                               # -A
    assert (a >= 1.0).all() and (a <= 16.0).all() and a.std() > 0
    np.testing.assert_array_equal(mixer[2], 1.0)


def test_routing_statistics_reach_telemetry_by_layer():
    """Two expert layers: a sample a step and layer of the rows routed
    to held experts and of their imbalance, without a fetch by the user."""
    from paddle_tpu import telemetry
    config = run.load_json("configs", "tiny-nemotron-h", DATA)
    family = run.load_module("families", config["family"])
    main, startup, loss = family.build(config)
    feed = family.make_batch(config, 2, np.random.default_rng(0))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()     # a side-fetch still in flight is published here
    label = telemetry.program_label(main)
    tokens = feed["tok"].size
    for layer in ("0", "1"):
        rows = telemetry.read_histogram("moe_rows_routed", program=label,
                                        layer=layer)
        combined = telemetry.read_histogram("moe_rows_combined",
                                            program=label, layer=layer)
        load = telemetry.read_histogram("moe_load_max_over_mean",
                                        program=label, layer=layer)
        handled = telemetry.read_histogram("moe_rows_handled", program=label,
                                           layer=layer)
        assert rows["count"] == load["count"] == handled["count"] == 3
        assert combined == rows                # what went in came back
        # a quarter of the experts held: the one rung, every pair (3 slots
        # a token)
        assert rows["sum"] <= handled["sum"] == 3 * 3 * tokens
        # 4 of 16 experts held, 3 chosen of 16: about 3/4 of a row a token
        assert 0.3 * tokens < rows["sum"] / 3 < 1.5 * tokens
        assert 1.0 <= load["sum"] / 3 <= 4.0


@pytest.mark.parametrize("experts", [64, 32],
                         ids=["a_sixteenth", "an_eighth"])
def test_a_model_trains_the_same_with_the_ladder_as_with_one_rung(
        experts, monkeypatch):
    """The tiny model as a 1/16 share or an eighth (4 of 64 or of 32
    experts held: rungs 72, 144 and 576, or 144 and 576, of its 192
    tokens x 3 slots), three Adam steps in float32: the losses equal
    those of the same model with the full size as its only rung, and the
    steps took the first rung."""
    from paddle_tpu import telemetry
    from paddle_tpu.ops import hybrid_ops
    config = dict(run.load_json("configs", "tiny-nemotron-h", DATA),
                  n_routed_experts_published=experts)
    family = run.load_module("families", config["family"])
    feed = family.make_batch(config, 2, np.random.default_rng(0))

    def losses():
        main, startup, loss = family.build(config)
        fluid.amp.disable(main)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup)
            out = [float(np.ravel(exe.run(main, feed=feed,
                                          fetch_list=[loss])[0])[0])
                   for _ in range(3)]
            exe.close()
        return out, telemetry.read_histogram(
            "moe_rows_handled", program=telemetry.program_label(main),
            layer="0")

    rungs = hybrid_ops._capacity_ladder(feed["tok"].size * 3, 4, experts)
    assert rungs == {64: (72, 144, 576), 32: (144, 576)}[experts]
    with_ladder, handled = losses()
    monkeypatch.setattr(hybrid_ops, "_capacity_ladder",
                        lambda pairs, held, experts: (pairs,))
    one_rung, whole = losses()
    assert with_ladder[0] > with_ladder[-1]            # it trains
    np.testing.assert_allclose(with_ladder, one_rung, rtol=2e-6)
    assert handled == {"count": 3, "sum": 3.0 * rungs[0]}
    assert whole == {"count": 3, "sum": 3 * 576.0}


# --- a trace can book every op of a layer to it -----------------------------

def test_name_scope_reaches_the_ops_their_gradients_and_the_hlo():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        with fluid.name_scope("outer"):
            with fluid.name_scope("inner"):
                h = fluid.layers.fc(input=x, size=4, bias_attr=False)
            h = fluid.layers.tanh(h)
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    under = {op.type: op.desc.attrs.get("op_namescope")
             for op in main.global_block().ops}
    assert under["mul"] == under["mul_grad"] == "/outer/inner/"
    assert under["tanh"] == under["tanh_grad"] == "/outer/"
    assert under["mean"] is None and under["sgd"] is None
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        text = exe.compiled_hlo(main, feed={"x": np.ones((2, 8), np.float32)},
                                fetch_list=[loss])
    assert "pd_role.forward/pd_scope.outer.inner/pd.mul/" in text
    assert "pd_role.backward/pd_scope.outer.inner/pd.mul_grad/" in text
    assert "pd_role.forward/pd.mean/" in text


def test_the_two_layers_build_every_op_under_their_name(tiny_program):
    """What ssm_time_pct.train and moe_time_pct.train read: the mixer's
    and the shared expert's `mul`s and their gradients too."""
    main, _ = tiny_program
    under = {}
    for op in main.global_block().ops:
        under.setdefault(op.desc.attrs.get("op_namescope"), []).append(op.type)
    mixer, experts = under["/mamba2_mixer/"], under["/moe_block/"]
    for op_type, count in (("mul", 4), ("ssd_scan", 2), ("causal_conv1d", 2),
                           ("rms_norm", 2)):
        assert mixer.count(op_type) == mixer.count(op_type + "_grad") == count
    for op_type, count in (("mul", 4), ("moe_router", 2), ("moe_experts", 2),
                           ("relu2", 2)):
        assert experts.count(op_type) == experts.count(op_type + "_grad") \
            == count
    assert not {"ssd_scan", "moe_experts", "moe_router"} & set(under[None])


# --- no table forgets an op --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_program():
    config = run.load_json("configs", "tiny-nemotron-h", DATA)
    main, _, loss = run.load_module("families", config["family"]).build(config)
    return main, loss


@pytest.fixture(scope="module")
def o2_dtypes():
    """{new op type: (dtype of its X, dtype of its first float output)} of
    the tiny model's forward under AMP O2."""
    config = run.load_json("configs", "tiny-nemotron-h", DATA)
    family = run.load_module("families", config["family"])
    main, startup, _ = family.build(dict(config, amp_level="O2"))
    names = {}
    for op in main.global_block().ops:
        if op.type in NEW_OPS and op.type not in names:
            slot = "TopkWeight" if op.type == "moe_router" else "Out"
            names[op.type] = [op.input("X")[0], op.output(slot)[0]]
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        got = exe.run(main, feed=family.make_batch(
            config, 2, np.random.default_rng(0)),
            fetch_list=sum(names.values(), []), return_numpy=False)
    found = [str(v.dtype) for v in got]
    return dict(zip(names, zip(found[::2], found[1::2])))


@pytest.mark.parametrize("op_type", NEW_OPS)
def test_every_table_knows_the_op(op_type, tiny_program, o2_dtypes):
    """roofline.op_cost prices it from its shapes (not by the default of
    one flop an output element), under AMP O2 its output has its
    input's dtype, bfloat16 after a projection (the router's weights are
    float32; hybrid_ops' docstring says which arithmetic each op keeps in
    float32 inside), the static analyzer has a shape rule for it and its gradient, and the
    tiny model holds it."""
    from paddle_tpu import roofline
    from paddle_tpu.analysis import infer
    main, _ = tiny_program
    assert op_type in {op.type for op in main.global_block().ops}
    x_dtype, out_dtype = o2_dtypes[op_type]
    assert out_dtype == ("float32" if op_type == "moe_router" else x_dtype)
    assert "bfloat16" in {x for x, _ in o2_dtypes.values()}
    assert infer.rule_kind(op_type) == "registry"
    assert infer.rule_kind(op_type + "_grad") == "grad"
    assert op_type in roofline._HYBRID_COST or \
        op_type in roofline._ELEMWISE_COST

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    ins, outs, attrs = {
        "rms_norm": ({"X": [aval(6, 8)], "Scale": [aval(8)]},
                     {"Out": [aval(6, 8)]}, {}),
        "relu2": ({"X": [aval(6, 8)]}, {"Out": [aval(6, 8)]}, {}),
        "causal_conv1d": ({"X": [aval(2, 6, 8)], "Filter": [aval(8, 4)],
                           "Bias": [aval(8)]}, {"Out": [aval(2, 6, 8)]}, {}),
        "ssd_scan": ({"X": [aval(2, 64, 4, 8)], "B": [aval(2, 64, 2, 16)]},
                     {"Out": [aval(2, 64, 4, 8)]}, {"chunk_size": 32}),
        "moe_router": ({"X": [aval(10, 8)], "W": [aval(8, 16)]},
                       {"TopkWeight": [aval(10, 3)]}, {"top_k": 3}),
        "moe_experts": ({"X": [aval(10, 8)], "W1": [aval(4, 8, 12)]},
                        {"Out": [aval(10, 8)]},
                        {"top_k": 3, "experts_held": 4, "num_experts": 16}),
    }[op_type]
    want = {"rms_norm": 5.0 * (48 + 8), "relu2": 2.0 * 48,
            "causal_conv1d": (2 * 4 + 4) * 96.0,
            "ssd_scan": 2.0 * 128 * (32 * 32 + 32 * 32 + 2 * 32 * 16),
            "moe_router": 2.0 * 10 * 128,
            "moe_experts": 4.0 * 10 * 3 * 0.25 * 8 * 12}[op_type]
    flops, _ = roofline.op_cost(op_type, ins, outs, attrs)
    assert flops == pytest.approx(want)
    assert roofline.op_cost(op_type + "_grad", ins, outs, attrs)[0] == \
        pytest.approx(2 * want)


@pytest.mark.parametrize("chunk,heads,groups,p,n", [
    (32, 4, 2, 8, 16), (128, 4, 2, 64, 128), (128, 64, 8, 64, 128)],
    ids=["chunked_form", "kernels", "kernels_at_the_hybrid_cell"])
def test_the_scans_price_is_the_algorithms_not_the_implementations(
        chunk, heads, groups, p, n):
    """roofline.op_cost prices ssd_scan by the chunked form's four
    products from the op's shapes, the same whether ssd_scan_chunked or
    the Pallas kernels run it (what the kernels issue beyond that, masked
    lanes and the gradient's recompute, is time, not work)."""
    from paddle_tpu import roofline
    from paddle_tpu.ops import hybrid_ops
    tiles = hybrid_ops.ssd_scan_ineligible(chunk, heads // groups, p, n)
    assert (tiles is None) == (p == 64)
    tokens = 2 * 256
    x = jax.ShapeDtypeStruct((2, 256, heads, p), jnp.float32)
    b = jax.ShapeDtypeStruct((2, 256, groups, n), jnp.float32)
    want = 2.0 * tokens * (chunk * groups * n + chunk * heads * p
                           + 2 * heads * p * n)
    args = ({"X": [x], "B": [b]}, {"Out": [x]}, {"chunk_size": chunk})
    assert roofline.op_cost("ssd_scan", *args)[0] == pytest.approx(want)
    assert roofline.op_cost("ssd_scan_grad", *args)[0] == \
        pytest.approx(2 * want)


def test_preflight_of_the_tiny_model_is_clean(tiny_program):
    """Shapes, dataflow and preflight over the whole train program: no
    error, no pass that died, no op without a rule, and the side-fetched
    routing statistics are not dead code."""
    from paddle_tpu import analysis
    main, loss = tiny_program
    report = analysis.analyze_program(main, feeds=["tok", "lab"],
                                      fetches=[loss.name])
    bad = [d.format() for d in report.diagnostics
           if d.severity != "info"]
    assert not bad, bad
