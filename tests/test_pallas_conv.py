"""The int8 conv kernel (ops/pallas_conv.py: the one conv kernel since PR
45) and the conv route (PR 25): the kernel's parity, the tiling gate's
reason labels, every float conv and its backward as XLA's convolution
whatever the shape, and the CPU scan+grad-conv warning.

conv2d_q8 is held to an integer lax.conv_general_dilated on the same
int8 operands with the same dequantization scales: int32 accumulation
is exact in any order, so the two differ by the float32 product's last
bit at most. On CPU the kernel runs under Pallas interpret mode, so this
whole file is tier-1 under JAX_PLATFORMS=cpu and re-runs compiled on a
real TPU unchanged.
"""

import collections
import glob
import os
import types
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import executor as em
from paddle_tpu import telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import layout as layout_mod
from paddle_tpu.ops import kernel_choice, pallas_conv, registry


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _series(name, label=None):
    s = telemetry.read_series(name)
    if label is None:
        return sum(s.values())
    return sum(v for k, v in s.items() if label in k)


# --- direct-kernel parity ----------------------------------------------

# (H, W, KH, KW, strides, paddings, dilations), C fixed at one 128 lane
# tile. Covers stride, asymmetric spatial dims, 1x1, dilation+padding,
# and mixed per-dim stride/padding.
CASES = [
    (6, 6, 3, 3, (1, 1), (1, 1), (1, 1)),
    (9, 9, 3, 3, (2, 2), (1, 1), (1, 1)),
    (8, 8, 1, 1, (1, 1), (0, 0), (1, 1)),
    (10, 10, 3, 3, (1, 1), (2, 2), (2, 2)),
    (7, 9, 2, 3, (2, 1), (1, 2), (1, 1)),
]


def _operands(h, w, kh, kw, n=2, c=128, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.bfloat16)
    wt = jnp.asarray(rng.standard_normal((c, c, kh, kw)) * 0.1,
                     jnp.bfloat16)
    return x, wt


def _ref_fwd(x, wt, s, p, d):
    """f32 lax.conv on the same bf16-rounded operands: the explicit
    backward's target."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), wt.astype(jnp.float32),
        window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d, dimension_numbers=("NHWC", "OIHW", "NHWC"))


def q8_operands(h, w, kh, kw, n=2, c=128, seed=0):
    """int8 x [N, H, W, C], int8 w [C, C, KH, KW] over the full range and
    a per-channel dequantization vector of uneven scales."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (n, h, w, c)), jnp.int8)
    wt = jnp.asarray(rng.integers(-127, 128, (c, c, kh, kw)), jnp.int8)
    dq = jnp.asarray(rng.uniform(0.5, 2.0, c) * 1e-4, jnp.float32)
    return x, wt, dq


def q8_reference(x, wt, dq, s, p, d):
    """The plain form of conv2d_q8: XLA's conv on the int8 operands,
    accumulated in int32, times the same scales in float32."""
    acc = jax.lax.conv_general_dilated(
        x, wt, window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d, dimension_numbers=("NHWC", "OIHW", "NHWC"),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * dq


@pytest.mark.parametrize("case", CASES)
def test_forward_parity(case):
    """The gate passes the geometry (asked, as the O3 route asks, with
    the bf16 operands before they are quantized) and the kernel equals
    the integer reference: stride, uneven spatial dims, 1x1, dilation
    with padding, per-dim stride and padding."""
    h, w, kh, kw, s, p, d = case
    assert pallas_conv.ineligible(*_operands(h, w, kh, kw), s, p, d) is None
    x, wt, dq = q8_operands(h, w, kh, kw)
    y = pallas_conv.conv2d_q8(x, wt, s, p, d, dq, out_dtype=jnp.float32)
    ref = q8_reference(x, wt, dq, s, p, d)
    assert y.shape == ref.shape and np.abs(np.asarray(ref)).max() > 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-6, atol=0)


# --- the eligibility gate ----------------------------------------------

def test_ineligible_reasons():
    x = jnp.zeros((2, 6, 6, 128), jnp.bfloat16)
    w = jnp.zeros((128, 128, 3, 3), jnp.bfloat16)
    args = ((1, 1), (1, 1), (1, 1))
    assert pallas_conv.ineligible(x, w, *args) is None
    assert pallas_conv.ineligible(x[0], w, *args) == "rank"
    assert pallas_conv.ineligible(x, w, *args, groups=2) == "groups"
    assert pallas_conv.ineligible(
        x.astype(jnp.float32), w, *args) == "dtype"
    assert pallas_conv.ineligible(
        x[..., :120], w[:, :120], *args) == "channels"
    # padding beyond (K-1)*d: the deleted grad-input kernel's bound,
    # kept so that the route is what it was
    assert pallas_conv.ineligible(
        x, w, (1, 1), (5, 5), (1, 1)) == "geometry"
    # output collapses to zero rows
    assert pallas_conv.ineligible(
        x, w, (1, 1), (0, 0), (4, 4)) == "geometry"
    # Paddle's legal 4-element [top, bottom, left, right] paddings: the
    # gate must label the fallback, not crash unpacking — these programs
    # ran on the lax path before the suite existed
    assert pallas_conv.ineligible(
        x, w, (1, 1), [1, 1, 1, 1], (1, 1)) == "attrs"
    # padded width beyond the VMEM row budget falls back instead of
    # failing Mosaic compilation at run time
    wide = jax.ShapeDtypeStruct((1, 6, 4096, 128), jnp.bfloat16)
    assert pallas_conv.ineligible(wide, w, *args) == "geometry"
    assert kernel_choice.REASONS["conv2d"] == {
        "mesh", "rank", "groups", "dtype", "channels", "attrs", "geometry"}


def _grad_op(outputs=("Input@GRAD", "Filter@GRAD"), s=(1, 1), p=(1, 1),
             d=(1, 1)):
    from paddle_tpu.framework.desc import OpDesc
    from paddle_tpu.framework.framework import Operator

    op_ = Operator.__new__(Operator)
    op_.block = None
    op_.desc = OpDesc(
        type="conv2d_grad",
        inputs={"Input": ["x"], "Filter": ["w"], "Output": ["y"],
                "Output@GRAD": ["y@GRAD"]},
        outputs={o: [o.replace("Input", "x").replace("Filter", "w")]
                 for o in outputs},
        attrs={"strides": list(s), "paddings": list(p),
               "dilations": list(d), "groups": 1})
    return op_


def test_zero_cotangent_returns_zeros():
    """Output@GRAD absent (conv output unused by the loss): the grad
    lowering emits explicit zero grads in the forward vars' shapes and
    dtypes, and books nothing."""
    x, wt = _operands(6, 6, 3, 3)
    outs = registry.get("conv2d_grad").lower(
        None, _grad_op(),
        {"Input": [x], "Filter": [wt], "Output@GRAD": [None]})
    dx, = outs["Input@GRAD"]
    dw, = outs["Filter@GRAD"]
    assert dx.shape == x.shape and dx.dtype == x.dtype
    assert dw.shape == wt.shape and dw.dtype == wt.dtype
    assert not np.asarray(dx, np.float32).any()
    assert not np.asarray(dw, np.float32).any()
    assert _series("pallas_kernel_total") == 0
    assert _series("pallas_fallback_total") == 0


@pytest.mark.parametrize("nhwc", [True, False], ids=["nhwc", "nchw"])
@pytest.mark.parametrize("case", CASES)
def test_explicit_backward_matches_float32_autodiff(case, nhwc):
    """conv2d_grad under AMP O2 (bf16 operands, f32 master weights) is
    the two transposes of the lax conv: within bf16 tolerance of
    jax.grad of the plain float32 conv, in Input's layout and dtype and
    canonical OIHW for the filter, for every geometry of CASES with the
    layout convention on (NHWC-tagged Input and cotangent) and off."""
    h, w, kh, kw, s, p, d = case
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, h, w, 128)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((128, 128, kh, kw)) * 0.1,
                     jnp.float32)
    ref, vjp = jax.vjp(lambda a, b: _ref_fwd(a, b, s, p, d), x, wt)
    ct = jnp.asarray(rng.standard_normal(ref.shape), jnp.bfloat16)
    dx_ref, dw_ref = vjp(ct.astype(jnp.float32))

    ctx = types.SimpleNamespace(
        amp_dtype="bfloat16", amp_level="O2",
        layout_of=lambda name: layout_mod.NHWC if nhwc else None)
    to_layout = (lambda a: a) if nhwc else \
        (lambda a: jnp.transpose(a, (0, 3, 1, 2)))
    outs = registry.get("conv2d_grad").lower(
        ctx, _grad_op(s=s, p=p, d=d),
        {"Input": [to_layout(x)], "Filter": [wt],
         "Output@GRAD": [to_layout(ct)]})
    dx, = outs["Input@GRAD"]
    dw, = outs["Filter@GRAD"]
    assert dx.dtype == jnp.float32 and dw.dtype == jnp.float32
    assert dx.shape == to_layout(x).shape and dw.shape == wt.shape
    for got, want in ((dx, to_layout(dx_ref)), (dw, dw_ref)):
        err = np.linalg.norm(np.asarray(got - want)) / \
            np.linalg.norm(np.asarray(want))
        assert err < 1e-2, err
    # only the slots the desc asks for are computed
    only_w = registry.get("conv2d_grad").lower(
        ctx, _grad_op(outputs=("Filter@GRAD",), s=s, p=p, d=d),
        {"Input": [to_layout(x)], "Filter": [wt],
         "Output@GRAD": [to_layout(ct)]})
    assert list(only_w) == ["Filter@GRAD"]
    np.testing.assert_array_equal(np.asarray(only_w["Filter@GRAD"][0]),
                                  np.asarray(dw))


def test_no_switch_selects_a_conv_route():
    """PADDLE_TPU_PALLAS_CONV is read nowhere, and the module attribute
    the benchmark's CPU test still patches exists and changes nothing."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sources = glob.glob(os.path.join(root, "paddle_tpu", "**", "*.py"),
                        recursive=True)
    sources += glob.glob(os.path.join(root, "*.py"))
    sources += glob.glob(os.path.join(root, "tools", "*.py"))
    sources += glob.glob(os.path.join(root, "benchmarks", "**", "*.py"),
                         recursive=True)
    assert len(sources) > 100
    readers = [f for f in sources
               if "PADDLE_TPU_PALLAS_CONV" in open(f).read()]
    assert not readers, readers
    x = jnp.zeros((2, 6, 6, 128), jnp.bfloat16)
    w = jnp.zeros((128, 128, 3, 3), jnp.bfloat16)
    args = ((1, 1), (1, 1), (1, 1))
    assert pallas_conv.PALLAS_CONV is True
    pallas_conv.PALLAS_CONV = False
    try:
        assert pallas_conv.ineligible(x, w, *args) is None
    finally:
        pallas_conv.PALLAS_CONV = True


# --- through-program: the route, its census, counters -------------------

def _bf16_convnet(level="O2"):
    """AMP conv3x3(C=128)+bn(relu)+conv1x1+bn(relu)+pool+fc+SGD: the
    bf16 NHWC 128-lane shapes the Pallas suite was built for, forward
    inside fused conv->bn->act windows."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[128, 6, 6],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(input=img, num_filters=128, filter_size=3,
                                padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu")
        c = fluid.layers.conv2d(input=b, num_filters=128, filter_size=1,
                                bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu")
        gp = fluid.layers.pool2d(input=b, global_pooling=True,
                                 pool_type="avg")
        logits = fluid.layers.fc(input=gp, size=5)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(
            loss, startup_program=startup)
    fluid.amp.enable(main, level=level)
    return main, startup, loss


def _convnet_feeds(k):
    rng = np.random.default_rng(6)
    return [{"img": rng.standard_normal((4, 128, 6, 6)).astype(np.float32),
             "label": rng.integers(0, 5, (4, 1)).astype(np.int64)}
            for _ in range(k)]


def _train_bf16_convnet(steps=3, level="O2"):
    main, startup, loss = _bf16_convnet(level)
    exe = fluid.Executor(fluid.CPUPlace())
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        return [float(np.ravel(exe.run(main, feed=f,
                                       fetch_list=[loss])[0])[0])
                for f in _convnet_feeds(steps)]


def _step_census(level):
    """Primitives of the traced train step, pallas_calls by their name."""
    def walk(jaxpr, acc):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                acc["pallas_call:" + e.params["name"]] += 1
                continue
            acc[e.primitive.name] += 1
            for v in e.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        walk(inner, acc)
        return acc

    main, startup, loss = _bf16_convnet(level)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    with em.scope_guard(scope):
        exe.run(startup)
        compiled, feed_vals, state_vals, rng = exe._aot_block(
            main, _convnet_feeds(1)[0], [loss], scope)
        return walk(jax.make_jaxpr(compiled.fn)(
            feed_vals, state_vals, np.uint32(rng)).jaxpr,
            collections.Counter())


def test_amp_o2_step_is_xla_convs():
    """An AMP O2 convnet step holds lax's conv three times per conv
    (forward, grad-input, grad-filter; the first conv's input is data
    and takes no gradient) and no pallas_call at all, at the very
    shapes the conv suite's gate passes and the deleted bn+act kernel
    took (PR 34): the step is XLA's alone. No conv site books a Pallas
    counter: a route that is not considered cannot fall back."""
    census = _step_census("O2")
    assert census["conv_general_dilated"] == 3 * 2 - 1, census
    kernels = {k for k in census if k.startswith("pallas_call:")}
    assert not kernels, kernels
    assert _series("pallas_kernel_total") == 0
    assert _series("pallas_fallback_total") == 0
    _train_bf16_convnet(steps=1)
    assert _series("pallas_kernel_total") == 0
    assert _series("pallas_fallback_total") == 0


def test_two_runs_from_one_seed_are_bit_equal():
    """Nothing but the program and the seed decides the step: two runs
    are bitwise equal, the second with the benchmark test's leftover
    switch flipped, and the loss falls."""
    l0 = _train_bf16_convnet()
    pallas_conv.PALLAS_CONV = False
    try:
        l1 = _train_bf16_convnet()
    finally:
        pallas_conv.PALLAS_CONV = True
    assert l0 == l1
    assert np.isfinite(l0).all() and l0[-1] < l0[0] + 0.5


def test_o3_int8_forward_routes_and_its_backward_runs():
    """AMP O3: each conv's forward is the int8 kernel (conv2d_q8, behind
    quant.ineligible_conv), its backward the same two lax transposes as
    under O2 (the int8 forward has no transpose rule), and training
    tracks O2."""
    census = _step_census("O3")
    assert census["pallas_call:conv2d_q8"] == 2, census
    assert census["conv_general_dilated"] == 2 * 2 - 1, census
    assert not [k for k in census if k.startswith("pallas_call:conv2d")
                and k != "pallas_call:conv2d_q8"], census
    telemetry.reset()
    l3 = _train_bf16_convnet(level="O3")
    # booked per trace of the step, and the step is traced more than once
    hits = _series("pallas_kernel_total", "op=conv2d")
    assert hits > 0 and hits % 2 == 0
    assert _series("quant_kernel_total", "op=conv2d") == hits
    assert _series("pallas_fallback_total") == 0
    l2 = _train_bf16_convnet(level="O2")
    np.testing.assert_allclose(l3, l2, rtol=0, atol=0.1)


def test_f32_conv_books_no_pallas_counter():
    """A plain f32 program is XLA's conv like every other: it runs to
    completion and no conv site books a hit or a fallback."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 9
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[8, 6, 6],
                                dtype="float32")
        c = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                                padding=1, bias_attr=False)
        loss = fluid.layers.mean(c)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    with em.scope_guard(scope):
        exe.run(startup)
        out, = exe.run(main, feed={
            "img": np.ones((2, 8, 6, 6), np.float32)}, fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()
    assert _series("pallas_fallback_total") == 0
    assert _series("pallas_kernel_total") == 0


def test_depthwise_conv2d_grad_matches_central_differences():
    """groups != 1 takes the same explicit backward (the transposes of
    the grouped lax conv): central differences agree, nothing is
    counted."""
    from op_test import OpTest

    rng = np.random.default_rng(12)
    x = rng.random((1, 2, 4, 4)).astype("float32")
    wt = rng.random((2, 1, 3, 3)).astype("float32")
    t = OpTest()
    t.op_type = "depthwise_conv2d"
    t.inputs = {"Input": x, "Filter": wt}
    t.attrs = {"strides": [1, 1], "paddings": [0, 0], "groups": 2}
    t.outputs = {"Output": np.zeros((1, 2, 2, 2), "float32")}
    t.check_grad(["Input", "Filter"], "Output",
                 max_relative_error=0.02)
    assert _series("pallas_fallback_total") == 0
    assert _series("pallas_kernel_total") == 0


# --- run_steps: windowed parity + the CPU scan+grad-conv warning -------

def _scan_convnet():
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[4, 6, 6],
                                dtype="float32")
        c = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                padding=1, bias_attr=False)
        loss = fluid.layers.mean(c)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    return main, startup, loss


def _feeds(k=2):
    rng = np.random.default_rng(8)
    return [{"img": rng.standard_normal((2, 4, 6, 6)).astype(np.float32)}
            for _ in range(k)]


def test_fused_window_parity_under_run_steps(monkeypatch):
    """run_steps (lax.scan window) over the bf16 net matches per-step
    dispatch: the fused conv->bn->act windows and the conv transposes
    trace identically inside the scan body. Tolerance only for the
    scan's f32 reduction-order drift."""
    monkeypatch.setattr(em, "_WARNED_CPU_SCAN_CONV", True)  # mute here

    seq = _train_bf16_convnet(steps=2)
    main, startup, loss = _bf16_convnet()
    exe = fluid.Executor(fluid.CPUPlace())
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        out, = exe.run_steps(main, feed_window=_convnet_feeds(2),
                             fetch_list=[loss], fetch_mode="stack")
    win = [float(v) for v in np.ravel(out)]
    np.testing.assert_allclose(seq, win, rtol=0, atol=5e-3)
    assert _series("pallas_kernel_total") == 0


def test_cpu_scan_grad_conv_warns_once(monkeypatch):
    """The PR 5 caveat surfaced at the API: a multi-step run_steps window
    with a conv backward on XLA:CPU warns (once per process) about the
    ~60x scan slowdown; steps=1 and conv-less programs stay silent."""
    monkeypatch.setattr(em, "_WARNED_CPU_SCAN_CONV", False)
    main, startup, loss = _scan_convnet()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    with em.scope_guard(scope):
        exe.run(startup)
        with pytest.warns(RuntimeWarning, match="conv backward"):
            exe.run_steps(main, feed_window=_feeds(), fetch_list=[loss])
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            exe.run_steps(main, feed_window=_feeds(), fetch_list=[loss])
        assert not [w for w in caught
                    if issubclass(w.category, RuntimeWarning)
                    and "conv backward" in str(w.message)], caught


def test_cpu_scan_warning_skips_single_step(monkeypatch):
    monkeypatch.setattr(em, "_WARNED_CPU_SCAN_CONV", False)
    em._maybe_warn_cpu_scan_conv(None, _scan_convnet()[0], steps=1)
    assert em._WARNED_CPU_SCAN_CONV is False
