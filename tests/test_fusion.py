"""Trace-time kernel fusion (ops/fusion.py, ISSUE 7): numeric parity
with the unfused per-op trace, per-reason fallback counters, and the
PADDLE_TPU_FUSION=0 escape hatch.

The fusion pass has one value-rewriting path (inference BN fold);
everything else, the training-mode conv+bn+act window included (PR 34:
its Pallas kernel went, XLA fuses the plain lowerings), composes the
registered member lowerings, and an optimizer bucket applies its
members' own arithmetic tensor by tensor, so both must be BITWISE
identical to the unfused trace — these tests pin exactly that: bitwise
asserts for compose/bucket paths, tolerance asserts only where the
rewrite legitimately reassociates float math (BN fold).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as em
from paddle_tpu import telemetry
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import fusion as fusion_mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _with_fusion(fuse, fn, *args, **kw):
    """Run fn under FUSION_OPT=fuse. Callers build a FRESH program inside
    fn — the jit and plan caches key on program identity."""
    old = fusion_mod.FUSION_OPT
    fusion_mod.FUSION_OPT = fuse
    try:
        return fn(*args, **kw)
    finally:
        fusion_mod.FUSION_OPT = old


def _fallbacks(reason=None):
    series = telemetry.read_series("fusion_fallback_total")
    if reason is None:
        return sum(series.values())
    return sum(v for k, v in series.items() if f"reason={reason}" in k)


def _state(scope):
    return {n: np.asarray(scope.find_var(n))
            for n in scope.local_var_names()
            if isinstance(scope.find_var(n), np.ndarray)
            or hasattr(scope.find_var(n), "dtype")}


def _assert_state_equal(a, b):
    assert set(a) == set(b), set(a) ^ set(b)
    for n in sorted(a):
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]),
                                      err_msg=f"state '{n}' diverged")


def _train_convnet(opt_factory, steps=3):
    """conv+bn(relu)+pool + an elementwise chain + two fc layers + an
    optimizer: one program that plans conv_bn_act, chain, fc_act and
    opt_bucket windows at once."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 21
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                                padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu")
        p = fluid.layers.pool2d(input=b, pool_size=2, pool_stride=2)
        s = fluid.layers.abs(fluid.layers.scale(p, scale=1.5))  # chain
        gp = fluid.layers.pool2d(input=s, global_pooling=True,
                                 pool_type="avg")
        h = fluid.layers.fc(input=gp, size=16, act="relu")      # fc_act
        logits = fluid.layers.fc(input=h, size=5)               # fc, no act
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        opt_factory().minimize(loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(5)
    scope = em.Scope()
    losses = []
    with em.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            x = rng.standard_normal((4, 3, 8, 8)).astype(np.float32)
            y = rng.integers(0, 5, (4, 1)).astype(np.int64)
            out, = exe.run(main, feed={"img": x, "label": y},
                           fetch_list=[loss])
            losses.append(float(np.ravel(out)[0]))
        state = _state(scope)
    return losses, state


@pytest.mark.parametrize("opt", ["sgd", "momentum", "adam"])
def test_training_parity_bitwise(opt):
    """Fused trace (conv+bn+act compose, chain, fc windows, bucketed
    optimizer) is bitwise identical to the unfused per-op trace."""
    factory = {
        "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.05),
        "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.05,
                                                     momentum=0.9),
        "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
    }[opt]
    l1, s1 = _with_fusion(True, _train_convnet, factory)
    l0, s0 = _with_fusion(False, _train_convnet, factory)
    assert l1 == l0
    _assert_state_equal(s1, s0)


def test_f32_window_fuses_without_fallback():
    """A float32 training conv+bn+act window is a window like any other:
    it fuses (one fused_conv_bn_act in the plan), books no fallback of
    any reason and matches the unfused trace bit for bit."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.05)
    l1, s1 = _with_fusion(True, _train_convnet, sgd)
    assert _fallbacks() == 0, telemetry.read_series("fusion_fallback_total")
    l0, s0 = _with_fusion(False, _train_convnet, sgd)
    assert l1 == l0
    _assert_state_equal(s1, s0)


def _bn_act_net(steps=2):
    """batch_norm(act) directly on the feed — the conv-less bn_act window
    — trained with SGD so the bn scale/bias pair exercises a 2-param
    fused_sgd bucket."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6, 4, 4], dtype="float32")
        b = fluid.layers.batch_norm(input=x, act="relu")
        loss = fluid.layers.mean(b)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(7)
    scope = em.Scope()
    losses = []
    with em.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            xv = rng.standard_normal((4, 6, 4, 4)).astype(np.float32)
            out, = exe.run(main, feed={"x": xv}, fetch_list=[loss])
            losses.append(float(np.ravel(out)[0]))
        state = _state(scope)
    return losses, state


def test_bn_act_without_conv_parity():
    l1, s1 = _with_fusion(True, _bn_act_net)
    l0, s0 = _with_fusion(False, _bn_act_net)
    assert l1 == l0
    _assert_state_equal(s1, s0)


def _infer_conv_bn(fetch_inter=False):
    """Inference-mode conv+bn(relu): the BN-fold path (or its
    fetched-intermediate fallback when the conv activation is fetched)."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 31
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        # bias_attr=False: a conv bias would interpose an elementwise_add
        # between conv and bn and break the window (and thus the fold)
        c = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                                padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu", is_test=True)
        out = fluid.layers.pool2d(input=b, global_pooling=True,
                                  pool_type="avg")
    exe = fluid.Executor(fluid.CPUPlace())
    x = np.random.default_rng(9).standard_normal((2, 3, 8, 8)) \
        .astype(np.float32)
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        fetch = [out] + ([c] if fetch_inter else [])
        res = exe.run(main, feed={"img": x}, fetch_list=fetch)
    return [np.asarray(r) for r in res]


def test_bn_fold_inference_parity():
    """Folding BN into the conv weights reassociates float math — close,
    not bitwise."""
    got, = _with_fusion(True, _infer_conv_bn)
    ref, = _with_fusion(False, _infer_conv_bn)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_fold_blocked_by_intermediate_fetch():
    """Fetching the conv activation protects it: the fold (which never
    materializes that tensor) must fall back to per-member execution —
    bitwise vs unfused — and count fetched_intermediate."""
    before = _fallbacks("fetched_intermediate")
    got = _with_fusion(True, _infer_conv_bn, fetch_inter=True)
    assert _fallbacks("fetched_intermediate") > before
    ref = _with_fusion(False, _infer_conv_bn, fetch_inter=True)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)


def _sparse_emb_net(steps=3):
    """is_sparse embedding under Adam: the SelectedRows grad keeps the
    per-param fast path (reason sparse_grad) while the dense fc pair
    still buckets."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[4], dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[50, 8], is_sparse=True,
            param_attr=fluid.ParamAttr(name="emb_w"))
        flat = fluid.layers.reshape(emb, shape=[-1, 32])
        logits = fluid.layers.fc(input=flat, size=50)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.Adam(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    feed = {"ids": np.array([[1, 7, 7, 3], [0, 2, 2, 2]], np.int64),
            "lbl": np.array([[5], [9]], np.int64)}
    losses = []
    with em.scope_guard(scope):
        exe.run(startup)
        scope.set_var("emb_w", np.linspace(
            -1, 1, 50 * 8).astype(np.float32).reshape(50, 8))
        for _ in range(steps):
            v, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.ravel(v)[0]))
        state = _state(scope)
    return losses, state


def test_sparse_grad_keeps_per_param_path():
    before = _fallbacks("sparse_grad")
    l1, s1 = _with_fusion(True, _sparse_emb_net)
    assert _fallbacks("sparse_grad") > before
    l0, s0 = _with_fusion(False, _sparse_emb_net)
    assert l1 == l0
    _assert_state_equal(s1, s0)


def _two_fc(opt_factory):
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 31
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt_factory().minimize(loss, startup_program=startup)
    rng = np.random.default_rng(3)
    feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
            "y": rng.standard_normal((4, 1)).astype(np.float32)}
    return main, startup, loss, feed


@pytest.mark.parametrize("opt", ["momentum", "adam"])
def test_bucket_builds_no_flat_buffer(opt):
    """The bucket is one scope over per-tensor updates: the compiled
    step holds no concatenate under pd.fused_<t> (the flat buffers cost
    21-25 % of a chip's step, PERF.md PR 27), and a bf16 member shares
    the bucket with the f32 ones, each output in its input's dtype."""
    import jax.numpy as jnp

    factory = {
        "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.05,
                                                     momentum=0.9),
        "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
    }[opt]
    main, startup, loss, feed = _two_fc(factory)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    seen = []

    def observe(op, ins, outs):
        if op.type.endswith(opt):   # fused_<opt>, or a member left outside
            seen.append((op.type, [str(v.dtype) for v in outs["ParamOut"]]))

    with em.scope_guard(scope):
        exe.run(startup)
        text = exe.compiled_hlo(main, feed=feed, fetch_list=[loss])
        fused = [ln for ln in text.split("\n") if f"pd.fused_{opt}" in ln]
        assert fused
        assert not [ln for ln in fused if " concatenate(" in ln]
        # one member and its accumulators in bf16: the trace-time dtypes
        w = main.global_block().all_parameters()[0].name
        state = [n for n in scope.local_var_names()
                 if n == w or n.startswith(w + "_")]
        assert len(state) == {"momentum": 2, "adam": 3}[opt], state
        for n in state:
            scope.set_var(n, jnp.asarray(scope.find_var(n), jnp.bfloat16))
        em._op_observers.append(observe)
        try:
            exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            em._op_observers.remove(observe)
        for n in state:
            assert scope.find_var(n).dtype == jnp.bfloat16, n
    # ONE bucket a trace (the step's, and memory.on_compile's second)
    assert seen and {t for t, _ in seen} == {f"fused_{opt}"}
    for _, dtypes in seen:
        assert sorted(dtypes) == ["bfloat16"] + ["float32"] * 3


def _run_steps_window(steps=3):
    """K-step run_steps window (lax.scan carries + donation) over the
    fused trace."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu")
        gp = fluid.layers.pool2d(input=b, global_pooling=True,
                                 pool_type="avg")
        logits = fluid.layers.fc(input=gp, size=5)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(23)
    feeds = [{"img": rng.standard_normal((4, 3, 8, 8)).astype(np.float32),
              "label": rng.integers(0, 5, (4, 1)).astype(np.int64)}
             for _ in range(steps)]
    scope = em.Scope()
    with em.scope_guard(scope):
        exe.run(startup)
        win, = exe.run_steps(main, feed_window=feeds, fetch_list=[loss],
                             fetch_mode="stack")
        state = _state(scope)
    return np.asarray(win), state


def test_run_steps_window_parity():
    w1, s1 = _with_fusion(True, _run_steps_window)
    w0, s0 = _with_fusion(False, _run_steps_window)
    np.testing.assert_array_equal(w1, w0)
    _assert_state_equal(s1, s0)


def _amp_window_net(kind, channels):
    """An AMP O2 step around one training-mode window on a bf16
    activation: `conv_bn_act` = conv3x3 -> bn(relu); `bn_act` = conv1x1
    -> max pool -> bn(relu), whose window holds no conv."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[channels, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if kind == "conv_bn_act":
            h = fluid.layers.conv2d(input=img, num_filters=channels,
                                    filter_size=3, padding=1,
                                    bias_attr=False)
        else:
            h = fluid.layers.conv2d(input=img, num_filters=channels,
                                    filter_size=1, bias_attr=False)
            h = fluid.layers.pool2d(input=h, pool_size=2, pool_stride=2)
        h = fluid.layers.batch_norm(input=h, act="relu")
        gp = fluid.layers.pool2d(input=h, global_pooling=True,
                                 pool_type="avg")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=gp, size=5), label))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(
            loss, startup_program=startup)
    fluid.amp.enable(main, level="O2")
    rng = np.random.default_rng(3)
    feeds = [{"img": rng.standard_normal((8, channels, 8, 8))
              .astype(np.float32),
              "label": rng.integers(0, 5, (8, 1)).astype(np.int64)}
             for _ in range(2)]
    return main, startup, loss, feeds


def _train_amp_window(kind, channels):
    """(losses, state, window types planned, pallas_calls in the step's
    jaxpr) of two steps of `_amp_window_net`."""
    import jax

    main, startup, loss, feeds = _amp_window_net(kind, channels)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = em.Scope()
    with em.scope_guard(scope):
        exe.run(startup)
        compiled, feed_vals, state_vals, rng = exe._aot_block(
            main, feeds[0], [loss], scope)
        # a jaxpr prints its nested jaxprs, so one search sees them all
        kernels = str(jax.make_jaxpr(compiled.fn)(
            feed_vals, state_vals, np.uint32(rng))).count("pallas_call")
        losses = [float(np.ravel(exe.run(main, feed=f,
                                         fetch_list=[loss])[0])[0])
                  for f in feeds]
        state = _state(scope)
    planned = sorted(g.op.type for g in (fusion_mod.plan(main) or {}).values()
                     if g.op is not None)
    return losses, state, planned, kernels


@pytest.mark.parametrize("channels", [64, 128])
@pytest.mark.parametrize("nhwc", [True, False], ids=["NHWC", "NCHW"])
@pytest.mark.parametrize("kind", ["conv_bn_act", "bn_act"])
def test_amp_window_is_plain_jnp_and_bitwise(kind, nhwc, channels,
                                             monkeypatch):
    """A bf16 training window lowers to plain jax.numpy whatever its
    layout and width (128 channels on NHWC is what the deleted bn+act
    kernel took): the window is planned, the traced step holds no
    pallas_call, no fallback is booked, and losses and state equal the
    unfused trace bit for bit."""
    from paddle_tpu.ops import layout as layout_mod
    monkeypatch.setattr(layout_mod, "LAYOUT_OPT", nhwc)
    l1, s1, planned, kernels = _with_fusion(
        True, _train_amp_window, kind, channels)
    assert "fused_" + kind in planned, planned
    assert kernels == 0
    assert _fallbacks() == 0, telemetry.read_series("fusion_fallback_total")
    l0, s0, unplanned, _ = _with_fusion(
        False, _train_amp_window, kind, channels)
    assert unplanned == []
    assert l1 == l0 and all(np.isfinite(l1))
    _assert_state_equal(s1, s0)


def test_no_kernel_reason_is_left():
    """With one branch there is nothing to fall back from: the fusion
    pass can book no `kernel_*` reason, tools/check_registry.py names
    none, and ops/fusion.py holds no pallas_call."""
    import inspect
    import os
    import re

    source = inspect.getsource(fusion_mod)
    assert "pallas" not in source.lower(), "a kernel is back"
    assert "kernel_" not in source
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tools", "check_registry.py")) as f:
        # a name that starts so, but for the module ops/kernel_choice.py
        assert not re.search(r"\bkernel_(?!choice\b)", f.read())


def test_roofline_sees_fused_ops():
    """The analytic cost model prices fused types from their prefixed
    member slots, and the one parse of compiled text counts
    instructions and fusions and reads a fusion through its body."""
    import jax
    from paddle_tpu import roofline, xplane

    aval = jax.ShapeDtypeStruct((2, 8, 8, 8), np.float32)
    filt = jax.ShapeDtypeStruct((8, 3, 3, 3), np.float32)
    flops, bytes_ = roofline.op_cost(
        "fused_conv_bn_act",
        {"0:Input": [jax.ShapeDtypeStruct((2, 3, 8, 8), np.float32)],
         "0:Filter": [filt]},
        {"1:Y": [aval]})
    # 2*out_elems*cin*kh*kw for the conv + ~10/elem for bn+act
    out_elems = 2 * 8 * 8 * 8
    assert flops == 2.0 * out_elems * 3 * 3 * 3 + 10.0 * out_elems
    assert bytes_ > 0

    p = jax.ShapeDtypeStruct((100,), np.float32)
    flops, _ = roofline.op_cost(
        "fused_adam", {"Param": [p, p], "Grad": [p, p]}, {})
    assert flops == 12.0 * 200

    hlo = """HloModule m
fused_computation {
  p0 = f32[8]{0} parameter(0)
  ROOT add = f32[8]{0} add(p0, p0)
}
ENTRY main {
  x = f32[8]{0} parameter(0)
  f = f32[8]{0} fusion(x), kind=kLoop, calls=fused_computation
  ROOT t = (f32[8]{0}, f32[8]{0}) tuple(f, x)
}
"""
    instrs = xplane.hlo_instructions(hlo)
    assert [i.name for i in instrs] == ["x", "f", "t"]   # the entry's own
    (fused,) = [i for i in instrs if i.opcode == "fusion"]
    assert fused.heavy == "elementwise" and fused.flops == 8.0
    assert fused.bytes == 2 * 8 * 4
    assert [i.name for i in xplane.compact(instrs)] == ["f"]


def test_plan_window_kinds():
    """The planner finds every expected window in the convnet and the
    gate turns it off wholesale."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                                padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(input=c, act="relu")
        s = fluid.layers.abs(fluid.layers.scale(b, scale=1.5))
        gp = fluid.layers.pool2d(input=s, global_pooling=True,
                                 pool_type="avg")
        logits = fluid.layers.fc(input=gp, size=5, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
            loss, startup_program=startup)

    old = fusion_mod.FUSION_OPT
    try:
        fusion_mod.FUSION_OPT = True
        groups = fusion_mod.plan(main)
        kinds = {g.kind for g in groups.values()}
        assert {"conv_bn_act", "chain", "fc_act", "opt_bucket"} <= kinds
        # anchor map is non-overlapping and in block order
        spans = sorted((g.start, g.end) for g in groups.values())
        for (s0, e0), (s1, _) in zip(spans, spans[1:]):
            assert e0 <= s1
        fusion_mod.FUSION_OPT = False
        assert fusion_mod.plan(main) is None
    finally:
        fusion_mod.FUSION_OPT = old
