"""Internal NHWC layout convention (ops/layout.py): numeric parity with
the canonical NCHW path on training steps (forward + vjp + optimizer),
intermediate fetches, and the eager interpreter.

The TPU-native analogue of the reference's data_layout_transform tests
(framework/data_layout_transform.cc): the layout convention must be a
pure performance transform — no observable semantic change.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as em
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import layout as layout_mod


@pytest.fixture(params=[True, False], ids=["nhwc", "nchw"])
def layout_opt(request, monkeypatch):
    monkeypatch.setattr(layout_mod, "LAYOUT_OPT", request.param)
    return request.param


def _train_convnet(steps=3, fetch_inter=False, use_jit=True):
    """Small image classifier exercising conv(bias)+bn+relu+pool+residual:
    returns per-step losses, final params, and optionally an intermediate
    conv activation fetch."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 77
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 16, 16],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c1 = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                                 padding=1, act="relu")   # bias path axis=1
        b1 = fluid.layers.batch_norm(input=c1, act="relu")
        c2 = fluid.layers.conv2d(input=b1, num_filters=8, filter_size=3,
                                 padding=1, bias_attr=False)
        b2 = fluid.layers.batch_norm(input=c2)
        res = fluid.layers.elementwise_add(x=b1, y=b2, act="relu")
        p = fluid.layers.pool2d(input=res, pool_size=2, pool_stride=2)
        gp = fluid.layers.pool2d(input=p, global_pooling=True,
                                 pool_type="avg")
        logits = fluid.layers.fc(input=gp, size=5)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(3)
    scope = em.Scope()
    losses, inter = [], None
    with em.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            x = rng.standard_normal((8, 3, 16, 16)).astype(np.float32)
            y = rng.integers(0, 5, (8, 1)).astype(np.int64)
            fetch = [loss] + ([c1] if fetch_inter else [])
            out = exe.run(main, feed={"img": x, "label": y},
                          fetch_list=fetch, use_jit=use_jit)
            losses.append(float(np.ravel(out[0])[0]))
            if fetch_inter:
                inter = np.asarray(out[1])
        params = {n: np.asarray(scope.find_var(n))
                  for n in scope.local_var_names()
                  if n.endswith((".w_0", ".b_0"))}
    return losses, params, inter


def _run_modes(fn):
    old = layout_mod.LAYOUT_OPT
    try:
        layout_mod.LAYOUT_OPT = False
        ref = fn()
        layout_mod.LAYOUT_OPT = True
        got = fn()
    finally:
        layout_mod.LAYOUT_OPT = old
    return ref, got


def test_convnet_train_parity():
    """NHWC-convention training matches canonical NCHW step for step —
    losses and every updated parameter."""
    (l_ref, p_ref, _), (l_got, p_got, _) = _run_modes(_train_convnet)
    np.testing.assert_allclose(l_got, l_ref, rtol=1e-4, atol=1e-5)
    assert p_ref.keys() == p_got.keys() and len(p_ref) >= 6
    for n in p_ref:
        np.testing.assert_allclose(p_got[n], p_ref[n], rtol=2e-4,
                                   atol=1e-5, err_msg=n)


def test_intermediate_fetch_is_canonical_nchw():
    """Fetching a conv activation mid-stack returns the user-visible NCHW
    layout and the same numbers as the NCHW path."""
    (_, _, i_ref), (_, _, i_got) = _run_modes(
        lambda: _train_convnet(steps=1, fetch_inter=True))
    assert i_got.shape == (8, 8, 16, 16)
    np.testing.assert_allclose(i_got, i_ref, rtol=1e-4, atol=1e-5)


def test_eager_matches_jit_under_nhwc(monkeypatch):
    """The eager interpreter shares the layout machinery: same numbers."""
    monkeypatch.setattr(layout_mod, "LAYOUT_OPT", True)
    l_jit, p_jit, _ = _train_convnet(steps=2, use_jit=True)
    l_eager, p_eager, _ = _train_convnet(steps=2, use_jit=False)
    np.testing.assert_allclose(l_eager, l_jit, rtol=1e-4, atol=1e-5)
    for n in p_jit:
        np.testing.assert_allclose(p_eager[n], p_jit[n], rtol=2e-4,
                                   atol=1e-5, err_msg=n)


def _train_deconv(steps=2):
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[4, 8, 8],
                                dtype="float32")
        tgt = fluid.layers.data(name="tgt", shape=[3, 16, 16],
                                dtype="float32")
        c = fluid.layers.conv2d(input=img, num_filters=6, filter_size=3,
                                padding=1, act="relu")
        up = fluid.layers.conv2d_transpose(input=c, num_filters=3,
                                           filter_size=4, stride=2,
                                           padding=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(up, tgt))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(5)
    losses = []
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        for _ in range(steps):
            x = rng.standard_normal((4, 4, 8, 8)).astype(np.float32)
            t = rng.standard_normal((4, 3, 16, 16)).astype(np.float32)
            v, = exe.run(main, feed={"img": x, "tgt": t},
                         fetch_list=[loss])
            losses.append(float(np.ravel(v)[0]))
    return losses


def test_conv2d_transpose_parity():
    """conv2d_transpose joins the NHWC convention (it previously ran NCHW,
    inconsistent with conv2d)."""
    ref, got = _run_modes(_train_deconv)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def _train_conv3d(steps=2):
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup):
        vol = fluid.layers.data(name="vol", shape=[2, 6, 6, 6],
                                dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        c = fluid.layers.conv3d(input=vol, num_filters=4, filter_size=3,
                                padding=1, act="relu")
        gp = fluid.layers.reduce_mean(c, dim=[1, 2, 3, 4], keep_dim=False)
        pred = fluid.layers.reshape(gp, [-1, 1])
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(6)
    losses = []
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        for _ in range(steps):
            x = rng.standard_normal((4, 2, 6, 6, 6)).astype(np.float32)
            t = rng.standard_normal((4, 1)).astype(np.float32)
            v, = exe.run(main, feed={"vol": x, "y": t}, fetch_list=[loss])
            losses.append(float(np.ravel(v)[0]))
    return losses


def test_conv3d_parity():
    """conv3d runs NDHWC internally; same numbers as canonical NCDHW."""
    ref, got = _run_modes(_train_conv3d)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def _prelu_sum():
    """prelu is layout-aware (ISSUE 7): under the NHWC tag its channel
    alpha broadcasts on the minor axis instead of forcing a barrier.
    C != H here so a layout bug breaks broadcasting or silently
    mis-applies alpha."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 9
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 6, 6], dtype="float32")
        c = fluid.layers.conv2d(input=x, num_filters=5, filter_size=3,
                                padding=1)
        p = fluid.layers.prelu(c, mode="channel")
        out = fluid.layers.reduce_sum(p)
    exe = fluid.Executor(fluid.CPUPlace())
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        v, = exe.run(main, feed={"x": np.ones((2, 4, 6, 6), np.float32)},
                     fetch_list=[out])
    return float(np.ravel(v)[0])


def test_prelu_after_conv_parity():
    ref, got = _run_modes(_prelu_sum)
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def _prelu_element_sum():
    """element-mode alpha is stored canonical [1, C, H, W]; under the
    NHWC tag the lowering must transpose it to minor-channel order, not
    reshape blindly (H != W != C here so a mix-up changes the sum)."""
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 6, 7], dtype="float32")
        c = fluid.layers.conv2d(input=x, num_filters=5, filter_size=3,
                                padding=1)
        p = fluid.layers.prelu(c, mode="element")
        out = fluid.layers.reduce_sum(p)
    exe = fluid.Executor(fluid.CPUPlace())
    with em.scope_guard(em.Scope()):
        exe.run(startup)
        v, = exe.run(main, feed={"x": np.ones((2, 4, 6, 7), np.float32)},
                     fetch_list=[out])
    return float(np.ravel(v)[0])


def test_prelu_element_after_conv_parity():
    ref, got = _run_modes(_prelu_element_sum)
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_persistable_set_after_run_invalidates_analysis():
    """Marking a var persistable between runs must reach the cached
    program analysis (r3 review finding: the executor caches read/write/
    persistable sets per program version)."""
    main, _ = fluid.Program(), fluid.Program()
    with fluid.program_guard(main):
        a = fluid.layers.data(name="a", shape=[4], dtype="float32")
        y = fluid.layers.scale(a, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    s = em.Scope()
    feed = {"a": np.ones((2, 4), np.float32)}
    with em.scope_guard(s):
        exe.run(main, feed=feed, fetch_list=[y], use_jit=False)
        assert s.find_var(y.name) is None
        y.persistable = True
        exe.run(main, feed=feed, fetch_list=[y], use_jit=False)
        assert s.find_var(y.name) is not None
