"""Oracle tests for the round-5 v2 wrapper tail: every new
trainer_config_helpers-parity wrapper runs against a numpy oracle, plus
the ADVICE r4 fixes (initial_std/mean -> initializer, warn on lr kwargs,
true vanilla recurrence) and the v2/plot Ploter."""

import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.v2 import layer as v2l
from paddle_tpu.v2 import networks as v2n


def _run(fetch, feed):
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(fluid.default_startup_program())
        outs = exe.run(feed=feed, fetch_list=list(fetch))
    return [np.asarray(o) for o in outs]


def _data(name, shape, dtype="float32"):
    return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                             append_batch_size=False)


RNG = np.random.RandomState(7)


class TestMatrixWrappers:
    def test_rotate_is_ccw_rot90(self):
        c, h, w = 2, 3, 4
        x = _data("x", [2, c * h * w])
        out = v2l.rotate(x, height=h, width=w)
        xs = RNG.randn(2, c * h * w).astype(np.float32)
        got, = _run([out], {"x": xs})
        want = np.rot90(xs.reshape(2, c, h, w), k=1, axes=(2, 3)) \
            .reshape(2, -1)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_sum_to_one_norm(self):
        x = _data("x", [3, 5])
        xs = np.abs(RNG.randn(3, 5)).astype(np.float32) + 0.1
        got, = _run([v2l.sum_to_one_norm(x)], {"x": xs})
        np.testing.assert_allclose(got, xs / xs.sum(1, keepdims=True),
                                   rtol=1e-5)

    def test_row_l2_norm(self):
        x = _data("x", [3, 5])
        xs = RNG.randn(3, 5).astype(np.float32)
        got, = _run([v2l.row_l2_norm(x)], {"x": xs})
        np.testing.assert_allclose(
            got, xs / np.linalg.norm(xs, axis=1, keepdims=True), rtol=1e-5)

    def test_l2_distance_and_dot_prod(self):
        a, b = _data("a", [4, 6]), _data("b", [4, 6])
        av = RNG.randn(4, 6).astype(np.float32)
        bv = RNG.randn(4, 6).astype(np.float32)
        d, p = _run([v2l.l2_distance(a, b), v2l.dot_prod(a, b)],
                    {"a": av, "b": bv})
        np.testing.assert_allclose(
            d[:, 0], np.linalg.norm(av - bv, axis=1), rtol=1e-5)
        np.testing.assert_allclose(p[:, 0], (av * bv).sum(1), rtol=1e-5)

    def test_out_prod(self):
        a, b = _data("a", [3, 4]), _data("b", [3, 5])
        av = RNG.randn(3, 4).astype(np.float32)
        bv = RNG.randn(3, 5).astype(np.float32)
        got, = _run([v2l.out_prod(a, b)], {"a": av, "b": bv})
        want = np.einsum("ni,nj->nij", av, bv).reshape(3, -1)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_linear_comb(self):
        m, size = 3, 4
        w, v = _data("w", [2, m]), _data("v", [2, m * size])
        wv = RNG.randn(2, m).astype(np.float32)
        vv = RNG.randn(2, m * size).astype(np.float32)
        got, = _run([v2l.linear_comb(w, v, size)], {"w": wv, "v": vv})
        want = np.einsum("nm,nms->ns", wv, vv.reshape(2, m, size))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_tensor_layer_bilinear(self):
        da, db, size = 3, 4, 2
        a, b = _data("a", [2, da]), _data("b", [2, db])
        out = v2l.tensor(a, b, size)
        av = RNG.randn(2, da).astype(np.float32)
        bv = RNG.randn(2, db).astype(np.float32)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            wname = [p.name for p in fluid.default_main_program()
                     .global_block().all_parameters()][0]
            wv = np.asarray(sc.find_var(wname))
            got, = exe.run(feed={"a": av, "b": bv}, fetch_list=[out])
        want = np.einsum("ni,isj,nj->ns", av,
                         wv.reshape(da, size, db), bv)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-5)


class TestProjectionsAndMixed:
    def test_mixed_sums_projections(self):
        x, y = _data("x", [2, 6]), _data("y", [2, 4])
        p1 = v2l.full_matrix_projection(x, size=4)
        p2 = v2l.identity_projection(y)
        out = v2l.mixed(input=[p1, p2])
        xs = RNG.randn(2, 6).astype(np.float32)
        ys = RNG.randn(2, 4).astype(np.float32)
        got, p1v = _run([out, p1], {"x": xs, "y": ys})
        np.testing.assert_allclose(got, p1v + ys, rtol=1e-5)

    def test_identity_projection_slice(self):
        x = _data("x", [3, 8])
        xs = RNG.randn(3, 8).astype(np.float32)
        got, = _run([v2l.identity_projection(x, offset=2, size=3)],
                    {"x": xs})
        np.testing.assert_allclose(got, xs[:, 2:5], rtol=1e-6)

    def test_dotmul_and_scaling_projection_param_counts(self):
        x = _data("x", [2, 5])
        v2l.dotmul_projection(x)
        v2l.scaling_projection(x)
        shapes = sorted(
            tuple(v.shape) for v in
            fluid.default_startup_program().global_block().vars.values()
            if getattr(v, "persistable", False))
        assert (1,) in shapes and (5,) in shapes

    def test_trans_full_matrix_projection_shares_transposed_weight(self):
        x = _data("x", [2, 4])
        out = v2l.trans_full_matrix_projection(x, size=3,
                                               param_attr="shared_w")
        xs = RNG.randn(2, 4).astype(np.float32)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            wv = np.asarray(sc.find_var("shared_w"))
            got, = exe.run(feed={"x": xs}, fetch_list=[out])
        assert wv.shape == (3, 4)                    # stored [size, in]
        np.testing.assert_allclose(np.asarray(got), xs @ wv.T, rtol=1e-5)

    def test_table_projection_is_embedding(self):
        ids = fluid.layers.data(name="ids", shape=[4, 1], dtype="int64",
                                append_batch_size=False)
        out = v2l.table_projection(ids, size=3, vocab_size=10)
        got, = _run([out], {"ids": np.array([[1], [2], [3], [1]],
                                            np.int64)})
        assert got.shape[-1] == 3
        np.testing.assert_allclose(got[0], got[3], rtol=1e-6)  # same id

    def test_conv_projection_no_bias(self):
        img = _data("img", [1, 3, 8, 8])
        before = set(
            fluid.default_startup_program().global_block().vars)
        v2l.conv_projection(img, filter_size=3, num_filters=4, padding=1)
        new = [v for v in
               fluid.default_startup_program().global_block().vars
               if v not in before]
        assert len(new) == 1                         # weight only, no bias


class TestMiscWrappers:
    def test_maxid(self):
        x = _data("x", [3, 7])
        xs = RNG.randn(3, 7).astype(np.float32)
        got, = _run([v2l.maxid(x)], {"x": xs})
        np.testing.assert_array_equal(got[:, 0], xs.argmax(1))

    def test_clip_resize_pad(self):
        x = _data("x", [2, 6])
        img = _data("img", [1, 2, 3, 3])
        xs = RNG.randn(2, 6).astype(np.float32) * 3
        imgs = RNG.randn(1, 2, 3, 3).astype(np.float32)
        c, r, p = _run(
            [v2l.clip(x, min=-1.0, max=1.0), v2l.resize(x, 3),
             v2l.pad(img, pad_c=[1, 0], pad_h=[0, 2], pad_w=[1, 1])],
            {"x": xs, "img": imgs})
        np.testing.assert_allclose(c, np.clip(xs, -1, 1), rtol=1e-6)
        assert r.shape == (4, 3)
        assert p.shape == (1, 3, 5, 5)
        np.testing.assert_allclose(p[:, 1:, 0:3, 1:4], imgs, rtol=1e-6)

    def test_scale_shift_param_shapes(self):
        x = _data("x", [2, 4])
        out = v2l.scale_shift(x)
        xs = RNG.randn(2, 4).astype(np.float32)
        got, = _run([out], {"x": xs})
        assert got.shape == xs.shape                 # w*x+b, w/b scalars

    def test_prelu_negative_slope(self):
        x = _data("x", [2, 4])
        out = v2l.prelu(x)
        xs = np.array([[-2.0, -1.0, 1.0, 2.0]] * 2, np.float32)
        got, = _run([out], {"x": xs})
        # default alpha 0.25
        np.testing.assert_allclose(
            got, np.where(xs > 0, xs, 0.25 * xs), rtol=1e-5)

    def test_gated_unit(self):
        x = _data("x", [3, 5])
        out = v2l.gated_unit(x, size=4, act="tanh")
        xs = RNG.randn(3, 5).astype(np.float32)
        got, = _run([out], {"x": xs})
        assert got.shape == (3, 4)
        assert np.all(np.abs(got) <= 1.0)            # tanh * sigmoid bound

    def test_factorization_machine_oracle(self):
        n, d, f = 3, 5, 4
        x = _data("x", [n, d])
        out = v2l.factorization_machine(x, factor_size=f)
        xs = RNG.randn(n, d).astype(np.float32)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            wname = [p.name for p in fluid.default_main_program()
                     .global_block().all_parameters()][0]
            vv = np.asarray(sc.find_var(wname))
            got, = exe.run(feed={"x": xs}, fetch_list=[out])
        want = 0.5 * (((xs @ vv) ** 2).sum(1)
                      - ((xs ** 2) @ (vv ** 2)).sum(1))
        np.testing.assert_allclose(np.asarray(got)[:, 0], want,
                                   rtol=1e-4, atol=1e-5)


class TestCosts:
    def test_sum_cost(self):
        x = _data("x", [2, 3])
        xs = RNG.randn(2, 3).astype(np.float32)
        got, = _run([v2l.sum_cost(x)], {"x": xs})
        np.testing.assert_allclose(float(got.ravel()[0]), xs.sum(),
                                   rtol=1e-5)

    def test_smooth_l1_cost(self):
        x, y = _data("x", [2, 3]), _data("y", [2, 3])
        xs = RNG.randn(2, 3).astype(np.float32)
        ys = RNG.randn(2, 3).astype(np.float32)
        got, = _run([v2l.smooth_l1_cost(x, y)], {"x": xs, "y": ys})
        assert np.isfinite(float(got.ravel()[0]))

    def test_multi_binary_label_cross_entropy(self):
        p = _data("p", [2, 3])
        lab = _data("lab", [2, 3])
        probs = np.array([[0.9, 0.1, 0.5], [0.2, 0.8, 0.6]], np.float32)
        labs = np.array([[1, 0, 1], [0, 1, 0]], np.float32)
        got, = _run([v2l.multi_binary_label_cross_entropy(p, lab)],
                    {"p": probs, "lab": labs})
        want = -(labs * np.log(probs)
                 + (1 - labs) * np.log(1 - probs)).sum(1).mean()
        np.testing.assert_allclose(float(got.ravel()[0]), want, rtol=1e-4)

    def test_huber_classification_cost_regions(self):
        f = _data("f", [4, 1])
        lab = _data("lab", [4, 1])
        fv = np.array([[2.0], [0.5], [-2.0], [-0.5]], np.float32)
        # labels {0,1} -> y' {-1,+1}
        lv = np.array([[1], [1], [1], [0]], np.float32)
        got, = _run([v2l.huber_classification_cost(f, lab)],
                    {"f": fv, "lab": lv})
        # z = y'*f = [2, .5, -2, .5] -> [0, .25, 8, .25]
        want = np.mean([0.0, 0.25, 8.0, 0.25])
        np.testing.assert_allclose(float(got.ravel()[0]), want, rtol=1e-5)


class TestAdviceFixes:
    def test_initial_std_becomes_initializer(self):
        x = _data("x", [64, 10])
        v2l.fc(x, size=50, initial_std=0.5, initial_mean=2.0)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            wname = [v for v, var in fluid.default_startup_program()
                     .global_block().vars.items()
                     if getattr(var, "persistable", False)
                     and tuple(var.shape) == (10, 50)][0]
            w = np.asarray(sc.find_var(wname))
        assert abs(w.mean() - 2.0) < 0.2             # not default init
        assert 0.3 < w.std() < 0.7

    def test_learning_rate_kwarg_warns(self):
        x = _data("x", [2, 4])
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            v2l.fc(x, size=3, learning_rate=0.1)
        assert any("learning_rate" in str(w.message) for w in rec)

    def test_unknown_kwarg_still_raises(self):
        x = _data("x", [2, 4])
        with pytest.raises(TypeError):
            v2l.fc(x, size=3, bogus_kwarg=1)

    def test_recurrent_true_vanilla_parameter_count_and_oracle(self):
        """h_t = tanh(x_t + W h_{t-1} + b): exactly one [size, size] W and
        one [size] bias; matches a numpy scan."""
        size = 4
        x = fluid.layers.data(name="x", shape=[size], dtype="float32",
                              lod_level=1)
        out = v2l.recurrent(x)
        last = fluid.layers.sequence_last_step(out)
        params = [(n, tuple(v.shape)) for n, v in
                  fluid.default_startup_program().global_block()
                  .vars.items() if getattr(v, "persistable", False)]
        shapes = sorted(s for _, s in params)
        assert shapes == [(4,), (4, 4)], params
        xs = RNG.randn(6, size).astype(np.float32)
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu.executor import LoDTensor
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            wname = [n for n, s in params if s == (4, 4)][0]
            bname = [n for n, s in params if s == (4,)][0]
            w = np.asarray(sc.find_var(wname))
            b = np.asarray(sc.find_var(bname))
            got, = exe.run(feed={"x": LoDTensor(xs, [[0, 6]])},
                           fetch_list=[last])
        h = np.zeros(size, np.float32)
        for t in range(6):
            h = np.tanh(xs[t] + h @ w + b)
        np.testing.assert_allclose(np.asarray(got).ravel(), h, rtol=1e-4,
                                   atol=1e-5)


class TestNetworksTail:
    def test_bidirectional_gru_shapes(self):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32",
                              lod_level=1)
        out = v2n.bidirectional_gru(x, size=5)
        assert out.shape[-1] == 10

    def test_simple_attention_is_convex_combination(self):
        """The context vector lies in the convex hull of the encoder
        states (softmax weights sum to 1)."""
        from paddle_tpu.executor import LoDTensor
        h = 4
        enc = fluid.layers.data(name="enc", shape=[h], dtype="float32",
                                lod_level=1)
        proj = fluid.layers.data(name="proj", shape=[h], dtype="float32",
                                 lod_level=1)
        state = fluid.layers.data(name="state", shape=[1, h],
                                  dtype="float32",
                                  append_batch_size=False)
        ctx = v2n.simple_attention(enc, proj, state)
        ev = RNG.randn(5, h).astype(np.float32)
        pv = RNG.randn(5, h).astype(np.float32)
        sv = RNG.randn(1, h).astype(np.float32)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(fluid.default_startup_program())
            got, = exe.run(
                feed={"enc": LoDTensor(ev, [[0, 5]]),
                      "proj": LoDTensor(pv, [[0, 5]]),
                      "state": sv},
                fetch_list=[ctx])
        got = np.asarray(got).ravel()
        assert got.shape == (h,)
        lo, hi = ev.min(0), ev.max(0)
        assert np.all(got >= lo - 1e-5) and np.all(got <= hi + 1e-5)


class TestPloter:
    def test_ploter_collects_and_writes(self, tmp_path):
        from paddle_tpu.v2.plot import Ploter
        p = Ploter("train", "test")
        for i in range(5):
            p.append("train", i, 1.0 / (i + 1))
        p.append("test", 0, 0.5)
        out = tmp_path / "curve.png"
        p.plot(str(out))
        assert out.exists() and out.stat().st_size > 0
        p.reset()
        assert p.__plot_data__["train"].step == []

    def test_ploter_disabled(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DISABLE_PLOT", "True")
        from paddle_tpu.v2.plot.plot import Ploter
        p = Ploter("train")
        p.append("train", 0, 1.0)
        out = tmp_path / "curve.png"
        p.plot(str(out))                 # no-op when disabled
        assert not out.exists()
