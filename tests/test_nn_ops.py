"""Correctness + grad checks for conv/pool/norm/softmax/loss/embedding ops
(reference: tests/unittests/test_conv2d_op.py, test_pool2d_op.py,
test_batch_norm_op.py, test_layer_norm_op.py, test_softmax_op.py,
test_cross_entropy_op.py, test_lookup_table_op.py …)."""

import numpy as np
import pytest

from op_test import OpTest


def _ref_conv2d(x, w, stride, pad):
    n, c, h, wd = x.shape
    co, ci, kh, kw = w.shape
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    out = np.zeros((n, co, oh, ow), dtype=np.float64)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh,
                       j * stride:j * stride + kw]
            out[:, :, i, j] = np.tensordot(patch, w, axes=([1, 2, 3],
                                                           [1, 2, 3]))
    return out


class TestConv2d(OpTest):
    op_type = "conv2d"

    def setup(self, stride=1, pad=1):
        rng = np.random.RandomState(11)
        x = rng.uniform(-1, 1, (2, 3, 6, 6)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 3, 3, 3)).astype(np.float32)
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {"strides": [stride, stride], "paddings": [pad, pad],
                      "dilations": [1, 1], "groups": 1}
        self.outputs = {"Output": _ref_conv2d(
            x.astype(np.float64), w.astype(np.float64), stride,
            pad).astype(np.float32)}

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-4)

    def test_output_stride2(self):
        self.setup(stride=2, pad=0)
        self.check_output(atol=1e-4)

    def test_grad(self):
        self.setup()
        self.check_grad(["Input", "Filter"], "Output",
                        max_relative_error=0.03)


class TestPool2d(OpTest):
    op_type = "pool2d"

    def _ref_pool(self, x, k, s, ptype):
        n, c, h, w = x.shape
        oh = (h - k) // s + 1
        ow = (w - k) // s + 1
        out = np.zeros((n, c, oh, ow), dtype=x.dtype)
        for i in range(oh):
            for j in range(ow):
                win = x[:, :, i * s:i * s + k, j * s:j * s + k]
                out[:, :, i, j] = win.max((2, 3)) if ptype == "max" \
                    else win.mean((2, 3))
        return out

    def setup(self, ptype="max"):
        rng = np.random.RandomState(12)
        x = rng.uniform(-1, 1, (2, 3, 6, 6)).astype(np.float32)
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": ptype, "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0],
                      "global_pooling": False}
        self.outputs = {"Out": self._ref_pool(x, 2, 2, ptype)}

    def test_max(self):
        self.setup("max")
        self.check_output()

    def test_avg(self):
        self.setup("avg")
        self.check_output()

    def test_avg_grad(self):
        self.setup("avg")
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestSoftmax(OpTest):
    op_type = "softmax"

    def setup(self):
        rng = np.random.RandomState(13)
        x = rng.uniform(-2, 2, (5, 7)).astype(np.float32)
        e = np.exp(x - x.max(-1, keepdims=True))
        self.inputs = {"X": x}
        self.outputs = {"Out": (e / e.sum(-1, keepdims=True)).astype(
            np.float32)}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestCrossEntropy(OpTest):
    op_type = "cross_entropy"

    def setup(self):
        rng = np.random.RandomState(14)
        logits = rng.uniform(0.1, 1.0, (6, 4)).astype(np.float32)
        probs = logits / logits.sum(-1, keepdims=True)
        label = rng.randint(0, 4, (6, 1)).astype(np.int64)
        loss = -np.log(probs[np.arange(6), label.ravel()]).reshape(6, 1)
        self.inputs = {"X": probs, "Label": label}
        self.outputs = {"Y": loss.astype(np.float32)}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Y", max_relative_error=0.05,
                        no_grad_set={"Label"})


class TestSoftmaxWithCE(OpTest):
    op_type = "softmax_with_cross_entropy"

    def setup(self):
        rng = np.random.RandomState(15)
        logits = rng.uniform(-2, 2, (6, 5)).astype(np.float32)
        label = rng.randint(0, 5, (6, 1)).astype(np.int64)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(6), label.ravel()]).reshape(6, 1)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm.astype(np.float32),
                        "Loss": loss.astype(np.float32)}

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.setup()
        self.check_grad(["Logits"], "Loss", max_relative_error=0.02,
                        no_grad_set={"Label"})


# (logits shape, label shape, label dtype, logits dtype, padded, soft,
# Softmax read downstream): 1031 = 50257 cut to a class count that no
# 128-lane tile divides
SWCE_CASES = {
    "rank2-label_n1-int64": ((6, 1031), (6, 1), "int64", "float32", 0, 0, 0),
    "rank2-label_n-int32": ((6, 1031), (6,), "int32", "float32", 0, 0, 0),
    "rank2-130x257": ((130, 257), (130, 1), "int64", "float32", 0, 0, 0),
    "rank3-label_bt-int64": ((2, 5, 1031), (2, 5), "int64", "float32",
                             0, 0, 0),
    "rank3-label_bt1-int32": ((2, 5, 1031), (2, 5, 1), "int32", "float32",
                              0, 0, 0),
    "rank2-bf16": ((6, 1031), (6, 1), "int64", "bfloat16", 0, 0, 0),
    "rank3-bf16": ((2, 5, 1031), (2, 5), "int64", "bfloat16", 0, 0, 0),
    "rank3-padded": ((3, 8, 1031), (3, 8, 1), "int64", "float32", 1, 0, 0),
    "rank3-padded-bf16": ((3, 8, 1031), (3, 8, 1), "int64", "bfloat16",
                          1, 0, 0),
    "rank2-softmax_read": ((6, 1031), (6, 1), "int64", "float32", 0, 0, 1),
    "rank3-softmax_read-bf16": ((2, 5, 1031), (2, 5), "int64", "bfloat16",
                                0, 0, 1),
    "soft-rank2": ((6, 1031), (6, 1031), "float32", "float32", 0, 1, 0),
    "soft-rank2-bf16": ((6, 1031), (6, 1031), "float32", "bfloat16",
                        0, 1, 0),
    "soft-rank2-softmax_read": ((6, 1031), (6, 1031), "float32", "float32",
                                0, 1, 1),
}


@pytest.mark.parametrize("case", list(SWCE_CASES))
def test_softmax_with_cross_entropy_against_dense(case):
    """Loss, Softmax and the Logits gradient under a non-uniform
    cotangent, against the dense formula in numpy float64 on the logits
    as the op receives them. Hard labels take the op's own rule
    (log-sum-exp and the label's logit, no log-probabilities), soft
    labels the dense path; both must read the same numbers, also where
    the objective reads the Softmax output as well."""
    import ml_dtypes
    import paddle_tpu as fluid
    from paddle_tpu.executor import LoDTensor

    shape, lshape, ldtype, dtype, padded, soft, reads = SWCE_CASES[case]
    rng = np.random.RandomState(31)
    x = rng.uniform(-4, 4, shape).astype(np.float32)
    classes = shape[-1]
    if soft:
        lab = rng.uniform(0, 1, lshape).astype(np.float32)
        lab /= lab.sum(-1, keepdims=True)
    else:
        lab = rng.randint(0, classes, lshape).astype(ldtype)
        lab.reshape(-1)[:2] = [0, classes - 1]      # both ends of the row
    w = rng.uniform(0.5, 2, shape[:-1] + (1,)).astype(np.float32)
    v = rng.uniform(-1, 1, shape).astype(np.float32) * reads
    lengths = [8, 2, 5] if padded else None

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        # a padded feed declares its features; [batch, time] come with it
        xv = fluid.layers.data(
            name="x", shape=[classes] if padded else list(shape),
            dtype="float32", append_batch_size=bool(padded),
            lod_level=int(padded), stop_gradient=False)
        lv = fluid.layers.data(name="lab", shape=list(lshape), dtype=ldtype,
                               append_batch_size=False)
        wv = fluid.layers.data(name="w", shape=list(w.shape),
                               dtype="float32", append_batch_size=False)
        logits = fluid.layers.cast(xv, dtype) if dtype != "float32" else xv
        helper = fluid.layer_helper.LayerHelper("softmax_with_cross_entropy")
        softmax = helper.create_tmp_variable(dtype="float32")
        loss = helper.create_tmp_variable(dtype="float32")
        helper.append_op(type="softmax_with_cross_entropy",
                         inputs={"Logits": [logits], "Label": [lv]},
                         outputs={"Softmax": [softmax], "Loss": [loss]},
                         attrs={"soft_label": bool(soft)})
        total = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(loss, wv))
        if reads:
            vv = fluid.layers.data(name="v", shape=list(shape),
                                   dtype="float32", append_batch_size=False)
            total = fluid.layers.elementwise_add(
                total, fluid.layers.reduce_sum(
                    fluid.layers.elementwise_mul(softmax, vv)))
        fluid.append_backward(total)
    feed_x = x
    if padded:
        offs = np.concatenate([[0], np.cumsum(lengths)])
        feed_x = LoDTensor(np.concatenate(
            [x[i, :n] for i, n in enumerate(lengths)]), [list(offs)])
    got_loss, got_sm, got_grad, got_total = fluid.Executor(
        fluid.CPUPlace()).run(
            main, feed={"x": feed_x, "lab": lab, "w": w, "v": v},
            fetch_list=[loss.name, softmax.name,
                        fluid.framework.grad_var_name(logits.name),
                        total.name])

    mask = np.ones(shape[:-1] + (1,))
    if padded:
        mask = (np.arange(shape[1])[None, :] <
                np.asarray(lengths)[:, None]).astype(np.float64)[..., None]
        x = x * mask.astype(np.float32)          # the padding is zeros
    bf16 = dtype == "bfloat16"
    seen = x.astype(ml_dtypes.bfloat16) if bf16 else x
    l64 = seen.astype(np.float64)
    e = np.exp(l64 - l64.max(-1, keepdims=True))
    sm = e / e.sum(-1, keepdims=True)
    if soft:
        target = lab.astype(np.float64)
    else:
        target = np.eye(classes)[lab.reshape(shape[:-1])]
    want_loss = -(target * np.log(sm)).sum(-1, keepdims=True) * mask
    want_grad = (sm * target.sum(-1, keepdims=True) - target) * (w * mask)
    want_grad += sm * (v - (sm * v).sum(-1, keepdims=True))
    # a padded position's loss is zero, so it adds nothing downstream
    np.testing.assert_allclose(
        got_total, [(want_loss * w).sum() + (sm * v).sum()], rtol=1e-5)
    if padded:      # a sequence fetch comes back packed: the valid rows
        want_loss, sm, want_grad, v = (
            np.concatenate([a[i, :n] for i, n in enumerate(lengths)])
            for a in (want_loss, sm, want_grad, v))

    assert got_loss.dtype == np.float32 and got_sm.dtype == np.float32
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got_sm, sm, rtol=2e-5, atol=1e-9)
    if bf16:
        # the gradient comes in the logits' dtype: the dense rule's
        # float32 value cast once, so within one bf16 ulp (2**-8) of it.
        # A Softmax read as well reaches the logits as a second bf16
        # term (sm * v; the part through lse rides the rule's own), and
        # the two are added in bf16: an ulp of each and of the sum
        assert got_grad.dtype == ml_dtypes.bfloat16
        ulps = np.abs(want_grad) + reads * (
            np.abs(sm * v) + np.abs(want_grad - sm * v))
        assert (np.abs(got_grad.astype(np.float64) - want_grad)
                <= 2.0 ** -8 * ulps).all()
    else:
        assert got_grad.dtype == np.float32
        np.testing.assert_allclose(got_grad, want_grad, rtol=2e-5,
                                   atol=1e-8)


def test_output_without_cotangent_leaves_the_backward(monkeypatch):
    """The generic gradient op differentiates only the outputs a
    cotangent arrives for. smooth_l1_loss's Diff is read by nothing here;
    its lowering is swapped for one whose Diff has an infinite slope at
    the fed 0 and says when its pullback is traced: it never is, and the
    gradient holds no 0 * inf."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.ops import registry

    pulled = []

    @jax.custom_vjp
    def watched(x):
        return jnp.sqrt(x)

    def watched_bwd(x, g):
        pulled.append(x.shape)
        return (g * 0.5 / jnp.sqrt(x),)

    watched.defvjp(lambda x: (jnp.sqrt(x), x), watched_bwd)

    def lower(ctx, op_, ins):
        x = jnp.asarray(ins["X"][0])
        return {"Out": [jnp.sum(2.0 * x, axis=1, keepdims=True)],
                "Diff": [watched(x)]}

    monkeypatch.setattr(registry.get("smooth_l1_loss"), "lower", lower)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 3], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        y = fluid.layers.data(name="y", shape=[4, 3], dtype="float32",
                              append_batch_size=False)
        fluid.append_backward(fluid.layers.mean(fluid.layers.smooth_l1(x, y)))
    zeros = np.zeros((4, 3), np.float32)
    grad, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": zeros, "y": zeros},
        fetch_list=[fluid.framework.grad_var_name("x")])
    assert pulled == []
    np.testing.assert_array_equal(grad, np.full((4, 3), 0.5, np.float32))


class TestLookupTable(OpTest):
    op_type = "lookup_table"

    def setup(self):
        rng = np.random.RandomState(16)
        w = rng.uniform(-1, 1, (10, 4)).astype(np.float32)
        ids = rng.randint(0, 10, (5, 1)).astype(np.int64)
        self.inputs = {"W": w, "Ids": ids}
        self.attrs = {}
        self.outputs = {"Out": w[ids.ravel()]}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["W"], "Out", max_relative_error=0.02,
                        no_grad_set={"Ids"})


class TestBatchNormTrain(OpTest):
    op_type = "batch_norm"

    def setup(self):
        rng = np.random.RandomState(17)
        x = rng.uniform(-1, 1, (3, 4, 2, 2)).astype(np.float32)
        scale = rng.uniform(0.5, 1.5, (4,)).astype(np.float32)
        bias = rng.uniform(-0.3, 0.3, (4,)).astype(np.float32)
        mean = np.zeros(4, np.float32)
        var = np.ones(4, np.float32)
        eps, mom = 1e-5, 0.9
        bm = x.mean((0, 2, 3))
        bv = x.var((0, 2, 3))
        y = (x - bm.reshape(1, 4, 1, 1)) / np.sqrt(
            bv.reshape(1, 4, 1, 1) + eps) * scale.reshape(1, 4, 1, 1) \
            + bias.reshape(1, 4, 1, 1)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": mean, "Variance": var}
        self.attrs = {"epsilon": eps, "momentum": mom, "is_test": False}
        self.outputs = {
            "Y": y.astype(np.float32),
            "MeanOut": (mean * mom + bm * (1 - mom)).astype(np.float32),
            "VarianceOut": (var * mom + bv * (1 - mom)).astype(np.float32),
            "SavedMean": bm.astype(np.float32),
            "SavedVariance": bv.astype(np.float32),
        }

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-4)

    def test_grad(self):
        self.setup()
        self.check_grad(["X", "Scale", "Bias"], "Y",
                        max_relative_error=0.05,
                        no_grad_set={"Mean", "Variance"})


class TestLayerNorm(OpTest):
    op_type = "layer_norm"

    def setup(self):
        rng = np.random.RandomState(18)
        x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
        scale = rng.uniform(0.5, 1.5, (6,)).astype(np.float32)
        bias = rng.uniform(-0.3, 0.3, (6,)).astype(np.float32)
        eps = 1e-5
        mean = x.mean(1, keepdims=True)
        var = x.var(1, keepdims=True)
        y = (x - mean) / np.sqrt(var + eps) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.attrs = {"epsilon": eps, "begin_norm_axis": 1}
        self.outputs = {"Y": y.astype(np.float32),
                        "Mean": mean.ravel().astype(np.float32),
                        "Variance": var.ravel().astype(np.float32)}

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-4)

    def test_grad(self):
        self.setup()
        self.check_grad(["X", "Scale", "Bias"], "Y",
                        max_relative_error=0.05)


class TestTopKAccuracy(OpTest):
    op_type = "top_k"

    def test_output(self):
        rng = np.random.RandomState(19)
        x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
        k = 2
        idx = np.argsort(-x, axis=1)[:, :k]
        vals = np.take_along_axis(x, idx, axis=1)
        self.inputs = {"X": x}
        self.attrs = {"k": k}
        self.outputs = {"Out": vals, "Indices": idx.astype(np.int64)}
        self.check_output()


def test_dropout_train_eval():
    import paddle_tpu as fluid
    x = fluid.layers.data(name="x", shape=[100], dtype="float32")
    out_train = fluid.layers.dropout(x, dropout_prob=0.3, is_test=False)
    out_eval = fluid.layers.dropout(x, dropout_prob=0.3, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((10, 100), np.float32)
    tr, ev = exe.run(fluid.default_main_program(), feed={"x": xs},
                     fetch_list=[out_train, out_eval])
    # eval mode scales by (1-p); train mode zeroes ~p of entries
    np.testing.assert_allclose(ev, xs * 0.7, rtol=1e-6)
    frac_zero = (tr == 0).mean()
    assert 0.15 < frac_zero < 0.45
    assert set(np.unique(tr)) <= {0.0, 1.0}


class TestHsigmoid:
    def test_cost_matches_manual_and_trains(self):
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod
        num_classes = 10
        x = fluid.layers.data(name="x", shape=[8], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        label = fluid.layers.data(name="hl", shape=[1], dtype="int64",
                                  append_batch_size=False)
        cost = fluid.layers.hsigmoid(
            x, label, num_classes,
            param_attr=fluid.ParamAttr(name="hs_w"),
            bias_attr=fluid.ParamAttr(name="hs_b"))
        loss = fluid.layers.mean(cost)
        fluid.backward.append_backward(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        rng = np.random.RandomState(0)
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            w = (rng.randn(num_classes - 1, 8) * 0.3).astype(np.float32)
            b = (rng.randn(1, num_classes - 1) * 0.1).astype(np.float32)
            sc.set_var("hs_w", w)
            sc.set_var("hs_b", b)
            xv = rng.randn(4, 8).astype(np.float32)
            lv = np.array([[3], [0], [9], [5]], np.int64)
            block = fluid.default_main_program().global_block()
            cv, gx = exe.run(fluid.default_main_program(),
                             feed={"x": xv, "hl": lv},
                             fetch_list=[cost, block.var("x@GRAD")])
        # manual reference: walk the SimpleCode tree per sample
        def manual(xr, lab):
            c = int(lab) + num_classes
            total, j = 0.0, 0
            while (c >> (j + 1)) >= 1:
                idx = (c >> (j + 1)) - 1
                bit = (c >> j) & 1
                pre = float(xr @ w[idx] + b[0, idx])
                total += np.logaddexp(0.0, pre) - bit * pre
                j += 1
            return total
        want = [manual(xv[i], lv[i, 0]) for i in range(4)]
        np.testing.assert_allclose(np.ravel(cv), want, rtol=1e-5)
        assert np.abs(gx).sum() > 0      # differentiable

    def test_probabilities_normalize(self):
        """sum_c P(c) = 1 under the tree factorization: exp(-cost) summed
        over all labels must be ~1 for any x."""
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod
        num_classes = 8
        x = fluid.layers.data(name="x", shape=[4], dtype="float32",
                              append_batch_size=False)
        label = fluid.layers.data(name="hl", shape=[1], dtype="int64",
                                  append_batch_size=False)
        cost = fluid.layers.hsigmoid(x, label, num_classes,
                                     bias_attr=False)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        rng = np.random.RandomState(1)
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            xv = np.repeat(rng.randn(1, 4).astype(np.float32),
                           num_classes, axis=0)
            lv = np.arange(num_classes, dtype=np.int64)[:, None]
            cv, = exe.run(fluid.default_main_program(),
                          feed={"x": xv, "hl": lv}, fetch_list=[cost])
        probs = np.exp(-np.ravel(cv))
        np.testing.assert_allclose(probs.sum(), 1.0, rtol=1e-4)


class TestBilinearInterp:
    def test_matches_manual_align_corners(self):
        import paddle_tpu as fluid
        x = fluid.layers.data(name="x", shape=[1, 2, 2], dtype="float32")
        up = fluid.layers.bilinear_interp(x, out_h=3, out_w=3)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.array([[[[0.0, 1.0], [2.0, 3.0]]]], np.float32)
        r, = exe.run(feed={"x": xv}, fetch_list=[up])
        want = np.array([[0.0, 0.5, 1.0], [1.0, 1.5, 2.0],
                         [2.0, 2.5, 3.0]], np.float32)
        np.testing.assert_allclose(r[0, 0], want, rtol=1e-6)

    def test_gradient_flows(self):
        import paddle_tpu as fluid
        x = fluid.layers.data(name="x", shape=[1, 2, 2], dtype="float32",
                              stop_gradient=False)
        up = fluid.layers.bilinear_interp(x, out_h=4, out_w=4)
        loss = fluid.layers.reduce_sum(up)
        fluid.backward.append_backward(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        block = fluid.default_main_program().global_block()
        g, = exe.run(feed={"x": np.ones((1, 1, 2, 2), np.float32)},
                     fetch_list=[block.var("x@GRAD")])
        # conservation: sum of grads equals number of output elements
        np.testing.assert_allclose(g.sum(), 16.0, rtol=1e-5)


class TestSelectiveFC:
    def test_masked_columns_zero_and_match_fc(self):
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        sel = fluid.layers.data(name="sel", shape=[6], dtype="float32")
        out = fluid.layers.selective_fc(
            x, sel, size=6, param_attr=fluid.ParamAttr(name="sfc_w"),
            bias_attr=fluid.ParamAttr(name="sfc_b"))
        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        rng = np.random.RandomState(0)
        with executor_mod.scope_guard(sc):
            exe.run(fluid.default_startup_program())
            w = rng.randn(4, 6).astype(np.float32)
            b = rng.randn(6).astype(np.float32)
            sc.set_var("sfc_w", w)
            sc.set_var("sfc_b", b)
            xv = rng.randn(3, 4).astype(np.float32)
            sv = (rng.rand(3, 6) < 0.5).astype(np.float32)
            r, = exe.run(feed={"x": xv, "sel": sv}, fetch_list=[out])
        np.testing.assert_allclose(r, (xv @ w + b) * sv, rtol=1e-5)
