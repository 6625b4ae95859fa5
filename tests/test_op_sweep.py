"""Per-op sweep: every registered op the rest of the suite does not already
exercise gets at least one OpTest here (reference discipline:
tests/unittests — 199 per-op files over op_test.py:212; coverage proven by
tools/op_coverage.py). Oracles are numpy; differentiable ops grad-check."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from op_test import OpTest

RNG = np.random.RandomState(33)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


# --- activations -------------------------------------------------------------
# (name, oracle, attrs, grad?, domain)
ACTIVATIONS = [
    ("logsigmoid", lambda x: np.log(_sigmoid(x)), {}, True, (-2, 2)),
    ("ceil", np.ceil, {}, False, (-2, 2)),
    ("floor", np.floor, {}, False, (-2, 2)),
    ("round", np.round, {}, False, (-2, 2)),
    ("tanh_shrink", lambda x: x - np.tanh(x), {}, True, (-2, 2)),
    ("softshrink", lambda x: np.where(x > 0.5, x - 0.5,
                                      np.where(x < -0.5, x + 0.5, 0)),
     {"lambda": 0.5}, True, (-2, 2)),
    ("hard_shrink", lambda x: np.where(np.abs(x) > 0.5, x, 0),
     {"threshold": 0.5}, True, (-2, 2)),
    ("brelu", lambda x: np.clip(x, -0.5, 0.8),
     {"t_min": -0.5, "t_max": 0.8}, True, (-2, 2)),
    ("leaky_relu", lambda x: np.where(x >= 0, x, 0.1 * x),
     {"alpha": 0.1}, True, (-2, 2)),
    ("soft_relu", lambda x: np.log1p(np.exp(np.clip(x, -3, 3))),
     {"threshold": 3.0}, True, (-2, 2)),
    ("elu", lambda x: np.where(x >= 0, x, 1.2 * (np.exp(x) - 1)),
     {"alpha": 1.2}, True, (-2, 2)),
    ("relu6", lambda x: np.clip(x, 0, 6), {}, True, (-2, 8)),
    ("pow", lambda x: np.power(x, 3.0), {"factor": 3.0}, True, (0.5, 2)),
    ("stanh", lambda x: 1.7159 * np.tanh(2.0 / 3.0 * x), {}, True, (-2, 2)),
    ("hard_sigmoid", lambda x: np.clip(0.2 * x + 0.5, 0, 1), {},
     True, (-2, 2)),
    ("swish", lambda x: x * _sigmoid(2.0 * x), {"beta": 2.0}, True, (-2, 2)),
    ("silu", lambda x: x * _sigmoid(x), {}, True, (-2, 2)),
    ("gelu", lambda x: x * 0.5 * (1 + np.vectorize(_erf)(x / np.sqrt(2))),
     {}, True, (-2, 2)),
    ("thresholded_relu", lambda x: np.where(x > 1.0, x, 0),
     {"threshold": 1.0}, True, (-3, 3)),
    ("sign", np.sign, {}, False, (-2, 2)),
]


def _erf(v):
    import math
    return math.erf(v)


class TestActivationSweep:
    @pytest.mark.parametrize("name,oracle,attrs,do_grad,domain",
                             ACTIVATIONS, ids=[a[0] for a in ACTIVATIONS])
    def test(self, name, oracle, attrs, do_grad, domain):
        lo, hi = domain
        x = RNG.uniform(lo, hi, (3, 4)).astype("float32")
        # keep numeric grads away from kinks/rounding cliffs
        for kink in (0.0, 0.5, -0.5, 1.0, -0.5, 0.8, 6.0):
            x[np.abs(x - kink) < 0.08] += 0.17
        t = OpTest()
        t.op_type = name
        t.inputs = {"X": x}
        t.attrs = dict(attrs)
        t.outputs = {"Out": oracle(x).astype("float32")}
        t.check_output(atol=1e-5)
        if do_grad:
            t.check_grad(["X"], "Out", max_relative_error=0.02)


# --- elementwise / compare / logical -----------------------------------------

class TestElementwisePow(OpTest):
    op_type = "elementwise_pow"

    def test(self):
        x = RNG.uniform(0.5, 2, (3, 4)).astype("float32")
        y = RNG.uniform(1, 3, (3, 4)).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": np.power(x, y)}
        self.check_output(rtol=1e-4)


class TestCompareOps:
    @pytest.mark.parametrize("op,fn", [
        ("equal", np.equal), ("not_equal", np.not_equal),
        ("less_equal", np.less_equal), ("greater_than", np.greater),
        ("greater_equal", np.greater_equal)])
    def test(self, op, fn):
        x = RNG.randint(0, 3, (2, 5)).astype("int32")
        y = RNG.randint(0, 3, (2, 5)).astype("int32")
        t = OpTest()
        t.op_type = op
        t.inputs = {"X": x, "Y": y}
        t.outputs = {"Out": fn(x, y)}
        t.check_output()


class TestLogicalOps:
    @pytest.mark.parametrize("op,fn", [
        ("logical_and", np.logical_and), ("logical_or", np.logical_or),
        ("logical_xor", np.logical_xor)])
    def test(self, op, fn):
        x = RNG.randint(0, 2, (6,)).astype(bool)
        y = RNG.randint(0, 2, (6,)).astype(bool)
        t = OpTest()
        t.op_type = op
        t.inputs = {"X": x, "Y": y}
        t.outputs = {"Out": fn(x, y)}
        t.check_output()


class TestClip(OpTest):
    op_type = "clip"

    def test(self):
        x = RNG.uniform(-2, 2, (3, 3)).astype("float32")
        x[np.abs(np.abs(x) - 0.7) < 0.1] = 0.0
        self.inputs = {"X": x}
        self.attrs = {"min": -0.7, "max": 0.7}
        self.outputs = {"Out": np.clip(x, -0.7, 0.7)}
        self.check_output()
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestClipByNorm(OpTest):
    op_type = "clip_by_norm"

    def test(self):
        x = RNG.uniform(-1, 1, (4, 3)).astype("float32") * 3
        norm = np.sqrt((x ** 2).sum())
        self.inputs = {"X": x}
        self.attrs = {"max_norm": 1.5}
        self.outputs = {"Out": x * (1.5 / max(norm, 1.5))}
        self.check_output(rtol=1e-4)


class TestFillZerosLike(OpTest):
    op_type = "fill_zeros_like"

    def test(self):
        x = RNG.rand(2, 3).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": np.zeros_like(x)}
        self.check_output()


# --- shape / data movement ---------------------------------------------------

class TestExpand(OpTest):
    op_type = "expand"

    def test(self):
        x = RNG.rand(2, 3).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"expand_times": [2, 3]}
        self.outputs = {"Out": np.tile(x, (2, 3))}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestGather(OpTest):
    op_type = "gather"

    def test(self):
        x = RNG.rand(6, 3).astype("float32")
        idx = np.array([0, 2, 5, 2], "int32")
        self.inputs = {"X": x, "Index": idx}
        self.outputs = {"Out": x[idx]}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestScatter(OpTest):
    op_type = "scatter"

    def test(self):
        x = RNG.rand(5, 3).astype("float32")
        ids = np.array([1, 3], "int32")
        upd = RNG.rand(2, 3).astype("float32")
        out = x.copy()
        out[ids] = upd
        self.inputs = {"X": x, "Ids": ids, "Updates": upd}
        self.outputs = {"Out": out}
        self.check_output()


class TestSplit:
    def test(self):
        x = RNG.rand(4, 6).astype("float32")
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            xv = fluid.layers.data(name="x", shape=[4, 6], dtype="float32",
                                   append_batch_size=False)
            a, b, c = fluid.layers.split(xv, 3, dim=1)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                ra, rb, rc = exe.run(fluid.default_main_program(),
                                     feed={"x": x}, fetch_list=[a, b, c])
        np.testing.assert_allclose(np.asarray(ra), x[:, :2])
        np.testing.assert_allclose(np.asarray(rb), x[:, 2:4])
        np.testing.assert_allclose(np.asarray(rc), x[:, 4:])


class TestSqueezeUnsqueeze:
    def test(self):
        x = RNG.rand(3, 1, 4).astype("float32")
        t = OpTest()
        t.op_type = "squeeze"
        t.inputs = {"X": x}
        t.attrs = {"axes": [1]}
        t.outputs = {"Out": x.reshape(3, 4)}
        t.check_output()
        t2 = OpTest()
        t2.op_type = "unsqueeze"
        t2.inputs = {"X": x.reshape(3, 4)}
        t2.attrs = {"axes": [0]}
        t2.outputs = {"Out": x.reshape(1, 3, 4)}
        t2.check_output()


class TestShapeOp(OpTest):
    op_type = "shape"

    def test(self):
        x = RNG.rand(3, 5, 2).astype("float32")
        self.inputs = {"Input": x}
        self.outputs = {"Out": np.array([3, 5, 2], "int32")}
        self.check_output()


class TestCumsum(OpTest):
    op_type = "cumsum"

    def test(self):
        x = RNG.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": np.cumsum(x, axis=1)}
        self.check_output(rtol=1e-5)
        self.check_grad(["X"], "Out")


class TestMultiplex(OpTest):
    op_type = "multiplex"

    def test(self):
        xs = [RNG.rand(4, 3).astype("float32") for _ in range(3)]
        ids = np.array([[0], [2], [1], [0]], "int32")
        out = np.stack([xs[int(i)][r] for r, i in enumerate(ids[:, 0])])
        self.inputs = {"X": [(f"mx_{i}", x) for i, x in enumerate(xs)],
                       "Ids": ids}
        self.outputs = {"Out": out}
        self.check_output()


class TestOneHot(OpTest):
    op_type = "one_hot"

    def test(self):
        x = np.array([[1], [0], [3]], "int64")
        out = np.zeros((3, 4), "float32")
        out[np.arange(3), x[:, 0]] = 1.0
        self.inputs = {"X": x.reshape(-1)}
        self.attrs = {"depth": 4}
        self.outputs = {"Out": out}
        self.check_output()


class TestArgMinMax:
    @pytest.mark.parametrize("op,fn", [("arg_max", np.argmax),
                                       ("arg_min", np.argmin)])
    def test(self, op, fn):
        x = RNG.rand(3, 5).astype("float32")
        t = OpTest()
        t.op_type = op
        t.inputs = {"X": x}
        t.attrs = {"axis": 1}
        t.outputs = {"Out": fn(x, axis=1).astype("int64")}
        t.check_output()


class TestPad(OpTest):
    op_type = "pad"

    def test(self):
        x = RNG.rand(2, 3).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"paddings": [1, 0, 0, 2], "pad_value": 0.5}
        self.outputs = {"Out": np.pad(x, ((1, 0), (0, 2)),
                                      constant_values=0.5)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestReduceMinProd:
    @pytest.mark.parametrize("op,fn", [("reduce_min", np.min),
                                       ("reduce_prod", np.prod)])
    def test(self, op, fn):
        x = (RNG.rand(3, 4).astype("float32") + 0.5)
        t = OpTest()
        t.op_type = op
        t.inputs = {"X": x}
        t.attrs = {"dim": [1]}
        t.outputs = {"Out": fn(x, axis=1)}
        t.check_output(rtol=1e-5)
        t.check_grad(["X"], "Out", max_relative_error=0.02)


# --- losses ------------------------------------------------------------------

class TestHingeLoss(OpTest):
    op_type = "hinge_loss"

    def test(self):
        logits = RNG.uniform(-2, 2, (6, 1)).astype("float32")
        logits[np.abs(np.abs(logits) - 1) < 0.1] = 0.0
        labels = RNG.randint(0, 2, (6, 1)).astype("float32")
        y = 2 * labels - 1
        self.inputs = {"Logits": logits, "Labels": labels}
        self.outputs = {"Loss": np.maximum(0, 1 - y * logits)}
        self.check_output()
        self.check_grad(["Logits"], "Loss", max_relative_error=0.02)


class TestHuberLoss(OpTest):
    op_type = "huber_loss"

    def test(self):
        x = RNG.uniform(-2, 2, (8, 1)).astype("float32")
        y = RNG.uniform(-2, 2, (8, 1)).astype("float32")
        d = 1.0
        r = y - x
        r[np.abs(np.abs(r) - d) < 0.1] *= 1.3
        x = (y - r).astype("float32")
        loss = np.where(np.abs(r) <= d, 0.5 * r * r,
                        d * (np.abs(r) - 0.5 * d))
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"delta": d}
        self.outputs = {"Residual": r, "Out": loss}
        self.check_output(no_check_set=("Residual",))
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestLogLoss(OpTest):
    op_type = "log_loss"

    def test(self):
        p = RNG.uniform(0.1, 0.9, (6, 1)).astype("float32")
        y = RNG.randint(0, 2, (6, 1)).astype("float32")
        eps = 1e-4
        loss = -y * np.log(p + eps) - (1 - y) * np.log(1 - p + eps)
        self.inputs = {"Predicted": p, "Labels": y}
        self.attrs = {"epsilon": eps}
        self.outputs = {"Loss": loss}
        self.check_output(rtol=1e-4)
        self.check_grad(["Predicted"], "Loss", max_relative_error=0.02)


class TestRankLoss(OpTest):
    op_type = "rank_loss"

    def test(self):
        left = RNG.uniform(-1, 1, (5, 1)).astype("float32")
        right = RNG.uniform(-1, 1, (5, 1)).astype("float32")
        label = RNG.randint(0, 2, (5, 1)).astype("float32")
        d = left - right
        loss = np.log1p(np.exp(d)) - label * d
        self.inputs = {"Left": left, "Right": right, "Label": label}
        self.outputs = {"Out": loss}
        self.check_output(rtol=1e-4)
        self.check_grad(["Left", "Right"], "Out", max_relative_error=0.02)


class TestMarginRankLoss(OpTest):
    op_type = "margin_rank_loss"

    def test(self):
        x1 = RNG.uniform(-1, 1, (5, 1)).astype("float32")
        x2 = RNG.uniform(-1, 1, (5, 1)).astype("float32")
        label = np.where(RNG.rand(5, 1) > 0.5, 1.0,
                         -1.0).astype("float32")
        m = 0.1
        act = -label * (x1 - x2) + m
        act[np.abs(act) < 0.05] += 0.12
        x1 = ((m - act) / -label + x2).astype("float32")
        loss = np.maximum(0, -label * (x1 - x2) + m)
        self.inputs = {"X1": x1, "X2": x2, "Label": label}
        self.attrs = {"margin": m}
        self.outputs = {"Out": loss}
        self.check_output(rtol=1e-4)
        self.check_grad(["X1", "X2"], "Out", max_relative_error=0.02)


class TestSmoothL1Loss(OpTest):
    op_type = "smooth_l1_loss"

    def test(self):
        x = RNG.uniform(-1.5, 1.5, (4, 3)).astype("float32")
        y = RNG.uniform(-1.5, 1.5, (4, 3)).astype("float32")
        d = x - y
        d[np.abs(np.abs(d) - 1.0) < 0.1] *= 1.25
        x = (y + d).astype("float32")
        ad = np.abs(d)
        el = np.where(ad < 1.0, 0.5 * d * d, ad - 0.5)
        out = el.sum(axis=1, keepdims=True)
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"sigma": 1.0}
        self.outputs = {"Out": out, "Diff": d}
        self.check_output(no_check_set=("Diff",))
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestSigmoidCEWithLogits(OpTest):
    op_type = "sigmoid_cross_entropy_with_logits"

    def test(self):
        x = RNG.uniform(-2, 2, (4, 3)).astype("float32")
        lbl = RNG.uniform(0, 1, (4, 3)).astype("float32")
        loss = np.maximum(x, 0) - x * lbl + np.log1p(np.exp(-np.abs(x)))
        self.inputs = {"X": x, "Label": lbl}
        self.outputs = {"Out": loss}
        self.check_output(rtol=1e-4)
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestSquaredL2:
    def test_norm(self):
        x = RNG.rand(3, 4).astype("float32")
        t = OpTest()
        t.op_type = "squared_l2_norm"
        t.inputs = {"X": x}
        t.outputs = {"Out": np.array([(x ** 2).sum()], "float32")}
        t.check_output(rtol=1e-5)
        t.check_grad(["X"], "Out", max_relative_error=0.02)

    def test_distance(self):
        x = RNG.rand(4, 3).astype("float32")
        y = RNG.rand(4, 3).astype("float32")
        t = OpTest()
        t.op_type = "squared_l2_distance"
        t.inputs = {"X": x, "Y": y}
        t.outputs = {"sub_result": x - y,
                     "Out": ((x - y) ** 2).sum(axis=1, keepdims=True)}
        t.check_output(no_check_set=("sub_result",), rtol=1e-5)
        t.check_grad(["X"], "Out", max_relative_error=0.02)


# --- NN ----------------------------------------------------------------------

class TestBilinearTensorProduct(OpTest):
    op_type = "bilinear_tensor_product"

    def test(self):
        b, m, n, o = 3, 4, 5, 2
        x = RNG.rand(b, m).astype("float32")
        y = RNG.rand(b, n).astype("float32")
        w = RNG.rand(o, m, n).astype("float32")
        bias = RNG.rand(1, o).astype("float32")
        out = np.einsum("bm,omn,bn->bo", x, w, y) + bias
        self.inputs = {"X": x, "Y": y, "Weight": w, "Bias": bias}
        self.outputs = {"Out": out}
        self.check_output(rtol=1e-4)
        self.check_grad(["X", "Y", "Weight"], "Out",
                        max_relative_error=0.02)


class TestLabelSmooth(OpTest):
    op_type = "label_smooth"

    def test(self):
        x = np.eye(4, dtype="float32")[RNG.randint(0, 4, 5)]
        eps = 0.1
        self.inputs = {"X": x}
        self.attrs = {"epsilon": eps}
        self.outputs = {"Out": (1 - eps) * x + eps / 4}
        self.check_output(rtol=1e-5)


class TestLrn(OpTest):
    op_type = "lrn"

    def test(self):
        x = RNG.rand(2, 6, 3, 3).astype("float32")
        n, k, alpha, beta = 5, 2.0, 1e-4, 0.75
        sq = np.zeros_like(x)
        c = x.shape[1]
        for i in range(c):
            lo, hi = max(0, i - n // 2), min(c, i + n // 2 + 1)
            sq[:, i] = (x[:, lo:hi] ** 2).sum(axis=1)
        out = x / (k + alpha * sq) ** beta
        self.inputs = {"X": x}
        self.attrs = {"n": n, "k": k, "alpha": alpha, "beta": beta}
        self.outputs = {"Out": out}
        self.check_output(rtol=1e-4)
        # lrn_grad is executed here and nowhere else: a test that only
        # BUILDS alexnet's backward registers the lazy grad op in its
        # worker, and tests/test_zz_op_coverage.py then asks for a run
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestKdaScan(OpTest):
    op_type = "kda_scan"

    def test(self):
        """The gated delta rule with a decay a channel against its
        recurrence token by token in numpy, 12 tokens in chunks of 4
        (tests/test_kda_moe.py holds the chunked form to the recurrence
        at the published decays; kda_scan_grad is executed here too)."""
        bsz, t, h, k, v = 1, 12, 2, 4, 3
        q, key, gate = (RNG.randn(bsz, t, h, k).astype("float32")
                        for _ in range(3))
        val = RNG.randn(bsz, t, h, v).astype("float32")
        a_log = np.log(RNG.uniform(1, 4, h)).astype("float32")
        dt_bias = RNG.randn(h * k).astype("float32")
        beta = RNG.randn(bsz, t, h).astype("float32")
        eps = 1e-6
        qn, kn = (x / np.sqrt((x * x).sum(-1, keepdims=True) + eps)
                  for x in (q, key))
        g = -np.exp(a_log)[:, None] * np.log1p(np.exp(
            gate + dt_bias.reshape(h, k)))
        out = np.zeros_like(val)
        state = np.zeros((bsz, h, k, v))
        for i in range(t):
            state = state * np.exp(g[:, i])[..., None]
            seen = np.einsum("bhk,bhkv->bhv", kn[:, i], state)
            state = state + _sigmoid(beta[:, i])[..., None, None] \
                * kn[:, i][..., None] * (val[:, i] - seen)[..., None, :]
            out[:, i] = np.einsum("bhk,bhkv->bhv", qn[:, i], state) / 2.0
        self.inputs = {"Q": q, "K": key, "V": val, "Gate": gate,
                       "ALog": a_log, "DtBias": dt_bias, "Beta": beta}
        self.attrs = {"chunk_size": 4, "epsilon": eps}
        self.outputs = {"Out": out.astype("float32")}
        self.check_output(rtol=1e-4, atol=1e-5)
        self.check_grad(["Q", "K", "V", "Gate", "Beta"], "Out",
                        max_relative_error=0.02)


class TestNormOp(OpTest):
    op_type = "norm"

    def test(self):
        x = RNG.rand(3, 4).astype("float32") + 0.1
        out = x / np.sqrt((x ** 2).sum(axis=1, keepdims=True) + 1e-10)
        self.inputs = {"X": x}
        self.attrs = {"axis": 1, "epsilon": 1e-10}
        self.outputs = {"Out": out}
        self.check_output(rtol=1e-4)
        self.check_grad(["X"], "Out", max_relative_error=0.02)


class TestAuc:
    def test_perfect_ranking(self):
        pred = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7], [0.6, 0.4]],
                        "float32")
        label = np.array([[1], [0], [1], [0]], "int64")
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            p = fluid.layers.data(name="p", shape=[4, 2], dtype="float32",
                                  append_batch_size=False)
            l = fluid.layers.data(name="l", shape=[4, 1], dtype="int64",
                                  append_batch_size=False)
            auc = fluid.layers.auc(p, l)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                out = exe.run(fluid.default_main_program(),
                              feed={"p": pred, "l": label},
                              fetch_list=[auc] if not isinstance(auc, tuple)
                              else [auc[0]])
        assert abs(float(np.asarray(out[0]).reshape(-1)[0]) - 1.0) < 0.02


# --- conv variants through layers -------------------------------------------

class TestConvVariants:
    def _run_conv(self, build, feed):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            out = build()
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup)
            return exe.run(main, feed=feed, fetch_list=[out])

    def test_conv2d_transpose_shape_and_values(self):
        x = np.ones((1, 2, 4, 4), "float32")

        def build():
            xv = fluid.layers.data(name="x", shape=[2, 4, 4],
                                   dtype="float32")
            return fluid.layers.conv2d_transpose(
                xv, num_filters=3, filter_size=2, stride=2,
                param_attr=fluid.ParamAttr(
                    name="ct_w",
                    initializer=fluid.initializer.Constant(0.5)),
                bias_attr=False)

        got, = self._run_conv(build, {"x": x})
        got = np.asarray(got)
        assert got.shape == (1, 3, 8, 8)
        # every output position receives exactly one kernel tap of each of
        # 2 input channels: 2 * 0.5 * 1 = 1.0
        np.testing.assert_allclose(got, np.ones_like(got), rtol=1e-5)

    def test_conv3d_matches_oracle(self):
        x = RNG.rand(1, 1, 3, 3, 3).astype("float32")
        w = RNG.rand(1, 1, 2, 2, 2).astype("float32")
        import itertools
        out = np.zeros((1, 1, 2, 2, 2), "float32")
        for d, h, ww in itertools.product(range(2), range(2), range(2)):
            out[0, 0, d, h, ww] = (x[0, 0, d:d+2, h:h+2, ww:ww+2] * w).sum()

        t = OpTest()
        t.op_type = "conv3d"
        t.inputs = {"Input": x, "Filter": w}
        t.attrs = {"strides": [1, 1, 1], "paddings": [0, 0, 0]}
        t.outputs = {"Output": out}
        t.check_output(rtol=1e-4)

    def test_depthwise_conv2d(self):
        x = RNG.rand(1, 2, 4, 4).astype("float32")
        w = RNG.rand(2, 1, 3, 3).astype("float32")
        out = np.zeros((1, 2, 2, 2), "float32")
        for c in range(2):
            for i in range(2):
                for j in range(2):
                    out[0, c, i, j] = (x[0, c, i:i+3, j:j+3] * w[c, 0]).sum()
        t = OpTest()
        t.op_type = "depthwise_conv2d"
        t.inputs = {"Input": x, "Filter": w}
        t.attrs = {"strides": [1, 1], "paddings": [0, 0], "groups": 2}
        t.outputs = {"Output": out}
        t.check_output(rtol=1e-4)


# --- RNN units ---------------------------------------------------------------

class TestRnnUnits:
    def test_gru_unit_trains(self):
        """gru_unit single step wired into a classifier converges."""
        B, D, H = 4, 6, 5
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[D], dtype="float32")
            h0 = fluid.layers.data(name="h", shape=[H], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            xp = fluid.layers.fc(input=x, size=3 * H)
            hidden, _, _ = fluid.layers.gru_unit(input=xp, hidden=h0,
                                                 size=3 * H)
            logits = fluid.layers.fc(input=hidden, size=3)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                feed = {"x": RNG.randn(B, D).astype("float32"),
                        "h": np.zeros((B, H), "float32"),
                        "y": RNG.randint(0, 3, (B, 1)).astype("int64")}
                first = None
                for _ in range(30):
                    v, = exe.run(fluid.default_main_program(), feed=feed,
                                 fetch_list=[loss])
                    first = first if first is not None else \
                        float(np.asarray(v).reshape(-1)[0])
        assert float(np.asarray(v).reshape(-1)[0]) < first * 0.5

    def test_lstm_unit_trains(self):
        B, D, H = 4, 6, 5
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[D], dtype="float32")
            h0 = fluid.layers.data(name="h", shape=[H], dtype="float32")
            c0 = fluid.layers.data(name="c", shape=[H], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h1, c1 = fluid.layers.lstm_unit(x, h0, c0)
            logits = fluid.layers.fc(input=h1, size=3)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                feed = {"x": RNG.randn(B, D).astype("float32"),
                        "h": np.zeros((B, H), "float32"),
                        "c": np.zeros((B, H), "float32"),
                        "y": RNG.randint(0, 3, (B, 1)).astype("int64")}
                first = None
                for _ in range(30):
                    v, = exe.run(fluid.default_main_program(), feed=feed,
                                 fetch_list=[loss])
                    first = first if first is not None else \
                        float(np.asarray(v).reshape(-1)[0])
        assert float(np.asarray(v).reshape(-1)[0]) < first * 0.5

    def test_lstmp_projection_shape(self):
        """dynamic_lstmp: projected output must have the projection size."""
        from paddle_tpu.executor import LoDTensor
        B_rows = [RNG.randn(3, 16).astype("float32"),
                  RNG.randn(2, 16).astype("float32")]
        offs = [0, 3, 5]
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[16], dtype="float32",
                                  lod_level=1)
            proj, cell = fluid.layers.dynamic_lstmp(
                input=x, size=16, proj_size=3)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                got, = exe.run(
                    fluid.default_main_program(),
                    feed={"x": LoDTensor(np.concatenate(B_rows), [offs])},
                    fetch_list=[proj], return_numpy=False)
        assert got.array().shape[-1] == 3


# --- misc --------------------------------------------------------------------

class TestIsEmpty(OpTest):
    op_type = "is_empty"

    def test(self):
        x = RNG.rand(3).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": np.array([False])}
        self.check_output()


class TestLodReset:
    def test(self):
        from paddle_tpu.executor import LoDTensor
        flat = RNG.rand(6, 2).astype("float32")
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[2], dtype="float32",
                                  lod_level=1)
            out = fluid.layers.lod_reset(x, target_lod=[0, 2, 6])
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                got, = exe.run(fluid.default_main_program(),
                               feed={"x": LoDTensor(flat, [[0, 3, 6]])},
                               fetch_list=[out], return_numpy=False)
        assert got.lod[0] == [0, 2, 6]
        np.testing.assert_allclose(got.array(), flat, rtol=1e-6)

    def test_print_op_passthrough(self):
        x = RNG.rand(2, 2).astype("float32")
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            xv = fluid.layers.data(name="x", shape=[2, 2], dtype="float32",
                                   append_batch_size=False)
            out = main.global_block().create_var(name="print_out",
                                                 dtype="float32")
            main.global_block().append_op(
                type="print", inputs={"In": [xv]}, outputs={"Out": [out]},
                attrs={"message": "sweep: "})
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                got, = exe.run(main, feed={"x": x}, fetch_list=[out])
        np.testing.assert_allclose(np.asarray(got), x)

    def test_shrink_rnn_memory_passthrough(self):
        x = RNG.rand(3, 4).astype("float32")
        t = OpTest()
        t.op_type = "shrink_rnn_memory"
        t.inputs = {"X": x}
        t.outputs = {"Out": x}
        t.check_output()


class TestRandomBatchSizeLike:
    @pytest.mark.parametrize("op", ["uniform_random_batch_size_like",
                                    "gaussian_random_batch_size_like"])
    def test(self, op):
        x = np.zeros((7, 3), "float32")
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            xv = fluid.layers.data(name="x", shape=[7, 3], dtype="float32",
                                   append_batch_size=False)
            out = main.global_block().create_var(name=f"{op}_out",
                                                 dtype="float32")
            main.global_block().append_op(
                type=op, inputs={"Input": [xv]}, outputs={"Out": [out]},
                attrs={"shape": [-1, 5], "min": -1.0, "max": 1.0,
                       "mean": 0.0, "std": 1.0})
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                got, = exe.run(main, feed={"x": x}, fetch_list=[out])
        got = np.asarray(got)
        assert got.shape == (7, 5)
        assert got.std() > 0.1


# --- optimizer ops vs numpy oracles ------------------------------------------

def _opt_run(opt, steps=2):
    """Run `steps` updates of a single 4-param weight under `opt`; return
    the weight trajectory and the (constant) gradient."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1, bias_attr=False,
                               param_attr=fluid.ParamAttr(name="ow"))
        loss = fluid.layers.mean(pred)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 4), "float32") * np.array([1., 2., 3., 4.])
    w0 = np.array([[0.5], [-0.3], [0.2], [0.1]], "float32")
    # d(mean(x @ w))/dw = mean over batch of x = [1,2,3,4]^T / 1
    grad = xs.mean(axis=0, keepdims=True).T
    scope = executor_mod.Scope()
    traj = [w0.copy()]
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        scope.set_var("ow", w0.copy())
        for _ in range(steps):
            exe.run(main, feed={"x": xs}, fetch_list=[loss])
            traj.append(np.asarray(scope.find_var("ow")).copy())
    return np.array(traj), grad


class TestOptimizerOracles:
    LR = 0.1

    def test_momentum(self):
        traj, g = _opt_run(fluid.optimizer.Momentum(self.LR, momentum=0.9))
        v = np.zeros_like(g)
        w = traj[0]
        for t in range(1, 3):
            v = 0.9 * v + g
            w = w - self.LR * v
            np.testing.assert_allclose(traj[t], w, rtol=1e-5, atol=1e-6)

    def test_adagrad(self):
        traj, g = _opt_run(fluid.optimizer.Adagrad(self.LR))
        m = np.zeros_like(g)
        w = traj[0]
        for t in range(1, 3):
            m = m + g * g
            w = w - self.LR * g / (np.sqrt(m) + 1e-6)
            np.testing.assert_allclose(traj[t], w, rtol=1e-5, atol=1e-6)

    def test_decayed_adagrad(self):
        traj, g = _opt_run(fluid.optimizer.DecayedAdagrad(self.LR,
                                                          decay=0.95))
        m = np.zeros_like(g)
        w = traj[0]
        for t in range(1, 3):
            m = 0.95 * m + 0.05 * g * g
            w = w - self.LR * g / (np.sqrt(m) + 1e-6)
            np.testing.assert_allclose(traj[t], w, rtol=1e-4, atol=1e-6)

    def test_adadelta(self):
        traj, g = _opt_run(fluid.optimizer.Adadelta(
            self.LR, epsilon=1e-6, rho=0.95))
        ag = np.zeros_like(g)
        au = np.zeros_like(g)
        w = traj[0]
        for t in range(1, 3):
            ag = 0.95 * ag + 0.05 * g * g
            upd = -np.sqrt((au + 1e-6) / (ag + 1e-6)) * g
            au = 0.95 * au + 0.05 * upd * upd
            # reference adadelta applies the raw update, no learning rate
            # (adadelta_op.cc)
            w = w + upd
            np.testing.assert_allclose(traj[t], w, rtol=1e-4, atol=1e-6)

    def test_adamax(self):
        traj, g = _opt_run(fluid.optimizer.Adamax(
            self.LR, beta1=0.9, beta2=0.999, epsilon=1e-8))
        m = np.zeros_like(g)
        u = np.zeros_like(g)
        w = traj[0]
        b1p = 1.0
        for t in range(1, 3):
            m = 0.9 * m + 0.1 * g
            u = np.maximum(0.999 * u, np.abs(g))
            b1p *= 0.9
            w = w - self.LR / (1 - b1p) * m / (u + 1e-8)
            np.testing.assert_allclose(traj[t], w, rtol=1e-4, atol=1e-6)

    def test_rmsprop(self):
        traj, g = _opt_run(fluid.optimizer.RMSProp(
            self.LR, rho=0.9, epsilon=1e-6, momentum=0.0))
        ms = np.zeros_like(g)
        mom = np.zeros_like(g)
        w = traj[0]
        for t in range(1, 3):
            ms = 0.9 * ms + 0.1 * g * g
            mom = 0.0 * mom + self.LR * g / np.sqrt(ms + 1e-6)
            w = w - mom
            np.testing.assert_allclose(traj[t], w, rtol=1e-4, atol=1e-6)

    def test_ftrl_runs_and_descends(self):
        traj, g = _opt_run(fluid.optimizer.Ftrl(self.LR), steps=3)
        assert not np.allclose(traj[0], traj[-1])

    def test_proximal_gd(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            pred = fluid.layers.fc(input=x, size=1, bias_attr=False,
                                   param_attr=fluid.ParamAttr(name="pw"))
            loss = fluid.layers.mean(pred)
            block = main.global_block()
        # append proximal ops directly (no python optimizer class for these)
        for op, extra in (("proximal_gd", {}),):
            t = OpTest()
            t.op_type = op
            w = np.array([0.5, -0.3, 0.2], "float32")
            g = np.array([0.1, 0.1, -0.2], "float32")
            lr = np.array([0.1], "float32")
            l1, l2 = 0.05, 0.05
            prox = w - 0.1 * g
            out = (np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * l1, 0)
                   / (1 + 0.1 * l2))
            t.inputs = {"Param": w, "Grad": g, "LearningRate": lr}
            t.attrs = {"l1": l1, "l2": l2}
            t.outputs = {"ParamOut": out}
            t.check_output(rtol=1e-5)

    def test_proximal_adagrad(self):
        w = np.array([0.5, -0.3, 0.2], "float32")
        g = np.array([0.1, 0.1, -0.2], "float32")
        m = np.array([0.01, 0.01, 0.01], "float32")
        lr, l1, l2 = 0.1, 0.05, 0.05
        m2 = m + g * g
        alr = lr / np.sqrt(m2)
        prox = w - alr * g
        out = (np.sign(prox) * np.maximum(np.abs(prox) - alr * l1, 0)
               / (1 + alr * l2))
        t = OpTest()
        t.op_type = "proximal_adagrad"
        t.inputs = {"Param": w, "Grad": g, "Moment": m,
                    "LearningRate": np.array([lr], "float32")}
        t.attrs = {"l1": l1, "l2": l2}
        t.outputs = {"ParamOut": out, "MomentOut": m2}
        t.check_output(rtol=1e-4)


class TestMaxout(OpTest):
    op_type = "maxout"

    def test(self):
        x = RNG.rand(2, 6, 3, 3).astype("float32")
        out = x.reshape(2, 3, 2, 3, 3).max(axis=2)
        self.inputs = {"X": x}
        self.attrs = {"groups": 2}
        self.outputs = {"Out": out}
        self.check_output()


class TestIm2Sequence(OpTest):
    op_type = "im2sequence"

    def test(self):
        x = RNG.rand(1, 2, 4, 4).astype("float32")
        kh = kw = 2
        rows = []
        for oh in range(3):
            for ow in range(3):
                # XLA patch layout: channel-major [C, kh, kw]
                rows.append(x[0, :, oh:oh+2, ow:ow+2].reshape(-1))
        self.inputs = {"X": x}
        self.attrs = {"kernels": [kh, kw], "strides": [1, 1]}
        self.outputs = {"Out": np.stack(rows)}
        self.check_output()


class TestRowConv(OpTest):
    op_type = "row_conv"

    def test(self):
        t, d, k = 5, 3, 2
        x = RNG.rand(t, d).astype("float32")
        w = RNG.rand(k + 1, d).astype("float32")
        out = np.zeros_like(x)
        for i in range(t):
            for j in range(k + 1):
                if i + j < t:
                    out[i] += x[i + j] * w[j]
        self.inputs = {"X": x, "Filter": w}
        self.outputs = {"Out": out}
        self.check_output(rtol=1e-5)
        self.check_grad(["X", "Filter"], "Out", max_relative_error=0.02)


class TestNce:
    def test_trains(self):
        """NCE loss over sampled negatives decreases with training
        (stochastic sampling — convergence, not an oracle)."""
        B, D, C = 8, 6, 20
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[D], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            cost = fluid.layers.nce(input=x, label=y, num_total_classes=C,
                                    num_neg_samples=5)
            loss = fluid.layers.mean(cost)
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                feed = {"x": RNG.randn(B, D).astype("float32"),
                        "y": RNG.randint(0, C, (B, 1)).astype("int64")}
                first = None
                for _ in range(40):
                    v, = exe.run(fluid.default_main_program(), feed=feed,
                                 fetch_list=[loss])
                    first = first if first is not None else \
                        float(np.asarray(v).reshape(-1)[0])
        assert float(np.asarray(v).reshape(-1)[0]) < first * 0.8
