"""Control-flow lowering tests: While/lax.while_loop, tensor arrays,
conditional blocks, Switch, IfElse, StaticRNN/DynamicRNN scan lowering
(reference: tests/unittests/test_while_op.py, test_dyn_rnn.py,
test_mnist_if_else_op.py)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.executor import LoDTensor


def make_lod(rows):
    flat = np.concatenate(rows, axis=0)
    offs = [0]
    for r in rows:
        offs.append(offs[-1] + len(r))
    return LoDTensor(flat, [offs])


def run_prog(feed, fetch, **kw):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe.run(fluid.default_main_program(), feed=feed,
                   fetch_list=fetch, **kw)


class TestWhile:
    def test_counter_sum(self):
        """sum integers 0..9 with a while loop."""
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64", value=10)
        acc = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = fluid.layers.less_than(x=i, y=limit)
        w = fluid.layers.While(cond=cond)
        with w.block():
            casted = fluid.layers.cast(i, "float32")
            new_acc = fluid.layers.elementwise_add(acc, casted)
            fluid.layers.assign(new_acc, acc)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        res, = run_prog({}, [acc])
        assert float(res[0]) == sum(range(10))

    def test_array_accumulate(self):
        """write i^2 into a tensor array inside the loop, read back after."""
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64", value=5)
        seed = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        arr = fluid.layers.array_write(seed, i, capacity=8)
        cond = fluid.layers.less_than(x=i, y=limit)
        w = fluid.layers.While(cond=cond)
        with w.block():
            fi = fluid.layers.cast(i, "float32")
            sq = fluid.layers.elementwise_mul(fi, fi)
            fluid.layers.array_write(sq, i, array=arr)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        third = fluid.layers.array_read(arr, fluid.layers.fill_constant(
            shape=[1], dtype="int64", value=3))
        ln = fluid.layers.array_length(arr)
        res, n = run_prog({}, [third, ln])
        assert float(res[0]) == 9.0
        assert int(n[0]) == 5


class TestConditionalBlock:
    def test_scalar_cond(self):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        flag = fluid.layers.data(name="flag", shape=[1], dtype="float32",
                                 append_batch_size=False)
        zero = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        out = fluid.layers.fill_constant(shape=[1], dtype="float32", value=-1.0)
        cond = fluid.layers.less_than(x=zero, y=flag)
        cb = fluid.layers.ConditionalBlock([cond], is_scalar_condition=True)
        with cb.block():
            s = fluid.layers.reduce_sum(x)
            fluid.layers.assign(s, out)
        xs = np.ones((2, 4), np.float32)
        r_true, = run_prog({"x": xs, "flag": np.array([1.0], np.float32)},
                           [out])
        assert float(r_true[0]) == 8.0
        r_false, = run_prog({"x": xs, "flag": np.array([-1.0], np.float32)},
                            [out])
        assert float(r_false[0]) == -1.0


class TestSwitch:
    def test_lr_warmup_style(self):
        step = fluid.layers.data(name="step", shape=[1], dtype="float32",
                                 append_batch_size=False)
        lr = fluid.layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        warmup = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                            value=100.0)
        with fluid.layers.Switch() as switch:
            with switch.case(fluid.layers.less_than(step, warmup)):
                v = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                               value=0.01)
                fluid.layers.assign(v, lr)
            with switch.default():
                v = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                               value=0.1)
                fluid.layers.assign(v, lr)
        r1, = run_prog({"step": np.array([10.0], np.float32)}, [lr])
        assert abs(float(r1[0]) - 0.01) < 1e-7
        r2, = run_prog({"step": np.array([200.0], np.float32)}, [lr])
        assert abs(float(r2[0]) - 0.1) < 1e-7


class TestIfElse:
    def test_row_select(self):
        x = fluid.layers.data(name="x", shape=[1], dtype="float32")
        zero = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                          value=0.0)
        cond = fluid.layers.less_than(x=x, y=zero)
        ie = fluid.layers.IfElse(cond)
        with ie.true_block():
            neg = fluid.layers.scale(ie.input(x), scale=-1.0)
            ie.output(neg)
        with ie.false_block():
            ie.output(ie.input(x))
        out = ie()
        xs = np.array([[-2.0], [3.0], [-5.0]], np.float32)
        res, = run_prog({"x": xs}, [out])
        np.testing.assert_allclose(res, np.abs(xs))


class TestStaticRNN:
    def test_cumsum_recurrence(self):
        """h_t = h_{t-1} + x_t over a fixed-length sequence."""
        x = fluid.layers.data(name="x", shape=[3], dtype="float32",
                              lod_level=1)
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            h = rnn.memory(shape=[3], value=0.0)
            nh = fluid.layers.elementwise_add(h, xt)
            rnn.update_memory(h, nh)
            rnn.step_output(nh)
        out = rnn()
        rows = [np.ones((4, 3), np.float32), np.ones((2, 3), np.float32)]
        res, = run_prog({"x": make_lod(rows)}, [out])
        # packed output: seq0 rows cumsum 1..4, seq1 rows 1..2
        np.testing.assert_allclose(res[:4, 0], [1, 2, 3, 4])
        np.testing.assert_allclose(res[4:, 0], [1, 2])


class TestDynamicRNNTrains:
    def test_convergence(self):
        """DynamicRNN-built GRU-ish cell trains on the vocab-split task."""
        x = fluid.layers.data(name="x", shape=[8], dtype="float32",
                              lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        rnn = fluid.layers.DynamicRNN()
        with rnn.block():
            xt = rnn.step_input(x)
            h = rnn.memory(shape=[16], value=0.0)
            concat = fluid.layers.concat([xt, h], axis=1)
            nh = fluid.layers.fc(input=concat, size=16, act="tanh")
            rnn.update_memory(h, nh)
            rnn.step_output(nh)
        hidden = rnn()
        last = fluid.layers.sequence_last_step(hidden)
        logits = fluid.layers.fc(input=last, size=2)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(loss)

        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(60):
            rows, labs = [], []
            for _ in range(16):
                n = rng.randint(2, 7)
                bias = rng.choice([-0.5, 0.5])
                rows.append((rng.randn(n, 8) * 0.3 + bias).astype(np.float32))
                labs.append([int(rows[-1].mean() > 0)])
            l, = exe.run(fluid.default_main_program(),
                         feed={"x": make_lod(rows),
                               "label": np.asarray(labs, np.int64)},
                         fetch_list=[loss])
            losses.append(float(np.ravel(l)[0]))
        assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.6, losses


class TestWhileGrad:
    """Gradients through user While loops (reference while_op.cc:96
    WhileGradOp). Analytic grads from append_backward
    are checked against closed-form and numeric central differences."""

    def _build(self):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        w = fluid.layers.data(name="w", shape=[4], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        y = fluid.layers.scale(x, scale=1.0)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64", value=3)
        cond = fluid.layers.less_than(x=i, y=limit)
        wl = fluid.layers.While(cond=cond)
        with wl.block():
            ny = fluid.layers.elementwise_add(
                fluid.layers.elementwise_mul(y, w), x)
            fluid.layers.assign(ny, y)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        loss = fluid.layers.reduce_sum(y)
        return loss

    def test_analytic_matches_closed_form(self):
        loss = self._build()
        fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(3)
        xv = rng.randn(4).astype(np.float32)
        wv = (rng.rand(4).astype(np.float32) * 0.8 + 0.1)
        gx, gw, lv = exe.run(
            fluid.default_main_program(), feed={"x": xv, "w": wv},
            fetch_list=[block.var("x@GRAD"), block.var("w@GRAD"), loss])
        # y3 = x*(w^3+w^2+w+1); dL/dx = w^3+w^2+w+1; dL/dw = x(3w^2+2w+1)
        np.testing.assert_allclose(
            float(np.ravel(lv)[0]), float(np.sum(xv * (wv**3 + wv**2 + wv + 1))),
            rtol=1e-5)
        np.testing.assert_allclose(gx, wv**3 + wv**2 + wv + 1, rtol=1e-5)
        np.testing.assert_allclose(gw, xv * (3 * wv**2 + 2 * wv + 1),
                                   rtol=1e-5)

    def test_numeric_gradient(self):
        loss = self._build()
        fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(7)
        xv = rng.randn(4).astype(np.float32)
        wv = (rng.rand(4).astype(np.float32) * 0.8 + 0.1)

        def run_loss(xa, wa):
            l, = exe.run(fluid.default_main_program(),
                         feed={"x": xa, "w": wa}, fetch_list=[loss])
            return float(np.ravel(l)[0])

        gx, = exe.run(fluid.default_main_program(),
                      feed={"x": xv, "w": wv},
                      fetch_list=[block.var("x@GRAD")])
        delta = 1e-2
        num = np.zeros(4, np.float64)
        for k in range(4):
            xp, xm = xv.copy(), xv.copy()
            xp[k] += delta
            xm[k] -= delta
            num[k] = (run_loss(xp, wv) - run_loss(xm, wv)) / (2 * delta)
        np.testing.assert_allclose(gx, num, rtol=2e-3, atol=2e-3)

    def test_while_training_converges(self):
        """A While-unrolled recurrence actually trains (the r2 failure mode
        was silent zero grads through While)."""
        x = fluid.layers.data(name="x", shape=[8], dtype="float32",
                              append_batch_size=False)
        target = fluid.layers.data(name="target", shape=[8], dtype="float32",
                                   append_batch_size=False)
        w = fluid.layers.create_parameter(shape=[8], dtype="float32",
                                          name="w_loop")
        y = fluid.layers.scale(x, scale=1.0)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64", value=2)
        cond = fluid.layers.less_than(x=i, y=limit)
        wl = fluid.layers.While(cond=cond)
        with wl.block():
            ny = fluid.layers.elementwise_add(y, w)
            fluid.layers.assign(ny, y)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        diff = fluid.layers.elementwise_sub(y, target)
        loss = fluid.layers.reduce_mean(fluid.layers.square(diff))
        fluid.optimizer.SGD(learning_rate=0.2).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.zeros(8, np.float32)
        tv = np.full(8, 3.0, np.float32)
        losses = []
        for _ in range(30):
            l, = exe.run(fluid.default_main_program(),
                         feed={"x": xv, "target": tv}, fetch_list=[loss])
            losses.append(float(np.ravel(l)[0]))
        assert losses[-1] < losses[0] * 1e-2, losses


class TestConditionalBlockGrad:
    """Gradients through conditional_block (reference
    conditional_block_op.cc grad registration)."""

    def _build(self):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        p = fluid.layers.data(name="p", shape=[1], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        flag = fluid.layers.data(name="flag", shape=[1], dtype="float32",
                                 append_batch_size=False)
        zero = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                          value=0.0)
        out = fluid.layers.scale(p, scale=1.0)
        cond = fluid.layers.less_than(x=zero, y=flag)
        cb = fluid.layers.ConditionalBlock([cond], is_scalar_condition=True)
        with cb.block():
            s = fluid.layers.reduce_sum(fluid.layers.scale(x, scale=2.0))
            fluid.layers.assign(s, out)
        loss = fluid.layers.reduce_sum(out)
        fluid.backward.append_backward(loss)
        return loss

    def test_grads_both_branches(self):
        loss = self._build()
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.arange(4, dtype=np.float32)
        pv = np.array([5.0], np.float32)
        # cond TRUE: out = 2*sum(x) -> dL/dx = 2, dL/dp = 0
        gx, gp = exe.run(
            fluid.default_main_program(),
            feed={"x": xv, "p": pv, "flag": np.array([1.0], np.float32)},
            fetch_list=[block.var("x@GRAD"), block.var("p@GRAD")])
        np.testing.assert_allclose(gx, np.full(4, 2.0), rtol=1e-6)
        np.testing.assert_allclose(gp, [0.0], atol=1e-7)
        # cond FALSE: out = p (passthrough) -> dL/dx = 0, dL/dp = 1
        gx, gp = exe.run(
            fluid.default_main_program(),
            feed={"x": xv, "p": pv, "flag": np.array([-1.0], np.float32)},
            fetch_list=[block.var("x@GRAD"), block.var("p@GRAD")])
        np.testing.assert_allclose(gx, np.zeros(4), atol=1e-7)
        np.testing.assert_allclose(gp, [1.0], rtol=1e-6)


class TestSilentZeroGradRaises:
    def test_no_grad_op_on_loss_path_raises(self):
        """write_to_array is NO_GRAD; putting it on the loss path must raise
        instead of silently training with zero gradient."""
        import pytest
        x = fluid.layers.data(name="x", shape=[4], dtype="float32",
                              append_batch_size=False)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        arr = fluid.layers.array_write(x, i, capacity=4)
        y = fluid.layers.array_read(arr, i)
        loss = fluid.layers.reduce_sum(y)
        with pytest.raises(RuntimeError, match="no gradient"):
            fluid.backward.append_backward(loss)

    def test_cap_overflow_poisons_grads(self):
        """A loop running past max_loop_iters must NaN-poison its grads
        (truncated replay is undefined), not silently return wrong ones."""
        w = fluid.layers.data(name="w", shape=[2], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        y = fluid.layers.scale(w, scale=0.0)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64",
                                           value=200)
        cond = fluid.layers.less_than(x=i, y=limit)
        wl = fluid.layers.While(cond=cond)   # default cap 128 < 200
        with wl.block():
            ny = fluid.layers.elementwise_add(y, w)
            fluid.layers.assign(ny, y)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        loss = fluid.layers.reduce_sum(y)
        fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        gw, lv = exe.run(fluid.default_main_program(),
                         feed={"w": np.ones(2, np.float32)},
                         fetch_list=[block.var("w@GRAD"), loss])
        assert float(np.ravel(lv)[0]) == 400.0      # forward stays exact
        assert np.all(np.isnan(gw)), gw             # grads poisoned

    def test_cap_raised_via_max_iters(self):
        """Same loop with max_iters=256 gives the true gradient."""
        w = fluid.layers.data(name="w", shape=[2], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        y = fluid.layers.scale(w, scale=0.0)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64",
                                           value=200)
        cond = fluid.layers.less_than(x=i, y=limit)
        wl = fluid.layers.While(cond=cond, max_iters=256)
        with wl.block():
            ny = fluid.layers.elementwise_add(y, w)
            fluid.layers.assign(ny, y)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        loss = fluid.layers.reduce_sum(y)
        fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        gw, = exe.run(fluid.default_main_program(),
                      feed={"w": np.ones(2, np.float32)},
                      fetch_list=[block.var("w@GRAD")])
        np.testing.assert_allclose(gw, [200.0, 200.0], rtol=1e-6)


class TestIncrementGrad:
    def test_float_increment_differentiable(self):
        """d(increment(x))/dx = 1 (was NO_GRAD, which the zero-grad check
        would now reject on the loss path)."""
        x = fluid.layers.data(name="x", shape=[2], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        y = fluid.layers.increment(fluid.layers.scale(x, scale=3.0),
                                   value=1.0, in_place=False)
        loss = fluid.layers.reduce_sum(y)
        fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        gx, = exe.run(fluid.default_main_program(),
                      feed={"x": np.ones(2, np.float32)},
                      fetch_list=[block.var("x@GRAD")])
        np.testing.assert_allclose(gx, [3.0, 3.0], rtol=1e-6)
