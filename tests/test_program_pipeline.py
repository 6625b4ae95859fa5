"""Program-level pipeline parallelism (reference ancestor:
gserver/gradientmachines/ParallelNeuralNetwork.h layer-to-device
assignment): a Program split at cut vars into
pp=4 stages on the 8-device CPU mesh must train with losses matching
single-device execution exactly (mean-loss microbatching contract)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.parallel.program_pipeline import PipelineTranspiler


def _build_mlp():
    """4-layer MLP regression: three natural cut points."""
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h1 = fluid.layers.fc(input=x, size=32, act="tanh",
                         param_attr=fluid.ParamAttr(name="w1"),
                         bias_attr=fluid.ParamAttr(name="b1"))
    h2 = fluid.layers.fc(input=h1, size=32, act="tanh",
                         param_attr=fluid.ParamAttr(name="w2"),
                         bias_attr=fluid.ParamAttr(name="b2"))
    h3 = fluid.layers.fc(input=h2, size=16, act="tanh",
                         param_attr=fluid.ParamAttr(name="w3"),
                         bias_attr=fluid.ParamAttr(name="b3"))
    pred = fluid.layers.fc(input=h3, size=1,
                           param_attr=fluid.ParamAttr(name="w4"),
                           bias_attr=fluid.ParamAttr(name="b4"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    return loss, [h1, h2, h3]


def _batches(steps, bsz=32):
    rng = np.random.RandomState(0)
    w = rng.randn(16, 1).astype(np.float32)
    for _ in range(steps):
        xs = rng.randn(bsz, 16).astype(np.float32)
        yield {"x": xs, "y": np.tanh(xs) @ w}


def _init_weights(scope):
    rng = np.random.RandomState(7)
    shapes = {"w1": (16, 32), "b1": (32,), "w2": (32, 32), "b2": (32,),
              "w3": (32, 16), "b3": (16,), "w4": (16, 1), "b4": (1,)}
    for n, s in shapes.items():
        scope.set_var(n, (rng.randn(*s) * 0.3).astype(np.float32))


class TestProgramPipeline:
    def test_pp4_matches_single_device(self):
        steps = 5

        # single-device oracle
        main_s, startup_s = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_s, startup_s):
            loss_s, _ = _build_mlp()
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss_s)
        exe = fluid.Executor(fluid.CPUPlace())
        scope_s = executor_mod.Scope()
        oracle = []
        with executor_mod.scope_guard(scope_s):
            exe.run(startup_s)
            _init_weights(scope_s)
            for feed in _batches(steps):
                v, = exe.run(main_s, feed=feed, fetch_list=[loss_s])
                oracle.append(float(np.asarray(v).ravel()[0]))

        # pp=4 pipeline through the transpiler API
        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            loss_p, cuts = _build_mlp()
        t = PipelineTranspiler()
        trainer = t.transpile(
            loss_p, cut_vars=cuts,
            optimizer=lambda: fluid.optimizer.SGD(learning_rate=0.1),
            num_microbatches=4)
        assert len(trainer.stages) == 4
        scope_p = executor_mod.Scope()
        piped = []
        with executor_mod.scope_guard(scope_p):
            trainer.startup(startup_p)
            _init_weights(scope_p)
            for feed in _batches(steps):
                piped.append(trainer.train_step(feed))

        np.testing.assert_allclose(piped, oracle, rtol=2e-4, atol=1e-6)

    def test_stage_partition_is_disjoint_and_placed(self):
        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            loss_p, cuts = _build_mlp()
        trainer = PipelineTranspiler().transpile(
            loss_p, cut_vars=cuts,
            optimizer=lambda: fluid.optimizer.SGD(learning_rate=0.1),
            num_microbatches=2)
        own = [set(s.param_names) for s in trainer.stages]
        for i in range(len(own)):
            for j in range(i + 1, len(own)):
                assert not (own[i] & own[j]), (own[i], own[j])
        assert set().union(*own) == {"w1", "b1", "w2", "b2",
                                     "w3", "b3", "w4", "b4"}
        # stages sit on distinct devices of the virtual mesh
        places = {s.place.device_id for s in trainer.stages}
        assert len(places) == 4

    def test_skip_connection_across_cut_rejected(self):
        import pytest
        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(input=x, size=8, act="tanh",
                                 param_attr=fluid.ParamAttr(name="sw1"))
            h2 = fluid.layers.fc(input=h1, size=8, act="tanh",
                                 param_attr=fluid.ParamAttr(name="sw2"))
            # skip: h1 feeds past the h2 cut
            h3 = fluid.layers.elementwise_add(
                fluid.layers.fc(input=h2, size=8,
                                param_attr=fluid.ParamAttr(name="sw3")), h1)
            pred = fluid.layers.fc(input=h3, size=1,
                                   param_attr=fluid.ParamAttr(name="sw4"))
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        with pytest.raises(ValueError, match="separate the graph"):
            PipelineTranspiler().transpile(
                loss, cut_vars=[h2],
                optimizer=lambda: fluid.optimizer.SGD(learning_rate=0.1),
                num_microbatches=2)

    def test_regularization_matches_single_device(self):
        steps = 3
        reg = fluid.regularizer.L2Decay(1e-3)

        main_s, startup_s = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_s, startup_s):
            loss_s, _ = _build_mlp()
            fluid.optimizer.SGD(learning_rate=0.1,
                                regularization=reg).minimize(loss_s)
        exe = fluid.Executor(fluid.CPUPlace())
        scope_s = executor_mod.Scope()
        oracle = []
        with executor_mod.scope_guard(scope_s):
            exe.run(startup_s)
            _init_weights(scope_s)
            for feed in _batches(steps):
                v, = exe.run(main_s, feed=feed, fetch_list=[loss_s])
                oracle.append(float(np.asarray(v).ravel()[0]))

        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            loss_p, cuts = _build_mlp()
        trainer = PipelineTranspiler().transpile(
            loss_p, cut_vars=cuts,
            optimizer=lambda: fluid.optimizer.SGD(learning_rate=0.1,
                                                  regularization=reg),
            num_microbatches=4)
        scope_p = executor_mod.Scope()
        piped = []
        with executor_mod.scope_guard(scope_p):
            trainer.startup(startup_p)
            _init_weights(scope_p)
            for feed in _batches(steps):
                piped.append(trainer.train_step(feed))
        np.testing.assert_allclose(piped, oracle, rtol=2e-4, atol=1e-6)
