"""Program-level jit-vs-eager parity: the executor
has two semantics — whole-block XLA jit and the op-by-op eager interpreter
(reference executor.cc's interpretation model, executor.py:1-17). Per-op
tests pin individual kernels; THIS pins the program-level glue (scope
handling, feed normalization, LoD side-channels, RNG stream, persistable
write-back) by running real book-shaped programs in both modes and
asserting identical results."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework import unique_name


def _run_both(build, feeds, steps=2, seed=7):
    """Build the same program twice (fresh name generator => identical
    parameter init streams), run `steps` training steps in jit and eager
    mode, return the two loss trajectories."""
    out = {}
    for use_jit in (True, False):
        unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            main.random_seed = startup.random_seed = seed
            loss = build()
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup, use_jit=use_jit)
            traj = []
            for _ in range(steps):
                r, = exe.run(main, feed=dict(feeds), fetch_list=[loss],
                             use_jit=use_jit)
                traj.append(float(np.asarray(r).ravel()[0]))
        out[use_jit] = traj
    return out[True], out[False]


def test_fit_a_line_parity():
    rng = np.random.RandomState(0)
    feeds = {"x": rng.randn(16, 13).astype(np.float32),
             "y": rng.randn(16, 1).astype(np.float32)}

    def build():
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return loss

    jit, eager = _run_both(build, feeds)
    np.testing.assert_allclose(jit, eager, rtol=1e-5, atol=1e-7)


def test_conv_classifier_parity():
    rng = np.random.RandomState(1)
    feeds = {"img": rng.rand(4, 1, 12, 12).astype(np.float32),
             "label": rng.randint(0, 4, (4, 1)).astype(np.int64)}

    def build():
        img = fluid.layers.data(name="img", shape=[1, 12, 12],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        conv = fluid.nets.simple_img_conv_pool(
            input=img, num_filters=4, filter_size=3, pool_size=2,
            pool_stride=2, act="relu")
        logits = fluid.layers.fc(input=conv, size=4)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return loss

    jit, eager = _run_both(build, feeds)
    np.testing.assert_allclose(jit, eager, rtol=1e-5, atol=1e-7)


def test_lod_sequence_parity():
    """Sequence program with a LoD feed: the padded-pack emulation and its
    @SEQLEN side channel must behave identically in both executors."""
    rng = np.random.RandomState(2)
    LoD = executor_mod.LoDTensor
    feeds = {"words": LoD(rng.randint(0, 30, (11, 1)).astype(np.int64),
                          [[0, 4, 7, 11]]),
             "label": rng.randint(0, 2, (3, 1)).astype(np.int64)}

    def build():
        words = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(input=words, size=[30, 8])
        proj = fluid.layers.fc(input=emb, size=32, num_flatten_dims=2)
        h, _c = fluid.layers.dynamic_lstm(input=proj, size=32)
        last = fluid.layers.sequence_last_step(h)
        logits = fluid.layers.fc(input=last, size=2)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        return loss

    jit, eager = _run_both(build, feeds)
    np.testing.assert_allclose(jit, eager, rtol=1e-5, atol=1e-7)


def test_dropout_rng_stream_parity():
    """Random ops draw from the scope's __rng_counter__-derived stream —
    jit and eager must consume the SAME stream (r3 pinned the seed into
    the jit cache key; this pins the runtime draw)."""
    rng = np.random.RandomState(3)
    feeds = {"x": rng.randn(8, 16).astype(np.float32),
             "y": rng.randn(8, 1).astype(np.float32)}

    def build():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.5)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return loss

    jit, eager = _run_both(build, feeds, steps=3)
    np.testing.assert_allclose(jit, eager, rtol=1e-5, atol=1e-7)
