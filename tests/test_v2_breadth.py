"""v2 user-surface breadth: networks composites,
numpy image augmentation, pooling/evaluator shims, mq2007 dataset, and the
acceptance bar — a reference-shaped v2 sentiment-LSTM script that touches
ONLY paddle_tpu.v2.* end-to-end (reference python/paddle/v2 demo style)."""

import numpy as np
import pytest

from paddle_tpu import v2 as paddle


class TestV2Networks:
    def test_sentiment_lstm_end_to_end(self):
        """The acceptance script: data -> embedding -> simple_lstm
        -> pooling -> fc -> classification_cost, trained by the v2 SGD
        event loop on the imdb reader surface, then infer()."""
        from paddle_tpu.dataset import imdb

        vocab = len(imdb.word_dict())
        words = paddle.layer.data(
            name="words", type=paddle.data_type.integer_value_sequence(vocab))
        label = paddle.layer.data(
            name="label", type=paddle.data_type.integer_value(2))
        emb = paddle.layer.embedding(input=words, size=32, vocab_size=vocab)
        lstm = paddle.networks.simple_lstm(input=emb, size=32)
        pooled = paddle.layer.pooling(lstm,
                                      pooling_type=paddle.pooling.Max)
        logits = paddle.layer.fc(input=pooled, size=2,
                                 act=paddle.activation.Linear)
        cost = paddle.layer.classification_cost(input=logits, label=label)

        parameters = paddle.parameters.create(cost)
        trainer = paddle.SGD(
            cost=cost, parameters=parameters,
            update_equation=paddle.optimizer.Adam(learning_rate=1e-2))

        def reader():
            src = imdb.train()()
            batch = []
            for i, (ws, lab) in enumerate(src):
                if i >= 96:
                    break
                batch.append((ws, [lab]))
                if len(batch) == 16:
                    yield batch
                    batch = []

        costs = []

        def handler(e):
            if isinstance(e, paddle.event.EndIteration):
                costs.append(e.cost)

        trainer.train(reader, num_passes=8, event_handler=handler,
                      feeding={"words": 0, "label": 1})
        assert np.isfinite(costs).all()
        # synthetic imdb splits vocab by sentiment: easily separable
        assert costs[-1] < costs[0] * 0.6, (costs[0], costs[-1])

        out = paddle.infer(output_layer=logits, parameters=parameters,
                           input=[([5, 6, 7],), ([3000, 3001],)],
                           feeding={"words": 0})
        assert np.asarray(out).shape == (2, 2)

        # SGD.test: forward-only evaluation on held-out data — trained
        # on separable synthetic imdb, test cost must be low and the
        # parameters must be untouched by testing
        from paddle_tpu.dataset import imdb as imdb_mod

        def test_reader():
            batch = []
            for i, (ws, lab) in enumerate(imdb_mod.test()()):
                if i >= 32:
                    break
                batch.append((ws, [lab]))
                if len(batch) == 16:
                    yield batch
                    batch = []

        before = {n: parameters[n].copy() for n in parameters.names()}
        result = trainer.test(test_reader, feeding={"words": 0, "label": 1})
        assert isinstance(result, paddle.event.TestResult)
        assert result.num_samples == 32
        assert result.cost < 0.5, result.cost
        for n, w in before.items():
            np.testing.assert_array_equal(parameters[n], w)

    def test_img_conv_pool_and_group(self):
        import paddle_tpu as fluid
        img = paddle.layer.data(name="im",
                                type=paddle.data_type.dense_vector(3 * 16 * 16))
        img4 = fluid.layers.reshape(img, [-1, 3, 16, 16])
        c1 = paddle.networks.simple_img_conv_pool(
            input=img4, filter_size=3, num_filters=4, pool_size=2,
            pool_stride=2, act=paddle.activation.Relu())
        g = paddle.networks.img_conv_group(
            input=img4, conv_num_filter=[4, 4], pool_size=2,
            conv_act=paddle.activation.Relu())
        # conv 3x3 valid on 16 -> 14, pool 2/2 -> 7; group keeps channels
        assert c1.shape[-1] == 7 and g.shape[1] == 4

    def test_bidirectional_lstm_and_gru_shapes(self):
        vocab = 50
        w = paddle.layer.data(
            name="w2", type=paddle.data_type.integer_value_sequence(vocab))
        emb = paddle.layer.embedding(input=w, size=8, vocab_size=vocab)
        bi = paddle.networks.bidirectional_lstm(input=emb, size=8)
        gru = paddle.networks.simple_gru(input=emb, size=8)
        assert bi.shape[-1] == 16 and gru.shape[-1] == 8


class TestV2NamespaceAliases:
    def test_canonical_reader_composition(self):
        """The composition every reference v2 script opens with:
        paddle.batch(paddle.reader.shuffle(paddle.dataset.X.train()))."""
        r = paddle.batch(
            paddle.reader.shuffle(paddle.dataset.uci_housing.train(),
                                  buf_size=64), batch_size=8)
        b = next(iter(r()))
        assert len(b) == 8 and len(b[0]) == 2

    def test_reader_creators(self):
        import os
        import tempfile
        from paddle_tpu.reader import creator
        from paddle_tpu.recordio import write_samples

        rows = list(creator.np_array(np.arange(6).reshape(3, 2))())
        assert [list(r) for r in rows] == [[0, 1], [2, 3], [4, 5]]
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "t.txt")
            with open(p, "w") as f:
                f.write("a\nbb\n")
            assert list(creator.text_file(p)()) == ["a", "bb"]
            rp = os.path.join(d, "x.recordio")
            write_samples(rp, [("s", 1), ("t", 2)])
            assert list(creator.recordio(rp, decode=True)()) == [
                ("s", 1), ("t", 2)]
            assert all(isinstance(r, bytes)
                       for r in creator.recordio(rp)())


class TestV2Image:
    def test_simple_transform_train_and_test(self):
        from paddle_tpu.v2 import image as v2_image
        rng = np.random.RandomState(0)
        im = rng.randint(0, 255, (40, 60, 3)).astype(np.uint8)
        test_out = v2_image.simple_transform(im, 32, 24, is_train=False,
                                             mean=[1.0, 2.0, 3.0])
        assert test_out.shape == (3, 24, 24) and test_out.dtype == np.float32
        train_out = v2_image.simple_transform(
            im, 32, 24, is_train=True, rng=np.random.RandomState(3))
        assert train_out.shape == (3, 24, 24)
        batch = v2_image.batch_images([test_out, train_out])
        assert batch.shape == (2, 3, 24, 24)

    def test_resize_short_keeps_aspect(self):
        from paddle_tpu.v2 import image as v2_image
        im = np.arange(20 * 10 * 3, dtype=np.uint8).reshape(20, 10, 3)
        out = v2_image.resize_short(im, 5)
        assert out.shape == (10, 5, 3)
        # constant image resizes to the same constant (bilinear sanity)
        const = np.full((8, 12, 3), 77, np.uint8)
        out2 = v2_image.resize_short(const, 6)
        assert (out2 == 77).all()

    def test_flip_and_crops(self):
        from paddle_tpu.v2 import image as v2_image
        im = np.arange(16).reshape(4, 4).astype(np.float32)
        np.testing.assert_array_equal(v2_image.left_right_flip(im),
                                      im[:, ::-1])
        assert v2_image.center_crop(im, 2).shape == (2, 2)
        assert v2_image.random_crop(
            im, 2, rng=np.random.RandomState(0)).shape == (2, 2)


class TestV2RecurrentGroup:
    def test_vanilla_rnn_matches_manual_recurrence(self):
        """recurrent_group with a named-memory fc step (the reference's
        canonical custom-RNN shape) vs a numpy recurrence oracle."""
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod

        H, D, vocab = 4, 3, 20
        seq = paddle.layer.data(
            name="sq3", type=paddle.data_type.integer_value_sequence(vocab))
        emb = paddle.layer.embedding(input=seq, size=D, vocab_size=vocab,
                                     param_attr="rg_emb")

        def step(x_t):
            prev = paddle.layer.memory(name="h", size=H)
            h = paddle.layer.fc(input=[x_t, prev], size=H,
                                act=paddle.activation.Tanh(),
                                param_attr="rg_w", bias_attr="rg_b",
                                name="h")
            return h

        out = paddle.layer.recurrent_group(step=step, input=emb)
        last = paddle.layer.last_seq(out)

        exe = fluid.Executor(fluid.CPUPlace())
        sc = executor_mod.Scope()
        with executor_mod.scope_guard(sc):
            exe.run(fluid.framework.framework.default_startup_program())
            LoD = executor_mod.LoDTensor
            ids = np.array([[1], [2], [3], [7], [8]], np.int64)
            feed = {"sq3": LoD(ids, [[0, 3, 5]])}
            got, = exe.run(
                fluid.framework.framework.default_main_program(),
                feed=feed, fetch_list=[last])
            emb_w = np.asarray(sc.find_var("rg_emb"))
            # fc over [x_t, prev]: first weight keeps the given name, the
            # second replica gets a generated one (reference
            # multiple_param_attr semantics) — find it by shape [H, H]
            w = np.asarray(sc.find_var("rg_w"))
            b = np.asarray(sc.find_var("rg_b"))
            w2_name, = [n for n in sc.local_var_names()
                        if n not in ("rg_w", "rg_b", "rg_emb")
                        and getattr(sc.find_var(n), "shape", None) == (H, H)]
            w2 = np.asarray(sc.find_var(w2_name))

        def run_seq(token_ids):
            h = np.zeros(H, np.float32)
            for t in token_ids:
                x = emb_w[t]
                h = np.tanh(x @ w + h @ w2 + b)
            return h

        want = np.stack([run_seq([1, 2, 3]), run_seq([7, 8])])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)

    def test_static_input_visible_every_step(self):
        """StaticInput: the same per-batch vector joins every step's
        computation (the reference seq2seq pattern for the encoded
        source)."""
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod

        seq = paddle.layer.data(name="sq5",
                                type=paddle.data_type.dense_vector_sequence(2))
        ctxv = paddle.layer.data(name="cx5",
                                 type=paddle.data_type.dense_vector(2))

        def step(x_t, c):
            prev = paddle.layer.memory(name="acc", size=2)
            s = paddle.layer.addto([x_t, c, prev], name="acc")
            return s

        out = paddle.layer.recurrent_group(
            step=step, input=[seq, paddle.layer.StaticInput(ctxv)])
        last = paddle.layer.last_seq(out)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(fluid.framework.framework.default_startup_program())
            LoD = executor_mod.LoDTensor
            x = np.array([[1, 1], [2, 2], [10, 10]], np.float32)
            feed = {"sq5": LoD(x, [[0, 2, 3]]),
                    "cx5": np.array([[0.5, 0.5], [3.0, 3.0]], np.float32)}
            got, = exe.run(
                fluid.framework.framework.default_main_program(),
                feed=feed, fetch_list=[last])
        # seq1: (1+.5) then +(2+.5) = 4; seq2: 10+3 = 13 — the static
        # vector is added at EVERY step
        np.testing.assert_allclose(np.asarray(got),
                                   [[4.0, 4.0], [13.0, 13.0]], rtol=1e-6)

    def test_memory_without_named_target_raises(self):
        emb = paddle.layer.data(name="sq4",
                                type=paddle.data_type.dense_vector(4))

        def bad_step(x_t):
            prev = paddle.layer.memory(name="nope", size=4)
            return paddle.layer.fc(input=[x_t, prev], size=4)  # unnamed

        with pytest.raises(ValueError, match="nope"):
            paddle.layer.recurrent_group(step=bad_step, input=emb)


class TestV2Evaluator:
    def test_classification_error(self):
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod
        pred = paddle.layer.data(name="p",
                                 type=paddle.data_type.dense_vector(3))
        lab = paddle.layer.data(name="l",
                                type=paddle.data_type.integer_value(3))
        err = paddle.evaluator.classification_error(input=pred, label=lab)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            p = np.array([[0.8, 0.1, 0.1], [0.2, 0.7, 0.1],
                          [0.3, 0.3, 0.4], [0.9, 0.05, 0.05]], np.float32)
            y = np.array([[0], [1], [1], [2]], np.int64)  # 2 right, 2 wrong
            from paddle_tpu.framework.framework import default_main_program
            got, = exe.run(default_main_program(), feed={"p": p, "l": y},
                           fetch_list=[err])
        assert abs(float(np.ravel(got)[0]) - 0.5) < 1e-6


class TestV2LayerWrappers:
    def _run(self, fetch, feed):
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod
        from paddle_tpu.framework.framework import default_main_program
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(fluid.framework.framework.default_startup_program())
            out, = exe.run(default_main_program(), feed=feed,
                           fetch_list=[fetch])
        return np.asarray(out)

    def test_elementwise_combinator_wrappers(self):
        a = paddle.layer.data(name="a", type=paddle.data_type.dense_vector(4))
        b = paddle.layer.data(name="b", type=paddle.data_type.dense_vector(4))
        w = paddle.layer.data(name="w", type=paddle.data_type.dense_vector(1))
        av = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.float32)
        bv = np.array([[4, 3, 2, 1], [8, 7, 6, 5]], np.float32)
        wv = np.array([[0.25], [0.5]], np.float32)
        feed = {"a": av, "b": bv, "w": wv}   # whole program runs per fetch
        got = self._run(paddle.layer.interpolation([a, b], w), feed)
        np.testing.assert_allclose(got, wv * av + (1 - wv) * bv, rtol=1e-6)
        got = self._run(paddle.layer.scaling(a, w), feed)
        np.testing.assert_allclose(got, av * wv, rtol=1e-6)
        got = self._run(paddle.layer.slope_intercept(a, slope=2.0,
                                                     intercept=1.0), feed)
        np.testing.assert_allclose(got, 2 * av + 1, rtol=1e-6)
        got = self._run(paddle.layer.repeat(a, 2), feed)
        assert got.shape == (2, 8)

    def test_structural_wrappers_build(self):
        """img_cmrnorm/maxout/bilinear_interp/crf/ctc/nce/hsigmoid build
        valid IR over the fluid ops (shape-level smoke; the underlying
        ops have their own numeric tests)."""
        import paddle_tpu as fluid
        img = paddle.layer.data(name="im4",
                                type=paddle.data_type.dense_vector(4 * 8 * 8))
        img4 = fluid.layers.reshape(img, [-1, 4, 8, 8])
        assert paddle.layer.img_cmrnorm(img4, size=5).shape[1] == 4
        assert paddle.layer.maxout(img4, groups=2).shape[1] == 2
        bi = paddle.layer.bilinear_interp(img4, out_size_x=16, out_size_y=16)
        assert tuple(bi.shape[2:]) == (16, 16)
        seq = paddle.layer.data(
            name="sq", type=paddle.data_type.integer_value_sequence(30))
        emb = paddle.layer.embedding(input=seq, size=8, vocab_size=30)
        tags = paddle.layer.data(
            name="tg", type=paddle.data_type.integer_value_sequence(5))
        feat = paddle.layer.fc(input=emb, size=5, num_flatten_dims=2)
        cost = paddle.layer.crf(input=feat, label=tags)
        assert cost is not None

    def test_huber_matches_definition(self):
        p = paddle.layer.data(name="p", type=paddle.data_type.dense_vector(1))
        y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
        cost = paddle.layer.huber_regression_cost(p, y, delta=2.0)
        pv = np.array([[0.0], [5.0]], np.float32)   # residuals 0 and 5
        yv = np.zeros((2, 1), np.float32)
        got = float(np.ravel(self._run(cost, {"p": pv, "y": yv}))[0])
        # per-element: 0 (quadratic at 0) and 2*5 - 0.5*4 = 8 -> mean 4
        assert abs(got - 4.0) < 1e-5, got


class TestMQ2007:
    def test_pairwise_reader_schema(self):
        from paddle_tpu.dataset import mq2007
        it = mq2007.train(format="pairwise")()
        label, hi, lo = next(it)
        assert label == 1.0 and hi.shape == (46,) and lo.shape == (46,)

    def test_listwise_and_pointwise(self):
        from paddle_tpu.dataset import mq2007
        rels, feats = next(mq2007.test(format="listwise")())
        assert feats.shape == (len(rels), 46)
        f, r = next(mq2007.test(format="pointwise")())
        assert f.shape == (46,) and r in (0.0, 1.0, 2.0)

    def test_ranknet_learns_pairwise_order(self):
        """rank_cost over mq2007 pairs: the planted LETOR signal must be
        learnable through the v2 surface (reference ssd/rank demos)."""
        from paddle_tpu.dataset import mq2007
        left = paddle.layer.data(name="left",
                                 type=paddle.data_type.dense_vector(46))
        right = paddle.layer.data(name="right",
                                  type=paddle.data_type.dense_vector(46))
        lab = paddle.layer.data(name="lab",
                                type=paddle.data_type.dense_vector(1))
        shared = paddle.layer.fc  # one scoring tower, shared weights
        sl = shared(input=left, size=1, param_attr="rank_w",
                    bias_attr="rank_b")
        sr = shared(input=right, size=1, param_attr="rank_w",
                    bias_attr="rank_b")
        cost = paddle.layer.rank_cost(left=sl, right=sr, label=lab)
        parameters = paddle.parameters.create(cost)
        trainer = paddle.SGD(
            cost=cost, parameters=parameters,
            update_equation=paddle.optimizer.Adam(learning_rate=1e-2))

        def reader():
            batch = []
            for i, (y, hi, lo) in enumerate(mq2007.train()()):
                if i >= 256:
                    break
                batch.append((hi, lo, [y]))
                if len(batch) == 32:
                    yield batch
                    batch = []

        costs = []
        trainer.train(
            reader, num_passes=3,
            event_handler=lambda e: costs.append(e.cost) if isinstance(
                e, paddle.event.EndIteration) else None,
            feeding={"left": 0, "right": 1, "lab": 2})
        assert costs[-1] < costs[0] * 0.9, (costs[0], costs[-1])
