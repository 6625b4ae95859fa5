"""Inference serving subsystem (ISSUE 13): AOT per-bucket program cache,
dynamic batching, and load shedding.

The load-bearing property is bitwise parity: a request served through
the batcher (coalesced with strangers, padded to a bucket, scattered
back) must equal the same rows served alone, which must equal a classic
`exe.run` on the pruned program. Everything else — the bucket ladder's
hit/miss accounting, deadline-vs-size batch closes, queue-full and
deadline sheds, the DLRM sparse path staying sparse — is checked
against the engine's python counters AND the telemetry series, so the
observability surface can't silently drift from the behavior.
"""

import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import telemetry
from paddle_tpu.errors import ServingOverloadError
from paddle_tpu.serving import (DynamicBatcher, ServingEngine, bucket_ladder,
                                overload_report, run_load)
from paddle_tpu.serving import slo as slo_mod


def _build_fc(scope, train_steps=0, in_dim=16, classes=4):
    """Adam-trained 2-layer fc classifier; returns (main, logits_name).
    Startup (and optional training) run inside `scope`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[in_dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        logits = fluid.layers.fc(input=h, size=classes)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        for _ in range(train_steps):
            exe.run(main,
                    feed={"x": rng.randn(8, in_dim).astype(np.float32),
                          "y": rng.randint(0, classes, (8, 1))
                          .astype(np.int64)},
                    fetch_list=[loss])
    return main, logits.name


def _feed(rng, n, in_dim=16):
    return {"x": rng.randn(n, in_dim).astype(np.float32)}


def _ctr(name, **labels):
    """One counter series' value (0.0 when absent). `labels` must be
    passed in the family's declared label order — read_series keys are
    the registry's serialized 'k=v,k=v' form."""
    key = ",".join(f"{k}={v}" for k, v in labels.items())
    return telemetry.read_series(name).get(key, 0.0)


def test_bucket_ladder_shape():
    assert bucket_ladder(8) == (1, 2, 4, 8)
    assert bucket_ladder(1) == (1,)
    # non-power-of-two max still caps the ladder at max_batch
    assert bucket_ladder(6)[-1] == 6


def test_batch_parity_bitwise():
    """Rows served alone == their slice of a padded batched run == the
    classic executor on the same pruned program (acceptance criterion).
    Bitwise parity holds within one bucket executable (rows are
    independent along the batch dim, so padding neighbors can't perturb
    them); across DIFFERENT buckets XLA may tile the matmul differently,
    so that comparison is allclose-at-ULP, not bitwise."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=3)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, buckets=[4])
    rng = np.random.RandomState(1)
    batch = _feed(rng, 3)                       # pads into bucket 4
    batched = eng.run_batch(dict(batch))[0]
    assert batched.shape == (3, 4)
    for i in range(3):                          # same bucket: bitwise
        alone = eng.run_batch({"x": batch["x"][i:i + 1]})[0]
        assert np.array_equal(alone[0], batched[i])
    exe = fluid.Executor(fluid.CPUPlace())
    classic = exe.run(eng.program, feed=dict(batch),
                      fetch_list=[logits], scope=scope)[0]
    assert np.array_equal(np.asarray(classic), batched)
    eng.close()
    # cross-bucket (1-row executable vs 4-row executable): numerically
    # identical up to reassociation ULPs
    eng2 = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                         scope=scope, max_batch=8)
    batched2 = eng2.run_batch(dict(batch))[0]
    for i in range(3):
        alone = eng2.run_batch({"x": batch["x"][i:i + 1]})[0]
        np.testing.assert_allclose(alone[0], batched2[i],
                                   rtol=1e-6, atol=1e-6)
    eng2.close()


def test_bucket_cache_hit_miss_and_eviction():
    """Per-bucket AOT executables: first touch of a bucket is a compile
    miss, repeats are hits, and a capacity-1 cache LRU-evicts — all
    mirrored in the serving_cache_* telemetry series."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=8)
    rng = np.random.RandomState(2)
    eng.run_batch(_feed(rng, 1))                # bucket 1: miss
    eng.run_batch(_feed(rng, 1))                # hit
    eng.run_batch(_feed(rng, 3))                # bucket 4: miss
    eng.run_batch(_feed(rng, 4))                # hit
    assert (eng.cache_misses, eng.cache_hits) == (2, 2)
    label = eng._label
    assert _ctr("serving_cache_miss_total", program=label, bucket="1") == 1
    assert _ctr("serving_cache_hit_total", program=label, bucket="4") == 1
    assert eng.bucket_runs == {1: 2, 4: 2}
    # compile time was observed per miss
    hist = telemetry.read_histogram("serving_compile_seconds",
                                    program=label, bucket="1")
    assert hist and hist["count"] == 1
    eng.close()

    eng2 = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                         scope=scope, max_batch=8, cache_capacity=1)
    eng2.run_batch(_feed(rng, 1))
    eng2.run_batch(_feed(rng, 2))               # evicts bucket 1
    eng2.run_batch(_feed(rng, 1))               # miss again
    assert eng2.evictions >= 1 and eng2.cache_misses == 3
    assert _ctr("serving_cache_evictions_total", program=eng2._label) >= 1
    eng2.close()


def test_run_batch_feed_validation():
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=4)
    rng = np.random.RandomState(3)
    with pytest.raises(KeyError):
        eng.run_batch({})
    with pytest.raises(ValueError):
        eng.run_batch({"x": np.zeros((0, 16), np.float32)})
    with pytest.raises(ValueError):
        eng.run_batch(_feed(rng, 5))            # over max_batch
    # infer() chunks an oversized feed instead of rejecting it
    big = _feed(rng, 6)
    out = eng.infer(big)[0]
    assert out.shape == (6, 4)
    assert np.array_equal(out[:4], eng.run_batch({"x": big["x"][:4]})[0])
    eng.close()
    with pytest.raises(RuntimeError):
        eng.infer(_feed(rng, 1))


def test_batcher_coalesce_scatter_parity():
    """Requests from different clients coalesce into one bucket and each
    future gets exactly its own rows back, bitwise."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=2)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=8)
    rng = np.random.RandomState(4)
    feeds = [_feed(rng, n) for n in (1, 2, 3)]
    # bitwise reference: the same rows coalesced by hand into one
    # run_batch call — identical bucket, identical padded tensor
    concat = eng.run_batch(
        {"x": np.concatenate([f["x"] for f in feeds])})[0]
    singles = [concat[0:1], concat[1:3], concat[3:6]]
    b = DynamicBatcher(eng, max_delay_ms=40.0, max_queue_depth=16)
    futs = [b.submit(f) for f in feeds]         # queue while stopped...
    b.start()                                   # ...so they coalesce
    try:
        for fut, want in zip(futs, singles):
            got = fut.result(timeout=30.0)[0]
            assert np.array_equal(got, want)
        st = b.stats()
        assert st["completed"] == 3 and st["shed"] == 0
        assert st["goodput_fraction"] == 1.0
    finally:
        b.stop()


def test_size_close_vs_deadline_close():
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=4)
    rng = np.random.RandomState(5)
    # a full bucket must close on size long before a 5s deadline
    b = DynamicBatcher(eng, max_delay_ms=5000.0, max_queue_depth=16)
    futs = [b.submit(_feed(rng, 1)) for _ in range(4)]
    t0 = time.monotonic()
    b.start()
    try:
        for fut in futs:
            fut.result(timeout=30.0)
        assert time.monotonic() - t0 < 4.0      # did not wait out 5s
        assert b.close_counts.get("size", 0) >= 1
        # a lone request must close on deadline, not hang for size
        fut = b.submit(_feed(rng, 1))
        fut.result(timeout=30.0)
        assert b.close_counts.get("deadline", 0) >= 1
    finally:
        b.stop()
    assert _ctr("serving_batches_total",
                program=eng._label, close="size") >= 1
    eng.close()


def test_overload_sheds_but_accepted_requests_keep_parity():
    """Bounded queue: overflow is rejected with ServingOverloadError
    (reason queue_full) instead of queue collapse, and the requests that
    WERE accepted still return bitwise-correct results."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=2)
    # single-bucket ladder so the shed-test reference runs share the
    # coalesced batch's executable (bitwise, not just allclose)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, buckets=[2])
    rng = np.random.RandomState(6)
    feeds = [_feed(rng, 1) for _ in range(4)]
    singles = [eng.run_batch(dict(f))[0] for f in feeds]
    b = DynamicBatcher(eng, max_delay_ms=20.0, max_queue_depth=2)
    accepted = [b.submit(feeds[0]), b.submit(feeds[1])]
    shed = []
    for f in feeds[2:]:
        with pytest.raises(ServingOverloadError) as ei:
            b.submit(f)
        shed.append(ei.value)
    assert all(e.reason == "queue_full" for e in shed)
    b.start()
    try:
        for fut, want in zip(accepted, singles):
            assert np.array_equal(fut.result(timeout=30.0)[0], want)
    finally:
        b.stop()
    st = b.stats()
    assert st["shed"] == 2 and st["completed"] == 2
    assert st["goodput_fraction"] == pytest.approx(0.5)
    assert _ctr("serving_shed_total", program=eng._label,
                reason="queue_full") >= 2
    eng.close()


def test_expired_deadline_sheds_at_pop():
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=4)
    rng = np.random.RandomState(7)
    b = DynamicBatcher(eng, max_delay_ms=30.0, max_queue_depth=8)
    fut = b.submit(_feed(rng, 1), deadline_ms=0.0)  # expired on arrival
    live = b.submit(_feed(rng, 2))                  # rides the same batch
    b.start()
    try:
        with pytest.raises(ServingOverloadError) as ei:
            fut.result(timeout=30.0)
        assert ei.value.reason == "deadline"
        assert live.result(timeout=30.0)[0].shape == (2, 4)
    finally:
        b.stop()
    eng.close()


def test_dlrm_fsdp_serve_stays_sparse():
    """Flagship scenario: DLRM scorer on an fsdp-row-sharded table. The
    serve path must never book a sparse_densify_fallback — the lookup
    lowers to a sparse take, not a dense one-hot matmul (acceptance
    criterion)."""
    import jax
    from paddle_tpu.parallel import embedding as emb_mod
    from paddle_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    telemetry.reset()
    rows, dim, slots = 64, 4, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[slots], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[rows, dim], is_sparse=True,
            param_attr=fluid.ParamAttr(name="emb_table"))
        flat = fluid.layers.reshape(emb, shape=[-1, slots * dim])
        h = fluid.layers.fc(input=flat, size=16, act="relu")
        prob = fluid.layers.softmax(fluid.layers.fc(input=h, size=2))
    main._mesh = make_mesh((4,), ("fsdp",))
    emb_mod.shard_table(main, "emb_table", "fsdp")
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
    eng = ServingEngine(main, feed_names=["ids"], fetch_names=[prob.name],
                        scope=scope, max_batch=4)
    rng = np.random.RandomState(8)
    out = eng.run_batch(
        {"ids": rng.randint(0, rows, (3, slots)).astype(np.int64)})[0]
    assert out.shape == (3, 2)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)
    assert sum(telemetry.read_series(
        "sparse_densify_fallback_total").values()) == 0
    eng.close()


def test_save_load_roundtrip_matches_in_memory(tmp_path):
    """Satellite: the saved inference model reloads, analyzes clean, and
    serves bitwise-identically to the in-memory program."""
    from paddle_tpu.analysis import analyze_program

    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=3)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        target = main.global_block().var(logits)
        fluid.io.save_inference_model(str(tmp_path), ["x"], [target], exe,
                                      main_program=main)
    mem = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=4)
    disk = ServingEngine(str(tmp_path), max_batch=4)
    assert disk.feed_names == ["x"] and disk.fetch_names == [logits]
    report = analyze_program(disk.program, feeds=disk.feed_names,
                             fetches=disk.fetch_names)
    assert not report.errors, [str(d) for d in report.errors]
    rng = np.random.RandomState(9)
    feed = _feed(rng, 3)
    assert np.array_equal(mem.run_batch(dict(feed))[0],
                          disk.run_batch(dict(feed))[0])
    mem.close()
    disk.close()


def test_prune_drops_training_state(tmp_path):
    """Satellite: the pruned inference program keeps only the forward
    params — no Adam moments/beta pows, no grads, no optimizer ops — and
    requesting a gradient as a save target is refused."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=2)
    # resident state is exactly the 4 fc params (2x weight + 2x bias)
    assert len(eng._state_names) == 4, eng._state_names
    for n in eng._state_names:
        assert "moment" not in n and "beta" not in n and "@GRAD" not in n
    block = eng.program.global_block()
    assert all(op.type not in ("adam", "sgd", "momentum")
               and not op.type.endswith("_grad") for op in block.ops)
    for n in block.desc.vars:
        assert "moment" not in n and "@GRAD" not in n, n
    eng.close()
    # converse: a gradient var as an inference target is refused — its
    # producer is stripped with the training tail, so the pruned program
    # can't compute it. Both the export path and the engine's admission
    # gate must say so, at build time, not at first compile.
    gname = next(n for n in main.global_block().desc.vars
                 if n.endswith("@GRAD"))
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        with pytest.raises(ValueError, match="gradient"):
            fluid.io.save_inference_model(
                str(tmp_path), ["x", "y"],
                [type("V", (), {"name": gname})()], exe,
                main_program=main)
    with pytest.raises(ValueError, match="not computable"):
        ServingEngine(main, feed_names=["x", "y"], fetch_names=[gname],
                      scope=scope)


def test_capi_machine_serves_loaded_model(tmp_path):
    """Satellite: the C-API backend stub rides ServingEngine with
    create/feed/fetch/destroy handle semantics."""
    from paddle_tpu.capi_backend import Machine

    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=2)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        target = main.global_block().var(logits)
        fluid.io.save_inference_model(str(tmp_path), ["x"], [target], exe,
                                      main_program=main)
    m = Machine(str(tmp_path))
    rng = np.random.RandomState(10)
    x = rng.randn(2, 16).astype(np.float32)
    m.set_input("x", x.tobytes(), (2, 16), 0)
    outs = m.forward()
    assert len(outs) == 1
    payload, dims = outs[0]
    got = np.frombuffer(payload, np.float32).reshape(dims)
    want = m.engine.run_batch({"x": x})[0]
    assert np.array_equal(got, want)
    m.destroy()
    with pytest.raises(RuntimeError):
        m.set_input("x", x.tobytes(), (2, 16), 0)


def test_concurrent_client_smoke_latency_histograms():
    """In-process concurrent-client harness: non-degenerate p50 <= p99
    (acceptance criterion), and the same quantiles are recoverable from
    the serving_request_seconds telemetry histogram."""
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, max_batch=8)
    rng = np.random.RandomState(11)
    for b in (1, 2, 4):                         # pre-compile the ladder
        eng.run_batch(_feed(rng, b))

    def make_feed(ci, ri):
        return _feed(rng, 1 + (ci + ri) % 3)

    b = DynamicBatcher(eng, max_delay_ms=3.0, max_queue_depth=32)
    b.start()
    try:
        payload = run_load(b, make_feed, clients=3, requests_per_client=4)
    finally:
        b.stop()
    assert payload["requests"] == 12
    assert 0.0 < payload["p50_ms"] <= payload["p99_ms"]
    assert payload["qps"] > 0 and payload["goodput_fraction"] == 1.0
    assert sum(payload["bucket_hits"].values()) >= 1
    assert payload["telemetry_p50_ms"] is not None
    assert payload["telemetry_p50_ms"] <= payload["telemetry_p99_ms"]
    # per-phase latency histograms exist for queue and compute too
    for phase in ("queue", "compute", "total"):
        h = telemetry.read_histogram("serving_request_seconds",
                                     program=eng._label, phase=phase)
        assert h and h["count"] >= 12
    eng.close()


def test_overload_report_slo_and_latency_bound():
    """Overload acceptance (ISSUE 16): injected overload drives the SLO
    fast-window burn above 1.0 while the normal phase stays below, and
    the accepted-request p99 under overload stays within a bound of the
    normal phase (shedding absorbs the excess, latency doesn't
    collapse). `overload_report` must carry the `slo` sub-dict with both
    windows."""
    slo_mod.reset()   # monitors are process-wide keyed by program label
    scope = executor_mod.Scope()
    main, logits = _build_fc(scope, train_steps=2)
    eng = ServingEngine(main, feed_names=["x"], fetch_names=[logits],
                        scope=scope, buckets=[4])
    rng = np.random.RandomState(8)
    eng.run_batch(_feed(rng, 4))                # warm the only bucket

    # ~15ms per 2-request batch + a 4-deep queue: a normal-phase client
    # (4 clients, one in-flight request each) can see at most 3 queued
    # strangers, so normal NEVER sheds; an overload client (8 total) can
    # see up to 7, so overload must — the shed signal separates the
    # phases deterministically
    real_run_batch = eng.run_batch

    def slow_run_batch(feed, **kw):
        time.sleep(0.015)
        return real_run_batch(feed, **kw)

    eng.run_batch = slow_run_batch
    b = DynamicBatcher(eng, max_delay_ms=30.0, max_queue_depth=4)
    b.start()
    try:
        report = overload_report(
            b, lambda ci, ri: _feed(np.random.RandomState(ci * 97 + ri), 2),
            clients=4, requests_per_client=6)
    finally:
        b.stop()
        eng.run_batch = real_run_batch
        eng.close()

    normal, over = report["normal"], report["overload"]
    assert over["shed_fraction"] > 0.0
    assert normal["p99_ms"] is not None and over["p99_ms"] is not None
    # accepted-latency bound: overload p99 may grow (deeper queue) but
    # must stay within a small multiple of normal — not collapse
    assert over["p99_ms"] <= 6.0 * normal["p99_ms"] + 150.0

    slo = report["slo"]
    assert slo is not None
    assert set(slo["windows"]) == {"fast", "slow"}
    assert slo["objective"]["availability"] == pytest.approx(0.999)
    # queue_full sheds overspend the 0.1% error budget immediately
    assert slo["overload"]["fast"] > 1.0
    assert slo["normal"]["fast"] <= 1.0
    assert report["batcher"]["slo"]["windows"]["fast"]["bad"] > 0
