"""Live observability plane (ISSUE 16): causal span tracing, the
scrapeable HTTP endpoint, and SLO burn-rate monitoring.

The acceptance properties pinned here: a serving request traced through
submit -> coalesce -> engine yields a span tree whose
queue+pad+compute+scatter children tile the parent (sum within 10%),
exportable as valid chrome-trace JSON; /metrics, /healthz and /spans
answer over real HTTP (http.client against the in-process server) while
a workload runs; /healthz flips to 503 when steps stall and when a crash
event lands; and the SLO monitor's fast/slow windows burn past 1.0
exactly when the error budget is being overspent.
"""

import http.client
import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import obs_server, telemetry, tracing
from paddle_tpu.serving import DynamicBatcher, ServingEngine
from paddle_tpu.serving import slo as slo_mod


@pytest.fixture(autouse=True)
def _fresh_obs_state():
    telemetry.reset()
    tracing.reset()
    slo_mod.reset()
    yield
    obs_server.stop()
    telemetry.reset()
    tracing.reset()
    slo_mod.reset()


def _get(port, route):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", route)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _get_json(port, route):
    status, body = _get(port, route)
    return status, json.loads(body)


def _build_fc_engine(scope, max_batch=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        logits = fluid.layers.fc(input=h, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
    return ServingEngine(main, feed_names=["x"],
                         fetch_names=[logits.name], scope=scope,
                         max_batch=max_batch)


# --- tracing core ------------------------------------------------------------

def test_span_context_nesting_and_parent_links():
    tracing.enable()
    with tracing.span("outer", program="p0") as outer:
        with tracing.span("inner") as inner:
            assert tracing.current_span() is inner
        assert tracing.current_span() is outer
    spans = {s["name"]: s for s in tracing.recent_spans()}
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["trace_id"] == spans["outer"]["trace_id"]
    assert spans["outer"]["parent_id"] is None
    assert spans["outer"]["attrs"]["program"] == "p0"
    assert spans["outer"]["end"] >= spans["inner"]["end"]


def test_tracing_disabled_is_noop():
    assert not tracing.enabled()
    with tracing.span("nope") as sp:
        sp.set_attr("k", "v").add_event("e")
    assert tracing.recent_spans() == []
    assert tracing.start_span("also_nope").sampled is False


def test_record_span_retroactive_and_tree():
    tracing.enable()
    t0 = time.monotonic()
    root = tracing.record_span("step", t0, t0 + 0.5,
                               attrs={"program": "p0"})
    tracing.record_span("compile", t0, t0 + 0.3, parent=root)
    roots = tracing.trace_tree(root.trace_id)
    assert len(roots) == 1
    assert roots[0]["name"] == "step"
    kids = roots[0]["children"]
    assert [k["name"] for k in kids] == ["compile"]
    assert abs(roots[0]["dur_s"] - 0.5) < 1e-9
    assert abs(kids[0]["dur_s"] - 0.3) < 1e-9


def test_head_sampling_is_deterministic_and_whole_trace():
    tracing.enable(sample=0.25)
    kept = 0
    for _ in range(16):
        root = tracing.start_span("req")
        child = tracing.start_span("phase", parent=root)
        child.end()
        root.end()
        kept += root.sampled
        # the keep/drop decision is inherited: never a partial tree
        assert child.sampled == root.sampled
    assert kept == 4
    assert len(tracing.recent_spans(name="req")) == 4


def test_ring_buffer_bounded_with_drop_counter():
    tracing.enable(capacity=10)
    t0 = time.monotonic()
    for i in range(25):
        tracing.record_span(f"s{i}", t0, t0 + 0.001)
    spans = tracing.recent_spans()
    assert len(spans) == 10
    assert spans[-1]["name"] == "s24"   # newest survives
    dropped = telemetry.read_series("trace_spans_dropped_total")
    assert sum(dropped.values()) == 15


def test_jsonl_export(tmp_path):
    tracing.enable()
    t0 = time.monotonic()
    tracing.record_span("a", t0, t0 + 0.1)
    tracing.record_span("b", t0, t0 + 0.2)
    path = tmp_path / "spans.jsonl"
    assert tracing.export_jsonl(str(path)) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["name"] for l in lines] == ["a", "b"]


def test_env_enable_sampling(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0.5")
    tracing.maybe_enable_from_env()
    assert tracing.enabled()
    tracing.reset()
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    tracing.maybe_enable_from_env()
    assert not tracing.enabled()


# --- serving request span tree (acceptance) ----------------------------------

def test_serving_span_tree_children_sum_to_parent(tmp_path):
    """A traced request's queue+pad+compute+scatter children must account
    for the parent within 10%, and the ring must export as loadable
    chrome-trace JSON (acceptance criterion)."""
    scope = executor_mod.Scope()
    eng = _build_fc_engine(scope)
    rng = np.random.RandomState(0)
    # warm every bucket the test could hit OUTSIDE tracing, so compile
    # time doesn't dominate bucket_select
    for n in (1, 2, 4, 8):
        eng.run_batch({"x": rng.randn(n, 16).astype(np.float32)})
    tracing.enable()
    with DynamicBatcher(eng, max_delay_ms=2.0) as batcher:
        futs = [batcher.submit(
                    {"x": rng.randn(2, 16).astype(np.float32)})
                for _ in range(4)]
        for f in futs:
            f.result(timeout=30.0)
    roots = tracing.recent_spans(name="serving_request")
    assert len(roots) == 4
    for root in roots:
        assert root["attrs"]["outcome"] == "ok"
        tree = tracing.trace_tree(root["trace_id"])
        assert len(tree) == 1
        kids = tree[0]["children"]
        names = [k["name"] for k in kids]
        for want in ("queue", "pad", "bucket_select", "compute",
                     "scatter"):
            assert want in names, f"missing child {want} in {names}"
        parent_dur = tree[0]["dur_s"]
        core = sum(k["dur_s"] for k in kids
                   if k["name"] in ("queue", "pad", "compute",
                                    "scatter"))
        every = sum(k["dur_s"] for k in kids)
        assert parent_dur > 0
        # all children tile the parent; the named four are within 10%
        assert abs(every - parent_dur) <= 0.10 * parent_dur + 1e-4
        assert core >= 0.90 * parent_dur - 1e-4
        assert core <= parent_dur + 1e-4

    out = tmp_path / "trace.json"
    n_events = tracing.export_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert n_events == len(doc["traceEvents"])
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(tracing.recent_spans())
    for e in xs:
        assert e["dur"] >= 0 and "name" in e and "ts" in e


def test_serving_shed_requests_end_spans():
    """Queue-full rejections happen before a span exists; deadline sheds
    end the request span with outcome=shed."""
    scope = executor_mod.Scope()
    eng = _build_fc_engine(scope)
    rng = np.random.RandomState(1)
    eng.run_batch({"x": rng.randn(4, 16).astype(np.float32)})
    tracing.enable()
    batcher = DynamicBatcher(eng, max_delay_ms=1.0)  # never started
    fut = batcher.submit({"x": rng.randn(2, 16).astype(np.float32)},
                         deadline_ms=0.0)
    time.sleep(0.01)
    batcher.start()
    with pytest.raises(Exception):
        fut.result(timeout=30.0)
    batcher.stop()
    shed = [s for s in tracing.recent_spans(name="serving_request")
            if s["attrs"].get("outcome") == "shed"]
    assert len(shed) == 1
    assert shed[0]["attrs"]["reason"] == "deadline"


# --- training step spans -----------------------------------------------------

def _tiny_trainer():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 4).astype(np.float32),
            "y": rng.randn(4, 1).astype(np.float32)}
    return main, startup, loss, feed


def _children(step):
    kids = [s for s in tracing.recent_spans(trace_id=step["trace_id"])
            if s["parent_id"] == step["span_id"]]
    return sorted(kids, key=lambda s: s["start"])


def _assert_tiled(step, names):
    """The step's children are `names` in order, lie inside it, and each
    starts where the one before ended: nothing of the step is outside."""
    kids = _children(step)
    assert [k["name"] for k in kids] == names
    assert kids[0]["start"] - step["start"] < 2e-3
    assert step["end"] - kids[-1]["end"] < 2e-3
    for kid in kids:
        assert step["start"] <= kid["start"] <= kid["end"] <= step["end"]
    for before, after in zip(kids, kids[1:]):
        assert after["start"] == before["end"]      # phase(): one instant
    covered = sum(k["dur_s"] for k in kids)
    assert covered == pytest.approx(step["dur_s"], abs=4e-3)


# the one dispatch sequence of run() and run_steps(), by the phase each
# stretch is booked under: gather + lookup, the call, the launch's own
# accounting + validation, the commit to the scope, the watchers, the
# fetches' return
PHASES = ["prepare", "launch", "bookkeep", "writeback", "bookkeep",
          "writeback"]


def test_executor_step_spans(monkeypatch):
    """`step` covers the whole Executor.run and its phases tile it.
    The parent's `step` was made after the fact from the launch's length
    and ended at a clock read past the bookkeeping: it sat late by what
    it left out. Now the run's log_event, the last thing bookkept, falls
    inside `step`, after `launch` has ended."""
    booked = []
    log_event = telemetry.log_event

    def spy(kind, **fields):
        if kind == "run":
            booked.append(time.monotonic())
        return log_event(kind, **fields)

    monkeypatch.setattr(telemetry, "log_event", spy)
    tracing.enable()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        before = [time.monotonic()]
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
            before.append(time.monotonic())
    steps = [s for s in tracing.recent_spans(name="step")
             if s["attrs"].get("program")
             == telemetry.program_label(main)]
    assert len(steps) == 3
    assert [s["attrs"]["cache"] for s in steps] == ["miss", "hit", "hit"]
    assert all(s["attrs"]["mode"] == "jit" for s in steps)
    booked = booked[-3:]
    for i, step in enumerate(steps):
        _assert_tiled(step, PHASES)
        # the whole call and nothing else: between the caller's own
        # clock reads, close to both
        assert before[i] <= step["start"] <= before[i] + 20e-3
        assert before[i + 1] - 20e-3 <= step["end"] <= before[i + 1]
        _, launch, _, commit, book, _ = _children(step)
        # the watchers run after the state went back to the scope
        assert launch["end"] <= commit["end"] <= book["start"]
        assert book["start"] <= booked[i] <= book["end"]
    # the first run's launch holds jax's trace, lower and compile, at the
    # times jax measured them; the bookkeeping the analysis. No invented
    # `compile` child at the step's start any more.
    _, cold_launch, _, _, cold_bookkeep, _ = _children(steps[0])
    built = [s for s in tracing.recent_spans(trace_id=steps[0]["trace_id"])
             if s["parent_id"] == cold_launch["span_id"]]
    assert {"trace", "lower", "compile"} <= {s["name"] for s in built}
    for s in built:
        assert cold_launch["start"] <= s["start"]
        assert s["end"] <= cold_launch["end"] + 1e-3
    # ... under the sink that books a first run (PR 51: every sink of
    # the bookkeeping has a span of its own)
    (build,) = [
        s for s in tracing.recent_spans(name="sink.build")
        if s["parent_id"] == cold_bookkeep["span_id"]]
    (analysis,) = [
        s for s in tracing.recent_spans(name="analysis")
        if s["parent_id"] == build["span_id"]]
    assert analysis["dur_s"] > 0
    assert all(not [k for k in _children(s) if k["name"] == "compile"]
               for s in steps)


def test_each_sink_has_a_span_inside_the_phase_that_holds_it(tmp_path):
    """PR 51: a step's host time is split by sink. The three stretches of
    `prepare` and every call site of `_book` are `sink.<name>` spans,
    children of their phase; the flight recorder's only while it records,
    the first run's only on a run that built. Nothing listening, `span`
    hands out the one shared no-op."""
    from paddle_tpu import inspector
    assert tracing.span("sink.gather") is tracing.span("sink.memory")
    tracing.enable()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])         # builds
        exe.run(main, feed=feed, fetch_list=[loss])
        inspector.enable_flight_recorder(str(tmp_path / "crash.json"))
        try:
            exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            inspector.disable_flight_recorder()
    cold, warm, recorded = [
        s for s in tracing.recent_spans(name="step")
        if s["attrs"].get("program") == telemetry.program_label(main)]

    def sinks(step):
        spans = tracing.recent_spans(trace_id=step["trace_id"])
        phase = {s["span_id"]: s["name"] for s in _children(step)}
        return [(phase[s["parent_id"]], s["name"])
                for s in sorted(spans, key=lambda s: s["start"])
                if s["name"].startswith("sink.")]

    steady = [("prepare", "sink.gather"), ("prepare", "sink.validate"),
              ("prepare", "sink.signature"), ("bookkeep", "sink.dynamics"),
              ("bookkeep", "sink.counters"), ("bookkeep", "sink.memory"),
              ("bookkeep", "sink.side_fetch"),
              ("writeback", "sink.side_fetch"),
              ("writeback", "sink.dynamics")]
    assert sinks(warm) == steady
    assert sinks(cold) == steady[:3] + [("bookkeep", "sink.build")] \
        + steady[3:]
    assert sinks(recorded) == steady[:7] + [("bookkeep", "sink.flight")] \
        + steady[7:]


def test_step_spans_share_the_step_id_in_run_and_run_steps():
    tracing.enable()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        scope.set_var("__rng_counter__", 40)
        exe.run(main, feed=feed, fetch_list=[loss])            # step 40
        exe.run_steps(main, feed_window=[feed] * 3,
                      fetch_list=[loss])                       # 41 .. 43
        exe.run(main, feed=feed, fetch_list=[loss])            # step 44
        assert scope.find_var("__rng_counter__") == 45
    label = telemetry.program_label(main)
    steps = [s for s in tracing.recent_spans(name="step")
             if s["attrs"].get("program") == label]
    assert [s["step"] for s in steps] == [40, 41, 44]
    assert steps[1]["attrs"]["mode"] == "window"
    assert steps[1]["attrs"]["steps"] == 3
    for step in steps:
        family = tracing.recent_spans(trace_id=step["trace_id"])
        assert len(family) >= 5
        assert {s["step"] for s in family} == {step["step"]}
    # one sequence: a window is tiled as a step is (the state goes back to
    # the scope before the watchers run)
    for step in steps:
        _assert_tiled(step, PHASES)


def test_run_steps_fallback_steps_are_plain_steps():
    """A window served by the per-step path is K `step` spans, each with
    its own id: no span of another shape around them."""
    tracing.enable()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        scope.set_var("__rng_counter__", 7)
        exe.run_steps(main, feed_window=[feed] * 2, fetch_list=[loss],
                      use_jit=False)
    label = telemetry.program_label(main)
    steps = [s for s in tracing.recent_spans(name="step")
             if s["attrs"].get("program") == label]
    assert [s["step"] for s in steps] == [7, 8]
    assert all(s["parent_id"] is None for s in steps)
    assert all(s["attrs"]["mode"] == "eager" for s in steps)
    for step in steps:
        _assert_tiled(step, PHASES)


# --- the one dispatch path ---------------------------------------------------

@pytest.mark.parametrize("mode, steps", [("jit", 1), ("eager", 1),
                                         ("window", 3)])
def test_one_dispatch_books_once_and_after_the_commit(mode, steps, tmp_path,
                                                      monkeypatch):
    """Whatever the mode, one call goes through one book: one profiler
    host event, one run event, one flight record, one memory sample,
    `steps` steps counted — and every one of them, the dynamics watcher
    included, fires with the new state and the PRNG counter already in
    the scope. Before PR 28 the per-step modes ran their watchers first
    (dynamics.on_step waits on the device every 16th step) and wrote the
    donated state back after."""
    from paddle_tpu import dynamics, inspector, memory, profiler
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    param = main.global_block().all_parameters()[0].name
    fired = []

    def spy(module, name, label=None, only=lambda *a, **k: True):
        real = getattr(module, name)

        def wrapper(*args, **kwargs):
            if only(*args, **kwargs):
                fired.append((label or name,
                              scope.find_var("__rng_counter__"),
                              np.array(scope.find_var(param))))
            return real(*args, **kwargs)
        monkeypatch.setattr(module, name, wrapper)

    def call():
        if mode == "window":
            return exe.run_steps(main, feed_window=[feed] * steps,
                                 fetch_list=[loss])
        return exe.run(main, feed=feed, fetch_list=[loss],
                       use_jit=mode == "jit")

    inspector.enable_flight_recorder(str(tmp_path / "crash.json"))
    try:
        with executor_mod.scope_guard(scope):
            exe.run(startup)
            scope.set_var("__rng_counter__", 15)    # a dynamics sample step
            call()                                  # cold: builds the block
            spy(profiler, "record_event")
            spy(telemetry, "log_event", "run_event",
                only=lambda kind, **f: kind in ("run", "run_window"))
            spy(inspector, "record_step")
            spy(memory, "on_run")
            spy(dynamics, "on_step", "dynamics")
            spy(dynamics, "on_window", "dynamics")
            counter0 = scope.find_var("__rng_counter__")
            param0 = np.array(scope.find_var(param))
            steps0 = sum(telemetry.read_series(
                "executor_steps_total").values())
            call()
            steps1 = sum(telemetry.read_series(
                "executor_steps_total").values())
            param1 = np.array(scope.find_var(param))
    finally:
        inspector.disable_flight_recorder()
    names = [n for n, _, _ in fired]
    for once in ("record_event", "run_event", "record_step", "on_run"):
        assert names.count(once) == 1, names
    assert names.count("dynamics") == (0 if mode == "eager" else 1), names
    assert steps1 - steps0 == steps
    assert not np.array_equal(param0, param1)       # SGD moved it
    for name, counter, value in fired:
        assert counter == counter0 + steps, (name, counter)
        np.testing.assert_array_equal(value, param1, err_msg=name)


def test_missing_state_is_one_error_for_every_entry_point():
    """run(), the compile-only entry points and the serving seam read the
    scope through one gather: the same scope gives the same error."""
    main, startup, loss, feed = _tiny_trainer()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()                    # startup never ran
    raised = []
    for entry in (
            lambda: exe.run(main, feed=feed, fetch_list=[loss], scope=scope),
            lambda: exe.run_steps(main, feed_window=[feed] * 2,
                                  fetch_list=[loss], scope=scope),
            lambda: exe._aot_block(main, feed, [loss], scope),
            lambda: exe.compiled_hlo(main, feed=feed, fetch_list=[loss],
                                     scope=scope),
            lambda: exe.prepare_serving(main, sorted(feed), [loss.name],
                                        scope)):
        with pytest.raises(RuntimeError, match="run the startup program "
                                               "first") as err:
            entry()
        raised.append(str(err.value))
    assert len(set(raised)) == 1, raised


def test_tracing_off_allocates_no_span(monkeypatch):
    """With the ring off and no profiler session a step pays a handful of
    no-op calls: no Span, no context object, no clock read of tracing's."""
    made = []
    init = tracing.Span.__init__

    def counting(self, *a, **kw):
        made.append(1)
        init(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "__init__", counting)
    monkeypatch.setattr(tracing, "_SpanCtx", None)     # any use would raise
    assert not tracing.active()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        for _ in range(1000):
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    assert made == []
    assert tracing.recent_spans() == []
    assert tracing.span("step") is tracing.span("launch")   # one shared no-op
    # what the six sites of a step cost while off: far under the 20 us a
    # tiny program's own dispatch takes (a loop of 1000 runs of the tiny
    # program measures the same on the parent within noise; this is the
    # part that is ours)
    t0 = time.perf_counter()
    for _ in range(1000):
        with tracing.span("step", step=None):
            tracing.phase("prepare")
            tracing.phase("launch")
            tracing.phase("bookkeep")
            tracing.phase("writeback")
        with tracing.span("input_wait"):
            pass
    assert (time.perf_counter() - t0) / 1000 < 20e-6


def test_phase_tiles_and_unwinds_on_error():
    tracing.enable()
    with pytest.raises(ValueError):
        with tracing.span("step", step=3) as step:
            tracing.phase("prepare")
            with tracing.span("inner"):
                assert tracing.owner_span() is tracing.current_span()
            assert tracing.owner_span() is step
            assert tracing.current_span().name == "prepare"
            tracing.phase("launch")
            raise ValueError("boom")
    assert tracing.current_span().sampled is False      # nothing left open
    assert tracing.owner_span().sampled is False
    spans = {s["name"]: s for s in tracing.recent_spans()}
    assert set(spans) == {"step", "prepare", "inner", "launch"}
    assert spans["launch"]["start"] == spans["prepare"]["end"]
    assert spans["inner"]["parent_id"] == spans["prepare"]["span_id"]
    assert spans["launch"]["parent_id"] == spans["step"]["span_id"]
    assert "boom" in spans["launch"]["attrs"]["error"]
    assert "boom" in spans["step"]["attrs"]["error"]
    assert {s["step"] for s in spans.values()} == {3}
    tracing.phase("orphan")                   # outside any span: nothing
    assert len(tracing.recent_spans()) == 4


def test_sampled_out_step_leaves_no_orphan_phase():
    """Head sampling drops a step whole: its phases and what runs inside
    them do not become roots with a sampling decision of their own."""
    tracing.enable(sample=0.5)
    for i in range(4):
        with tracing.span("step", step=i):
            tracing.phase("prepare")
            with tracing.span("inner"):
                pass
            tracing.phase("launch")
    spans = tracing.recent_spans()
    assert [s["step"] for s in spans if s["name"] == "step"] == [1, 3]
    assert len(spans) == 2 * 4
    kept = {s["span_id"] for s in spans}
    assert all(s["parent_id"] in kept for s in spans if s["name"] != "step")


def test_feeder_spans_on_their_threads():
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

    tracing.enable()
    batch = {"x": np.zeros((2, 4), np.float32)}
    feeder = DoubleBufferedFeeder(lambda: iter([batch] * 3), capacity=2)
    try:
        assert len(list(feeder)) == 3
    finally:
        feeder.stop()
    waits = tracing.recent_spans(name="input_wait")
    builds = tracing.recent_spans(name="input_build")
    assert len(builds) == 3
    assert len(waits) == 4                      # three batches and the stop
    # the producer's spans are roots of their own: no step owns a batch
    # built ahead of time
    assert all(b["parent_id"] is None for b in builds)
    stall = telemetry.snapshot()["histograms"]["input_stall_seconds"][""]
    assert stall["count"] == 4
    assert sum(w["dur_s"] for w in waits) >= stall["sum"]


def test_spans_are_annotations_in_the_profilers_trace(tmp_path):
    """Under jax.profiler.start_trace the program's spans are `pd.*`
    events of the xplane's host plane, with the ring off: `pd.step` and
    its four phases on the dispatching thread, the feeder's on theirs."""
    from benchmarks import program_trace
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder
    import jax

    assert not tracing.enabled()
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feeder = DoubleBufferedFeeder(lambda: iter([feed] * 4),
                                  device=exe.device, capacity=2)
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        scope.set_var("__rng_counter__", 100)
        exe.run(main, feed=feed, fetch_list=[loss])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for batch in feeder:
                exe.run(main, feed=batch, fetch_list=[loss])
        finally:
            jax.profiler.stop_trace()
            feeder.stop()
    assert tracing.recent_spans() == []
    got = program_trace.reduce_dir(str(tmp_path))
    assert [s["step"] for s in got["host_steps"]] == [101, 102, 103, 104]
    for step in got["host_steps"]:
        assert set(step["phases"]) == set(PHASES)
        assert step["self_s"] < 0.1 * step["seconds"] + 50e-6
    assert len(got["host_spans"]["input_build"]) == 4
    assert len(got["host_spans"]["input_wait"]) == 5
    assert got["device_steps"] == []            # the CPU has no device plane


def test_build_seconds_booked_once_per_block():
    main, startup, loss, feed = _tiny_trainer()
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    label = telemetry.program_label(main)

    def series():
        return {k.rsplit("phase=", 1)[-1]: v for k, v in
                telemetry.read_series(
                    "executor_build_seconds_total").items()
                if k.startswith("program=%s," % label)}

    with executor_mod.scope_guard(scope):
        exe.run(startup)
        assert series() == {}
        t0 = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=[loss])
        wall = time.perf_counter() - t0
        first = series()
        exe.run(main, feed=feed, fetch_list=[loss])
        second = series()
    assert set(first) == {"trace", "lower", "compile", "analysis", "execute"}
    assert all(v >= 0 for v in first.values())
    assert first["trace"] > 0 and first["compile"] > 0
    assert first["analysis"] > 0
    assert sum(first.values()) == pytest.approx(wall, rel=0.10)
    assert second == first                     # a steady step books none
    # and jax's own compile counter still agrees with the compile phase
    compile_s = sum(telemetry.read_series(
        "executor_compile_seconds_total").values())
    assert compile_s >= first["compile"] * 0.5


def test_build_events_merge_nested_traces():
    events = [("trace", 1.0, 2.0), ("trace", 0.0, 5.0), ("trace", 6.0, 7.0),
              ("lower", 5.0, 6.5), ("compile", 7.0, 9.0)]
    assert telemetry.merge_build_events(events) == [
        ("compile", 7.0, 9.0), ("lower", 5.0, 6.5), ("trace", 0.0, 5.0),
        ("trace", 6.0, 7.0)]
    assert telemetry.build_phase_seconds(events) == {
        "trace": 6.0, "lower": 1.5, "compile": 2.0}
    assert executor_mod._build_seconds(events, 10.0, 0.5) == {
        "trace": 6.5, "lower": 1.5, "compile": 2.0, "execute": 0.5,
        "analysis": 0.0}


def test_checkpoint_spans(tmp_path):
    tracing.enable()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        fluid.layers.fc(input=x, size=2)
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_params(exe, str(tmp_path), main)
        fluid.io.load_params(exe, str(tmp_path), main)
    assert len(tracing.recent_spans(name="checkpoint_save")) == 1
    assert len(tracing.recent_spans(name="checkpoint_load")) == 1
    save = tracing.recent_spans(name="checkpoint_save")[0]
    assert save["attrs"]["bytes"] > 0


# --- SLO burn rate -----------------------------------------------------------

def test_slo_burn_rate_windows():
    clock = [1000.0]
    mon = slo_mod.SLOMonitor(
        slo_mod.SLO("m0", availability=0.999),
        clock=lambda: clock[0])
    for _ in range(995):
        mon.record(ok=True)
    assert mon.burn_rate(slo_mod.FAST_WINDOW_S) == 0.0
    for _ in range(5):
        mon.record(ok=False)
    # 5/1000 bad against a 0.001 budget: burning 5x
    rep = mon.report()
    assert rep["windows"]["fast"]["burn_rate"] == pytest.approx(5.0)
    assert rep["windows"]["slow"]["burn_rate"] == pytest.approx(5.0)
    assert telemetry.read_gauge("slo_burn_rate", model="m0",
                                window="fast") == pytest.approx(5.0)
    # fast window forgets the incident, slow window still remembers
    clock[0] += slo_mod.FAST_WINDOW_S + 1
    rep = mon.report()
    assert rep["windows"]["fast"]["burn_rate"] == 0.0
    assert rep["windows"]["slow"]["burn_rate"] == pytest.approx(5.0)
    # and the slow window ages out too
    clock[0] += slo_mod.SLOW_WINDOW_S
    rep = mon.report()
    assert rep["windows"]["slow"]["burn_rate"] == 0.0
    assert rep["windows"]["slow"]["total"] == 0


def test_slo_latency_objective_counts_slow_success_as_bad():
    mon = slo_mod.SLOMonitor(
        slo_mod.SLO("m1", availability=0.9, latency_ms=50.0))
    mon.record(ok=True, latency_s=0.01)
    mon.record(ok=True, latency_s=0.2)   # completed but too slow
    rep = mon.report()
    assert rep["windows"]["fast"]["bad"] == 1
    assert rep["windows"]["fast"]["burn_rate"] == pytest.approx(5.0)


def test_slo_registry_shared_per_model():
    a = slo_mod.monitor_for("modelA")
    assert slo_mod.monitor_for("modelA") is a
    a.record(ok=False)
    reports = slo_mod.all_reports()
    assert "modelA" in reports
    assert reports["modelA"]["windows"]["fast"]["bad"] == 1


def test_batcher_stats_carry_slo():
    scope = executor_mod.Scope()
    eng = _build_fc_engine(scope)
    rng = np.random.RandomState(2)
    with DynamicBatcher(eng, max_delay_ms=2.0) as batcher:
        fut = batcher.submit(
            {"x": rng.randn(2, 16).astype(np.float32)})
        fut.result(timeout=30.0)
        stats = batcher.stats()
    assert stats["slo"]["windows"]["fast"]["total"] == 1
    assert stats["slo"]["windows"]["fast"]["burn_rate"] == 0.0
    assert stats["slo"]["objective"]["availability"] == 0.999


# --- HTTP endpoints ----------------------------------------------------------

def test_obs_endpoints_serve_live_data():
    srv = obs_server.start(port=0)
    assert srv.port
    tracing.enable()
    telemetry.counter("input_batches_total",
                      "reader batches produced").inc(3)
    t0 = time.monotonic()
    tracing.record_span("step", t0, t0 + 0.01,
                        attrs={"program": "p0"})

    status, body = _get(srv.port, "/metrics")
    assert status == 200
    text = body.decode()
    assert "# TYPE input_batches_total counter" in text
    assert "input_batches_total 3" in text
    assert "obs_requests_total" in text   # the scrape counts itself

    status, spans = _get_json(srv.port, "/spans?n=5")
    assert status == 200
    assert spans["enabled"] is True
    assert [s["name"] for s in spans["spans"]] == ["step"]

    status, report = _get_json(srv.port, "/report")
    assert status == 200
    assert report["spans_buffered"] == 1
    assert report["metrics_families"] >= 1

    status, index = _get_json(srv.port, "/")
    assert status == 200
    assert "/metrics" in index["endpoints"]

    status, _err = _get_json(srv.port, "/nope")
    assert status == 404


def test_healthz_verdicts_and_stall_flip():
    srv = obs_server.start(port=0)
    # never stepped: healthy (a pure serving process is not stalled)
    status, rep = _get_json(srv.port, "/healthz")
    assert status == 200
    assert rep["checks"]["step"]["ran"] is False

    telemetry.log_event("run", program="p0", seconds=0.01)
    telemetry.gauge(
        "executor_last_step_seconds",
        "wall seconds of the most recent executor step").set(0.01)
    status, rep = _get_json(srv.port, "/healthz?max_age=60")
    assert status == 200 and rep["status"] == "ok"
    assert rep["checks"]["step"]["stalled"] is False

    # steps stall: the same scrape with a tight staleness threshold
    # flips to 503 (acceptance criterion)
    time.sleep(0.05)
    status, rep = _get_json(srv.port, "/healthz?max_age=0.01")
    assert status == 503
    assert rep["status"] == "unhealthy"
    assert rep["checks"]["step"]["stalled"] is True


def test_healthz_crash_and_slo_degraded():
    srv = obs_server.start(port=0)
    # SLO burning fast -> degraded but still 200 (alert, not dead)
    mon = slo_mod.monitor_for("m9")
    for _ in range(10):
        mon.record(ok=False)
    status, rep = _get_json(srv.port, "/healthz")
    assert status == 200
    assert rep["status"] == "degraded"
    assert rep["checks"]["slo"]["burn_rates"]["m9"]["fast"] > 1.0

    # a crash event is a hard unhealthy
    telemetry.log_event("crash", error="RuntimeError: boom",
                        program="p0")
    status, rep = _get_json(srv.port, "/healthz")
    assert status == 503
    assert rep["checks"]["last_error"]["error"] \
        == "RuntimeError: boom"


def test_crash_hook_logs_event():
    """inspector.notify_crash feeds the event /healthz reads."""
    from paddle_tpu import inspector
    main = fluid.Program()
    inspector.notify_crash(None, main, RuntimeError("kaput"))
    evs = telemetry.recent_events(kind="crash")
    assert len(evs) == 1
    assert "kaput" in evs[0]["error"]


def test_obs_cli_subcommand(tmp_path, capsys):
    """`python -m paddle_tpu obs` end-to-end in-process: server up,
    traced smoke steps, self-scrape over HTTP, chrome-trace export."""
    from paddle_tpu import cli
    out = tmp_path / "trace.json"
    rc = cli.main(["obs", "--steps", "2", "--batch", "4",
                   "--export-trace", str(out)])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(line)
    assert summary["metrics"]["status"] == 200
    assert summary["metrics"]["bytes"] > 0
    assert summary["healthz"]["checks"]["step"]["ran"] is True
    assert summary["spans"]["buffered"] > 0
    doc = json.loads(out.read_text())
    assert any(e.get("name") == "step" for e in doc["traceEvents"])


def test_env_port_autostart(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_PORT", "0")
    srv = obs_server.maybe_start_from_env()
    assert srv is not None and srv.port
    status, _ = _get(srv.port, "/metrics")
    assert status == 200
