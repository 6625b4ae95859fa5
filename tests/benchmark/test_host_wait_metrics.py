"""The five readers of PR 51, unlisted until a `benchmark` PR enters them
(PERF.md section 7): `host_wait_ms.train` on hand-made counters and on a
real pipelined loop, `watcher_idle_ms.train` and the three phase medians
on the fixture trace of test_program_trace.py and on a copy of it whose
device sits idle under `pd.bookkeep` and a `pd.sink.memory` inside it;
each returns None without evidence."""

import os

import numpy as np
import pytest

from benchmarks import evidence, program_trace, run

from .test_program_trace import CELL, MS, _text, _write

PHASE_READERS = {
    "step_prepare_ms.train": 0.5,
    "step_bookkeep_ms.train": 1.0,
    "step_writeback_ms.train": 0.5,
}
TRACE_READERS = dict(PHASE_READERS, **{"watcher_idle_ms.train": 0.0})
READERS = sorted(TRACE_READERS) + ["host_wait_ms.train"]
SOURCES = {"host_wait_ms.train": "program_counter",
           "watcher_idle_ms.train": "device_trace"}


def _watched_text():
    """The fixture with the optimizer's operation starting at 7 ms, not 6:
    the device idles 5 .. 7 ms, under pd.launch until 6.5 and under
    pd.bookkeep after; and a pd.sink.memory 6.6 .. 6.9 inside bookkeep."""
    text = _text().replace(
        "events { metadata_id: 3 offset_ps: 6000000000 "
        "duration_ps: 3000000000 }",
        "events { metadata_id: 3 offset_ps: 7000000000 "
        "duration_ps: 2000000000 }")
    text = text.replace(
        "    events { metadata_id: 9 offset_ps: 100000000 "
        "duration_ps: 100000000 }\n",
        "    events { metadata_id: 9 offset_ps: 100000000 "
        "duration_ps: 100000000 }\n"
        "    events { metadata_id: 10 offset_ps: 6600000000 "
        "duration_ps: 300000000 }\n")
    text = text.replace(
        '  event_metadata { key: 9 value { id: 9 name: '
        '"$builtins isinstance" } }\n',
        '  event_metadata { key: 9 value { id: 9 name: '
        '"$builtins isinstance" } }\n'
        '  event_metadata { key: 10 value { id: 10 name: '
        '"pd.sink.memory" } }\n')
    assert "pd.sink.memory" in text and "offset_ps: 7000000000" in text
    return text


def _traced(root, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", root)
    return {"cell": {"name": CELL}, "trace": None}


@pytest.fixture(scope="module")
def trace_root(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("host_wait"), _text())


@pytest.fixture(scope="module")
def watched_root(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("host_wait_watched"),
                  _watched_text())


@pytest.mark.parametrize("name", READERS)
def test_reader_says_what_it_is(name):
    reader = run.load_module("layer_metrics", name)
    assert reader.UNIT == "ms" and reader.MOVES == "train_items_per_s"
    assert reader.SOURCE == SOURCES.get(name, "program_span")
    assert reader.LAYER == (
        "device" if name == "watcher_idle_ms.train" else "executor")


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
def test_trace_reader_on_the_fixture(trace_root, monkeypatch, name):
    reader = run.load_module("layer_metrics", name)
    assert reader.compute(_traced(trace_root, monkeypatch)) == pytest.approx(
        TRACE_READERS[name], abs=1e-9)


def test_phases_tile_the_step_and_sum_to_the_overhead(trace_root,
                                                      monkeypatch):
    ev = _traced(trace_root, monkeypatch)
    value = {name: run.load_module("layer_metrics", name).compute(ev)
             for name in sorted(PHASE_READERS) + [
                 "step_launch_ms.train", "step_host_overhead_ms.train"]}
    phases = sum(value[name] for name in PHASE_READERS)
    assert phases == pytest.approx(value["step_host_overhead_ms.train"])
    (step,) = program_trace.of_evidence(ev)["host_steps"]
    assert phases + value["step_launch_ms.train"] == pytest.approx(
        1e3 * step["seconds"])


def test_idle_under_bookkeep_and_a_sink_is_the_watchers(watched_root,
                                                        monkeypatch):
    """5 .. 6.5 ms is the launch's; 6.5 .. 7 ms the device waits on the
    bookkeeping, 0.3 ms of it on the sink the gap names."""
    ev = _traced(watched_root, monkeypatch)
    reduced = program_trace.of_evidence(ev)
    assert reduced["idle_gaps"] == {
        "launch": pytest.approx(1.5 * MS),
        "bookkeep": pytest.approx(0.2 * MS),
        "sink.memory": pytest.approx(0.3 * MS)}
    # the sink's time leaves its phase's self time, not its length
    assert reduced["host_self"]["sink.memory"] == pytest.approx(0.3 * MS)
    assert reduced["host_self"]["bookkeep"] == pytest.approx(0.7 * MS)
    reader = run.load_module("layer_metrics", "watcher_idle_ms.train")
    assert reader.compute(ev) == pytest.approx(0.5)
    bookkeep = run.load_module("layer_metrics", "step_bookkeep_ms.train")
    assert bookkeep.compute(ev) == pytest.approx(1.0)


def test_idle_under_the_steps_own_arguments_is_not_the_watchers(monkeypatch):
    """`prepare` and its three sinks gather and check what the step runs
    on: on four chips the device idles under `sink.validate` in the
    change as it did under `prepare` in the parent, and neither is a
    watcher's doing (my chip runs, PR 51)."""
    gaps = {"sink.validate": 5.2 * MS, "sink.gather": 0.4 * MS,
            "sink.signature": 0.1 * MS, "prepare": 6.2 * MS,
            "launch": 3.8 * MS, "none": 23.0 * MS, "between ops": 2.1 * MS,
            "bookkeep": 0.5 * MS, "sink.memory": 1.0 * MS,
            "sink.dynamics": 0.5 * MS}
    monkeypatch.setattr(program_trace, "of_evidence", lambda ev: {
        "host_steps": [{}] * 4, "idle_gaps": gaps})
    reader = run.load_module("layer_metrics", "watcher_idle_ms.train")
    assert reader.compute({}) == pytest.approx(0.5)


def test_the_tables_print_the_sinks_with_no_edit(watched_root, capsys):
    program_trace.print_tables(
        program_trace.reduce_dir(os.path.join(watched_root, CELL)))
    printed = capsys.readouterr().out
    self_times = printed[printed.index("self ms of pd.* host spans"):]
    assert "sink.memory" in self_times
    assert "sink.memory" in printed[printed.index("device idle by"):]


@pytest.mark.parametrize("waits, expected", [
    ({"program=p1,site=dynamics": {"sum": 0.030, "count": 3},
      "program=p1,site=side_fetch": {"sum": 0.015, "count": 50},
      "program=p0,site=check_nan_inf": {"sum": 0.005, "count": 1}}, 1.0),
    ({"program=p1,site=dynamics": {"sum": 0.0, "count": 0}}, 0.0),
    ({}, 0.0),          # steps ran and no site was reached: 0, not None
    (None, 0.0),
])
def test_host_wait_is_every_site_over_the_windows_steps(waits, expected):
    counters = {"executor_steps_total": {
        "place=TPUPlace:0,program=p1": 48, "place=TPUPlace:0,program=p0": 2}}
    if waits is not None:
        counters["executor_host_wait_seconds"] = waits
    reader = run.load_module("layer_metrics", "host_wait_ms.train")
    assert reader.compute({"counters": counters}) == pytest.approx(expected)


def test_host_wait_of_a_parent_is_not_zero_but_nothing(monkeypatch):
    """A program that declares no such family waits uncounted."""
    from paddle_tpu import telemetry

    catalog = dict(telemetry.METRIC_CATALOG)
    del catalog["executor_host_wait_seconds"]
    monkeypatch.setattr(telemetry, "METRIC_CATALOG", catalog)
    reader = run.load_module("layer_metrics", "host_wait_ms.train")
    assert reader.compute({"counters": {"executor_steps_total": {
        "place=TPUPlace:0,program=p1": 48}}}) is None


def test_a_pipelined_window_with_the_defaults_reads_zero():
    """The benchmark's own loop in small: steps dispatched
    return_numpy=False, the oldest fetched once two are in flight, the
    window's counters a delta. Period 16 puts two samples in 24 steps."""
    import paddle_tpu as fluid
    from paddle_tpu import dynamics, telemetry
    from paddle_tpu import executor as executor_mod

    telemetry.reset()
    dynamics.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            input=fluid.layers.fc(input=x, size=1), label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    feed = {"x": np.ones((8, 4), np.float32), "y": np.ones((8, 1), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    in_flight = []
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])         # warm-up
        before = evidence.counters_now()
        for _ in range(24):
            in_flight.append(exe.run(main, feed=feed, fetch_list=[loss],
                                     return_numpy=False)[0])
            if len(in_flight) >= 2:
                np.asarray(in_flight.pop(0))
        np.asarray(in_flight.pop(0))
        counters = evidence.counters_delta(before, evidence.counters_now())
    reader = run.load_module("layer_metrics", "host_wait_ms.train")
    assert reader.compute({"counters": counters}) == 0.0
    assert evidence.family_total(counters, "dynamics_samples_total") >= 1
    exe.close()
    telemetry.reset()
    dynamics.reset()


@pytest.mark.parametrize("name", READERS)
def test_reader_without_evidence_returns_none(tmp_path, monkeypatch, name):
    """No trace under TRACE_DIR/<cell>, no step in the window's counters
    (an untraced run, a window that ran nothing): nothing to read."""
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    reader = run.load_module("layer_metrics", name)
    assert reader.compute({"cell": {"name": CELL}, "trace": None,
                           "counters": {}}) is None
