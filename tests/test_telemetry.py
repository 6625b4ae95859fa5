"""Unified telemetry subsystem (ISSUE 1): registry semantics, executor run
tracing + retrace cause, Prometheus exposition round-trip, JSONL step log,
merged chrome trace, CLI subcommand, cross-host reduce (real 2-process
jax.distributed, same harness as test_jax_distributed), and the satellite
fixes that rode along (print-op grad, conv_operator filter, threadpool
submit/shutdown atomicity, xplane device-plane aggregation)."""

import json
import math
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.disable_step_log()
    telemetry.reset()


# --- registry semantics ------------------------------------------------------

class TestMetricPrimitives:
    def test_counter_inc_and_labels(self):
        c = telemetry.counter("t_total", "help txt", labels=("op",))
        c.labels(op="a").inc()
        c.labels(op="a").inc(2.5)
        c.labels(op="b").inc()
        snap = telemetry.snapshot()
        assert snap["counters"]["t_total"] == {"op=a": 3.5, "op=b": 1.0}

    def test_label_free_family_proxies_single_child(self):
        telemetry.counter("t_plain").inc(4)
        assert telemetry.snapshot()["counters"]["t_plain"] == {"": 4.0}

    def test_gauge_set_overwrites(self):
        g = telemetry.gauge("t_g")
        g.set(5)
        g.set(2.5)
        assert telemetry.snapshot()["gauges"]["t_g"][""] == 2.5

    def test_histogram_buckets_cumulative_sum_count(self):
        h = telemetry.histogram("t_h", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):   # one per bucket + overflow
            h.observe(v)
        s = telemetry.snapshot()["histograms"]["t_h"][""]
        assert s["buckets"] == [0.1, 1.0, 10.0]
        assert s["counts"] == [1, 1, 1, 1]
        assert s["count"] == 4
        assert abs(s["sum"] - 55.55) < 1e-9

    def test_registration_idempotent_but_kind_conflict_raises(self):
        assert telemetry.counter("t_dup") is telemetry.counter("t_dup")
        with pytest.raises(ValueError, match="already registered"):
            telemetry.gauge("t_dup")

    def test_wrong_label_names_raise(self):
        c = telemetry.counter("t_lbl", labels=("a",))
        with pytest.raises(ValueError, match="takes labels"):
            c.labels(b="x")
        with pytest.raises(ValueError, match="use .labels"):
            c.inc()

    def test_default_buckets_log_scale(self):
        b = telemetry.default_buckets()
        assert b[0] == pytest.approx(1e-6)
        assert all(hi / lo == pytest.approx(4.0)
                   for lo, hi in zip(b, b[1:]))

    def test_concurrent_increments_do_not_lose_updates(self):
        c = telemetry.counter("t_race")

        def spin():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=spin) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert telemetry.snapshot()["counters"]["t_race"][""] == 8000.0


def test_histogram_quantile_tail_clamp_is_counted():
    """When the requested rank falls in the +Inf bucket the returned
    value is the last finite edge — a floor, not an estimate. That clamp
    must be observable: telemetry_quantile_tail_clamped_total{name}
    increments exactly when it happens (ISSUE 16 satellite)."""
    h = telemetry.histogram("t_clamp", buckets=(0.1, 1.0), labels=("k",))
    h.labels(k="a").observe(0.05)
    h.labels(k="a").observe(50.0)      # +Inf tail
    # p25 resolves inside a finite bucket: no clamp counted
    assert telemetry.histogram_quantile("t_clamp", 0.25, k="a") \
        == pytest.approx(0.05, abs=0.05)
    assert telemetry.read_series(
        "telemetry_quantile_tail_clamped_total") == {}
    # p99's rank lands in the overflow: clamped to the last edge + count
    assert telemetry.histogram_quantile("t_clamp", 0.99, k="a") == 1.0
    clamped = telemetry.read_series("telemetry_quantile_tail_clamped_total")
    assert clamped == {"name=t_clamp": 1.0}
    telemetry.histogram_quantile("t_clamp", 0.99, k="a")
    clamped = telemetry.read_series("telemetry_quantile_tail_clamped_total")
    assert clamped == {"name=t_clamp": 2.0}


# --- executor run tracing (ISSUE acceptance criteria) ------------------------

def _build_train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(n):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((n, 4)).astype("float32"),
            "y": rng.standard_normal((n, 1)).astype("float32")}


class TestExecutorTracing:
    def test_two_step_run_events_and_retrace_signature(self, tmp_path):
        log = str(tmp_path / "steps.jsonl")
        telemetry.enable_step_log(log)
        main, startup, loss = _build_train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed=_feed(8), fetch_list=[loss])

        events = telemetry.recent_events()
        compiles = [e for e in events if e["kind"] == "compile"]
        runs = [e for e in events if e["kind"] == "run"]
        # >= because the startup program compiles+runs too
        assert len(compiles) >= 1
        assert len(runs) >= 2
        assert all(e["kind"] != "cache_miss" for e in events)
        train_runs = [e for e in runs if e.get("mode") == "jit"
                      and e.get("feeds") == 2]
        assert len(train_runs) >= 2
        for e in train_runs:
            assert e["seconds"] >= e["execute_s"] >= 0
            assert e["compile_s"] >= 0
            assert e["feeds"] == 2 and e["fetches"] == 1
        assert train_runs[0]["cache"] == "miss"
        assert train_runs[1]["cache"] == "hit"

        # matching counters on the Prometheus surface
        text = telemetry.prometheus_text()
        assert "executor_runs_total" in text
        assert "executor_compiles_total" in text
        assert "optimizer_steps_total" in text
        snap = telemetry.snapshot()
        assert sum(snap["counters"]["executor_runs_total"].values()) == \
            len(runs)
        assert sum(snap["counters"]["executor_compiles_total"].values()) == \
            len(compiles)

        # changed batch size -> exactly one retrace event carrying the
        # NEW signature
        exe.run(main, feed=_feed(16), fetch_list=[loss])
        misses = [e for e in telemetry.recent_events()
                  if e["kind"] == "cache_miss"]
        assert len(misses) == 1
        sig = misses[0]["signature"]
        assert ["x", "(16, 4)", "float32"] in sig
        assert ["y", "(16, 1)", "float32"] in sig
        assert misses[0]["changed"]
        assert sum(telemetry.snapshot()["counters"]
                   ["executor_cache_misses_total"].values()) == 1

        # the same records landed in the JSONL file
        telemetry.disable_step_log()
        recs = telemetry.read_step_log(log)
        kinds = [r["kind"] for r in recs]
        assert kinds.count("run") >= 3
        assert kinds.count("compile") >= 1
        assert kinds.count("cache_miss") == 1
        assert all("ts" in r and "host" in r for r in recs)

    def test_global_norm_gauge_with_clipping(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(input=x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(clip_norm=1.0))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed=_feed(8), fetch_list=[loss])
        assert len(out) == 1   # side-fetch must not leak to the caller
        gauges = telemetry.snapshot()["gauges"]
        (norm,) = gauges["optimizer_global_norm"].values()
        assert norm > 0
        # and minimize() counted the build
        assert telemetry.snapshot()["counters"][
            "optimizer_minimize_total"]["optimizer=sgd"] >= 1

    def test_side_fetch_in_flight_is_published_when_the_executor_closes(self):
        """A side-fetch still on the device stays queued (a pipelined loop
        keeps its steps in flight); close() waits for it, so the last
        step's gauge is not lost, and each publication is a `side_fetch`
        event of the step log, in step order."""
        class InFlight:
            def __init__(self, value):
                self.value = value

            def is_ready(self):
                return False

            def __array__(self, dtype=None, copy=None):
                return np.asarray([self.value], dtype)

        exe = fluid.Executor(fluid.CPUPlace())
        for value in (2.5, 3.5):
            exe._side_pending.append(
                ("optimizer_global_norm", InFlight(value), "pipelined"))
        exe._publish_side_fetches()
        assert len(exe._side_pending) == 2
        assert "program=pipelined" not in telemetry.snapshot()[
            "gauges"].get("optimizer_global_norm", {})
        exe.close()
        assert not exe._side_pending
        assert telemetry.snapshot()["gauges"]["optimizer_global_norm"][
            "program=pipelined"] == 3.5
        events = [e for e in telemetry.recent_events(kind="side_fetch")
                  if e["program"] == "pipelined"]
        assert [e["values"] for e in events] == [[2.5], [3.5]]
        assert events[0]["metric"] == "optimizer_global_norm"

    def test_feed_conversion_metrics(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        feeder = fluid.DataFeeder(feed_list=[x], place=fluid.CPUPlace(),
                                  program=main)
        feeder.feed([(np.zeros(3, np.float32),) for _ in range(4)])
        snap = telemetry.snapshot()
        assert snap["counters"]["feed_conversion_seconds_total"][""] > 0
        assert snap["histograms"]["feed_conversion_seconds"][""]["count"] == 1

    def test_input_stall_histogram(self):
        from paddle_tpu.reader.pipeline import DoubleBufferedFeeder
        feeder = DoubleBufferedFeeder(
            lambda: iter([{"a": np.zeros(2)}] * 3))
        assert len(list(feeder)) == 3
        snap = telemetry.snapshot()
        assert snap["counters"]["input_batches_total"][""] == 3.0
        assert snap["histograms"]["input_stall_seconds"][""]["count"] >= 3


# --- Prometheus text round-trip ----------------------------------------------

def _parse_prometheus(text):
    """Minimal exposition-format parser: {(name, labels-string): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        metric, val = line.rsplit(" ", 1)
        name, _, labels = metric.partition("{")
        out[(name, labels.rstrip("}"))] = float(
            "inf" if val == "+Inf" else val)
    return out


class TestPrometheusExport:
    def test_round_trip_counter_gauge(self):
        telemetry.counter("rt_total", labels=("k",)).labels(k='va"l').inc(7)
        telemetry.gauge("rt_g").set(0.25)
        parsed = _parse_prometheus(telemetry.prometheus_text())
        assert parsed[("rt_total", 'k="va\\"l"')] == 7.0
        assert parsed[("rt_g", "")] == 0.25

    def test_histogram_exposition_is_cumulative_with_inf(self):
        h = telemetry.histogram("rt_h", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = telemetry.prometheus_text()
        parsed = _parse_prometheus(text)
        assert parsed[("rt_h_bucket", 'le="0.1"')] == 1
        assert parsed[("rt_h_bucket", 'le="1"')] == 2
        assert parsed[("rt_h_bucket", 'le="+Inf"')] == 3
        assert parsed[("rt_h_count", "")] == 3
        assert parsed[("rt_h_sum", "")] == pytest.approx(5.55)
        assert "# TYPE rt_h histogram" in text

    def test_help_and_type_lines(self):
        telemetry.counter("rt_doc_total", "documented metric").inc()
        text = telemetry.prometheus_text()
        assert "# HELP rt_doc_total documented metric" in text
        assert "# TYPE rt_doc_total counter" in text


# --- step log + chrome trace + CLI -------------------------------------------

class TestStepLogAndExports:
    def test_read_step_log_tolerates_torn_tail(self, tmp_path):
        p = tmp_path / "log.jsonl"
        telemetry.enable_step_log(str(p))
        telemetry.log_event("run", seconds=0.5)
        telemetry.disable_step_log()
        with open(p, "a") as f:
            f.write('{"kind": "run", "seco')   # crash mid-write
        recs = telemetry.read_step_log(str(p))
        assert len(recs) == 1 and recs[0]["seconds"] == 0.5

    def test_merged_chrome_trace(self, tmp_path):
        from paddle_tpu import profiler
        with profiler.profiler():
            with profiler.record("host_evt"):
                pass
        telemetry.log_event("run", seconds=0.001, program="p0")
        out = tmp_path / "trace.json"
        telemetry.export_chrome_trace(str(out))
        trace = json.loads(out.read_text())
        names = {e["name"] for e in trace["traceEvents"]}
        assert "host_evt" in names
        assert "run" in names
        cats = {e["name"]: e["cat"] for e in trace["traceEvents"]}
        assert cats["host_evt"] == "host"
        assert cats["run"] == "step"
        # profiler events publish into the registry too
        hist = telemetry.snapshot()["histograms"]["profiler_event_seconds"]
        assert hist["event=host_evt"]["count"] == 1

    def test_cli_snapshot_prometheus_and_log(self, tmp_path, capsys):
        from paddle_tpu import cli
        telemetry.counter("cli_total").inc(2)
        assert cli.main(["telemetry"]) == 0
        out = capsys.readouterr().out
        assert "cli_total = 2" in out
        assert cli.main(["telemetry", "--prometheus"]) == 0
        assert "cli_total 2" in capsys.readouterr().out

        log = tmp_path / "s.jsonl"
        telemetry.enable_step_log(str(log))
        telemetry.log_event("run", seconds=0.01)
        telemetry.log_event("cache_miss",
                            signature=[["x", "(8,)", "float32"]])
        telemetry.disable_step_log()
        assert cli.main(["telemetry", "--log", str(log)]) == 0
        out = capsys.readouterr().out
        assert "2 events" in out and "cache_miss" in out
        assert "retrace signature" in out
        assert cli.main(["telemetry", "--log", str(log), "--tail", "1"]) == 0
        (line,) = capsys.readouterr().out.strip().splitlines()
        assert json.loads(line)["kind"] == "cache_miss"

    def test_env_var_enables_step_log(self, tmp_path):
        p = tmp_path / "env.jsonl"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_STEP_LOG=str(p))
        code = ("import paddle_tpu.telemetry as t; "
                "t.log_event('run', seconds=1.0)")
        subprocess.run([sys.executable, "-c", code], check=True, env=env)
        recs = telemetry.read_step_log(str(p))
        assert len(recs) == 1 and recs[0]["kind"] == "run"


# --- cross-host reduce -------------------------------------------------------

class TestReduce:
    def test_single_process_reduce_is_local(self):
        telemetry.counter("r_total").inc(3)
        snap = telemetry.snapshot(reduce=True)
        assert snap["counters"]["r_total"][""] == 3.0

    def test_merge_snapshots_sums_all_kinds(self):
        a = {"counters": {"c": {"k=a": 1.0}}, "gauges": {"g": {"": 2.0}},
             "histograms": {"h": {"": {"buckets": [1.0], "counts": [1, 0],
                                       "sum": 0.5, "count": 1}}}}
        b = {"counters": {"c": {"k=a": 2.0, "k=b": 5.0}},
             "gauges": {"g": {"": 3.0}},
             "histograms": {"h": {"": {"buckets": [1.0], "counts": [0, 2],
                                       "sum": 4.0, "count": 2}}}}
        m = telemetry._merge_snapshots([a, b])
        assert m["hosts"] == 2
        assert m["counters"]["c"] == {"k=a": 3.0, "k=b": 5.0}
        assert m["gauges"]["g"][""] == 5.0
        h = m["histograms"]["h"][""]
        assert h["counts"] == [1, 2] and h["count"] == 3
        assert h["sum"] == pytest.approx(4.5)

    def test_two_process_reduce(self):
        """Real 2-process jax.distributed reduce over the coordination
        service (harness: test_jax_distributed)."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        env.pop("PADDLE_TRAINER_ID", None)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(HERE), env.get("PYTHONPATH", "")])
        procs = [subprocess.Popen(
            [sys.executable, os.path.join(HERE, "_telemetry_worker.py"),
             coordinator, "2", str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True) for pid in (0, 1)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=180)
                outs.append((p.returncode, out, err))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for rc, out, err in outs:
            assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\n" \
                            f"stderr:{err}"
            assert "RESULT" in out, out
        results = [json.loads(out.split("RESULT", 1)[1])
                   for _, out, _ in outs]
        assert all(r["counter"] == 3 for r in results)


# --- xplane aggregation (satellite) ------------------------------------------

def _varint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        out += bytes([b7 | (0x80 if n else 0)])
        if not n:
            return out


def _ld(fno, payload):
    return _varint((fno << 3) | 2) + _varint(len(payload)) + payload


def _vi(fno, val):
    return _varint(fno << 3) + _varint(val)


def _xevent(mid, ps, off=0):
    return _ld(4, _vi(1, mid) + _vi(2, off) + _vi(3, ps))   # XLine.events=4


def _xline(events, name=None):
    return (_ld(2, name.encode()) if name else b"") + b"".join(events)


def _xplane(name, lines, meta):
    body = _ld(2, name.encode())
    for line in lines:
        body += _ld(3, line)
    for mid, mname in meta.items():
        body += _ld(4, _vi(1, mid) + _ld(2, _vi(1, mid) +
                                         _ld(2, mname.encode())))
    return _ld(1, body)


class TestXplaneAggregation:
    def _write(self, tmp_path, planes):
        d = tmp_path / "trace"
        d.mkdir()
        (d / "host.xplane.pb").write_bytes(b"".join(planes))
        return str(d)

    def test_device_planes_read_the_ops_line_alone(self, tmp_path):
        from paddle_tpu import xplane
        meta = {1: "fusion.1", 2: "copy.2"}
        # the core's own `XLA Ops` line + a derived step line repeating
        # the instruction: counted once, never from the derived line
        raw = _xline([_xevent(1, 100), _xevent(2, 30, off=100)], "XLA Ops")
        derived = _xline([_xevent(1, 100)], "Steps")
        dev0 = _xplane("/device:TPU:0", [raw, derived], meta)
        dev1 = _xplane("/device:TPU:1", [raw], meta)
        host = _xplane("/host:CPU", [_xline([_xevent(1, 999)])], meta)
        trace = self._write(tmp_path, [dev0, dev1, host])
        steps = xplane.device_steps(trace)
        assert [s["device"] for s in steps] == ["/device:TPU:0",
                                                "/device:TPU:1"]
        for step in steps:      # a step per core, the host plane unread
            assert [(e[0], e[2]) for e in step["events"]] == [
                ("fusion.1", 100), ("copy.2", 30)]
        account = xplane.step_account(trace, accounts=[])
        assert sum(r["ms"] for s in account["steps"] for r in s["rows"]
                   if r["name"] == "fusion.1") == pytest.approx(200e-9)

    def test_host_only_trace_falls_back(self, tmp_path):
        from paddle_tpu import xplane
        meta = {1: "op.a"}
        host = _xplane("/host:CPU",
                       [_xline([_xevent(1, 10)]), _xline([_xevent(1, 5)])],
                       meta)
        steps = xplane.device_steps(self._write(tmp_path, [host]))
        # no device plane (the CPU backend): every host line that holds
        # instruction-like events is a "device" of its own, marked so
        assert [(s["host"], [(e[0], e[2]) for e in s["events"]])
                for s in steps] == [(True, [("op.a", 10)]),
                                    (True, [("op.a", 5)])]

    def test_plane_events_per_line_view(self, tmp_path):
        from paddle_tpu import xplane
        meta = {1: "op.a"}
        plane = _xplane("/device:TPU:0",
                        [_xline([_xevent(1, 10)]), _xline([_xevent(1, 7)])],
                        meta)
        d = self._write(tmp_path, [plane])
        (path,) = [os.path.join(d, f) for f in os.listdir(d)]
        per = xplane.plane_events(path)["/device:TPU:0"]
        assert [ln["events"] for ln in per] == [[("op.a", 0, 10)],
                                                [("op.a", 0, 7)]]


# --- satellite regression tests ----------------------------------------------

class TestSatellites:
    def test_print_op_grad_is_identity(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3], dtype="float32",
                                  append_batch_size=False,
                                  stop_gradient=False)
            printed = fluid.layers.Print(x, message="t: ")
            y = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(printed, printed))
            (gx,) = fluid.calc_gradient(y, x)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.array([1.0, -2.0, 3.0], np.float32)
        from paddle_tpu import executor as executor_mod
        with executor_mod.scope_guard(executor_mod.Scope()):
            (g,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
        np.testing.assert_allclose(np.asarray(g), 2 * xv, rtol=1e-6)

    def test_conv_operator_rejects_filter_layer(self):
        from paddle_tpu import v2
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[1, 8, 8],
                                    dtype="float32")
            with pytest.raises(ValueError, match="filter"):
                v2.layer.conv_operator(img, filter=img, filter_size=3,
                                       num_filters=2)

    def test_threadpool_submit_vs_shutdown_no_stranded_task(self):
        """A task that passed the closed check must run even when
        shutdown() lands immediately after — previously its queue entry
        could sit behind the _SHUTDOWN sentinels forever."""
        from paddle_tpu.threadpool import ThreadPool
        for _ in range(50):
            pool = ThreadPool(2)
            barrier = threading.Barrier(2)
            futs = []

            def submitter():
                barrier.wait()
                try:
                    for _ in range(20):
                        futs.append(pool.run(lambda: None))
                except RuntimeError:
                    pass           # closed: acceptable, just not a hang

            t = threading.Thread(target=submitter)
            t.start()
            barrier.wait()
            pool.shutdown()
            t.join(timeout=10)
            assert not t.is_alive()
            for f in futs:         # accepted => must complete
                f.result(timeout=10)

    def test_threadpool_run_after_shutdown_raises(self):
        from paddle_tpu.threadpool import ThreadPool
        pool = ThreadPool(1)
        pool.shutdown()
        with pytest.raises(RuntimeError, match="shut down"):
            pool.run(lambda: None)
