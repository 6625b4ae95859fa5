"""Profiler (reference: python/paddle/fluid/profiler.py:33 cuda_profiler,
:76 profiler; platform/profiler.cc, device_tracer.cc).

On TPU the device tracer is jax.profiler (XLA/TensorBoard trace). The host
event profiler records per-run wall times of the compiled block, mirroring
the reference's RecordEvent aggregation table."""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Optional

import jax

__all__ = ["cuda_profiler", "profiler", "start_profiler", "stop_profiler",
           "reset_profiler", "export_chrome_trace"]

_events: Dict[str, List[float]] = defaultdict(list)
# (name, start_ts, duration) triples for the chrome-trace export
# (reference tools/timeline.py:31 merges host+device events the same way)
_timeline: List = []
_active = False
_epoch = time.perf_counter()


# Cached telemetry handles for the host hot path: [module, family,
# registry-generation, {event_name: child}]. telemetry.reset() clears the
# registry, which would leave a bare cached child orphaned (observing into
# a family no exporter sees) — the generation int-compare catches that and
# re-resolves once instead of on every event.
_event_hist = [None, None, -1, {}]


def _event_child(name: str):
    tel = _event_hist[0]
    if tel is None:
        from . import telemetry as tel
        _event_hist[0] = tel
    gen = tel.registry().generation()
    if _event_hist[1] is None or _event_hist[2] != gen:
        _event_hist[1] = tel.histogram(
            "profiler_event_seconds", "host profiler event durations",
            labels=("event",))
        _event_hist[2] = gen
        _event_hist[3] = {}
    children = _event_hist[3]
    child = children.get(name)
    if child is None:
        child = children[name] = _event_hist[1].labels(event=name)
    return child


def record_event(name: str, seconds: float, start: Optional[float] = None):
    if _active:
        _events[name].append(seconds)
        if start is not None:
            _timeline.append((name, start - _epoch, seconds))
        # publish into the shared registry too, so one telemetry snapshot
        # answers both "which op eats the step" and "which step ate the
        # minute" (ISSUE tentpole: profiler keeps its API, feeds telemetry)
        _event_child(name).observe(seconds)


@contextlib.contextmanager
def record(name: str):
    if not _active:      # keep the interpreter hot path overhead-free
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record_event(name, time.perf_counter() - t0, start=t0)


def is_active() -> bool:
    return _active


def reset_profiler():
    _events.clear()
    _timeline.clear()


def export_chrome_trace(path: str):
    """Write recorded host events as a Chrome tracing JSON (chrome://tracing
    / Perfetto), the host half of the reference's timeline.py:31 output.
    Device-side kernels live in the TensorBoard trace captured by
    profiler(trace_dir=...) — point Perfetto at both for the merged view."""
    import json
    events = [{"name": name, "ph": "X", "pid": 0, "tid": 0,
               "ts": start * 1e6, "dur": dur * 1e6,
               "cat": "host"}
              for name, start, dur in _timeline]
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return path


def start_profiler(state="All", trace_dir: Optional[str] = None):
    global _active
    _active = True
    from . import telemetry
    telemetry.counter(
        "profiler_sessions_total", "profiling sessions started",
        labels=("traced",)).labels(
            traced=str(bool(trace_dir)).lower()).inc()
    _steps_at_start[0] = sum(
        telemetry.read_series("executor_steps_total").values())
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    _start_trace_dir[0] = trace_dir


_start_trace_dir = [None]
_steps_at_start = [0.0]


def wants_device_table() -> bool:
    """True while a traced (trace_dir) profiling session is active — the
    executor then makes sure every block it launches has its account by
    instruction (xplane.remember_account; a block compiled with the
    static memory analysis on already has), so that the device report at
    stop compiles nothing."""
    return _active and _start_trace_dir[0] is not None


def _traced_steps() -> Optional[int]:
    """Executor steps run during the current traced session (delta of the
    executor_steps_total counter since start_profiler); None when no step
    ran — the report then skips flops-rate columns rather than divide by
    a guessed step count."""
    from . import telemetry
    delta = sum(telemetry.read_series(
        "executor_steps_total").values()) - _steps_at_start[0]
    return int(delta) if delta > 0 else None


def _end_trace():
    trace_dir = _start_trace_dir[0]
    if trace_dir:
        jax.profiler.stop_trace()
        _start_trace_dir[0] = None
    return trace_dir


def stop_profiler(sorted_key=None, profile_path=None):
    global _active
    _active = False
    trace_dir = _end_trace()
    _print_table(sorted_key)
    if trace_dir:
        _print_device_table(trace_dir, sorted_key)
    try:
        if jax.process_count() > 1:
            # multi-process runs get the fleet line: step skew, slowest
            # host and goodput — the cross-host view no single-host table
            # above can show
            from . import fleet
            print(fleet.format_fleet(fleet.fleet_snapshot()))
            gp = fleet.goodput_report()
            if gp:
                print("[fleet] goodput {:.1%} over {:.2f}s wall".format(
                    gp["goodput_fraction"], gp["span_s"]))
    except Exception:  # noqa: BLE001 - summary line is best-effort
        pass


def finish_trace_report(steps: Optional[int] = None, probe: bool = True):
    """Silent counterpart of stop_profiler for programmatic capture
    (roofline.capture, fleet.capture): stop the traced session and return
    the roofline report dict without printing anything. Returns None when
    no trace was active."""
    global _active
    _active = False
    trace_dir = _end_trace()
    if not trace_dir:
        return None
    from . import roofline
    return roofline.collect_report(
        trace_dir, steps=steps if steps is not None else _traced_steps(),
        probe=probe)


def _print_device_table(trace_dir, sorted_key=None):
    """Per-IR-op device-time attribution for the whole-block jit
    (reference ParseEvents, platform/profiler.h:137-166): the trace's
    instructions joined to the accounts the executor kept of its compiled
    blocks (xplane.step_account: op instance, FLOPs, bytes, floor),
    folded by program op and enriched by roofline.py with the analytic
    FLOPs/bytes, achieved TF/s and a compute/memory/unattributed verdict.
    Unmapped device time is pooled under "(unattributed)" so fractions
    sum to the true device total. Nothing is compiled here."""
    from . import roofline

    try:
        report = roofline.collect_report(trace_dir, steps=_traced_steps())
    except Exception as e:  # noqa: BLE001 - truncated/foreign .xplane.pb
        print(f"[device] (trace unreadable: {type(e).__name__}: {e})")
        return
    if report is None or not report.get("rows"):
        return
    if not report.get("mapped"):
        # no account names what ran (eager run, foreign trace): keep the
        # old silent behaviour instead of an all-unattributed table
        return
    for line in roofline.format_report(report):
        print(line)


def _print_table(sorted_key=None):
    if not _events:
        return
    rows = []
    for name, times in _events.items():
        total = sum(times)
        rows.append((name, len(times), total, total / len(times),
                     min(times), max(times)))
    if sorted_key in ("total", None):
        rows.sort(key=lambda r: -r[2])
    elif sorted_key == "calls":
        rows.sort(key=lambda r: -r[1])
    elif sorted_key == "ave":
        rows.sort(key=lambda r: -r[3])
    print(f"{'Event':40s} {'Calls':>8s} {'Total(s)':>10s} {'Ave(s)':>10s} "
          f"{'Min(s)':>10s} {'Max(s)':>10s}")
    for name, calls, total, ave, mn, mx in rows:
        print(f"{name:40s} {calls:8d} {total:10.4f} {ave:10.4f} "
              f"{mn:10.4f} {mx:10.4f}")


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Source-compat alias: wraps an XLA trace around the block
    (reference profiler.py:33 drove nvprof)."""
    with profiler("All", trace_dir=output_file):
        yield


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             trace_dir: Optional[str] = None):
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
