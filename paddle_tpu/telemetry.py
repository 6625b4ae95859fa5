"""Unified telemetry: process-wide metrics registry + structured step log.

The reference framework's observability is split across platform/profiler.cc
(host event table), device_tracer.cc (CUPTI kernels) and tools/timeline.py
(post-hoc trace merge). This module is the TPU-native consolidation: one
process-wide registry of counters / gauges / log-scale histograms (labeled,
Prometheus-exportable, fleet-reducible over hosts) plus a structured
step-event log — one JSONL record per Executor.run with the
compile-vs-execute split, donated-buffer stats and the shape/dtype
signature that caused any jit retrace. `profiler.py` (host wall times) and
`xplane.py` (device HLO attribution) keep their APIs but publish into this
registry, so a single `snapshot()` answers both "which op eats the step"
and "which step ate the minute".

Hot-path cost: one lock + dict update per metric op; event logging is a
dict build + deque append (and one JSON line when a sink is enabled).
Everything is import-light — jax is only touched for the cross-host
reduce and the compile-time listener, both lazily/guarded.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "counter", "gauge", "histogram", "registry", "MetricsRegistry",
    "snapshot", "prometheus_text", "log_event", "recent_events",
    "enable_step_log", "disable_step_log", "step_log_path", "read_step_log",
    "export_chrome_trace", "default_buckets", "reset", "program_label",
    "jax_compile_seconds", "watch_build", "merge_build_events",
    "build_phase_seconds",
    "signature_of", "is_ready", "host_wait", "read_gauge", "read_series",
    "read_histogram", "histogram_quantile",
]


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

def default_buckets() -> Tuple[float, ...]:
    """Fixed log-scale histogram buckets: powers of 4 from 1 microsecond to
    ~67 seconds. Fixed (not adaptive) so bucket counts from different hosts
    and different runs add cell-wise — the property the cross-host reduce
    and Prometheus rate() queries rely on."""
    return tuple(1e-6 * (4.0 ** i) for i in range(14))


def _label_key(labels: Dict[str, str]) -> str:
    """Canonical serialized label set — doubles as the cross-host merge key."""
    return ",".join(f"{k}={labels[k]}" for k in sorted(labels))


# one lock for all series mutations: += on an attribute is a
# read-modify-write and reader/feeder threads update concurrently with the
# training loop; contention is negligible at per-step granularity
_VALUES_LOCK = threading.Lock()


class _Child:
    """One (metric, label-values) time series."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, amount: float = 1.0):
        with _VALUES_LOCK:
            self.value += amount

    def set(self, value: float):
        with _VALUES_LOCK:
            self.value = float(value)


class _HistChild:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float]):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)   # last = +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float):
        value = float(value)
        with _VALUES_LOCK:
            self.sum += value
            self.count += 1
            for i, le in enumerate(self.buckets):
                if value <= le:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1


class _Family:
    """A named metric with a fixed label-name schema; `.labels(**kw)`
    resolves (and lazily creates) one child series per label-value tuple.
    Label-free families proxy inc/set/observe to their single () child."""

    kind = "counter"

    def __init__(self, reg: "MetricsRegistry", name: str, help: str,
                 labelnames: Sequence[str], buckets=None):
        self._reg = reg
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._buckets = tuple(buckets) if buckets else None
        self._children: Dict[Tuple[str, ...], Any] = {}

    def _make_child(self):
        if self.kind == "histogram":
            return _HistChild(self._buckets or default_buckets())
        return _Child()

    def labels(self, **kw):
        if set(kw) != set(self.labelnames):
            raise ValueError(
                f"metric '{self.name}' takes labels {self.labelnames}, "
                f"got {sorted(kw)}")
        key = tuple(str(kw[k]) for k in self.labelnames)
        with self._reg._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make_child()
            return child

    def _default_child(self):
        if self.labelnames:
            raise ValueError(
                f"metric '{self.name}' is labeled {self.labelnames}; "
                f"use .labels(...)")
        return self.labels()

    # label-free conveniences
    def inc(self, amount: float = 1.0):
        self._default_child().inc(amount)

    def set(self, value: float):
        self._default_child().set(value)

    def observe(self, value: float):
        self._default_child().observe(value)

    def series(self) -> Dict[str, Any]:
        """{serialized-labels: child} snapshot view."""
        with self._reg._lock:
            return {_label_key(dict(zip(self.labelnames, k))): c
                    for k, c in self._children.items()}


class _Counter(_Family):
    kind = "counter"


class _Gauge(_Family):
    kind = "gauge"


class _Histogram(_Family):
    kind = "histogram"


class MetricsRegistry:
    """Thread-safe name -> metric family registry. Re-registering the same
    name with the same kind returns the existing family (idempotent, so
    instrumented modules can declare metrics at call sites)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = {}
        self._generation = 0

    def _get_or_make(self, cls, name, help, labels, buckets=None):
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != cls.kind:
                    raise ValueError(
                        f"metric '{name}' already registered as {fam.kind}")
                return fam
            fam = cls(self, name, help, labels, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> _Counter:
        return self._get_or_make(_Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> _Gauge:
        return self._get_or_make(_Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "", labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> _Histogram:
        return self._get_or_make(_Histogram, name, help, labels, buckets)

    def families(self) -> List[_Family]:
        with self._lock:
            return list(self._families.values())

    def clear(self):
        with self._lock:
            self._families.clear()
            self._generation += 1

    def generation(self) -> int:
        """Bumped by clear()/reset(); lets hot paths that cache a family or
        child handle (profiler.record_event) self-invalidate with one int
        compare instead of re-resolving through the registry lock.
        Deliberately lock-free: int loads are atomic under the GIL and a
        stale read only costs one redundant re-resolve."""
        return self._generation  # thread-lint: ok lockset-mixed-guard

    # --- snapshots ----------------------------------------------------------
    def local_snapshot(self) -> Dict[str, Any]:
        """JSON-serializable view of every series in this process."""
        snap = {"host": _host_index(), "counters": {}, "gauges": {},
                "histograms": {}}
        for fam in self.families():
            if fam.kind == "histogram":
                dst = snap["histograms"].setdefault(fam.name, {})
                for lk, ch in fam.series().items():
                    with _VALUES_LOCK:   # counts/sum/count read consistently
                        dst[lk] = {"buckets": list(ch.buckets),
                                   "counts": list(ch.counts),
                                   "sum": ch.sum, "count": ch.count}
            else:
                dst = snap[fam.kind + "s"].setdefault(fam.name, {})
                for lk, ch in fam.series().items():
                    dst[lk] = ch.value
        return snap


_REG = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REG


def counter(name: str, help: str = "", labels: Sequence[str] = ()):
    return _REG.counter(name, help, labels)


def gauge(name: str, help: str = "", labels: Sequence[str] = ()):
    return _REG.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels: Sequence[str] = (),
              buckets: Optional[Sequence[float]] = None):
    return _REG.histogram(name, help, labels, buckets)


def read_gauge(name: str, **labels) -> Optional[float]:
    """Last value of one gauge series, or None when the family or the exact
    label set does not exist yet. A read-only peek: unlike `.labels(...)` it
    never creates the series, so observers (the inspector flight recorder
    reading optimizer_global_norm) cannot pollute the registry with empty
    children."""
    with _REG._lock:
        fam = _REG._families.get(name)
        if fam is None or fam.kind != "gauge":
            return None
        if set(labels) != set(fam.labelnames):
            return None
        child = fam._children.get(
            tuple(str(labels[k]) for k in fam.labelnames))
        return None if child is None else child.value


def read_series(name: str) -> Dict[str, float]:
    """All series of one counter/gauge family as {label_key: value}
    (label_key is the registry's serialized 'k=v,k=v' form; the unlabeled
    series maps from ''). Same read-only contract as read_gauge: never
    creates the family or any child. Empty when the family is absent or a
    histogram. Used by the memory CLI to fold per-device hbm_*
    gauges without knowing the device labels in advance."""
    with _REG._lock:
        fam = _REG._families.get(name)
        if fam is None or fam.kind == "histogram":
            return {}
        return {
            ",".join(f"{k}={v}" for k, v in zip(fam.labelnames, key)):
                child.value
            for key, child in fam._children.items()}


def read_histogram(name: str, **labels) -> Optional[Dict[str, float]]:
    """{'sum', 'count'} of one histogram series, or None when the family or
    the exact label set does not exist. Same read-only contract as
    read_gauge — never creates the family or a child. Used by fleet.py to
    price input stall (input_stall_seconds) and checkpoint badput without
    registering the histograms from an observer."""
    with _REG._lock:
        fam = _REG._families.get(name)
        if fam is None or fam.kind != "histogram":
            return None
        if set(labels) != set(fam.labelnames):
            return None
        child = fam._children.get(
            tuple(str(labels[k]) for k in fam.labelnames))
        if child is None:
            return None
        with _VALUES_LOCK:
            return {"sum": child.sum, "count": child.count}


def histogram_quantile(name: str, q: float, **labels) -> Optional[float]:
    """Quantile estimate of one histogram series from its cumulative bucket
    counts (Prometheus histogram_quantile semantics: find the bucket whose
    cumulative count crosses rank q*total, interpolate linearly inside it).
    Accuracy is bounded by the bucket geometry — with default_buckets()'s
    powers-of-4 ladder an estimate is within 4x of the true value, which is
    enough to rank p50 against p99 and track trends. None when the series
    does not exist or has no observations; same read-only contract as
    read_histogram. The serving harness reads request-latency p50/p99 here."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    with _REG._lock:
        fam = _REG._families.get(name)
        if fam is None or fam.kind != "histogram":
            return None
        if set(labels) != set(fam.labelnames):
            return None
        child = fam._children.get(
            tuple(str(labels[k]) for k in fam.labelnames))
        if child is None:
            return None
        with _VALUES_LOCK:
            counts = list(child.counts)
            edges = list(child.buckets)
            total = child.count
    if total <= 0:
        return None
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts[:-1]):
        prev = cum
        cum += c
        if cum >= rank and c > 0:
            lo = edges[i - 1] if i > 0 else 0.0
            hi = edges[i]
            frac = min(max((rank - prev) / c, 0.0), 1.0)
            return lo + (hi - lo) * frac
    # rank fell in the +Inf tail: the true quantile is beyond the last
    # finite edge, so the returned value is a floor, not an estimate.
    # Signal that once per (name, scrape interval) via a counter so
    # dashboards can annotate the clamped p99 instead of trusting it.
    counter("telemetry_quantile_tail_clamped_total",
            "histogram_quantile ranks that fell in the +Inf bucket and "
            "were clamped to the last finite edge (the returned quantile "
            "is a floor)", labels=("name",)).labels(name=name).inc()
    return edges[-1]


def _host_index() -> int:
    # env-derived (reference PADDLE_TRAINER_ID): reading jax.process_index()
    # here would force backend init from a metrics call
    return int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)


# ---------------------------------------------------------------------------
# Cross-host reduce
# ---------------------------------------------------------------------------

def _merge_snapshots(snaps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Sum-merge per-host snapshots into fleet totals. Counters, histogram
    cells and gauges all add — a reduced gauge is the fleet total (e.g.
    per-host queue depths sum to fleet backlog); per-host values stay
    available in the unreduced snapshot."""
    out = {"hosts": len(snaps), "counters": {}, "gauges": {},
           "histograms": {}}
    for snap in snaps:
        for kind in ("counters", "gauges"):
            for name, series in snap.get(kind, {}).items():
                dst = out[kind].setdefault(name, {})
                for lk, v in series.items():
                    dst[lk] = dst.get(lk, 0.0) + v
        for name, series in snap.get("histograms", {}).items():
            dst = out["histograms"].setdefault(name, {})
            for lk, h in series.items():
                acc = dst.get(lk)
                if acc is None or list(acc["buckets"]) != list(h["buckets"]):
                    if acc is None:
                        dst[lk] = {"buckets": list(h["buckets"]),
                                   "counts": list(h["counts"]),
                                   "sum": h["sum"], "count": h["count"]}
                    else:   # bucket-schema skew: keep first host's layout,
                        acc["sum"] += h["sum"]         # fold scalars only
                        acc["count"] += h["count"]
                    continue
                acc["counts"] = [a + b for a, b in
                                 zip(acc["counts"], h["counts"])]
                acc["sum"] += h["sum"]
                acc["count"] += h["count"]
    return out


def snapshot(reduce: bool = False) -> Dict[str, Any]:
    """Registry snapshot. reduce=True returns FLEET-WIDE totals: every
    host's snapshot rides an allgather (parallel/_collectives.py) and the
    series sum-merge by (metric, labels) — the multi-controller equivalent
    of scraping every pserver and adding (single-process: identical to the
    local snapshot)."""
    local = _REG.local_snapshot()
    if not reduce:
        return local
    from .parallel import multihost
    payloads = multihost.allgather_bytes(
        json.dumps(local, sort_keys=True).encode("utf-8"))
    return _merge_snapshots([json.loads(p.decode("utf-8"))
                             for p in payloads])


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def _prom_labels(label_key: str, extra: str = "") -> str:
    if not label_key and not extra:
        return ""
    parts = []
    if label_key:
        for pair in label_key.split(","):
            k, _, v = pair.partition("=")
            v = v.replace("\\", "\\\\").replace('"', '\\"')
            parts.append(f'{k}="{v}"')
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}"


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def prometheus_text(snap: Optional[Dict[str, Any]] = None) -> str:
    """Render a snapshot (default: local) in the Prometheus text exposition
    format — counters/gauges as single samples, histograms as cumulative
    `_bucket{le=...}` + `_sum` + `_count` (the scrape surface a serving
    fleet sidecar exposes)."""
    snap = snap if snap is not None else _REG.local_snapshot()
    helps = {f.name: (f.help, f.kind) for f in _REG.families()}
    lines: List[str] = []
    for kind_key, prom_kind in (("counters", "counter"), ("gauges", "gauge")):
        for name in sorted(snap.get(kind_key, {})):
            help_, _ = helps.get(name, ("", prom_kind))
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {prom_kind}")
            for lk in sorted(snap[kind_key][name]):
                lines.append(f"{name}{_prom_labels(lk)} "
                             f"{_fmt(snap[kind_key][name][lk])}")
    for name in sorted(snap.get("histograms", {})):
        help_, _ = helps.get(name, ("", "histogram"))
        if help_:
            lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} histogram")
        for lk in sorted(snap["histograms"][name]):
            h = snap["histograms"][name][lk]
            cum = 0
            for le, c in zip(list(h["buckets"]) + [math.inf], h["counts"]):
                cum += c
                le_label = 'le="%s"' % _fmt(le)
                lines.append(
                    f"{name}_bucket{_prom_labels(lk, le_label)} {cum}")
            lines.append(f"{name}_sum{_prom_labels(lk)} {_fmt(h['sum'])}")
            lines.append(f"{name}_count{_prom_labels(lk)} {h['count']}")
    return "\n".join(lines) + ("\n" if lines else "")


# ---------------------------------------------------------------------------
# Structured step-event log (JSONL)
# ---------------------------------------------------------------------------

_EVENTS_MAX = 4096
_events: "collections.deque" = collections.deque(maxlen=_EVENTS_MAX)
_events_lock = threading.Lock()
_log_path: Optional[str] = None
_log_file = None


def enable_step_log(path: str):
    """Mirror every event to `path` as one JSON line per event (in addition
    to the in-memory ring buffer). Also settable via PADDLE_TPU_STEP_LOG."""
    global _log_path, _log_file
    # open() hits the filesystem — do it before taking the lock so a
    # slow/hung open can't stall every concurrent log_event(); only the
    # reference swap happens under _events_lock
    f = open(path, "a", buffering=1)   # line-buffered
    with _events_lock:
        old, _log_file = _log_file, f
        _log_path = path
    if old is not None:
        old.close()


def disable_step_log():
    global _log_path, _log_file
    with _events_lock:
        old, _log_file = _log_file, None
        _log_path = None
    if old is not None:
        old.close()


def step_log_path() -> Optional[str]:
    with _events_lock:
        return _log_path


def log_event(kind: str, **fields) -> Dict[str, Any]:
    """Record a structured event: wall timestamp + monotonic timestamp
    (perf_counter, merge key for the chrome-trace export) + host + kind +
    caller fields. Returns the record."""
    rec = {"ts": time.time(), "mono": time.perf_counter(),
           "host": _host_index(), "kind": kind}
    rec.update(fields)
    with _events_lock:
        _events.append(rec)
        if _log_file is not None:
            try:
                _log_file.write(json.dumps(rec, default=str) + "\n")
            except (OSError, ValueError):
                pass    # a torn sink must never kill the training step
    return rec


def recent_events(n: Optional[int] = None,
                  kind: Optional[str] = None) -> List[Dict[str, Any]]:
    with _events_lock:
        evs = list(_events)
    if kind is not None:
        evs = [e for e in evs if e.get("kind") == kind]
    return evs[-n:] if n else evs


def read_step_log(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL step log; tolerates a torn final line (crash mid-write)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


if os.environ.get("PADDLE_TPU_STEP_LOG"):
    enable_step_log(os.environ["PADDLE_TPU_STEP_LOG"])


# ---------------------------------------------------------------------------
# Merged chrome-trace export
# ---------------------------------------------------------------------------

def export_chrome_trace(path: str, events: Optional[Iterable[Dict]] = None):
    """One Perfetto-loadable timeline with BOTH telemetry step events (run /
    compile / cache_miss rows, tid 1) and the profiler's host events (tid 0)
    — the merged view the reference's tools/timeline.py produced from
    separate host+device dumps. Both sources share the perf_counter
    timebase ('mono' here, profiler._epoch there)."""
    from . import profiler as profiler_mod
    epoch = profiler_mod._epoch
    trace = [{"name": name, "ph": "X", "pid": 0, "tid": 0,
              "ts": start * 1e6, "dur": dur * 1e6, "cat": "host"}
             for name, start, dur in profiler_mod._timeline]
    for e in (events if events is not None else recent_events()):
        dur = float(e.get("seconds", 0.0) or 0.0)
        start = float(e.get("mono", 0.0)) - epoch - dur
        args = {k: v for k, v in e.items()
                if k not in ("mono", "kind") and _json_ok(v)}
        trace.append({"name": e.get("kind", "event"), "ph": "X",
                      "pid": 0, "tid": 1, "ts": start * 1e6,
                      "dur": dur * 1e6, "cat": "step", "args": args})
    with open(path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)
    return path


def _json_ok(v) -> bool:
    return isinstance(v, (str, int, float, bool, list, tuple, type(None)))


# ---------------------------------------------------------------------------
# Executor-facing helpers
# ---------------------------------------------------------------------------

_prog_labels: Dict[int, str] = {}
_prog_seq = [0]
_prog_lock = threading.Lock()


def program_label(program) -> str:
    """Stable short label for a Program within this process ("p0", "p1"…)
    — id() is unreadable and Programs carry no user-facing name."""
    lbl = getattr(program, "_telemetry_label", None)
    if lbl is None:
        # the seq bump is a read-modify-write; two threads labelling
        # concurrently must not mint the same "pN"
        with _prog_lock:
            lbl = getattr(program, "_telemetry_label", None)
            if lbl is None:
                lbl = f"p{_prog_seq[0]}"
                _prog_seq[0] += 1
                try:
                    program._telemetry_label = lbl
                except AttributeError:
                    pass
    return lbl


def signature_of(feed_vals: Dict[str, Any]) -> Tuple[Tuple[str, str, str], ...]:
    """(name, shape, dtype) triples for a feed dict — the retrace identity:
    jax.jit keys its trace cache on exactly these avals."""
    sig = []
    for name in sorted(feed_vals):
        v = feed_vals[name]
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        sig.append((name, str(tuple(shape) if shape is not None else ()),
                    str(dtype)))
    return tuple(sig)


def is_ready(value) -> bool:
    """Whether reading `value` on the host would not wait on the device:
    a jax array says so itself, anything else is on the host already."""
    ready = getattr(value, "is_ready", None)
    return ready is None or ready()


def host_wait(value, program: str, site: str):
    """Block the host until `value`, a device array or a pytree of them,
    is computed, and hand it back: the one way the executor waits on the
    device for a value the caller did not ask for. The wait is booked
    where it is made, as seconds of the blocking call alone in
    executor_host_wait_seconds{program, site}, and only when a leaf is
    not ready on arrival: a value already computed costs nothing and
    books nothing, so a steady pipelined loop reads 0 under every site."""
    import jax
    if all(is_ready(leaf) for leaf in jax.tree_util.tree_leaves(value)):
        return value
    t0 = time.perf_counter()
    jax.block_until_ready(value)
    histogram(
        "executor_host_wait_seconds",
        "seconds the host blocked on a device value the caller did not "
        "ask for, by the site that waited: dynamics (a forced drain of "
        "the pending samples), side_fetch (the flight recorder's norm), "
        "check_nan_inf, profiler_sync, lod_writeback",
        labels=("program", "site")).labels(
            program=program, site=site).observe(time.perf_counter() - t0)
    return value


# Accumulated backend-compile seconds, fed by jax.monitoring: XLA fires
# '/jax/core/compile/backend_compile_duration' for every real compilation
# (including jit retraces the executor-level cache can't see). Reading the
# accumulator before/after a run call splits that run's wall time into
# compile vs execute without AOT-lowering anything.
_compile_secs = [0.0]
_compile_listener_installed = [False]
# While the executor watches a call that may build a block (watch_build),
# every trace / lower / compile event jax reports inside it, as (phase,
# start, end) on time.monotonic(). None outside: a steady step pays one
# comparison, and only when jax compiles something.
_BUILD_PHASE_OF = {"jaxpr_trace_duration": "trace",
                   "jaxpr_to_mlir_module_duration": "lower",
                   "backend_compile_duration": "compile"}
_build_watch: List[Optional[list]] = [None]
# the program whose block the watched call may build: the label of the
# persistent cache's hits and misses inside it ("-" outside any watch:
# a jit of the caller's own)
_build_program: List[Optional[str]] = [None]
_CACHE_RESULT_OF = {"cache_hits": "hit", "cache_misses": "miss"}


def _install_compile_listener():
    if _compile_listener_installed[0]:
        return
    _compile_listener_installed[0] = True
    try:
        import jax.monitoring

        def _on_duration(name, secs, **kw):
            watch = _build_watch[0]
            if watch is not None:
                phase = _BUILD_PHASE_OF.get(name.rsplit("/", 1)[-1])
                if phase is not None:
                    end = time.monotonic()
                    watch.append((phase, end - float(secs), end))
            if name.endswith("backend_compile_duration"):
                _compile_secs[0] += float(secs)
                counter("jax_backend_compile_seconds_total",
                        "XLA backend compile wall seconds").inc(float(secs))
                counter("jax_backend_compiles_total",
                        "XLA backend compilations").inc()

        def _on_event(name, **kw):
            # jax's persistent compilation cache says of every executable
            # it was asked for whether it loaded it or had to compile:
            # what setup_compile_s cannot tell (a load and a compile are
            # both `backend_compile` seconds)
            if "/compilation_cache/" in name:
                result = _CACHE_RESULT_OF.get(name.rsplit("/", 1)[-1])
                if result is not None:
                    counter(
                        "executor_compile_cache_total",
                        "executables jax's persistent compilation cache "
                        "was asked for, by the program whose block was "
                        "being built (- outside one) and whether it was "
                        "loaded (hit) or compiled (miss)",
                        labels=("program", "result")).labels(
                            program=_build_program[0] or "-",
                            result=result).inc()

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    except Exception:   # jax absent/too old: compile split degrades to 0
        pass


def jax_compile_seconds() -> float:
    """Monotone accumulator of XLA backend-compile seconds in this process."""
    _install_compile_listener()
    return _compile_secs[0]


@contextlib.contextmanager
def watch_build(program: Optional[str] = None):
    """Collect jax's own trace / lower / compile events for the length of
    the block: yields the list they land in, [(phase, start, end)] on
    time.monotonic(). `backend_compile` covers a persistent-cache load
    too: which of the two it was is booked beside it, under `program`, as
    executor_compile_cache_total{program, result}. The executor opens
    this around a call whose signature it has not seen, never around a
    steady step. Not re-entrant: an inner watch (a nested Executor.run)
    takes the events, the outer one resumes."""
    _install_compile_listener()
    saved, events = (_build_watch[0], _build_program[0]), []
    _build_watch[0], _build_program[0] = events, program
    try:
        yield events
    finally:
        _build_watch[0], _build_program[0] = saved


def merge_build_events(events) -> List[Tuple[str, float, float]]:
    """watch_build() events with each phase's overlapping intervals
    joined: a jit traced inside another (jax reports both, the inner
    inside the outer) is one stretch, counted once."""
    merged: List[Tuple[str, float, float]] = []
    for phase in sorted(set(e[0] for e in events)):
        reach = None
        for start, end in sorted((s, e) for p, s, e in events if p == phase):
            if reach is None or start > reach:
                merged.append((phase, start, end))
                reach = end
            elif end > reach:
                merged[-1] = (phase, merged[-1][1], end)
                reach = end
    return merged


def build_phase_seconds(events) -> Dict[str, float]:
    """{phase: seconds} the watch_build() events cover on the clock."""
    out: Dict[str, float] = {}
    for phase, start, end in merge_build_events(events):
        out[phase] = out.get(phase, 0.0) + (end - start)
    return out


_install_compile_listener()


def reset():
    """Clear every metric series and the in-memory event buffer (tests).
    The JSONL sink, program labels and the compile accumulator survive —
    they are process-lifetime state."""
    _REG.clear()
    with _events_lock:
        _events.clear()


# --- declared metric catalog -------------------------------------------------
# The single source of truth for every metric family this codebase may
# create: name -> (kind, label set, help). `tools/check_registry.py
# check_metric_names` lints both directions against it — every
# counter()/gauge()/histogram() call site in paddle_tpu/ must declare a
# cataloged name with the cataloged label set, and every catalog entry
# must have at least one emitter — so label-set drift between emitters
# and readers (read_gauge/fleet.py/obs dashboards) is caught at lint
# time, not on a dashboard. Entries marked dynamic=True are created with
# a computed name (a loop or a program-attached mark) that the AST
# scanner cannot see; the lint exempts them from the needs-an-emitter
# direction but still checks any readers.

def _m(kind, labels=(), help="", dynamic=False):
    return {"kind": kind, "labels": tuple(labels), "help": help,
            "dynamic": dynamic}


METRIC_CATALOG = {
    # executor
    "executor_runs_total": _m("counter", ("program", "place", "mode"),
                              "Executor.run calls"),
    "executor_steps_total": _m("counter", ("program", "place"),
                               "training/eval steps executed"),
    "executor_run_seconds": _m("histogram", ("program", "mode"),
                               "Executor.run wall seconds"),
    "executor_last_step_seconds": _m("gauge", (),
                                     "wall seconds of the latest step"),
    "executor_host_wait_seconds": _m(
        "histogram", ("program", "site"),
        "seconds the host blocked on a device value the caller did not "
        "ask for, by site (telemetry.host_wait); nothing in a steady "
        "pipelined loop"),
    "executor_compiles_total": _m("counter", ("program", "place"),
                                  "block traces/compiles"),
    "executor_compile_seconds_total": _m(
        "counter", ("program", "place"),
        "XLA compile wall seconds inside Executor.run"),
    "executor_compile_cache_total": _m(
        "counter", ("program", "result"),
        "executables jax's persistent compilation cache was asked for, by "
        "program and hit (loaded) or miss (compiled)"),
    "executor_build_seconds_total": _m(
        "counter", ("program", "phase"),
        "first run of a compiled block, by phase: trace, lower, compile, "
        "analysis, execute"),
    "executor_cache_hits_total": _m(
        "counter", ("program", "place"),
        "runs served by an already-traced signature"),
    "executor_cache_misses_total": _m(
        "counter", ("program", "place"), "signature-cache misses"),
    "executor_window_fallback_total": _m(
        "counter", ("program", "reason"),
        "run_steps windows that fell back to per-step execution"),
    "recompute_segments_total": _m(
        "counter", ("program", "decision", "reason"),
        "segments between two checkpoints, a trace, by what the executor "
        "decided from their bytes and the device's limit (recompute.py): "
        "kept (fits: nothing runs twice) or replayed (budget, no_limit: "
        "the device reports none, unknown_shape)"),
    "recompute_fallback_total": _m(
        "counter", ("program",),
        "compiles that ran out of device memory with segments kept and "
        "were made again with every segment replayed"),
    "recompute_segments_kept_bytes": _m(
        "gauge", ("program",),
        "bytes the kept segments hold across the turn to the backward, "
        "as estimated at the last trace"),
    "recompute_ops_total": _m(
        "counter", ("program", "type"),
        "forward ops that run again in the backward, a trace, by op "
        "type"),
    "recompute_kept_total": _m(
        "counter", ("program", "type"),
        "replayed forward ops handed the outputs their first run kept "
        "(registry.OpDef.kept_in_replay: all of them, or kda_scan's chunk "
        "inverses, from which it runs the rest again), a lowering, by op "
        "type"),
    "recompute_kept_bytes": _m(
        "counter", ("program",),
        "bytes of the outputs kept across the forward for a replayed op, "
        "a lowering"),
    "optimizer_steps_total": _m("counter", ("program",),
                                "runs of optimizer-carrying programs"),
    "optimizer_minimize_total": _m("counter", ("optimizer",),
                                   "Optimizer.minimize calls"),
    "optimizer_global_norm": _m(
        "gauge", ("program",),
        "pre-clip gradient global norm (telemetry side-fetch)",
        dynamic=True),
    "moe_rows_routed": _m(
        "histogram", ("program", "layer"),
        "(token, slot) pairs routed to the experts held here, a sample a "
        "step and expert layer (telemetry side-fetch; models/nemotron_h)",
        dynamic=True),
    "moe_rows_combined": _m(
        "histogram", ("program", "layer"),
        "rows the held experts' grouped product was given and the "
        "scatter-add returned; equals moe_rows_routed unless a row is "
        "lost (telemetry side-fetch)",
        dynamic=True),
    "moe_load_max_over_mean": _m(
        "histogram", ("program", "layer"),
        "rows of the busiest held expert over the held experts' mean, a "
        "sample a step and expert layer (telemetry side-fetch)",
        dynamic=True),
    "moe_rows_handled": _m(
        "histogram", ("program", "layer"),
        "rows the expert layer gathered, multiplied and scattered: the "
        "smallest capacity of its ladder that holds moe_rows_routed (twice "
        "a uniform router's share, four times it, or all N x top_k: "
        "hybrid_ops._capacity_ladder; telemetry side-fetch)",
        dynamic=True),
    "loss": _m(
        "gauge", ("program",),
        "mean next-token cross-entropy, the last step's (telemetry "
        "side-fetch; models/window_moe)",
        dynamic=True),
    "loss_main": _m(
        "gauge", ("program",),
        "next-token cross-entropy of a model with multi-token-prediction "
        "modules, the last step's (telemetry side-fetch; models/mla_moe)",
        dynamic=True),
    "loss_mtp": _m(
        "gauge", ("program",),
        "the prediction modules' cross-entropy before its weight, the "
        "last step's (telemetry side-fetch; models/mla_moe)",
        dynamic=True),
    "loss_diffusion": _m(
        "gauge", ("program",),
        "position-weighted masked-diffusion cross-entropy of a model "
        "trained by diffusion over blocks, the last step's (telemetry "
        "side-fetch; models/block_diffusion_moe)",
        dynamic=True),
    "masked_share": _m(
        "gauge", ("program",),
        "share of the last step's data tokens the input pipeline masked "
        "(telemetry side-fetch; models/block_diffusion_moe)",
        dynamic=True),
    "loop_exit_loss": _m(
        "histogram", ("program", "exit"),
        "mean next-token cross-entropy of one exit of a looped decoder, a "
        "sample a step and exit (telemetry side-fetch; models/looped_lm)",
        dynamic=True),
    "loop_exit_mass": _m(
        "histogram", ("program", "exit"),
        "mean mass the exits' gate gives one exit of a looped decoder; "
        "the exits' sum to 1 (telemetry side-fetch; models/looped_lm)",
        dynamic=True),
    "hc_res_sum_error": _m(
        "gauge", ("program",),
        "largest |row sum - 1| or |column sum - 1| of any residual map "
        "H_res the last step's hyper-connections made, over tokens and "
        "sublayers (telemetry side-fetch; models/mla_moe with hc_mult)",
        dynamic=True),
    "hc_res_diagonal_mass": _m(
        "gauge", ("program",),
        "mean of trace(H_res) / n over the last step's tokens and "
        "sublayers: 1 keeps the residual streams apart, 1 / n mixes them "
        "evenly (telemetry side-fetch; models/mla_moe with hc_mult)",
        dynamic=True),
    "jax_backend_compiles_total": _m("counter", (),
                                     "XLA backend compiles observed"),
    "jax_backend_compile_seconds_total": _m(
        "counter", (), "XLA backend compile wall seconds"),
    "donation_fallback_total": _m("counter", ("program",),
                                  "buffer-donation fallbacks"),
    "oom_errors_total": _m("counter", ("program",),
                           "device OOMs classified by the executor"),
    "nonfinite_detections_total": _m(
        "counter", ("program", "source"),
        "non-finite values caught by checks/probes"),
    "feed_conversion_seconds": _m("histogram", (),
                                  "host feed conversion wall seconds"),
    "feed_conversion_seconds_total": _m(
        "counter", (), "cumulative host feed conversion seconds"),
    # fusion / lowering / kernels
    "fusion_fallback_total": _m("counter", ("program", "reason"),
                                "fusion pattern bail-outs"),
    "pallas_kernel_total": _m("counter", ("op",),
                              "pallas kernel launches"),
    "pallas_fallback_total": _m("counter", ("op", "reason"),
                                "pallas kernels that fell back to XLA"),
    "flash_backward_total": _m("counter", ("form", "reason"),
                               "flash attention backward lowerings, fused "
                               "(one kernel) or split by a shape ground"),
    "flash_edge_subtiles_total": _m(
        "counter", ("kernel", "mask", "grain", "state"),
        "blocks in which a masked flash call visits the tiles an edge of "
        "its mask crosses, by state: dead (not computed), open (no "
        "predicate) or held (under one); once a lowering"),
    "attention_window_total": _m("counter", ("window",),
                                 "forward attention lowerings under a "
                                 "sliding window, by its keys"),
    "attention_kv_groups_total": _m(
        "counter", ("op", "groups", "form", "ground"),
        "forward attention lowerings under grouped-query attention, by "
        "form: kernel (K/V read at their own head count) or repeated, "
        "with the ground"),
    "kda_scan_total": _m("counter", ("chunk", "path"),
                         "forward kda_scan lowerings, by the delta rule's "
                         "chunk length and the path taken (a replayed op's: "
                         "`kernel_given_inverse` where it was handed the "
                         "chunk inverses its first run kept, else "
                         "`kernel_replay` / `chunked_replay`)"),
    "kda_scan_head_decay_total": _m(
        "counter", ("path", "groups"),
        "of kda_scan_total's lowerings, those with a decay a head, by path "
        "and the value heads that read one key head"),
    "hyper_connection_sublayers_total": _m(
        "counter", ("program",),
        "sublayers behind hyper-connections (a sinkhorn_knopp op each), "
        "a trace"),
    "hyper_connection_replays_total": _m(
        "counter", ("program",),
        "sublayers behind hyper-connections whose forward the backward "
        "runs again (a replayed sinkhorn_knopp op each), a trace"),
    "activation_kept_total": _m("counter", ("act",),
                                "lowerings of an activation evaluated once "
                                "and kept (ops/math_ops.py KEPT_ACTS)"),
    "sibling_products_merged_total": _m(
        "counter", ("program", "direction"),
        "groups of sibling products of one activation traced as one "
        "contraction (ops/sibling_products.py)"),
    "tp_gather_pinned_total": _m(
        "counter", ("program", "side"),
        "products whose operand or output cotangent is constrained to "
        "cross the model axis once (tensor_parallel.gather_once)"),
    "quant_kernel_total": _m("counter", ("op",),
                             "ops routed through int8/fp8 quantization"),
    "quant_fallback_total": _m("counter", ("op", "reason"),
                               "quantizable ops kept at full precision"),
    "kernel_efficiency": _m("gauge", ("op", "shape"),
                            "measured/roofline kernel efficiency"),
    "device_op_seconds_total": _m("counter", ("op",),
                                  "per-op device seconds (profiled)"),
    # sparse / embedding
    "sparse_apply_rows_total": _m("counter", ("op",),
                                  "rows touched by sparse applies"),
    "sparse_densify_fallback_total": _m(
        "counter", ("op", "reason"), "sparse paths densified"),
    "emb_cache_hits_total": _m("counter", ("table",),
                               "embedding hot-row cache hits"),
    "emb_cache_misses_total": _m("counter", ("table",),
                                 "embedding hot-row cache misses"),
    "emb_cache_hit_rate": _m("gauge", ("table",),
                             "embedding cache rolling hit rate"),
    "emb_cache_evictions_total": _m("counter", ("policy",),
                                    "embedding cache evictions"),
    "emb_cache_flush_bytes_total": _m(
        "counter", (), "dirty embedding bytes flushed to host"),
    "emb_cache_prefetch_total": _m("counter", (),
                                   "embedding prefetch batches staged"),
    "emb_cache_prefetch_overlap_fraction": _m(
        "gauge", (), "prefetch time hidden under compute"),
    # memory
    "hbm_bytes_in_use": _m("gauge", ("device",),
                           "live HBM bytes (tracker)"),
    "hbm_peak_bytes": _m("gauge", ("device",), "peak HBM bytes"),
    "hbm_limit_bytes": _m("gauge", ("device",), "HBM capacity"),
    "hbm_class_bytes": _m("gauge", ("device", "kind"),
                          "HBM bytes by allocation class"),
    # input pipeline
    "input_batches_total": _m("counter", (), "reader batches produced"),
    "input_windows_total": _m("counter", (), "reader windows produced"),
    "input_window_dropped_batches_total": _m(
        "counter", (), "tail batches dropped at window close"),
    "input_stall_seconds": _m("histogram", (),
                              "executor wait on the input pipeline"),
    # checkpoint io
    "checkpoint_bytes": _m("gauge", ("op",),
                           "payload bytes of the last save/load"),
    "checkpoint_saves_total": _m("counter", (),
                                 "checkpoints written by this process"),
    "checkpoint_last_step": _m("gauge", (),
                               "step of the newest checkpoint"),
    "checkpoint_save_seconds": _m("histogram", (),
                                  "wall seconds per checkpoint save",
                                  dynamic=True),
    "checkpoint_load_seconds": _m("histogram", (),
                                  "wall seconds per checkpoint load",
                                  dynamic=True),
    # multihost / fleet
    "multihost_initialize_total": _m("counter", (),
                                     "distributed init calls"),
    "multihost_processes": _m("gauge", (), "process count at init"),
    "fleet_step_skew": _m("gauge", (), "max-min step skew across hosts"),
    "fleet_straggler_host": _m("gauge", (),
                               "host index of the slowest step"),
    "goodput_fraction": _m("gauge", (), "goodput fraction of wall time"),
    "goodput_seconds": _m("gauge", ("bucket",),
                          "wall seconds by goodput bucket"),
    "collective_time_seconds": _m("gauge", (),
                                  "total collective device seconds"),
    "collective_exposed_seconds": _m(
        "gauge", (), "collective seconds not hidden by compute"),
    # planner / parallel
    "planner_fallback_total": _m("counter", ("program", "reason"),
                                 "sharding planner bail-outs"),
    "planner_params": _m("gauge", ("program", "role", "factor"),
                         "parameters by planned role and shard factor"),
    "planner_shard_bytes": _m(
        "gauge", ("program", "role", "factor"),
        "bytes one chip holds of a planned role's parameters"),
    "overlap_buckets_total": _m("counter", ("program",),
                                "gradient overlap buckets built"),
    "overlap_fallback_total": _m("counter", ("program", "reason"),
                                 "overlap scheduling bail-outs"),
    # grad audit
    "grad_l2": _m("gauge", ("program", "param"), "per-param grad L2"),
    "grad_abs_mean": _m("gauge", ("program", "param"),
                        "per-param grad |mean|"),
    "grad_audit_flags_total": _m("counter",
                                 ("program", "param", "status"),
                                 "grad audit anomaly flags"),
    # profiler / roofline
    "profiler_sessions_total": _m("counter", ("traced",),
                                  "profiler sessions"),
    "profiler_event_seconds": _m("histogram", ("event",),
                                 "profiler event wall seconds"),
    "mfu_nominal": _m("gauge", (), "MFU vs nominal peak", dynamic=True),
    "mfu_vs_sustained": _m("gauge", (), "MFU vs sustained peak",
                           dynamic=True),
    "device_duty_cycle": _m("gauge", (), "device busy fraction",
                            dynamic=True),
    # inspector
    "inspector_crash_reports_total": _m(
        "counter", (), "crash reports written"),
    # serving
    "serving_request_seconds": _m("histogram", ("program", "phase"),
                                  "per-request latency by phase"),
    "serving_batches_total": _m("counter", ("program", "close"),
                                "batches closed, by close cause"),
    "serving_shed_total": _m("counter", ("program", "reason"),
                             "requests shed by overload control"),
    "serving_queue_depth": _m("gauge", ("program",),
                              "requests waiting in the batcher"),
    "serving_bucket_runs_total": _m("counter", ("program", "bucket"),
                                    "batches executed per bucket"),
    "serving_cache_hit_total": _m("counter", ("program", "bucket"),
                                  "AOT executable cache hits"),
    "serving_cache_miss_total": _m("counter", ("program", "bucket"),
                                   "AOT executable cache misses"),
    "serving_cache_evictions_total": _m(
        "counter", ("program",), "bucket executables LRU-evicted"),
    "serving_compile_seconds": _m("histogram", ("program", "bucket"),
                                  "AOT lower+compile seconds"),
    "serving_fallback_total": _m("counter", ("program", "reason"),
                                 "requests on the non-AOT path"),
    # observability plane (this PR)
    "slo_burn_rate": _m("gauge", ("model", "window"),
                        "error-budget burn rate by window"),
    "telemetry_quantile_tail_clamped_total": _m(
        "counter", ("name",),
        "quantiles clamped to the last finite bucket edge"),
    "trace_spans_dropped_total": _m(
        "counter", (), "spans evicted from the trace ring buffer"),
    "obs_requests_total": _m("counter", ("endpoint",),
                             "observability endpoint scrapes"),
    # run sentinel
    "sentinel_alerts_total": _m(
        "counter", ("rule", "severity"),
        "deduplicated sentinel alerts, by rule and severity"),
    "sentinel_hangs_total": _m("counter", (),
                               "hang-watchdog deadline expiries"),
    "train_loss": _m("gauge", ("program",),
                     "training loss observed by the run sentinel"),
    # training-dynamics observatory (dynamics.py)
    "dynamics_update_ratio": _m(
        "gauge", ("program", "series"),
        "per-series |dW|/(|W|+eps) from the fused on-device reduction"),
    "dynamics_grad_rms": _m("gauge", ("program", "series"),
                            "per-series gradient RMS"),
    "dynamics_weight_rms": _m("gauge", ("program", "series"),
                              "per-series parameter RMS"),
    "dynamics_dead_layers": _m(
        "gauge", ("program",), "series currently classified dead-layer"),
    "dynamics_frozen_params": _m(
        "gauge", ("program",), "series currently classified frozen-param"),
    "dynamics_unhealthy_series": _m(
        "gauge", ("program",), "series with any non-ok dynamics verdict"),
    "dynamics_samples_total": _m(
        "counter", ("program", "how"),
        "dynamics samples recorded, by how the row reached the host: "
        "found ready by a later step, or forced by a reader's drain"),
    "dynamics_update_norm_total": _m(
        "counter", ("program", "source"),
        "parameters of a traced step's dynamics table, a compile, by the "
        "update ratio's numerator: the rule's own step, or the difference "
        "of the parameter's values (an old copy kept behind the update)"),
}
