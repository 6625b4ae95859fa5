"""Causal span tracing for serving requests and training steps.

The metrics registry (telemetry.py) answers "how is the fleet doing in
aggregate"; this module answers "where did *this* request's 40 ms go".
Spans carry a trace id / span id / parent id, the step's id, monotonic
timestamps, attrs, and point events.

Design points, in the order they matter:

  * **One helper, two sinks.** `span(name)` brackets live code. Under a
    profiler session (`jax.profiler.start_trace`, `profiler.profiler()`
    with a trace directory, the benchmark's traced steps) it is a
    `jax.profiler.TraceAnnotation("pd.<name>")`: the span sits on
    `/host:CPU` of the xplane file, on the device trace's clock, beside
    what the device ran. When `enabled()` it also lands, finished, in a
    bounded process-wide ring that `/spans` (obs_server.py) and the
    exporters read. With neither it is a shared no-op: no clock read, no
    allocation.
  * **Phases tile their span.** `phase(name)` ends the previous phase of
    the innermost live span on this thread and starts the next at the
    same instant; the span's exit ends the last one. The executor's
    `step` is `prepare`, `launch`, `bookkeep`, `writeback`.
  * **A step's spans share its id.** `span(name, step=n)` records the
    executor's `__rng_counter__`; children inherit it.
  * **Off by default.** Enable the ring programmatically with `enable()`
    or via `PADDLE_TPU_TRACE` (``1`` for everything, a float like
    ``0.1`` for head sampling).
  * **Head sampling at the root.** The keep/drop decision is made once,
    when a root span starts, and inherited by every child — a trace is
    either complete or absent, never a partial tree. The sampler is a
    deterministic error-feedback accumulator (no RNG), so a 0.25 rate
    keeps exactly every 4th trace.
  * **Spans from other clocks.** `start_span()` hands out a handle
    carried across threads; `record_span()` creates a span from
    timestamps the caller measured anyway (the batcher's phases,
    checkpoint io, jax's own compile events). Ring only.
  * **Exports.** `export_chrome_trace()` writes Perfetto-loadable
    ``{"traceEvents": [...]}`` JSON of the ring (complete "X" events,
    µs); `export_jsonl()` one JSON object per line.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from . import telemetry

_DEFAULT_CAPACITY = 4096

_LOCK = threading.Lock()
_SPANS: "list" = []          # ring of finished span dicts (bounded)
_CAPACITY = _DEFAULT_CAPACITY
_ENABLED = False
_SAMPLE = 1.0
_SAMPLE_ACC = 0.0            # error-feedback accumulator for head sampling
_IDS = itertools.count(1)
# .stack — list of live sampled Span objects; .ctx — innermost live span()
_LOCAL = threading.local()
_Annotation = jax.profiler.TraceAnnotation
_ANNOTATION_PREFIX = "pd."

# offset from time.monotonic() to wall-clock, so spans recorded from
# monotonic timestamps can still report a wall "ts"
_WALL_OFFSET = time.time() - time.monotonic()


class Span:
    """One live span. End it with `.end()` (or let the `span()` context
    manager do it); only ended spans reach the ring buffer."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start",
                 "end_t", "attrs", "events", "sampled", "step")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], start: float, sampled: bool,
                 attrs: Optional[Dict[str, Any]] = None,
                 step: Optional[int] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.step = step
        self.start = start
        self.end_t: Optional[float] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.events: List[Dict[str, Any]] = []
        self.sampled = sampled

    def set_attr(self, key: str, value: Any) -> "Span":
        if self.sampled:
            self.attrs[key] = value
        return self

    def add_event(self, name: str, **attrs) -> "Span":
        if self.sampled:
            ev = {"name": name, "t": time.monotonic()}
            if attrs:
                ev.update(attrs)
            self.events.append(ev)
        return self

    def end(self, end: Optional[float] = None, **attrs):
        if self.end_t is not None:  # idempotent: first end wins
            return
        self.end_t = time.monotonic() if end is None else end
        if attrs and self.sampled:
            self.attrs.update(attrs)
        _finish(self)

    def to_dict(self) -> Dict[str, Any]:
        end = self.end_t if self.end_t is not None else time.monotonic()
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "step": self.step,
            "start": self.start,
            "end": end,
            "dur_s": max(end - self.start, 0.0),
            "ts": self.start + _WALL_OFFSET,
            "attrs": self.attrs,
            "events": self.events,
        }


class _NullSpan:
    """Returned when tracing is off or the trace was head-sampled out.
    Accepts the whole Span surface and does nothing; `sampled` stays
    False so children created under it stay null too."""

    __slots__ = ()
    sampled = False
    trace_id = span_id = parent_id = step = None
    name = ""

    def set_attr(self, key, value):
        return self

    def add_event(self, name, **attrs):
        return self

    def end(self, end=None, **attrs):
        pass

    def to_dict(self):
        return {}


_NULL = _NullSpan()


def _next_id() -> str:
    return f"{os.getpid():x}.{next(_IDS):x}"


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _sample_root() -> bool:
    """Deterministic head sampling: keep when the accumulated rate
    crosses 1.0 (an error-feedback quantizer — exact long-run rate,
    no RNG so traces are reproducible)."""
    global _SAMPLE_ACC
    with _LOCK:
        s = _SAMPLE
        if s >= 1.0:
            return True
        if s <= 0.0:
            return False
        _SAMPLE_ACC += s
        if _SAMPLE_ACC >= 1.0:
            _SAMPLE_ACC -= 1.0
            return True
    return False


def _finish(sp: Span):
    if not sp.sampled:
        return
    d = sp.to_dict()
    with _LOCK:
        _SPANS.append(d)
        dropped = len(_SPANS) - _CAPACITY
        if dropped > 0:
            del _SPANS[:dropped]
            telemetry.counter(
                "trace_spans_dropped_total",
                "finished spans evicted from the bounded ring buffer").inc(
                    dropped)


# --- lifecycle ---------------------------------------------------------------

def enable(sample: float = 1.0, capacity: Optional[int] = None):
    """Turn the ring on. `sample` in (0, 1] head-samples root spans;
    `capacity` bounds the finished-span ring."""
    global _ENABLED, _SAMPLE, _CAPACITY
    with _LOCK:
        _SAMPLE = min(max(float(sample), 0.0), 1.0)
        if capacity is not None:
            _CAPACITY = max(int(capacity), 1)
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def active() -> bool:
    """Whether a span would be written anywhere: the ring is on, or a
    profiler session is collecting annotations."""
    return _ENABLED or _Annotation.is_enabled()


def reset():
    """Drop all recorded spans and restore defaults (tests)."""
    global _SPANS, _ENABLED, _SAMPLE, _SAMPLE_ACC, _CAPACITY
    with _LOCK:
        _SPANS = []
        _SAMPLE_ACC = 0.0
        _SAMPLE = 1.0
        _CAPACITY = _DEFAULT_CAPACITY
    _ENABLED = False
    _LOCAL.stack = []
    _LOCAL.ctx = None


def maybe_enable_from_env():
    """Honor PADDLE_TPU_TRACE: '1'/'true'/'on' → full tracing, a float
    like '0.1' → head sampling at that rate, '0' → leave off."""
    raw = os.environ.get("PADDLE_TPU_TRACE", "").strip().lower()
    if not raw:
        return
    sample = None
    if raw in ("1", "true", "on", "yes"):
        sample = 1.0
    elif raw in ("0", "false", "off", "no"):
        return
    else:
        try:
            sample = float(raw)
        except ValueError:
            return
    if sample and sample > 0.0:
        enable(sample=sample)


# --- span creation -----------------------------------------------------------

def current_span():
    """The innermost live span on this thread's context stack (or a null
    span). Lets leaf code attach attrs/events without plumbing handles."""
    st = _stack()
    return st[-1] if st else _NULL


def owner_span():
    """The ring span of this thread's innermost live `span()`, looking
    past its phases (or a null span): where code inside a phase puts
    what it learned about the whole."""
    ctx = getattr(_LOCAL, "ctx", None)
    while ctx is not None and ctx.is_phase:
        ctx = ctx.outer
    return ctx.sp if ctx is not None else _NULL


def start_span(name: str, parent=None, attrs: Optional[Dict] = None,
               step: Optional[int] = None, start: Optional[float] = None):
    """Start a ring span without touching the context stack — for handles
    carried across threads (e.g. a serving request whose children are
    recorded by the batcher worker). Caller must `.end()` it. `step`
    defaults to the parent's."""
    if not _ENABLED:
        return _NULL
    if parent is None or isinstance(parent, _NullSpan):
        if parent is None:
            st = _stack()
            parent = st[-1] if st else None
    if parent is not None and not parent.sampled:
        return _NULL
    if parent is None:
        if not _sample_root():
            return _NULL
        trace_id = _next_id()
        parent_id = None
    else:
        trace_id = parent.trace_id
        parent_id = parent.span_id
        if step is None:
            step = parent.step
    return Span(name, trace_id, _next_id(), parent_id,
                time.monotonic() if start is None else start, True, attrs,
                step)


class _SpanCtx:
    """One live `span()` or `phase()` on this thread: the profiler
    annotation while a session runs, the ring span while enabled."""

    __slots__ = ("name", "attrs", "step", "is_phase", "sp", "ann", "outer")

    def __init__(self, name, attrs, step, is_phase=False):
        self.name = name
        self.attrs = attrs
        self.step = step
        self.is_phase = is_phase
        self.sp = _NULL
        self.ann = None
        self.outer = None

    def open(self, start=None):
        self.outer = getattr(_LOCAL, "ctx", None)
        _LOCAL.ctx = self
        if _Annotation.is_enabled():
            # the annotation starts when it is made and ends at __exit__
            name = _ANNOTATION_PREFIX + self.name
            self.ann = (_Annotation(name) if self.step is None
                        else _Annotation(name, step=self.step))
        # a span inside one that was sampled out (or that opened while the
        # ring was off) is dropped with it: a trace is whole or absent
        if _ENABLED and (self.outer is None or self.outer.sp.sampled):
            self.sp = start_span(self.name, attrs=self.attrs,
                                 step=self.step, start=start)
            if self.sp.sampled:
                _stack().append(self.sp)
        return self.sp

    def close(self, exc_type=None, exc=None, end=None):
        # what is still open inside goes first: the last phase, and
        # whatever an exception left behind
        inner = getattr(_LOCAL, "ctx", None)
        chain = []
        while inner is not None and inner is not self:
            chain.append(inner)
            inner = inner.outer
        if inner is self:
            for ctx in chain:
                ctx._end(exc_type, exc, end)
            _LOCAL.ctx = self.outer
        self._end(exc_type, exc, end)

    def _end(self, exc_type, exc, end):
        sp = self.sp
        if sp.sampled:
            st = _stack()
            if st and st[-1] is sp:
                st.pop()
            elif sp in st:
                st.remove(sp)
            if exc_type is not None:
                sp.set_attr("error", f"{exc_type.__name__}: {exc}")
            sp.end(end)
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None

    def __enter__(self):
        return self.open()

    def __exit__(self, exc_type, exc, tb):
        self.close(exc_type, exc)
        return False


class _OffCtx:
    """What `span()` hands out while nothing listens."""

    __slots__ = ()

    def __enter__(self):
        return _NULL

    def __exit__(self, exc_type, exc, tb):
        return False


_OFF = _OffCtx()


def span(name: str, step: Optional[int] = None, **attrs):
    """Context manager: a span over the enclosed code, child of this
    thread's innermost live span; yields the ring's Span (a null span
    unless `enabled()` and sampled). `step` is the id the spans of one
    executor step share (children inherit it). Keep `name` static: it
    is the key the readers join on."""
    if not (_ENABLED or _Annotation.is_enabled()):
        return _OFF
    return _SpanCtx(name, attrs or None, step)


def phase(name: str):
    """End the previous phase of this thread's innermost live `span()`
    and start phase `name` at the same instant, as its child; the span's
    exit ends the last phase. Spans opened inside a phase are its
    children. Outside any live span, and while nothing listens, this
    does nothing."""
    ctx = getattr(_LOCAL, "ctx", None)
    if ctx is None:
        return
    now = time.monotonic() if _ENABLED else None
    if ctx.is_phase:
        ctx.close(end=now)
    if _ENABLED or _Annotation.is_enabled():
        _SpanCtx(name, None, None, is_phase=True).open(start=now)


def capture_context():
    """Snapshot this thread's innermost live span as a handle a worker
    thread can `adopt()` — how DoubleBufferedFeeder's builder threads
    parent their prefetch spans under the owning step trace instead of
    minting orphan roots. None (and adopt(None) is a no-op) when nothing
    is live."""
    st = _stack()
    return st[-1] if st else None


class _AdoptCtx:
    __slots__ = ("ctx", "pushed")

    def __init__(self, ctx):
        self.ctx = ctx
        self.pushed = False

    def __enter__(self):
        if self.ctx is not None and getattr(self.ctx, "sampled", False):
            _stack().append(self.ctx)
            self.pushed = True
        return self.ctx

    def __exit__(self, exc_type, exc, tb):
        if self.pushed:
            st = _stack()
            if st and st[-1] is self.ctx:
                st.pop()
            elif self.ctx in st:
                st.remove(self.ctx)
        return False


def adopt(ctx):
    """Context manager: make a `capture_context()` handle (taken on
    another thread) this thread's current span, so `span()`/`start_span`
    children recorded here join the owning trace. The adopted span is
    NOT ended on exit — its owner ends it."""
    return _AdoptCtx(ctx)


def record_span(name: str, start: float, end: float, parent=None,
                trace_id: Optional[str] = None,
                attrs: Optional[Dict] = None):
    """Create an already-finished ring span from monotonic timestamps
    the caller measured anyway (batcher phases, checkpoint io, jax's
    compile events). Returns the span (its span_id can parent further
    such spans)."""
    if not _ENABLED:
        return _NULL
    step = None
    if parent is not None:
        if not parent.sampled:
            return _NULL
        tid, pid, step = parent.trace_id, parent.span_id, parent.step
    elif trace_id is not None:
        tid, pid = trace_id, None
    else:
        if not _sample_root():
            return _NULL
        tid, pid = _next_id(), None
    sp = Span(name, tid, _next_id(), pid, float(start), True, attrs, step)
    sp.end(end=float(end))
    return sp


# --- read / export -----------------------------------------------------------

def recent_spans(n: Optional[int] = None, name: Optional[str] = None,
                 trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Finished spans, oldest first, optionally filtered by span name or
    trace id, optionally the last `n` after filtering."""
    with _LOCK:
        out = list(_SPANS)
    if name is not None:
        out = [s for s in out if s["name"] == name]
    if trace_id is not None:
        out = [s for s in out if s["trace_id"] == trace_id]
    if n is not None:
        out = out[-int(n):]
    return out


def trace_tree(trace_id: str) -> List[Dict[str, Any]]:
    """The spans of one trace as a forest: roots with nested
    "children" lists, children sorted by start time."""
    spans = recent_spans(trace_id=trace_id)
    by_id = {s["span_id"]: dict(s, children=[]) for s in spans}
    roots = []
    for s in by_id.values():
        parent = by_id.get(s["parent_id"]) if s["parent_id"] else None
        (parent["children"] if parent else roots).append(s)
    for s in by_id.values():
        s["children"].sort(key=lambda c: c["start"])
    roots.sort(key=lambda c: c["start"])
    return roots


def export_chrome_trace(path: str,
                        spans: Optional[List[Dict]] = None) -> int:
    """Write spans as chrome-trace / Perfetto JSON (`traceEvents` with
    complete "X" events, microsecond timestamps). Returns the number of
    events written. Load in chrome://tracing or ui.perfetto.dev."""
    spans = recent_spans() if spans is None else spans
    pid = os.getpid()
    # one display row per trace: tid = trace ordinal, labelled via
    # thread_name metadata so request trees stack instead of interleaving
    tids: Dict[str, int] = {}
    events = []
    for s in spans:
        tid = tids.setdefault(s["trace_id"], len(tids) + 1)
        args = {"span_id": s["span_id"], "trace_id": s["trace_id"]}
        if s.get("step") is not None:
            args["step"] = s["step"]
        args.update(s.get("attrs") or {})
        events.append({
            "name": s["name"], "ph": "X", "pid": pid, "tid": tid,
            "ts": s["start"] * 1e6,
            "dur": max(s["end"] - s["start"], 0.0) * 1e6,
            "cat": "paddle_tpu", "args": args,
        })
    for trace_id, tid in tids.items():
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid,
                       "args": {"name": f"trace {trace_id}"}})
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return len(events)


def export_jsonl(path: str, spans: Optional[List[Dict]] = None) -> int:
    """Write spans (default: the whole ring) as JSONL; returns count."""
    spans = recent_spans() if spans is None else spans
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    return len(spans)


maybe_enable_from_env()
