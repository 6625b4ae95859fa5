"""Memory observability: HBM accounting, peak attribution, OOM forensics.

The reference framework's Memory layer (buffer allocators + the
memory_optimize liveness pass, paddle/fluid/memory/*, transpiler's
memory_optimize) is subsumed by PJRT/XLA here by design — XLA's buffer
assignment decides every allocation. That leaves the framework blind to
the resource that actually bounds TPU training: HBM. This module is the
space-side sibling of telemetry.py (time) and inspector.py (numerics):

1. **Static analysis** — after a block's first jit compile the executor
   calls `on_compile`, which re-lowers the SAME jitted fn from avals
   (shapes only, donated buffers never kept alive) and captures
   `Compiled.memory_analysis()` — argument / output / temp / alias /
   generated-code bytes — into a `ProgramMemory` record,
   `memory_*_bytes` gauges and the step-event log. The executable's text
   is parsed once there (`xplane.hlo_instructions`): a scheduled-HLO
   liveness walk (`hlo_peak_liveness`) attributes the high-water mark to
   the top-k IR ops through the `pd.<type>` named-scope metadata, and the
   same list is the step's account by instruction that the executor
   keeps (`Executor.step_account`).
2. **Live accounting** — a `MemoryTracker` samples `device.memory_stats()`
   (TPU) or falls back to summing `jax.live_arrays()` (CPU backends
   return None) per Executor.run, classifies state into
   params / opt-state / feeds / activations by scope metadata, and feeds
   the `hbm_bytes_in_use` / `hbm_peak_bytes` gauges and the inspector
   flight-recorder ring.
3. **Headroom arithmetic** — `HeadroomModel` fits peak(b) = fixed +
   per_sample*b from static analyses at two or more batch sizes and
   gives the max batch under an HBM budget (`default_budget`); the
   overlap pass and the hot-row cache size themselves with it.
4. **OOM forensics** — `maybe_oom_error` turns a raw RESOURCE_EXHAUSTED
   (jax XlaRuntimeError) into a structured `errors.OOMError` carrying
   the breakdown, top live buffers, donation losses and concrete
   suggestions; the executor raises it through the inspector crash-report
   path. Surfaced by `python -m paddle_tpu memory` (cli.py).

Everything here must be advisory: analysis/tracking failures are caught
at the executor call sites and never fail a training step.
"""

from __future__ import annotations

import math
import re
import threading
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import flags
from . import telemetry

__all__ = [
    "ProgramMemory", "MemoryTracker", "HeadroomModel",
    "analyze", "hlo_peak_liveness", "shape_bytes", "nbytes_of",
    "classify", "tracker", "top_live_buffers", "live_array_bytes",
    "is_oom", "maybe_oom_error", "default_budget", "device_limit",
    "records", "latest_record", "reset", "memory_report",
    "crash_section", "build_smoke", "on_compile", "on_run",
    "per_shard_param_bytes",
]


def per_shard_param_bytes(program, scope=None):
    """Per-device parameter bytes under the program's mesh, with the
    per-axis breakdown (`by_axes`: "replicated" / "fsdp" / "fsdp+tp" /
    ...) the sharding planner's byte validation pins against
    (parallel.planner.validate_plan_bytes, <= 1% — a hard test failure
    on drift). Thin delegation to parallel.per_shard_param_bytes; lives
    here too because memory accounting callers reach for memory.py
    first."""
    from .parallel import per_shard_param_bytes as _impl
    return _impl(program, scope)

GiB = 1 << 30

flags.define("memory_analysis", True,
             "capture Compiled.memory_analysis() + an HLO peak-liveness "
             "walk after each block's first jit compile (memory.on_compile; "
             "live-read, 0 disables the extra AOT lower/compile)")
flags.define("memory_tracker", True,
             "sample device.memory_stats()/jax.live_arrays() per "
             "Executor.run into hbm_* gauges (memory.MemoryTracker; "
             "live-read)")
flags.define("hbm_budget_bytes", 0,
             "HBM budget for headroom estimates on backends whose "
             "memory_stats() reports no bytes_limit (0 = 16 GiB default)")


# ---------------------------------------------------------------------------
# Shape/byte helpers
# ---------------------------------------------------------------------------

def shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string — 'f32[128,13]{1,0}' -> 6656; tuple
    shapes '(f32[8], s32[])' sum their elements; unknown element types
    (token, opaque) count zero. The one table of element sizes is
    xplane's."""
    from . import xplane
    return xplane.shape_bytes(shape_str)


def nbytes_of(value) -> int:
    """Bytes of an array-like from shape/dtype metadata ONLY — never reads
    the data, so donated (deleted) jax arrays and ShapeDtypeStructs are
    safe to measure."""
    if value is None:
        return 0
    shape = getattr(value, "shape", None)
    dtype = getattr(value, "dtype", None)
    if shape is None or dtype is None:
        arr = getattr(value, "array", None)
        if callable(arr):          # LoDTensor
            return nbytes_of(arr())
        try:
            a = np.asarray(value)
        except Exception:
            return 0
        shape, dtype = a.shape, a.dtype
    try:
        n = 1
        for d in shape:
            n *= int(d)
        return n * np.dtype(dtype).itemsize
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# HLO peak-liveness walk
# ---------------------------------------------------------------------------

# ops that alias rather than allocate: their "output" is a view/pointer
_ZERO_COST_OPS = frozenset({"bitcast", "get-tuple-element", "tuple",
                            "bitcast-convert"})


def hlo_peak_liveness(hlo, top_k: int = 8) -> Optional[Dict]:
    """Walk the scheduled entry computation (Compiled.as_text() emits
    is_scheduled=true, so instruction order IS the schedule), assign each
    instruction's output buffer a [def, last-use] live range, and report
    the position and composition of the liveness high-water mark — an
    estimate of XLA buffer assignment, not a reimplementation (fusion
    internals and layout padding are invisible at this level). `hlo` is
    the module's text or its parse (`xplane.hlo_instructions`, the one
    parse: names, shapes and operands come from it). Each peak buffer is
    attributed back to the IR op whose pd.<type> named scope emitted it,
    so the answer reads 'conv2d output, not %fusion.42'."""
    from . import xplane

    instrs = xplane.hlo_instructions(hlo) if isinstance(hlo, str) else hlo
    instrs = [i for i in instrs if i.entry]
    n = len(instrs)
    if n == 0:
        return None
    names = [i.name for i in instrs]
    defpos = {nm: pos for pos, nm in enumerate(names)}
    sizes = {i.name: 0 if i.opcode in _ZERO_COST_OPS
             else xplane.shape_bytes(i.shape) for i in instrs}
    last_use = dict(defpos)
    for pos, i in enumerate(instrs):
        for u in i.operands:
            if u in defpos and u != i.name:
                last_use[u] = max(last_use[u], pos)
    # argument buffers exist for the whole execution (XLA cannot free a
    # caller-owned input) and the ROOT buffer is the output — pin to end
    for i in instrs:
        if i.opcode == "parameter":
            last_use[i.name] = n - 1
    last_use[names[-1]] = n - 1

    delta = [0] * (n + 1)
    for nm in names:
        b = sizes[nm]
        if not b:
            continue
        delta[defpos[nm]] += b
        delta[last_use[nm] + 1] -= b
    running = 0
    peak = 0
    peak_pos = 0
    for pos in range(n):
        running += delta[pos]
        if running > peak:
            peak, peak_pos = running, pos
    live = [nm for nm in names
            if sizes[nm] and defpos[nm] <= peak_pos <= last_use[nm]]
    live.sort(key=lambda nm: -sizes[nm])
    by_name = {i.name: i for i in instrs}
    top = [{"instruction": nm, "bytes": sizes[nm],
            "op": xplane.op_label(by_name[nm])}
           for nm in live[:top_k]]
    return {"peak_bytes": peak, "peak_pos": peak_pos,
            "n_instructions": n, "live_at_peak": len(live), "top": top}


# ---------------------------------------------------------------------------
# Static analysis records
# ---------------------------------------------------------------------------

class ProgramMemory:
    """One compiled block's static memory footprint
    (Compiled.memory_analysis() + the liveness walk + donation audit)."""

    __slots__ = ("program", "place", "signature", "argument_bytes",
                 "output_bytes", "temp_bytes", "alias_bytes",
                 "generated_code_bytes", "donated_bytes",
                 "donation_lost_bytes", "peak", "account", "module",
                 "xla_flops")

    def __init__(self, program="?", place="?", signature=None):
        self.program = program
        self.place = place
        self.signature = signature
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.alias_bytes = 0
        self.generated_code_bytes = 0
        self.donated_bytes = 0
        self.donation_lost_bytes = 0
        self.peak: Optional[Dict] = None
        # the step's account by instruction (xplane.hlo_instructions,
        # compacted), its HLO module name and XLA's own FLOP count: not
        # part of to_dict(), the executor keeps them on the compiled block
        self.account = None
        self.module = None
        self.xla_flops = None

    @property
    def total_bytes(self) -> int:
        """Static HBM high-water estimate: arguments + (non-aliased)
        outputs + XLA temporaries + executable code."""
        return (self.argument_bytes + self.output_bytes - self.alias_bytes
                + self.temp_bytes + self.generated_code_bytes)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "program": self.program, "place": self.place,
            "signature": ([list(s) for s in self.signature]
                          if self.signature else None),
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "donated_bytes": self.donated_bytes,
            "donation_lost_bytes": self.donation_lost_bytes,
            "total_bytes": self.total_bytes,
            "peak": self.peak,
        }


_LOCK = threading.Lock()
_RECORDS: Dict[str, ProgramMemory] = {}   # prog_label -> latest record
_MAX_RECORDS = 256
_DONATION_WARNED = False


def _remember(rec: ProgramMemory):
    with _LOCK:
        _RECORDS[rec.program] = rec
        while len(_RECORDS) > _MAX_RECORDS:
            _RECORDS.pop(next(iter(_RECORDS)))


def records() -> List[ProgramMemory]:
    with _LOCK:
        return list(_RECORDS.values())


def latest_record(prog_label: str) -> Optional[ProgramMemory]:
    with _LOCK:
        return _RECORDS.get(prog_label)


def aval_of(x):
    """Shape, dtype and — for an array committed to its device, or an
    aval that names one — the sharding of `x`, and nothing that keeps its
    buffer alive. The sharding makes an AOT lower() from avals the same
    computation, under the same persistent-cache key, as the jit call on
    the arrays was (an uncommitted array lowers unplaced there too)."""
    import jax
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        arr = np.asarray(x)
        shape, dtype = arr.shape, arr.dtype
    uncommitted = getattr(x, "committed", None) is False
    sharding = None if uncommitted else getattr(x, "sharding", None)
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _shard_nbytes(value) -> int:
    """Bytes one device holds of `value`: its shard under the array's
    (or aval's) sharding, all of it without one."""
    sharding = getattr(value, "sharding", None)
    shape = getattr(value, "shape", None)
    if sharding is None or not shape:
        return nbytes_of(value)
    return int(np.prod(sharding.shard_shape(tuple(shape)))) \
        * np.dtype(value.dtype).itemsize


def compile_from_avals(fn, avals):
    """AOT lower().compile() of a jitted block fn from avals (shapes,
    dtypes and shardings only: donated state buffers must never be kept
    alive by a capture). A real recompile unless the persistent
    compilation cache covers it."""
    with warnings.catch_warnings():
        # backends without donation support (CPU) warn per compile; the
        # executor's jit call already surfaced it once — the audit
        # reports the loss in bytes instead
        warnings.filterwarnings("ignore", message=".*donated buffers.*")
        return fn.lower(*avals).compile()


def account_of(compiled_exe, mesh=None):
    """(HLO module name, the step's account by instruction, XLA's FLOP
    count or None) of a compiled executable: the one parse of its text
    (xplane.hlo_instructions), compacted to what takes time on the
    device."""
    from . import xplane
    text = compiled_exe.as_text()
    instrs = xplane.hlo_instructions(text, mesh=mesh)
    return xplane.module_name(text), instrs, _xla_flops(compiled_exe)


def _xla_flops(compiled_exe) -> Optional[float]:
    try:
        ca = compiled_exe.cost_analysis()
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(d.get("flops", 0.0))
    except Exception:  # noqa: BLE001 - backend-dependent
        return None


def analyze(fn, feed_vals, state_vals, rng_counter=0, *, program="?",
            place="?", signature=None, top_k: int = 8,
            mesh=None) -> ProgramMemory:
    """AOT-lower the jitted block fn from avals and read XLA's
    CompiledMemoryStats, then parse the executable's text ONCE
    (xplane.hlo_instructions) for both the scheduled-HLO liveness walk
    and the step's account by instruction (`rec.account`, with the
    collectives' mesh axes when the planner's `mesh` is given)."""
    import jax
    from . import xplane

    avals = jax.tree_util.tree_map(
        aval_of, (feed_vals, state_vals, np.uint32(rng_counter)))
    compiled = compile_from_avals(fn, avals)
    stats = compiled.memory_analysis()

    rec = ProgramMemory(program=program, place=place, signature=signature)
    rec.argument_bytes = int(getattr(stats, "argument_size_in_bytes", 0))
    rec.output_bytes = int(getattr(stats, "output_size_in_bytes", 0))
    rec.temp_bytes = int(getattr(stats, "temp_size_in_bytes", 0))
    rec.alias_bytes = int(getattr(stats, "alias_size_in_bytes", 0))
    rec.generated_code_bytes = int(
        getattr(stats, "generated_code_size_in_bytes", 0))
    # per device, as XLA's alias_size is: a sharded array's shard
    rec.donated_bytes = sum(
        _shard_nbytes(v) for v in jax.tree_util.tree_leaves(state_vals))
    rec.donation_lost_bytes = max(rec.donated_bytes - rec.alias_bytes, 0)
    try:
        rec.module, instrs, rec.xla_flops = account_of(compiled, mesh)
        rec.peak = hlo_peak_liveness(instrs, top_k=top_k)
        rec.account = xplane.compact(instrs)
    except Exception:
        rec.peak = None
    _remember(rec)
    return rec


def _publish(rec: ProgramMemory):
    """Record -> memory_*_bytes gauges + one memory_analysis step event."""
    for field, value in (
            ("argument", rec.argument_bytes), ("output", rec.output_bytes),
            ("temp", rec.temp_bytes), ("alias", rec.alias_bytes),
            ("generated_code", rec.generated_code_bytes),
            ("donated", rec.donated_bytes),
            ("donation_lost", rec.donation_lost_bytes),
            ("total", rec.total_bytes)):
        telemetry.gauge(
            f"memory_{field}_bytes",
            f"static memory analysis: {field} bytes of the compiled block",
            labels=("program",)).labels(program=rec.program).set(value)
    telemetry.log_event(
        "memory_analysis", program=rec.program, place=rec.place,
        argument_bytes=rec.argument_bytes, output_bytes=rec.output_bytes,
        temp_bytes=rec.temp_bytes, alias_bytes=rec.alias_bytes,
        generated_code_bytes=rec.generated_code_bytes,
        donation_lost_bytes=rec.donation_lost_bytes,
        total_bytes=rec.total_bytes,
        peak_bytes=(rec.peak or {}).get("peak_bytes"))


def _audit_donation(rec: ProgramMemory):
    """Donation audit: donated state the backend did NOT alias in
    memory_analysis() means the optimizer update copies instead of
    reusing HBM in place — double the parameter footprint. Counted per
    program; warned once per process (CPU backends never alias, and a
    test suite full of small programs must not drown in warnings)."""
    global _DONATION_WARNED
    if not rec.donated_bytes or rec.donation_lost_bytes <= 0:
        return
    telemetry.counter(
        "donation_fallback_total",
        "compiles where donated buffers were not aliased in-place by XLA",
        labels=("program",)).labels(program=rec.program).inc()
    if not _DONATION_WARNED:
        _DONATION_WARNED = True
        warnings.warn(
            f"paddle_tpu memory: {_fmt_bytes(rec.donation_lost_bytes)} of "
            f"{_fmt_bytes(rec.donated_bytes)} donated state in program "
            f"'{rec.program}' was not aliased by XLA "
            f"(memory_analysis alias={_fmt_bytes(rec.alias_bytes)}); "
            f"updates will copy instead of reusing HBM in place. Expected "
            f"on CPU backends (no donation support); on TPU check for "
            f"dtype/sharding mismatches between a parameter and its "
            f"update. [warned once; see donation_fallback_total]",
            RuntimeWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Live accounting
# ---------------------------------------------------------------------------

def live_array_bytes(device=None) -> int:
    """Sum of jax.live_arrays() nbytes (optionally restricted to one
    device) — the CPU-backend fallback for device.memory_stats()."""
    import jax
    total = 0
    try:
        arrs = jax.live_arrays()
    except Exception:
        return 0
    for a in arrs:
        try:
            if device is not None and device not in a.devices():
                continue
            total += int(a.nbytes)
        except Exception:
            continue
    return total


def top_live_buffers(limit: int = 10,
                     names_by_id: Optional[Dict[int, str]] = None
                     ) -> List[Dict[str, Any]]:
    """Largest live device buffers, named when the caller can map array
    identity back to scope/feed variable names (OOM forensics)."""
    import jax
    try:
        arrs = jax.live_arrays()
    except Exception:
        return []
    rows = []
    for a in arrs:
        try:
            rows.append({"nbytes": int(a.nbytes),
                         "shape": [int(d) for d in a.shape],
                         "dtype": str(a.dtype),
                         "name": (names_by_id or {}).get(id(a))})
        except Exception:
            continue
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:limit]


_CLASS_CACHE: Dict[Tuple, Tuple] = {}


def classify(program, state_vals: Dict[str, Any],
             feed_vals: Dict[str, Any]) -> Dict[str, int]:
    """Split a run's inputs into params / opt_state / feeds bytes by scope
    metadata: parameters are the block's Parameter vars, every other
    persistable state (optimizer accumulators like `<param>_velocity_*`,
    LR vars, BN stats) is opt_state. Byte counts come from avals only, so
    donated arrays are safe to classify after the step ran.

    Bytes are PER-DEVICE: vars sharded under the program's mesh (row-
    sharded embedding tables and their accumulators, tensor/ZeRO-sharded
    params) divide by their shard factor
    (parallel/embedding.state_shard_factor), so the hbm_class_bytes
    breakdown and HeadroomModel inputs describe what one device actually
    holds — the number that OOMs."""
    key = (id(program), getattr(program, "_version", 0))
    hit = _CLASS_CACHE.get(key)
    if hit is None or hit[0] is not program:
        params = {p.name for p in program.global_block().all_parameters()}
        factors: Dict[str, int] = {}
        if getattr(program, "_mesh", None) is not None and (
                getattr(program, "_param_shardings", None)
                or getattr(program, "_sharded_tables", None)):
            from .parallel import embedding as embedding_mod
            for n in state_vals:
                f = embedding_mod.state_shard_factor(program, n)
                if f > 1:
                    factors[n] = f
        _CLASS_CACHE[key] = (program, params, factors)
        while len(_CLASS_CACHE) > 64:
            _CLASS_CACHE.pop(next(iter(_CLASS_CACHE)))
        hit = _CLASS_CACHE[key]
    params, factors = hit[1], hit[2]
    out = {"params": 0, "opt_state": 0, "feeds": 0}
    for n, v in state_vals.items():
        b = nbytes_of(v)
        f = factors.get(n, 1)
        if f > 1:
            b = -(-b // f)   # ceil: XLA pads uneven shards
        out["params" if n in params else "opt_state"] += b
    for v in feed_vals.values():
        out["feeds"] += nbytes_of(v)
    return out


class MemoryTracker:
    """Per-run HBM sampler. On TPU `device.memory_stats()` reports the
    allocator's truth (bytes_in_use / peak_bytes_in_use / bytes_limit);
    CPU backends return None and the tracker falls back to summing
    jax.live_arrays(). Feeds the hbm_* gauges and keeps a process-lifetime
    peak for OOM reports."""

    def __init__(self):
        self.peak_bytes = 0
        self.last: Dict[str, Any] = {}

    def sample(self, device=None, program: Optional[str] = None,
               classes: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
        stats = None
        if device is not None:
            try:
                stats = device.memory_stats()
            except Exception:
                stats = None
        if stats:
            in_use = int(stats.get("bytes_in_use", 0) or 0)
            limit = int(stats.get("bytes_limit", 0) or 0)
            dev_peak = int(stats.get("peak_bytes_in_use", in_use) or in_use)
            source = "device"
        else:
            in_use = live_array_bytes(device)
            limit = int(flags.get("hbm_budget_bytes") or 0)
            dev_peak = in_use
            source = "live_arrays"
        self.peak_bytes = max(self.peak_bytes, dev_peak, in_use)
        label = str(device) if device is not None else "?"
        telemetry.gauge(
            "hbm_bytes_in_use", "device memory in use after the last run "
            "(memory_stats, or live-array sum on backends without stats)",
            labels=("device",)).labels(device=label).set(in_use)
        telemetry.gauge(
            "hbm_peak_bytes", "high-water device memory across the process",
            labels=("device",)).labels(device=label).set(self.peak_bytes)
        if limit:
            telemetry.gauge(
                "hbm_limit_bytes", "device memory capacity (bytes_limit, "
                "or the hbm_budget_bytes flag)",
                labels=("device",)).labels(device=label).set(limit)
        cls = dict(classes or {})
        if classes is not None:
            cls["activations"] = max(in_use - sum(classes.values()), 0)
            for kind, v in cls.items():
                telemetry.gauge(
                    "hbm_class_bytes",
                    "live bytes by class: params/opt_state/feeds/activations",
                    labels=("device", "kind")).labels(
                        device=label, kind=kind).set(v)
        self.last = {"device": label, "source": source, "program": program,
                     "bytes_in_use": in_use, "peak_bytes": self.peak_bytes,
                     "limit_bytes": limit, "classes": cls}
        return self.last


_TRACKER = MemoryTracker()


def tracker() -> MemoryTracker:
    return _TRACKER


def reset():
    """Forget records and tracker state (test isolation)."""
    global _DONATION_WARNED
    with _LOCK:
        _RECORDS.clear()
    _CLASS_CACHE.clear()
    _TRACKER.peak_bytes = 0
    _TRACKER.last = {}
    _DONATION_WARNED = False


# ---------------------------------------------------------------------------
# Executor hooks
# ---------------------------------------------------------------------------

def on_compile(exe, compiled, program, prog_label, place_label,
               feed_vals, state_vals, rng_counter,
               signature=None) -> Optional[ProgramMemory]:
    """Executor hook after a block's first jit compile: static analysis +
    gauges + donation audit. Gated on the live memory_analysis flag."""
    if not flags.get("memory_analysis"):
        return None
    rec = analyze(compiled.fn, feed_vals, state_vals, rng_counter,
                  program=prog_label, place=place_label,
                  signature=signature, mesh=getattr(program, "_mesh", None))
    _publish(rec)
    _audit_donation(rec)
    return rec


def on_run(exe, program, prog_label, feed_vals,
           state_vals) -> Optional[Dict[str, Any]]:
    """Executor hook after every run: one tracker sample. Gated on the
    live memory_tracker flag."""
    if not flags.get("memory_tracker"):
        return None
    classes = None
    try:
        classes = classify(program, state_vals, feed_vals)
    except Exception:
        pass
    return _TRACKER.sample(device=getattr(exe, "device", None),
                           program=prog_label, classes=classes)


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_PAT = re.compile(r"RESOURCE_EXHAUSTED|[Oo]ut of memory|"
                      r"[Aa]llocation .* exceeds|OOM when allocating")


def is_oom(exc: BaseException) -> bool:
    """Does this look like the runtime ran out of device memory? jax
    surfaces XLA's RESOURCE_EXHAUSTED status as XlaRuntimeError with the
    status name in the message — string-matched here because the
    exception type itself is backend-private."""
    return bool(_OOM_PAT.search(str(exc)))


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}TiB"


def maybe_oom_error(exe, program, prog_label, exc, feed_vals=None,
                    state_vals=None):
    """If `exc` is a raw backend OOM, build the structured errors.OOMError
    that should replace it (carrying breakdown, top live buffers, donation
    losses and suggestions); otherwise None. Never raises: forensics that
    fail must not mask the original error."""
    from .errors import OOMError
    if isinstance(exc, OOMError) or not is_oom(exc):
        return None
    try:
        return _build_oom_error(exe, program, prog_label, exc,
                                feed_vals or {}, state_vals or {})
    except Exception:
        return None


def _build_oom_error(exe, program, prog_label, exc, feed_vals, state_vals):
    from .errors import OOMError
    telemetry.counter(
        "oom_errors_total", "device OOMs surfaced as errors.OOMError",
        labels=("program",)).labels(program=prog_label).inc()

    breakdown: Dict[str, Any] = {}
    try:
        breakdown.update(classify(program, state_vals, feed_vals))
    except Exception:
        pass
    device = getattr(exe, "device", None)
    stats = None
    if device is not None:
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
    if stats:
        breakdown["bytes_in_use"] = int(stats.get("bytes_in_use", 0) or 0)
        breakdown["bytes_limit"] = int(stats.get("bytes_limit", 0) or 0)
        breakdown["peak_bytes_in_use"] = int(
            stats.get("peak_bytes_in_use", 0) or 0)
    else:
        breakdown["bytes_in_use"] = live_array_bytes(device)

    rec = latest_record(prog_label)
    names_by_id = {}
    for n, v in list(state_vals.items()) + list(feed_vals.items()):
        try:
            names_by_id[id(v)] = n
        except Exception:
            pass
    top = top_live_buffers(10, names_by_id)

    suggestions: List[str] = []
    lost = rec.donation_lost_bytes if rec else 0
    if lost:
        suggestions.append(
            f"{_fmt_bytes(lost)} of donated state was not aliased by XLA "
            f"(donation fallback doubles the parameter footprint) — see "
            f"donation_fallback_total and the compile-time warning")
    if getattr(program, "_amp_dtype", None) is None:
        suggestions.append(
            "enable mixed precision (amp.decorate, level O2) to roughly "
            "halve parameter/activation bytes")
    if rec is not None and rec.temp_bytes > max(rec.argument_bytes, 1):
        suggestions.append(
            f"XLA temporaries dominate ({_fmt_bytes(rec.temp_bytes)} temp "
            f"vs {_fmt_bytes(rec.argument_bytes)} arguments) — "
            f"rematerialize activations or shard the model "
            f"(parallel.shard_all_params_zero)")
    suggestions.append(
        "reduce the batch size — `python -m paddle_tpu memory --batch B` "
        "reads the static footprint at batch B without running a step")

    lines = [f"out of device memory running program '{prog_label}'",
             f"  backend error: {str(exc).splitlines()[0][:300]}"]
    cls = {k: v for k, v in breakdown.items()
           if k in ("params", "opt_state", "feeds")}
    if cls:
        lines.append("  live breakdown: " + ", ".join(
            f"{k}={_fmt_bytes(v)}" for k, v in cls.items()))
    if rec is not None:
        lines.append(
            f"  static analysis: args={_fmt_bytes(rec.argument_bytes)} "
            f"out={_fmt_bytes(rec.output_bytes)} "
            f"temp={_fmt_bytes(rec.temp_bytes)} "
            f"total={_fmt_bytes(rec.total_bytes)}")
    for s in suggestions:
        lines.append(f"  suggestion: {s}")
    # keep the status name in the message so callers matching the raw
    # XlaRuntimeError text (retry loops) still do
    lines.append("  (RESOURCE_EXHAUSTED)")
    return OOMError("\n".join(lines), program=prog_label,
                    breakdown=breakdown, top_buffers=top,
                    donation_lost_bytes=lost,
                    analysis=rec.to_dict() if rec else None,
                    suggestions=suggestions,
                    device=str(device) if device is not None else None)


# ---------------------------------------------------------------------------
# Headroom estimation
# ---------------------------------------------------------------------------

class HeadroomModel:
    """peak(b) = fixed_bytes + per_item_bytes * b, least-squares fit from
    static analyses at >= 2 batch sizes. Linear in the batch because every
    per-sample buffer (feeds, activations, logits) scales with b while
    params/opt-state/code do not; XLA padding and fusion keep it only
    approximately linear, so a prediction far from the fitted points
    wants a fresh analysis at the predicted batch.

    For sharded programs both inputs are per-device numbers: the static
    analyses XLA returns for an SPMD module are post-partitioning, and
    classify() divides sharded state (row-sharded embedding tables and
    their optimizer accumulators included) by its shard factor — so
    fixed_bytes carries the per-shard table + opt-state footprint and
    max_batch() answers against one device's budget, the one that OOMs."""

    def __init__(self, fixed_bytes: float, per_item_bytes: float,
                 points: Optional[Sequence[Tuple[int, int]]] = None):
        self.fixed_bytes = float(fixed_bytes)
        self.per_item_bytes = float(per_item_bytes)
        self.points = [(int(b), int(y)) for b, y in (points or [])]

    @classmethod
    def fit(cls, points: Sequence[Tuple[int, int]]) -> "HeadroomModel":
        pts = sorted({(int(b), int(y)) for b, y in points})
        if len({b for b, _ in pts}) < 2:
            raise ValueError("HeadroomModel.fit needs analyses at >= 2 "
                             "distinct batch sizes")
        xs = [b for b, _ in pts]
        ys = [y for _, y in pts]
        mx = sum(xs) / len(xs)
        my = sum(ys) / len(ys)
        var = sum((x - mx) ** 2 for x in xs)
        slope = sum((x - mx) * (y - my) for x, y in pts) / var
        slope = max(slope, 0.0)
        fixed = max(my - slope * mx, 0.0)
        return cls(fixed, slope, pts)

    def predict(self, batch: int) -> int:
        return int(round(self.fixed_bytes + self.per_item_bytes * batch))

    def max_batch(self, budget_bytes: int) -> Optional[int]:
        """Largest batch fitting the budget; None when the footprint does
        not grow with the batch (nothing to bound)."""
        if self.per_item_bytes <= 0:
            return None
        if budget_bytes <= self.fixed_bytes:
            return 0
        return int((budget_bytes - self.fixed_bytes) // self.per_item_bytes)

    def headroom(self, budget_bytes: int, batch: int) -> int:
        """Device bytes left under `budget_bytes` after the predicted
        peak at `batch` — what's genuinely free for extra resident state.
        The beyond-HBM embedding cache sizes its hot-row slab from this
        (emb_cache.budget_from_headroom subtracts the window feed buffer
        on top). Clamped at 0: an over-budget batch has no headroom."""
        return max(0, int(budget_bytes) - self.predict(batch))

    def to_dict(self) -> Dict[str, Any]:
        return {"fixed_bytes": int(self.fixed_bytes),
                "per_item_bytes": round(self.per_item_bytes, 2),
                "points": self.points}


# what the runtime holds back of a chip's HBM: a v5e's allocator reports
# a bytes_limit of 16,909,336,064 B of its 16 GiB (my chip run, PR 67;
# 15.75 GiB, and the compiler refuses a step against the same figure:
# "16.21 of 15.75 GB", PERF.md section 4)
_RUNTIME_HOLDS = 16 * GiB - 16_909_336_064


def device_limit(device) -> Optional[int]:
    """The bytes a step may fill on `device`: the allocator's bytes_limit
    where the device reports one; for a device that is a description
    with no runtime behind it (tools/describe_step.py's v5e) the HBM on
    record for its kind less what the runtime holds back, so that a step
    described is the step the chip will run; None where the device
    reports no limit and none is on record (the CPU). What decides from
    it (recompute.plan) then decides as if nothing could be kept."""
    from . import chip
    try:
        stats = device.memory_stats()
    except Exception:   # not addressable: a described device
        stats = None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    row = chip.peaks(device)
    return row.hbm_bytes - _RUNTIME_HOLDS if row else None


def default_budget(device=None) -> int:
    """HBM budget for headroom estimates: the device's bytes_limit when
    memory_stats reports one, else the hbm_budget_bytes flag, else the
    HBM size on record for the device kind (chip.PEAKS; an unknown
    accelerator raises). The CPU reports nothing and has no row: it
    keeps a 16 GiB stand-in so headroom arithmetic can be rehearsed."""
    import jax
    from . import chip
    device = device if device is not None else jax.devices()[0]
    try:
        stats = device.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:
        pass
    v = int(flags.get("hbm_budget_bytes") or 0)
    if v > 0:
        return v
    row = chip.peaks(device)
    return row.hbm_bytes if row else 16 * GiB


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def memory_report() -> Dict[str, Any]:
    """One JSON-able view of everything this module knows (CLI summary)."""
    return {"programs": [r.to_dict() for r in records()],
            "tracker": dict(_TRACKER.last),
            "peak_bytes": _TRACKER.peak_bytes}


def crash_section() -> Dict[str, Any]:
    """The 'memory' section of an inspector crash report."""
    return {"tracker": dict(_TRACKER.last),
            "peak_bytes": _TRACKER.peak_bytes,
            "programs": [r.to_dict() for r in records()[-8:]],
            "live_buffers": top_live_buffers(5)}


# ---------------------------------------------------------------------------
# Smoke programs (memory CLI + tests)
# ---------------------------------------------------------------------------

def build_smoke(name: str) -> Dict[str, Any]:
    """Build one of the named smoke programs for memory measurements:
    'fit_a_line' (13->1 linear regression, SGD) or 'resnet' (CIFAR-shaped
    ResNet classifier, Momentum). Returns {main, startup, loss, feed_fn,
    data_fn, label}: feed_fn(b) yields aval-only feeds (ShapeDtypeStructs,
    safe at any batch — static analysis never materializes them),
    data_fn(b) yields real zero arrays for executed steps."""
    import jax
    import paddle_tpu as fluid
    from .framework import unique_name

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            if name == "fit_a_line":
                x = fluid.layers.data(name="x", shape=[13], dtype="float32")
                y = fluid.layers.data(name="y", shape=[1], dtype="float32")
                pred = fluid.layers.fc(input=x, size=1, act=None)
                cost = fluid.layers.square_error_cost(input=pred, label=y)
                loss = fluid.layers.mean(cost)
                fluid.optimizer.SGD(learning_rate=0.01).minimize(
                    loss, startup_program=startup)
                feeds = {"x": ((13,), np.float32), "y": ((1,), np.float32)}
            elif name == "resnet":
                from . import models
                img = fluid.layers.data(name="img", shape=[3, 32, 32],
                                        dtype="float32")
                label = fluid.layers.data(name="label", shape=[1],
                                          dtype="int64")
                loss, _, _ = models.build_image_classifier(
                    models.resnet_cifar10, img, label, class_dim=10,
                    depth=20)
                fluid.optimizer.Momentum(
                    learning_rate=0.001, momentum=0.9).minimize(
                        loss, startup_program=startup)
                feeds = {"img": ((3, 32, 32), np.float32),
                         "label": ((1,), np.int64)}
            else:
                raise ValueError(f"unknown smoke program '{name}' "
                                 f"(known: fit_a_line, resnet)")

    def feed_fn(batch: int):
        return {n: jax.ShapeDtypeStruct((batch,) + shape, dtype)
                for n, (shape, dtype) in feeds.items()}

    def data_fn(batch: int):
        return {n: np.zeros((batch,) + shape, dtype)
                for n, (shape, dtype) in feeds.items()}

    return {"main": main, "startup": startup, "loss": loss,
            "feed_fn": feed_fn, "data_fn": data_fn, "label": name}
