"""AOT inference serving engine (reference: the fluid inference library —
paddle/fluid/inference/io.cc pruned-ProgramDesc loading + the capi
GradientMachine serving surface; here re-imagined TPU-natively).

`ServingEngine` owns one pruned inference program end to end:

  * **Admission**: the program is pruned to the inference fetch set
    (`Program.prune` drops the training tail, including in-place optimizer
    updates), cloned `for_test`, and gated through the static analyzer —
    an error-severity diagnostic or a leaked training-only op refuses to
    serve rather than compile a broken artifact.
  * **AOT program cache**: one XLA executable per padded batch-size bucket
    (powers-of-two ladder by default), produced by `jit(fn).lower(avals)
    .compile()` — the same AOT pattern the executor's static memory
    analysis uses — and LRU-evicted under `cache_capacity`. Compiles are
    booked in `serving_compile_seconds`; lookups in
    `serving_cache_{hit,miss}_total{bucket=}`.
  * **Resident state**: persistable weights are device-put once at engine
    construction and thereafter round-trip through the executable's
    donated state argument (donation only off-CPU, matching
    Executor._jit_compile's contract) — serving never re-uploads weights.
    On a meshed program (fsdp-sharded DLRM tables) the first call shards
    host state per the program's in_shardings and the sharded device
    arrays become the residents.

Requests with LoD inputs (sequence models through the C-API) fall back to
the classic Executor.run path on the same pruned program — counted in
`serving_fallback_total{reason=}`, never silently.

`ServingEngine(..., quantize="int8")` serves the quantized program
(quant.py): weights are pre-quantized once at admission and baked into
the bucket executables as constants; activations get dynamic per-call
scales in-trace. Ineligible ops/weights fall back per
`quant_fallback_total{op,reason}` and serve at full precision.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..errors import ProgramVerifyError

DEFAULT_MAX_BATCH = 64

#: Op roles that must never appear in a served program. Op types are
#: checked structurally too (tools/check_registry.check_serving): a
#: `*_grad` suffix or an optimizer bucket type is training-only even when
#: an op_role attribute was lost along the way.
TRAINING_ONLY_ROLES = ("backward", "optimize")


def training_only_op_types() -> frozenset:
    """Op types that only make sense while training: every optimizer the
    fusion pass knows how to bucket, their fused/sparse twins, and the
    grad-accumulation helpers. Gradient ops are matched by their `_grad`
    suffix via `is_training_only_op` instead of enumeration."""
    from ..ops import fusion
    out = set(fusion.OPTIMIZER_BUCKET_OPS)
    out.update(t for t in fusion.FUSED_OP_TYPES
               if "sparse" in t or any(o in t for o in
                                       fusion.OPTIMIZER_BUCKET_OPS))
    return frozenset(out)


def is_training_only_op(op_type: str, op_role: Optional[str]) -> bool:
    return (op_role in TRAINING_ONLY_ROLES
            or op_type.endswith("_grad")
            or op_type in training_only_op_types())


def bucket_ladder(max_batch: int = DEFAULT_MAX_BATCH) -> Tuple[int, ...]:
    """Powers-of-two padded batch sizes up to and including max_batch."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    """Pad axis 0 to `rows` by repeating the last row: edge padding keeps
    values in-distribution (no NaN from log(0)-style ops on zero rows) and
    the mask is implicit — only the first n result rows are returned."""
    n = arr.shape[0]
    if n == rows:
        return arr
    pad = np.repeat(arr[-1:], rows - n, axis=0)
    return np.concatenate([arr, pad], axis=0)


class ServingEngine:
    """AOT-compiled serving for one inference program.

    `model` is either a `save_inference_model` directory (loaded into a
    private scope) or an in-memory Program (pruned here; weights read from
    `scope`/the global scope). `infer(feed)` is the synchronous
    single-caller surface; `run_batch` is the batcher's hot path.
    """

    def __init__(self, model, feed_names: Optional[Sequence[str]] = None,
                 fetch_names: Optional[Sequence[str]] = None, place=None,
                 scope=None, max_batch: int = DEFAULT_MAX_BATCH,
                 buckets: Optional[Sequence[int]] = None,
                 cache_capacity: Optional[int] = None,
                 emb_cache_budget_bytes: Optional[int] = None,
                 emb_cache_tables: Optional[Dict[str, int]] = None,
                 quantize: Optional[str] = None):
        from .. import io as io_mod
        from ..executor import (Executor, Scope, TPUPlace, scope_guard,
                                global_scope)

        self.place = place if place is not None else TPUPlace(0)
        self._exe = Executor(self.place)
        self.device = self._exe.device
        self._lock = threading.RLock()
        self._closed = False

        if isinstance(model, str):
            self._scope = Scope()
            with scope_guard(self._scope):
                program, loaded_feeds, fetch_targets = \
                    io_mod.load_inference_model(model, self._exe)
            feed_names = list(feed_names or loaded_feeds)
            fetch_names = list(fetch_names
                               or [v.name for v in fetch_targets])
        else:
            program = model
            if not feed_names or not fetch_names:
                raise ValueError(
                    "ServingEngine(program) needs explicit feed_names and "
                    "fetch_names (a model_dir carries them in __model__)")
            feed_names = list(feed_names)
            fetch_names = list(fetch_names)
            program = io_mod._strip_training_ops(program) \
                .prune(feed_names, fetch_names).clone(for_test=True)
            self._scope = scope if scope is not None else global_scope()

        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.program = program
        self._label = telemetry.program_label(program)

        # Quantized serving (quant.py): mark the pruned program O3 so the
        # serving trace routes eligible matmul/conv compute through int8
        # (or fp8), then pre-quantize persistable weights ONCE here,
        # host-side — the (q, scale) pairs bake into every bucket
        # executable as constants, so per-call cost is only the dynamic
        # activation scales inside the traced program. Ineligible weights
        # are counted in quant_fallback_total and served unquantized.
        self.quantize = quantize
        self.quant_report: Optional[Dict[str, object]] = None
        if quantize is not None:
            from .. import quant as quant_mod
            if quantize not in ("int8", "fp8"):
                raise ValueError(
                    f"quantize must be 'int8' or 'fp8', got {quantize!r}")
            program._amp_dtype = "bfloat16"
            program._amp_level = "O3"
            program._quant_mode = quantize
            self.quant_report = quant_mod.prequantize(
                program, self._scope, quantize)
            telemetry.log_event(
                "serving_prequantize", program=self._label, mode=quantize,
                quantized=len(self.quant_report["quantized"]),
                skipped=len(self.quant_report["skipped"]))

        self._admit(program, feed_names, fetch_names)

        # ladder + cache geometry
        if buckets is not None:
            self.buckets = tuple(sorted(set(int(b) for b in buckets)))
            if not self.buckets or self.buckets[0] < 1:
                raise ValueError(f"bad bucket ladder {buckets}")
        else:
            self.buckets = bucket_ladder(max_batch)
        self.max_batch = self.buckets[-1]
        self.cache_capacity = (int(cache_capacity) if cache_capacity
                               else len(self.buckets))

        # feed geometry from the program desc: leading dim must be the
        # batch (-1) for the bucket ladder to apply
        block = program.global_block()
        self._feed_meta: Dict[str, Tuple[Tuple[int, ...], np.dtype]] = {}
        for n in feed_names:
            v = block.desc.var(n)
            shape = tuple(int(d) for d in v.shape)
            if not shape or shape[0] != -1:
                raise ValueError(
                    f"feed '{n}' has static shape {shape}; serving buckets "
                    f"pad the leading batch dim, which must be -1")
            self._feed_meta[n] = (shape, np.dtype(str(v.dtype)))

        # compile the shared step fn once; per-bucket AOT executables are
        # lowered from it on demand
        self._compiled, self._state_names, self._persist_out = \
            self._exe.prepare_serving(program, feed_names, fetch_names,
                                      self._scope)

        # device-resident weights: committed to the serving device when
        # unmeshed; on a meshed program the first call distributes host
        # arrays per in_shardings and the sharded results become resident
        import jax
        self._state: Dict[str, object] = {}
        mesh = getattr(program, "_mesh", None)
        for n in self._state_names:
            v = self._scope.find_var(n)
            arr = np.asarray(v.array() if hasattr(v, "array") else v)
            self._state[n] = arr if mesh is not None \
                else jax.device_put(arr, self.device)

        # beyond-HBM tables (read-only hot-row cache, ISSUE 14): swap the
        # resident full table for a [cache_rows, dim] slab backed by a
        # host-DRAM authoritative copy; per-request ids remap to cache
        # slots under the engine lock in run_batch. Inference never
        # writes rows, so eviction never flushes. Must run before the
        # first bucket executable is lowered — the state avals change.
        self._emb_cache = None
        if emb_cache_budget_bytes is not None or emb_cache_tables:
            from ..parallel import emb_cache as emb_cache_mod
            self._emb_cache = emb_cache_mod.enable_serving(
                self, budget_bytes=emb_cache_budget_bytes,
                tables=emb_cache_tables)

        self._executables: "collections.OrderedDict[int, object]" = \
            collections.OrderedDict()
        # python-side mirrors of the telemetry counters (tests + stats())
        self.cache_hits = 0
        self.cache_misses = 0
        self.evictions = 0
        self.bucket_runs: Dict[int, int] = {}

    # --- admission ----------------------------------------------------------
    def _admit(self, program, feed_names, fetch_names):
        """PR 12 analyzer as an admission gate + training-op leak check."""
        leaked = [
            f"op[{i}] {op.type} (role={op.desc.attrs.get('op_role')})"
            for i, op in enumerate(program.global_block().ops)
            if is_training_only_op(op.type,
                                   op.desc.attrs.get("op_role"))]
        if leaked:
            raise ValueError(
                f"refusing to serve: training-only ops survived pruning: "
                f"{leaked} — the inference fetch set likely includes a "
                f"gradient or optimizer output")
        # a gradient fetch doesn't leak ops — its producer was stripped,
        # leaving the fetch uncomputable; refuse at admission instead of
        # failing obscurely at the first bucket compile
        block = program.global_block()
        produced = {n for op in block.ops for n in op.output_arg_names}
        for n in fetch_names:
            v = block.desc.vars.get(n)
            if (n not in produced and n not in feed_names
                    and not (v is not None and v.persistable)):
                raise ValueError(
                    f"refusing to serve: fetch '{n}' is not computable "
                    f"from the feeds — no op in the pruned program "
                    f"produces it (a gradient/optimizer output is not an "
                    f"inference fetch)")
        from ..analysis import analyze_program
        report = analyze_program(program, feeds=list(feed_names),
                                 fetches=list(fetch_names))
        if report.errors:
            raise ProgramVerifyError(report.errors,
                                     program_name="serving admission")
        telemetry.log_event("serving_admit", program=self._label,
                            ops=len(program.global_block().ops),
                            warnings=len(report.warnings))

    # --- bucket cache -------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _executable_locked(self, bucket: int):
        """AOT executable for one bucket — LRU cache with telemetry.
        Caller holds self._lock (the `_locked` suffix is the repo's
        convention for that contract; the thread lint enforces it)."""
        import jax
        from ..memory import aval_of

        ex = self._executables.get(bucket)
        if ex is not None:
            self._executables.move_to_end(bucket)
            self.cache_hits += 1
            telemetry.counter(
                "serving_cache_hit_total",
                "serving bucket-executable cache hits",
                labels=("program", "bucket")).labels(
                    program=self._label, bucket=str(bucket)).inc()
            return ex
        self.cache_misses += 1
        telemetry.counter(
            "serving_cache_miss_total",
            "serving bucket-executable cache misses (AOT compiles)",
            labels=("program", "bucket")).labels(
                program=self._label, bucket=str(bucket)).inc()
        feed_avals = {
            n: jax.ShapeDtypeStruct((bucket,) + shape[1:], dtype)
            for n, (shape, dtype) in self._feed_meta.items()}
        state_avals = {n: aval_of(v) for n, v in self._state.items()}
        t0 = time.perf_counter()
        ex = self._compiled.fn.lower(
            feed_avals, state_avals, np.uint32(0)).compile()
        dt = time.perf_counter() - t0
        telemetry.histogram(
            "serving_compile_seconds",
            "AOT lower+compile wall seconds per bucket executable",
            labels=("program", "bucket")).labels(
                program=self._label, bucket=str(bucket)).observe(dt)
        telemetry.log_event("serving_compile", program=self._label,
                            bucket=bucket, seconds=dt)
        self._executables[bucket] = ex
        while len(self._executables) > self.cache_capacity:
            evicted, _ = self._executables.popitem(last=False)
            self.evictions += 1
            telemetry.counter(
                "serving_cache_evictions_total",
                "bucket executables LRU-evicted",
                labels=("program",)).labels(program=self._label).inc()
            telemetry.log_event("serving_evict", program=self._label,
                                bucket=evicted)
        return ex

    # --- execution ----------------------------------------------------------
    def run_batch(self, feed: Dict[str, np.ndarray],
                  valid_rows: Optional[int] = None,
                  _phase_marks: Optional[Dict] = None) -> List[np.ndarray]:
        """Execute one coalesced batch: pad to the smallest admissible
        bucket, run its AOT executable, slice the valid rows back out.
        The donated state round-trips: the returned new_state (same
        buffers off-CPU) becomes the resident state for the next call.

        `_phase_marks`, when a dict, is filled with contiguous
        (start, end) monotonic pairs for the pad / bucket_select /
        compute phases (+ the chosen bucket) — the tracing hook the
        batcher uses to record per-request child spans retroactively."""
        if self.closed:
            raise RuntimeError("ServingEngine is closed")
        t_enter = time.monotonic() if _phase_marks is not None else 0.0
        arrays = {}
        n = None
        for name in self.feed_names:
            if name not in feed:
                raise KeyError(f"missing feed '{name}'; engine feeds: "
                               f"{self.feed_names}")
            shape, dtype = self._feed_meta[name]
            a = np.ascontiguousarray(feed[name], dtype=dtype)
            if a.ndim != len(shape):
                raise ValueError(
                    f"feed '{name}' rank {a.ndim} != declared {len(shape)}")
            if n is None:
                n = a.shape[0]
            elif a.shape[0] != n:
                raise ValueError(
                    f"feeds disagree on batch: '{name}' has {a.shape[0]} "
                    f"rows, expected {n}")
            arrays[name] = a
        if n == 0:
            raise ValueError("empty batch")
        if n > self.max_batch:
            raise ValueError(
                f"batch {n} exceeds the largest bucket {self.max_batch}; "
                f"split the request (infer() chunks automatically)")
        rows = valid_rows if valid_rows is not None else n
        bucket = self.bucket_for(n)
        with self._lock:
            if self._emb_cache is not None:
                # ids -> cache slots (misses stage from the host slab
                # into self._state's slab); padding afterwards repeats
                # the last row, so pad rows carry valid slot ids
                arrays = self._emb_cache.prepare_feed(arrays)
            padded = {name: _pad_rows(a, bucket)
                      for name, a in arrays.items()}
            if _phase_marks is not None:
                t_pad = time.monotonic()
                _phase_marks["bucket"] = bucket
                _phase_marks["pad"] = (t_enter, t_pad)
            ex = self._executable_locked(bucket)
            if _phase_marks is not None:
                t_sel = time.monotonic()
                _phase_marks["bucket_select"] = (t_pad, t_sel)
            fetch, _lens, new_state = ex(padded, self._state,
                                         np.uint32(0))
            if _phase_marks is not None:
                _phase_marks["compute"] = (t_sel, time.monotonic())
            self._state = new_state
            self.bucket_runs[bucket] = \
                self.bucket_runs.get(bucket, 0) + 1
        telemetry.counter(
            "serving_bucket_runs_total",
            "batches executed per bucket",
            labels=("program", "bucket")).labels(
                program=self._label, bucket=str(bucket)).inc()
        return [np.asarray(f)[:rows] for f in fetch]

    def infer(self, feed: Dict[str, object]) -> List[np.ndarray]:
        """Synchronous single-caller inference. Dense feeds go through the
        bucketed AOT path (chunked when larger than the top bucket);
        LoDTensor feeds fall back to the classic executor on the same
        pruned program."""
        from ..executor import LoDTensor, scope_guard

        if self.closed:
            raise RuntimeError("ServingEngine is closed")
        if any(isinstance(feed.get(n), LoDTensor) and feed[n].lod
               for n in self.feed_names):
            telemetry.counter(
                "serving_fallback_total",
                "requests served by the non-AOT executor path",
                labels=("program", "reason")).labels(
                    program=self._label, reason="lod").inc()
            with self._lock:
                with scope_guard(self._scope):
                    outs = self._exe.run(self.program, feed=dict(feed),
                                         fetch_list=list(self.fetch_names),
                                         scope=self._scope)
            return [np.asarray(o) for o in outs]

        arrays = {n: np.asarray(feed[n]) for n in self.feed_names}
        n = arrays[self.feed_names[0]].shape[0]
        if n <= self.max_batch:
            return self.run_batch(arrays)
        parts = []
        for start in range(0, n, self.max_batch):
            chunk = {k: v[start:start + self.max_batch]
                     for k, v in arrays.items()}
            parts.append(self.run_batch(chunk))
        return [np.concatenate([p[i] for p in parts], axis=0)
                for i in range(len(self.fetch_names))]

    # --- lifecycle / introspection ------------------------------------------
    def stats(self) -> Dict[str, object]:
        # under the run lock: counters and resident state are mutated by
        # the batcher worker mid-run_batch, and stats() is called from
        # client/monitoring threads
        with self._lock:
            out = {
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "evictions": self.evictions,
                "bucket_runs": dict(self.bucket_runs),
                "buckets": list(self.buckets),
                "resident_state": len(self._state or ()),
            }
        if self._emb_cache is not None:
            out["emb_cache"] = self._emb_cache.stats()
        return out

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def close(self):
        """Destroy-handle semantics (C-API `paddle_tpu_machine_destroy`):
        drop executables and resident device state; further calls raise."""
        with self._lock:
            self._executables.clear()
            self._state = {}
            self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
