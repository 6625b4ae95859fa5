"""Communication/compute overlap for data-parallel training (ISSUE 9).

The reference's ParallelExecutor earned its multi-device speed from
dependency-graph scheduling: each gradient's NCCL all-reduce launches as
soon as backward produces it, overlapped with the rest of backward
(PAPER.md §fluid distributed). On TPU the collective itself is inserted
by XLA's GSPMD partitioner, so the lever moves from "launch NCCL
eagerly" to "give XLA's scheduler room to hide the ICI time". This
module is that lever, in three layers:

1. **Bucketed eager gradient sync** — `plan()` groups a dp-mesh-tagged
   program's parameter gradients into size-capped per-dtype buckets in
   readiness order (ascending last-producer op index: the order backward
   finishes them) and the executor flushes each bucket at trace time
   immediately after its last producing grad op. A flush pins every
   member gradient to the replicated sharding under a
   `pd.coll.dp_grad_bucket<i>` named scope — a pure annotation, so
   numerics stay bitwise vs. the unscheduled trace — which moves the
   partial-sum -> replicated resolution point from "lazily, where the
   optimizer consumes the grad" to "eagerly, the moment the grad is
   ready", exactly the slack the latency-hiding scheduler needs to
   overlap the all-reduce with the remaining backward compute.

2. **Latency-hiding schedule plumbing** — `compiler_options()` returns
   the async-collective + latency-hiding-scheduler XLA options for the
   executor's single `jax.jit` call site (`Executor._jit_compile`, both
   the per-step and the `run_steps` scan path). Options are gated to the
   TPU backend (CPU/GPU XLA rejects them at first call) and validated
   once per process by compiling a trivial probe; a rejected set degrades
   to no options and counts an `overlap_fallback_total` reason.

3. **Auto steps-per-call** — `choose_steps_per_call()` picks the
   dispatch-amortization window K from the measured per-step Python
   overhead (K large enough that host dispatch is <= a target fraction
   of device time) bounded by the HBM headroom left after the K=1
   footprint, via memory.py's HeadroomModel (the window feed buffer
   scales linearly in K the way activations scale in batch).

Env knobs: `PADDLE_TPU_OVERLAP=1` (default on) gates all three layers;
`PADDLE_TPU_OVERLAP_BUCKET_MB` caps bucket size (default 4 MiB, read at
plan time); `PADDLE_TPU_OVERLAP_XLA_FLAGS="k=v,k=v"` overrides the
compiler-option set on any backend (still probe-validated). Per-reason
`overlap_fallback_total{program,reason}` mirrors fusion_fallback_total:
tp_sharded (model-parallel grads, no cross-dp sum to schedule) /
sharded_param (spec names an axis the mesh lacks) / missing_grad /
sparse_grad / constraint_failed at the bucket layer, platform /
rejected_options at the compile layer. Since the planner (ISSUE 15),
dp/fsdp-sharded params no longer skip: their grads bucket per
(dtype, spec) group and flush as eager reduce-scatters.

GSPMD attribution caveat: the all-reduce HLO instructions inherit the
*producer's* op_name metadata (the grad op), not the bucket scope — the
sharding-constraint nodes carrying `pd.coll.dp_grad_bucket<i>` are
compiled away into the neighbouring fusions. fleet.collective_table
therefore pools real dp-grad collectives under `(gspmd:<op>)` labels;
the per-bucket sites appear wherever the partitioner materializes
collectives at the constraint itself (resharding paths) and in the
synthetic-xplane tests that pin the reporting machinery.
"""

from __future__ import annotations

import bisect
import math
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "OVERLAP_OPT", "Bucket", "OverlapPlan", "plan", "count_fallback",
    "compiler_options", "TPU_OVERLAP_OPTIONS", "choose_steps_per_call",
]

# default ON; PADDLE_TPU_OVERLAP=0 restores the unscheduled trace and
# plain jit compiles (the bitwise-parity baseline)
OVERLAP_OPT = os.environ.get("PADDLE_TPU_OVERLAP", "1") == "1"


def _bucket_cap_bytes() -> int:
    """Per-bucket payload cap. Read at plan time so tests can shrink it
    (a tiny cap forces multiple buckets out of KB-sized test models)."""
    try:
        mb = float(os.environ.get("PADDLE_TPU_OVERLAP_BUCKET_MB", "4"))
    except ValueError:
        mb = 4.0
    return max(int(mb * 1024 * 1024), 1)


def count_fallback(program, reason: str, amount: int = 1):
    """overlap_fallback_total{program,reason} — the same per-reason
    telemetry shape as fusion_fallback_total / executor_window_fallback."""
    from .. import telemetry
    telemetry.counter(
        "overlap_fallback_total",
        "gradients or compile paths that kept the unscheduled sync by "
        "reason (communication/compute overlap pass)",
        labels=("program", "reason")).labels(
        program=telemetry.program_label(program), reason=reason).inc(amount)


# --------------------------------------------------------------------------
# Layer 1: bucketed eager gradient sync (trace-time pass)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Bucket:
    """One flush unit: `grads[i]` is the gradient of `params[i]`, all the
    same declared dtype AND the same parameter spec group, total payload
    <= the plan-time cap. `anchor` is the global-block index of the LAST
    op producing any member gradient — the executor flushes the bucket
    right after that op executes. `spec` is the spec group's entry tuple:
    empty for replicated params (the pure-dp case, pinned to the
    replicated sharding = eager all-reduce) and the parameter's own spec
    for ZeRO/fsdp-sharded ones (pinned to the param spec = eager
    reduce-scatter)."""
    index: int
    params: Tuple[str, ...]
    grads: Tuple[str, ...]
    dtype: str
    bytes: int
    anchor: int
    spec: Tuple = ()

    @property
    def site(self) -> str:
        prefix = "_".join(_spec_axes(self.spec)) or "dp"
        return f"{prefix}_grad_bucket{self.index}"


def _spec_axes(spec) -> Tuple[str, ...]:
    """Sorted axis names a spec tuple shards over (tuple entries like
    ("fsdp","tp") flattened); () for replicated."""
    axes = set()
    for ent in (spec or ()):
        for a in (ent if isinstance(ent, (tuple, list))
                  else (ent,) if ent else ()):
            axes.add(str(a))
    return tuple(sorted(axes))


def _norm_spec(spec) -> Tuple:
    """Hashable canonical form of a spec tuple (lists -> tuples, trailing
    Nones stripped) — the bucket group key next to dtype."""
    out = [tuple(ent) if isinstance(ent, list) else ent
           for ent in (spec or ())]
    while out and not out[-1]:
        out.pop()
    return tuple(out)


class OverlapPlan:
    """Buckets for one program version, indexed for the executor's trace
    loop. Cached like fusion plans, so it must stay stateless across
    traces — flush_range takes everything per-trace as arguments."""

    def __init__(self, buckets: List[Bucket]):
        self.buckets = buckets
        self.by_anchor: Dict[int, List[Bucket]] = {}
        for b in buckets:
            self.by_anchor.setdefault(b.anchor, []).append(b)
        self.anchors = sorted(self.by_anchor)

    @property
    def sites(self) -> List[str]:
        return [b.site for b in self.buckets]

    def flush_range(self, ctx, env, lo: int, hi: int):
        """Flush every bucket anchored in [lo, hi) — the op index span the
        trace loop just executed (a fused group advances several indices
        at once, so anchors inside the group flush after it)."""
        i = bisect.bisect_left(self.anchors, lo)
        while i < len(self.anchors) and self.anchors[i] < hi:
            for b in self.by_anchor[self.anchors[i]]:
                _flush(ctx, b, env)
            i += 1


_PLANS: Dict[Tuple[int, int], Tuple[Any, Optional[OverlapPlan]]] = {}


def plan(program) -> Optional[OverlapPlan]:
    """The program's bucket plan, or None when overlap is off / the
    program is not dp-mesh-tagged / it has no dense replicated parameter
    gradients. Cached per (id, version) like fusion.plan."""
    if not OVERLAP_OPT:
        return None
    mesh = getattr(program, "_mesh", None)
    if mesh is None or "dp" not in getattr(mesh, "axis_names", ()):
        return None
    key = (id(program), getattr(program, "_version", 0))
    hit = _PLANS.get(key)
    if hit is not None and hit[0] is program:
        return hit[1]
    if len(_PLANS) > 64:
        _PLANS.clear()
    p = _build(program)
    _PLANS[key] = (program, p)
    return p


def _dtype_nbytes(dtype: str) -> int:
    import numpy as np
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        return 4


def _grad_pairs(program) -> List[Tuple[str, str]]:
    """(param, grad) name pairs. append_backward records them on the
    program (`_grad_param_pairs`); older programs fall back to the
    grad_var_name convention against declared block vars."""
    pairs = getattr(program, "_grad_param_pairs", None)
    if pairs:
        return list(pairs)
    from ..framework.framework import grad_var_name
    block = program.global_block()
    out = []
    for p in block.all_parameters():
        if not getattr(p, "trainable", True):
            continue
        g = grad_var_name(p.name)
        if block.desc.has_var(g):
            out.append((p.name, g))
    return out


_CONSUMER_CACHE: Dict[Tuple[int, int], Dict[str, str]] = {}


def _grad_consumer_map(program) -> Dict[str, str]:
    """{grad var name -> consuming optimizer op type}, cached per
    (program, version): lets _flush label a SelectedRows gradient as
    handled-by-scatter-apply vs genuinely unsupported."""
    key = (id(program), getattr(program, "_version", 0))
    hit = _CONSUMER_CACHE.get(key)
    if hit is not None:
        return hit
    out: Dict[str, str] = {}
    try:
        ops = program.global_block().ops
    except AttributeError:   # synthetic test programs
        ops = ()
    for op_ in ops:
        ins = op_.desc.inputs
        if "Grad" in ins and "Param" in ins and ins["Grad"]:
            out[ins["Grad"][0]] = op_.type
    _CONSUMER_CACHE[key] = out
    while len(_CONSUMER_CACHE) > 64:
        _CONSUMER_CACHE.pop(next(iter(_CONSUMER_CACHE)))
    return out


def _build(program) -> Optional[OverlapPlan]:
    import numpy as np

    from . import planner as planner_mod

    block = program.global_block()
    pairs = _grad_pairs(program)
    if not pairs:
        return None
    # one pass over the block: where is each gradient last produced?
    last: Dict[str, int] = {}
    for i, op in enumerate(block.ops):
        for name in op.desc.output_arg_names():
            last[name] = i
    specs = getattr(program, "_param_shardings", {})
    mesh = getattr(program, "_mesh", None)
    mesh_axes = set(getattr(mesh, "axis_names", ()) or ())
    splan = getattr(program, "_sharding_plan", None)
    model_axes = planner_mod.model_axes(
        splan.layout if splan is not None else None)
    items = []  # (anchor, pname, gname, dtype, nbytes, spec)
    for pname, gname in pairs:
        anchor = last.get(gname)
        if anchor is None:
            continue  # grad never produced in this block (pruned)
        if pname in (getattr(program, "_sharded_tables", None) or {}):
            # row-sharded embedding table: the grad is SelectedRows by
            # construction and the scatter-apply optimizer consumes it —
            # handled by the sparse path, not an overlap miss
            count_fallback(program, "sharded_table_sparse_path")
            continue
        if gname in (getattr(program, "_sparse_grad_names", None) or ()):
            # is_sparse embedding grad (append_backward records these):
            # stays SelectedRows end-to-end on purpose
            count_fallback(program, "sparse_grad_handled")
            continue
        spec = _norm_spec(specs.get(pname))
        if spec:
            axes = set(_spec_axes(spec))
            if axes & model_axes:
                # genuinely model-parallel (tensor-sharded) grad: each
                # shard holds DIFFERENT values, there is no cross-dp sum
                # to schedule — GSPMD's per-param resharding stays
                count_fallback(program, "tp_sharded")
                continue
            if axes - mesh_axes:
                # spec names an axis this mesh doesn't have — can't pin
                # to it; keep the historical reason for dashboards
                count_fallback(program, "sharded_param")
                continue
            # dp/fsdp spec group: the grad IS a cross-dp sum; pinning it
            # to the param's spec is an eager reduce-scatter — bucketed
            # below per (dtype, spec) group
        try:
            var = block.var(gname) if block.desc.has_var(gname) \
                else block.var(pname)
            shape = tuple(var.shape or ())
            dtype = str(var.dtype)
        except Exception:
            count_fallback(program, "unknown_var")
            continue
        if any(d is None or d < 0 for d in shape):
            count_fallback(program, "dynamic_shape")
            continue
        nbytes = int(np.prod(shape, dtype=np.int64)) * _dtype_nbytes(dtype) \
            if shape else _dtype_nbytes(dtype)
        items.append((anchor, pname, gname, dtype, nbytes, spec))
    if not items:
        return None
    # readiness order: ascending last-producer index = the order backward
    # finishes gradients (reverse-topological over the forward graph)
    items.sort(key=lambda it: (it[0], it[2]))
    cap = _bucket_cap_bytes()
    buckets: List[Bucket] = []
    # (dtype, spec) group -> [params, grads, bytes, anchor]: grads only
    # bucket with grads that pin to the SAME sharding, so a replicated
    # fc bias never rides an fsdp weight's reduce-scatter bucket
    open_by_group: Dict[Tuple[str, Tuple], List[Any]] = {}

    def _close(group):
        acc = open_by_group.pop(group, None)
        if acc:
            dtype, spec = group
            buckets.append(Bucket(
                index=len(buckets), params=tuple(acc[0]),
                grads=tuple(acc[1]), dtype=dtype, bytes=acc[2],
                anchor=acc[3], spec=spec))

    for anchor, pname, gname, dtype, nbytes, spec in items:
        group = (dtype, spec)
        acc = open_by_group.get(group)
        if acc is not None and acc[2] + nbytes > cap:
            _close(group)
            acc = None
        if acc is None:
            acc = open_by_group[group] = [[], [], 0, anchor]
        acc[0].append(pname)
        acc[1].append(gname)
        acc[2] += nbytes
        acc[3] = max(acc[3], anchor)
    # deterministic close order for the stragglers: by group key
    for group in sorted(open_by_group, key=repr):
        _close(group)
    buckets.sort(key=lambda b: (b.anchor, b.index))
    # re-number in anchor order so site indices follow flush order
    buckets = [Bucket(index=i, params=b.params, grads=b.grads,
                      dtype=b.dtype, bytes=b.bytes, anchor=b.anchor,
                      spec=b.spec)
               for i, b in enumerate(buckets)]
    return OverlapPlan(buckets)


def _flush(ctx, bucket: Bucket, env: Dict[str, Any]):
    """Pin every dense member gradient to the bucket's spec-group
    sharding — replicated for the pure-dp group (eager all-reduce), the
    param's own dp/fsdp spec for a ZeRO group (eager reduce-scatter) —
    under the bucket's pd.coll scope. Pure annotation — the constrained
    value is the same value, so the trace stays bitwise identical; only
    WHERE the partitioner resolves the cross-device sum moves."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..ops.common import SelectedRowsVal
    from ._collectives import coll_scope

    program = ctx.program
    mesh = getattr(program, "_mesh", None)
    if mesh is None:
        return
    try:
        repl = NamedSharding(mesh, PartitionSpec(*bucket.spec))
    except (TypeError, ValueError):
        count_fallback(program, "constraint_failed")
        return
    emitted = 0
    with coll_scope(bucket.site):
        for gname in bucket.grads:
            v = env.get(gname)
            if v is None:
                count_fallback(program, "missing_grad")
                continue
            if isinstance(v, SelectedRowsVal):
                # sparse grads keep the per-param SelectedRows path —
                # densifying an embedding grad to bucket it is O(vocab).
                # Distinguish "the scatter-apply optimizer handles this"
                # (expected, not a miss) from a consumer that will
                # densify anyway (a genuine overlap+sparse gap).
                from ..ops import sparse_ops
                opt_t = _grad_consumer_map(program).get(gname)
                if opt_t in sparse_ops.SPARSE_APPLY_OPS \
                        and sparse_ops.sparse_apply_enabled():
                    count_fallback(program, "sparse_grad_handled")
                else:
                    count_fallback(program, "sparse_grad_unsupported")
                continue
            try:
                env[gname] = jax.lax.with_sharding_constraint(v, repl)
                emitted += 1
            except Exception:  # non-jax value / rank mismatch
                count_fallback(program, "constraint_failed")
    if emitted:
        from .. import telemetry
        telemetry.counter(
            "overlap_buckets_total",
            "gradient buckets flushed eagerly by the overlap pass "
            "(per trace)",
            labels=("program",)).labels(
            program=telemetry.program_label(program)).inc()


# --------------------------------------------------------------------------
# Layer 2: latency-hiding schedule plumbing (compiler options)
# --------------------------------------------------------------------------

# the async-collective + latency-hiding set for TPU backends: every one
# is accepted by the installed libtpu (0.0.34; compiled for a described
# v5e:2x2 and on four chips by chip_smoke.py --multichip, which prints
# the verdict). _validate() still checks the set before first use and
# counts a rejection, so a libtpu that drops one shows up as
# overlap_fallback_total{reason="rejected_options"}, not as a failed step.
TPU_OVERLAP_OPTIONS: Dict[str, str] = {
    "xla_tpu_enable_latency_hiding_scheduler": "true",
    "xla_tpu_enable_async_collective_fusion": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    "xla_tpu_overlap_compute_collective_tc": "true",
}

_VALIDATED: Dict[Tuple[Tuple[str, str], ...], bool] = {}


def _parse_env_options(s: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        out[k.strip()] = v.strip() or "true"
    return out


def _validate(opts: Dict[str, str]) -> bool:
    """Once per process per option set: compile-and-run a trivial jit with
    the options. XLA reports an unknown option as INVALID_ARGUMENT at the
    first call (not at jit() construction), which means 'drop the set'."""
    key = tuple(sorted(opts.items()))
    hit = _VALIDATED.get(key)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp
    try:
        jax.jit(lambda a: a + 1, compiler_options=dict(opts))(
            jnp.zeros((), jnp.int32))
        ok = True
    except jax.errors.JaxRuntimeError:  # INVALID_ARGUMENT: unknown option
        ok = False
    _VALIDATED[key] = ok
    return ok


def compiler_options(program=None) -> Optional[Dict[str, str]]:
    """The compiler_options dict for this program's jit compile, or None
    for a plain compile. None whenever there is nothing to overlap (off
    gate, no mesh) — keeping single-host compiles byte-identical to
    pre-overlap builds — or when the backend/options fail validation."""
    if not OVERLAP_OPT:
        return None
    if program is not None and getattr(program, "_mesh", None) is None:
        return None
    env = os.environ.get("PADDLE_TPU_OVERLAP_XLA_FLAGS")
    if env is not None:
        opts = _parse_env_options(env)
        if not opts:
            return None
    else:
        import jax
        if jax.default_backend() != "tpu":
            # CPU/GPU XLA rejects the TPU scheduler flags at first call;
            # the bucket layer still runs (it is backend-neutral)
            count_fallback(program, "platform")
            return None
        opts = dict(TPU_OVERLAP_OPTIONS)
    if not _validate(opts):
        count_fallback(program, "rejected_options")
        return None
    return opts


# --------------------------------------------------------------------------
# Layer 3: auto steps-per-call
# --------------------------------------------------------------------------

def choose_steps_per_call(python_overhead_ms: Optional[float] = None,
                          step_time_ms: Optional[float] = None,
                          feed_bytes_per_step: Optional[int] = None,
                          peak_bytes: Optional[int] = None,
                          budget_bytes: Optional[int] = None,
                          target_overhead_frac: float = 0.02,
                          lo: int = 1, hi: int = 64) -> int:
    """Pick the run_steps window K.

    Amortization: with K steps per dispatch the per-step Python cost is
    overhead/K, so K = ceil(overhead / (frac * step_time)) caps host
    dispatch at `target_overhead_frac` of device time. Memory: the
    stacked [K, B, ...] feed window grows linearly in K on top of the
    K=1 footprint, the same linear shape HeadroomModel fits for batch
    sizes — max_batch(budget) over (fixed = peak - one window,
    per_item = one window) bounds K to the HBM headroom. Missing
    measurements degrade gracefully: no timing signal means 'as large as
    memory allows', no memory signal means the amortization value alone,
    neither means `hi`. Result is always clamped to [lo, hi]."""
    lo = max(1, int(lo))
    hi = max(lo, int(hi))
    k = hi
    if python_overhead_ms and step_time_ms and step_time_ms > 0 \
            and target_overhead_frac > 0:
        need = python_overhead_ms / (target_overhead_frac * step_time_ms)
        k = min(k, max(lo, int(math.ceil(need))))
    if feed_bytes_per_step and budget_bytes:
        from ..memory import HeadroomModel
        model = HeadroomModel(
            fixed_bytes=max(0.0, float(peak_bytes or 0)
                            - float(feed_bytes_per_step)),
            per_item_bytes=float(feed_bytes_per_step))
        k_mem = model.max_batch(int(budget_bytes))
        if k_mem is not None:
            k = min(k, max(lo, k_mem))
    return max(lo, min(k, hi))
