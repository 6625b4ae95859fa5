"""Training-dynamics observatory: on-device per-layer parameter/gradient
health time-series.

The reference framework's `show_parameter_stats_period` prints per-parameter
value/grad/momentum magnitudes every N batches by syncing each tensor to the
host. On TPU that per-param round-trip is exactly the sync stall the jitted
step exists to avoid, so this module computes the whole table as **one fused
on-device reduction appended to the traced step**: `plan()` resolves each
trainable parameter's grad var and optimizer moments at trace time,
`sampled_stats()` emits a single [groups, fields] float32 array inside the
jit (gated by `lax.cond` on the step counter so off-period steps pay one
predicate, not the reduction), and the host reads it **from a step already
finished**: the executor hands a sampled step's row to `on_step` still in
flight, it waits in the observatory's one pending queue, and the first
later step (or read) that finds it ready records it, in step order. No
step waits on the device for its own sample, so a pipelined loop keeps
its steps in flight; a verdict fires at most as many dispatches late as
the loop keeps in flight. `drain(wait=True)` (the read side below,
`Executor.close()`) reads what is pending at once, and books the wait.

Three layers:

1. **On-device** — per-series {weight l2/rms/max-abs, grad l2/rms/zero-frac,
   update ratio sqrt(sum dW^2)/(||W||+eps), optimizer-moment rms}. dW is
   **the step the optimizer's rule took, as a function of what the update
   left behind** (`optimizer_ops.dense_step`: sgd `lr * g` on the rule's
   own `Grad`, momentum `lr * v_new`, adam `lr_t * m1_new / (sqrt(m2_new)
   + eps)` with the beta powers the rule read), not `w_new - w_old`: the
   state is donated and every rule writes into its parameter's buffer, so
   an old value with a reader behind the update is copied out ahead of it
   on EVERY step (a cond's operands exist whichever branch runs), 8 bytes
   of traffic a float32 parameter. The reduction reads no old value of a
   parameter's size. `_discover_rules` finds each parameter's rule when
   the plan is built; a parameter without one (adagrad, rmsprop, ftrl, the
   proximal rules, two writers), one whose gradient arrives as SelectedRows
   and one kept narrower than float32 (a step can round away whole there,
   and frozen-param has to see what the rounding left) keep the difference
   and their copy, and `dynamics_update_norm_total{program, source="step"
   | "difference"}` counts both kinds, parameters a compile. Series are
   per-parameter on small programs and collapse to planner roles
   (parallel.planner.classify_params: embedding/attn_qkv/ffn_up/...) past
   MAX_PARAM_SERIES, bounding cardinality on billion-param programs. Fields
   with nothing to measure (no grad writer, no moments, no update this step)
   carry the -1.0 absent sentinel; NaN therefore always means genuinely
   non-finite values.
2. **History + verdicts** — a bounded ring per series with EWMA baselines,
   classifying each sample into the stable codes of HEALTH_CATALOG
   (dead-layer / frozen-param / exploding-update / saturating / ...). The
   grad-status half of the catalog is shared with inspector.GradientAudit,
   which delegates to `classify_grad()` so the two planes can never disagree
   on what "vanishing" means. Samples also stream to a JSONL file next to
   the telemetry step log (PADDLE_TPU_DYNAMICS_LOG overrides).
3. **Surfacing** — dynamics_* gauges (sentinel.ALERT_CATALOG pages on
   update-ratio spikes and dead layers), the /dynamics obs-server endpoint,
   `python -m paddle_tpu dynamics` CLI, and a crash-report section.

Knobs: PADDLE_TPU_DYNAMICS=0 disables; PADDLE_TPU_DYNAMICS_PERIOD (default
16) sets the sampling period; both read per-plan so tests can flip
them via override(). The eager fallback path does not sample — dynamics
rides the traced step only.
"""

from __future__ import annotations

import collections
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import telemetry
from .framework.desc import VarType
from .framework.framework import grad_var_name

STATE_KEY = "__dynamics__"

STAT_FIELDS = (
    "weight_l2", "weight_rms", "weight_max_abs",
    "grad_l2", "grad_rms", "grad_zero_frac",
    "update_ratio", "moment_rms",
)

# fields that may legitimately be absent (-1.0 on device -> None on host)
_OPTIONAL_FIELDS = frozenset(
    ("grad_l2", "grad_rms", "grad_zero_frac", "update_ratio", "moment_rms"))

_FLOAT_DTYPES = ("float16", "bfloat16", "float32", "float64")

# Single constants table for every band in the observatory — inspector's
# GradientAudit defaults resolve from here too (satellite: the two
# subsystems can never disagree on what "vanishing" means).
THRESHOLDS: Dict[str, float] = {
    # per-step grad classification (shared with GradientAudit)
    "grad_vanishing_abs_mean": 1e-8,
    "grad_exploding_max_abs": 1e3,
    # time-series verdicts
    "dead_grad_rms": 1e-12,          # grad present but ~exactly zero
    "frozen_update_ratio": 1e-12,    # weights not moving despite live grads
    "exploding_update_floor": 1e-1,  # |dW|/|W| above this is always suspect
    "exploding_update_band": 8.0,    # ... or this multiple of the EWMA
    "saturating_fraction": 0.995,    # activation |mean| vs max-abs
    # window lengths (samples, not steps)
    "verdict_window": 8,
    "verdict_warmup": 2,
}

# Stable health codes. Every classification site goes through _code() so
# tools/check_registry.py can pin this catalog against the emit sites in
# both directions (a code emitted but not cataloged, or cataloged but never
# emitted, fails the lint).
HEALTH_CATALOG: Dict[str, str] = {
    "ok": "series within all bands",
    "dead-layer": "grad rms ~ 0 across the verdict window (no learning "
                  "signal reaches this layer)",
    "frozen-param": "update ratio ~ 0 across the verdict window while "
                    "grads are live (optimizer not applying them)",
    "exploding-update": "|dW|/|W| above the absolute floor or the EWMA "
                        "band (LR spike / divergence precursor)",
    "saturating": "activation |mean| pinned against max-abs (probe sites; "
                  "nonlinearity stuck in its flat region)",
    "nonfinite": "NaN/Inf values in the gradient",
    "zero": "gradient identically zero this step (or param detached)",
    "vanishing": "gradient |mean| below the vanishing band",
    "exploding": "gradient max-abs above the exploding band",
}

MAX_PARAM_SERIES = 32      # past this, series collapse to planner roles
RING_CAPACITY = 512        # samples kept per series
EWMA_ALPHA = 0.15          # matches sentinel.Baseline smoothing
DEFAULT_PERIOD = 16
_EPS = 1e-12


def _code(code: str) -> str:
    assert code in HEALTH_CATALOG, f"uncataloged health code {code!r}"
    return code


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------

_FORCE_ENABLED: Optional[bool] = None
_FORCE_PERIOD: Optional[int] = None


def enabled() -> bool:
    if _FORCE_ENABLED is not None:
        return _FORCE_ENABLED
    return os.environ.get("PADDLE_TPU_DYNAMICS", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def period() -> int:
    if _FORCE_PERIOD is not None:
        return _FORCE_PERIOD
    raw = os.environ.get("PADDLE_TPU_DYNAMICS_PERIOD", "").strip()
    try:
        p = int(raw) if raw else DEFAULT_PERIOD
    except ValueError:
        p = DEFAULT_PERIOD
    return max(p, 1)


class override:
    """Context manager forcing the observatory on/off (and optionally the
    period) regardless of the environment — the tests use this rather
    than mutating os.environ."""

    def __init__(self, enabled: Optional[bool], period: Optional[int] = None):
        self._enabled = enabled
        self._period = period
        self._saved: Tuple[Optional[bool], Optional[int]] = (None, None)

    def __enter__(self):
        global _FORCE_ENABLED, _FORCE_PERIOD
        self._saved = (_FORCE_ENABLED, _FORCE_PERIOD)
        _FORCE_ENABLED = self._enabled
        if self._period is not None:
            _FORCE_PERIOD = int(self._period)
        return self

    def __exit__(self, *exc):
        global _FORCE_ENABLED, _FORCE_PERIOD
        _FORCE_ENABLED, _FORCE_PERIOD = self._saved
        return False


def cache_token(program) -> Optional[Tuple[bool, int]]:
    """Part of the executor's jit-cache key: flipping the knob or the
    period must recompile (the traced step's outputs change shape)."""
    if not enabled() or plan(program) is None:
        return None
    return (True, period())


# ---------------------------------------------------------------------------
# Trace-time plan
# ---------------------------------------------------------------------------

class _StepRule:
    """The dense rule that alone writes a parameter, and the names of what
    its step (`optimizer_ops.dense_step`) is made of: the rule's own `Grad`
    input (None where the step reads none), the new state tensors, and
    the scalars as (name, whether the state of before the step tells the
    value the rule read: ops behind the rule wrote it, none ahead)."""
    __slots__ = ("op", "grad", "new", "scalars")

    def __init__(self, op, grad, new, scalars):
        self.op = op
        self.grad = grad
        self.new = new
        self.scalars = scalars


class _ParamEntry:
    __slots__ = ("name", "grad", "sparse_grad", "moments", "role", "rule")

    def __init__(self, name, grad, sparse_grad, moments, role, rule=None):
        self.name = name
        self.grad = grad
        self.sparse_grad = sparse_grad
        self.moments = moments
        self.role = role
        self.rule = rule


class _Group:
    __slots__ = ("name", "role", "params")

    def __init__(self, name, role, params):
        self.name = name
        self.role = role
        self.params = params


class DynamicsPlan:
    __slots__ = ("groups", "grab_names", "period", "n_params")

    def __init__(self, groups, grab_names, period_, n_params):
        self.groups = groups
        self.grab_names = grab_names
        self.period = period_
        self.n_params = n_params


def _param_roles(program, params) -> Dict[str, str]:
    try:
        from .parallel.planner import classify_params
        roles = classify_params(program)
    except Exception:
        roles = {}
    return {p: roles.get(p, "dense") for p in params}


def _discover_moments(block, param_shapes) -> Dict[str, List[str]]:
    """Optimizer accumulators: inputs of any op with a Param slot whose
    persistable desc shape equals the param's (excludes the [1]-shaped
    global beta-pow accumulators)."""
    moments: Dict[str, List[str]] = {}
    for op in block.ops:
        pnames = op.desc.inputs.get("Param")
        if not pnames or pnames[0] not in param_shapes:
            continue
        pname = pnames[0]
        pshape = param_shapes[pname]
        for slot, names in op.desc.inputs.items():
            if slot in ("Param", "Grad", "LearningRate"):
                continue
            for n in names:
                if n == pname or not block.desc.has_var(n):
                    continue
                d = block.desc.var(n)
                if not d.persistable or d.shape is None:
                    continue
                if tuple(d.shape) != tuple(pshape):
                    continue
                if (d.dtype or "float32") not in _FLOAT_DTYPES:
                    continue
                bucket = moments.setdefault(pname, [])
                if n not in bucket:
                    bucket.append(n)
    return moments


def _discover_rules(block, param_shapes) -> Dict[str, _StepRule]:
    """Per parameter, the optimizer op whose step stands for its update:
    a rule with a step sibling (`optimizer_ops.step_reads`) that is the
    one writer of the parameter and of its new moments, whose gradient
    nothing rewrites behind it and whose scalars are still told when the
    block ends: by the trace where nothing wrote them behind the rule,
    else by the state of before the step where nothing wrote them ahead
    of it (Adam's beta powers, advanced behind every adam op). Any other
    parameter keeps the difference of its values."""
    from .ops import optimizer_ops
    writers: Dict[str, List[int]] = {}
    for i, op in enumerate(block.ops):
        for n in op.output_arg_names:
            writers.setdefault(n, []).append(i)

    def first(names):
        return names[0] if names else None

    def told(name, old):
        """Whether the end of the block still tells the scalar the rule at
        `i` read: in the trace, or (`old`) in the state of before."""
        if name is None:
            return False
        return not old or (
            block.desc.has_var(name) and block.desc.var(name).persistable
            and all(w > i for w in writers[name]))

    rules: Dict[str, _StepRule] = {}
    for i, op in enumerate(block.ops):
        pname = first(op.desc.inputs.get("Param"))
        reads = optimizer_ops.step_reads(op) if pname in param_shapes \
            else None
        if reads is None or writers.get(pname) != [i]:
            continue
        new_slots, reads_grad, scalar_slots = reads
        new = tuple(first(op.desc.output(s)) for s in new_slots)
        grad = first(op.desc.input("Grad")) if reads_grad else None
        scalars = tuple((n, any(w >= i for w in writers.get(n, ())))
                        for n in (first(op.desc.input(s))
                                  for s in scalar_slots))
        if reads_grad and (grad is None
                           or any(w >= i for w in writers.get(grad, ()))):
            continue
        if all(writers.get(n) == [i] for n in new) \
                and all(told(n, old) for n, old in scalars):
            rules[pname] = _StepRule(op, grad, new, scalars)
    return rules


def _build_plan(program) -> Optional[DynamicsPlan]:
    block = program.global_block()
    params = [p for p in block.all_parameters()
              if getattr(p, "trainable", True)
              and (p.dtype or "float32") in _FLOAT_DTYPES]
    if not params:
        return None

    written = set()
    for op in block.ops:
        written.update(op.output_arg_names)

    entries = []
    for p in params:
        g = grad_var_name(p.name)
        grad = None
        sparse = False
        if g in written and block.desc.has_var(g):
            d = block.desc.var(g)
            if (d.dtype or "float32") in _FLOAT_DTYPES:
                grad = g
                sparse = d.type == VarType.SELECTED_ROWS
        entries.append((p.name, grad, sparse, tuple(p.shape or ())))
    if not any(e[1] for e in entries):
        # no grads written anywhere: startup / serving / inference program
        return None

    param_shapes = {name: shape for name, _, _, shape in entries}
    moments = _discover_moments(block, param_shapes)
    rules = _discover_rules(block, param_shapes)
    roles = _param_roles(program, list(param_shapes))

    pents = [_ParamEntry(name, grad, sparse,
                         tuple(moments.get(name, ())), roles[name],
                         rules.get(name))
             for name, grad, sparse, _ in entries]

    if len(pents) <= MAX_PARAM_SERIES:
        groups = [_Group(e.name, e.role, [e]) for e in pents]
    else:
        by_role: Dict[str, List[_ParamEntry]] = {}
        for e in pents:
            by_role.setdefault(e.role, []).append(e)
        groups = [_Group(role, role, es)
                  for role, es in sorted(by_role.items())]
    groups.sort(key=lambda grp: grp.name)

    # what the reduction reads of the trace beside the state: the raw
    # gradients, and of a rule's step its own gradient and the scalars
    # written ahead of it
    grab = {e.grad for e in pents if e.grad is not None}
    for r in rules.values():
        grab.update(n for n, old in r.scalars if not old)
        if r.grad is not None:
            grab.add(r.grad)
    grab = sorted(grab)
    return DynamicsPlan(groups, tuple(grab), period(), len(pents))


def plan(program) -> Optional[DynamicsPlan]:
    """Resolve (and cache on the program) the reduction plan, or None when
    dynamics is off / the program trains nothing / it is an inspector
    bisection clone."""
    if not enabled():
        return None
    if getattr(program, "_inspector_internal", False):
        return None
    key = (getattr(program, "_version", 0), period())
    cached = getattr(program, "_dynamics_plan", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    built = _build_plan(program)
    program._dynamics_plan = (key, built)
    return built


# ---------------------------------------------------------------------------
# On-device fused reduction (traced inside the executor's step fn)
# ---------------------------------------------------------------------------

def _resolve_step(ent: _ParamEntry, old_state, new_state, grabs):
    """(gradient, new tensors, scalars) for `optimizer_ops.dense_step` where
    the rule's step gives this parameter's update, decided where the step
    is traced; None where the difference of its values has to. It has to
    for a parameter no dense rule with a step alone updates (the plan's
    finding), for one whose gradient arrived as SelectedRows (the rule
    took its scatter-apply, and every statistic reduces over the touched
    rows), and for one kept narrower than float32: there a step can round
    away whole, and frozen-param has to see what the rounding left."""
    import jax.numpy as jnp
    rule = ent.rule
    if rule is None or jnp.result_type(new_state[ent.name]).itemsize < 4:
        return None
    g = grabs.get(rule.grad)
    if any(getattr(v, "rows", None) is not None
           for v in (g, grabs.get(ent.grad))):
        return None
    new = [new_state.get(n) for n in rule.new]
    scalars = [(old_state if old else grabs).get(n)
               for n, old in rule.scalars]
    if any(v is None for v in new + scalars) \
            or (rule.grad is not None and g is None):
        return None
    return g, new, scalars


def _count_sources(prog_label: str, steps: Dict[str, Any]):
    family = telemetry.counter(
        "dynamics_update_norm_total",
        "parameters of a traced step's dynamics table, a compile, by "
        "where the update ratio's numerator comes from: the optimizer "
        "rule's own step, or the difference of the parameter's values "
        "(which keeps a copy of the old one behind the update)",
        labels=("program", "source"))
    n_step = sum(1 for v in steps.values() if v is not None)
    # both series exist after a compile: a reader tells 0 from not booked
    family.labels(program=prog_label, source="step").inc(n_step)
    family.labels(program=prog_label, source="difference").inc(
        len(steps) - n_step)


def _group_row(grp: _Group, steps, old_state, new_state, grabs):
    import jax.numpy as jnp
    from .ops import optimizer_ops
    f32 = jnp.float32
    zero = jnp.zeros((), f32)
    w_sumsq, w_max, w_n = zero, zero, 0.0
    g_sumsq, g_nonzero, g_n = zero, zero, 0.0
    d_sumsq = zero
    m_sumsq, m_n = zero, 0.0
    has_grad = has_update = has_moment = False

    for ent in grp.params:
        if ent.name not in steps:
            continue
        w_new = new_state[ent.name]
        gval = grabs.get(ent.grad) if ent.grad is not None else None
        # sparse-grad params: EVERY statistic (weight, update, moment)
        # reduces over the rows this step touched — a full-table pass
        # would reintroduce the O(table rows) temporaries the sparse
        # apply path exists to avoid (pinned by test_sparse_grad's
        # temp_bytes_independent_of_table_rows). SelectedRows-ness is a
        # RUNTIME value type (the var desc still says LOD_TENSOR), so
        # the traced value's `.rows`, not the plan, is the signal
        rows = getattr(gval, "rows", None)
        if rows is not None:
            wf = jnp.take(jnp.asarray(w_new), rows, axis=0).astype(f32)
        else:
            wf = jnp.asarray(w_new).astype(f32)
        w_sumsq = w_sumsq + jnp.sum(jnp.square(wf))
        w_max = jnp.maximum(w_max, jnp.max(jnp.abs(wf)))
        w_n += float(wf.size)
        # the update's norm: of the rule's own step, as a function of what
        # the update left behind, wherever _resolve_step found one; nothing
        # of such a parameter's size is read from before the update
        has_update = True
        reads = steps[ent.name]
        if reads is not None:
            g, new, scalars = reads
            if g is not None:
                # the rule's upcast (optimizer_ops._param_grad)
                g = jnp.asarray(g).astype(jnp.result_type(w_new))
            df = optimizer_ops.dense_step(
                ent.rule.op, g, [jnp.asarray(v) for v in new],
                scalars).astype(f32)
        else:
            w_old = jnp.asarray(old_state[ent.name])
            if rows is not None:
                w_old = jnp.take(w_old, rows, axis=0)
            df = wf - w_old.astype(f32)
        d_sumsq = d_sumsq + jnp.sum(jnp.square(df))
        if gval is not None:
            # SelectedRows grads reduce over the touched rows only — no
            # densify (the sparse_densify_fallback counters stay at 0)
            gf = jnp.asarray(getattr(gval, "values", gval)).astype(f32)
            has_grad = True
            g_sumsq = g_sumsq + jnp.sum(jnp.square(gf))
            g_nonzero = g_nonzero + jnp.sum((gf != 0).astype(f32))
            g_n += float(gf.size)
        for mname in ent.moments:
            mval = new_state.get(mname)
            if mval is None:
                continue
            mval = jnp.asarray(mval)
            if rows is not None and mval.shape == jnp.shape(w_new):
                mval = jnp.take(mval, rows, axis=0)
            mf = mval.astype(f32)
            has_moment = True
            m_sumsq = m_sumsq + jnp.sum(jnp.square(mf))
            m_n += float(mf.size)

    absent = jnp.asarray(-1.0, f32)
    w_l2 = jnp.sqrt(w_sumsq)
    row = [
        w_l2,
        jnp.sqrt(w_sumsq / max(w_n, 1.0)),
        w_max,
        jnp.sqrt(g_sumsq) if has_grad else absent,
        jnp.sqrt(g_sumsq / max(g_n, 1.0)) if has_grad else absent,
        (1.0 - g_nonzero / max(g_n, 1.0)) if has_grad else absent,
        (jnp.sqrt(d_sumsq) / (w_l2 + _EPS)) if has_update else absent,
        jnp.sqrt(m_sumsq / max(m_n, 1.0)) if has_moment else absent,
    ]
    return jnp.stack([jnp.asarray(v, f32) for v in row])


def sampled_stats(dyn_plan: Optional[DynamicsPlan], old_state, new_state,
                  grabs, rng_counter, prog_label: str = ""):
    """[len(groups), len(STAT_FIELDS)] float32, or None when no plan. Off
    period-boundary steps return a NaN filler (never read host-side — the
    executor knows the counter — but it must be popped before check_nan).

    `old_state` is donated and every rule writes its outputs into its
    inputs' buffers, so whatever of it the sampled branch reads, XLA
    copies out ahead of the update on every step, sampled or not (a
    cond's operands exist whichever branch runs). So the branch is not
    given it: it closes over what `_resolve_step` resolved, scalars of
    `old_state` among them, and over `old`, the old value of each
    parameter that has to fall to the difference."""
    if dyn_plan is None:
        return None
    import jax
    import jax.numpy as jnp
    shape = (len(dyn_plan.groups), len(STAT_FIELDS))

    steps = {ent.name: _resolve_step(ent, old_state, new_state, grabs)
             for grp in dyn_plan.groups for ent in grp.params
             if ent.name in old_state and ent.name in new_state}
    _count_sources(prog_label, steps)
    old = {n: old_state[n] for n, reads in steps.items() if reads is None}

    def _take(_):
        return jnp.stack([_group_row(grp, steps, old, new_state, grabs)
                          for grp in dyn_plan.groups])

    def _skip(_):
        return jnp.full(shape, jnp.nan, jnp.float32)

    if dyn_plan.period <= 1:
        return _take(None)
    hit = jnp.mod(jnp.asarray(rng_counter, jnp.uint32),
                  jnp.uint32(dyn_plan.period)) == 0
    return jax.lax.cond(hit, _take, _skip, None)


# ---------------------------------------------------------------------------
# Per-step grad classification (shared with inspector.GradientAudit)
# ---------------------------------------------------------------------------

def classify_grad(nonfinite: bool, l2: float, abs_mean: float,
                  max_abs: float,
                  vanishing_threshold: Optional[float] = None,
                  exploding_threshold: Optional[float] = None) -> str:
    """The one grad-health decision procedure: GradientAudit delegates here
    so its verdicts and the observatory's use identical bands."""
    vt = (THRESHOLDS["grad_vanishing_abs_mean"]
          if vanishing_threshold is None else vanishing_threshold)
    et = (THRESHOLDS["grad_exploding_max_abs"]
          if exploding_threshold is None else exploding_threshold)
    if nonfinite:
        return _code("nonfinite")
    if l2 == 0.0:
        return _code("zero")
    if abs_mean < vt:
        return _code("vanishing")
    if max_abs > et:
        return _code("exploding")
    return _code("ok")


# ---------------------------------------------------------------------------
# Host-side observatory: rings, EWMA baselines, verdicts, export
# ---------------------------------------------------------------------------

class _Series:
    __slots__ = ("role", "ring", "ewma", "n", "code", "since_step")

    def __init__(self, role: str):
        self.role = role
        self.ring = collections.deque(maxlen=RING_CAPACITY)
        self.ewma: Dict[str, float] = {}
        self.n = 0
        self.code = _code("ok")
        self.since_step: Optional[int] = None


class _Observatory:
    def __init__(self):
        self.lock = threading.RLock()
        self.programs: Dict[str, Dict[str, _Series]] = {}
        self.activations: Dict[str, Dict[str, Dict[str, Any]]] = {}
        # sampled rows dispatched and not yet on the host, oldest first:
        # (program label, plan, stats, [(row of a window's stack or None,
        # the counter that step ran with)])
        self.pending: collections.deque = collections.deque()
        self._turn = threading.Lock()   # held by the one thread draining
        self.samples = 0
        self._log_fh = None
        self._log_path: Optional[str] = None

    # -- JSONL export -------------------------------------------------------

    def _resolve_log_path(self) -> Optional[str]:
        explicit = os.environ.get("PADDLE_TPU_DYNAMICS_LOG", "").strip()
        if explicit:
            return explicit
        step_log = telemetry.step_log_path()
        if step_log:
            root, _ = os.path.splitext(step_log)
            return root + ".dynamics.jsonl"
        return None

    def _write_log(self, rec: Dict[str, Any]):
        path = self._resolve_log_path()
        if path is None:
            return
        try:
            if self._log_fh is None or self._log_path != path:
                if self._log_fh is not None:
                    self._log_fh.close()
                self._log_fh = open(path, "a", buffering=1)
                self._log_path = path
            self._log_fh.write(json.dumps(rec) + "\n")
        except OSError:
            self._log_fh = None
            self._log_path = None

    # -- classification -----------------------------------------------------

    def _classify(self, s: _Series, vals: Dict[str, Optional[float]]) -> str:
        present = [v for v in vals.values() if v is not None]
        if any(not math.isfinite(v) for v in present):
            return _code("nonfinite")
        win = int(THRESHOLDS["verdict_window"])
        hist = [h[1] for h in list(s.ring)[-(win - 1):]] + [vals]
        g = vals.get("grad_rms")
        u = vals.get("update_ratio")
        if g is not None and len(hist) >= win and all(
                h.get("grad_rms") is not None
                and h["grad_rms"] <= THRESHOLDS["dead_grad_rms"]
                for h in hist):
            return _code("dead-layer")
        if (u is not None and g is not None
                and g > THRESHOLDS["dead_grad_rms"]
                and len(hist) >= win and all(
                    h.get("update_ratio") is not None
                    and h["update_ratio"]
                    <= THRESHOLDS["frozen_update_ratio"]
                    for h in hist)):
            return _code("frozen-param")
        base = s.ewma.get("update_ratio")
        if (u is not None and base is not None
                and s.n >= THRESHOLDS["verdict_warmup"]
                and u > max(THRESHOLDS["exploding_update_floor"],
                            THRESHOLDS["exploding_update_band"] * base)):
            return _code("exploding-update")
        return _code("ok")

    # -- sample intake ------------------------------------------------------

    def drain(self, wait: bool = False):
        """Record the pending samples whose rows are on the host by now,
        oldest first, and stop at the first still in flight: unless
        `wait`, which blocks on each in turn and books the wait under
        site `dynamics`. One thread records at a time, which keeps the
        step order; the turn is no lock of the table's, so a record's
        file write and a reader's wait on the device are held under
        nothing the training thread needs: its own drain never waits for
        the turn, the thread that has it takes those rows too."""
        if not self._turn.acquire(blocking=wait):
            return
        try:
            while self.pending:
                head = self.pending[0]
                how = "ready"
                if not telemetry.is_ready(head[2]):
                    if not wait:
                        return
                    try:
                        telemetry.host_wait(head[2], head[0], "dynamics")
                    except Exception:
                        pass    # the step failed on the device: dropped
                    how = "forced"
                self.pending.popleft()
                self._publish(head, how)
        finally:
            self._turn.release()

    def _publish(self, entry, how: str):
        prog_label, dyn_plan, stats, picks = entry
        try:
            arr = np.asarray(stats)
            for row, step in picks:
                self.record(prog_label, step, dyn_plan,
                            arr if row is None else arr[row], how)
        except Exception:
            pass

    def record(self, prog_label: str, step: int, dyn_plan: DynamicsPlan,
               row_arr: np.ndarray, how: str = "ready"):
        arr = np.asarray(row_arr, np.float64)
        log_recs = []
        with self.lock:
            series_map = self.programs.setdefault(prog_label, {})
            ts = time.time()
            for gi, grp in enumerate(dyn_plan.groups):
                vals: Dict[str, Optional[float]] = {}
                for fi, fname in enumerate(STAT_FIELDS):
                    v = float(arr[gi, fi])
                    if fname in _OPTIONAL_FIELDS and v < 0.0:
                        vals[fname] = None
                    else:
                        vals[fname] = v
                s = series_map.get(grp.name)
                if s is None:
                    s = series_map[grp.name] = _Series(grp.role)
                code = self._classify(s, vals)
                if code != s.code:
                    s.since_step = step
                s.code = code
                # score-before-absorb: the sample was judged against the
                # baseline it did not yet influence
                for fname, v in vals.items():
                    if v is None or not math.isfinite(v):
                        continue
                    prev = s.ewma.get(fname)
                    s.ewma[fname] = (v if prev is None else
                                     prev + EWMA_ALPHA * (v - prev))
                s.ring.append((step, vals))
                s.n += 1
                self._emit_series_gauges(prog_label, grp.name, vals)
                log_recs.append({
                    "ts": ts, "program": prog_label, "step": step,
                    "series": grp.name, "role": grp.role, "code": code,
                    **{k: (v if v is None or math.isfinite(v) else str(v))
                       for k, v in vals.items()}})
            self.samples += 1
            self._emit_program_gauges(prog_label, series_map, how)
        # JSONL export happens outside the observatory lock (file IO can
        # block); each record is one buffered write, so lines from
        # concurrent recorders interleave whole, never torn
        for rec in log_recs:
            self._write_log(rec)

    def _emit_series_gauges(self, prog_label, series, vals):
        u = vals.get("update_ratio")
        if u is not None and math.isfinite(u):
            telemetry.gauge(
                "dynamics_update_ratio",
                "per-series |dW|/(|W|+eps) from the fused on-device "
                "dynamics reduction",
                labels=("program", "series")).labels(
                    program=prog_label, series=series).set(u)
        g = vals.get("grad_rms")
        if g is not None and math.isfinite(g):
            telemetry.gauge(
                "dynamics_grad_rms",
                "per-series gradient RMS (dynamics observatory)",
                labels=("program", "series")).labels(
                    program=prog_label, series=series).set(g)
        w = vals.get("weight_rms")
        if w is not None and math.isfinite(w):
            telemetry.gauge(
                "dynamics_weight_rms",
                "per-series parameter RMS (dynamics observatory)",
                labels=("program", "series")).labels(
                    program=prog_label, series=series).set(w)

    def _emit_program_gauges(self, prog_label, series_map, how):
        dead = sum(1 for s in series_map.values()
                   if s.code == "dead-layer")
        frozen = sum(1 for s in series_map.values()
                     if s.code == "frozen-param")
        unhealthy = sum(1 for s in series_map.values() if s.code != "ok")
        # emitted every sample (including 0) so the sentinel baselines warm
        # up on healthy history instead of skipping an absent series
        telemetry.gauge(
            "dynamics_dead_layers",
            "series currently classified dead-layer",
            labels=("program",)).labels(program=prog_label).set(dead)
        telemetry.gauge(
            "dynamics_frozen_params",
            "series currently classified frozen-param",
            labels=("program",)).labels(program=prog_label).set(frozen)
        telemetry.gauge(
            "dynamics_unhealthy_series",
            "series with any non-ok dynamics verdict",
            labels=("program",)).labels(program=prog_label).set(unhealthy)
        telemetry.counter(
            "dynamics_samples_total",
            "dynamics samples recorded by the observatory, by how the row "
            "reached the host: found ready by a later step, or forced by "
            "a reader's drain",
            labels=("program", "how")).labels(
                program=prog_label, how=how).inc()

    # -- activation saturation (fed from inspector probes) ------------------

    def observe_probes(self, prog_label: str, stats):
        with self.lock:
            acts = self.activations.setdefault(prog_label, {})
            for site, st in stats.items():
                if getattr(site, "kind", None) != "probe":
                    continue
                try:
                    mx = max(abs(st.min), abs(st.max))
                    sat = (mx > 0 and st.size > 1 and st.abs_mean
                           >= THRESHOLDS["saturating_fraction"] * mx)
                    acts[site.var] = {
                        "code": _code("saturating") if sat else _code("ok"),
                        "abs_mean": st.abs_mean, "max_abs": mx,
                        "op_index": site.op_index}
                except Exception:
                    continue

    # -- read side ----------------------------------------------------------

    def verdicts(self) -> List[Dict[str, Any]]:
        out = []
        with self.lock:
            for prog, series_map in self.programs.items():
                for name, s in series_map.items():
                    if s.code != "ok":
                        out.append({"program": prog, "series": name,
                                    "role": s.role, "code": s.code,
                                    "since_step": s.since_step})
            for prog, acts in self.activations.items():
                for var, rec in acts.items():
                    if rec.get("code") != "ok":
                        out.append({"program": prog, "series": var,
                                    "role": "activation",
                                    "code": rec["code"],
                                    "since_step": None})
        return out

    def payload(self, recent: int = 32) -> Dict[str, Any]:
        with self.lock:
            programs = {}
            for prog, series_map in self.programs.items():
                series = {}
                for name, s in series_map.items():
                    rows = list(s.ring)[-max(recent, 0):]
                    last = rows[-1][1] if rows else {}
                    series[name] = {
                        "role": s.role, "verdict": s.code,
                        "since_step": s.since_step, "samples": s.n,
                        "baseline": dict(s.ewma), "last": last,
                        "recent": [{"step": st, **vals}
                                   for st, vals in rows]}
                programs[prog] = {
                    "series": series,
                    "activations": self.activations.get(prog, {})}
            return {"enabled": enabled(), "period": period(),
                    "fields": list(STAT_FIELDS),
                    "thresholds": dict(THRESHOLDS),
                    "health_codes": dict(HEALTH_CATALOG),
                    "samples_recorded": self.samples,
                    "programs": programs,
                    "verdicts": self.verdicts()}

    def crash_section(self) -> Optional[Dict[str, Any]]:
        with self.lock:
            if not self.programs and not self.activations:
                return None
            last = {}
            for prog, series_map in self.programs.items():
                last[prog] = {
                    name: {"verdict": s.code,
                           "last": (s.ring[-1][1] if s.ring else {}),
                           "step": (s.ring[-1][0] if s.ring else None)}
                    for name, s in series_map.items()}
            samples = self.samples
        return {"verdicts": self.verdicts(), "last": last,
                "samples_recorded": samples}


_OBS = _Observatory()


# ---------------------------------------------------------------------------
# Executor entry points
# ---------------------------------------------------------------------------

def on_step(program, prog_label: str, stats, rng_counter: int):
    """Queue the step's stats row if this step was a sample (the executor
    passes the pre-increment counter the traced cond saw; any other row
    is dropped here), then record what earlier samples have reached the
    host. `stats` is an output of the step just dispatched and is never
    waited on: no later step takes it as an input, so it is never
    donated and stays readable until a drain reads it."""
    dyn_plan = plan(program)
    if dyn_plan is not None and stats is not None \
            and int(rng_counter) % dyn_plan.period == 0:
        _OBS.pending.append(
            (prog_label, dyn_plan, stats, [(None, int(rng_counter))]))
    drain()


def on_window(program, prog_label: str, stats, base_counter: int,
              steps: int):
    """The same for a run_steps window: the period-boundary rows of its
    stacked [K, groups, fields] stats (step i ran with counter
    base_counter+i) are one pending entry."""
    dyn_plan = plan(program)
    if dyn_plan is not None and stats is not None:
        picks = [(i, int(base_counter) + i) for i in range(int(steps))
                 if (int(base_counter) + i) % dyn_plan.period == 0]
        if picks:
            _OBS.pending.append((prog_label, dyn_plan, stats, picks))
    drain()


def drain(wait: bool = False):
    """Record the pending samples that are on the host; with `wait`, all
    of them, blocking on those still in flight (a reader asked, or the
    executor is closing). Never raises."""
    try:
        _OBS.drain(wait)
    except Exception:
        pass


def observe_probes(prog_label: str, stats):
    """Inspector hook: activation-probe stats feed `saturating` verdicts."""
    if not enabled():
        return
    try:
        _OBS.observe_probes(prog_label, stats)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Read side
# ---------------------------------------------------------------------------

def payload(recent: int = 32) -> Dict[str, Any]:
    """The /dynamics endpoint + `dynamics --json` body. A reader asked:
    what is pending is read first, whatever it waits."""
    drain(wait=True)
    return _OBS.payload(recent=recent)


def verdicts() -> List[Dict[str, Any]]:
    drain(wait=True)
    return _OBS.verdicts()


def crash_section() -> Optional[Dict[str, Any]]:
    """Compact last-snapshot for inspector crash/hang reports. It takes
    what is on the host and waits for nothing: a hang report is written
    because the device does not answer."""
    drain()
    return _OBS.crash_section()


def reset():
    """Drop all recorded history (tests)."""
    global _OBS
    with _OBS.lock:
        if _OBS._log_fh is not None:
            try:
                _OBS._log_fh.close()
            except OSError:
                pass
    _OBS = _Observatory()
