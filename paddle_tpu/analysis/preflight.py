"""Fast-path preflight advisors (the `preflight` pass).

The runtime already measures every missed fast path after the fact
(pallas_fallback_total, fusion_fallback_total, overlap_fallback_total,
sparse_densify_fallback_total). This pass answers the question those
counters can't: *before compile*, which ops will miss, and what
one-line change fixes it. It dry-runs the real gates — never parallel
re-implementations:

  quant    — quant.gate_for_op over the quantizable ops' desc avals,
             only when the program is decorated O3 (_quant_mode set):
             which matmul/conv ops will count into quant_fallback_total
             at trace time, per reason, with the one-line fix.
  sharding — `_param_shardings` specs against the mesh axis sizes; GSPMD
             requires every annotated dim divisible by the product of
             its axes, and an axis name the mesh lacks silently means
             "replicated", which is never what the annotation intended.
             These two are the only *errors* this pass emits.
  layout   — NHWC tag propagation walk (layout.AWARE_OPS/AGNOSTIC_OPS):
             ops that force a transpose barrier, as advisory info.
  plans    — fusion.plan / overlap.plan summaries, as advisory info.

Missed fast paths are warnings (the program runs, slower); plan
summaries and layout barriers are info.
"""

from __future__ import annotations

_PROBE_BATCH = 8  # stand-in for symbolic -1 dims; gates never read it


class _Aval:
    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.ndim = len(shape)
        self.dtype = dtype


def _quant_hint(reason, op_type, k):
    return {
        "disabled": "PADDLE_TPU_QUANT=0 is set — unset it (or =1) to "
                    "re-enable the quantized path",
        "mode": "this mode/op pair has no quantized kernel (fp8 needs "
                "backend support and the quant conv is int8-only); use "
                "PADDLE_TPU_QUANT_MODE=int8",
        "rank": "the quantized matmul tiles 2-D operands only",
        "dtype": "operands must reach the gate as bf16/f32 — integer or "
                 "f64 matmuls never quantize",
        "shape": f"contraction depth K={k} must be >= 32 and a multiple "
                 f"of 8 to amortize the scale sweeps on the int8 MXU "
                 f"tile; pad the feature dim",
        "kernel": "the int8 conv kernel tiles channels in 128 lanes: it "
                  "needs Ci and Co multiples of 128, groups=1, "
                  "2-element strides/paddings/dilations, padding < "
                  "effective kernel, padded width <= 2048, and no mesh "
                  "over more than one device",
        "error_bound": "the trace-time error estimate exceeds "
                       "PADDLE_TPU_QUANT_TOL; raise the tolerance to "
                       "accept the quantization noise",
    }.get(reason, reason)


def _check_quant(pctx):
    """Dry-run quant.gate_for_op — the REAL eligibility gate, not a
    re-implementation — over every quantizable op's desc avals, so an O3
    program learns before compile which ops will count into
    quant_fallback_total and why. Only runs when the program is actually
    decorated O3 (program._quant_mode set): an O1/O2 program falling
    back everywhere is the configured behavior, not a diagnosis."""
    import jax.numpy as jnp

    from .. import quant

    qmode = getattr(pctx.program, "_quant_mode", None)
    if not qmode:
        return
    block = pctx.block
    slots = {"conv2d": ("Input", "Filter"),
             "depthwise_conv2d": ("Input", "Filter")}
    per_reason = {}
    rollup = {}
    for i, op in enumerate(pctx.ops):
        if op.type not in quant.QUANT_OPS:
            continue
        xslot, yslot = slots.get(op.type, ("X", "Y"))
        xn = (op.desc.input(xslot) or [None])[0]
        yn = (op.desc.input(yslot) or [None])[0]
        if not (xn and yn and block.desc.has_var(xn)
                and block.desc.has_var(yn)):
            continue
        xv, yv = block.desc.var(xn), block.desc.var(yn)
        if xv.shape is None or yv.shape is None:
            continue
        # mxu_cast runs before the gate: O3 operands arrive bf16
        x = _Aval([_PROBE_BATCH if d == -1 else d for d in xv.shape],
                  jnp.bfloat16)
        y = _Aval(yv.shape, jnp.bfloat16)
        try:
            reason = quant.gate_for_op(
                op.type, {xslot: [x], yslot: [y]},
                dict(op.desc.attrs), qmode, nhwc=False)
        except Exception:  # noqa: BLE001 - odd desc shapes: shapes pass
            continue       # already diagnosed those
        if reason is None:
            continue
        k = x.shape[-1] if op.type in ("mul", "matmul") else None
        seen = per_reason.get(reason, 0)
        if seen >= 4:
            rollup[reason] = rollup.get(reason, 0) + 1
            continue
        per_reason[reason] = seen + 1
        pctx.emit(
            "warning", "quant-fallback",
            f"{op.type} will keep the bf16 path under O3 (reason: "
            f"{reason}) and count into quant_fallback_total",
            op_index=i, var=xn, hint=_quant_hint(reason, op.type, k))
    for reason, n in sorted(rollup.items()):
        pctx.emit("warning", "quant-fallback",
                  f"{n} more quantizable op(s) fall back for the same "
                  f"reason ({reason}) — details suppressed after the "
                  f"first {per_reason[reason]}")


def _axis_factor(entry, axis_sizes):
    """(divisor, missing axis names) for one PartitionSpec entry."""
    if entry is None:
        return 1, []
    axes = entry if isinstance(entry, (tuple, list)) else (entry,)
    factor, missing = 1, []
    for a in axes:
        if a in axis_sizes:
            factor *= int(axis_sizes[a])
        else:
            missing.append(a)
    return factor, missing


def _check_shardings(pctx):
    specs = getattr(pctx.program, "_param_shardings", None) or {}
    if not specs:
        return
    mesh = getattr(pctx.program, "_mesh", None)
    if mesh is None:
        pctx.emit("warning", "sharding-no-mesh",
                  f"{len(specs)} parameter(s) carry sharding specs but "
                  f"the program has no mesh — the annotations are dead",
                  hint="tag the program with parallel.make_mesh before "
                       "sharding parameters")
        return
    axis_sizes = dict(getattr(mesh, "shape", None) or {})
    block = pctx.block
    for pname in sorted(specs):
        spec = specs[pname]
        v = block.desc.vars.get(pname)
        if v is None or v.shape is None:
            pctx.emit("error", "sharding-unknown-param",
                      f"sharding spec {spec} names '{pname}', which is "
                      f"not a var of the global block", var=pname)
            continue
        shape = list(v.shape)
        if len(spec) > len(shape):
            pctx.emit("error", "sharding-rank",
                      f"spec {spec} has {len(spec)} entries but "
                      f"'{pname}' is rank {len(shape)} ({shape})",
                      var=pname)
            continue
        for d, entry in enumerate(spec):
            factor, missing = _axis_factor(entry, axis_sizes)
            if missing:
                pctx.emit(
                    "error", "sharding-unknown-axis",
                    f"spec {spec} for '{pname}' names mesh axis "
                    f"'{missing[0]}' but the mesh only has "
                    f"{sorted(axis_sizes) or 'no axes'}", var=pname,
                    hint="GSPMD treats an unknown axis as replicated — "
                         "fix the axis name or rebuild the mesh with it")
                continue
            if factor > 1 and shape[d] != -1 and shape[d] % factor:
                pctx.emit(
                    "error", "sharding-indivisible",
                    f"'{pname}' dim {d} has size {shape[d]}, not "
                    f"divisible by the {factor}-way split of spec entry "
                    f"{entry!r}", var=pname,
                    hint=f"pad the dim to "
                         f"{-(-shape[d] // factor) * factor} or shard a "
                         f"different dim")


def _check_layout(pctx):
    from ..ops import layout as layout_mod

    tagged = set()  # var names carrying an NHWC-family tag
    flagged = set()  # one advisory per op type
    for i, op in enumerate(pctx.ops):
        t = op.type
        base = t[: -len("_grad")] if t.endswith("_grad") else t
        ins = set(op.input_arg_names)
        if base in layout_mod.AWARE_OPS:
            tagged.update(op.output_arg_names)
            continue
        hit = sorted(ins & tagged)
        if not hit:
            continue
        if base in layout_mod.AGNOSTIC_OPS:
            tagged.update(op.output_arg_names)
            continue
        if base not in flagged:
            flagged.add(base)
            pctx.emit(
                "info", "layout-barrier",
                f"consumes NHWC-tagged '{hit[0]}' but is neither "
                f"layout-aware nor layout-agnostic: under "
                f"PADDLE_TPU_NHWC the value transposes back to "
                f"NCHW here", op_index=i, var=hit[0])


def _check_plans(pctx):
    from ..ops import fusion
    from ..parallel import overlap

    program = pctx.program
    if not fusion.FUSION_OPT:
        pctx.emit("info", "fusion-plan",
                  "fusion is disabled (PADDLE_TPU_FUSION=0): every op "
                  "traces individually")
    else:
        groups = fusion.plan(program)
        if groups:
            kinds = {}
            for g in groups.values():
                kinds[g.kind] = kinds.get(g.kind, 0) + 1
            desc = ", ".join(f"{k} x{n}" for k, n in sorted(kinds.items()))
            pctx.emit("info", "fusion-plan",
                      f"{len(groups)} fused window(s): {desc}")

    mesh = getattr(program, "_mesh", None)
    if mesh is None or "dp" not in getattr(mesh, "axis_names", ()):
        return  # overlap only applies to dp-tagged programs
    plan = overlap.plan(program)
    if plan is None:
        pctx.emit("info", "overlap-plan",
                  "dp mesh but no overlap buckets (overlap disabled or "
                  "no dense replicated parameter gradients)")
    else:
        pctx.emit("info", "overlap-plan",
                  f"{len(plan.buckets)} eager all-reduce bucket(s) over "
                  f"{sum(len(b.grads) for b in plan.buckets)} gradient(s)")


def _check_emb_cache(pctx):
    """Beyond-HBM hot-row cache sizing: the per-step touched-row bound
    for a cached table is the total id count its lookups can feed in one
    step (every id distinct in the worst case). When that bound exceeds
    cache_rows, steady-state steps evict rows they staged moments ago —
    and a fused run_steps window, whose whole-id union must be resident
    at once, can fail outright. Static shapes only; -1 dims probe as
    _PROBE_BATCH, so the bound scales with the real batch at runtime."""
    program = pctx.program
    from ..parallel import emb_cache as emb_cache_mod

    sized = {}  # table -> cache_rows (active cache wins over requests)
    cache = emb_cache_mod.active_cache(program)
    if cache is not None:
        for t in cache.tables().values():
            sized[t.name] = t.cache_rows
    for name, rows in emb_cache_mod.requested_rows(program).items():
        sized.setdefault(name, int(rows))
    if not sized:
        return

    block = pctx.block
    bound = {}     # table -> summed worst-case ids per step
    first_op = {}  # table -> op index of its first lookup
    for i, op in enumerate(pctx.ops):
        if op.type != "lookup_table":
            continue
        wname = (op.input("W") or [None])[0]
        ids = (op.input("Ids") or [None])[0]
        if wname not in sized or not ids or not block.has_var(ids):
            continue
        shape = tuple(block.var(ids).shape or ())
        n = 1
        for d in shape:
            n *= _PROBE_BATCH if int(d) == -1 else int(d)
        bound[wname] = bound.get(wname, 0) + n
        first_op.setdefault(wname, i)
    for wname, n in sorted(bound.items()):
        if n <= sized[wname]:
            continue
        pctx.emit(
            "warning", "emb-cache-thrash",
            f"cached table '{wname}' can touch up to {n} unique rows "
            f"per step (batch probed as {_PROBE_BATCH} for -1 dims) but "
            f"cache_rows={sized[wname]}: steady-state steps will evict "
            f"rows staged the same step, and a fused window's id union "
            f"may not fit the slab at all",
            op_index=first_op[wname], var=wname,
            hint="raise cache_rows (or the enable() budget) above the "
                 "per-step touched-row bound, or lower the batch size")


def _check_planner(pctx):
    """Planner-output diagnostics (ISSUE 15), on top of the per-spec
    checks in _check_shardings:

      * sharding-batch-indivisible — a feed's dim-0 batch does not
        divide by its data-axis split, so GSPMD pads every step's input;
      * sharding-overcommit — one tensor dim sharded by an axis product
        larger than the dim itself (shards would be empty/padded);
      * norm-sharded — a role the planner keeps replicated on purpose
        (norm scale/bias, layer bias) carries a spec anyway: legal, but
        almost always a hand-annotation mistake since the bytes saved
        are trivial and every use pays a gather.
    """
    program = pctx.program
    mesh = getattr(program, "_mesh", None)
    if mesh is None:
        return
    axis_sizes = dict(getattr(mesh, "shape", None) or {})
    block = pctx.block

    # feeds: explicit _feed_shardings dim-0 entries vs static batch dims
    for name, spec in sorted(
            (getattr(program, "_feed_shardings", None) or {}).items()):
        if not spec or not block.has_var(name):
            continue
        shape = tuple(block.var(name).shape or ())
        if not shape or int(shape[0]) == -1:
            continue  # symbolic batch: runtime-sized, nothing to check
        factor, _missing = _axis_factor(spec[0], axis_sizes)
        if factor > 1 and int(shape[0]) % factor:
            pctx.emit(
                "error", "sharding-batch-indivisible",
                f"feed '{name}' has batch dim {shape[0]}, not divisible "
                f"by the {factor}-way data split of spec entry "
                f"{spec[0]!r}", var=name,
                hint=f"feed a global batch that is a multiple of "
                     f"{factor}, or re-plan on a smaller data axis")

    specs = getattr(program, "_param_shardings", None) or {}
    if not specs:
        return

    # axis overcommit: one dim split by more ways than it has elements
    for pname in sorted(specs):
        v = block.desc.vars.get(pname)
        if v is None or v.shape is None:
            continue  # _check_shardings already errors unknown params
        shape = list(v.shape)
        for d, entry in enumerate(specs[pname]):
            if d >= len(shape):
                break
            factor, _missing = _axis_factor(entry, axis_sizes)
            if factor > 1 and 0 < int(shape[d]) < factor:
                pctx.emit(
                    "error", "sharding-overcommit",
                    f"'{pname}' dim {d} has size {shape[d]} but spec "
                    f"entry {entry!r} splits it {factor} ways — "
                    f"{factor - int(shape[d])} shard(s) would be empty",
                    var=pname,
                    hint="drop one axis from the entry or shard a "
                         "larger dim")

    # norm/bias roles carrying a spec: replicated-by-design params
    from ..parallel import planner as planner_mod
    try:
        roles = planner_mod.classify_params(program)
    except Exception:
        return
    for pname in sorted(specs):
        if roles.get(pname) not in ("norm", "bias"):
            continue
        if not any(e for e in specs[pname]):
            continue
        pctx.emit(
            "warning", "norm-sharded",
            f"'{pname}' is a {roles[pname]} parameter (planner keeps "
            f"these replicated) but carries spec {specs[pname]} — the "
            f"bytes saved are trivial and every use pays a gather",
            var=pname,
            hint="let planner.plan assign this spec, or drop the "
                 "hand annotation")


def run(pctx):
    _check_quant(pctx)
    _check_shardings(pctx)
    _check_layout(pctx)
    _check_plans(pctx)
    _check_emb_cache(pctx)
    _check_planner(pctx)
