#!/usr/bin/env python
"""Registry-consistency lint (ISSUE 7 satellite): every op named in the
layout pass's AGNOSTIC_OPS/AWARE_OPS sets and in the fusion pass's
pattern tables must actually be registered in ops/registry.py. A typo in
one of those tables doesn't raise at runtime — the pattern just never
matches and the optimization silently turns off — so CI pins the sets
against the registry instead.

    python tools/check_registry.py        # exits 1 and lists offenders

Names ending in `_grad` are checked against their base op: grad kernels
are materialized lazily by registry.try_get, so only the forward
registration proves the name is real.

The collective-kind lint (ISSUE 8 satellite) pins xplane.COLLECTIVE_KINDS
the same way: every pattern must classify back to its own kind through
`collective_kind` (match order matters — a pattern shadowed by an earlier
kind silently misattributes), each busbw factor table entry must have a
kind and vice versa, and each kind's canonical HLO spelling must land in
the roofline waterfall's "collective" bucket — otherwise a new kind falls
into "(unattributed)" or the wrong waterfall bar without any test failing.

The sparse-table lint (ISSUE 10 satellite) pins sparse_ops.SPARSE_APPLY_OPS
against the optimizer lowerings, the executor's sparse-aware boundary set
and the fused-bucket types: a missing entry doesn't raise either — the
gradient silently densifies and the update goes O(table rows).

The Pallas-table lint (ISSUE 11 satellite; PR 25 left one conv dispatch,
the int8 conv under O3) pins pallas_conv.KERNELS the same way: orphan
kernels, a quantizable conv op without a dispatch entry or the reverse,
a `_grad` entry (the backward transposes the lax conv; no kernel is
dispatched for it). Since PR 45 it also pins kernel_choice.REASONS, the
one table of the reasons a lowering may give for declining a Pallas
kernel, against the source of each op's gate, both ways, and
kernel_choice as the only creator of pallas_kernel_total and
pallas_fallback_total.

The quant-table lint (ISSUE 20 satellite) pins quant.QUANT_OPS the same
way, both directions: every quantizable op must be registered AND its
lowering must consult the quant gate (else the op silently loses
quantization under O3), every lowering that routes through quant must be
in the table (else prequantize/preflight/roofline don't know it exists),
and the gates' produced fallback reasons must match FALLBACK_REASONS
exactly (an undeclared reason is an unlabelled quant_fallback_total
series; a declared-but-never-produced one is a dead counter label).

The infer-rules lint (ISSUE 12 satellite) pins the static analyzer's
shape-pass coverage: every registered op must resolve to exactly one
rule source (a hand-written analysis CHECKER, the registry's own
infer_shape, the jax.eval_shape fallback list, or the explicit
DYNAMIC_SHAPE_OPS allowlist) — a newly registered op with no rule makes
the analyzer silently blind to everything downstream of it. Orphan
entries in the analysis tables are flagged in the converse direction.

The serving lint (ISSUE 13 satellite) builds both shipped examples'
inference programs (transformer logits, DLRM probabilities), applies the
ServingEngine's own strip->prune->clone, and pins the result: every
surviving op must have a registered lowering and none may be a
training-only op (optimizer / `_grad` / fused-optimizer) — a leak here
means prune kept a training subgraph and serving would mutate weights.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:        # `python tools/check_registry.py` as written
    sys.path.insert(0, REPO)


def check_tables():
    """[(table, name), ...] for every table entry with no registration."""
    from paddle_tpu.ops import fusion, layout, registry

    registered = set(registry.registered_ops())
    tables = {
        "layout.AWARE_OPS": layout.AWARE_OPS,
        "layout.AGNOSTIC_OPS": layout.AGNOSTIC_OPS,
        "fusion.CONV_OPS": fusion.CONV_OPS,
        "fusion.ACT_OPS": fusion.ACT_OPS,
        "fusion.CHAIN_OPS": fusion.CHAIN_OPS,
        "fusion.OPTIMIZER_BUCKET_OPS": fusion.OPTIMIZER_BUCKET_OPS,
        "fusion.FUSED_OP_TYPES": fusion.FUSED_OP_TYPES,
    }
    problems = []
    for tname in sorted(tables):
        for name in sorted(tables[tname]):
            base = name[:-5] if name.endswith("_grad") else name
            if base not in registered:
                problems.append((tname, name))
    return problems


def check_collective_kinds():
    """[(where, message), ...] consistency problems in the collective
    classification tables (xplane.COLLECTIVE_KINDS / _BUSBW_FACTOR) and
    their agreement with the roofline waterfall's bucket patterns."""
    from paddle_tpu import roofline, xplane

    problems = []
    kinds = [k for k, _ in xplane.COLLECTIVE_KINDS]
    if len(set(kinds)) != len(kinds):
        problems.append(("xplane.COLLECTIVE_KINDS", "duplicate kind"))
    for kind, pats in xplane.COLLECTIVE_KINDS:
        for pat in pats:
            got = xplane.collective_kind(pat)
            if got != kind:
                problems.append((
                    "xplane.COLLECTIVE_KINDS",
                    f"pattern '{pat}' of kind '{kind}' classifies as "
                    f"'{got}' — match order shadows it"))
        # the canonical (first) pattern must also land in the waterfall's
        # collective bucket, or fleet and waterfall disagree on the split
        if roofline._bucket(pats[0] + ".1") != "collective":
            problems.append((
                "roofline._COLLECTIVE_PAT",
                f"kind '{kind}' spelling '{pats[0]}' not bucketed as "
                f"'collective' by the waterfall"))
        if xplane.busbw_factor(kind, 4) <= 0:
            problems.append((
                "xplane._BUSBW_FACTOR",
                f"kind '{kind}' has no busbw factor — its busbw column "
                f"would read as raw algbw"))
    for kind in xplane._BUSBW_FACTOR:
        if kind not in kinds:
            problems.append((
                "xplane._BUSBW_FACTOR",
                f"factor for unknown kind '{kind}'"))
    return problems


# what only paddle_tpu/xplane.py may hold: the names of an HLO
# instruction's attributes (a regex over compiled text reaches for them)
# and the walk of the xplane wire format
_HLO_TEXT_MARKERS = ("replica_groups", "op_name=", "custom_call_target",
                     "dim_labels", "lhs_contracting_dims")
_GONE_READERS = ("aggregate_dir", "collective_events_dir",
                 "hlo_participants", "hlo_counts", "_hlo_supplier",
                 "hlo_op_names", "hlo_collectives", "aggregate_lines",
                 "register_hlo_supplier", "consume_suppliers")


def check_one_parse(root=None):
    """[(where, message), ...] — in the package, one function parses
    compiled text (xplane.hlo_instructions) and one reads a trace
    (xplane.device_steps): no other file names an HLO attribute or walks
    the wire format (`xplane.fields`), and the readers they replaced do
    not come back."""
    root = root or os.path.join(REPO, "paddle_tpu")
    problems = []
    for dirpath, _, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, os.path.dirname(root))
            with open(path) as f:
                text = f.read()
            for gone in _GONE_READERS:
                if gone in text:
                    problems.append((rel, f"names the removed reader "
                                          f"'{gone}'"))
            if os.path.basename(path) == "xplane.py" \
                    and os.path.dirname(path) == root:
                continue
            for marker in _HLO_TEXT_MARKERS:
                if marker in text:
                    problems.append((
                        rel, f"reads compiled text for itself ('{marker}'): "
                             f"xplane.hlo_instructions is the one parse"))
            if "xplane.fields" in text or "xplane_mod.fields" in text:
                problems.append((
                    rel, "walks the xplane wire format: "
                         "xplane.device_steps is the one reader"))
    return problems


def check_jit_sites():
    """[(where, message), ...] — executor.py must funnel every compile
    through the single `Executor._jit_compile` jit call site (ISSUE 9):
    that is where the overlap pass's compiler_options (latency-hiding
    scheduler, async collectives) are threaded, so a new direct call
    site would silently compile without them. The module-level `@jax.jit`
    decorator (no parenthesis) is the one sanctioned exception."""
    import inspect

    from paddle_tpu import executor

    problems = []
    src = inspect.getsource(executor)
    sites = src.count("jax.jit(")
    if sites != 1:
        problems.append((
            "executor.jax.jit",
            f"{sites} direct jit call sites in executor.py (expected "
            f"exactly 1, inside _jit_compile) — a new site skips the "
            f"overlap compiler_options threading"))
    helper = getattr(executor.Executor, "_jit_compile", None)
    if helper is None:
        problems.append(("executor._jit_compile",
                         "Executor._jit_compile helper is missing"))
    else:
        hsrc = inspect.getsource(helper)
        if "jax.jit(" not in hsrc:
            problems.append((
                "executor._jit_compile",
                "the single jit call site is not inside _jit_compile"))
        if "compiler_options(" not in hsrc:
            problems.append((
                "executor._jit_compile",
                "_jit_compile does not thread overlap.compiler_options"))
    return problems


def check_sparse_table():
    """[(where, message), ...] — pin sparse_ops.SPARSE_APPLY_OPS (ISSUE 10)
    against the three layers that must agree on it: every listed optimizer
    needs a `<op>_apply` scatter kernel in ops/sparse_ops.py AND a
    SelectedRows branch in ops/optimizer_ops.py that calls it, every
    listed op (plus its fused_sparse_ bucket variant) must sit in
    executor._SPARSE_AWARE_OPS so the sparse boundary doesn't densify its
    Grad input first, and the fused variants must be registered +
    FUSED_OP_TYPES-listed. The converse holds too: a `*_apply` kernel for
    an op missing from SPARSE_APPLY_OPS silently never runs — `sum` (grad
    accumulation) is the one sparse-aware op with no apply kernel."""
    import inspect

    from paddle_tpu import executor
    from paddle_tpu.ops import fusion, optimizer_ops, registry, sparse_ops

    problems = []
    registered = set(registry.registered_ops())
    opt_src = inspect.getsource(optimizer_ops)
    for t in sparse_ops.SPARSE_APPLY_OPS:
        if t not in registered:
            problems.append(("sparse_ops.SPARSE_APPLY_OPS",
                             f"'{t}' is not registered in ops/registry.py"))
        if not callable(getattr(sparse_ops, t + "_apply", None)):
            problems.append(("sparse_ops.SPARSE_APPLY_OPS",
                             f"'{t}' has no {t}_apply scatter kernel in "
                             f"ops/sparse_ops.py"))
        if f"sparse_ops.{t}_apply" not in opt_src:
            problems.append((
                "optimizer_ops", f"'{t}' lowering never calls "
                f"sparse_ops.{t}_apply — its SelectedRows branch is gone "
                f"and the boundary would densify silently"))
        for name in (t, "fused_sparse_" + t):
            if name not in executor._SPARSE_AWARE_OPS:
                problems.append((
                    "executor._SPARSE_AWARE_OPS",
                    f"'{name}' missing — the sparse boundary densifies "
                    f"its Grad input before the scatter kernel sees it"))
        if "fused_sparse_" + t not in fusion.FUSED_OP_TYPES:
            problems.append((
                "fusion.FUSED_OP_TYPES",
                f"'fused_sparse_{t}' missing — its bucket op would fail "
                f"the registration lint"))
    for name in dir(sparse_ops):
        if name.endswith("_apply") and callable(getattr(sparse_ops, name)):
            op = name[:-len("_apply")]
            if op not in sparse_ops.SPARSE_APPLY_OPS:
                problems.append((
                    "sparse_ops.SPARSE_APPLY_OPS",
                    f"kernel '{name}' exists but '{op}' is not listed — "
                    f"the scatter path silently never runs"))
    if "sum" not in executor._SPARSE_AWARE_OPS:
        problems.append((
            "executor._SPARSE_AWARE_OPS",
            "'sum' missing — SelectedRows grad accumulation densifies"))
    return problems


def check_emb_cache():
    """[(where, message), ...] — pin parallel/emb_cache.CACHE_AWARE_OPS
    (ISSUE 14) against the layers that make slot remapping sound. The
    cache swaps a [rows, dim] table for a [cache_rows, dim] slab and
    remaps feed ids to slots, so exactly two op families may touch a
    cached table: the lookup pair (gathers by the remapped ids) and the
    SelectedRows scatter-apply optimizers (their rows ARE the remapped
    ids). Drift in either direction corrupts silently: a SPARSE_APPLY_OPS
    member missing from CACHE_AWARE_OPS makes enable() reject valid
    programs using that optimizer, while a CACHE_AWARE_OPS member that is
    NOT sparse-aware in the executor densifies its Grad — and a dense
    update writes EVERY slot, including stale tenants of other rows."""
    import inspect

    from paddle_tpu import executor
    from paddle_tpu.ops import sparse_ops
    from paddle_tpu.parallel import emb_cache

    problems = []
    aware = emb_cache.CACHE_AWARE_OPS
    for name in ("lookup_table", "lookup_table_grad"):
        if name not in aware:
            problems.append((
                "emb_cache.CACHE_AWARE_OPS",
                f"'{name}' missing — enable() would refuse every program "
                f"containing the op the cache exists to serve"))
    scatter = set()
    for t in sparse_ops.SPARSE_APPLY_OPS:
        for name in (t, "fused_sparse_" + t):
            scatter.add(name)
            if name not in aware:
                problems.append((
                    "emb_cache.CACHE_AWARE_OPS",
                    f"'{name}' missing — enable() rejects any cached "
                    f"table trained with that optimizer even though its "
                    f"SelectedRows rows are exactly the remapped slots"))
            if name not in executor._SPARSE_AWARE_OPS:
                problems.append((
                    "executor._SPARSE_AWARE_OPS",
                    f"'{name}' missing — under the hot-row cache its "
                    f"densified Grad would update every cache slot, "
                    f"silently corrupting rows resident for other ids"))
    for name in sorted(aware - scatter
                       - {"lookup_table", "lookup_table_grad"}):
        problems.append((
            "emb_cache.CACHE_AWARE_OPS",
            f"'{name}' is listed but is neither the lookup pair nor a "
            f"SPARSE_APPLY_OPS scatter op — no slot-remap semantics "
            f"justify letting it touch a cache slab"))
    dsrc = inspect.getsource(emb_cache._discover)
    if "CACHE_AWARE_OPS" not in dsrc:
        problems.append((
            "emb_cache._discover",
            "table discovery no longer validates referencing ops against "
            "CACHE_AWARE_OPS — an op with no remap path could index the "
            "slab with global row ids"))
    return problems


def _package_sources():
    """{path relative to the repo: text} of every module of paddle_tpu/."""
    sources = {}
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for fname in sorted(files):
            if fname.endswith(".py"):
                path = os.path.join(dirpath, fname)
                with open(path) as f:
                    sources[os.path.relpath(path, REPO)] = f.read()
    return sources


def check_pallas_table():
    """[(where, message), ...] — pin the kernel layer's two tables.

    kernel_choice.REASONS (op -> the reasons its gate may give for the
    XLA path), for every op in it (a registered op, or a kernel that
    kernel_choice.WITHIN places in one): the reasons the sources of its gate
    (kernel_choice.GATES) can return equal the declared set, both ways.
    A produced reason that is not declared raises at the first lowering
    that meets it; a declared reason nothing produces is a dead counter
    label. And kernel_choice is the only module of paddle_tpu/ that
    creates pallas_kernel_total or pallas_fallback_total: a second
    creator is a second place that decides what a hit is.

    pallas_conv.KERNELS against ops/registry.py and quant.QUANT_OPS.
    The only conv dispatch is the int8 conv2d_q8 under AMP O3 (every
    float conv and its backward is lax.conv_general_dilated), so the
    silent failure modes are: an orphan entry (an op that isn't
    registered, or whose lowering never quantizes: the kernel never
    runs), a quantizable conv op missing from KERNELS (a route nobody
    audits) and a `_grad` entry (conv2d_grad transposes the lax conv; a
    kernel listed for it is dead code that reads as a route)."""
    import importlib
    import inspect
    import re

    from paddle_tpu import quant
    from paddle_tpu.ops import kernel_choice, pallas_conv, registry

    problems = []
    registered = set(registry.registered_ops())
    quant_convs = {k for k, entry in quant.QUANT_OPS.items()
                   if entry == "qconv2d"}
    for name in sorted(pallas_conv.KERNELS):
        if name.endswith("_grad"):
            problems.append((
                "pallas_conv.KERNELS",
                f"'{name}' lists a backward kernel, but conv2d_grad "
                f"transposes the lax conv and dispatches none"))
            continue
        if name not in registered:
            problems.append((
                "pallas_conv.KERNELS",
                f"'{name}' dispatched but not registered in "
                f"ops/registry.py — orphan kernel"))
        if name not in quant_convs:
            problems.append((
                "pallas_conv.KERNELS",
                f"'{name}' is not a qconv2d entry of quant.QUANT_OPS — "
                f"its lowering never reaches the int8 kernel"))
        for fn in pallas_conv.KERNELS[name]:
            if not callable(fn):
                problems.append(("pallas_conv.KERNELS",
                                 f"'{name}' lists a non-callable kernel"))
    for name in sorted(quant_convs - set(pallas_conv.KERNELS)):
        problems.append((
            "pallas_conv.KERNELS",
            f"quant.QUANT_OPS '{name}' routes to qconv2d but has no "
            f"dispatch entry here"))

    # every reason a gate can return must be declared, and vice versa
    for name in sorted(set(kernel_choice.REASONS) | set(kernel_choice.GATES)):
        where = f"kernel_choice.REASONS['{name}']"
        if kernel_choice.WITHIN.get(name, name) not in registered:
            problems.append((where, "op is not registered in "
                                    "ops/registry.py, nor a kernel WITHIN "
                                    "one that is"))
        if name not in kernel_choice.GATES or \
                name not in kernel_choice.REASONS:
            problems.append((
                where, "REASONS and GATES must both hold the op: a table "
                       "entry whose gate the lint cannot read is unpinned"))
            continue
        produced = set()
        for dotted in kernel_choice.GATES[name]:
            module, fn = dotted.split(".")
            gate = getattr(importlib.import_module(
                f"paddle_tpu.ops.{module}"), fn, None)
            if gate is None:
                problems.append((where, f"gate '{dotted}' does not exist"))
                continue
            produced |= set(re.findall(r'return "([a-z_]+)"',
                                       inspect.getsource(gate)))
        declared = kernel_choice.REASONS[name]
        for reason in sorted(produced - declared):
            problems.append((
                where,
                f"gate returns '{reason}' but it is not declared — "
                f"kernel_choice.book raises on it at lowering"))
        for reason in sorted(declared - produced):
            problems.append((
                where,
                f"declared reason '{reason}' is never produced by the "
                f"gate — dead counter label"))

    creates = re.compile(
        r'\b(?:counter|gauge|histogram)\(\s*'
        r'["\'](pallas_kernel_total|pallas_fallback_total)["\']')
    for rel, text in sorted(_package_sources().items()):
        if rel == os.path.join("paddle_tpu", "ops", "kernel_choice.py"):
            continue
        for metric in sorted(set(creates.findall(text))):
            problems.append((
                rel, f"creates {metric}: only ops/kernel_choice.py may, "
                     f"through book()"))
    return problems


def check_quant_table():
    """[(where, message), ...] — pin quant.QUANT_OPS (ISSUE 20) against
    ops/registry.py, the lowering sources, and the fallback-reason
    vocabulary, both directions (module docstring lists the silent
    failure modes). A lowering "consults the gate" when its source (or,
    one delegation deep, a `_name(ctx, op_, ins)` callee's source —
    depthwise_conv2d delegates to _conv2d) references the quant routing
    surface: ineligible_* / qmatmul / qconv2d."""
    import inspect
    import re

    from paddle_tpu import quant
    from paddle_tpu.ops import registry

    _ROUTE = re.compile(r"quant\.(ineligible_matmul|ineligible_conv|"
                        r"qmatmul|qconv2d)\(")

    def _consults_gate(fn, depth=1):
        try:
            src = inspect.getsource(fn)
        except (OSError, TypeError):
            return False
        if _ROUTE.search(src):
            return True
        if depth <= 0:
            return False
        mod = inspect.getmodule(fn)
        return any(
            callable(getattr(mod, callee, None)) and
            _consults_gate(getattr(mod, callee), depth - 1)
            for callee in re.findall(r"\b(_[a-z0-9_]+)\(ctx, op_, ins\)",
                                     src))

    problems = []
    registered = set(registry.registered_ops())
    for op_type, entry in sorted(quant.QUANT_OPS.items()):
        if op_type not in registered:
            problems.append((
                "quant.QUANT_OPS",
                f"'{op_type}' is quantizable but not registered in "
                f"ops/registry.py — the route can never run"))
            continue
        if not callable(getattr(quant, entry, None)):
            problems.append((
                "quant.QUANT_OPS",
                f"'{op_type}' names entry point '{entry}' which is not "
                f"a callable in quant.py"))
        lower = registry.get(op_type).lower
        if lower is None or not _consults_gate(lower):
            problems.append((
                "quant.QUANT_OPS",
                f"'{op_type}' lowering never consults the quant gate — "
                f"the op silently loses quantization under O3"))
    for op_type in sorted(registered - set(quant.QUANT_OPS)):
        lower = registry.get(op_type).lower
        if lower is None:
            continue
        try:
            src = inspect.getsource(lower)
        except (OSError, TypeError):
            continue
        if _ROUTE.search(src):
            problems.append((
                "quant.QUANT_OPS",
                f"'{op_type}' lowering routes through quant but is not "
                f"in QUANT_OPS — prequantize/preflight/roofline are "
                f"blind to it"))
    produced = set()
    for gate in (quant.ineligible_matmul, quant.ineligible_conv):
        produced |= set(re.findall(r'return "([a-z_]+)"',
                                   inspect.getsource(gate)))
    for reason in sorted(produced - quant.FALLBACK_REASONS):
        problems.append((
            "quant.FALLBACK_REASONS",
            f"a gate returns '{reason}' but it is not declared — an "
            f"unlabelled quant_fallback_total series"))
    for reason in sorted(quant.FALLBACK_REASONS - produced):
        problems.append((
            "quant.FALLBACK_REASONS",
            f"declared reason '{reason}' is never produced by a gate — "
            f"dead counter label"))
    return problems


def check_infer_rules():
    """[(where, message), ...] — pin the static analyzer's shape-pass
    coverage (ISSUE 12) against ops/registry.py. Every registered op
    must be covered by one of analysis/infer.py's rule sources
    (`rule_kind` != None); an uncovered op makes the shapes pass mark
    all downstream shapes unknown without any test noticing. Conversely,
    names in the analysis tables that aren't registered are typos: the
    rule silently never fires. Overlap between the explicit tables is
    flagged too — precedence would hide one of the entries."""
    from paddle_tpu.analysis import infer
    from paddle_tpu.ops import registry

    problems = []
    registered = set(registry.registered_ops())
    for t in sorted(registered):
        if infer.rule_kind(t) is None:
            problems.append((
                "analysis.infer",
                f"registered op '{t}' has no shape rule: add a CHECKER, "
                f"a registry infer_shape, or list it in EVAL_SHAPE_OPS / "
                f"DYNAMIC_SHAPE_OPS"))
    tables = {
        "analysis.DYNAMIC_SHAPE_OPS": infer.DYNAMIC_SHAPE_OPS,
        "analysis.EVAL_SHAPE_OPS": infer.EVAL_SHAPE_OPS,
        "analysis.CHECKERS": set(infer.CHECKERS),
    }
    for tname in sorted(tables):
        for name in sorted(tables[tname]):
            base = name[:-5] if name.endswith("_grad") else name
            if base not in registered:
                problems.append((
                    tname, f"'{name}' is not registered in "
                           f"ops/registry.py — orphan rule entry"))
    for a in sorted(tables):
        for b in sorted(tables):
            if a >= b:
                continue
            for name in sorted(tables[a] & tables[b]):
                problems.append((
                    a, f"'{name}' also listed in {b} — rule-source "
                       f"precedence hides one of them"))
    return problems


def check_serving_programs():
    """[(where, message), ...] — pin the two shipped inference programs
    (ISSUE 13) against the registry and the serving admission gate. Each
    example's build_programs() declares its serving surface
    (infer_feeds/infer_fetches); after the same strip->prune->clone the
    ServingEngine applies, every surviving op must have a registered
    lowering (an unregistered op only fails at first compile, long after
    model export) and none may be training-only: an optimizer/grad op
    leaking into a pruned program means prune kept a training subgraph
    alive and every serve call would silently mutate the weights."""
    problems = []
    repo = REPO
    import importlib.util

    from paddle_tpu import io as io_mod
    from paddle_tpu import serving
    from paddle_tpu.framework import unique_name
    from paddle_tpu.ops import registry

    registered = set(registry.registered_ops())
    examples = {
        "transformer_long_context": dict(seqlen=8, vocab=32),
        "criteo_dlrm": dict(rows=64, dim=4, slots=3),
    }
    for name, tiny in sorted(examples.items()):
        path = os.path.join(repo, "examples", "fluid",
                            f"train_{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"_lint_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
            with unique_name.guard():
                progs = mod.build_programs(**tiny)
        except Exception as e:  # noqa: BLE001 - a broken example IS a finding
            problems.append((f"examples.{name}",
                             f"build_programs failed: {e}"))
            continue
        feeds = progs.get("infer_feeds")
        fetches = progs.get("infer_fetches")
        if not feeds or not fetches:
            problems.append((
                f"examples.{name}",
                "build_programs declares no infer_feeds/infer_fetches — "
                "the example has no serving surface"))
            continue
        pruned = (io_mod._strip_training_ops(progs["main"])
                  .prune(feeds, fetches).clone(for_test=True))
        for op in pruned.global_block().ops:
            role = op.desc.attrs.get("op_role")
            if serving.is_training_only_op(op.type, role):
                problems.append((
                    f"examples.{name}",
                    f"training-only op '{op.type}' (role={role!r}) "
                    f"survived the inference prune — serving it would "
                    f"mutate weights per request"))
            if op.type not in registered:
                problems.append((
                    f"examples.{name}",
                    f"pruned inference program contains '{op.type}' with "
                    f"no registered lowering — first serve compile would "
                    f"fail after export"))
    return problems


def check_planner_roles():
    """[(where, msg)] pinning the sharding planner's vocabulary (ISSUE 15
    satellite) — one data x fsdp x tp vocabulary, no drift:

      * every op the classifier tables name (OP_INPUT_ROLES keys,
        TRANSPARENT_OPS, ATTENTION_OPS, HEAD_OPS, MATMUL_OPS) is
        registered — a typo'd op never raises, the rule just silently
        stops matching;
      * SPEC_ROLES == ROLES in both directions: a role the spec table
        distinguishes but no classifier rule produces is dead code, and
        a classifier role the spec table doesn't know silently falls
        into the replicated default;
      * embedding.py agrees with the planner's `embedding` role: its
        SpecLayout IS the planner's class (re-export, not a copy) and
        shard_table's written spec for a default-axes 2-D table matches
        `role_spec("embedding", 2)` — the second vocabulary staying gone.
    """
    from paddle_tpu.ops import registry
    from paddle_tpu.parallel import embedding, planner

    registered = set(registry.registered_ops())
    problems = []

    tables = {
        "planner.OP_INPUT_ROLES":
            sorted({op for (op, _slot) in planner.OP_INPUT_ROLES}),
        "planner.TRANSPARENT_OPS": sorted(planner.TRANSPARENT_OPS),
        "planner.ATTENTION_OPS": sorted(planner.ATTENTION_OPS),
        "planner.HEAD_OPS": sorted(planner.HEAD_OPS),
        "planner.MATMUL_OPS": sorted(planner.MATMUL_OPS),
    }
    for tname in sorted(tables):
        for name in tables[tname]:
            base = name[:-5] if name.endswith("_grad") else name
            if base not in registered:
                problems.append(
                    (tname, f"names op '{name}', which is not registered "
                            f"in ops/registry.py"))

    for role in sorted(planner.SPEC_ROLES - planner.ROLES):
        problems.append(
            ("planner.SPEC_ROLES",
             f"role '{role}' has a spec but no classifier rule produces "
             f"it (not in OP_INPUT_ROLES values or WALK_ROLES)"))
    for role in sorted(planner.ROLES - planner.SPEC_ROLES):
        problems.append(
            ("planner.ROLES",
             f"classifier role '{role}' is missing from SPEC_ROLES — "
             f"role_spec silently replicates it"))

    if embedding.SpecLayout is not planner.SpecLayout:
        problems.append(
            ("embedding.SpecLayout",
             "is not planner.SpecLayout — a second spec vocabulary "
             "crept back"))
    layout = planner.SpecLayout()
    if tuple(layout.embeddings()) != tuple(layout.role_spec("embedding", 2)):
        problems.append(
            ("embedding role",
             f"SpecLayout.embeddings() {layout.embeddings()} != "
             f"role_spec('embedding', 2) "
             f"{layout.role_spec('embedding', 2)}"))
    # shard_table writes what the planner would: synthesize a program
    # with one 2-D table and compare channels
    import paddle_tpu as pd
    from paddle_tpu.framework import unique_name
    with unique_name.guard():
        prog = pd.Program()
        start = pd.Program()
        with pd.program_guard(prog, start):
            import paddle_tpu.layers as pd_layers
            ids = pd_layers.data(name="_lint_ids", shape=[1], dtype="int64")
            pd_layers.embedding(input=ids, size=[16, 4])
        tables = embedding.shard_embeddings(
            prog, mesh=None, layout=layout,
            axis=(layout.fsdp_axis, layout.tensor_axis))
        for t in tables:
            wrote = tuple((prog._param_shardings or {}).get(t) or ())
            want = tuple(layout.role_spec("embedding", 2))
            if wrote != want:
                problems.append(
                    ("embedding.shard_table",
                     f"wrote spec {wrote} for '{t}' but the planner's "
                     f"embedding role says {want}"))
    return problems


def check_metric_names():
    """[(where, message), ...] — pin every telemetry metric family
    created anywhere in paddle_tpu/ against telemetry.METRIC_CATALOG
    (ISSUE 16 satellite), both directions. A mistyped metric name or a
    drifted label set never raises at runtime: the emitter happily
    creates a new family, and the reader (read_gauge / fleet.py /
    dashboards) silently gets None forever. The scan is AST-based
    (literal first arguments to counter()/gauge()/histogram() calls);
    dynamically-named families (the roofline gauge loop, the executor's
    program-attached side-fetch marks, multihost's f-string histograms)
    carry `dynamic=True` catalog entries, which exempts them from the
    needs-an-emitter direction. Reader call sites with literal names
    (read_gauge/read_histogram/read_series/histogram_quantile) are
    checked too: the read helpers return None on a label-set mismatch,
    so a reader asking for labels the emitter doesn't write is exactly
    the silent-drift bug this lint exists to catch."""
    import ast

    from paddle_tpu import telemetry

    catalog = telemetry.METRIC_CATALOG
    problems = []

    def _literal_labels(node):
        """A labels= AST node -> tuple of label names, or None when it
        is not a literal sequence of string constants."""
        if node is None:
            return ()
        if isinstance(node, (ast.Tuple, ast.List)):
            out = []
            for el in node.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value,
                                                               str):
                    out.append(el.value)
                else:
                    return None
            return tuple(out)
        return None

    emitters = {}   # name -> list of (kind, labels-or-None, where)
    readers = []    # (fn, name, label-names-or-None, where)
    read_kinds = {"read_gauge": ("gauge",),
                  "read_histogram": ("histogram",),
                  "histogram_quantile": ("histogram",),
                  "read_series": ("counter", "gauge")}
    for rel, text in sorted(_package_sources().items()):
        try:
            tree = ast.parse(text)
        except SyntaxError as e:
            problems.append((rel, f"unparseable: {e}"))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            attr = (fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else None)
            if attr is None or not node.args:
                continue
            first = node.args[0]
            name = (first.value
                    if isinstance(first, ast.Constant)
                    and isinstance(first.value, str) else None)
            where = f"{rel}:{node.lineno}"
            if attr in ("counter", "gauge", "histogram"):
                if name is None:
                    continue  # dynamic name: catalog covers it
                labels_node = None
                for kw in node.keywords:
                    if kw.arg == "labels":
                        labels_node = kw.value
                if labels_node is None and len(node.args) >= 3:
                    labels_node = node.args[2]
                emitters.setdefault(name, []).append(
                    (attr, _literal_labels(labels_node), where))
            elif attr in read_kinds and name is not None:
                # keyword args on the read helpers ARE label names;
                # a **dynamic expansion (arg=None) is unverifiable
                labelnames = []
                for kw in node.keywords:
                    if kw.arg is None:
                        labelnames = None
                        break
                    labelnames.append(kw.arg)
                readers.append((attr, name,
                                None if labelnames is None
                                else tuple(labelnames), where))

    # direction 1: every literal emitter must match the catalog
    for name in sorted(emitters):
        entry = catalog.get(name)
        for kind, labels, where in emitters[name]:
            if entry is None:
                problems.append((
                    where, f"metric '{name}' ({kind}) is not in "
                           f"telemetry.METRIC_CATALOG — add it or fix "
                           f"the typo"))
                continue
            if kind != entry["kind"]:
                problems.append((
                    where, f"metric '{name}' created as {kind} but "
                           f"cataloged as {entry['kind']}"))
            if labels is not None and set(labels) != set(entry["labels"]):
                problems.append((
                    where, f"metric '{name}' created with labels "
                           f"{sorted(labels)} but cataloged with "
                           f"{sorted(entry['labels'])} — label-set "
                           f"drift"))

    # direction 2: every non-dynamic catalog entry needs an emitter
    for name in sorted(catalog):
        if catalog[name].get("dynamic"):
            continue
        if name not in emitters:
            problems.append((
                "telemetry.METRIC_CATALOG",
                f"'{name}' is cataloged but no counter/gauge/histogram "
                f"call site in paddle_tpu/ creates it — dead entry or "
                f"renamed emitter"))

    # readers: the silent-None direction
    for fn, name, labelnames, where in readers:
        entry = catalog.get(name)
        if entry is None:
            problems.append((
                where, f"{fn}('{name}') reads a metric that is not in "
                       f"the catalog — returns None forever"))
            continue
        if entry["kind"] not in read_kinds[fn]:
            problems.append((
                where, f"{fn}('{name}') reads a {entry['kind']} family "
                       f"— kind mismatch returns None"))
        if fn != "read_series" and labelnames is not None \
                and set(labelnames) != set(entry["labels"]):
            problems.append((
                where, f"{fn}('{name}') passes labels "
                       f"{sorted(labelnames)} but the family is labeled "
                       f"{sorted(entry['labels'])} — the read helper "
                       f"returns None on this mismatch"))
    return problems


def check_alert_rules():
    """[(where, message), ...] — pin sentinel.ALERT_CATALOG against
    telemetry.METRIC_CATALOG (ISSUE 17 satellite), the same
    both-directions discipline as check_metric_names. A rule watching a
    mistyped metric never raises: `Sentinel.poll` reads None forever and
    the rule silently never fires — exactly the drift this catches. Also
    pins the rule schema (direction/severity/reducer vocabularies,
    positive z, non-negative cooldown), that label filters only name
    labels the watched family actually has, and that the alert counter's
    own catalog entry carries exactly the {rule, severity} labels
    `Sentinel._raise` emits."""
    from paddle_tpu import sentinel, telemetry

    catalog = telemetry.METRIC_CATALOG
    problems = []
    for name, rule in sorted(sentinel.ALERT_CATALOG.items()):
        where = f"sentinel.ALERT_CATALOG['{name}']"
        entry = catalog.get(rule["metric"])
        if entry is None:
            problems.append((
                where, f"watches metric '{rule['metric']}' which is not "
                       f"in telemetry.METRIC_CATALOG — the rule can "
                       f"never fire"))
            continue
        if entry["kind"] not in ("gauge", "counter"):
            problems.append((
                where, f"watches a {entry['kind']} family — the sentinel "
                       f"reads gauges/counters only"))
        lf = rule.get("label_filter") or {}
        extra = set(lf) - set(entry["labels"])
        if extra:
            problems.append((
                where, f"label filter names {sorted(extra)} but "
                       f"'{rule['metric']}' is labeled "
                       f"{sorted(entry['labels'])} — the filter would "
                       f"drop every sample"))
        if lf and not entry["labels"]:
            problems.append((
                where, f"label filter on unlabeled family "
                       f"'{rule['metric']}'"))
        if rule["direction"] not in sentinel.DIRECTIONS:
            problems.append((
                where, f"direction '{rule['direction']}' not in "
                       f"{sentinel.DIRECTIONS}"))
        if rule["severity"] not in sentinel.SEVERITIES:
            problems.append((
                where, f"severity '{rule['severity']}' not in "
                       f"{sentinel.SEVERITIES}"))
        if rule.get("reduce") not in sentinel.REDUCERS:
            problems.append((
                where, f"reducer '{rule.get('reduce')}' not in "
                       f"{sentinel.REDUCERS}"))
        if not rule["z"] > 0:
            problems.append((where, f"z threshold {rule['z']} must be "
                                    f"positive"))
        if rule["cooldown_s"] < 0:
            problems.append((where, "negative cooldown"))

    # the emitter side: the ledger's counter must be cataloged with
    # exactly the labels Sentinel._raise sets (rule, severity) — the
    # call-site/catalog match itself is check_metric_names' job
    alerts_entry = catalog.get("sentinel_alerts_total")
    if alerts_entry is None:
        problems.append((
            "telemetry.METRIC_CATALOG",
            "'sentinel_alerts_total' missing — sentinel alerts would "
            "mint an uncataloged family"))
    elif set(alerts_entry["labels"]) != {"rule", "severity"}:
        problems.append((
            "telemetry.METRIC_CATALOG",
            f"'sentinel_alerts_total' labeled "
            f"{sorted(alerts_entry['labels'])} but the sentinel emits "
            f"{{rule, severity}}"))
    return problems


def check_dynamics_rules():
    """[(where, message), ...] — pin the training-dynamics observatory
    (ISSUE 19 satellite) in both directions:

    * every health code a classification site emits (literal argument to
      dynamics._code(...)) exists in dynamics.HEALTH_CATALOG, and every
      cataloged code has at least one emit site — a stable code the docs
      and dashboards key on can't silently vanish or be minted ad hoc;
    * every dynamics_* metric the observatory emits is in
      telemetry.METRIC_CATALOG and vice versa (the catalog's dynamics_*
      slice has no dead entries) — the emit-site/catalog match itself is
      check_metric_names' job;
    * the dynamics_* sentinel rules exist, watch cataloged dynamics_*
      families, and every dynamics_* ALERT_CATALOG rule resolves — a
      renamed gauge can't orphan the pager."""
    import ast
    import os

    from paddle_tpu import dynamics, sentinel, telemetry

    problems = []
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "paddle_tpu", "dynamics.py")
    rel = os.path.join("paddle_tpu", "dynamics.py")
    with open(path) as f:
        tree = ast.parse(f.read())

    emitted_codes = {}   # code -> first where
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        attr = (fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None)
        if attr != "_code" or not node.args:
            continue
        first = node.args[0]
        where = f"{rel}:{node.lineno}"
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            problems.append((
                where, "_code() called with a non-literal health code — "
                       "the catalog lint cannot pin it"))
            continue
        emitted_codes.setdefault(first.value, where)

    for code, where in sorted(emitted_codes.items()):
        if code not in dynamics.HEALTH_CATALOG:
            problems.append((
                where, f"health code '{code}' is not in "
                       f"dynamics.HEALTH_CATALOG — add it or fix the "
                       f"typo"))
    for code in sorted(dynamics.HEALTH_CATALOG):
        if code not in emitted_codes:
            problems.append((
                "dynamics.HEALTH_CATALOG",
                f"'{code}' is cataloged but no _code() site in "
                f"dynamics.py emits it — dead entry or renamed code"))

    # dynamics_* metric slice, both directions (emitter literals in
    # dynamics.py vs the METRIC_CATALOG dynamics_* entries)
    emitted_metrics = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        attr = (fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None)
        if attr not in ("counter", "gauge", "histogram") or not node.args:
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            emitted_metrics.add(first.value)
    cataloged = {n for n in telemetry.METRIC_CATALOG
                 if n.startswith("dynamics_")}
    for n in sorted(emitted_metrics - cataloged):
        problems.append((
            rel, f"dynamics emits metric '{n}' with no dynamics_* "
                 f"METRIC_CATALOG entry"))
    for n in sorted(cataloged - emitted_metrics):
        problems.append((
            "telemetry.METRIC_CATALOG",
            f"'{n}' is cataloged but dynamics.py never emits it — dead "
            f"entry or renamed gauge"))

    # the sentinel slice: the observatory's pager rules must exist and
    # resolve against cataloged dynamics_* families
    dyn_rules = {n: r for n, r in sentinel.ALERT_CATALOG.items()
                 if n.startswith("dynamics_")}
    for expect in ("dynamics_update_ratio_spike", "dynamics_dead_layer"):
        if expect not in dyn_rules:
            problems.append((
                "sentinel.ALERT_CATALOG",
                f"'{expect}' rule missing — the observatory has no pager "
                f"for this failure mode"))
    for name, rule in sorted(dyn_rules.items()):
        if rule["metric"] not in cataloged:
            problems.append((
                f"sentinel.ALERT_CATALOG['{name}']",
                f"watches '{rule['metric']}' which is not a cataloged "
                f"dynamics_* family — the rule can never fire"))
    return problems


def check_thread_catalog():
    """[(where, message), ...] — pin analysis/threads.THREAD_CATALOG
    against the actual `threading.Thread`/`go()` creation sites in
    paddle_tpu/ in both directions (ISSUE 18 satellite). An uncataloged
    thread has no declared lifetime discipline (daemon? joined by its
    owner?) and renders anonymously in sentinel hang reports; a stale
    catalog entry documents a thread that no longer exists. Declared
    daemon/joined flags are also checked against what the census can
    prove at each site, so the catalog can't quietly drift into
    documenting the wrong shutdown contract."""
    from paddle_tpu.analysis import threads

    return threads.catalog_problems()


def main():
    problems = check_tables()
    for tname, name in problems:
        print(f"{tname}: '{name}' is not registered in ops/registry.py")
    coll = check_collective_kinds()
    for where, msg in coll:
        print(f"{where}: {msg}")
    jit = check_jit_sites() + check_one_parse()
    for where, msg in jit:
        print(f"{where}: {msg}")
    sparse = check_sparse_table()
    for where, msg in sparse:
        print(f"{where}: {msg}")
    embc = check_emb_cache()
    for where, msg in embc:
        print(f"{where}: {msg}")
    pallas = check_pallas_table()
    for where, msg in pallas:
        print(f"{where}: {msg}")
    quantp = check_quant_table()
    for where, msg in quantp:
        print(f"{where}: {msg}")
    inferp = check_infer_rules()
    for where, msg in inferp:
        print(f"{where}: {msg}")
    servp = check_serving_programs()
    for where, msg in servp:
        print(f"{where}: {msg}")
    plroles = check_planner_roles()
    for where, msg in plroles:
        print(f"{where}: {msg}")
    metrics = check_metric_names()
    for where, msg in metrics:
        print(f"{where}: {msg}")
    alerts = check_alert_rules()
    for where, msg in alerts:
        print(f"{where}: {msg}")
    thrc = check_thread_catalog()
    for where, msg in thrc:
        print(f"{where}: {msg}")
    dynp = check_dynamics_rules()
    for where, msg in dynp:
        print(f"{where}: {msg}")
    problems = problems + coll + jit + sparse + embc + pallas + quantp \
        + inferp + servp + plroles + metrics + alerts + thrc + dynp
    if problems:
        print(f"{len(problems)} lint problem"
              f"{'' if len(problems) == 1 else 's'}")
        return 1
    print("registry lint ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
