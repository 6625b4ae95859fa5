"""Registry-consistency lint as a tier-1 gate (ISSUE 7 satellite): a
typo in layout.AGNOSTIC_OPS/AWARE_OPS or the fusion pattern tables
doesn't raise — the pattern just never matches and the optimization
silently turns off. tools/check_registry.py pins every table entry
against ops/registry.py; this test runs it both in-process (precise
assertion message) and as the CLI (the CI entry point)."""

import importlib.util
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_registry", os.path.join(REPO, "tools", "check_registry.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tables_registered():
    problems = _load_checker().check_tables()
    assert not problems, (
        "optimization tables name unregistered ops: "
        + ", ".join(f"{t}:{n}" for t, n in problems))


def test_tables_nonempty():
    """The lint is vacuous if an import regression empties a table."""
    from paddle_tpu.ops import fusion, layout

    assert layout.AWARE_OPS and layout.AGNOSTIC_OPS
    assert fusion.CONV_OPS and fusion.ACT_OPS and fusion.CHAIN_OPS
    assert fusion.OPTIMIZER_BUCKET_OPS and fusion.FUSED_OP_TYPES


def test_jit_sites_consolidated():
    """ISSUE 9 satellite: executor.py keeps exactly ONE direct jit call
    site (Executor._jit_compile), where the overlap pass's
    compiler_options are threaded into both compile paths. A second
    site — or a helper that stops threading the options — trips the
    lint before it silently ships unscheduled compiles."""
    problems = _load_checker().check_jit_sites()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_jit_lint_reads_real_source():
    """The lint is vacuous if it stops seeing the executor module: pin
    that the counted source actually contains the helper it checks."""
    import inspect

    from paddle_tpu import executor

    src = inspect.getsource(executor)
    assert "_jit_compile" in src and src.count("jax.jit(") == 1


def test_one_parse_of_compiled_text_and_one_reader_of_a_trace():
    """ISSUE 35: no regex over HLO text and no walk of the xplane wire
    format outside paddle_tpu/xplane.py; the replaced readers are gone."""
    assert _load_checker().check_one_parse() == []


def test_one_parse_lint_catches_a_second_reader(tmp_path):
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir()
    (pkg / "xplane.py").write_text("A = 'replica_groups'\n")   # its home
    (pkg / "fleet.py").write_text(
        "import re\nG = re.compile(r'replica_groups=')\n"
        "def f(x):\n    return xplane.aggregate_dir(x)\n")
    (pkg / "tool.py").write_text("for f in xplane.fields(b''):\n    pass\n")
    found = _load_checker().check_one_parse(str(pkg))
    assert {w for w, _ in found} == {"paddle_tpu/fleet.py",
                                    "paddle_tpu/tool.py"}
    assert any("removed reader 'aggregate_dir'" in m for _, m in found)
    assert any("reads compiled text" in m for _, m in found)
    assert any("wire format" in m for _, m in found)


def test_sparse_table_consistent():
    """ISSUE 10 satellite: SPARSE_APPLY_OPS, the optimizer lowerings'
    SelectedRows branches, executor._SPARSE_AWARE_OPS and the
    fused_sparse_ bucket types must all agree — a gap in any of them
    silently densifies the gradient instead of failing."""
    problems = _load_checker().check_sparse_table()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_sparse_lint_catches_missing_entry(monkeypatch):
    """Sanity: dropping an op from SPARSE_APPLY_OPS trips the converse
    check (its _apply kernel still exists but would never run)."""
    from paddle_tpu.ops import sparse_ops

    checker = _load_checker()
    monkeypatch.setattr(sparse_ops, "SPARSE_APPLY_OPS",
                        ("sgd", "momentum"))
    problems = checker.check_sparse_table()
    assert any("adam" in m for _, m in problems), problems


def test_pallas_table_consistent():
    """pallas_conv.KERNELS must agree with the op registry and
    quant.QUANT_OPS (since PR 25 the one conv dispatch is the int8 conv
    under O3, and an orphan entry or a missing one doesn't raise, the
    route is just never audited), kernel_choice.REASONS with the source
    of every op's gate, and kernel_choice must be the only creator of
    the two counters (tests/test_kernel_choice.py takes the table op by
    op)."""
    problems = _load_checker().check_pallas_table()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_pallas_lint_catches_grad_entry_and_missing_op(monkeypatch):
    """Sanity: a conv2d_grad entry (no backward kernel is dispatched:
    conv2d_grad transposes the lax conv), a quantizable conv op dropped
    from KERNELS, and a shrunk table of the conv gate's reasons each
    trip the lint."""
    from paddle_tpu.ops import kernel_choice, pallas_conv

    checker = _load_checker()
    orig = pallas_conv.KERNELS
    monkeypatch.setattr(
        pallas_conv, "KERNELS",
        dict(orig, conv2d_grad=(pallas_conv.conv2d_q8,)))
    problems = checker.check_pallas_table()
    assert any("conv2d_grad" in m for _, m in problems), problems

    kernels = dict(orig)
    del kernels["depthwise_conv2d"]
    monkeypatch.setattr(pallas_conv, "KERNELS", kernels)
    problems = checker.check_pallas_table()
    assert any("depthwise_conv2d" in m for _, m in problems), problems

    monkeypatch.setattr(pallas_conv, "KERNELS", orig)
    monkeypatch.setitem(kernel_choice.REASONS, "conv2d",
                        kernel_choice.REASONS["conv2d"] - {"geometry"})
    problems = checker.check_pallas_table()
    assert any("geometry" in m for _, m in problems), problems


def test_quant_table_consistent():
    """ISSUE 20 satellite: quant.QUANT_OPS must agree with the op
    registry, the lowering sources (each table entry's lowering consults
    the quant gate — directly or one delegation deep) and
    quant.FALLBACK_REASONS. A gap doesn't raise: the op just silently
    serves at full precision under O3, or a fallback reason ships as an
    unlabelled counter series."""
    problems = _load_checker().check_quant_table()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_quant_table_nonempty():
    """The lint is vacuous if an import regression empties the table."""
    from paddle_tpu import quant

    assert quant.QUANT_OPS and quant.FALLBACK_REASONS
    assert {"mul", "matmul", "conv2d"} <= set(quant.QUANT_OPS)


def test_quant_lint_catches_defects(monkeypatch):
    """Sanity, all four directions: an unregistered table entry, a table
    entry whose lowering never routes through quant, a bogus entry-point
    name, and a declared-but-never-produced fallback reason."""
    from paddle_tpu import quant

    checker = _load_checker()
    orig = quant.QUANT_OPS

    monkeypatch.setattr(quant, "QUANT_OPS",
                        {**orig, "phantom_matmul": "qmatmul"})
    problems = checker.check_quant_table()
    assert any("phantom_matmul" in m and "not registered" in m
               for _, m in problems), problems

    # relu is registered but its lowering never consults the quant gate
    monkeypatch.setattr(quant, "QUANT_OPS", {**orig, "relu": "qmatmul"})
    problems = checker.check_quant_table()
    assert any("relu" in m and "never consults" in m
               for _, m in problems), problems

    monkeypatch.setattr(quant, "QUANT_OPS", {**orig, "mul": "qphantom"})
    problems = checker.check_quant_table()
    assert any("qphantom" in m for _, m in problems), problems

    monkeypatch.setattr(quant, "QUANT_OPS", orig)
    monkeypatch.setattr(quant, "FALLBACK_REASONS",
                        quant.FALLBACK_REASONS | {"phase_of_moon"})
    problems = checker.check_quant_table()
    assert any("phase_of_moon" in m and "never produced" in m
               for _, m in problems), problems


def test_quant_lint_catches_missing_table_entry(monkeypatch):
    """Converse direction: a lowering that routes through quant whose op
    type is dropped from QUANT_OPS (prequantize/preflight/roofline
    would be blind to it)."""
    from paddle_tpu import quant

    checker = _load_checker()
    trimmed = {k: v for k, v in quant.QUANT_OPS.items() if k != "matmul"}
    monkeypatch.setattr(quant, "QUANT_OPS", trimmed)
    problems = checker.check_quant_table()
    assert any("'matmul'" in m and "not" in m and "QUANT_OPS" in m
               for _, m in problems), problems


def test_infer_rules_cover_registry():
    """ISSUE 12 satellite: every registered op resolves to exactly one
    shape-rule source in analysis/infer.py (checker, registry
    infer_shape, eval-shape probe, or the dynamic allowlist). An
    uncovered op makes the shapes pass silently mark everything
    downstream unknown."""
    problems = _load_checker().check_infer_rules()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_infer_lint_catches_uncovered_op(monkeypatch):
    """Sanity: registering an op with no infer rule trips the coverage
    direction of the lint."""
    from paddle_tpu.ops import registry

    checker = _load_checker()
    orig = registry.registered_ops

    def with_phantom():
        return list(orig()) + ["definitely_uncovered_op"]

    monkeypatch.setattr(registry, "registered_ops", with_phantom)
    problems = checker.check_infer_rules()
    assert any("definitely_uncovered_op" in m and "no shape rule" in m
               for _, m in problems), problems


def test_infer_lint_catches_orphan_and_overlap(monkeypatch):
    """Sanity: a table entry for an unregistered op is an orphan, and
    the same op in two tables trips the precedence check."""
    from paddle_tpu.analysis import infer

    checker = _load_checker()
    monkeypatch.setattr(
        infer, "DYNAMIC_SHAPE_OPS",
        infer.DYNAMIC_SHAPE_OPS | {"definitely_not_an_op"})
    problems = checker.check_infer_rules()
    assert any("definitely_not_an_op" in m and "orphan" in m
               for _, m in problems), problems

    overlap_op = next(iter(infer.EVAL_SHAPE_OPS))
    monkeypatch.setattr(
        infer, "DYNAMIC_SHAPE_OPS",
        infer.DYNAMIC_SHAPE_OPS | {overlap_op})
    problems = checker.check_infer_rules()
    assert any(overlap_op in m and "precedence" in m
               for _, m in problems), problems


def test_emb_cache_table_consistent():
    """ISSUE 14 satellite: emb_cache.CACHE_AWARE_OPS must stay exactly
    the lookup pair plus the SPARSE_APPLY_OPS scatter family, and every
    member must be sparse-aware in the executor — drift in either
    direction corrupts silently (enable() rejecting valid optimizers,
    or a densified grad overwriting stale slot tenants)."""
    problems = _load_checker().check_emb_cache()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_emb_cache_lint_catches_drift(monkeypatch):
    """Sanity both ways: an extra CACHE_AWARE_OPS member with no remap
    semantics trips the converse audit; a shrunken set trips the
    missing-scatter-op direction."""
    from paddle_tpu.parallel import emb_cache

    checker = _load_checker()
    orig = emb_cache.CACHE_AWARE_OPS
    monkeypatch.setattr(emb_cache, "CACHE_AWARE_OPS",
                        orig | {"matmul"})
    problems = checker.check_emb_cache()
    assert any("'matmul'" in m and "slot-remap" in m
               for _, m in problems), problems

    monkeypatch.setattr(emb_cache, "CACHE_AWARE_OPS", orig - {"adam"})
    problems = checker.check_emb_cache()
    assert any("'adam' missing" in m for _, m in problems), problems


def test_serving_programs_clean():
    """ISSUE 13 satellite: both shipped inference programs (transformer
    logits, DLRM probabilities), after the ServingEngine's own
    strip->prune->clone, contain only registered, non-training ops. A
    grad/optimizer op leaking through prune means serving would mutate
    weights per request; an unregistered op means the first serve
    compile fails long after export."""
    problems = _load_checker().check_serving_programs()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_serving_lint_catches_training_op(monkeypatch):
    """Sanity: widening the training-only set so a benign forward op
    (softmax) counts as training-only must trip the lint on the DLRM
    program — proving the checker actually walks the pruned ops."""
    from paddle_tpu import serving

    checker = _load_checker()
    orig = serving.is_training_only_op
    monkeypatch.setattr(
        serving, "is_training_only_op",
        lambda op_type, op_role=None: (op_type == "softmax"
                                       or orig(op_type, op_role)))
    problems = checker.check_serving_programs()
    assert any("training-only op 'softmax'" in m for _, m in problems), (
        problems)


def test_serving_lint_catches_unregistered_op(monkeypatch):
    """Sanity: hiding a core op from the registry trips the
    no-registered-lowering direction."""
    from paddle_tpu.ops import registry

    checker = _load_checker()
    orig = registry.registered_ops

    def without_softmax():
        return [t for t in orig() if t != "softmax"]

    monkeypatch.setattr(registry, "registered_ops", without_softmax)
    problems = checker.check_serving_programs()
    assert any("'softmax'" in m and "no registered lowering" in m
               for _, m in problems), problems


def test_planner_roles_consistent():
    """ISSUE 15 satellite: the sharding planner's vocabulary stays one
    vocabulary — every classifier-table op registered, SPEC_ROLES ==
    producible ROLES in both directions, and embedding.py's table specs
    agreeing with the planner's `embedding` role (SpecLayout identity +
    shard_table writing role_spec('embedding', 2))."""
    problems = _load_checker().check_planner_roles()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_planner_lint_catches_drift(monkeypatch):
    """Sanity in three directions: an unregistered op in a classifier
    table, a spec-table role no rule produces, and a producible role the
    spec table doesn't know."""
    from paddle_tpu.parallel import planner

    checker = _load_checker()
    orig_transparent = planner.TRANSPARENT_OPS
    monkeypatch.setattr(
        planner, "TRANSPARENT_OPS",
        orig_transparent | {"definitely_not_an_op"})
    problems = checker.check_planner_roles()
    assert any("definitely_not_an_op" in m for _, m in problems), problems

    monkeypatch.setattr(planner, "TRANSPARENT_OPS", orig_transparent)
    monkeypatch.setattr(planner, "SPEC_ROLES",
                        planner.SPEC_ROLES | {"bogus_role"})
    problems = checker.check_planner_roles()
    assert any("bogus_role" in m and "no classifier rule" in m
               for _, m in problems), problems

    monkeypatch.setattr(planner, "SPEC_ROLES",
                        planner.SPEC_ROLES - {"bogus_role", "ffn_down"})
    problems = checker.check_planner_roles()
    assert any("ffn_down" in m and "SPEC_ROLES" in m
               for _, m in problems), problems


def test_planner_lint_catches_embedding_divergence(monkeypatch):
    """Sanity: an embedding.py table spec diverging from the planner's
    embedding role (the second-vocabulary regression) trips the lint."""
    from paddle_tpu.parallel import embedding, planner

    checker = _load_checker()
    monkeypatch.setattr(
        planner.SpecLayout, "embeddings",
        lambda self: (self.fsdp_axis, None))
    problems = checker.check_planner_roles()
    assert any("embedding" in w for w, _ in problems), problems


def test_cli_passes():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_registry.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr[-1500:]
    assert "registry lint ok" in r.stdout


def test_cli_catches_typo():
    """Sanity: the checker actually reports a bogus table entry."""
    from paddle_tpu.ops import layout

    checker = _load_checker()
    layout.AGNOSTIC_OPS.add("definitely_not_an_op")
    try:
        problems = checker.check_tables()
    finally:
        layout.AGNOSTIC_OPS.discard("definitely_not_an_op")
    assert ("layout.AGNOSTIC_OPS", "definitely_not_an_op") in problems


def test_metric_names_consistent():
    """ISSUE 16 satellite: every telemetry family created anywhere in
    paddle_tpu/ must match telemetry.METRIC_CATALOG in name, kind, and
    label set — and every cataloged non-dynamic entry must still have an
    emitter. Either direction drifting means a dashboard/reader silently
    gets None."""
    problems = _load_checker().check_metric_names()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_metric_lint_catches_uncataloged_emitter(monkeypatch):
    """Sanity (and proof the AST scan is non-vacuous): dropping a real
    emitter's catalog entry trips the unknown-metric direction at its
    actual call site."""
    from paddle_tpu import telemetry

    checker = _load_checker()
    monkeypatch.delitem(telemetry.METRIC_CATALOG, "serving_shed_total")
    problems = checker.check_metric_names()
    assert any("serving_shed_total" in m and "not in" in m
               for _, m in problems), problems
    assert any(w.startswith("paddle_tpu") for w, m in problems
               if "serving_shed_total" in m)


def test_metric_lint_catches_kind_and_label_drift(monkeypatch):
    from paddle_tpu import telemetry

    checker = _load_checker()
    orig = telemetry.METRIC_CATALOG["serving_shed_total"]
    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "serving_shed_total",
        dict(orig, kind="gauge"))
    problems = checker.check_metric_names()
    assert any("created as counter" in m and "cataloged as gauge" in m
               for _, m in problems), problems

    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "serving_shed_total",
        dict(orig, labels=("program", "reason", "phantom")))
    problems = checker.check_metric_names()
    assert any("serving_shed_total" in m and "label-set drift" in m
               for _, m in problems), problems


def test_metric_lint_catches_dead_catalog_entry(monkeypatch):
    from paddle_tpu import telemetry

    checker = _load_checker()
    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "phantom_metric_total",
        {"kind": "counter", "labels": (), "help": "", "dynamic": False})
    problems = checker.check_metric_names()
    assert any("phantom_metric_total" in m and "no counter" in m
               for _, m in problems), problems


def test_alert_rules_consistent():
    """ISSUE 17 satellite: every sentinel.ALERT_CATALOG rule must watch
    a cataloged telemetry metric with a compatible label set, keep its
    schema inside the sentinel's vocabularies, and the alert counter's
    own catalog entry must carry exactly {rule, severity} — either
    direction drifting means a rule that silently never fires."""
    problems = _load_checker().check_alert_rules()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_alert_lint_catches_bogus_metric(monkeypatch):
    """Sanity: a rule watching a metric the catalog doesn't know trips
    the can-never-fire direction at the rule's name."""
    from paddle_tpu import sentinel

    checker = _load_checker()
    monkeypatch.setitem(
        sentinel.ALERT_CATALOG, "phantom_rule",
        dict(sentinel.ALERT_CATALOG["loss_spike"],
             metric="definitely_not_a_metric"))
    problems = checker.check_alert_rules()
    assert any("phantom_rule" in w and "never fire" in m
               for w, m in problems), problems


def test_alert_lint_catches_phantom_label_filter(monkeypatch):
    """Sanity: a label filter naming a label the watched family doesn't
    have would drop every sample — the lint must see it."""
    from paddle_tpu import sentinel

    checker = _load_checker()
    monkeypatch.setitem(
        sentinel.ALERT_CATALOG, "slo_fast_burn",
        dict(sentinel.ALERT_CATALOG["slo_fast_burn"],
             label_filter={"phantom": "x"}))
    problems = checker.check_alert_rules()
    assert any("slo_fast_burn" in w and "phantom" in m
               for w, m in problems), problems


def test_alert_lint_catches_schema_drift(monkeypatch):
    """Sanity: direction/severity/reducer outside the vocabularies and
    a drifted sentinel_alerts_total label set all trip."""
    from paddle_tpu import sentinel, telemetry

    checker = _load_checker()
    monkeypatch.setitem(
        sentinel.ALERT_CATALOG, "loss_spike",
        dict(sentinel.ALERT_CATALOG["loss_spike"], direction="sideways"))
    problems = checker.check_alert_rules()
    assert any("sideways" in m for _, m in problems), problems

    monkeypatch.setitem(
        sentinel.ALERT_CATALOG, "loss_spike",
        dict(sentinel.ALERT_CATALOG["loss_spike"], direction="high"))
    orig = telemetry.METRIC_CATALOG["sentinel_alerts_total"]
    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "sentinel_alerts_total",
        dict(orig, labels=("rule",)))
    problems = checker.check_alert_rules()
    assert any("sentinel_alerts_total" in m and "severity" in m
               for _, m in problems), problems


def test_metric_lint_catches_reader_label_drift(monkeypatch):
    """A reader passing a label set the emitter doesn't write is the
    silent-None bug: read_gauge call sites must match the catalog."""
    from paddle_tpu import telemetry

    checker = _load_checker()
    orig = telemetry.METRIC_CATALOG["executor_last_step_seconds"]
    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "executor_last_step_seconds",
        dict(orig, labels=("phantom",)))
    problems = checker.check_metric_names()
    assert any("read" in m and "None" in m
               and "executor_last_step_seconds" in m
               for _, m in problems) or \
        any("executor_last_step_seconds" in m and "drift" in m
            for _, m in problems), problems


def test_thread_catalog_consistent():
    """ISSUE 18 satellite: every Thread/go creation site in paddle_tpu/
    matches a THREAD_CATALOG entry and every entry matches a site, with
    daemon/joined declarations pinned to what the source actually does."""
    problems = _load_checker().check_thread_catalog()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_thread_lint_catches_uncataloged_site(monkeypatch):
    """Deleting a catalog entry must surface its creation site as
    undeclared — new background threads can't ship uncensused."""
    from paddle_tpu.analysis import threads

    checker = _load_checker()
    monkeypatch.delitem(threads.THREAD_CATALOG, "serving-batcher")
    problems = checker.check_thread_catalog()
    assert any("batcher.py" in w and "not declared" in m
               for w, m in problems), problems


def test_thread_lint_catches_stale_entry(monkeypatch):
    """A catalog entry whose creation site no longer exists is stale
    documentation; the lint must flag it for removal."""
    from paddle_tpu.analysis import threads

    checker = _load_checker()
    monkeypatch.setitem(
        threads.THREAD_CATALOG, "pd-phantom-",
        dict(module="paddle_tpu/phantom.py", prefix=True, daemon=True,
             joined=False, help="never created"))
    problems = checker.check_thread_catalog()
    assert any("pd-phantom-" in w and "no matching" in m
               for w, m in problems), problems


def test_thread_lint_catches_daemon_and_join_drift(monkeypatch):
    """Flipping declared daemon-ness or claiming a join that doesn't
    exist must both trip: the catalog documents lifetime contracts."""
    from paddle_tpu.analysis import threads

    checker = _load_checker()
    monkeypatch.setitem(
        threads.THREAD_CATALOG, "serving-batcher",
        dict(threads.THREAD_CATALOG["serving-batcher"], daemon=False))
    problems = checker.check_thread_catalog()
    assert any("daemon" in m and "serving-batcher" in m
               for _, m in problems), problems

    monkeypatch.setitem(
        threads.THREAD_CATALOG, "serving-batcher",
        dict(threads.THREAD_CATALOG["serving-batcher"], daemon=True))
    monkeypatch.setitem(
        threads.THREAD_CATALOG, "pd-reader-buffered",
        dict(threads.THREAD_CATALOG["pd-reader-buffered"], joined=True))
    problems = checker.check_thread_catalog()
    assert any("joined=True" in m and "no join site" in m
               for _, m in problems), problems


def test_dynamics_rules_consistent():
    """ISSUE 19 satellite: health codes emitted by dynamics._code sites
    match HEALTH_CATALOG both ways, the dynamics_* METRIC_CATALOG slice
    has no dead entries, and the observatory's sentinel rules exist and
    watch cataloged dynamics_* families."""
    problems = _load_checker().check_dynamics_rules()
    assert not problems, "; ".join(f"{w}: {m}" for w, m in problems)


def test_dynamics_lint_catches_uncataloged_code(monkeypatch):
    """Deleting a health code from the catalog must surface its emit
    site — verdict codes are a stable vocabulary, not ad-hoc strings."""
    from paddle_tpu import dynamics

    checker = _load_checker()
    monkeypatch.delitem(dynamics.HEALTH_CATALOG, "dead-layer")
    problems = checker.check_dynamics_rules()
    assert any("dead-layer" in m and "HEALTH_CATALOG" in m
               for _, m in problems), problems


def test_dynamics_lint_catches_dead_catalog_metric(monkeypatch):
    """A dynamics_* catalog entry nothing emits is stale documentation;
    and dropping a gauge the sentinel rules watch orphans the pager."""
    from paddle_tpu import telemetry

    checker = _load_checker()
    monkeypatch.setitem(
        telemetry.METRIC_CATALOG, "dynamics_phantom_gauge",
        telemetry.METRIC_CATALOG["dynamics_grad_rms"])
    problems = checker.check_dynamics_rules()
    assert any("dynamics_phantom_gauge" in m and "never emits" in m
               for _, m in problems), problems

    monkeypatch.delitem(telemetry.METRIC_CATALOG, "dynamics_phantom_gauge")
    monkeypatch.delitem(telemetry.METRIC_CATALOG, "dynamics_dead_layers")
    problems = checker.check_dynamics_rules()
    assert any("dynamics_dead_layer" in w and "can never fire" in m
               for w, m in problems), problems
