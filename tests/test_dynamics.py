"""Training-dynamics observatory (ISSUE 19): the fused on-device
parameter/gradient health reduction and its host-side verdict layer.

The acceptance properties pinned here: turning the observatory on does
not perturb training numerics AT ALL (bitwise parity of final weights,
stats on vs off — the reduction is appended to the traced step, never
inserted into it); the run_steps scan samples exactly one row per
period boundary (no per-step host sync); the verdict layer classifies
synthetic time-series into the stable health codes dashboards key on
(dead-layer, frozen-param, exploding-update, nonfinite); GradientAudit's
thresholds come from the SAME constants table (single source of truth,
ISSUE 19 satellite); and /dynamics answers over real HTTP with the
payload schema the CLI and dashboards consume."""

import http.client
import json
import math
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import dynamics, obs_server, telemetry
from paddle_tpu import executor as executor_mod
from paddle_tpu.framework import unique_name


@pytest.fixture(autouse=True)
def _fresh_dynamics_state():
    telemetry.reset()
    dynamics.reset()
    yield
    obs_server.stop()
    telemetry.reset()
    dynamics.reset()


def _build_program(seed=7, minimize=None):
    """`minimize(loss, startup)` appends the optimizer (Momentum if None)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            if minimize is None:
                fluid.optimizer.Momentum(
                    learning_rate=0.01, momentum=0.9).minimize(
                        loss, startup_program=startup)
            else:
                minimize(loss, startup)
    return main, startup, loss


def _batches(n, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        xb = rng.rand(batch, 4).astype(np.float32)
        yb = (xb.sum(axis=1, keepdims=True) * 0.5).astype(np.float32)
        out.append({"x": xb, "y": yb})
    return out


def _param_names(main):
    return sorted(p.name for p in main.global_block().all_parameters())


def _train(steps, *, dyn_enabled, period=1):
    """Fresh program + scope, `steps` per-step runs; -> {param: ndarray}."""
    main, startup, loss = _build_program()
    feeds = _batches(steps)
    scope = executor_mod.Scope()
    with dynamics.override(dyn_enabled, period):
        with executor_mod.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for feed in feeds:
                exe.run(main, feed=feed, fetch_list=[loss.name])
            return {n: np.array(scope.find_var(n))
                    for n in _param_names(main)}


def test_bitwise_parity_stats_on_vs_off():
    """The fused reduction reads the step's values; it must never feed
    back into them. Same seed, same batches: final weights are bitwise
    identical with the observatory off and sampling every step."""
    base = _train(5, dyn_enabled=False)
    dynamics.reset()
    telemetry.reset()
    observed = _train(5, dyn_enabled=True, period=1)
    assert base.keys() == observed.keys()
    for name in base:
        assert np.array_equal(base[name], observed[name]), (
            f"{name} diverged with dynamics enabled")
    # and the observed run actually sampled (the parity is not vacuous)
    assert dynamics.payload()["samples_recorded"] >= 5


def test_per_step_sampling_respects_period():
    """period=2: the startup run advances the counter to 1, so steps
    commit counters 2..7 and exactly 2|counter samples land."""
    with dynamics.override(True, 2):
        main, startup, loss = _build_program()
        scope = executor_mod.Scope()
        with executor_mod.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for feed in _batches(6):
                exe.run(main, feed=feed, fetch_list=[loss.name])
    assert dynamics.payload()["samples_recorded"] == 3


def test_run_steps_window_samples_period_boundaries():
    """The scan stacks a [K, G, 8] row block on-device; the host unpack
    must record exactly one sample per period boundary inside the
    window — here counters 2..9 with period 4 hit 4 and 8."""
    with dynamics.override(True, 4):
        main, startup, loss = _build_program()
        scope = executor_mod.Scope()
        with executor_mod.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run_steps(main, feed_window=_batches(8),
                          fetch_list=[loss.name])
    assert dynamics.payload()["samples_recorded"] == 2
    # both samples belong to every series' ring (one program, 4 params)
    progs = dynamics.payload()["programs"]
    assert len(progs) == 1
    for series in next(iter(progs.values()))["series"].values():
        assert series["samples"] == 2


# -- where the update ratio's numerator comes from ----------------------------

_RULES = {
    "sgd": lambda lr: fluid.optimizer.SGD(learning_rate=lr),
    "momentum": lambda lr: fluid.optimizer.Momentum(
        learning_rate=lr, momentum=0.9),
    "nesterov": lambda lr: fluid.optimizer.Momentum(
        learning_rate=lr, momentum=0.9, use_nesterov=True),
    "adam": lambda lr: fluid.optimizer.Adam(learning_rate=lr),
    # the rule's Grad is not the raw gradient, and its LearningRate is
    # written by ops of the block ahead of it
    "sgd_clip_decay_schedule": lambda lr: fluid.optimizer.SGD(
        learning_rate=fluid.layers.exponential_decay(lr, 2, 0.5),
        regularization=fluid.regularizer.L2Decay(0.1)),
    # no step sibling: the difference of the parameter's values stays
    "adagrad": lambda lr: fluid.optimizer.Adagrad(learning_rate=lr),
}


def _update_norm_sources():
    out = {"step": 0, "difference": 0}
    for key, v in telemetry.read_series("dynamics_update_norm_total").items():
        out[dict(kv.split("=") for kv in key.split(","))["source"]] += int(v)
    return out


def _observed_steps(rule, window, steps=3):
    """`steps` steps under `rule` sampling every one: the weights before
    the first and after each ([steps + 1] dicts), each step's table
    ([steps] of {series: {field: value}}) and the update-norm counter."""
    telemetry.reset()
    dynamics.reset()
    def minimize(loss, startup):
        if rule == "sgd_clip_decay_schedule":
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(clip_norm=0.05))
        _RULES[rule](0.05).minimize(loss, startup_program=startup)

    main, startup, loss = _build_program(seed=11, minimize=minimize)
    names = _param_names(main)
    feeds = _batches(steps, seed=3)
    scope = executor_mod.Scope()

    def weights():
        return {n: np.array(scope.find_var(n), np.float64) for n in names}

    with dynamics.override(True, 1), executor_mod.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ws = [weights()]
        if window:
            # the window's steps are bitwise the per-step path's
            # (tests/test_run_steps.py): the weights between its steps
            # come from a second scope run step by step
            beside = executor_mod.Scope()
            for n in scope.local_var_names():
                beside.set_var(n, np.array(scope.find_var(n)))
            exe.run_steps(main, feed_window=feeds, fetch_list=[loss.name])
            with dynamics.override(False):
                for feed in feeds:
                    exe.run(main, feed=feed, fetch_list=[loss.name],
                            scope=beside)
                    ws.append({n: np.array(beside.find_var(n), np.float64)
                               for n in names})
            for n in names:
                assert np.array_equal(ws[-1][n], weights()[n])
        else:
            for feed in feeds:
                exe.run(main, feed=feed, fetch_list=[loss.name])
                ws.append(weights())
    (prog,) = dynamics.payload()["programs"].values()
    tables = [{name: s["recent"][i] for name, s in prog["series"].items()}
              for i in range(steps)]
    return ws, tables, _update_norm_sources()


@pytest.mark.parametrize("window", [False, True], ids=["run", "run_steps"])
@pytest.mark.parametrize("fused", [False, True], ids=["single", "bucket"])
@pytest.mark.parametrize("rule", sorted(_RULES))
def test_update_ratio_from_the_rules_own_step(rule, fused, window,
                                              monkeypatch):
    """The sampled update ratio is ||w1 - w0|| / ||w1|| of the fetched
    weights whichever source its numerator has; a rule with a step sibling
    (single op or fused bucket, per step or in a window) reads no old
    value and books `source="step"`, any other keeps the difference; the
    other seven fields are bitwise the difference-based table's."""
    from paddle_tpu.ops import fusion
    monkeypatch.setattr(fusion, "FUSION_OPT", fused)
    ws, tables, sources = _observed_steps(rule, window)
    compiles = 1  # run, or run_steps' window: one trace of the step each
    want = "difference" if rule == "adagrad" else "step"
    other = "step" if want == "difference" else "difference"
    assert sources == {want: 4 * compiles, other: 0}
    for i, table in enumerate(tables):
        assert sorted(table) == sorted(ws[0])
        for name, vals in table.items():
            w0, w1 = ws[i][name], ws[i + 1][name]
            ratio = np.linalg.norm(w1 - w0) / np.linalg.norm(w1)
            assert ratio > 0
            assert vals["update_ratio"] == pytest.approx(ratio, rel=1e-3)

    # the table as the difference of values gave it: no rule is found
    monkeypatch.setattr(dynamics, "_discover_rules", lambda *a: {})
    _, by_difference, sources = _observed_steps(rule, window)
    assert sources == {"step": 0, "difference": 4 * compiles}
    for table, old in zip(tables, by_difference):
        for name, vals in table.items():
            for field in dynamics.STAT_FIELDS:
                if field != "update_ratio":
                    assert vals[field] == old[name][field], (name, field)
            assert vals["update_ratio"] == pytest.approx(
                old[name]["update_ratio"], rel=1e-3)


def test_parameter_narrower_than_float32_keeps_the_difference():
    """In bfloat16 a step can round away whole: the table has to report
    what the rounding left (frozen-param reads it), so such a parameter
    keeps `w_new - w_old` though its rule has a step, and is counted."""
    import jax.numpy as jnp
    main, _, _ = _build_program()
    with dynamics.override(True, 1):
        plan = dynamics.plan(main)
    rng = np.random.RandomState(5)
    old, new, grabs = {}, {}, {}
    for grp in plan.groups:
        (ent,) = grp.params
        assert ent.rule is not None and ent.rule.op.type == "momentum"
        shape = main.global_block().var(ent.name).shape
        w = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        # a step of 1e-4 of the weight: under half a bfloat16 ulp
        v = (w.astype(jnp.float32) * 1e-2).astype(jnp.bfloat16)
        old[ent.name], new[ent.name] = w, (w - 0.01 * v).astype(jnp.bfloat16)
        new[ent.rule.new[0]] = v
        grabs[ent.grad] = jnp.ones(shape, jnp.bfloat16)
        for name, _ in ent.rule.scalars:
            grabs[name] = old[name] = jnp.full((1,), 0.01, jnp.float32)
    table = np.asarray(dynamics.sampled_stats(plan, old, new, grabs, 0, "pX"))
    assert _update_norm_sources() == {"step": 0, "difference": 4}
    ratio = table[:, dynamics.STAT_FIELDS.index("update_ratio")]
    assert np.all(ratio == 0.0), ratio
    # the same values in float32 take the rule's step, which is not zero
    as_f32 = lambda d: {n: v.astype(jnp.float32) for n, v in d.items()}
    table = np.asarray(dynamics.sampled_stats(
        plan, as_f32(old), as_f32(new), as_f32(grabs), 0, "pX"))
    assert _update_norm_sources() == {"step": 4, "difference": 4}
    ratio = table[:, dynamics.STAT_FIELDS.index("update_ratio")]
    assert np.all((ratio > 5e-5) & (ratio < 2e-4)), ratio


# -- verdict layer on synthetic series --------------------------------------


def _plan_one(name="fc_0.w_0", role="ffn_up"):
    ent = dynamics._ParamEntry(name, name + "@GRAD", False, [], role)
    grp = dynamics._Group(name, role, [ent])
    return dynamics.DynamicsPlan([grp], (ent.grad,), 1, 1)


def _row(weight_l2=1.0, weight_rms=0.1, weight_max_abs=0.5, grad_l2=1.0,
         grad_rms=0.1, grad_zero_frac=0.0, update_ratio=0.01,
         moment_rms=-1.0):
    vals = dict(weight_l2=weight_l2, weight_rms=weight_rms,
                weight_max_abs=weight_max_abs, grad_l2=grad_l2,
                grad_rms=grad_rms, grad_zero_frac=grad_zero_frac,
                update_ratio=update_ratio, moment_rms=moment_rms)
    return np.array([[vals[f] for f in dynamics.STAT_FIELDS]], np.float64)


def _feed(plan, rows, prog="pX"):
    for step, row in enumerate(rows):
        dynamics._OBS.record(prog, step, plan, row)


def _verdict_codes():
    return {(v["program"], v["series"]): v["code"]
            for v in dynamics.verdicts()}


def test_dead_layer_verdict_and_gauge():
    plan = _plan_one()
    win = int(dynamics.THRESHOLDS["verdict_window"])
    _feed(plan, [_row(grad_l2=0.0, grad_rms=0.0, update_ratio=0.0)] * win)
    assert _verdict_codes() == {("pX", "fc_0.w_0"): "dead-layer"}
    assert telemetry.read_gauge("dynamics_dead_layers", program="pX") == 1.0


def test_frozen_param_needs_live_gradients():
    """Zero updates with LIVE gradients is frozen-param (an optimizer
    or lr problem), distinct from dead-layer (a gradient-flow one)."""
    plan = _plan_one()
    win = int(dynamics.THRESHOLDS["verdict_window"])
    _feed(plan, [_row(grad_rms=0.1, update_ratio=0.0)] * win)
    assert _verdict_codes() == {("pX", "fc_0.w_0"): "frozen-param"}
    assert telemetry.read_gauge(
        "dynamics_frozen_params", program="pX") == 1.0


def test_exploding_update_vs_ewma_baseline():
    """A ratio 50x the EWMA baseline (and above the absolute floor)
    flips the verdict the LR-spike pager keys on; a steady ratio at the
    baseline never does."""
    plan = _plan_one()
    _feed(plan, [_row(update_ratio=0.01)] * 8)
    assert not dynamics.verdicts()
    _feed(plan, [_row(update_ratio=0.5)])
    assert _verdict_codes() == {("pX", "fc_0.w_0"): "exploding-update"}


def test_nonfinite_wins_over_history():
    plan = _plan_one()
    win = int(dynamics.THRESHOLDS["verdict_window"])
    _feed(plan, [_row(grad_rms=0.0, update_ratio=0.0)] * win)
    _feed(plan, [_row(weight_l2=float("nan"))])
    assert _verdict_codes() == {("pX", "fc_0.w_0"): "nonfinite"}


def test_absent_optional_fields_round_trip_as_none():
    """-1 is the on-device 'absent' sentinel for optional fields (no
    grad this step, no optimizer moment); it must surface as null, not
    a negative statistic."""
    plan = _plan_one()
    _feed(plan, [_row(grad_l2=-1.0, grad_rms=-1.0, grad_zero_frac=-1.0,
                      update_ratio=-1.0, moment_rms=-1.0)])
    series = dynamics.payload()["programs"]["pX"]["series"]["fc_0.w_0"]
    last = series["last"]
    for field in ("grad_l2", "grad_rms", "update_ratio", "moment_rms"):
        assert last[field] is None
    assert last["weight_l2"] == 1.0


def test_jsonl_export(tmp_path, monkeypatch):
    path = str(tmp_path / "dyn.jsonl")
    monkeypatch.setenv("PADDLE_TPU_DYNAMICS_LOG", path)
    plan = _plan_one()
    _feed(plan, [_row()] * 2)
    recs = [json.loads(ln) for ln in open(path)]
    assert len(recs) == 2
    assert recs[0]["series"] == "fc_0.w_0"
    assert recs[0]["code"] == "ok"
    assert math.isclose(recs[1]["update_ratio"], 0.01)


# -- threshold unification (GradientAudit satellite) ------------------------


def test_gradient_audit_thresholds_come_from_dynamics_table():
    """ISSUE 19 satellite: GradientAudit's band edges resolve from
    dynamics.THRESHOLDS — one constants table, not two drifting ones."""
    from paddle_tpu.inspector import GradientAudit

    main, _, _ = _build_program()
    audit = GradientAudit(main)
    assert audit.vanishing_threshold == \
        dynamics.THRESHOLDS["grad_vanishing_abs_mean"]
    assert audit.exploding_threshold == \
        dynamics.THRESHOLDS["grad_exploding_max_abs"]


def test_gradient_audit_tracks_table_edits(monkeypatch):
    """Editing the shared table moves a FRESH audit's bands — the
    regression this pins is someone re-hardcoding the literals."""
    from paddle_tpu.inspector import GradientAudit

    main, _, _ = _build_program()
    monkeypatch.setitem(dynamics.THRESHOLDS,
                        "grad_vanishing_abs_mean", 3e-5)
    assert GradientAudit(main).vanishing_threshold == 3e-5


def test_classify_grad_bands():
    cg = dynamics.classify_grad
    assert cg(True, 1.0, 1.0, 1.0) == "nonfinite"
    assert cg(False, 0.0, 0.0, 0.0) == "zero"
    assert cg(False, 1e-9, 1e-9, 1e-9) == "vanishing"
    assert cg(False, 1e4, 1.0, 1e4) == "exploding"
    assert cg(False, 0.1, 0.05, 0.2) == "ok"
    # explicit overrides (the audit's constructor args) still win
    assert cg(False, 1e-3, 1e-3, 1e-3,
              vanishing_threshold=1e-2) == "vanishing"


# -- HTTP surface -----------------------------------------------------------


def test_dynamics_endpoint_serves_payload():
    plan = _plan_one()
    win = int(dynamics.THRESHOLDS["verdict_window"])
    _feed(plan, [_row(grad_l2=0.0, grad_rms=0.0, update_ratio=0.0)] * win)
    srv = obs_server.start(port=0)
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request("GET", "/dynamics?n=4")
        resp = conn.getresponse()
        assert resp.status == 200
        body = json.loads(resp.read())
    finally:
        conn.close()
    assert body["enabled"] in (True, False)
    assert body["samples_recorded"] == win
    series = body["programs"]["pX"]["series"]["fc_0.w_0"]
    assert series["verdict"] == "dead-layer"
    assert len(series["recent"]) == 4
    assert [v["code"] for v in body["verdicts"]] == ["dead-layer"]


# -- no watcher waits on the device (PR 51) -----------------------------------


def _recorded_steps():
    """Steps of the samples in the table, in the order they were recorded,
    read without a drain (dynamics.payload would force one)."""
    obs = dynamics._OBS
    (series_map,) = obs.programs.values() if obs.programs else ({},)
    rings = [[step for step, _ in s.ring] for s in series_map.values()]
    assert all(r == rings[0] for r in rings)
    return rings[0] if rings else []


def _by_label(series, label, count=int):
    """{value of `label`: sum of count(child)} over a family's series."""
    out = {}
    for labels, child in series.items():
        key = dict(kv.split("=") for kv in labels.split(","))[label]
        out[key] = out.get(key, 0) + count(child)
    return out


def _host_waits():
    """{site: samples booked} of executor_host_wait_seconds."""
    return _by_label(telemetry.snapshot()["histograms"].get(
        "executor_host_wait_seconds", {}), "site", lambda h: h["count"])


def _samples_by_how():
    return _by_label(telemetry.read_series("dynamics_samples_total"), "how")


def test_pipelined_loop_reads_its_samples_from_steps_already_finished():
    """return_numpy=False with two steps in flight, 40 steps, period 16:
    no step waits for its own sample, so nothing is booked under site
    `dynamics`; each sample is in the table at most two dispatches after
    its own, in step order; Executor.close() leaves none pending."""
    in_flight_max, losses = 2, []
    main, startup, loss = _build_program()
    scope = executor_mod.Scope()
    in_flight, late = [], {}
    with dynamics.override(True, 16), executor_mod.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("__rng_counter__", 9)     # steps 9..48: 16, 32, 48
        for i, feed in enumerate(_batches(40)):
            out, = exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            in_flight.append(out)
            if len(in_flight) >= in_flight_max:
                losses.append(float(np.asarray(in_flight.pop(0)).ravel()[0]))
            for step in _recorded_steps():
                late.setdefault(step, (9 + i) - step)
        assert _host_waits().get("dynamics", 0) == 0
        assert "forced" not in _samples_by_how()
        # 48 is the last dispatch: on the host or not, close() has it
        assert set(late) >= {16, 32}
        assert all(0 <= n <= in_flight_max for n in late.values()), late
        exe.close()
    assert _recorded_steps() == [16, 32, 48]
    assert not dynamics._OBS.pending
    assert sum(_samples_by_how().values()) == 3
    assert np.isfinite(losses).all()


def test_a_reader_drains_what_is_pending_and_books_it_forced(monkeypatch):
    """A row still in flight stays queued whatever the steps behind it
    do; dynamics.payload() waits for it, books the wait under site
    `dynamics` and the sample as how="forced"."""
    monkeypatch.setattr(telemetry, "is_ready", lambda value: False)
    main, startup, loss = _build_program()
    scope = executor_mod.Scope()
    with dynamics.override(True, 4), executor_mod.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("__rng_counter__", 4)
        for feed in _batches(6):                # 4..9: 4 and 8 sample
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    assert len(dynamics._OBS.pending) == 2 and not _recorded_steps()
    assert not _host_waits()
    body = dynamics.payload()
    assert body["samples_recorded"] == 2
    assert _recorded_steps() == [4, 8]
    assert _samples_by_how() == {"forced": 2}
    assert _host_waits() == {"dynamics": 2}


def _sleep_until_pending_rows_are_ready(seconds=10.0):
    """The test's own bounded wait: it sleeps, asks nothing of the device
    and books nothing. Fails if a queued row still reads as in flight
    after `seconds`."""
    deadline = time.monotonic() + seconds
    while not all(telemetry.is_ready(entry[2])
                  for entry in dynamics._OBS.pending):
        assert time.monotonic() < deadline, "a row never became ready"
        time.sleep(0.001)


def test_a_synchronous_loop_records_each_sample_in_its_own_step():
    """return_numpy=True: the step's row is an output of the execution
    whose loss is on the host by now, and the first unforced drain that
    finds it ready records it, in step order, with no wait booked. As a
    rule that is the run's own last drain. jax's CPU client may report the
    row ready a thread hand-off behind the loss (0.3 ms when the host
    sleeps, one interpreter switch interval when it keeps running Python,
    seen for a step loaded from the compile cache), and these six runs
    take less than that: so the test sleeps until the row reads as ready
    and makes the drain the next step would start with."""
    main, startup, loss = _build_program()
    scope = executor_mod.Scope()
    seen = []
    with dynamics.override(True, 2), executor_mod.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("__rng_counter__", 2)
        for feed in _batches(6):                # 2..7: 2, 4, 6 sample
            exe.run(main, feed=feed, fetch_list=[loss])
            _sleep_until_pending_rows_are_ready()
            dynamics.drain()                    # takes what is ready
            seen.append(list(_recorded_steps()))
    assert seen == [[2], [2], [2, 4], [2, 4], [2, 4, 6], [2, 4, 6]]
    assert _samples_by_how() == {"ready": 3}
    assert not _host_waits()


def _wait_dynamics(forced, tmp_path, monkeypatch):
    main, startup, loss = _build_program()
    with dynamics.override(True, 1):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_batches(1)[0], fetch_list=[loss],
                return_numpy=False)
        if forced:                              # a reader asks
            dynamics.drain(wait=True)


def _wait_side_fetch(forced, tmp_path, monkeypatch):
    from paddle_tpu import inspector

    def clipped(loss, startup):
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(clip_norm=1.0))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(
            loss, startup_program=startup)

    main, startup, loss = _build_program(minimize=clipped)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if forced:                                  # it wants this step's norm
        inspector.enable_flight_recorder(str(tmp_path / "crash.json"))
    try:
        exe.run(main, feed=_batches(1)[0], fetch_list=[loss],
                return_numpy=False)
    finally:
        inspector.disable_flight_recorder()


def _wait_check_nan_inf(forced, tmp_path, monkeypatch):
    monkeypatch.setattr(executor_mod, "_CHECK_NAN_INF", forced)
    main, startup, loss = _build_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_batches(1)[0], fetch_list=[loss], return_numpy=False)


def _wait_profiler_sync(forced, tmp_path, monkeypatch):
    from paddle_tpu import profiler
    main, startup, loss = _build_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if forced:
        profiler.start_profiler()
    try:
        exe.run(main, feed=_batches(1)[0], fetch_list=[loss],
                return_numpy=False)
    finally:
        if forced:
            profiler.stop_profiler()
            profiler.reset_profiler()


def _wait_lod_writeback(forced, tmp_path, monkeypatch):
    """Sequence state goes back to the scope packed: host work."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32",
                              lod_level=1)
        kept = main.global_block().create_var(
            name="kept_rows", shape=[-1, 2], dtype="float32",
            persistable=True, lod_level=1 if forced else 0)
        fluid.layers.assign(fluid.layers.scale(x, scale=2.0), output=kept)
    rows = fluid.LoDTensor(
        np.arange(10, dtype=np.float32).reshape(5, 2), [[0, 2, 5]])
    scope = executor_mod.Scope()
    fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": rows}, fetch_list=[], scope=scope)
    assert isinstance(scope.find_var("kept_rows"),
                      fluid.LoDTensor) == forced


@pytest.mark.parametrize("forced", [True, False], ids=["forced", "default"])
@pytest.mark.parametrize("site", ["dynamics", "side_fetch", "check_nan_inf",
                                  "profiler_sync", "lod_writeback"])
def test_each_wait_on_the_device_is_booked_at_its_site(
        site, forced, tmp_path, monkeypatch):
    """Every place the executor blocks on a device value the caller did
    not ask for books the wait under its own site when its flag takes it
    there with the value still in flight, and nothing otherwise. The
    CPU finishes these steps before the host looks, so every value is
    made to read as in flight."""
    monkeypatch.setattr(telemetry, "is_ready", lambda value: False)
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        globals()["_wait_" + site](forced, tmp_path, monkeypatch)
    booked = _host_waits()
    assert set(booked) == ({site} if forced else set()), booked
    assert all(n >= 1 for n in booked.values())


def test_readers_draining_beside_the_training_thread_lose_nothing(
        monkeypatch):
    """The pending queue is shared by the training thread (on_step) and
    any reader (the obs server's /dynamics): with more readers than
    cores forcing drains while steps are dispatched, every sample is
    recorded once, in step order. Each row reads as in flight the first
    time it is asked, so the ready and the forced paths race."""
    import sys
    import threading
    asked = set()

    def first_time_in_flight(value):
        if id(value) in asked:
            return True
        asked.add(id(value))
        return False

    monkeypatch.setattr(telemetry, "is_ready", first_time_in_flight)
    main, startup, loss = _build_program()
    scope = executor_mod.Scope()
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            dynamics.drain(wait=True)

    readers = [threading.Thread(target=reader, daemon=True)
               for _ in range((os.cpu_count() or 4) + 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with dynamics.override(True, 1), executor_mod.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            scope.set_var("__rng_counter__", 100)
            for t in readers:
                t.start()
            for feed in _batches(60):
                exe.run(main, feed=feed, fetch_list=[loss],
                        return_numpy=False)
            exe.close()
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert _recorded_steps() == list(range(100, 160))
    assert sum(_samples_by_how().values()) == 60
