"""A checkpoint is where the backward MAY cut: the executor replays a
segment only if keeping its forward values would not fit the device
(paddle_tpu/recompute.py). The device's limit reaches the decision
through `memory.device_limit`, the reader the executor calls, which the
tests patch: the CPU reports none, and then every segment is replayed and
the step is the parent's. Tiny shapes on the CPU, the kernels
interpreted."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward, memory, recompute, telemetry
from paddle_tpu import executor as executor_mod

import test_kda_kept_inverse as delta_rule
import test_recompute_kept as attention
from test_recompute_kept import kernels_in_the_step, loss_and_grads

LAYERS = attention.LAYERS           # three: segments 0 and 1 may be replayed
ROOMY = 1 << 50


def report(monkeypatch, limit):
    """The device reports `limit` bytes (None: no limit, the CPU's own
    answer)."""
    monkeypatch.setattr(memory, "device_limit", lambda device: limit)


def traced(built, fetch=None):
    """(the executor, the step function as it traces `built`, its
    arguments): nothing compiled."""
    main, startup, loss, feed = built
    exe = fluid.Executor(fluid.CPUPlace())

    def step_fn(program, names):
        return exe._make_step_fn(program, names,
                                 exe._persistable_outputs(program), {})

    rng = np.uint32(0)
    state = jax.eval_shape(step_fn(startup, []), {}, {}, rng)[2]
    return exe, step_fn(main, fetch or [loss.name]), (feed, state, rng)


def decided(built):
    """The plan the executor makes of `built` under the patched limit."""
    exe, fn, args = traced(built)
    jax.eval_shape(fn, *args)
    return exe.recompute_plan(built[0])


def limit_that_keeps(built, monkeypatch, wanted):
    """The smallest limit under which exactly `wanted` (a prefix of the
    order of choice) is kept."""
    report(monkeypatch, ROOMY)
    roomy = decided(built)
    sizes = {i: d.nbytes for i, d in roomy.decisions.items()}
    return recompute.MARGIN_BYTES + recompute.estimate(
        sizes, roomy.held, wanted)


def lowered(built):
    exe, fn, args = traced(built)
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


DECISIONS = {
    # what is kept of segments 0 and 1, and why each was decided so
    "all_fit": ([1, 0], {0: "fits", 1: "fits"}),
    "one_fits": ([1], {0: "budget", 1: "fits"}),
    "none_fits": ([], {0: "budget", 1: "budget"}),
    "no_limit": (None, {0: "no_limit", 1: "no_limit"}),
}


@pytest.mark.parametrize("case", list(DECISIONS))
def test_a_segment_is_replayed_only_if_it_does_not_fit(case, monkeypatch):
    """A kept segment's ops stand once in the lowered step, with no
    barrier and no `pd_recompute` scope; one that does not fit is replayed
    behind its barrier as the IR spells it; of two equal segments the
    later is kept first. The program is not edited either way."""
    kept, reasons = DECISIONS[case]
    built = attention.build("causal")
    spelt = built[0].to_json()
    limit = None if kept is None else limit_that_keeps(built, monkeypatch,
                                                       kept)
    report(monkeypatch, limit)
    made = decided(built)
    assert {i: d.reason for i, d in made.decisions.items()} == reasons
    assert {i for i, d in made.decisions.items() if d.kept} == \
        set(kept or ())
    assert all(d.nbytes > 0 for d in made.decisions.values())
    assert made.limit == limit and (made.estimate is None) == (limit is None)
    if limit is not None:
        assert made.estimate + recompute.MARGIN_BYTES <= limit
        assert made.kept_bytes == sum(made.decisions[i].nbytes for i in kept)
    text = lowered(built)
    replayed = sorted(set(reasons) - set(kept or ()))
    for segment in reasons:
        assert (f"pd_recompute.{segment}/" in text) == (segment in replayed)
        assert (f"pd_recompute.{segment}/pd.recompute_barrier" in text) \
            == (segment in replayed)
    assert kernels_in_the_step(*built)["flash_fwd"] == LAYERS
    assert built[0].to_json() == spelt
    assert sorted(backward.replayed_ops(built[0])) == sorted(reasons)


def test_without_a_limit_the_lowered_step_is_the_parents(monkeypatch):
    """The CPU reports no limit: the lowered text equals, letter for
    letter, that of the loop that knows of no decision (every op of the
    block through `_exec_op`)."""
    built = attention.build("causal")
    assert memory.device_limit(fluid.Executor(fluid.CPUPlace()).device) \
        is None
    ours = lowered(built)
    with monkeypatch.context() as patch:
        patch.setattr(recompute, "segments", lambda block: [])
        parents = lowered(built)
    assert ours == parents
    assert all(f"pd_recompute.{segment}/pd.recompute_barrier" in ours
               for segment in (0, 1))


def test_no_bit_moves_whatever_is_kept(monkeypatch):
    """Loss and every gradient of the three-layer program are equal bit
    for bit between every segment replayed, every segment kept, one of
    each, and no checkpoints at all."""
    built = attention.build("causal")
    limits = {case: None if kept is None
              else limit_that_keeps(built, monkeypatch, kept)
              for case, (kept, _) in DECISIONS.items()}
    results = {}
    for case, limit in limits.items():
        report(monkeypatch, limit)
        results[case] = loss_and_grads(*built)
    report(monkeypatch, None)
    results["no_checkpoints"] = loss_and_grads(
        *attention.build("causal", checkpoints=False))
    loss, grads = results.pop("no_limit")
    assert np.isfinite(loss).all() and len(grads) == 4 * LAYERS
    for case, (other_loss, other_grads) in results.items():
        assert np.array_equal(loss, other_loss), case
        assert sorted(grads) == sorted(other_grads)
        for name in grads:
            assert np.abs(grads[name]).max() > 0
            assert np.array_equal(grads[name], other_grads[name]), \
                (case, name)


def _attention_program():
    return attention.build("two_kv_heads"), attention.SDPA, \
        ("Out", "LSE"), "flash_fwd", {"flash_fwd": LAYERS}


def _delta_rule_program():
    layers = delta_rule.LAYERS
    return delta_rule.build("kda"), "kda_scan", ("Entering", "Inverse"), \
        "kda_scan", {"kda_scan_fwd": layers, "kda_scan_bwd": layers}


@pytest.mark.parametrize("program", [_attention_program,
                                     _delta_rule_program],
                         ids=["attention", "delta_rule"])
def test_an_op_that_keeps_outputs_runs_once_in_a_kept_segment(program,
                                                              monkeypatch):
    """An op with `kept_in_replay` outputs in a kept segment is neither
    handed anything nor run again: the step holds its forward kernel once
    a layer (and no given-inverse kernel), the names its replay would
    have written ARE the first forward's values, which its explicit
    gradient op reads, and nothing is booked as handed on."""
    built, op_type, slots, prefix, kernels = program()
    main, startup, loss, feed = built
    report(monkeypatch, ROOMY)
    assert {k: n for k, n in kernels_in_the_step(*built).items()
            if k.startswith(prefix)} == kernels
    block = main.global_block()
    first = [op for op in block.ops if op.type == op_type
             and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    again = [op for op in block.ops if op.type == op_type
             and backward.RECOMPUTE_ATTR in op.desc.attrs]
    assert again and len(first) == len(again) + 1
    fetch = []
    for fwd, op in zip(first, reversed(again)):
        grad, = [g for g in block.ops if g.type == op_type + "_grad"
                 and g.input(slots[-1]) == op.output(slots[-1])]
        for slot in slots:
            fetch += fwd.output(slot) + op.output(slot)
    before = dict(telemetry.read_series("recompute_kept_total"))
    values = attention.run(main, startup, feed, fetch)
    for kept, bound in zip(values[::2], values[1::2]):
        assert np.isfinite(kept).all() and np.array_equal(kept, bound)
    assert dict(telemetry.read_series("recompute_kept_total")) == before


def test_the_decision_is_counted(monkeypatch):
    """recompute_segments_total{program, decision, reason} a trace, the
    gauge of the kept bytes as estimated, and recompute_ops_total for
    what still runs again and nothing else."""
    for name in ("recompute_segments_total", "recompute_fallback_total",
                 "recompute_segments_kept_bytes"):
        assert name in telemetry.METRIC_CATALOG
    assert telemetry.METRIC_CATALOG["recompute_segments_total"]["labels"] \
        == ("program", "decision", "reason")
    built = attention.build("causal")
    main, startup, loss, feed = built
    report(monkeypatch, limit_that_keeps(built, monkeypatch, [1]))
    label = f"program={telemetry.program_label(main)}"

    def booked(name):
        return {k[len(label) + 1:]: v
                for k, v in telemetry.read_series(name).items()
                if k.startswith(label + ",")}

    def added(name, before):
        return {k: v - before.get(k, 0) for k, v in booked(name).items()
                if v != before.get(k, 0)}

    segments = booked("recompute_segments_total")
    ran_again = booked("recompute_ops_total")
    attention.run(main, startup, feed, [loss.name])
    assert added("recompute_segments_total", segments) == {
        "decision=kept,reason=fits": 1, "decision=replayed,reason=budget": 1}
    again = backward.replayed_ops(main, handed_on=False)[0]
    assert added("recompute_ops_total", ran_again) == {
        f"type={t}": again.count(t) for t in again}
    assert telemetry.read_series("recompute_segments_kept_bytes")[label] \
        == sum(d.nbytes for d in decided(built).decisions.values()
               if d.kept) > 0
    assert label not in telemetry.read_series("recompute_fallback_total")


def test_a_value_without_a_shape_leaves_its_segment_replayed():
    held = recompute.Held(always=100, turn=10, grads={0: 5, 1: 5, 2: 5})
    roomy = recompute.choose({0: None, 1: 8, 2: 8}, held, ROOMY)
    assert roomy == {0: recompute.Decision(False, "unknown_shape", None),
                     1: recompute.Decision(True, "fits", 8),
                     2: recompute.Decision(True, "fits", 8)}
    # the smallest first; of equal ones the later; the first that does
    # not fit ends the search
    tight = recompute.choose({0: 4, 1: 8, 2: 8}, held,
                             recompute.MARGIN_BYTES + 100 + 10 + 4 + 8)
    assert [i for i, d in tight.items() if d.kept] == [0, 2]
    assert tight[1] == recompute.Decision(False, "budget", 8)
    assert all(d.reason == "no_limit" and not d.kept for d in
               recompute.choose({0: 4, 1: None}, None, None).values())


def test_the_estimate_weighs_the_turn_and_every_segments_backward():
    sizes = {0: 30, 1: 20, 2: 10}
    held = recompute.Held(always=1000, turn=50, grads={2: 5, 1: 40, 0: 100})
    # nothing kept: the first segment's backward, every gradient there
    assert recompute.estimate(sizes, held, []) == 1000 + 100 + 30
    # the last kept: dead before the gradients come, the peak is where it was
    assert recompute.estimate(sizes, held, [2]) == 1000 + 100 + 30
    # all kept: live together at the turn, and segment 0's backward still
    # holds its own beside every gradient
    assert recompute.estimate(sizes, held, [0, 1, 2]) == 1000 + 100 + 30
    assert recompute.estimate({0: 30, 1: 20, 2: 60}, held, [0, 1, 2]) \
        == 1000 + 50 + 110


def _refusing_the_first_compile_of(target, monkeypatch):
    """Executor._jit_compile patched: the first compiled step of `target`
    traces and then raises the compiler's RESOURCE_EXHAUSTED; returns the
    list of its compiles."""
    compiles = []
    jit_compile = executor_mod.Executor._jit_compile

    def patched(self, program, fn, sh):
        jitted = jit_compile(self, program, fn, sh)
        if program is not target:
            return jitted
        compiles.append(program)
        if len(compiles) > 1:
            return jitted

        def refused(*args):
            jax.eval_shape(fn, *args)       # the trace, then no room
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                "Ran out of memory in memory space hbm.")
        return refused

    monkeypatch.setattr(executor_mod.Executor, "_jit_compile", patched)
    return compiles


def test_a_compile_that_runs_out_of_memory_is_made_again_all_replayed(
        monkeypatch):
    """The net under the estimate: RESOURCE_EXHAUSTED from a compile with
    segments kept makes the executor trace and compile once more with
    every segment replayed, counted and warned of; the step's numbers are
    those of the replayed step. A step with nothing kept raises as it
    did."""
    built = attention.build("causal")
    main = built[0]
    expected = loss_and_grads(*built)
    report(monkeypatch, ROOMY)
    label = f"program={telemetry.program_label(main)}"
    with monkeypatch.context() as patch:
        compiles = _refusing_the_first_compile_of(main, patch)
        with pytest.warns(RuntimeWarning, match="every segment replayed"):
            got = loss_and_grads(*built)
    assert len(compiles) == 2
    assert telemetry.read_series("recompute_fallback_total")[label] == 1
    assert np.array_equal(got[0], expected[0])
    for name in expected[1]:
        assert np.array_equal(got[1][name], expected[1][name]), name
    segments = {k: v for k, v in telemetry.read_series(
        "recompute_segments_total").items() if k.startswith(label + ",")}
    assert segments == {f"{label},decision=kept,reason=fits": 2,
                        f"{label},decision=replayed,reason=no_limit": 2,
                        f"{label},decision=replayed,reason=budget": 2}
    # nothing kept, nothing to fall back to
    report(monkeypatch, None)
    other = attention.build("causal")
    compiles = _refusing_the_first_compile_of(other[0], monkeypatch)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        loss_and_grads(*other)
    assert len(compiles) == 1
    assert telemetry.read_series("recompute_fallback_total") == {label: 1}
