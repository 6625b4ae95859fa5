"""benchmarks/layer_metrics/moe_rows_handled_over_routed.train.py: the
window's handled rows over its routed rows from the two histograms, and
nothing where a program books neither (a parent before the capacity
ladder, a cell without expert layers)."""

import pytest

from benchmarks import run

READER = run.load_module("layer_metrics", "moe_rows_handled_over_routed.train")


def series(per_step, steps):
    return {"layer=%d,program=p1" % i: {"sum": rows * steps, "count": steps}
            for i, rows in enumerate(per_step)}


def test_ratio_is_of_the_sums_over_steps_and_layers():
    ev = {"counters": {
        "moe_rows_routed": series([2000.0, 1050.0, 25.0, 50.0], 50),
        "moe_rows_handled": series([3072.0] * 4, 50)}}
    assert READER.compute(ev) == pytest.approx(4 * 3072 / 3125.0)
    assert (READER.UNIT, READER.MOVES, READER.LAYER, READER.SOURCE) == (
        "x", "train_items_per_s", "experts", "program_counter")


@pytest.mark.parametrize("counters", [
    {},                                                      # no expert layer
    {"moe_rows_routed": series([1536.0], 50)},               # the parent
    {"moe_rows_routed": series([0.0], 50),
     "moe_rows_handled": series([3072.0], 50)},              # nothing routed
    {"moe_rows_routed": {}, "moe_rows_handled": {}}],
    ids=["neither", "parent", "nothing-routed", "empty"])
def test_a_program_without_both_reports_nothing(counters):
    assert READER.compute({"counters": counters}) is None


def test_the_tiny_hybrid_cell_reports_it_on_the_cpu():
    """The rehearsal cell's own counters: every step handles at least
    what it routed and at most every pair."""
    import os
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    ev = run.measure("tiny-nemotron-h.train", seed=7, seconds=0.5, trace=0,
                     data_dir=data)
    ratio = READER.compute(ev)
    routed = sum(v["sum"] for v in ev["counters"]["moe_rows_routed"].values())
    pairs = sum(v["count"] for v in ev["counters"]["moe_rows_routed"].values()
                ) * ev["items_per_step"] * ev["config"]["num_experts_per_tok"]
    assert ev["correct"] and 1.0 <= ratio <= pairs / routed
