"""Traces/compiles the executor booked inside the measured window
(`executor_compiles_total` delta). 0 is right: a step that compiles
inside the window means the warm-up missed a shape."""

from benchmarks import evidence

LAYER = "executor"
UNIT = "count"
MOVES = "train_items_per_s"
SOURCE = "program_counter"


def compute(ev):
    return evidence.family_total(ev["counters"], "executor_compiles_total") or 0
