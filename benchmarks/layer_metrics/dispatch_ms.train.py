"""Median host time of one Executor.run(..., return_numpy=False) call:
the benchmark's own `dispatch` span around each call in the window. The
host is busy this long per step whatever the device does."""

import statistics

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    spans = ev["spans"].get("dispatch")
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
