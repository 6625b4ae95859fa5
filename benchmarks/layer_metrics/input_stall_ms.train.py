"""Mean wait of the training loop on the input pipeline's queue, per
batch: the delta of the program's `input_stall_seconds` histogram
(reader/pipeline.py books it where the consumer waits) over the window."""

from benchmarks import evidence

LAYER = "input pipeline"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_counter"


def compute(ev):
    total = evidence.family_total(ev["counters"], "input_stall_seconds", "sum")
    count = evidence.family_total(ev["counters"], "input_stall_seconds",
                                  "count")
    if not count:
        return None
    return 1e3 * total / count
