"""Milliseconds of a traced step that a chip's core spends in
collectives: the seconds `collective_time_pct.train` counts, over the
cell's `trace_steps`. `XLA Ops` is the core's own timeline, one
instruction after another, so time in a collective's instruction there
is time the core did not compute: what the overlap pass did not hide.
None where the step holds no collective."""

from benchmarks import run

LAYER = "collectives"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    ops = run.load_module(
        "layer_metrics", "collective_time_pct.train").collective_ops(ev)
    if ops is None:
        return None
    return 1e3 * sum(secs for _, secs in ops) / ev["cell"]["trace_steps"]
