"""Median host time of the `prepare` phase of the traced steps: the
program's own `pd.prepare` span inside `pd.step`, read from the profiler
trace. The step's arguments gathered from feed and scope
(`sink.gather`), each checked against where the compiled block wants it
and the block looked up (`sink.validate`), the feed's signature
(`sink.signature`). With `step_launch_ms.train` the three phase readers
tile `pd.step`; the three alone are `step_host_overhead_ms.train` split
by phase."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_host_ms(
        ev, lambda step: step["phases"].get("prepare", 0.0))
