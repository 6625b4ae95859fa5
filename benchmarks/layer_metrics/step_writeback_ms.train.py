"""Median host time of the `writeback` phase of the traced steps: both
`pd.writeback` spans inside `pd.step`, read from the profiler trace. The
state and the counter going back to the scope, then the fetches rebuilt
for the caller and what the queues publish of earlier steps. With
`step_launch_ms.train` the three phase readers tile `pd.step`; the three
alone are `step_host_overhead_ms.train` split by phase."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_host_ms(
        ev, lambda step: step["phases"].get("writeback", 0.0))
