"""Median host time of the `bookkeep` phase of the traced steps: both
`pd.bookkeep` spans inside `pd.step`, read from the profiler trace. The
launch's own accounting, then every watcher and sink of
`Executor._book`, each under its own span (`sink.dynamics`,
`sink.counters`, `sink.memory`, `sink.side_fetch`, and `sink.flight` /
`sink.build` where they run). With `step_launch_ms.train` the three
phase readers tile `pd.step`; the three alone are
`step_host_overhead_ms.train` split by phase."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_host_ms(
        ev, lambda step: step["phases"].get("bookkeep", 0.0))
