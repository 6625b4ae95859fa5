"""Median of `pd.step` minus its `launch` child over the traced steps:
what the framework adds to a jit dispatch inside Executor.run (prepare,
bookkeep, writeback), read from the program's spans in the profiler
trace."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_host_ms(
        ev, lambda step: step["seconds"] - step["phases"].get("launch", 0.0))
