"""Median host time of the `launch` phase of the traced steps: the
program's own `pd.launch` span around the call of the compiled step
inside Executor.run, read from the profiler trace. What a jit dispatch
costs with nothing of the framework around it."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_host_ms(
        ev, lambda step: step["phases"].get("launch", 0.0))
