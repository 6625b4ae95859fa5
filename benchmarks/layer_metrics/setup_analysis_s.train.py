"""Seconds of set-up in the first-compile hooks, the static memory
analysis that lowers and compiles each block a second time from avals
(`executor_build_seconds_total{phase=analysis}`, read in process)."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(ev):
    return program_trace.build_seconds(("analysis",))
