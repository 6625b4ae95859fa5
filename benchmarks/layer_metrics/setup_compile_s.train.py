"""Seconds of set-up in XLA's backend compile or the persistent cache's
load, over every block this process compiled
(`executor_build_seconds_total{phase=compile}`, read in process)."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(ev):
    return program_trace.build_seconds(("compile",))
