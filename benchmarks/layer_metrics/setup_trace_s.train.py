"""Seconds of set-up spent turning Programs into HLO: the executor's walk
from Program to step function, jax's tracing of it and the lowering to
HLO, over every block this process compiled
(`executor_build_seconds_total{phase=trace|lower}`, read in process)."""

from benchmarks import program_trace

LAYER = "executor"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(ev):
    return program_trace.build_seconds(("trace", "lower"))
