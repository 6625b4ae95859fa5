"""Median of the input pipeline's `pd.input_build` span in the traced
steps: the producer thread's time for one batch, the host-to-device copy
included (reader/pipeline.py). The pipeline's headroom is the step time
over this."""

from benchmarks import program_trace

LAYER = "input pipeline"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def compute(ev):
    return program_trace.median_span_ms(ev, "input_build")
