"""Device ms a traced step spends in operations lowered from program ops
of `op_role` backward (the `pd_role.backward` scope in their op_name),
median over the traced steps."""

from benchmarks import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return program_trace.median_role_ms(ev, "backward")
