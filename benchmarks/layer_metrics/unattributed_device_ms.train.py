"""Device ms a traced step spends in operations whose op_name holds no
`pd_role.` scope, median over the traced steps: how far the split into
forward, backward and optimizer can be trusted. A program without role
scopes reads its whole busy time here."""

from benchmarks import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return program_trace.median_role_ms(ev, "unattributed")
