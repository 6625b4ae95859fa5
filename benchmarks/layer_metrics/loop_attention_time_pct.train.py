"""Share of the device's busy time, over the traced steps, spent in the
attention of a looped decoder (family `ouro`): every program op
`models.looped_lm` builds under fluid.name_scope("loop_attention"),
lowered as `pd_scope.loop_attention`: the two rotations and the attention
op of each of the `total_ut_steps x num_hidden_layers` layer applications,
in the first forward pass, in the replayed one (the rotations run again;
the attention op is handed the first forward's output and row statistics
and runs no kernel, PR 54) and in the backward, together. The four maps
and the norms around them are the model's and are not counted. None
without a trace or where the trace holds no such scope (a parent
program)."""

from benchmarks import rooflines

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "loop_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
