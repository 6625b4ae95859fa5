"""Share of the device's busy time, over the traced steps, spent in the
exits of a looped decoder (family `ouro`): every program op
`models.looped_lm` builds under fluid.name_scope("loop_exit"), lowered as
`pd_scope.loop_exit`: the head's product over the whole vocabulary, the
cross-entropy, the gate and the mix's terms of each of the
`total_ut_steps` exits, forward, replayed (exits 1 to 3 fall in the
segment of the next pass's first layer application) and backward,
together. The final norm ahead of an exit is the loop's own and is not
counted. What the depth cut inflates: a stage that holds 8 of 48 layers
runs four whole heads beside 32 layer applications where the whole model
runs them beside 192. None without a trace or where the trace holds no
such scope (a parent program)."""

from benchmarks import rooflines

LAYER = "loop exits"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "loop_exit"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
