"""Share of the device's busy time, over the traced steps, spent in
operations of the state-space mixer, forward and backward: every program
op `layers.mamba2_mixer` builds (it builds them under
fluid.name_scope("mamba2_mixer"), which the executor lowers as
`pd_scope.mamba2_mixer`): the two projections, the causal conv, the
selective scan, the gated norm, the splits and reshapes. The layer's
pre-norm and residual add are the model's and are not counted."""

from benchmarks import rooflines

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "mamba2_mixer"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
