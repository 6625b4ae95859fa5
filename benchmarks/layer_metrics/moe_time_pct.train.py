"""Share of the device's busy time, over the traced steps, spent in
operations of the expert layers, forward and backward: every program op
`layers.moe_block` builds (under fluid.name_scope("moe_block"), lowered
as `pd_scope.moe_block`): the router, the sort, gather, grouped products,
weighting and scatter-add of `moe_experts`, and the shared expert's two
products and its activation; a prediction module's own expert layer
too (`pd_scope.mtp_block.moe_block`). The layer's pre-norm and residual
add are the model's and are not counted."""

from benchmarks import rooflines

LAYER = "experts"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "moe_block"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
