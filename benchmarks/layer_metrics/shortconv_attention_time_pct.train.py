"""Share of the device's busy time, over the traced steps, spent in the
grouped-query attention layers of family `lfm2_moe` (one of the five
layers held, the only part of the step quadratic in the sequence),
forward and backward: every program op built under
fluid.name_scope("gqa_attention"): the rms_norm a query and key head, the
rotation of 32 query and 8 key heads of 64, the causal
`layers.fused_attention` op (K and V widened to the query heads, two of
which share a lane block, and the flash kernels), and their gradients;
the replayed layer runs the norms and rotations a second time and reads
the attention's kept output (PR 54). The four projections, the layer's
norms and the residual adds are the model's and not counted. None without
a trace or where no op carries the scope."""

from benchmarks import rooflines

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "gqa_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
