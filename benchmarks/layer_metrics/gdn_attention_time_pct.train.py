"""Share of the device's busy time, over the traced steps, spent in the
gated attention layer of family `qwen3_next` (one of the four layers
held, the only part of the step quadratic in the sequence), forward and
backward: every program op built under
fluid.name_scope("gated_attention"): the rms_norm (weight 1 + w) a query
and key head, the rotation of the first 64 of 256 dims of 16 query and 2
key heads, the causal `layers.fused_attention` op (K and V read by the
flash kernels at their own 2 heads), the sigmoid of the gate and its
product with the output, and their gradients; the replayed layer runs
the norms and rotations a second time and reads the attention's kept
output (PR 54). The five maps, the layer's norms and the residual adds
are the model's and not counted. None without a trace or where no op
carries the scope."""

from benchmarks import rooflines

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "gated_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
