"""Share of the device's busy time, over the traced steps, spent in the
full-attention layers of family `laguna`, forward and backward: every
program op built under a fluid.name_scope with `global_attention` among
its parts: the YaRN rotation of the trailing half of 48 query and 8 key
heads (cosines and sines times the attention factor), the causal
`layers.fused_attention` op (the K/V repeat and the flash kernels), the
gate a head, and where the layer is replayed in the backward the same ops
a second time. The four projections, the norms and the residual adds are
the model's and not counted. None without a trace or where no op carries
the scope (the sliding-window cell's family builds the same scope; its
cell does not list this metric)."""

from benchmarks import rooflines

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "global_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
