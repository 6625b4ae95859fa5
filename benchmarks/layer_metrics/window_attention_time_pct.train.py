"""Share of the device's busy time, over the traced steps, spent in the
sliding-window attention layers, forward and backward: every program op
built under a fluid.name_scope with `window_attention` among its parts
(lowered as `pd_scope.window_attention...`): the rotations of queries and
keys and `layers.fused_attention`'s own op with its `window`, which is
the K/V repeat and the flash kernels whose walk ranges take the window,
and in the backward the same kernels' gradient and the sum over each
group of heads. The full-attention layers (no positions, no window) are
built under `global_attention` and are not counted; nor are the four
projections, plain products of the model's, the blocks' pre-norms and
residual adds. None without a trace or where no op carries the scope (a
parent's program)."""

from benchmarks import rooflines

LAYER = "windowed attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "window_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
