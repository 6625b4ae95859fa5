"""Share of the device's busy time, over the traced steps, spent in the
sliding-window attention layers of a decoder whose layers differ in head
count and carry a gate a head (family `laguna`), forward and backward:
every program op built under a fluid.name_scope with `window_attention`
among its parts: the default rotations of 64 query and 8 key heads,
`layers.fused_attention`'s op under a window of 512 keys (the K/V repeat
and the flash kernels, every walked tile of which is masked at tiles of
512 rows), the gate's map from the normed input, its sigmoid and the
product with the heads' outputs, and where the layer is replayed in the
backward the same ops a second time. The reduction of
`window_attention_time_pct.train`, whose reader computes it; that
metric's entry is held to the sliding-window cell. The four projections,
the norms and the residual adds are the model's and not counted. None
without a trace or where no op carries the scope."""

from benchmarks import run

LAYER = "windowed attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "window_attention_time_pct.train").compute
