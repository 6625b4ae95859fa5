"""Share of the device's busy time, over the traced steps, spent in the
expert layers of family `qwen3_next` (32 held experts 512 wide, top 10
of 512, a shared expert behind a sigmoid gate a token), forward and
backward: every program op `layers.moe_block` builds (under
fluid.name_scope("moe_block")): the softmax router, the sort, gather,
grouped products, weighting and return of `moe_experts`, the shared
expert and its gate, and what the backward replays of them. The
reduction of `moe_time_pct.train`, whose reader computes it; that
metric's entry is held to its two cells. None without a trace or where
no op carries the scope."""

from benchmarks import run

LAYER = "experts"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "moe_time_pct.train").compute
