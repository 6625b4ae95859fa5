"""Share of the device's busy time, over the traced steps, spent in the
expert layers of a rank that holds an eighth of the experts behind a
hyper-connection (family `xing4`: 8 held of 64, 1024 wide at a hidden
size of 3584, top 4, beside a shared expert), forward and backward: every
program op `layers.moe_block` builds (under
fluid.name_scope("moe_block")): the sigmoid router, the sort, gather,
grouped products, weighting and return of `moe_experts` inside the rung
of its capacity ladder that the step's routed rows take, the shared
expert's three products, and what the backward replays of them. The
reduction of `moe_time_pct.train`, whose reader computes it; that
metric's entry is held to its two cells. None without a trace or where no
op carries the scope."""

from benchmarks import run

LAYER = "experts"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "moe_time_pct.train").compute
