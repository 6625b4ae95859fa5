"""Share of the device's busy time, over the traced steps, spent in
operations of latent attention without positions (family `kimi_linear`:
one direct query map, no rotation, keys 192 wide beside values 128 wide
handed to the attention op with zero lanes up to 256), forward and
backward: every program op `layers.latent_attention` builds (under
fluid.name_scope("latent_attention")), the padding and the slice of the
lanes among them. The reduction of `mla_time_pct.train`, whose reader
computes it; that metric's entry is held to its cell. None without a
trace or where no op carries the scope."""

from benchmarks import run

LAYER = "latent attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "mla_time_pct.train").compute
