"""Share of the device's busy time, over the traced steps, spent in
operations of latent attention behind a hyper-connection (family
`xing4`: a latent query, both rotations under YaRN in the DeepSeek keys,
keys 192 wide beside values 128 wide handed to the attention op with zero
lanes up to 256 and the scores' YaRN factor folded into q), forward and
backward: every program op `layers.latent_attention` builds (under
fluid.name_scope("latent_attention")), the padding and the slice of the
lanes among them. The reduction of `mla_time_pct.train`, whose reader
computes it; that metric's entry is held to its cell. The GLM cell runs
the same layer at equal widths under a plain residual path: the two
cells' shares differ by what the widths and the rotation cost. None
without a trace or where no op carries the scope."""

from benchmarks import run

LAYER = "latent attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "mla_time_pct.train").compute
