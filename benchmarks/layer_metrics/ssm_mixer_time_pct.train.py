"""Share of the device's busy time, over the traced steps, spent in
operations of the state-space mixers of a model that recomputes them:
every program op `layers.mamba2_mixer` builds (`pd_scope.mamba2_mixer`),
in the first forward pass, in the replayed one (`pd_recompute.<segment>`
stands outside the layer's scope and hides nothing of it) and in the
backward, together. The reduction of `ssm_time_pct.train`, whose reader
computes it; that metric's entry is held to the hybrid cell. The layer's
pre-norm, its residual add and the feed-forward behind it are the
model's and are not counted. None without a trace or where the trace
holds no such scope."""

from benchmarks import run

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "ssm_time_pct.train").compute
