"""Share of the device's busy time, over the traced steps, spent in
forward ops that the backward runs a second time, in a program whose
every layer is a recomputed segment and four of five hold the gated short
convolution (family `lfm2_moe`): the ops lowered under
`pd_recompute.<segment>`: the mixers' maps and their forward kernel, the
attention layer's projections, norms and rotations (its flash forward is
not among them: PR 54 keeps its output and row statistics), the dense
feed-forward and the expert layers' routers and grouped products. The
reduction of `recompute_time_pct.train`, whose reader computes it; that
metric's entry is held to its one cell. None without a trace or where the
trace holds no such scope."""

from benchmarks import run

LAYER = "recomputation"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "recompute_time_pct.train").compute
