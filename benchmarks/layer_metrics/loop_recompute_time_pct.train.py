"""Share of the device's busy time, over the traced steps, spent in
forward ops that the backward runs a second time, in a program whose
every layer application is a recomputed segment and whose layers are run
`total_ut_steps` times over one set of weights (family `ouro`): the ops
lowered under `pd_recompute.<segment>`, 31 of the 32 applications' maps,
norms, rotations and feed-forwards and the heads and gates of the exits
but the last, each reading the shared weights once more; an
application's attention op is not among them (PR 54 keeps its output and
row statistics). The reduction of `recompute_time_pct.train`, whose
reader computes it; that metric's entry is held to its one cell. None
without a trace or where the trace holds no such scope."""

from benchmarks import run

LAYER = "recomputation"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "recompute_time_pct.train").compute
