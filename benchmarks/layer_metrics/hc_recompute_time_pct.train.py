"""Share of the device's busy time, over the traced steps, spent in
forward ops that the backward runs a second time, in a program whose
every block is a recomputed segment between two checkpoints of four
streams a token (family `xing4`): the ops lowered under
`pd_recompute.<segment>`, the hyper-connections' maps, sweeps and mixes,
the expert layers and latent attention's maps among them; the attention
op itself is not (PR 54 keeps its output and row statistics), and a
segment the executor keeps for its bytes (PR 67) runs nothing twice. The
reduction of `recompute_time_pct.train`, whose reader computes it; that
metric's entry is held to its one cell. None without a trace or where
the trace holds no such scope."""

from benchmarks import run

LAYER = "recomputation"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "recompute_time_pct.train").compute
