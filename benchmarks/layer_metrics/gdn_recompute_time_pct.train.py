"""Share of the device's busy time, over the traced steps, spent in
forward ops that the backward runs a second time, in a program whose
every layer is a recomputed segment and three of four hold the delta
rule under a decay a head (family `qwen3_next`): the ops lowered under
`pd_recompute.<segment>`, the `kda_scan` forward kernel among them; the
attention layer's flash call is not (PR 54 keeps its output and row
statistics). The reduction of `recompute_time_pct.train`, whose reader
computes it; that metric's entry is held to its one cell. None without a
trace or where the trace holds no such scope."""

from benchmarks import run

LAYER = "recomputation"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "recompute_time_pct.train").compute
