"""Rows the expert layers handled over the rows routed to them, over the
window's steps and the expert layers (family `qwen3_next`): the sums of
the program's `moe_rows_handled` and `moe_rows_routed` histograms. A
layer that holds a sixteenth of its experts has two rungs (PR 58): a
quarter of the 163,840 (token, slot) pairs, 40,960, where the routed rows
fit, else all of them: 4 at a uniform router (10,240 rows) on the small
rung, 16 on the large. The reduction of
`moe_rows_handled_over_routed.train`, whose reader computes it; that
metric's entry is held to its two cells. None where the program books
neither histogram."""

from benchmarks import run

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

compute = run.load_module("layer_metrics",
                          "moe_rows_handled_over_routed.train").compute
