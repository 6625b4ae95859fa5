"""Rows the expert layers handled over the rows routed to them, over
the window's steps and the expert layers (family `xing4`): the sums of
the program's `moe_rows_handled` and `moe_rows_routed` histograms. A
layer that holds an eighth of its experts has two rungs, 4,096 and 16,384
pairs at 4,096 tokens top 4: about 2 at a uniform router on the small
rung (4,096 handled over 2,048 routed a layer), 8 on the large one. The
reduction of `moe_rows_handled_over_routed.train`, whose reader computes
it; that metric's entry is held to its two cells. None where the program
books neither histogram."""

from benchmarks import run

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

compute = run.load_module("layer_metrics", "moe_rows_handled_over_routed.train").compute
