"""Rows of the busiest of the 32 held experts over their mean, averaged
over the window's steps and the expert layers (family `qwen3_next`): the
program's `moe_load_max_over_mean` histogram. Under top 10 of 512 a held
expert sees about 320 rows a layer of 16,384 tokens; the busiest one's
rows set the grouped product's longest group. The reduction of
`moe_load_max_over_mean.train`, whose reader computes it; that metric's
entry is held to its two cells. None where the program books no such
histogram."""

from benchmarks import run

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

compute = run.load_module("layer_metrics",
                          "moe_load_max_over_mean.train").compute
