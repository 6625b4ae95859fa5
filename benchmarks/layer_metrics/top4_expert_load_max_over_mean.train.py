"""Rows of the busiest of the 8 held experts over their mean, averaged
over the window's steps and the expert layers (family `lfm2_moe`): the
program's `moe_load_max_over_mean` histogram. With no shared expert and
top 4 of 64 a held expert sees about 1,024 rows a layer of 16,384 tokens;
the busiest one's rows set the grouped product's longest group. The
reduction of `moe_load_max_over_mean.train`, whose reader computes it;
that metric's entry is held to its two cells. None where the program
books no such histogram."""

from benchmarks import run

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

compute = run.load_module("layer_metrics",
                          "moe_load_max_over_mean.train").compute
