"""Rows of the busiest of the 32 held experts over their mean, averaged
over the window's steps and the expert layers (family `laguna`): the
program's `moe_load_max_over_mean` histogram. At a mean of 256 rows an
expert a layer the busiest expert's tail tiles are a larger share of the
grouped product than in the cells that hold 8 wide experts. The
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
