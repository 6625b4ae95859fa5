"""Rows of the busiest of the 8 held experts over their mean, averaged
over the window's steps and the expert layers (family `xing4`): the
program's `moe_load_max_over_mean` histogram. The held experts are an
eighth of a sigmoid router's 64 under a selection bias that
`models.balance_routers` moves, so what the rank is sent hangs on the
rule: the sum of the rows picks the rung, this the grouped product's tail
tiles. The reduction of `moe_load_max_over_mean.train`, whose reader
computes it; that metric's entry is held to its two cells. None where
the program books no such histogram."""

from benchmarks import run

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

compute = run.load_module("layer_metrics", "moe_load_max_over_mean.train").compute
