"""Share of the v5e roofline the grouped expert products reach at 32 held
experts of width 512 under top 10 of 512 (about 320 rows an expert a
layer of 16,384 tokens at a uniform router): the least time for the
operations and bytes of one step's useful products
(`family.expert_product_cost` at the rows the traced steps routed to held
experts, times `family.expert_layers`) over the device time a traced
step spends in the Mosaic kernels `gmm` and `tgmm`. The reduction of
`expert_matmul_roofline_pct.train`, whose reader computes it; that
metric's entry is held to its two cells. Forward products a replayed
layer runs again and the tiles a rung's capacity holds past the routed
rows are time and not work: under 100 by construction. None without a
trace, where the program books no routed rows or the trace holds neither
kernel."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "expert_matmul_roofline_pct.train").compute
