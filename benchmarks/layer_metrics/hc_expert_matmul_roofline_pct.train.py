"""Share of the v5e roofline the grouped expert products reach at 8 held
experts of width 1024 under a hidden size of 3584 (family `xing4`: about
256 rows an expert a layer at a uniform router, where a product of 3.7M
bf16 weights an expert sits under the chip's ridge and the held experts'
weights bound it): the least time for the operations and bytes of one
step's useful products (`family.expert_product_cost` at the rows the
traced steps routed to held experts, times `family.expert_layers`) over
the device time a traced step spends in the Mosaic kernels `gmm` and
`tgmm`. The reduction of `expert_matmul_roofline_pct.train`, whose
reader computes it; that metric's entry is held to its two cells.
Forward products the gradient or a replayed block runs again are time
and not work: under 100 by construction. None without a trace, where the
program books no routed rows or the trace holds neither kernel."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "expert_matmul_roofline_pct.train").compute
