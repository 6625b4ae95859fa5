"""Share of the v5e roofline the flash attention kernels reach in a
looped decoder, where one layer's kernels run `total_ut_steps` times a
step: the least time for the operations and bytes of one step's attention
ops AT THE CAUSAL MASK'S LIVE PAIRS (`family.attention_kernel_cost` a
sequence, [1, 4096, 16, 128] in the cell; times the sequences of a step,
times `family.attention_ops_per_step`, the `total_ut_steps x
num_hidden_layers` ops whose kernels run: a replayed op is handed the
first forward's output and row statistics and runs none, PR 54, and the
count leaves it out) over the device time a traced step spends in the
Mosaic kernels `flash_fwd`, `flash_dq` and `flash_dkv`: the reduction of
`mla_flash_roofline_pct.train`, whose reader computes it, on this
family's cost. The backward computes the score blocks again and the
kernels walk the diagonal's tiles whole: time and not work, so the share
is under 100 by construction. None without a trace, where the trace
holds none of the kernels, or where the family prices no attention."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "mla_flash_roofline_pct.train").compute
