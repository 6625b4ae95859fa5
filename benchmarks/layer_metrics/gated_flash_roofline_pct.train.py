"""Share of the v5e roofline the flash attention kernels reach where the
layers differ in head count and three of five attend under a window
shorter than two tiles: the least time for the operations and bytes of
one step's attention ops AT THE MASKS' LIVE PAIRS
(`family.attention_kernel_cost` a sequence: the mean over the layers,
each at its own head count; a sliding layer at 4,063,488 of a causal
33,558,528 pairs at 8192 positions under 512 keys; times the sequences of
a step, times `family.attention_ops_per_step`) over the device time a
traced step spends in the Mosaic kernels `flash_fwd`, `flash_dq` and
`flash_dkv`: the reduction of `mla_flash_roofline_pct.train`, whose
reader computes it, on this family's cost. A sliding layer's walked tiles
are all masked, by the diagonal, by the window's far edge or by both, and
walk about twice its live pairs; the backward computes the score blocks
again and a replayed layer runs its forward kernel twice: all of that is
time and none of it work, so the share is under 100 by construction.
None without a trace, where the trace holds none of the kernels, or
where the family prices no attention."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "mla_flash_roofline_pct.train").compute
