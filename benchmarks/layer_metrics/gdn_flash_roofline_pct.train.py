"""Share of the v5e roofline the flash attention kernels reach at 16
query heads over 2 key/value heads of 256 and 16,384 positions: the
least time for the operations and bytes of one step's attention op AT
THE CAUSAL MASK'S LIVE PAIRS (`family.attention_kernel_cost` a sequence:
134,225,920 pairs, K and V counted at their published 2 heads; times the
sequences of a step, times `family.attention_ops_per_step`) over the
device time a traced step spends in the Mosaic kernels `flash_fwd`,
`flash_dq` and `flash_dkv`: the reduction of
`mla_flash_roofline_pct.train`, whose reader computes it, on this
family's cost. Tiles the diagonal crosses are walked in sub-tiles and the
backward computes the score blocks again: that is time and not work, so
the share is under 100 by construction. None without a trace, where the
trace holds none of the kernels, or where the family prices no
attention."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "mla_flash_roofline_pct.train").compute
