"""Share of the v5e roofline the flash attention kernels reach where some
layers attend under a sliding window: the least time for the operations
and bytes of one step's attention ops AT THE MASKS' LIVE PAIRS
(`family.attention_kernel_cost` a sequence: the mean over the layers of
both kinds, a windowed layer at its window's pairs, 25.2M of a causal
33.6M at 8192 positions under 4096 keys; times the sequences of a step,
times `family.attention_ops_per_step`) over the device time a traced step
spends in the Mosaic kernels `flash_fwd`, `flash_dq` and `flash_dkv`: the
reduction of `mla_flash_roofline_pct.train`, whose reader computes it, on
this family's cost. The kernels walk whole tiles along the diagonal and
along the window's far edge, and the backward computes the score blocks
again, which the time includes and the operations do not: under 100 by
construction. A kernel that walked the tiles before the window would
lower its own share by about a fifth. None without a trace, where the
trace holds none of the kernels, or where the family prices no attention
(a parent's)."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "mla_flash_roofline_pct.train").compute
