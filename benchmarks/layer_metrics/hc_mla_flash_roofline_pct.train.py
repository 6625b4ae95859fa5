"""Share of the v5e roofline the flash attention kernels reach on latent
attention whose keys are 192 wide and whose values are 128 wide, 32
heads over 4096 positions (family `xing4`): the least time for the
operations and bytes of one step's attention ops AT THE PUBLISHED WIDTHS
(`family.attention_kernel_cost` a sequence, over the causal mask's live
pairs, times the sequences of a step, times
`family.attention_ops_per_step`) over the device time a traced step
spends in the Mosaic kernels `flash_fwd`, `flash_dq` and `flash_dkv`:
the reduction of `mla_flash_roofline_pct.train`, whose reader computes
it, on this family's cost. The layer hands the kernels 256 lanes a head,
64 and 128 of them zeros, which they walk: that is time and not work, as
are the score blocks the backward computes again, so the share is under
100 by construction and lower by what the zero lanes cost. None without a
trace, where the trace holds none of the kernels, or where the family
prices no attention."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics", "mla_flash_roofline_pct.train").compute
