"""Share of the v5e roofline the flash attention kernels reach under the
block-diffusion mask: the least time for the operations and bytes of one
step's attention ops AT THE MASK'S LIVE PAIRS (`family.attention_kernel_
cost` a sequence: L (L + block) pairs where the two streams' square has
4 L^2; times the sequences of a step, times
`family.attention_ops_per_step`) over the device time a traced step
spends in the Mosaic kernels `flash_fwd`, `flash_dq` and `flash_dkv`:
the reduction of `mla_flash_roofline_pct.train`, whose reader computes
it, on this family's cost. The kernels walk whole tiles along the block
diagonal and the backward kernels compute the score blocks again, dq and
dkv each, which the time includes and the operations do not; the
own-block part's L x block pairs are counted as work and run outside the
kernels, a thousandth of the pairs: under 100 by construction. A kernel
that walked dead tiles would lower its own share. None without a trace,
where the trace holds none of the kernels, or where the family prices no
attention (a parent's)."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "mla_flash_roofline_pct.train").compute
