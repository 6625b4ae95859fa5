"""Share of the device's busy time, over the traced steps, spent in
block-diffusion attention, forward and backward: every program op built
under a fluid.name_scope with `block_diffusion_attention` among its
parts (lowered as `pd_scope.block_diffusion_attention...`): the RMSNorm
over every query and key head, the rotation, and
`layers.block_diffusion_attention`'s own op nested in it, which is the
K/V repeat, the flash kernels over the clean keys, the own-block part
and the merge of the two by their row statistics, and in the backward
the same kernels' gradients and the sum over each group of heads. The
four projections are plain products of the model's and are not counted,
nor are the blocks' pre-norms and residual adds."""

from benchmarks import rooflines

LAYER = "block-diffusion attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "block_diffusion_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
