"""Share of the device's busy time, over the traced steps, spent in
operations of latent attention, forward and backward: every program op
`layers.latent_attention` builds (under fluid.name_scope
("latent_attention"), lowered as `pd_scope.latent_attention`), from the
two low-rank maps and their norms through the rotation, the assembly of
the keys and the attention kernels to the output map. The prediction
module's block is counted too: its scope is nested
(`pd_scope.mtp_block.latent_attention`), which
`rooflines.scope_share_pct` reads as it reads any scope with
`latent_attention` among its parts. The blocks' pre-norms and
residual adds are the model's and are not counted."""

from benchmarks import rooflines

LAYER = "latent attention"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "latent_attention"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
