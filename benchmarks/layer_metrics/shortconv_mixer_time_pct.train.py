"""Share of the device's busy time, over the traced steps, spent in
operations of the gated short-convolution mixers (family `lfm2_moe`):
every program op `layers.short_conv_mixer` builds (under
fluid.name_scope("short_conv_mixer"), lowered as
`pd_scope.short_conv_mixer`): the three input maps (the thirds B, C and x
of the published projection), the op `causal_conv1d` in its gated form
(C * conv_3(B * x): the kernels `gated_conv1d_fwd` / `gated_conv1d_bwd`)
and the output map, in the first forward pass, in the replayed one
(`pd_recompute.<segment>` stands outside the layer's scope and hides
nothing of it) and in the backward, together. The layer's pre-norm, its
residual add and the feed-forward behind it are the model's and are not
counted. None without a trace or where the trace holds no such scope (a
parent program)."""

from benchmarks import rooflines

LAYER = "short-convolution mixer"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "short_conv_mixer"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
