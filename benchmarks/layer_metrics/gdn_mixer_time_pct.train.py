"""Share of the device's busy time, over the traced steps, spent in
operations of the Gated DeltaNet mixers of family `qwen3_next`: every
program op `layers.gdn_mixer` builds (under fluid.name_scope("gdn_mixer"),
lowered as `pd_scope.gdn_mixer`): the six maps of the published in_proj
and their three short convolutions, the op `kda_scan` in its head-decay
form (the L2 norms, the decays a head, the chunked delta rule at 16 key
heads under 32 value heads), the norm a head with its silu gate behind
it and the output map, in the first forward pass, in the replayed one
(`pd_recompute.<segment>` stands outside the layer's scope and hides
nothing of it) and in the backward, together. The layer's pre-norm, its
residual add and the expert layer behind it are the model's and are not
counted. None without a trace or where the trace holds no such scope (a
parent program)."""

from benchmarks import rooflines

LAYER = "delta-rule mixer"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "gdn_mixer"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
