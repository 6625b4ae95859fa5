"""Share of the device's busy time, over the traced steps, spent in
operations of the Kimi-Delta-Attention mixers: every program op
`layers.kda_mixer` builds (under fluid.name_scope("kda_mixer"), lowered
as `pd_scope.kda_mixer`): the three projections and their short
convolutions, the two low-rank gates and beta's map, the op `kda_scan`
(the L2 norms, the decays, the chunked delta rule), the head norm times
its sigmoid gate and the output map, in the first forward pass, in the
replayed one (`pd_recompute.<segment>` stands outside the layer's scope
and hides nothing of it) and in the backward, together. The layer's
pre-norm, its residual add and the feed-forward behind it are the model's
and are not counted. None without a trace or where the trace holds no
such scope (a parent program)."""

from benchmarks import rooflines

LAYER = "delta-rule mixer"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPE = "kda_mixer"


def compute(ev):
    return rooflines.scope_share_pct(ev, SCOPE)
