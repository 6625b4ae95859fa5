"""Share of the device's busy time, over the traced steps, spent in the
hyper-connections of a decoder whose residual path is four streams a
token (family `xing4`; manifold-constrained hyper-connections,
arXiv:2512.24880): every program op built under the name scopes
`hyper_connection_maps` (the norm over a token's n C numbers, the
projection onto the 2 n + n^2 maps, the three maps, and nested in it
`sinkhorn_knopp`, the twenty sweeps), `hc_pre_mix` (what a sublayer
reads) and `hc_post_res_mix` (what it writes back), first forward,
replayed (`pd_recompute.<n>`) and gradient alike, two sublayers a block.
The sublayers themselves (attention, experts), their norms, the copy of
the embedding into the streams and the last sum are not counted. What the
changed residual path costs a step; `hc_roofline_pct.train` sets it
against the least it could. None without a trace or where no op carries
such a scope (a parent program)."""

from benchmarks import rooflines

LAYER = "residual path"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPES = ("hyper_connection_maps", "sinkhorn_knopp", "hc_pre_mix",
          "hc_post_res_mix")


def scoped_seconds(ev, scopes=SCOPES):
    """([seconds under ops built in any of `scopes`], [busy seconds]) a
    traced device step; None without a trace or where the trace holds no
    such scope."""
    steps = rooflines.scoped_steps(ev)
    if not steps:
        return None
    under = [sum(secs for (_, name), secs in step["by_op"].items()
                 if set(scopes) & set(name.split(".")))
             for step in steps]
    if not any(under):
        return None
    return under, [step["busy_s"] for step in steps]


def share_pct(ev, scopes=SCOPES):
    """100 x the seconds under `scopes` over the busy seconds, all traced
    steps together; None where scoped_seconds() finds nothing."""
    found = scoped_seconds(ev, scopes)
    if found is None:
        return None
    under, busy = found
    return 100.0 * sum(under) / sum(busy)


compute = share_pct
