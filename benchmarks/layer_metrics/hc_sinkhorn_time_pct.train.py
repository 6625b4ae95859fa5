"""Share of the device's busy time, over the traced steps, spent in the
Sinkhorn-Knopp sweeps of the hyper-connections' residual maps (family
`xing4`): the program ops built under the name scope `sinkhorn_knopp`
(nested in `hyper_connection_maps`): twenty sweeps, columns then rows,
over a [4, 4] map a token and sublayer, forward, replayed, and in the
gradient op the sweeps run again from M_0 and pulled back. Forty
dependent steps over 16 numbers a token: the part of the residual path
that is bound by latency and not by bytes, which `hc_time_pct.train`
holds too. None without a trace or where no op carries the scope."""

from benchmarks import run

LAYER = "residual path"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

SCOPES = ("sinkhorn_knopp",)

_share_pct = run.load_module("layer_metrics", "hc_time_pct.train").share_pct


def compute(ev):
    return _share_pct(ev, SCOPES)
