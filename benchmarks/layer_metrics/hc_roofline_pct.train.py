"""Share of the v5e's HBM roofline the hyper-connections reach, whatever
implements them: the least time for the bytes one train step's
hyper-connections have to move (`family.hyper_connection_cost` at the
step's tokens: a forward, a replay and a backward of every sublayer's two
halves with the sublayer between them, X read twice and X' written once
a pass, the cotangents likewise, the maps' own bytes besides; the replay
counted for the share of the sublayers whose forward the backward really
runs again, `hyper_connection_replays_total` over
`hyper_connection_sublayers_total`: the last block lies behind the last
checkpoint and a segment the executor keeps is not lowered again, so
neither counts a replay) over the device time a traced step spends under the
scopes of `hc_time_pct.train` (median over the traced steps). XLA's
unfused passes, float32 copies of the streams, the sweeps' forty
dependent steps and the sum of X's three cotangents are in the time and
not in the bytes: under 100 by construction. None without a trace, where
no op carries the scopes (a parent program), or where the family prices
no hyper-connection."""

import statistics

from benchmarks import rooflines, run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

_scoped_seconds = run.load_module("layer_metrics",
                                  "hc_time_pct.train").scoped_seconds


def replayed_share():
    """Share of the traced sublayers whose forward was lowered a second
    time, from the program's two trace-time counters (the whole process':
    a window's delta holds no trace); None, the family's default, where
    the program books none."""
    from paddle_tpu import telemetry

    counters = telemetry.snapshot()["counters"]
    first = sum(counters.get("hyper_connection_sublayers_total",
                             {}).values())
    again = sum(counters.get("hyper_connection_replays_total", {}).values())
    return again / first if first else None


def compute(ev):
    found = _scoped_seconds(ev)
    family = run.load_module("families", ev["config"]["family"])
    if found is None or not hasattr(family, "hyper_connection_cost"):
        return None
    bytes_ = family.hyper_connection_cost(
        ev["config"], ev["items_per_step"], replayed_share())
    return rooflines.roofline_pct(ev, 0.0, bytes_,
                                  statistics.median(found[0]))
