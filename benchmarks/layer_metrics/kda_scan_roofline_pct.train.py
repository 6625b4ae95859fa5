"""Share of the v5e roofline the gated delta rule reaches, whatever
implements it: the least time for the operations and bytes of one step's
delta rules (`family.kda_scan_cost` at the step's tokens: the
recurrence's three [K, V] products a token a head forward and twice that
backward, which no chunk length moves; q, k, v, the gate, beta and o and
their gradients once; times `family.kda_layers`) over the device time a
traced step spends under the program op `kda_scan`, first and replayed,
and its gradient op (median over the traced steps). The chunked form's
within-chunk products and solves, the walk over the chunks, a replayed
forward and what the gradient computes again are in the time and not in
the operations: under 100 by construction. None without a trace, where no
operation was lowered from the op (a parent program), or where the family
prices no delta rule."""

import statistics

from benchmarks import rooflines, run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    under = rooflines.op_seconds(ev, ("kda_scan",))
    family = run.load_module("families", ev["config"]["family"])
    if under is None or not hasattr(family, "kda_scan_cost"):
        return None
    flops, bytes_ = family.kda_scan_cost(ev["config"], ev["items_per_step"])
    layers = family.kda_layers(ev["config"])
    return rooflines.roofline_pct(ev, layers * flops, layers * bytes_,
                                  statistics.median(under))
