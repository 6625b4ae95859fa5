"""Share of the v5e roofline the selective scan reaches: the least time
for the operations and bytes of one step's scans (`family.scan_cost` at
the step's tokens, times the Mamba layers) over the device time a traced
step spends under the program op `ssd_scan` and its gradient (median
over the traced steps). The gradient recomputes the chunk-local blocks,
which the time includes and the operations do not: under 100 by
construction."""

import statistics

from benchmarks import rooflines, run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    under = rooflines.op_seconds(ev, ("ssd_scan",))
    if under is None:
        return None
    family = run.load_module("families", ev["config"]["family"])
    flops, bytes_ = family.scan_cost(ev["config"], ev["items_per_step"])
    layers = family.scan_layers(ev["config"])
    return rooflines.roofline_pct(ev, layers * flops, layers * bytes_,
                                  statistics.median(under))
