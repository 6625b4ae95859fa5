"""Share of the v5e roofline the grouped expert products reach: the
least time for the operations and bytes of one step's products
(`family.expert_product_cost`, at the rows the traced steps themselves
routed to held experts, `rooflines.traced_rows_routed`, times the expert
layers) over the device time a traced step spends in the Mosaic kernels
`gmm` and `tgmm`. The time
includes the forward products the backward runs again (a generic
gradient re-traces the lowering and XLA does not merge Pallas calls);
the operations do not, so the share is under 100 by construction."""

from benchmarks import rooflines, run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

KERNELS = ("gmm", "tgmm")


def compute(ev):
    rows = rooflines.traced_rows_routed(ev)
    if ev["trace"] is None or rows is None:
        return None
    seconds = sum(secs for name, secs in ev["trace"]["device_ops"]
                  if name in KERNELS) / ev["cell"]["trace_steps"]
    if not seconds:
        return None
    family = run.load_module("families", ev["config"]["family"])
    flops, bytes_ = family.expert_product_cost(ev["config"], rows)
    layers = rooflines.layers_of(ev, "E")
    return rooflines.roofline_pct(ev, layers * flops, layers * bytes_,
                                  seconds)
