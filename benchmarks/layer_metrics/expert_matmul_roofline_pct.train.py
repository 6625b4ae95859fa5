"""Share of the v5e roofline the grouped expert products reach: the
least time for the operations and bytes of one step's useful products
(`family.expert_product_cost`, at the rows the traced steps themselves
routed to held experts, `rooflines.traced_rows_routed`, times
`family.expert_layers`) over the device time a traced step spends in the
Mosaic kernels `gmm` and `tgmm`. The time includes every forward product
the backward runs again: a generic gradient re-traces the lowering, and
the chip's compile merges the re-traced kernels with the forward's only
where no conditional stands between them (PR 36: a ladder's gradient
branch runs two forward products again). The operations count each
product once whatever implements it, so the share is under 100 by
construction and a recompute lowers it."""

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
    layers = family.expert_layers(ev["config"])
    return rooflines.roofline_pct(ev, layers * flops, layers * bytes_,
                                  seconds)
