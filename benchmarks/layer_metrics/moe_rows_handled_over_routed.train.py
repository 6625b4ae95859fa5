"""Rows the expert layers handled over the rows routed to them, over the
window's steps and the expert layers: the sums of the program's
`moe_rows_handled` and `moe_rows_routed` histograms (telemetry
side-fetches of the moe_experts op's own counts, published without a host
sync). `moe_experts` gathers, multiplies and scatters inside the smallest
rung of its capacity ladder that holds a step's routed rows: 1 is a layer
that handles exactly what it was sent, N x top_k over the routed rows one
that handles every pair whatever was sent (the program before the ladder
books no `moe_rows_handled`: None)."""

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"


def compute(ev):
    handled, routed = (
        sum(v["sum"] for v in ev["counters"].get(name, {}).values())
        for name in ("moe_rows_handled", "moe_rows_routed"))
    if not handled or not routed:
        return None
    return handled / routed
