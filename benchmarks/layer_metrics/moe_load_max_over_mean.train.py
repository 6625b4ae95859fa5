"""Rows of the busiest held expert over the held experts' mean, averaged
over the window's steps and the expert layers: the program's
`moe_load_max_over_mean` histogram (a telemetry side-fetch of the
moe_experts op's own count, published without a host sync). 1 is even
routing; the grouped product's time follows the sum of the rows, its
tail tiles this."""

LAYER = "experts"
UNIT = "x"
MOVES = "train_items_per_s"
SOURCE = "program_counter"


def compute(ev):
    series = ev["counters"].get("moe_load_max_over_mean")
    count = sum(v["count"] for v in series.values()) if series else 0
    if not count:
        return None
    return sum(v["sum"] for v in series.values()) / count
