"""Share of the traced steps' span in which no operation ran on the
device: 1 - union of the device's operation intervals over the time from
the first operation's start to the last one's end."""

LAYER = "device"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    trace = ev["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
