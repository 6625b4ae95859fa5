"""Share of the device's busy time spent in Mosaic (Pallas) custom
calls, from the traced steps: the conv suite, fusion's bn+act and flash
attention where their gates pass; 0 where none is in the step."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    trace = ev["trace"]
    if trace is None:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
