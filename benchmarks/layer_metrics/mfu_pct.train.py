"""Model FLOP/s utilization of the measured window: the FLOPs the
family's arithmetic says an item requires (forward and backward, nothing
recomputed) times items per second, over chips times the published bf16
peak of the device kind. End to end, not a kernel's roofline share."""

from benchmarks import trace_reduce

LAYER = "model step"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "host_clock"


def compute(ev):
    rate = ev["items"] / ev["window_s"]
    device = ev["device"]
    peak = trace_reduce.peak_flops(device["kind"]) * device["count"]
    return 100.0 * ev["required_flops_per_item"] * rate / peak
