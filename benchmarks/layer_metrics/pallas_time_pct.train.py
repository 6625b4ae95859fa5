"""Share of the device's busy time spent in Mosaic (Pallas) custom
calls, from the traced steps: the flash attention kernels and the
grouped expert products (megablox `gmm` / `tgmm`) where their gates
pass; 0 where the step holds none (ResNet-50 since PR 34: its convs,
batch norms and activations are XLA's). A kernel that replaces slower
XLA ops raises it: read it beside the rate."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    trace = ev["trace"]
    if trace is None:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
