"""Share of the v5e roofline the gated short convolution's kernels reach:
the least time for the operations and bytes of one step's operators
(`family.conv_kernel_cost` at the step's tokens: the three thirds read
and the result written forward, the three and the cotangent read and
three gradients written backward, eleven [T, D] arrays in bf16; times
`family.conv_layers`) over the device time a traced step spends in the
Mosaic kernels `gated_conv1d_fwd` and `gated_conv1d_bwd`
(ops/pallas_conv1d.py, the calls that carry a gate). A replayed layer
runs the forward kernel a second time, and the gradient's kernel computes
the gated input and the taps' sums again: time and not work, so the share
is under 100 by construction. None without a trace, where the trace holds
neither kernel (a parent program, or a shape the gate declines), or where
the family prices no such operator."""

from benchmarks import rooflines, run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

KERNELS = ("gated_conv1d_fwd", "gated_conv1d_bwd")


def compute(ev):
    if ev["trace"] is None:
        return None
    seconds = sum(secs for name, secs in ev["trace"]["device_ops"]
                  if name in KERNELS) / ev["cell"]["trace_steps"]
    family = run.load_module("families", ev["config"]["family"])
    if not seconds or not hasattr(family, "conv_kernel_cost"):
        return None
    flops, bytes_ = family.conv_kernel_cost(ev["config"],
                                            ev["items_per_step"])
    layers = family.conv_layers(ev["config"])
    return rooflines.roofline_pct(ev, layers * flops, layers * bytes_,
                                  seconds)
