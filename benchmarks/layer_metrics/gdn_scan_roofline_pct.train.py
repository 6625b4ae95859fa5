"""Share of the v5e roofline the gated delta rule reaches under a decay
a head and 16 key heads beneath 32 value heads: the least time for the
operations and bytes of one step's delta rules (`family.kda_scan_cost`
at the step's tokens: the recurrence's three [K, V] products a token a
value head forward and twice that backward; q and k at their own 16
heads, v, o, the gate [32] and beta and their gradients once; times
`family.kda_layers`) over the device time a traced step spends under the
program op `kda_scan`, first and replayed, and its gradient op. The
reduction of `kda_scan_roofline_pct.train`, whose reader computes it, on
this family's cost; that metric's entry is held to its one cell. The
chunked form's within-chunk products and solves, a replayed forward and
what the gradient computes again are time and not work: under 100 by
construction. None without a trace, where no operation was lowered from
the op (a parent program), or where the family prices no delta rule."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "kda_scan_roofline_pct.train").compute
