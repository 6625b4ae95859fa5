"""Share of the v5e roofline the selective scan reaches at ONE group of
64 heads, two head blocks of 32 a group at the chunk of 128 the cell
lowers the scan with (pallas_scan.heads_a_step): the least time for the operations
and bytes of one step's scans, one forward and one gradient a Mamba layer
(`family.scan_cost` at the step's tokens and the chunk the scan is
lowered with, times the Mamba layers), over the device time a traced step
spends under the program op `ssd_scan`, first and replayed, and its
gradient (median over the traced steps). The reduction of
`ssd_scan_roofline_pct.train`, whose reader computes it, on this
family's cost; that metric's entry is held to the hybrid cell. The
replayed forward and the chunk-local blocks the gradient computes again
are in the time and not in the operations: under 100 by construction,
and lower by what recomputation costs the scan. None without a trace,
where no operation was lowered from the op, or where the family prices
no scan."""

from benchmarks import run

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

compute = run.load_module("layer_metrics",
                          "ssd_scan_roofline_pct.train").compute
