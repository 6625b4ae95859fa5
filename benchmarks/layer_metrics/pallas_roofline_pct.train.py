"""How near the package's own Mosaic kernels run to the chip's roofline
for the work they do AS IMPLEMENTED: over the instructions of a traced
step that are Pallas calls of `paddle_tpu/ops/` (flash attention, the
delta rule, the scan, the short convolution, `pair_sum`), the sum of the
floors each call declares of itself (`kernel_floor_ms`: max(declared
FLOPs / peak, declared bytes / HBM bandwidth), `ops/kernel_cost.py`)
over the sum of their device time; median over the traced steps. The
kernel families' own shares (`*_flash_roofline_pct.train`,
`gdn_scan_roofline_pct.train`, ...) divide the work the MODEL requires
by the time under the op; this one divides the work the kernel does, so
the two together say whether a kernel adds work or does its work
slowly. jax's own `gmm` / `tgmm` are left out: their estimate counts
every row of the buffer, an upper bound. From the step's account by
instruction (`benchmarks/step_account.py`); None where the program keeps
no account, where its calls declare nothing (a parent program) and
where the step holds no kernel (ResNet-50)."""

import statistics

from benchmarks import step_account

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    account = step_account.of_evidence(ev)
    if account is None or not account["peak_flops"]:
        return None
    shares = []
    for step in step_account.steps_of(account):
        rows = [r for r in step["rows"]
                if r["joined"] and r.get("declared_by") == "kernel"
                and r.get("kernel_floor_ms") is not None]
        taken = sum(r["ms"] for r in rows)
        if taken > 0:
            shares.append(
                100.0 * sum(r["kernel_floor_ms"] for r in rows) / taken)
    return statistics.median(shares) if shares else None
