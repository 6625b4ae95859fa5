"""How near the bandwidth-bound instructions run to the chip's HBM
bandwidth: over the instructions of a traced step with no product
inside whose floor is their bytes (Adam, residual adds, norms, the
loss; copies, collectives and Mosaic calls aside), the sum of their
floors over the sum of their device time; median over the traced steps.
From the step's account by instruction (`benchmarks/step_account.py`);
None where the program keeps no account."""

from benchmarks import step_account

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return step_account.floor_share_pct(ev, step_account.is_membound)
