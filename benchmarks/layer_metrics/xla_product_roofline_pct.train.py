"""How near XLA's own products run to the chip's roofline: over the
instructions of a traced step whose work is a dot or a convolution
(alone or inside a fusion; a v5e compiles a dot to a convolution; Mosaic
calls aside, their families price them), the sum of their floors,
max(FLOPs / peak, bytes / HBM bandwidth) each, over the sum of their
device time; median over the traced steps. From the step's account by
instruction (`benchmarks/step_account.py`); None where the program keeps
no account or the step holds no product."""

from benchmarks import step_account

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return step_account.floor_share_pct(ev, step_account.is_product)
