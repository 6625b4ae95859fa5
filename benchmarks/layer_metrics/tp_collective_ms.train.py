"""Milliseconds of a traced step that a chip's core spends in the
collectives of the `tp` mesh axis (the activations' reductions and
gathers of tensor parallelism): `exposed_collective_ms.train`'s seconds,
the same events, of the instructions whose replica groups run along
`tp` alone, from the step's account by instruction
(`benchmarks/step_account.py`). With `fsdp_collective_ms.train` and the
groups that span both axes (which `benchmarks/step_account.py` prints)
it sums to that metric. None where the step holds no such collective or
the program keeps no account."""

from benchmarks import step_account

LAYER = "collectives"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return step_account.axis_ms(ev, "tp")
