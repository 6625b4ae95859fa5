"""Share of the device's busy time in instructions that move data and
compute nothing: `copy`, `copy-start` / `-done`, `transpose`, slices and
fusions with no arithmetic inside (a dtype cast counts as a move), from
the step's account by instruction (`benchmarks/step_account.py`); median
over the traced steps. None where the program keeps no account."""

from benchmarks import step_account

LAYER = "model step"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def compute(ev):
    return step_account.time_pct(ev, step_account.is_copy)
