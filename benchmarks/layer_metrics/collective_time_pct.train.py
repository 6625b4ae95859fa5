"""Share of the device's busy time spent in collectives, from the traced
steps: seconds on a chip's `XLA Ops` line in instructions named after
`all-reduce`, `all-gather`, `reduce-scatter`, `all-to-all` or
`collective-permute` (with or without `-start`/`-done`, and the fusions
XLA names after them, `all-reduce-scatter` among them), averaged over
the chips, over `busy_s`. None where the step holds no collective: a
one-chip cell does not report it."""

import re

LAYER = "collectives"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def collective_ops(ev):
    """[[instruction name, seconds a chip]] of the traced steps'
    collectives, longest first; None without a trace or a collective."""
    trace = ev["trace"]
    if trace is None:
        return None
    return [[name, secs] for name, secs in trace["device_ops"]
            if COLLECTIVE.search(name)] or None


def compute(ev):
    ops = collective_ops(ev)
    if ops is None:
        return None
    return 100.0 * sum(secs for _, secs in ops) / ev["trace"]["busy_s"]
