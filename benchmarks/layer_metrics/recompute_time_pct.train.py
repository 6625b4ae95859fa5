"""Share of the device's busy time, over the traced steps, spent in
forward ops that the backward runs a second time: the program ops
`append_backward(checkpoints=)` appended again ahead of a segment's
gradient ops, which the executor lowers under `pd_recompute.<segment>`
inside `pd_role.backward` (so `backward_device_ms.train` holds them too,
and `unattributed_device_ms.train` does not). What recomputation costs in
time; what it buys is memory (the cell's `batch_sizing`). An operation is
counted once whatever nests inside it (trace_reduce.self_seconds). None
without a trace or where the trace holds no such scope (a program that
recomputes nothing, a parent's)."""

import functools
import os
import re

from benchmarks import program_trace, trace_reduce

LAYER = "recomputation"
UNIT = "%"
MOVES = "train_items_per_s"
SOURCE = "device_trace"

# executor._exec_op: `pd_role.backward/pd_recompute.<segment>/...`
_REPLAYED = re.compile(r"pd_recompute\.([0-9]+)")
REPLAYED = "replayed"


def replayed(op_name):
    """REPLAYED for an operation lowered from a replayed forward op, from
    its HLO op_name; None for any other."""
    return REPLAYED if _REPLAYED.search(op_name or "") else None


def steps_of(devices):
    """program_trace.reduce_events()' `device_steps` of {plane:
    {"modules", "ops": [(label, role, REPLAYED or None, start, end)]}}:
    `by_op` is keyed (role, REPLAYED) or (role, "(<HLO kind>)")."""
    return program_trace.reduce_events(devices, [])["device_steps"]


@functools.lru_cache(maxsize=2)
def _steps_of_file(path, _mtime):
    from jax.profiler import ProfileData

    by_metadata, devices = None, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        modules, ops = [], []
        for line in plane.lines:
            spans = [(ev, ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                     for ev in line.events]
            if line.name == program_trace.MODULES_LINE:
                modules = [(ev.name, start, end) for ev, start, end in spans]
            elif line.name == trace_reduce.OPS_LINE:
                for ev, start, end in spans:
                    op_name = next(
                        (str(v) for k, v in ev.stats
                         if k in program_trace.OP_NAME_STATS), None)
                    if op_name is None:   # a v5e keeps it on the metadata
                        if by_metadata is None:
                            by_metadata = program_trace.metadata_op_names(
                                path)
                        op_name = by_metadata.get(plane.name, {}).get(ev.name)
                    role, _ = program_trace.provenance_of(op_name)
                    ops.append((trace_reduce.op_label(ev.name), role,
                                replayed(op_name), start, end))
        if ops:
            devices[plane.name] = {"modules": modules, "ops": ops}
    return steps_of(devices)


def replayed_steps(ev):
    """The traced device steps of the run `ev` is the evidence of, keyed
    as steps_of() keys them; None if not traced or no file."""
    from benchmarks import run

    path = program_trace._newest_xplane(
        os.path.join(run.TRACE_DIR, ev["cell"]["name"]))
    if ev["trace"] is None or path is None:
        return None
    return _steps_of_file(path, os.path.getmtime(path))


def compute(ev):
    steps = replayed_steps(ev)
    if not steps:
        return None
    under = sum(secs for step in steps
                for (_, key), secs in step["by_op"].items() if key == REPLAYED)
    if not under:
        return None
    return 100.0 * under / sum(step["busy_s"] for step in steps)
