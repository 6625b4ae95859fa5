"""What the roofline shares and the time shares of the hybrid cell's
layer metrics read in common: the device's published HBM bandwidth, the
device seconds a traced step spends under given program ops or under the
name scope a layer built its ops in, the rows the traced steps routed,
and the share of a roofline a kernel reached."""

import functools
import os
import re

from benchmarks import program_trace, trace_reduce

# Per chip, keyed by jax's `device_kind`, beside trace_reduce.PEAKS' bf16
# FLOP/s. Google Cloud documentation, "TPU v5e" (system architecture):
# 819 GB/s of HBM2e.
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

# executor._exec_op: `pd_role.<role>/pd_scope.<outer.inner>/pd.<type>`
_SCOPE = re.compile(r"pd_scope\.([A-Za-z0-9_.\-]+)")


def scope_of(op_name):
    """The fluid.name_scope an operation's program op was built under,
    from its HLO op_name; None where it was built under none."""
    found = _SCOPE.search(op_name or "")
    return found.group(1) if found else None


@functools.lru_cache(maxsize=2)
def _scoped_steps_of_file(path, _mtime):
    """program_trace.reduce_events()' `device_steps` of one xplane file,
    with the name scope in the place of the program op: `by_op` is keyed
    (role, scope), or (role, "(<HLO kind>)") outside every scope."""
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
                                scope_of(op_name), start, end))
        if ops:
            devices[plane.name] = {"modules": modules, "ops": ops}
    return program_trace.reduce_events(devices, [])["device_steps"]


def scoped_steps(ev):
    """The traced device steps of the run `ev` is the evidence of, keyed
    by name scope (above); None if not traced or no file."""
    from benchmarks import run

    path = program_trace._newest_xplane(
        os.path.join(run.TRACE_DIR, ev["cell"]["name"]))
    if ev["trace"] is None or path is None:
        return None
    return _scoped_steps_of_file(path, os.path.getmtime(path))


def scope_share_pct(ev, scope):
    """Share of the device's busy time, over the traced steps, under the
    operations built in a name scope with `scope` among its dotted parts
    (the scope itself, one nested in it, or the same layer built inside
    another: `mtp_block.moe_block` is a `moe_block`), forward and
    backward; None without a trace or where the trace holds no such scope
    (a parent program)."""
    steps = scoped_steps(ev)
    if not steps:
        return None
    under = sum(secs for step in steps
                for (_, name), secs in step["by_op"].items()
                if scope in name.split("."))
    if not under:
        return None
    return 100.0 * under / sum(step["busy_s"] for step in steps)


def op_seconds(ev, ops):
    """[seconds] per traced device step spent under the program ops `ops`
    (forward and `<op>_grad`, any role); None without a device trace or
    where no operation of the trace was lowered from any of them (a
    parent program)."""
    reduced = program_trace.of_evidence(ev)
    if reduced is None or not reduced["device_steps"]:
        return None
    wanted = set(ops) | {op + "_grad" for op in ops}
    under = [sum(secs for (_, op), secs in step["by_op"].items()
                 if op in wanted) for step in reduced["device_steps"]]
    return under if any(under) else None


def roofline_pct(ev, flops, bytes_, seconds):
    """100 x the least time the chip could take for `flops` operations
    and `bytes_` of HBM traffic (the larger of the two over their
    published peaks) over the `seconds` it took."""
    kind = ev["device"]["kind"]
    least = max(flops / trace_reduce.peak_flops(kind),
                bytes_ / HBM_BYTES_PER_S[kind])
    return 100.0 * least / seconds


def traced_rows_routed(ev):
    """Mean over the expert layers and over traced steps of the (token,
    slot) pairs routed to held experts. The program publishes each step's
    counts, in step order, as a `side_fetch` event of its step log once
    they are on the host (Executor._publish_side_fetches), which is at
    most `steps_in_flight` dispatches later: the last `trace_steps` -
    `steps_in_flight` events are traced steps whatever the lag. The
    window's mean would not do: the router trains, and the rows it sends
    here drift from step to step (PERF.md section 4). None where the
    program books none."""
    from paddle_tpu import telemetry

    counts = [e["values"] for e in telemetry.recent_events(kind="side_fetch")
              if e.get("metric") == "moe_rows_routed"]
    cell = ev["cell"]
    tail = counts[-max(1, cell["trace_steps"] - cell["steps_in_flight"]):]
    flat = [v for values in tail for v in values]
    return sum(flat) / len(flat) if flat else None
