"""From a profiler trace (`*.xplane.pb`) to what the metrics need: the
device's busy seconds in the traced window, the time of each device
operation, which of them are Mosaic (Pallas) kernels, and the idle gaps
labelled by what the host was doing. Also the table of peaks.

Read with jax.profiler.ProfileData alone. The planes and lines named
here are those of the TPU v5e traces this benchmark took (PR 23): a plane
`/device:TPU:<n>` per chip whose line `XLA Ops` holds one event per
executed HLO instruction, named by the instruction's whole text
(`%conv2d.51 = bf16[...] custom-call(...), custom_call_target=
"tpu_custom_call"`), back to back; and a plane `/host:CPU` whose python
thread's line holds the TraceAnnotations, on the same clock. The steps of
the present cells hold no loop, so none of their events encloses another;
a `while` (a run_steps window's scan) does enclose its body's events on
that line, which is what self_seconds() is for.
"""

import glob
import os
import re

# Per chip, keyed by jax's `device_kind`. Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16. (Its 819 GB/s of HBM
# joins the table with the first metric that reads it.)
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12},
}


class UnknownDeviceError(KeyError):
    """A device whose published peaks nobody wrote into PEAKS."""


def peak_flops(device_kind):
    try:
        return PEAKS[device_kind]["bf16_flops_per_s"]
    except KeyError:
        raise UnknownDeviceError(
            "no published peak on record for device_kind %r: add it to "
            "benchmarks/trace_reduce.py PEAKS with its source"
            % (device_kind,)) from None


DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
# gaps shorter than this are the device's own pauses between operations
# and are summed under "between ops", not laid at the host's door
MIN_GAP_S = 20e-6


def union_seconds(intervals):
    """Total length covered by [start, end) intervals, and the gaps
    between the covered stretches as (start, end), in time order."""
    covered, gaps, reach = 0.0, [], None
    for start, end in sorted(intervals):
        if reach is None:
            covered, reach = end - start, end
        elif start > reach:
            gaps.append((reach, start))
            covered += end - start
            reach = end
        elif end > reach:
            covered += end - reach
            reach = end
    return covered, gaps


def self_seconds(events):
    """{name: seconds} where an operation that encloses others (a while
    or a conditional and its body) is charged only what its children
    leave: `events` are (name, start, end) on one line."""
    totals, stack = {}, []
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            stack.pop()
        totals[name] = totals.get(name, 0.0) + (end - start)
        if stack:
            parent = stack[-1][0]
            inside = min(end, stack[-1][2]) - start
            totals[parent] = totals.get(parent, 0.0) - inside
        stack.append((name, start, end))
    return totals


def label_gaps(gaps, host_spans):
    """{label: seconds} of idle: each gap goes to the host span
    (name, start, end) that covers most of it, `none` if no span
    touches it, `between ops` if it is shorter than MIN_GAP_S."""
    totals = {}
    for g0, g1 in gaps:
        label = "between ops"
        if g1 - g0 >= MIN_GAP_S:
            best, label = 0.0, "none"
            for name, s0, s1 in host_spans:
                overlap = min(g1, s1) - max(g0, s0)
                if overlap > best:
                    best, label = overlap, name
        totals[label] = totals.get(label, 0.0) + (g1 - g0)
    return totals


def reduce_events(device_ops, host_spans, kernels=()):
    """The reduction itself, on plain lists (seconds on one clock).
    device_ops: {device: [(name, start, end)]}; host_spans: [(name,
    start, end)]; kernels: names of the operations that are Mosaic
    kernels. Busy and window are averaged over the devices."""
    busy = window = 0.0
    op_totals, gap_totals = {}, {}
    for events in device_ops.values():
        covered, gaps = union_seconds([(s, e) for _, s, e in events])
        busy += covered
        window += (max(e for _, _, e in events)
                   - min(s for _, s, _ in events))
        for name, secs in self_seconds(events).items():
            op_totals[name] = op_totals.get(name, 0.0) + secs
        for label, secs in label_gaps(gaps, host_spans).items():
            gap_totals[label] = gap_totals.get(label, 0.0) + secs
    n = len(device_ops)

    def longest_first(totals):
        return sorted(([k, v / n] for k, v in totals.items()),
                      key=lambda kv: -kv[1])

    return {"busy_s": busy / n, "window_s": window / n,
            "device_ops": longest_first(op_totals),
            "idle_gaps": longest_first(gap_totals),
            "kernel_s": sum(op_totals.get(k, 0.0) for k in set(kernels)) / n}


def read_xplane(path):
    """(device_ops, host_spans, kernels) of one xplane file, for
    reduce_events. Times in seconds from the trace's own origin."""
    from jax.profiler import ProfileData

    device_ops, host_spans, kernels = {}, [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    name = op_label(ev.name)
                    events.append((name, ev.start_ns * 1e-9,
                                   (ev.start_ns + ev.duration_ns) * 1e-9))
                    if is_mosaic(ev.name):
                        kernels.add(name)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append(
                            (ev.name[len(HOST_SPAN_PREFIX):],
                             ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return ({k: v for k, v in device_ops.items() if v}, host_spans, kernels)


_INSTRUCTION = re.compile(r"^%([\w\-.]+?)(?:\.\d+)? = ")


def op_label(text):
    """The name an operation goes by in the breakdown: the HLO
    instruction's name without its number, so the 53 `%conv2d.<n>` of a
    step are one row `conv2d` (a Pallas kernel's instruction is named
    after the pallas_call, a fusion after its kind)."""
    found = _INSTRUCTION.match(text)
    return found.group(1) if found else text[:64]


def is_mosaic(text):
    """Whether an HLO instruction is a Mosaic (Pallas) custom call."""
    return 'custom_call_target="tpu_custom_call"' in text


def reduce_dir(trace_dir):
    """reduce_events() of the newest xplane file under `trace_dir`; None
    if there is none or it holds no operation on a device."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    device_ops, host_spans, kernels = read_xplane(paths[-1])
    if not device_ops:
        return None
    return reduce_events(device_ops, host_spans, kernels)
