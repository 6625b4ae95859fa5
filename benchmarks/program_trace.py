#!/usr/bin/env python3
"""From the traced steps' profiler trace (`*.xplane.pb`) to what the
*program* did: device time by op_role and by program op, the program's
own host spans with their self times, and the idle gaps labelled by the
program span they fall under.

    python3 benchmarks/program_trace.py <trace_dir> [--dump]

prints the tables PERF.md §5 is written from; `--dump` prints the first
device operation's name and stats instead (what a new runtime's trace
says about an operation). trace_reduce.py answers "how busy was the
device and with which HLO kinds"; this file joins the same events to the
program through two things the executor leaves behind:

  * every HLO instruction's `op_name` holds the named scopes its program
    op was lowered under: `jit(fn)/pd_role.<op_role>/pd.<type>/<prim>`.
    The v5e trace (PR 24, first chip call) carries it as the stat
    `tf_op` of the operation's event *metadata*, which
    jax.profiler.ProfileData does not show; so the events and their
    times come from ProfileData, and the provenance from a second, plain
    pass over the file's wire format. Tried in this order, the first that
    yields an op_name for some operation wins: a stat of the event
    itself, a stat of its metadata, `metadata={op_name="..."}` in the
    instruction's text. A parent program has `pd.<type>` and no role:
    its time is all `unattributed` by role.
  * the program's `tracing.span`s are `pd.<name>` TraceAnnotations on
    `/host:CPU`, on the device's clock: `pd.step` with its phases
    `pd.prepare`, `pd.launch`, `pd.bookkeep`, `pd.writeback`, and the
    input pipeline's `pd.input_wait` / `pd.input_build` on their threads.

A step on the device is one event of the line `XLA Modules` (one run of
the compiled step); without that line the whole trace is one step.
"""

import functools
import glob
import os
import re
import statistics
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import trace_reduce  # noqa: E402

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "pd."
ROLES = ("forward", "backward", "optimize", "unattributed")
OP_NAME_STATS = ("tf_op", "op_name")

_ROLE = re.compile(r"pd_role\.([A-Za-z0-9_]+)")
_TYPE = re.compile(r"(?<![A-Za-z0-9_])pd\.(coll\.[A-Za-z0-9_.\-]+|[A-Za-z0-9_@]+)")
_TEXT_OP_NAME = re.compile(r'op_name="([^"]*)"')


def provenance_of(op_name):
    """(role, program op) from an HLO op_name: the outermost
    `pd_role.<role>` and the outermost `pd.<type>` scope; ("unattributed",
    None) where there is neither."""
    if not op_name:
        return "unattributed", None
    role = _ROLE.search(op_name)
    kind = _TYPE.search(op_name)
    if role is None and kind is None:
        return "unattributed", None
    return (role.group(1) if role else "unattributed",
            kind.group(1) if kind else None)


# --- the metadata's stats, from the wire format ----------------------------

def metadata_op_names(path):
    """{plane name: {event metadata name: op_name}} from the stats of the
    XEventMetadata entries (XPlane.event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7). The decoder of the wire format is the
    program's (`paddle_tpu.xplane.fields`: plain protobuf, nothing of the
    program in it)."""
    from paddle_tpu.xplane import fields as _fields

    with open(path, "rb") as f:
        space = f.read()
    found = {}
    for number, wire, plane in _fields(space):
        if number != 1 or wire != 2:
            continue
        plane_name, events, stat_names = "", [], {}
        for number, wire, value in _fields(plane):
            if number == 2 and wire == 2:
                plane_name = value.decode("utf-8", "replace")
            elif number == 4 and wire == 2:
                events.extend(v for n, w, v in _fields(value)
                              if n == 2 and w == 2)
            elif number == 5 and wire == 2:
                key = name = None
                for n, w, v in _fields(value):
                    if n == 1 and w == 0:
                        key = v
                    elif n == 2 and w == 2:
                        for n2, w2, v2 in _fields(v):
                            if n2 == 2 and w2 == 2:
                                name = v2.decode("utf-8", "replace")
                if key is not None and name is not None:
                    stat_names[key] = name
        if not plane_name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        wanted = {k for k, v in stat_names.items() if v in OP_NAME_STATS}
        names = found.setdefault(plane_name, {})
        for event in events:
            name, op_name = None, None
            for n, w, v in _fields(event):
                if n == 2 and w == 2:
                    name = v.decode("utf-8", "replace")
                elif n == 5 and w == 2:
                    key = text = None
                    for n2, w2, v2 in _fields(v):
                        if n2 == 1 and w2 == 0:
                            key = v2
                        elif n2 == 5 and w2 == 2:
                            text = v2.decode("utf-8", "replace")
                        elif n2 == 7 and w2 == 0:
                            text = stat_names.get(v2)
                    if key in wanted and text:
                        op_name = text
            if name is not None and op_name:
                names[name] = op_name
    return found


# --- reading -----------------------------------------------------------------

def read_xplane(path):
    """What one xplane file holds, for reduce_events(): `devices` =
    {plane: {"modules": [(name, start, end)], "ops": [(label, role, op,
    start, end)]}}, `spans` = [(name, thread, start, end, step)] of the
    program's `pd.*` annotations, `source` = which of "event_stat",
    "metadata_stat", "hlo_text" gave the op_names (None: none did).
    Seconds from the trace's own origin."""
    from jax.profiler import ProfileData

    raw, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            device = raw.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    device["modules"] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif line.name == trace_reduce.OPS_LINE:
                    for ev in line.events:
                        stat = next((str(v) for k, v in ev.stats
                                     if k in OP_NAME_STATS), None)
                        device["ops"].append(
                            (ev.name, stat, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
        else:
            # a line is a thread; two Python threads are both `python3`,
            # so a thread goes by its line's name and place in the plane
            for index, line in enumerate(plane.lines):
                thread = "%s#%d" % (line.name, index)
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        step = next((v for k, v in ev.stats if k == "step"),
                                    None)
                        spans.append(
                            (ev.name[len(SPAN_PREFIX):], thread,
                             ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, step))
    raw = {k: v for k, v in raw.items() if v["ops"]}

    def from_text(name):
        found = _TEXT_OP_NAME.search(name)
        return found.group(1) if found else None

    source, lookup = None, None
    if any(stat for d in raw.values() for _, stat, _, _ in d["ops"]):
        source = "event_stat"
    elif raw:
        by_plane = metadata_op_names(path)
        if any(by_plane.values()):
            source = "metadata_stat"
            lookup = by_plane
        elif any(from_text(name) for d in raw.values()
                 for name, _, _, _ in d["ops"]):
            source = "hlo_text"
    devices = {}
    for plane, device in raw.items():
        ops = []
        for name, stat, start, end in device["ops"]:
            if source == "metadata_stat":
                stat = lookup.get(plane, {}).get(name)
            elif source == "hlo_text":
                stat = from_text(name)
            role, op = provenance_of(stat)
            ops.append((trace_reduce.op_label(name), role, op, start, end))
        devices[plane] = {"modules": device["modules"], "ops": ops}
    return devices, spans, source


# --- reducing ----------------------------------------------------------------

def _steps_of(device):
    """[(start, end, [op events])]: the runs of the module that took the
    most time, each with the operations that started inside it; one step
    over everything where the trace has no module line."""
    ops = sorted(device["ops"], key=lambda e: e[3])
    by_name = {}
    for name, start, end in device["modules"]:
        by_name[name] = by_name.get(name, 0.0) + (end - start)
    if not by_name:
        return [(ops[0][3], max(e[4] for e in ops), ops)]
    main = max(by_name, key=by_name.get)
    steps = []
    for name, start, end in sorted(device["modules"], key=lambda m: m[1]):
        if name == main:
            steps.append((start, end, [e for e in ops
                                       if start <= e[3] < end]))
    return [s for s in steps if s[2]]


def _nest(spans):
    """[(span, depth-1 children)] per thread by containment: spans are
    (name, thread, start, end, step)."""
    nested = []
    by_thread = {}
    for span in spans:
        by_thread.setdefault(span[1], []).append(span)
    for thread_spans in by_thread.values():
        stack = []
        for span in sorted(thread_spans, key=lambda s: (s[2], -s[3])):
            while stack and stack[-1][0][3] <= span[2]:
                stack.pop()
            entry = (span, [])
            if stack:
                stack[-1][1].append(span)
            stack.append(entry)
            nested.append(entry)
    return nested


def label_gaps(gaps, spans):
    """{label: seconds} of idle. Each stretch of a gap of MIN_GAP_S or
    more goes to the innermost program span over it (the shortest one
    that covers it), so a gap that runs from `prepare` into `launch` is
    split between them; what no span covers is `none`; shorter gaps are
    `between ops`."""
    totals = {}
    innermost_first = sorted(spans, key=lambda s: s[3] - s[2])
    for g0, g1 in gaps:
        if g1 - g0 < trace_reduce.MIN_GAP_S:
            totals["between ops"] = totals.get("between ops", 0.0) + g1 - g0
            continue
        open_parts = [(g0, g1)]
        for name, _, s0, s1, _ in innermost_first:
            if s1 <= g0 or s0 >= g1 or not open_parts:
                continue
            left = []
            for p0, p1 in open_parts:
                o0, o1 = max(p0, s0), min(p1, s1)
                if o1 <= o0:
                    left.append((p0, p1))
                    continue
                totals[name] = totals.get(name, 0.0) + (o1 - o0)
                if p0 < o0:
                    left.append((p0, o0))
                if o1 < p1:
                    left.append((o1, p1))
            open_parts = left
        for p0, p1 in open_parts:
            totals["none"] = totals.get("none", 0.0) + (p1 - p0)
    return totals


def reduce_events(devices, spans, source=None):
    """The reduction itself, on read_xplane()'s plain lists.

    -> {"source", "device_steps": [{"device", "window_s", "busy_s",
    "by_role": {role: s}, "by_op": {(role, op or HLO kind): s}}] in time
    order, "host_steps": [{"step", "seconds", "phases": {name: s},
    "self_s"}] one per `pd.step`, "host_spans": {name: [seconds]} of every
    `pd.*` span, "host_self": {name: seconds} summed self times,
    "idle_gaps": {label: s} over the spans of the threads that hold a
    `pd.step`}."""
    device_steps, gap_totals = [], {}
    nested = _nest(spans)
    step_threads = {s[1] for s, _ in nested if s[0] == "step"}
    gap_spans = [s for s in spans if s[1] in step_threads]
    for plane, device in sorted(devices.items()):
        _, gaps = trace_reduce.union_seconds(
            [(e[3], e[4]) for e in device["ops"]])
        for label, secs in label_gaps(gaps, gap_spans).items():
            gap_totals[label] = gap_totals.get(label, 0.0) + secs
        for start, end, ops in _steps_of(device):
            busy, _ = trace_reduce.union_seconds([(e[3], e[4]) for e in ops])
            keyed = trace_reduce.self_seconds(
                [((role, op if op is not None else "(%s)" % label),
                  s, e) for label, role, op, s, e in ops])
            by_role = dict.fromkeys(ROLES, 0.0)
            for (role, _), secs in keyed.items():
                by_role[role if role in by_role else "unattributed"] += secs
            device_steps.append({
                "device": plane, "window_s": end - start, "busy_s": busy,
                "by_role": by_role, "by_op": keyed})
    host_steps, host_spans, host_self = [], {}, {}
    for span, children in nested:
        name, _, start, end, step = span
        inside = sum(c[3] - c[2] for c in children)
        host_spans.setdefault(name, []).append(end - start)
        host_self[name] = host_self.get(name, 0.0) + (end - start) - inside
        if name == "step":
            phases = {}
            for child in children:
                phases[child[0]] = phases.get(child[0], 0.0) \
                    + (child[3] - child[2])
            host_steps.append({"step": step, "start": start,
                               "seconds": end - start, "phases": phases,
                               "self_s": (end - start) - inside})
    host_steps.sort(key=lambda s: s["start"])
    return {"source": source, "device_steps": device_steps,
            "host_steps": host_steps, "host_spans": host_spans,
            "host_self": host_self, "idle_gaps": gap_totals}


@functools.lru_cache(maxsize=4)
def _reduce_file(path, _mtime):
    return reduce_events(*read_xplane(path))


def _newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def reduce_dir(trace_dir):
    """reduce_events() of the newest xplane file under `trace_dir`; None
    if there is none."""
    path = _newest_xplane(trace_dir)
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path))


# --- what the layer metrics read ---------------------------------------------

def of_evidence(ev):
    """The reduction of the traced steps of the run `ev` is the evidence
    of (run.TRACE_DIR/<cell>), or None: not traced, or no file."""
    from benchmarks import run

    return reduce_dir(os.path.join(run.TRACE_DIR, ev["cell"]["name"]))


def median_host_ms(ev, of_step):
    """Median over the traced `pd.step` spans of `of_step(step)` seconds,
    in ms; None where the trace holds no such span (a parent program)."""
    reduced = of_evidence(ev)
    if reduced is None or not reduced["host_steps"]:
        return None
    return 1e3 * statistics.median(of_step(s) for s in reduced["host_steps"])


def median_span_ms(ev, name):
    reduced = of_evidence(ev)
    if reduced is None or not reduced["host_spans"].get(name):
        return None
    return 1e3 * statistics.median(reduced["host_spans"][name])


def median_role_ms(ev, role):
    """Median over the traced steps of the device ms booked to `role`;
    None without a device trace, and for a named role where no operation
    carried a role (a parent program: everything is unattributed)."""
    reduced = of_evidence(ev)
    if reduced is None or not reduced["device_steps"]:
        return None
    steps = reduced["device_steps"]
    if role != "unattributed" and not any(
            s["by_role"][r] for s in steps for r in ROLES[:3]):
        return None
    return 1e3 * statistics.median(s["by_role"][role] for s in steps)


def build_seconds(phases):
    """Sum of executor_build_seconds_total over all programs for the
    given phases, read in process; None where the program books no such
    counter."""
    from paddle_tpu import telemetry

    series = telemetry.read_series("executor_build_seconds_total")
    if not series:
        return None
    return sum(v for k, v in series.items()
               if k.rsplit("phase=", 1)[-1] in phases)


# --- the tables --------------------------------------------------------------

def _table(title, rows, unit="ms"):
    print("\n%s" % title)
    for name, value in rows:
        print("  %-44s %12.3f %s" % (name, value, unit))


def print_tables(reduced):
    steps, hosts = reduced["device_steps"], reduced["host_steps"]
    print("provenance of device operations: %s" % reduced["source"])
    if steps:
        n = len(steps)
        print("device steps: %d, busy %.3f ms a step (median), window "
              "%.3f ms" % (n, 1e3 * statistics.median(
                  s["busy_s"] for s in steps), 1e3 * statistics.median(
                      s["window_s"] for s in steps)))
        _table("device ms a step by op_role (median over steps)",
               [(r, 1e3 * statistics.median(s["by_role"][r] for s in steps))
                for r in ROLES])
        totals = {}
        for s in steps:
            for key, secs in s["by_op"].items():
                totals[key] = totals.get(key, 0.0) + secs / n
        rows = sorted(totals.items(), key=lambda kv: -kv[1])[:30]
        _table("device ms a step by (op_role, program op), mean over steps; "
               "(name) = no pd. scope, the HLO kind",
               [("%s / %s" % key, 1e3 * secs) for key, secs in rows])
    if hosts:
        names = sorted({p for h in hosts for p in h["phases"]})
        _table("host ms of one pd.step (median over %d)" % len(hosts),
               [("step", 1e3 * statistics.median(
                   h["seconds"] for h in hosts))]
               + [("  " + p, 1e3 * statistics.median(
                   h["phases"].get(p, 0.0) for h in hosts)) for p in names]
               + [("  (self: between phases)", 1e3 * statistics.median(
                   h["self_s"] for h in hosts))])
    _table("every pd.* host span: count x median ms",
           [("%s x%d" % (name, len(v)), 1e3 * statistics.median(v))
            for name, v in sorted(reduced["host_spans"].items())])
    _table("self ms of pd.* host spans, summed over the trace",
           [(name, 1e3 * v) for name, v in sorted(
               reduced["host_self"].items(), key=lambda kv: -kv[1])])
    _table("device idle by innermost program span, summed over the trace",
           [(name, 1e3 * v) for name, v in sorted(
               reduced["idle_gaps"].items(), key=lambda kv: -kv[1])])


def dump_first_op(trace_dir):
    """The first device operation's name and stats, and its metadata's
    op_name: what this runtime's trace says about an operation."""
    from jax.profiler import ProfileData

    path = _newest_xplane(trace_dir)
    by_plane = metadata_op_names(path)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        print("plane %s, lines %s" % (plane.name,
                                      [ln.name for ln in plane.lines]))
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                print("name: %s" % ev.name)
                for key, value in ev.stats:
                    print("  stat %s = %s" % (key, value))
                print("  metadata op_name: %s" % by_plane.get(
                    plane.name, {}).get(ev.name))
                return


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0].startswith("-"):
        sys.stderr.write(__doc__.split("\n\n")[1] + "\n")
        return 2
    if "--dump" in argv[1:]:
        dump_first_op(argv[0])
        return 0
    reduced = reduce_dir(argv[0])
    if reduced is None:
        sys.stderr.write("no *.xplane.pb under %s\n" % argv[0])
        return 1
    print_tables(reduced)
    return 0


if __name__ == "__main__":
    sys.exit(main())
