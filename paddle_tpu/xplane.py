"""XSpace/XPlane (.xplane.pb) wire-format parser + HLO->IR-op attribution.

jax.profiler.trace writes xplane protos; the tensorboard profile plugin in
this image can't load them (TF version skew), so this decodes the wire
format directly — only the fields needed to aggregate device-op time:

  XSpace.planes=1 / XPlane{name=2, lines=3, event_metadata=4}
  XLine{name=2, timestamp_ns=3, events=4}
  XEvent{metadata_id=1, offset_ps=2, duration_ps=3}
  XEventMetadata map entry {key=1, value=2} / XEventMetadata{id=1, name=2}

The executor wraps every IR op's lowering in jax.named_scope("pd.<type>")
(executor._exec_op), so the compiled module's per-instruction
`metadata={op_name="jit(fn)/.../pd.<type>/<prim>"}` carries the IR op that
emitted each HLO instruction — including the representative op of each
fusion. `hlo_op_names` extracts that mapping from the optimized HLO text
and `attribute` joins it with the xplane per-instruction timings, giving
the reference ParseEvents-style "which op eats the step" table for the
whole-block jit (reference platform/profiler.h:137-166)."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional

__all__ = ["aggregate", "aggregate_dir", "aggregate_lines", "hlo_op_names",
           "attribute", "category", "fields", "parse_plane",
           "plane_events", "timeline_dir", "COLLECTIVE_KINDS",
           "collective_kind", "hlo_collectives", "exposed_in_line",
           "collective_events_dir"]


def _varint(buf, i):
    r = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << shift
        if not b & 0x80:
            return r, i
        shift += 7


def fields(buf):
    """Yield (field_number, wire_type, value) over a serialized message."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i: i + ln]
            i += ln
        elif wt == 5:
            v = buf[i: i + 4]
            i += 4
        elif wt == 1:
            v = buf[i: i + 8]
            i += 8
        else:
            raise ValueError(f"wire type {wt}")
        yield fno, wt, v


def parse_plane(buf):
    name = ""
    lines = []
    meta = {}
    for fno, wt, v in fields(buf):
        if fno == 2 and wt == 2:
            name = v.decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            lines.append(v)
        elif fno == 4 and wt == 2:
            k = None
            mname = None
            for f2, w2, v2 in fields(v):
                if f2 == 1 and w2 == 0:
                    k = v2
                elif f2 == 2 and w2 == 2:
                    for f3, w3, v3 in fields(v2):
                        if f3 == 1 and w3 == 0 and k is None:
                            k = v3
                        elif f3 == 2 and w3 == 2:
                            mname = v3.decode("utf-8", "replace")
            if k is not None and mname is not None:
                meta[k] = mname
    return name, lines, meta


def aggregate_lines(path) -> Dict[str, list]:
    """-> {plane_name: [{event_name: total_ps} per XLine]} — per-line
    aggregation so callers can dedup a plane's derived lines (xplane device
    planes repeat each instruction on the raw XLA-op line AND on derived
    step/module/framework-op lines)."""
    buf = open(path, "rb").read()
    out: Dict[str, list] = {}
    for fno, wt, v in fields(buf):
        if fno != 1 or wt != 2:
            continue
        pname, lines, meta = parse_plane(v)
        per_line = out.setdefault(pname, [])
        for line in lines:
            agg: Dict[str, int] = {}
            for f2, w2, v2 in fields(line):
                if f2 != 4 or w2 != 2:   # XLine.events
                    continue
                mid = dur = 0
                for f3, w3, v3 in fields(v2):
                    if f3 == 1 and w3 == 0:
                        mid = v3
                    elif f3 == 3 and w3 == 0:
                        dur = v3
                name = meta.get(mid, f"#{mid}")
                agg[name] = agg.get(name, 0) + dur
            per_line.append(agg)
    return out


def plane_events(path) -> Dict[str, list]:
    """-> {plane_name: [line, ...]} where each line is
    {"name": str, "timestamp_ns": int,
     "events": [(event_name, offset_ps, duration_ps), ...]}.

    The full-resolution view of the same planes `aggregate_lines` sums:
    XLine.timestamp_ns anchors the line on the wall clock and
    XEvent.offset_ps places each event within the line, so
    timestamp_ns*1e3 + offset_ps orders events across lines and planes —
    the timeline the waterfall/duty-cycle analysis needs."""
    buf = open(path, "rb").read()
    out: Dict[str, list] = {}
    for fno, wt, v in fields(buf):
        if fno != 1 or wt != 2:
            continue
        pname, lines, meta = parse_plane(v)
        per_line = out.setdefault(pname, [])
        for line in lines:
            lname = ""
            ts_ns = 0
            events = []
            for f2, w2, v2 in fields(line):
                if f2 == 2 and w2 == 2:      # XLine.name
                    lname = v2.decode("utf-8", "replace")
                elif f2 == 3 and w2 == 0:    # XLine.timestamp_ns
                    ts_ns = v2
                elif f2 == 4 and w2 == 2:    # XLine.events
                    mid = off = dur = 0
                    for f3, w3, v3 in fields(v2):
                        if f3 == 1 and w3 == 0:
                            mid = v3
                        elif f3 == 2 and w3 == 0:
                            off = v3
                        elif f3 == 3 and w3 == 0:
                            dur = v3
                    events.append((meta.get(mid, f"#{mid}"), off, dur))
            per_line.append({"name": lname, "timestamp_ns": ts_ns,
                             "events": events})
    return out


def timeline_dir(trace_dir) -> list:
    """Merge every .xplane.pb under trace_dir into a flat list of
    {"plane", "line", "timestamp_ns", "events"} records (events carry
    (name, offset_ps, duration_ps)), device planes first."""
    records = []
    for p in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True):
        for pname, lines in plane_events(p).items():
            for line in lines:
                records.append({"plane": pname, "line": line["name"],
                                "timestamp_ns": line["timestamp_ns"],
                                "events": line["events"]})
    records.sort(key=lambda r: (not r["plane"].startswith("/device:"),
                                r["plane"], r["timestamp_ns"]))
    return records


def aggregate(path) -> Dict[str, Dict[str, int]]:
    """-> {plane_name: {event_name: total_ps}} (lines summed)."""
    out = {}
    for pname, per_line in aggregate_lines(path).items():
        agg = out.setdefault(pname, {})
        for line_agg in per_line:
            for name, ps in line_agg.items():
                agg[name] = agg.get(name, 0) + ps
    return out


_INSTR_LIKE = re.compile(r"[\w.\-]+\Z")


def instr_like(name: str) -> bool:
    """True when an event name looks like an HLO instruction ('dot.4',
    'fusion.12', 'reduce-window') rather than host bookkeeping. Host
    planes interleave python-source events ('$profiler.py:226 trace'),
    runtime markers ('TfrtCpuExecutable::Execute',
    'ThunkExecutor::Execute (wait...)') and dispatch wrappers
    ('PjitFunction(f)') with the real instruction events — all of which
    contain '$', ':', '(', or spaces that no instruction name can. The
    program's own spans (tracing.span: 'pd.step', 'pd.launch', ...) sit
    on the same host plane and are host time, not instructions."""
    return (_INSTR_LIKE.fullmatch(name) is not None
            and not name.startswith("pd."))


def aggregate_dir(trace_dir) -> Dict[str, int]:
    """Merge the DEVICE planes of every .xplane.pb under trace_dir into ONE
    {event_name: total_ps} map. Within a device plane an instruction shows
    up once per line that mentions it (raw XLA-op line + derived
    step/module lines), so per plane we take the per-name MAX across lines
    — one line's worth, not the double-counted sum — then sum across planes
    (per-core time adds up) and files.

    Fallback: traces with no '/device:' plane at all (e.g. CPU-backend jax
    writes only host planes) merge the host planes instead — with the SAME
    per-name max-across-lines dedup (host planes repeat events on derived
    lines too), and restricted to instruction-like event names so python
    source events and runtime markers (`instr_like`) don't swamp the
    table."""
    device: Dict[str, int] = {}
    host: Dict[str, int] = {}
    for p in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True):
        for pname, per_line in aggregate_lines(p).items():
            target = device if pname.startswith("/device:") else host
            plane: Dict[str, int] = {}
            for line_agg in per_line:
                for name, ps in line_agg.items():
                    if target is host and not instr_like(name):
                        continue
                    plane[name] = max(plane.get(name, 0), ps)
            for name, ps in plane.items():
                target[name] = target.get(name, 0) + ps
    return device if device else host


_HLO_LINE = re.compile(
    r"%?([\w.\-]+)\s*=\s*\S.*metadata=\{[^}]*op_name=\"([^\"]*)\"")
_PD_SCOPE = re.compile(r"pd\.([A-Za-z0-9_@]+)")
# framework collective call sites (jax.named_scope("pd.coll.<site>") in
# parallel/): the site component may contain dots, which _PD_SCOPE's
# character class deliberately excludes, so it gets its own regex
_PD_COLL = re.compile(r"pd\.coll\.([A-Za-z0-9_.\-]+)")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction_name: ir_op_type} from optimized-HLO text, using the
    pd.<type> named-scope component of each op_name (instructions outside
    any pd scope — infeed, copies, jax-internal reductions — map to their
    trailing op_name component). Instructions inside a pd.coll.<site>
    collective scope map to 'coll.<site>' so the roofline table shows the
    emitting call site, not a bare 'coll'."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.search(line)
        if not m:
            continue
        instr, op_name = m.group(1), m.group(2)
        coll = _PD_COLL.search(op_name)
        if coll:
            out[instr] = "coll." + coll.group(1)
            continue
        pd = _PD_SCOPE.search(op_name)
        if pd:
            out[instr] = pd.group(1)
        else:
            tail = [t for t in op_name.split("/") if t]
            out[instr] = tail[-1] if tail else op_name
    return out


def attribute(instr_ps: Dict[str, int],
              opname_by_instr: Dict[str, str],
              other_label: Optional[str] = None) -> Dict[str, int]:
    """Join per-instruction timings with the HLO mapping -> per-IR-op-type
    total picoseconds. Events with no HLO mapping (host bookkeeping,
    runtime internals) are dropped, or pooled under `other_label`."""
    agg: Dict[str, int] = {}
    for instr, ps in instr_ps.items():
        op = opname_by_instr.get(instr)
        if op is None:
            if other_label is None:
                continue
            op = other_label
        agg[op] = agg.get(op, 0) + ps
    return agg


def category(name: str) -> str:
    """HLO instruction text -> coarse op kind ('%fusion.123 = ...' ->
    'fusion'; falls back to the leading token)."""
    tok = name.lstrip("%").split(" ", 1)[0]
    return tok.split(".")[0]


# --- collective classification ----------------------------------------------

# (kind, substring patterns) in match order. Covers the HLO spellings
# ('all-reduce.3', 'all-gather-start'), the squashed forms some runtimes
# emit ('AllReduce'), and the framework-level names ('ppermute'). The
# first matching kind wins, so narrower kinds must precede kinds whose
# patterns are substrings of theirs (tools/check_registry.py lints this
# table for self-consistency: every pattern must classify as its own
# kind, or a new entry silently falls into another bucket).
COLLECTIVE_KINDS = (
    ("reduce-scatter", ("reduce-scatter", "reducescatter",
                        "reduce_scatter")),
    ("all-reduce", ("all-reduce", "allreduce", "all_reduce",
                    "cross-replica-sum")),
    ("all-gather", ("all-gather", "allgather", "all_gather")),
    ("all-to-all", ("all-to-all", "alltoall", "all_to_all")),
    ("collective-permute", ("collective-permute", "collectivepermute",
                            "collective_permute", "ppermute")),
    ("send/recv", ("send", "recv")),
)

# busbw factor per kind (nccl-tests convention): the ratio of bytes that
# actually cross links to bytes in the buffer, as a function of the
# participant count n. all-reduce moves each byte out and back
# (2(n-1)/n), gather/scatter/alltoall move the (n-1)/n remote fraction,
# a permute hop and a send/recv pair move the whole buffer once.
_BUSBW_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n if n > 1 else 0.0,
    "all-gather": lambda n: (n - 1) / n if n > 1 else 0.0,
    "reduce-scatter": lambda n: (n - 1) / n if n > 1 else 0.0,
    "all-to-all": lambda n: (n - 1) / n if n > 1 else 0.0,
    "collective-permute": lambda n: 1.0,
    "send/recv": lambda n: 1.0,
}


def collective_kind(name: str) -> Optional[str]:
    """Collective kind for an HLO instruction / xplane event name, or None
    for non-collective events ('fusion.3', 'dot.1', 'infeed')."""
    low = name.lower()
    for kind, pats in COLLECTIVE_KINDS:
        if any(p in low for p in pats):
            return kind
    return None


def busbw_factor(kind: str, n: int) -> float:
    fn = _BUSBW_FACTOR.get(kind)
    return fn(max(int(n), 1)) if fn else 0.0


# dtype token -> bytes per element for HLO shape strings ('f32[4,128]')
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_TOK = re.compile(r"([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    """Payload bytes of an HLO shape string — 'f32[4,128]{1,0}', 'bf16[]'
    or a tuple '(f32[8], f32[32])'. Async '-start' ops carry (input,
    output) tuples aliasing one transfer, so tuples report their largest
    component, not the sum. Unknown dtypes count 4 bytes/elem."""
    sizes = []
    for dtype, dims in _SHAPE_TOK.findall(shape_text):
        if dtype == "token":
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dtype, 4))
    if not sizes:
        return 0
    if shape_text.lstrip().startswith("("):
        return max(sizes)
    return sum(sizes)


_HLO_COLL = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(\([^)]*\)|[\w\[\]{},:]+)\s+([\w\-]+)(?:\(|\b)")


def hlo_collectives(hlo_text: str) -> Dict[str, dict]:
    """{instruction_name: {"kind", "site", "bytes"}} for the collective
    instructions of one optimized-HLO module. kind is classified from the
    opcode via COLLECTIVE_KINDS; site is the pd.coll.<site> named-scope
    component of metadata op_name (None for GSPMD-inserted collectives
    outside any tagged region); bytes is the output-shape payload — the
    '-done' half of an async start/done pair reports 0 bytes so the pair's
    payload is not double-counted (its device time still joins the site)."""
    out: Dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _HLO_COLL.match(line)
        if not m:
            continue
        instr, shape, opcode = m.group(1), m.group(2), m.group(3)
        kind = collective_kind(opcode)
        if kind is None:
            continue
        site = near = None
        mm = _HLO_LINE.search(line)
        if mm:
            c = _PD_COLL.search(mm.group(2))
            if c:
                site = c.group(1)
            else:
                # GSPMD-inserted collective: no framework line emitted it,
                # but it inherits the op_name of the op it was split from —
                # the pd.<op_type> scope names the responsible layer
                s = _PD_SCOPE.search(mm.group(2))
                if s:
                    near = s.group(1)
        nbytes = 0 if opcode.endswith("-done") else _shape_bytes(shape)
        out[instr] = {"kind": kind, "site": site, "near": near,
                      "bytes": nbytes}
    return out


def hlo_participants(hlo_text: str) -> Optional[int]:
    """Participant count of the module's collectives, parsed from
    replica_groups — either the iota form '<=[4]' or explicit groups
    '{{0,1,2,3}}'. None when the module has no replica_groups."""
    m = re.search(r"replica_groups=\[[0-9,]+\]<=\[(\d+)\]", hlo_text)
    if m:
        return int(m.group(1))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", hlo_text)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip()])
    return None


def exposed_in_line(events) -> Dict[str, int]:
    """{collective_event_name: exposed_ps} for one line's (name, offset_ps,
    duration_ps) events: the part of each collective's duration covered by
    NO concurrent non-collective event. An async all-reduce whose '-done'
    wait runs under a fusion kernel is hidden (overlapped); collective
    time with nothing else on the line is exposed step time."""
    other = []
    colls = []
    for name, off, dur in events:
        if dur <= 0:
            continue
        if collective_kind(name) is None:
            other.append((off, off + dur))
        else:
            colls.append((name, off, off + dur))
    # merge the non-collective intervals once
    other.sort()
    merged = []
    for s, e in other:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    out: Dict[str, int] = {}
    for name, s, e in colls:
        covered = 0
        for ms, me in merged:
            if me <= s:
                continue
            if ms >= e:
                break
            covered += min(e, me) - max(s, ms)
        out[name] = out.get(name, 0) + max((e - s) - covered, 0)
    return out


def collective_events_dir(trace_dir) -> Dict[str, dict]:
    """Merge every .xplane.pb under trace_dir into {event_name: {"kind",
    "total_ps", "exposed_ps"}} for the collective events. Same dedup
    discipline as aggregate_dir — per plane take each name's MAX across
    lines (derived step/module lines repeat the raw XLA-op line; on CPU
    traces collective work also lands on per-device thread lines, so a
    busiest-line-only pick would miss it), then sum across planes and
    files. exposed_ps comes from the line that contributed the max: the
    part of the collective's duration no concurrent non-collective event
    on that line covers."""
    device_planes = []
    host_planes = []
    for p in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True):
        for pname, lines in plane_events(p).items():
            if pname.startswith("/device:"):
                device_planes.append(lines)
            else:
                filtered = []
                for line in lines:
                    evs = [e for e in line["events"] if instr_like(e[0])]
                    if evs:
                        filtered.append({**line, "events": evs})
                if filtered:
                    host_planes.append(filtered)
    planes = device_planes or host_planes
    out: Dict[str, dict] = {}
    for lines in planes:
        plane_best: Dict[str, tuple] = {}   # name -> (total_ps, exposed_ps)
        for line in lines:
            tot: Dict[str, int] = {}
            for name, _, dur in line["events"]:
                if collective_kind(name) is not None:
                    tot[name] = tot.get(name, 0) + dur
            if not tot:
                continue
            exposed = exposed_in_line(line["events"])
            for name, ps in tot.items():
                cur = plane_best.get(name)
                if cur is None or ps > cur[0]:
                    plane_best[name] = (ps, exposed.get(name, 0))
        for name, (ps, exp) in plane_best.items():
            rec = out.setdefault(name, {"kind": collective_kind(name),
                                        "total_ps": 0, "exposed_ps": 0})
            rec["total_ps"] += ps
            rec["exposed_ps"] += exp
    return out
