"""One account of a compiled step by instruction, and one reader of a trace.

Three things, each the only one of its kind in the package:

* `hlo_instructions(text, mesh=None)` — the one parse of a compiled
  module's text (`Compiled.as_text()`): a record per instruction of the
  entry computation (and of the computations control flow calls), with
  what it is: opcode, what does the work inside (`heavy`), FLOPs, bytes,
  the program op it was lowered from (role, name scope, op type and the
  op's position in its block, from the executor's named scopes in
  `op_name`) and, for a collective, kind, payload, replica groups and the
  mesh axis they run along. Everything else that wants to know something
  about compiled text (the liveness walk of memory.py, the operator's
  report of roofline.py, the collective table of fleet.py,
  tools/describe_step.py) reads this list.
* `device_steps(trace_dir)` — the one reader of a profiler trace
  (`*.xplane.pb`, decoded from the wire format: the tensorboard plugin in
  this image cannot load it). The device planes' `XLA Ops` line only, a
  step = one `XLA Modules` event, each event with its instruction name
  and its metadata's stats.
* `step_account(trace_dir)` — the join: per traced step and instruction
  the device ms, the least the chip could take (`max(flops / peak,
  bytes / hbm)`, peaks from chip.py) and for a collective the bus
  bandwidth. Rows sum to the step's busy time. The executor leaves each
  compiled block's list here (`remember_account`), keyed by HLO module
  name; the first join in a traced process saves it beside the trace, so
  the same table prints later from the directory alone.

  XSpace.planes=1 / XPlane{name=2, lines=3, event_metadata=4,
                           stat_metadata=5}
  XLine{name=2, timestamp_ns=3, events=4}
  XEvent{metadata_id=1, offset_ps=2, duration_ps=3, stats=4}
  XEventMetadata{id=1, name=2, stats=5} / XStatMetadata{id=1, name=2}
  XStat{metadata_id=1, double=2, uint64=3, int64=4, str=5, ref=7}

This file imports nothing of the framework at module level
(tools/xplane.py loads it by path)."""

from __future__ import annotations

import functools
import glob
import json
import math
import os
import re
import struct
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

__all__ = ["hlo_instructions", "Instr", "provenance", "device_steps",
           "step_account", "remember_account", "known_accounts",
           "shape_bytes", "fields", "plane_events", "timeline_dir",
           "category", "instr_like", "COLLECTIVE_KINDS", "collective_kind",
           "busbw_factor", "exposed_in_line", "instruction_name", "op_label",
           "module_name", "compact", "is_async", "main_steps", "floor_seconds",
           "ACCOUNT_FILE", "OPS_LINE", "MODULES_LINE"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ACCOUNT_FILE = "step_account.json"


# --- the wire format --------------------------------------------------------

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


def _named(buf):
    """(id, name) of an XEventMetadata / XStatMetadata map entry, and the
    value message."""
    key = name = None
    inner = b""
    for f, w, v in fields(buf):
        if f == 1 and w == 0:
            key = v
        elif f == 2 and w == 2:
            inner = v
            for f2, w2, v2 in fields(v):
                if f2 == 1 and w2 == 0 and key is None:
                    key = v2
                elif f2 == 2 and w2 == 2:
                    name = v2.decode("utf-8", "replace")
    return key, name, inner


def _stats(buf, stat_field, stat_names):
    """{stat name: value} of the XStats in field `stat_field` of `buf`."""
    out = {}
    for f, w, v in fields(buf):
        if f != stat_field or w != 2:
            continue
        key = value = None
        for f2, w2, v2 in fields(v):
            if f2 == 1 and w2 == 0:
                key = v2
            elif f2 == 2 and w2 == 1:
                value = struct.unpack("<d", v2)[0]
            elif f2 in (3, 4) and w2 == 0:
                value = v2
            elif f2 == 5 and w2 == 2:
                value = v2.decode("utf-8", "replace")
            elif f2 == 7 and w2 == 0:
                value = stat_names.get(v2)
        if key in stat_names and value is not None:
            out[stat_names[key]] = value
    return out


def _planes(path):
    """Yield (plane name, [line], {metadata id: (name, stats)}) of one
    xplane file; a line is {"name", "timestamp_ns", "events": [(metadata
    id, offset_ps, duration_ps)]}. The one walk of the wire format."""
    with open(path, "rb") as f:
        space = f.read()
    for fno, wt, plane in fields(space):
        if fno != 1 or wt != 2:
            continue
        pname, raw_lines, raw_meta, stat_names = "", [], [], {}
        for f2, w2, v2 in fields(plane):
            if w2 != 2:
                continue
            if f2 == 2:
                pname = v2.decode("utf-8", "replace")
            elif f2 == 3:
                raw_lines.append(v2)
            elif f2 == 4:
                raw_meta.append(v2)
            elif f2 == 5:
                key, name, _ = _named(v2)
                if key is not None and name is not None:
                    stat_names[key] = name
        meta = {}
        for entry in raw_meta:
            key, name, inner = _named(entry)
            if key is not None and name is not None:
                meta[key] = (name, _stats(inner, 5, stat_names))
        lines = []
        for raw in raw_lines:
            lname, ts_ns, events = "", 0, []
            for f3, w3, v3 in fields(raw):
                if f3 == 2 and w3 == 2:
                    lname = v3.decode("utf-8", "replace")
                elif f3 == 3 and w3 == 0:
                    ts_ns = v3
                elif f3 == 4 and w3 == 2:
                    mid = off = dur = 0
                    for f4, w4, v4 in fields(v3):
                        if w4 != 0:
                            continue
                        if f4 == 1:
                            mid = v4
                        elif f4 == 2:
                            off = v4
                        elif f4 == 3:
                            dur = v4
                    events.append((mid, off, dur))
            lines.append({"name": lname, "timestamp_ns": ts_ns,
                          "events": events})
        yield pname, lines, meta


def _xplane_files(trace_dir):
    if os.path.isfile(trace_dir):
        return [trace_dir]
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def plane_events(path) -> Dict[str, list]:
    """-> {plane_name: [line, ...]} where each line is
    {"name": str, "timestamp_ns": int,
     "events": [(event_name, offset_ps, duration_ps), ...]}: every line of
    every plane, the raw view (tools/xplane.py --timeline, the waterfall).
    XLine.timestamp_ns anchors the line on the wall clock and
    XEvent.offset_ps places each event within the line."""
    out: Dict[str, list] = {}
    for pname, lines, meta in _planes(path):
        per_line = out.setdefault(pname, [])
        for line in lines:
            per_line.append({
                "name": line["name"], "timestamp_ns": line["timestamp_ns"],
                "events": [(meta[m][0] if m in meta else f"#{m}", off, dur)
                           for m, off, dur in line["events"]]})
    return out


def timeline_dir(trace_dir) -> list:
    """Every .xplane.pb under trace_dir as a flat list of {"plane",
    "line", "timestamp_ns", "events"} records (events carry (name,
    offset_ps, duration_ps)), device planes first."""
    records = []
    for p in _xplane_files(trace_dir):
        for pname, lines in plane_events(p).items():
            for line in lines:
                records.append({"plane": pname, "line": line["name"],
                                "timestamp_ns": line["timestamp_ns"],
                                "events": line["events"]})
    records.sort(key=lambda r: (not r["plane"].startswith("/device:"),
                                r["plane"], r["timestamp_ns"]))
    return records


_INSTR_LIKE = re.compile(r"[\w.\-]+\Z")
_EVENT_INSTR = re.compile(r"%?([\w.\-]+)")


def instr_like(name: str) -> bool:
    """True when an event name looks like an HLO instruction ('dot.4',
    'fusion.12', 'reduce-window') rather than host bookkeeping. Host
    planes interleave python-source events ('$profiler.py:226 trace'),
    runtime markers ('TfrtCpuExecutable::Execute') and dispatch wrappers
    ('PjitFunction(f)') with the real instruction events — all of which
    contain '$', ':', '(', or spaces that no instruction name can. The
    program's own spans (tracing.span: 'pd.step', ...) sit on the same
    host plane and are host time, not instructions."""
    return (_INSTR_LIKE.fullmatch(name) is not None
            and not name.startswith("pd."))


def instruction_name(event_name: str) -> str:
    """The HLO instruction an `XLA Ops` event is a run of. A v5e names the
    event by the instruction's whole text ('%fusion.3 = bf16[8]{0}
    fusion(...)'), a CPU trace by the bare name ('fusion.3')."""
    m = _EVENT_INSTR.match(event_name)
    return m.group(1) if m else event_name


def category(name: str) -> str:
    """HLO instruction text -> coarse op kind ('%fusion.123 = ...' ->
    'fusion'; falls back to the leading token)."""
    tok = name.lstrip("%").split(" ", 1)[0]
    return tok.split(".")[0]


def device_steps(trace_dir) -> List[Dict[str, Any]]:
    """The traced steps of every .xplane.pb under `trace_dir`, in time
    order per device:

        {"device": plane, "module": name of the `XLA Modules` event (None
         without that line), "start_ps", "end_ps", "events": [(instruction
         name, start_ps, duration_ps, stats)]}

    Only the device planes' `XLA Ops` line is read: the core's own
    timeline, one instruction after another. The derived step / module /
    framework-op lines repeat its events and are never counted. A step is
    one event of `XLA Modules` with the operations that start inside it;
    operations outside every module event are dropped (another program's,
    or a step the trace cut). `stats` are the event metadata's stats
    (`tf_op` = the op_name on a v5e) and are shared between the runs of
    one instruction.

    A trace with no device plane (the CPU backend writes host planes
    only) falls back to the instruction-like events of the host lines,
    each line one "device" (marked `"host": True`) and the whole trace one
    step. A small computation runs on the calling python thread, whose
    line also holds runtime spans that look alike (`DevicePut`,
    `shard_args`): `step_account` drops what no account names there."""
    device, host = [], []
    for path in _xplane_files(trace_dir):
        for pname, lines, meta in _planes(path):
            on_device = pname.startswith("/device:")
            if on_device:
                modules, ops = [], []
                for line in lines:
                    base = line["timestamp_ns"] * 1000
                    if line["name"] == MODULES_LINE:
                        modules += [(meta[m][0] if m in meta else f"#{m}",
                                     base + off, base + off + dur)
                                    for m, off, dur in line["events"]]
                    elif line["name"] == OPS_LINE:
                        ops += [(base + off, dur, m)
                                for m, off, dur in line["events"]]
                ops.sort()
                named = {m: (instruction_name(v[0]), v[1])
                         for m, v in meta.items()}
                if not modules and ops:
                    modules = [(None, ops[0][0],
                                max(s + d for s, d, _ in ops))]
                cursor = 0
                for mname, start, end in sorted(modules, key=lambda m: m[1]):
                    while cursor < len(ops) and ops[cursor][0] < start:
                        cursor += 1
                    first = cursor
                    while cursor < len(ops) and ops[cursor][0] < end:
                        cursor += 1
                    if cursor > first:
                        events = []
                        for s, d, m in ops[first:cursor]:
                            name, stats = named.get(m, (f"#{m}", {}))
                            events.append((name, s, d, stats))
                        device.append({
                            "device": pname, "module": mname,
                            "start_ps": start, "end_ps": end,
                            "events": events})
            elif not device:
                for index, line in enumerate(lines):
                    base = line["timestamp_ns"] * 1000
                    evs = [(meta[m][0], base + off, dur, meta[m][1])
                           for m, off, dur in line["events"]
                           if m in meta and instr_like(meta[m][0])]
                    if evs:
                        evs.sort(key=lambda e: e[1])
                        host.append({
                            "device": "%s/%s#%d" % (pname, line["name"],
                                                    index),
                            "module": None, "host": True,
                            "start_ps": evs[0][1],
                            "end_ps": max(e[1] + e[2] for e in evs),
                            "events": evs})
    return device if device else host


def _self_ps(events):
    """{instruction: ps} of one step's events where an operation that
    encloses others (a while or a conditional and its body) is charged
    only what they leave, so the values sum to the time the line is
    busy."""
    totals: Dict[str, int] = {}
    stack: List[Tuple[str, int]] = []
    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        totals[name] = totals.get(name, 0) + dur
        if stack:
            parent, parent_end = stack[-1]
            totals[parent] -= min(end, parent_end) - start
        stack.append((name, end))
    return totals


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


@functools.lru_cache(maxsize=4096)
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


# --- shapes -----------------------------------------------------------------

# dtype token -> bytes per element for HLO shape strings ('f32[4,128]');
# a dtype not listed (token, opaque) holds no bytes
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "u2": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_TOK = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# the same with the layout that follows: a memory space `S(n)` in it says
# the array lives on the chip (VMEM, SMEM), not in HBM
_SHAPE_LAYOUT = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](\{[^{}]*\})?")


def _arrays(shape_text: str) -> List[Tuple[str, int]]:
    """[(dtype, elements)] of the arrays in an HLO shape string."""
    out = []
    for dtype, dims in _SHAPE_TOK.findall(shape_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, n))
    return out


def shape_bytes(shape_text: str, largest: bool = False,
                hbm_only: bool = False) -> int:
    """Bytes of an HLO shape string: 'f32[128,13]{1,0}' -> 6656; a tuple
    '(f32[8], s32[])' sums its elements, or with `largest` gives the
    largest of them (an async '-start' carries (input, output, context)
    aliasing one transfer). Token and opaque types hold nothing. With
    `hbm_only`, an array whose layout names a memory space
    ('f32[8]{0:T(256)S(1)}': prefetched into VMEM) counts nothing: it is
    not read from HBM by the instruction that uses it."""
    sizes = []
    for dtype, dims, layout in _SHAPE_LAYOUT.findall(shape_text):
        n = 0 if hbm_only and "S(" in layout else 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dtype, 0))
    if not sizes:
        return 0
    return max(sizes) if largest else sum(sizes)


def _async_moved(shape_text: str) -> int:
    """Bytes an async '-start' moves: its shape is (operands, output,
    context) or ((operands), output, context), one transfer; the output
    is what moves (a slice-start names its whole operand first)."""
    s = shape_text.strip()
    if s.startswith("(("):
        depth = 0
        for i, c in enumerate(s[1:], 1):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                rest = shape_bytes(s[i + 1:], largest=True)
                if rest:
                    return rest
                break
    return shape_bytes(s, largest=True)


def _dims(shape_text: str) -> List[int]:
    m = _SHAPE_TOK.search(shape_text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def first_array(shape_text: str) -> Tuple[int, str]:
    """(elements, 'dtype[dims]{minor_to_major}') of the first array of a
    shape, tiling and memory space left out: 'bf16[8,128]{1,0:T(8,128)}'
    -> (1024, 'bf16[8,128]{1,0}')."""
    m = _SHAPE_TOK.search(shape_text)
    if not m:
        return 0, ""
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    text = "%s[%s]" % m.groups()
    rest = shape_text[m.end():]
    if rest.startswith("{"):
        layout = rest[1:rest.index("}")].split(":")[0]
        if layout:
            text += "{%s}" % layout
    return n, text


def _plain(shape_text: str) -> str:
    """A shape without its layout: 'bf16[8,128]{1,0:T(8,128)}' ->
    'bf16[8,128]'; a tuple keeps its arrays."""
    found = _SHAPE_TOK.findall(shape_text)
    if len(found) == 1:
        return "%s[%s]" % found[0]
    return "(" + ", ".join("%s[%s]" % f for f in found) + ")"


# --- the one parse of compiled text -----------------------------------------

class Instr(NamedTuple):
    """One instruction of a compiled step. `flops` is None for a Mosaic
    call: XLA's count stops at the call. What the call does stands in
    `declared_flops`, `declared_transcendentals` and `declared_bytes`,
    the `cost_estimate` the call itself carries in its `backend_config`
    (ops/kernel_cost.py: the work as implemented, MXU FLOPs at the passes
    they cost, the bytes its pipeline moves; the bytes scaled by the
    share of the call's operands and results that are in HBM, where XLA
    holds one of them in VMEM: `S(1)` in its layout), with `declared_by`
    "kernel" for one of the package's and "jax" for megablox `gmm` /
    `tgmm`, whose estimate counts every row of the buffer and is an upper
    bound; all four None for every other instruction, for a call that
    declared nothing and in an account an older cache served (compiled
    text from before PR 66 carries no estimate: nothing fails, the
    kernels' floor is just not there). `flops` stays None for a Mosaic
    call whatever it declares: the benchmark's readers tell one by it.
    `mxu_flops` is `flops`
    with a float32 product at `highest` precision counted as the 6 bf16
    passes it runs as (the trace's own `flops` stat; the floor's); `at` is the program
    op's position in the block it was lowered from; `kind`, `payload`,
    `group_size`, `groups`, `axis`, `site`, `channel` (its `channel_id`:
    an asynchronous collective is one instruction in each computation
    that holds a piece of it, all under one id) and `moved` (the shape
    the collective itself writes, where `shape` is its fusion's) are set
    for a collective;
    `recompute` is the segment of a forward op replayed in the backward
    (backward.append_backward(checkpoints=)), None for every other."""
    name: str
    opcode: str
    heavy: str
    flops: Optional[float]
    mxu_flops: Optional[float]
    bytes: int
    shape: str
    detail: str
    op_name: str
    role: str
    scope: Optional[str]
    op: Optional[str]
    at: Optional[int]
    entry: bool
    operands: Tuple[str, ...]
    kind: Optional[str] = None
    payload: int = 0
    group_size: Optional[int] = None
    groups: Optional[str] = None
    axis: Optional[str] = None
    site: Optional[str] = None
    recompute: Optional[int] = None
    channel: Optional[int] = None
    moved: str = ""
    declared_flops: Optional[float] = None
    declared_transcendentals: Optional[float] = None
    declared_bytes: Optional[int] = None
    declared_by: Optional[str] = None


_ROLE = re.compile(r"pd_role\.([A-Za-z0-9_]+)")
_SCOPE = re.compile(r"pd_scope\.([A-Za-z0-9_.\-]+)")
_TYPE = re.compile(
    r"(?<![A-Za-z0-9_])pd\.(coll\.[A-Za-z0-9_.\-]+|[A-Za-z0-9_@]+)")
_AT = re.compile(r"pd_at\.([0-9]+)")
# a framework collective call site (jax.named_scope("pd.coll.<site>") in
# parallel/), wherever it sits under the program op's scope
_COLL = re.compile(r"(?<![A-Za-z0-9_])pd\.coll\.([A-Za-z0-9_.\-]+)")
AT_SCOPE = "pd_at."
# executor._exec_op, around a replayed forward op, inside its role
RECOMPUTE_SCOPE = "pd_recompute."
_RECOMPUTE = re.compile(r"pd_recompute\.([0-9]+)")


def recompute_of(op_name: str) -> Optional[int]:
    """The recomputation segment an HLO op_name's program op replays a
    forward op of (`pd_recompute.<n>`), None for any other op."""
    found = _RECOMPUTE.search(op_name or "")
    return int(found.group(1)) if found else None


def provenance(op_name: str):
    """(role, name scope, program op, position) from an HLO op_name, the
    executor's named scopes `pd_at.<n>/pd_role.<role>/pd_scope.<name>/
    pd.<type>`: the outermost of each; ("unattributed", None, None, None)
    where there is none. A `pd.coll.<site>` scope reads as op
    'coll.<site>'."""
    if not op_name:
        return "unattributed", None, None, None
    role = _ROLE.search(op_name)
    scope = _SCOPE.search(op_name)
    kind = _TYPE.search(op_name)
    at = _AT.search(op_name)
    return (role.group(1) if role else "unattributed",
            scope.group(1) if scope else None,
            kind.group(1) if kind else None,
            int(at.group(1)) if at else None)


# one instruction: "[ROOT] %name = shape opcode(operands), attrs". The
# fast form holds no tuple shape and no parenthesis among the operands;
# the rest goes through the scanner
_FAST = re.compile(
    r"\s*(ROOT )?%?([\w.\-]+) = ([a-z][a-z0-9]*\[[0-9,]*\]\S*) "
    r"([\w\-]+)\(([^()]*)\)(.*)")
_PARENS = re.compile(r"[()]")
_COMMENT = re.compile(r"/\*.*?\*/")
_PERCENT_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_WINDOW = re.compile(r"window=\{([^}]*)\}")
_DIM_LABELS = re.compile(r"dim_labels=([\w]+)_([\w]+)->([\w]+)")
_INT_ATTR = re.compile(r"\b(feature_group_count|batch_group_count)=(\d+)")
_DIMS_ATTR = re.compile(
    r"\b(lhs_contracting_dims|lhs_batch_dims|dimensions)=\{([0-9,]*)\}")
_GROUPS = re.compile(
    r"replica_groups=(\{\{[0-9,{} ]*\}\}|\{\}|\[[0-9,]+\]<=\[[0-9,]+\]"
    r"(?:T\([0-9,]+\))?)")
_PAIRS = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")

MOSAIC_TARGET = "tpu_custom_call"
_MOSAIC_CALL = 'custom_call_target="%s"' % MOSAIC_TARGET
# what a Mosaic call declares of itself: an object behind the serialized
# body in its backend_config, the numbers as strings
_COST_KEY = '"cost_estimate":{'
_DECLARED = re.compile(r", cost_estimate=\{([^{}]*)\}")
_DECLARED_FIELD = re.compile(r'"(\w+)":"?(\d+)"?')
# jax's own kernels under the package's ops (megablox, hybrid_ops): their
# estimate is jax's, every row of the buffer
JAX_KERNELS = frozenset({"gmm", "tgmm"})
# a custom call that joins the pieces of an async slice under one name:
# it moves nothing
_ALIASING_TARGET = 'custom_call_target="ConcatBitcast"'
# opcodes that only alias: no time on the device, no buffer of their own
ZERO_COST = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast", "after-all", "partition-id", "replica-id"})
# data movement: nothing is computed
_MOVES = frozenset({
    "copy", "copy-start", "copy-done", "transpose", "reshape", "slice",
    "slice-start", "slice-done", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "broadcast", "reverse", "gather", "convert",
    "bitcast-convert", "iota", "dynamic-reshape"})
# one transcendental a element: XLA counts them apart from FLOPs
_TRANSCENDENTAL = frozenset({
    "exponential", "log", "logistic", "power", "sqrt", "cbrt", "rsqrt",
    "tanh", "sine", "cosine", "tan", "erf", "exponential-minus-one",
    "log-plus-one", "atan2"})
# one operation an output element (XLA's HloCostAnalysis counts integer
# and predicate arithmetic alike)
_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "negate",
    "abs", "sign", "compare", "select", "clamp", "and", "or", "xor", "not",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "remainder",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "is-finite", "popcnt", "count-leading-zeros", "real", "imag",
    "reduce-precision", "stochastic-convert", "convert", "map"})
_REDUCES = frozenset({"reduce", "reduce-window"})
_SCATTERS = frozenset({"scatter", "select-and-scatter"})
_CONTROL = frozenset({"while", "conditional", "call"})


def _scan(line):
    """(root, name, shape, opcode, operand text, attrs) of an instruction
    line, or None: the slow path, for tuple shapes and nested
    parentheses."""
    s = line.strip()
    root = s.startswith("ROOT ")
    if root:
        s = s[5:]
    eq = s.find(" = ")
    if eq < 0:
        return None
    name, rest = s[:eq].lstrip("%"), s[eq + 3:]
    if rest.startswith("("):
        depth = 0
        for m in _PARENS.finditer(rest):
            depth += 1 if m.group() == "(" else -1
            if depth == 0:
                break
        else:
            return None
        cut = m.end()
    else:
        cut = rest.find(" ")
        if cut < 0:
            return None
    shape, rest = rest[:cut], rest[cut:].lstrip()
    start = rest.find("(")
    if start < 0:
        return None
    depth = 0
    for m in _PARENS.finditer(rest, start):
        depth += 1 if m.group() == "(" else -1
        if depth == 0:
            break
    else:
        return None
    return (root, name, shape, rest[:start], rest[start + 1:m.start()],
            rest[m.end():])


def _operand_names(text):
    if not text:
        return ()
    if "/*" in text:
        text = _COMMENT.sub("", text)
    if "%" in text and "(" not in text:
        return tuple(_PERCENT_NAME.findall(text))
    if "(" not in text:
        return tuple(t.split()[-1].lstrip("%")
                     for t in text.split(",") if t.strip())
    out, depth, start = [], 0, 0
    for i, c in enumerate(text):
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == "," and depth == 0:
            out.append(text[start:i])
            start = i + 1
    out.append(text[start:])
    return tuple(t.split()[-1].lstrip("%") for t in out if t.strip())


class _Raw(NamedTuple):
    root: bool
    name: str
    shape: str
    opcode: str
    operands: Tuple[str, ...]
    attrs: str


def _computations(text):
    """(OrderedDict name -> [_Raw], entry name) of a module's text."""
    comps: "OrderedDict[str, List[_Raw]]" = OrderedDict()
    entry = current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " \t":
            current = None
            if line.endswith("{") and not line.startswith("HloModule"):
                head = line[:-1].split("(", 1)[0].split()
                if head:
                    cname = head[-1].lstrip("%")
                    current = comps.setdefault(cname, [])
                    if head[0] == "ENTRY":
                        entry = cname
            continue
        if current is None:
            continue
        # the backend's own configuration (tile sizes, cycle estimates, a
        # Mosaic kernel's body) is most of a line and nothing here reads it
        cut = line.find(", backend_config=")
        if cut >= 0:
            declared = ""
            if _MOSAIC_CALL in line[:cut]:
                # the call's own statement of its work follows the body:
                # one search from the line's end, never over the body
                at = line.rfind(_COST_KEY)
                end = line.find("}", at)
                if at > cut and end > at:
                    declared = ", cost_estimate=" \
                        + line[at + len(_COST_KEY) - 1:end + 1]
            line = line[:cut] + declared
        m = _FAST.match(line)
        if m:
            current.append(_Raw(bool(m.group(1)), m.group(2), m.group(3),
                                m.group(4), _operand_names(m.group(5)),
                                m.group(6)))
            continue
        got = _scan(line)
        if got is not None:
            root, name, shape, opcode, operands, attrs = got
            current.append(_Raw(root, name, shape, opcode,
                                _operand_names(operands), attrs))
    return comps, entry


def is_async(opcode: str) -> bool:
    """A '-start' / '-done' half: the transfer runs under the
    instructions between the two, so it has no floor of its own to add
    to theirs."""
    return opcode.endswith("-start") or opcode.endswith("-done")


def floor_seconds(instr, peak, hbm):
    """(seconds, "flops" | "bytes") the chip needs at least for one run of
    an instruction: max(MXU FLOPs / peak, HBM bytes / bandwidth), and
    which of the two it is. None for an async half, for control flow
    (its body's instructions carry it) and for a Mosaic call, whose
    floor is `kernel_floor_seconds`': XLA's count stops at the call, and
    the benchmark's readers tell a call by this None."""
    if is_async(instr.opcode) or instr.heavy == "control" \
            or instr.flops is None:
        return None
    return _floor((instr.mxu_flops or 0.0) / peak, instr.bytes / hbm)


def _floor(work, moved):
    return max(work, moved), "bytes" if moved >= work else "flops"


def kernel_floor_seconds(instr, peak, hbm):
    """`floor_seconds` of a Mosaic call from what the call declares of
    itself (`Instr.declared_*`): max(declared FLOPs / peak, declared
    bytes / bandwidth) and which of the two. None for every other
    instruction and for a call that declared nothing (compiled before
    PR 66, or served by a cache from then)."""
    if instr.declared_by is None:
        return None
    return _floor((instr.declared_flops or 0.0) / peak,
                  (instr.declared_bytes or 0) / hbm)


def _valid_positions(size, stride, pad_lo, lhs_dil, rhs_dil, in_size,
                     out_size):
    """How many (output position, kernel position) pairs of one spatial
    dimension read an input element and not padding or a dilation hole:
    what XLA's cost analysis counts a convolution by."""
    reach = (in_size - 1) * lhs_dil + 1
    count = 0
    for k in range(size):
        base = k * rhs_dil - pad_lo
        if lhs_dil == 1 and stride > 0:
            # out positions o with 0 <= o*stride + base < reach
            lo = 0 if base >= 0 else (-base + stride - 1) // stride
            hi = min(out_size - 1, (reach - 1 - base) // stride) \
                if reach - 1 - base >= 0 else -1
            count += max(hi - lo + 1, 0)
            continue
        for o in range(out_size):
            at = o * stride + base
            if 0 <= at < reach and at % lhs_dil == 0:
                count += 1
    return count


def _window_of(attrs, n_spatial):
    """Per spatial dimension (size, stride, pad_lo, lhs_dilate,
    rhs_dilate) of a convolution's window attribute."""
    fieldsets = {"size": [1] * n_spatial, "stride": [1] * n_spatial,
                 "pad": [(0, 0)] * n_spatial,
                 "lhs_dilate": [1] * n_spatial,
                 "rhs_dilate": [1] * n_spatial}
    m = _WINDOW.search(attrs)
    if m:
        for item in m.group(1).split():
            key, _, value = item.partition("=")
            if key == "pad":
                fieldsets[key] = [tuple(int(p) for p in part.split("_"))
                                  for part in value.split("x")]
            elif key in fieldsets:
                fieldsets[key] = [int(p) for p in value.split("x")]
    return [(fieldsets["size"][i], fieldsets["stride"][i],
             fieldsets["pad"][i][0], fieldsets["lhs_dilate"][i],
             fieldsets["rhs_dilate"][i]) for i in range(n_spatial)]


_VALID_MEMO: Dict[tuple, int] = {}


def _precision_passes(raw, shapes):
    """How many bf16 passes of the MXU one multiply-add of a product
    costs: a float32 product asked for at `highest` precision runs as 6
    (at `high`, 3), and XLA's own count of a v5e step, which the trace's
    event metadata carries, multiplies by it."""
    if "operand_precision={high" not in raw.attrs or not raw.operands:
        return 1
    if not shapes.get(raw.operands[0], "").lstrip("(").startswith("f32["):
        return 1
    return 6 if "operand_precision={highest" in raw.attrs else 3


def _conv_flops(raw, shapes, mxu=False):
    """2 x multiply-adds of a convolution from its window, feature groups
    and shapes, counting only the window positions that read an input
    element (so a grad-input conv over a dilated operand and a padded
    border cost what they compute)."""
    labels = _DIM_LABELS.search(raw.attrs)
    if not labels or len(raw.operands) < 2:
        return 0.0
    lhs_l, rhs_l, out_l = labels.groups()
    lhs = _dims(shapes.get(raw.operands[0], ""))
    rhs = _dims(shapes.get(raw.operands[1], ""))
    out = _dims(raw.shape)
    if len(lhs) != len(lhs_l) or len(rhs) != len(rhs_l) \
            or len(out) != len(out_l):
        return 0.0
    groups = dict((k, int(v)) for k, v in _INT_ATTR.findall(raw.attrs))
    batch = lhs[lhs_l.index("b")] // groups.get("batch_group_count", 1)
    in_feat = lhs[lhs_l.index("f")] // groups.get("feature_group_count", 1)
    out_feat = out[out_l.index("f")]
    n_spatial = len(out_l) - 2
    fma = float(batch * in_feat * out_feat)
    window = _window_of(raw.attrs, n_spatial)
    for d in range(n_spatial):
        digit = str(d)
        size, stride, pad_lo, lhs_dil, rhs_dil = window[d]
        key = (rhs[rhs_l.index(digit)], stride, pad_lo, lhs_dil, rhs_dil,
               lhs[lhs_l.index(digit)], out[out_l.index(digit)])
        if key not in _VALID_MEMO:
            _VALID_MEMO[key] = _valid_positions(*key)
        fma *= _VALID_MEMO[key]
    return 2.0 * fma * (_precision_passes(raw, shapes) if mxu else 1)


def _dot_flops(raw, shapes, mxu=False):
    """2 x M x N x K of a dot: the output's elements (batch, M, N) times
    the contracted extent of the left operand."""
    lhs = _dims(shapes.get(raw.operands[0], "")) if raw.operands else []
    attrs = dict(_DIMS_ATTR.findall(raw.attrs))
    k = 1
    for d in attrs.get("lhs_contracting_dims", "").split(","):
        if d and int(d) < len(lhs):
            k *= lhs[int(d)]
    return 2.0 * math.prod(_dims(raw.shape)) * k \
        * (_precision_passes(raw, shapes) if mxu else 1)


def _hbm(shape_text):
    return shape_bytes(shape_text, hbm_only=True)


def _elements(shape_text):
    return sum(n for _, n in _arrays(shape_text))


class _Module:
    """The computations of one module and what each costs, worked out
    once a computation."""

    def __init__(self, comps):
        self.comps = comps
        self.shapes = {name: {r.name: r.shape for r in raws}
                       for name, raws in comps.items()}
        self._flops: Dict[tuple, float] = {}
        self._opcodes: Dict[str, frozenset] = {}

    def called(self, raw):
        names = [m.group(2) for m in _CALLS.finditer(raw.attrs)]
        b = _BRANCHES.search(raw.attrs)
        if b:
            names += [t.strip().lstrip("%") for t in b.group(1).split(",")
                      if t.strip()]
        return [n for n in names if n in self.comps]

    def comp_flops(self, cname, mxu=False):
        key = (cname, mxu)
        if key not in self._flops:
            self._flops[key] = 0.0     # a cycle would be a malformed text
            self._flops[key] = sum(
                self.flops(cname, r, mxu) or 0.0 for r in self.comps[cname])
        return self._flops[key]

    def opcodes(self, cname):
        """Every opcode under a fused computation, nested fusions'
        included (a reducer's `add` is not the fusion's arithmetic)."""
        if cname not in self._opcodes:
            self._opcodes[cname] = frozenset()
            found = set()
            for r in self.comps[cname]:
                found.add(r.opcode)
                if r.opcode == "fusion":
                    for c in self.called(r):
                        found |= self.opcodes(c)
                elif r.opcode == "custom-call":
                    t = _TARGET.search(r.attrs)
                    if t and t.group(1) == MOSAIC_TARGET:
                        found.add("mosaic")
            self._opcodes[cname] = frozenset(found)
        return self._opcodes[cname]

    def inner_collective(self, raw):
        """The collective a fusion wraps (XLA's `all-reduce-scatter`, its
        async collective fusions, a gather fused with its consumer), or
        None."""
        if raw.opcode != "fusion":
            return None
        for c in self.called(raw):
            for r in self.comps[c]:
                if collective_kind(r.opcode) is not None:
                    return r
                found = self.inner_collective(r)
                if found is not None:
                    return found
        return None

    def flops(self, cname, raw, mxu=False) -> Optional[float]:
        """Operations of one instruction as XLA's cost analysis counts
        them: a multiply-add is two, one an output element of elementwise
        arithmetic, a reduce one an element folded in; transcendentals
        and data movement none; None for a Mosaic call. With `mxu`, a
        float32 product at `highest` precision counts its 6 passes."""
        op = raw.opcode
        shapes = self.shapes[cname]
        if op == "convolution":
            return _conv_flops(raw, shapes, mxu)
        if op == "dot":
            return _dot_flops(raw, shapes, mxu)
        if op in _ELEMENTWISE:
            return float(_elements(raw.shape))
        if op == "reduce":
            n_in = len(raw.operands) // 2 or 1
            folded = sum(math.prod(_dims(shapes.get(o, "")))
                         for o in raw.operands[:n_in])
            return float(max(folded - _elements(raw.shape), 0)) \
                * max(sum(self.comp_flops(c, mxu) for c in self.called(raw)), 1.0)
        if op == "reduce-window":
            size = math.prod(w[0] for w in _window_of(
                raw.attrs, len(_dims(raw.shape))))
            return float(_elements(raw.shape) * max(size - 1, 0))
        if op == "scatter":
            updates = raw.operands[len(raw.operands) // 3 * 2:] \
                if len(raw.operands) >= 3 else ()
            return float(sum(math.prod(_dims(shapes.get(o, "")))
                             for o in updates))
        if op == "select-and-scatter":
            size = math.prod(w[0] for w in _window_of(
                raw.attrs, len(_dims(raw.shape))))
            source = math.prod(_dims(shapes.get(raw.operands[1], ""))) \
                if len(raw.operands) > 1 else 0
            return float(source * size)
        if op == "custom-call":
            t = _TARGET.search(raw.attrs)
            return None if t and t.group(1) == MOSAIC_TARGET else 0.0
        if op in ("fusion", "call"):
            return sum(self.comp_flops(c, mxu) for c in self.called(raw))
        if op == "while":
            # body and condition once, whatever the trip count: XLA's
            # cost analysis does the same, and a trace shows every run of
            # the body's instructions under their own names
            return sum(self.comp_flops(c, mxu) for c in self.called(raw))
        if op == "conditional":
            return max((self.comp_flops(c, mxu) for c in self.called(raw)),
                       default=0.0)
        return 0.0

    def heavy(self, raw):
        """What does the work inside an instruction."""
        op = raw.opcode
        if op == "custom-call":
            t = _TARGET.search(raw.attrs)
            target = t.group(1) if t else "custom-call"
            if target != MOSAIC_TARGET:
                return target
            named = _OP_NAME.search(raw.attrs)
            parts = named.group(1).split("/") if named else []
            if "pallas_call" in parts and parts.index("pallas_call") > 0:
                kernel = parts[parts.index("pallas_call") - 1]
                if kernel.startswith("jit(") and kernel.endswith(")"):
                    kernel = kernel[4:-1]
                return kernel
            return raw.name.split(".")[0]
        if collective_kind(op) is not None:
            return "collective"
        if op == "fusion":
            inside = frozenset().union(
                *(self.opcodes(c) for c in self.called(raw)))
        else:
            inside = frozenset({op})
        if "mosaic" in inside:
            return "mosaic"
        for name in ("convolution", "dot"):
            if name in inside:
                return name
        if op == "fusion" and any(collective_kind(o) for o in inside):
            return "collective"
        if inside & _SCATTERS:
            return "scatter"
        if inside & _REDUCES:
            return "reduce"
        if "sort" in inside:
            return "sort"
        if inside & (_ELEMENTWISE - {"convert"}) or inside & _TRANSCENDENTAL:
            return "elementwise"
        if op in _CONTROL:
            return "control"
        if inside & _MOVES or op in ("copy", "fusion"):
            return "copy"
        return op

    def bytes(self, cname, raw):
        """Bytes read and written by one instruction, from the shapes: operands read once, outputs written once, those in
        HBM (an operand prefetched into VMEM, `S(1)` in its layout, was
        paid for by the copy that brought it). Where a fusion only slices
        an operand, or updates one in place, what it touches is the
        slice."""
        op = raw.opcode
        shapes = self.shapes[cname]
        if op.endswith("-done") or op in ZERO_COST or op in _CONTROL:
            return 0
        if op.endswith("-start"):
            return 2 * _async_moved(raw.shape)
        if op == "custom-call" and _ALIASING_TARGET in raw.attrs:
            return 0
        out = _hbm(raw.shape)
        if op in ("slice", "dynamic-slice", "gather"):
            return 2 * out
        if op == "dynamic-update-slice" and len(raw.operands) > 1:
            update = _hbm(shapes.get(raw.operands[1], ""))
            return 2 * update
        reads = [_hbm(shapes.get(o, "")) for o in raw.operands]
        if op == "fusion":
            for c in self.called(raw):
                reads, out = self._fusion_traffic(c, reads, out)
        return sum(reads) + out

    def _fusion_traffic(self, cname, reads, out):
        """A fusion's (reads by operand, bytes written) with what it does
        not touch left out: an operand it only slices costs the slices; one
        it updates in place costs the update; one it only hands on (an
        aliased buffer of a collective in flight, fused beside a product)
        or gives to a collective costs nothing here, as the collective's
        own result does not: the payload is the collective's."""
        raws = self.comps[cname]
        shapes = self.shapes[cname]
        by_name = {r.name: r for r in raws}
        users: Dict[str, List[_Raw]] = {}
        for r in raws:
            for o in r.operands:
                users.setdefault(o, []).append(r)
        numbered = {}
        for r in raws:
            if r.opcode == "parameter" and r.operands:
                try:
                    numbered[int(r.operands[0])] = r.name
                except ValueError:
                    pass
        reads = list(reads)

        def behind_bitcasts(r):
            while r is not None and r.opcode == "bitcast" and r.operands:
                r = by_name.get(r.operands[0])
            return r

        def updated_in_place(r):
            """(the operand a dynamic-update-slice updates in place, the
            update's bytes) for a result that is one, behind bitcasts."""
            r = behind_bitcasts(r)
            if r is None or r.opcode != "dynamic-update-slice" \
                    or len(r.operands) < 2:
                return None
            base = behind_bitcasts(by_name.get(r.operands[0]))
            if base is None or base.opcode != "parameter":
                return None
            return base.name, _hbm(shapes.get(r.operands[1], ""))

        root = next((r for r in raws if r.root), raws[-1] if raws else None)
        # each result by itself: one of several may be an update in place
        # (a scan's stacked output written beside a product)
        tupled = root is not None and root.opcode == "tuple"
        results = [by_name.get(o) for o in root.operands] if tupled \
            else [root]
        updates = [updated_in_place(r) for r in results]
        in_place = {u[0] for u in updates if u}
        if tupled or in_place:
            out = sum(u[1] if u else _hbm(r.shape)
                      for r, u in zip(results, updates)
                      if u or (r is not None and r.opcode != "parameter"
                               and collective_kind(r.opcode) is None))
        for index, pname in numbered.items():
            if index >= len(reads):
                continue
            uses = users.get(pname, ())
            if pname in in_place and len(uses) == 1:
                reads[index] = 0
            elif uses and all(u.opcode == "tuple"
                              or collective_kind(u.opcode) is not None
                              for u in uses):
                reads[index] = 0
            else:
                sliced = self._sliced_bytes(cname, pname)
                if sliced is not None:
                    reads[index] = min(reads[index], sliced)
        return reads, out

    def _sliced_bytes(self, cname, pname):
        """Bytes the fused computation `cname` reads of its parameter
        `pname` where all it does with it is slice it, itself or in the
        fusions nested in it (a loop body's product whose operand fusion
        picks one step's row of a scan's stacked input); None where
        anything reads it whole."""
        raws = self.comps[cname]
        uses = [r for r in raws if pname in r.operands]
        total = 0
        for use in uses:
            if use.opcode in ("slice", "dynamic-slice", "gather") \
                    and use.operands[0] == pname:
                total += _hbm(use.shape)
                continue
            inner = self.called(use) if use.opcode == "fusion" else ()
            if len(inner) != 1:
                return None
            by_index = {r.operands[0]: r.name for r in self.comps[inner[0]]
                        if r.opcode == "parameter" and r.operands}
            for at, operand in enumerate(use.operands):
                if operand != pname:
                    continue
                nested = self._sliced_bytes(inner[0], by_index.get(str(at)))
                if nested is None:
                    return None
                total += nested
        return total if uses else None


def _parse_groups(text: Optional[str], pairs: Optional[str]):
    """[[device index]] of a collective's `replica_groups` in any of its
    spellings (explicit `{{0,1},{2,3}}`, iota `[2,2]<=[4]`, transposed
    iota `[2,2]<=[2,2]T(1,0)`), or of a permute's source-target pairs;
    None for the empty form (all devices, a count the text does not
    give)."""
    if pairs is not None:
        found = re.findall(r"\{([0-9, ]+)\}", pairs)
        return [[int(t) for t in g.split(",") if t.strip()] for g in found]
    if not text or text == "{}":
        return None
    if text.startswith("{"):
        found = re.findall(r"\{([0-9, ]+)\}", text)
        return [[int(t) for t in g.split(",") if t.strip()] for g in found]
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", text)
    if not m:
        return None
    dims = [int(d) for d in m.group(1).split(",")]
    reshape = [int(d) for d in m.group(2).split(",")]
    perm = [int(d) for d in m.group(3).split(",")] if m.group(3) \
        else list(range(len(reshape)))
    # iota(prod).reshape(reshape).transpose(perm).reshape(dims)
    strides = [0] * len(reshape)
    acc = 1
    for i in reversed(range(len(reshape))):
        strides[i] = acc
        acc *= reshape[i]
    t_shape = [reshape[p] for p in perm]
    t_strides = [strides[p] for p in perm]
    flat = []
    index = [0] * len(t_shape)
    for _ in range(acc):
        flat.append(sum(i * s for i, s in zip(index, t_strides)))
        for d in reversed(range(len(t_shape))):
            index[d] += 1
            if index[d] < t_shape[d]:
                break
            index[d] = 0
    size = dims[-1] if dims else 1
    return [flat[i:i + size] for i in range(0, len(flat), size)]


def _mesh_axes(mesh):
    """[(axis name, size)] of a planner mesh (`jax.sharding.Mesh`: its
    `.shape` maps names to sizes in device-array order) or of a plain
    mapping; None without one."""
    if mesh is None:
        return None
    return [(str(k), int(v)) for k, v in dict(
        getattr(mesh, "shape", mesh)).items()]


def _axis_of(groups, axes):
    """The mesh axes a collective's groups run along, joined by '+' in
    mesh order ('tp', 'fsdp', or 'fsdp+tp' where a group spans both): the
    axes whose coordinate differs inside a group. Device indices are
    positions in the mesh's device array, row-major, as jit assigns
    them. None where no group holds two devices or an index lies outside
    the mesh."""
    if not groups or not axes:
        return None
    sizes = [s for _, s in axes]
    total = math.prod(sizes)
    varying = set()
    for group in groups:
        coords = []
        for dev in group:
            if not 0 <= dev < total:
                return None
            c, rest = [], dev
            for s in reversed(sizes):
                c.append(rest % s)
                rest //= s
            coords.append(c[::-1])
        for d in range(len(sizes)):
            if len({c[d] for c in coords}) > 1:
                varying.add(d)
    if not varying:
        return None
    return "+".join(axes[d][0] for d in sorted(varying))


def _detail(raw, shapes):
    """Operand shapes and window of a product, for the tables."""
    if raw.opcode not in ("convolution", "dot"):
        return ""
    parts = [_plain(shapes.get(o, "?")) for o in raw.operands[:2]]
    text = " * ".join(parts)
    w = _WINDOW.search(raw.attrs)
    if w and w.group(1) != "size=1":
        text += " {%s}" % w.group(1)
    labels = _DIM_LABELS.search(raw.attrs)
    if labels:
        text += " %s_%s->%s" % labels.groups()
    groups = _INT_ATTR.findall(raw.attrs)
    for key, value in groups:
        if value != "1":
            text += " %s=%s" % (key, value)
    return text


def _name_async_halves(comps):
    """`async-start(...), calls=%c` / `async-done` are the plain spelling
    of what the text elsewhere calls `slice-start` / `slice-done` (or
    `copy-`, `all-gather-`...): give each half the opcode of the
    operation its computation wraps, so one rule reads both."""
    for raws in comps.values():
        started = {}
        for index, raw in enumerate(raws):
            if raw.opcode == "async-start":
                m = _CALLS.search(raw.attrs)
                inner = next((r.opcode for r in comps.get(
                    m.group(2) if m else "", ()) if r.opcode != "parameter"),
                    None)
                if inner:
                    started[raw.name] = inner
                    raws[index] = raw._replace(opcode=inner + "-start")
            elif raw.opcode in ("async-done", "async-update") \
                    and raw.operands and raw.operands[0] in started:
                inner = started[raw.operands[0]]
                if raw.opcode == "async-update":
                    started[raw.name] = inner
                raws[index] = raw._replace(
                    opcode=inner + raw.opcode[len("async"):])


def hlo_instructions(text: str, mesh=None) -> List[Instr]:
    """The one parse of a compiled module's text: an `Instr` for every
    instruction of the entry computation, in schedule order (`entry`
    True), then for those of the computations control flow reaches (a
    while's body, a conditional's branches), whose runs show in a trace
    under their caller's. A fusion is read through its called
    computation: `heavy` names what does the work inside (`convolution`,
    `dot`, a Mosaic kernel's name, `reduce`, `scatter`, `elementwise`,
    `copy` where nothing is computed), `flops` and `bytes` what it costs
    (the entry rows' `flops` sum to the step's, as XLA's cost analysis
    counts them). `mesh` (the planner's) gives each collective the mesh
    axis its replica groups run along."""
    comps, entry = _computations(text or "")
    if entry is None:
        return []
    _name_async_halves(comps)
    module = _Module(comps)
    axes = _mesh_axes(mesh)
    reached, order = {entry}, [entry]
    for cname in order:
        for raw in comps[cname]:
            if raw.opcode in _CONTROL:
                for c in module.called(raw):
                    if c not in reached:
                        reached.add(c)
                        order.append(c)
    out: List[Instr] = []
    by_name: Dict[str, Instr] = {}
    for cname in order:
        shapes = module.shapes[cname]
        is_entry = cname == entry
        for raw in comps[cname]:
            named = _OP_NAME.search(raw.attrs)
            op_name = named.group(1) if named else ""
            role, scope, op, at = provenance(op_name)
            flops = module.flops(cname, raw)
            mxu_flops = flops and module.flops(cname, raw, mxu=True)
            heavy = module.heavy(raw)
            nbytes = module.bytes(cname, raw)
            detail = ""
            if heavy in ("convolution", "dot"):
                inner = raw
                inner_shapes = shapes
                if raw.opcode == "fusion":
                    for c in module.called(raw):
                        found = next((r for r in comps[c] if r.opcode in (
                            "convolution", "dot")), None)
                        if found is not None:
                            inner, inner_shapes = found, module.shapes[c]
                            break
                detail = _detail(inner, inner_shapes)
            coll: Dict[str, Any] = {}
            kind = collective_kind(raw.opcode)
            moved = raw
            if kind is None:
                moved = module.inner_collective(raw)
                kind = moved and collective_kind(moved.opcode)
            if kind is not None:
                g = _GROUPS.search(moved.attrs)
                p = _PAIRS.search(moved.attrs)
                groups = _parse_groups(g.group(1) if g else None,
                                       p.group(1) if p else None)
                site = _COLL.search(op_name)
                site = site.group(1) if site else None
                channel = _CHANNEL.search(moved.attrs)
                coll = {
                    "kind": kind,
                    "channel": int(channel.group(1)) if channel else None,
                    "moved": _plain(moved.shape),
                    "payload": 0 if moved.opcode.endswith("-done")
                    or raw.name.startswith("async-collective-done")
                    else (_async_moved(moved.shape)
                          if moved.opcode.endswith("-start")
                          else shape_bytes(moved.shape)),
                    "group_size": max((len(x) for x in groups), default=None)
                    if groups else None,
                    "groups": (g.group(1) if g else
                               "pairs{%s}" % p.group(1) if p else None),
                    "axis": _axis_of(groups, axes),
                    "site": site}
            if coll and coll["groups"] is None and raw.operands:
                # a '-done' half names no groups: they are its start's
                start = by_name.get(raw.operands[0])
                if start is not None and start.kind == coll["kind"]:
                    coll.update(group_size=start.group_size,
                                groups=start.groups, axis=start.axis,
                                channel=start.channel)
            declared = _DECLARED.search(raw.attrs) \
                if raw.opcode == "custom-call" else None
            if declared is not None:
                said = dict(_DECLARED_FIELD.findall(declared.group(1)))
                # XLA may hold an operand or a result of the call in VMEM
                # (`S(1)` in its layout: a copy brought it there and paid
                # for it, as for any instruction's): the call's pipeline
                # then reads it at no HBM cost, so the declared bytes
                # count by the share of the call's arrays that are in HBM
                whole = sum(shape_bytes(shapes.get(o, ""))
                            for o in raw.operands) + shape_bytes(raw.shape)
                coll.update(
                    declared_flops=float(said.get("flops", 0)),
                    declared_transcendentals=float(
                        said.get("transcendentals", 0)),
                    declared_bytes=int(said.get("bytes_accessed", 0))
                    * nbytes // max(whole, 1),
                    declared_by="jax" if heavy in JAX_KERNELS else "kernel")
            by_name[raw.name] = Instr(
                raw.name, raw.opcode, heavy, flops, mxu_flops, nbytes,
                raw.shape, detail, op_name, role, scope, op,
                at, is_entry, raw.operands,
                recompute=recompute_of(op_name), **coll)
            out.append(by_name[raw.name])
    return out


def op_label(instr) -> str:
    """What an instruction goes by in a per-op table: a collective's call
    site (`coll.<site>`), else the program op it was lowered from
    (`conv2d_grad`); outside any pd scope
    (copies the compiler made, jax-internal reductions) the last
    component of its op_name, and without one its opcode."""
    if instr.site:
        return "coll." + instr.site
    if instr.op:
        return instr.op
    tail = [t for t in instr.op_name.split("/") if t]
    return tail[-1] if tail else instr.opcode


def module_name(text: str) -> Optional[str]:
    """'jit_fn' from 'HloModule jit_fn, is_scheduled=true, ...'."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", text or "")
    return m.group(1) if m else None


# --- the accounts the executor leaves ---------------------------------------

_ACCOUNTS: "OrderedDict[str, List[Tuple[List[Instr], Dict[str, Any]]]]" = \
    OrderedDict()
_MAX_ACCOUNTS = 32


def compact(instrs: List[Instr]) -> List[Instr]:
    """The account without what never takes time on the device
    (parameters, tuples, bitcasts) and without the operand lists: what a
    compiled block keeps."""
    return [i._replace(operands=()) for i in instrs
            if i.opcode not in ZERO_COST]


def remember_account(module: str, instrs: List[Instr], **info) -> None:
    """Keep a compiled block's account under its HLO module name, where a
    reader of a trace in the same process finds it (`known_accounts`).
    Several blocks share a name (every program's step is `jit_fn`): all
    are kept, the join picks by the instructions a step ran. `info` goes
    back to the reader untouched (the program's label, its analytic cost
    table)."""
    held = _ACCOUNTS.setdefault(module, [])
    held.append((instrs, info))
    del held[:-8]
    _ACCOUNTS.move_to_end(module)
    while len(_ACCOUNTS) > _MAX_ACCOUNTS:
        _ACCOUNTS.popitem(last=False)


def known_accounts(module: Optional[str] = None):
    """[(instructions, info)] kept under `module` (a trace's `XLA
    Modules` event name: 'jit_fn(123)' reads as 'jit_fn'); all of them
    without a name."""
    if module is None:
        return [pair for held in _ACCOUNTS.values() for pair in held]
    return list(_ACCOUNTS.get(module.split("(")[0], ()))


def forget_accounts() -> None:
    _ACCOUNTS.clear()


def _load_accounts(trace_dir):
    path = os.path.join(trace_dir, ACCOUNT_FILE)
    if os.path.isdir(trace_dir) and os.path.isfile(path):
        with open(path) as f:
            saved = json.load(f)
        names = saved["fields"]
        return ([[Instr(operands=(), **dict(zip(names, row)))
                  for row in acct] for acct in saved["accounts"]],
                saved.get("device_kind"))
    return None, None


def _save_accounts(trace_dir, accounts, device_kind):
    names = [f for f in Instr._fields if f != "operands"]
    with open(os.path.join(trace_dir, ACCOUNT_FILE), "w") as f:
        json.dump({"fields": names, "device_kind": device_kind,
                   "accounts": [[[getattr(i, n) for n in names]
                                 for i in acct] for acct in accounts]}, f)


def save_required(trace_dir, cost) -> None:
    """Leave the analytic per-op-type table (`roofline.program_cost`'s
    REQUIRED FLOPs and bytes, which only the process that ran the program
    can form) in the account file beside the trace, once, so that a
    report read from the directory alone sets required beside executed."""
    path = os.path.join(trace_dir, ACCOUNT_FILE)
    try:
        with open(path) as f:
            saved = json.load(f)
        if "required" in saved:
            return
        saved["required"] = {
            op: {k: d[k] for k in ("flops", "bytes") if k in d}
            for op, d in cost.items()}
        with open(path, "w") as f:
            json.dump(saved, f)
    except (OSError, ValueError):
        pass


def saved_required(trace_dir) -> Optional[Dict[str, Dict[str, float]]]:
    """`save_required`'s table, or None."""
    try:
        with open(os.path.join(trace_dir, ACCOUNT_FILE)) as f:
            return json.load(f).get("required")
    except (OSError, ValueError):
        return None


def _peaks(device_kind):
    """(FLOP/s, HBM bytes/s, kind) from chip.py's table: of `device_kind`
    when given (a trace read away from its chip), else of this process's
    first device; (None, None, kind) for the CPU."""
    try:
        from . import chip
    except ImportError:       # loaded by path, outside the package
        return None, None, device_kind
    row = chip.PEAKS.get(device_kind) if device_kind else None
    if row is None and device_kind is None:
        try:
            import jax
            device = jax.devices()[0]
            device_kind = device.device_kind
            row = chip.peaks(device)
        except Exception:  # noqa: BLE001 - no backend: no floor
            row = None
    if row is None:
        return None, None, device_kind
    return row.bf16_tflops * 1e12, row.hbm_gbps * 1e9, device_kind


_ROW_FIELDS = ("name", "opcode", "heavy", "flops", "bytes", "shape", "detail",
               "role", "scope", "op", "at", "kind", "payload", "group_size",
               "axis", "site", "recompute", "declared_flops",
               "declared_transcendentals", "declared_bytes", "declared_by")


def _unjoined_row(name, stats):
    """The row of an event no account names: its name, what the trace's
    own op_name (`tf_op`) says of its provenance, and no cost."""
    op_name = str(stats.get("tf_op") or stats.get("op_name") or "")
    role, scope, op, at = provenance(op_name)
    kind = collective_kind(name)
    row = dict.fromkeys(_ROW_FIELDS)
    row.update(name=name, opcode=category(name),
               heavy="collective" if kind else category(name), shape="",
               detail="", role=role, scope=scope, op=op, at=at, kind=kind,
               payload=0, recompute=recompute_of(op_name),
               label=op or category(name), joined=False)
    return row


def step_account(trace_dir, accounts=None) -> Optional[Dict[str, Any]]:
    """The join of `device_steps(trace_dir)` with the compiled blocks'
    accounts:

        {"steps": [{"device", "module", "host", "busy_ms", "window_ms",
                    "rows": [row]}], "joined": share of busy time whose
         instruction an account names, "used": indices of the accounts
         that named something, "device_kind", "peak_flops",
         "hbm_bytes_per_s"}

    One row a step and instruction that ran, longest first: the account's
    fields (`name`, `opcode`, `heavy`, `flops`, `bytes`, `role`, `scope`,
    `op`, `at`, `shape`, `detail`, and for a collective `kind`,
    `payload`, `group_size`, `axis`, `site`) with `ms` (time on the
    core's timeline, an enclosing while or conditional charged only what
    its body leaves, so the rows sum to `busy_ms`), `count`, `floor_ms` =
    max(flops / peak, bytes / HBM bandwidth) with `bound` = which of the
    two it is, "flops" or "bytes" (None without peaks: the CPU; for an
    async half, whose transfer runs under other instructions; for control
    flow, whose body's rows carry it; for a Mosaic call, at which XLA's
    count stops: `flops` and `floor_ms` stay None on its row, which is how
    the benchmark's readers tell one), for a Mosaic call the four
    `declared_*` fields of its `Instr` with `kernel_floor_ms` =
    max(declared FLOPs / peak, declared bytes / HBM bandwidth) x `count`
    and `kernel_bound` (None on every other row, without peaks, and
    where the call declared nothing: an account from before PR 66) and
    `busbw_gbps` for a collective (payload over its time, times
    the nccl-tests factor at the instruction's own group size). An event
    no account names keeps its name, the trace's op_name (`tf_op`) for
    its provenance, and no cost.

    `accounts` are lists of `Instr`; by default those the executor left
    in this process (`remember_account`), else the ones an earlier join
    saved beside the trace. None where the trace holds no operation."""
    steps = device_steps(trace_dir)
    if not steps:
        return None
    device_kind = None
    if accounts is None:
        accounts = [instrs for instrs, _ in known_accounts()]
        if accounts:
            if os.path.isdir(trace_dir) and not os.path.isfile(
                    os.path.join(trace_dir, ACCOUNT_FILE)):
                _, _, device_kind = _peaks(None)
                try:
                    _save_accounts(trace_dir, accounts, device_kind)
                except OSError:
                    pass
        else:
            accounts, device_kind = _load_accounts(trace_dir)
            accounts = accounts or []
    peak, hbm, device_kind = _peaks(device_kind)
    tables = [{i.name: i for i in acct} for acct in accounts]
    out_steps = []
    joined_ps = busy_ps = 0
    chosen: Dict[Any, Dict[str, Instr]] = {}
    used: List[int] = []
    for step in steps:
        totals = _self_ps(step["events"])
        counts: Dict[str, int] = {}
        stats: Dict[str, Dict[str, Any]] = {}
        for name, _, _, st in step["events"]:
            counts[name] = counts.get(name, 0) + 1
            stats.setdefault(name, st)
        key = (step["device"], step["module"])
        if key not in chosen:
            # several blocks share a module name: the one that names the
            # most of what this step ran, the newest of equals
            best, best_hits = {}, 1
            for table in tables:
                hits = sum(1 for n in totals if n in table)
                if hits >= best_hits:
                    best, best_hits = table, hits
            chosen[key] = best
            if best:
                used.append(next(i for i, t in enumerate(tables)
                                 if t is best))
        table = chosen[key]
        exposed = {}
        if any(collective_kind(name) for name in totals):
            exposed = exposed_in_line([(name, start, dur) for name, start,
                                       dur, _ in step["events"]])
        rows = []
        for name, ps in totals.items():
            instr = table.get(name)
            ms = ps / 1e9
            if instr is None:
                row = _unjoined_row(name, stats[name])
            else:
                row = {k: getattr(instr, k) for k in _ROW_FIELDS}
                row["label"] = op_label(instr)
                row["joined"] = True
                joined_ps += ps
            row["ms"] = ms
            row["count"] = counts[name]
            row["floor_ms"] = row["bound"] = None
            floor = instr is not None and peak and hbm \
                and floor_seconds(instr, peak, hbm)
            if floor:
                row["floor_ms"] = 1e3 * counts[name] * floor[0]
                row["bound"] = floor[1]
            row["kernel_floor_ms"] = row["kernel_bound"] = None
            floor = instr is not None and peak and hbm \
                and kernel_floor_seconds(instr, peak, hbm)
            if floor:
                row["kernel_floor_ms"] = 1e3 * counts[name] * floor[0]
                row["kernel_bound"] = floor[1]
            if row["kind"]:
                # the part no concurrent non-collective event on the line
                # covers; on a chip's `XLA Ops` line, all of it
                row["exposed_ms"] = min(exposed.get(name, ps), ps) / 1e9
            row["busbw_gbps"] = None
            if row["kind"] and row["payload"] and ms > 0:
                row["busbw_gbps"] = (
                    row["payload"] * counts[name] / (ms / 1e3) / 1e9
                    * busbw_factor(row["kind"], row["group_size"] or 1))
            rows.append(row)
        if step.get("host") and tables:
            # a host line: what no instruction of the block names is the
            # runtime's own span, not device time
            rows = [r for r in rows if r["joined"]]
            if not rows:
                continue
        rows.sort(key=lambda r: -r["ms"])
        step_busy = int(round(sum(r["ms"] for r in rows) * 1e9))
        busy_ps += step_busy
        out_steps.append({
            "device": step["device"], "module": step["module"],
            "host": bool(step.get("host")), "busy_ms": step_busy / 1e9,
            "window_ms": (step["end_ps"] - step["start_ps"]) / 1e9,
            "rows": rows})
    return {"steps": out_steps,
            "joined": joined_ps / busy_ps if busy_ps else 0.0,
            "used": sorted(set(used)), "device_kind": device_kind,
            "peak_flops": peak, "hbm_bytes_per_s": hbm}


def main_steps(account: Optional[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The steps of the module that took the most device time in a
    `step_account` (the training step, not the startup program or a
    reference's jit that the trace also caught)."""
    if not account:
        return []
    busy: Dict[Any, float] = {}
    for st in account["steps"]:
        busy[st["module"]] = busy.get(st["module"], 0.0) + st["busy_ms"]
    main = max(busy, key=busy.get)
    return [st for st in account["steps"] if st["module"] == main]
