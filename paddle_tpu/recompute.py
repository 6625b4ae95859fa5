"""Which segments of a checkpointed step the backward replays.

`append_backward(checkpoints=)` says where the backward MAY cut: it
appends, for every segment between two checkpoints, a `recompute_barrier`
and the forward ops again (backward.py). Whether a segment IS replayed is
decided here, where the Place is known: at the executor's trace of the
block (`Executor._trace_block`), from what the program and the device
show and nothing else.

* What keeping segment i costs: `S_i`, the bytes, as traced, of the first
  forward's values whose mirrors (`<name>@RECOMPUTE`) its replay would
  write again. What an op declares `kept_in_replay` outlives the forward
  anyway and counts nothing. A value the trace holds in no plain array
  (no shape to read) leaves its segment replayed.
* What the step holds whatever is decided (`Held`), by when it holds it:
  always, the persistable state and what every barrier lets in (the
  checkpoints, the kept outputs, a mask or a table of positions); at the
  turn from forward to backward, what the forward ops behind the last
  checkpoint wrote (head and loss); in a segment's backward, a gradient a
  trained parameter whose gradient is made by then (they live to the
  optimizer's ops, behind the backward).
* The estimate of the step's peak (`estimate`): at the turn every kept
  segment's values are live together; in a segment's backward its own
  values (kept, or written again: one replayed segment is live at a
  time), those of the kept segments before it and the gradients so far.
* The choice (`choose`): segments are kept, the smallest `S_i` first and
  of equal ones the later first (its values die first in the backward,
  while few gradients are there yet), while the estimate stays under the
  device's limit (`memory.device_limit`) less MARGIN_BYTES. A device that
  reports no limit (the CPU) keeps nothing: every segment is replayed,
  and the step is the one the IR spells.

A kept segment runs nothing twice: its barrier and its replayed ops are
not lowered, and the names they would have written are bound to the first
forward's values (`bind`). The `Program` is not edited: `replayed_ops`
keeps saying what MAY be replayed, `Executor.recompute_plan(program)`
what the last trace decided.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

from . import memory, telemetry
from .backward import RECOMPUTE_ATTR, RECOMPUTE_SUFFIX, replayed_ops
from .framework.framework import grad_var_name
from .ops import registry

__all__ = ["MARGIN_BYTES", "Segment", "Decision", "Held", "Plan",
           "segments", "estimate", "choose", "plan", "bind", "book"]

# What the estimate leaves out, as one constant: the gradient ops' own
# working set (an expert layer's conditional buffers, a kernel's float32
# partial sums), the copies XLA keeps of a kept value in another layout
# or precision, and the distance between the described schedule and the
# chip's. Its ground is PERF.md section 6, PR 67: the table of estimate,
# described `temp_bytes` and the chip's `memory_peak_bytes` for the six
# checkpointed cells.
MARGIN_BYTES = 7 * memory.GiB // 4


class Segment(NamedTuple):
    """One segment as the IR spells it: `barrier` and `ops` are positions
    in the block of its recompute_barrier and its replayed forward ops."""
    index: int
    barrier: int
    ops: List[int]


class Decision(NamedTuple):
    kept: bool
    reason: str               # fits | budget | no_limit | unknown_shape
    nbytes: Optional[int]     # S_i; None where a value had no shape


class Held(NamedTuple):
    """What the step holds whatever is decided, by when it holds it."""
    always: int               # the state and what the barriers let in
    turn: int                 # at the turn: what the ops behind the last
    #                           checkpoint wrote, and their gradients
    grads: Dict[int, int]     # {segment: the gradients made by the end of
    #                           its backward}, of trained parameters


class Plan(NamedTuple):
    decisions: Dict[int, Decision]
    skipped: frozenset        # block positions that are bound, not lowered
    limit: Optional[int]
    held: Optional[Held]      # None without a limit: nothing is weighed
    estimate: Optional[int]   # bytes at the step's peak as estimated, the
    #                           margin not among them; None without a limit

    @property
    def kept_bytes(self) -> int:
        return sum(d.nbytes for d in self.decisions.values() if d.kept)


def segments(block) -> List[Segment]:
    found: Dict[int, Segment] = {}
    for at, op in enumerate(block.ops):
        index = op.desc.attrs.get(RECOMPUTE_ATTR)
        if index is None:
            continue
        if op.type == "recompute_barrier":
            found[index] = Segment(index, at, [])
        else:
            found[index].ops.append(at)
    return [found[i] for i in sorted(found)]


def _written_again(block, seg: Segment) -> List[str]:
    """The first forward's names whose mirrors segment `seg` writes again,
    without what a replayed op is handed (kept_in_replay). A reshape's
    output counts like any other: under the chip's tiled layouts a
    reshape that splits the minor dimension is a copy, and leaving them
    out read 1e9 B further under the compiler (PERF.md section 6, PR 67)."""
    names = []
    for at in seg.ops:
        op = block.ops[at]
        handed = {n for slot in registry.get(op.type).kept_in_replay
                  if registry.KEPT_SLOT + slot in op.desc.inputs
                  for n in op.desc.output(slot)}
        names += [n[:-len(RECOMPUTE_SUFFIX)] for n in op.output_arg_names
                  if n.endswith(RECOMPUTE_SUFFIX) and n not in handed]
    return names


def _held(program, block, segs: List[Segment], nbytes, state: int) -> Held:
    """What the step holds whatever is decided (module docstring); a
    value without a shape counts nothing here."""
    def known(names):
        return sum(nbytes(n) or 0 for n in dict.fromkeys(names))

    let_in = [n for seg in segs for n in block.ops[seg.barrier].input("X")]
    # behind the last checkpoint: the forward ops after the op that wrote
    # the last segment's end (its cotangent is what the barrier waits for)
    ends = {n[:-len(grad_var_name(""))]
            for n in block.ops[segs[-1].barrier].input("Dep")}
    tail, behind = [], False
    for op in block.ops:
        if op.desc.attrs.get("op_role") in ("backward", "optimize"):
            break
        if behind:
            tail += op.output_arg_names
        behind = behind or bool(ends & set(op.output_arg_names))
    # a trained parameter's gradient lives from the op that first writes
    # it to the optimizer's ops behind the backward: by the barrier it is
    # first written behind (None: behind none, at the turn)
    trained = {g: p for p, g in getattr(program, "_grad_param_pairs", ())}
    under = {seg.barrier: seg.index for seg in segs}
    made: Dict[Optional[int], int] = {}
    region = None
    for at, op in enumerate(block.ops):
        region = under.get(at, region)
        for g in op.output_arg_names:
            if g in trained:
                made[region] = made.get(region, 0) \
                    + (nbytes(trained.pop(g)) or 0)
    grads, so_far = {}, made.get(None, 0)
    for seg in reversed(segs):      # the backward's order
        so_far += made.get(seg.index, 0)
        grads[seg.index] = so_far
    return Held(state + known(let_in), known(tail) + made.get(None, 0),
                grads)


def estimate(sizes: Dict[int, Optional[int]], held: Held, kept) -> int:
    """Bytes at the step's peak with the segments `kept` kept. At the
    turn from forward to backward every kept segment's values are live
    beside what the head and the loss wrote; in a segment's backward its
    own values (kept or written again), those of the kept segments before
    it and every gradient made so far."""
    peak = held.turn + sum(sizes[i] for i in kept)
    for index, grads in held.grads.items():
        peak = max(peak, grads + (sizes[index] or 0)
                   + sum(sizes[i] for i in kept if i < index))
    return held.always + peak


def choose(sizes: Dict[int, Optional[int]], held: Optional[Held],
           limit: Optional[int]) -> Dict[int, Decision]:
    """{segment: Decision} from each segment's `S_i` (None: unknown), the
    bytes held whatever is decided and the device's limit (None: none
    reported): segments are kept, the smallest first and of equal ones
    the later first, while the estimate stays under the limit less the
    margin."""
    if limit is None:
        return {i: Decision(False, "no_limit", s) for i, s in sizes.items()}
    decisions = {i: Decision(False, "unknown_shape", None)
                 for i, s in sizes.items() if s is None}
    order = sorted((i for i in sizes if sizes[i] is not None),
                   key=lambda i: (sizes[i], -i))
    kept: List[int] = []
    for i in order:
        if estimate(sizes, held, kept + [i]) > limit - MARGIN_BYTES:
            break
        kept.append(i)
    for i in order:
        decisions[i] = Decision(True, "fits", sizes[i]) if i in kept \
            else Decision(False, "budget", sizes[i])
    return decisions


def plan(program, env: Dict[str, Any], state_bytes: int,
         limit: Optional[int], refused: bool = False) -> Optional[Plan]:
    """The decision for the root block of `program`, read at the trace's
    first replayed op: `env` holds every value of the first forward by
    then. None where the program holds no segment. `refused`: the
    compiler ran out of memory on this step with segments kept, which is
    the budget's last word: every segment is replayed."""
    block = program.global_block()
    segs = segments(block)
    if not segs:
        return None

    def nbytes(name):
        value = env.get(name)
        if getattr(value, "shape", None) is None \
                or getattr(value, "dtype", None) is None:
            return None
        return memory.nbytes_of(value)

    sizes = {}
    for seg in segs:
        each = [nbytes(n) for n in dict.fromkeys(_written_again(block, seg))]
        sizes[seg.index] = None if None in each else sum(each)
    held = _held(program, block, segs, nbytes, state_bytes) \
        if limit is not None else None
    decisions = choose(sizes, held, limit)
    if refused:
        decisions = {i: Decision(False, "budget", s)
                     for i, s in sizes.items()}
    kept = [i for i, d in decisions.items() if d.kept]
    skipped = frozenset(at for seg in segs if seg.index in kept
                        for at in [seg.barrier] + seg.ops)
    return Plan(decisions, skipped, limit, held,
                estimate(sizes, held, kept) if held else None)


def bind(op, env: Dict[str, Any], layouts: Dict[str, str]):
    """A kept segment's op is not lowered: the names it would have
    written are the first forward's values (and their layout tags). The
    barrier's outputs are its inputs; a replayed op's mirrors are the
    values they mirror."""
    if op.type == "recompute_barrier":
        pairs = zip(op.input("X"), op.output("Out"))
    else:
        pairs = ((n[:-len(RECOMPUTE_SUFFIX)], n)
                 for n in op.output_arg_names
                 if n.endswith(RECOMPUTE_SUFFIX))
    for first, name in pairs:
        if first in env:
            env[name] = env[first]
            if first in layouts:
                layouts[name] = layouts[first]


def book(program, made: Plan):
    """The trace's decision in the metrics registry: segments by decision
    and reason, the forward ops that run again by type, and the kept
    bytes as estimated."""
    label = telemetry.program_label(program)
    by_decision = telemetry.counter(
        "recompute_segments_total",
        "segments between two checkpoints, a trace, by what the executor "
        "decided (kept: nothing runs twice; replayed) and why",
        labels=("program", "decision", "reason"))
    for d in made.decisions.values():
        by_decision.labels(program=label, reason=d.reason,
                           decision="kept" if d.kept else "replayed").inc()
    # what runs again: an op handed all the outputs its first run kept
    # books itself where it is lowered (registry.handed_on)
    by_type = telemetry.counter(
        "recompute_ops_total",
        "forward ops that run again in the backward, a trace, by op type",
        labels=("program", "type"))
    for index, types in replayed_ops(program, handed_on=False).items():
        if not made.decisions[index].kept:
            for op_type in types:
                by_type.labels(program=label, type=op_type).inc()
    telemetry.gauge(
        "recompute_segments_kept_bytes",
        "bytes of the forward values the kept segments hold across the "
        "turn to the backward, as estimated at the last trace",
        labels=("program",)).labels(program=label).set(made.kept_bytes)
