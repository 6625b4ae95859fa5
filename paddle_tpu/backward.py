"""IR-level autodiff: append gradient ops to a Program.

TPU-native equivalent of the reference's source-to-source backward pass
(reference: python/paddle/fluid/backward.py:425 append_backward, :117
_addup_repetitive_outputs_, :167 no-grad pruning). Gradients are *ops in the
IR*, not a jax.grad closure — the program stays the product, serializable and
inspectable; JAX only executes it. Each forward op's grad ops come from the
registry's grad makers (generic vjp-backed by default, registry.py).

Fan-in accumulation: when several consumers contribute to one variable's
gradient, later contributions are renamed and summed eagerly (pairwise `sum`
ops), which is semantically the reference's @RENAME@ + sum_op insertion.

Recomputation by segments (`checkpoints=`): the forward ops between two
checkpoint variables are a segment. Before a segment's grad ops are
emitted, the forward ops they read are appended again, in the backward's
role and marked `recompute_segment` = the segment's index, reading the
segment's inputs behind one `recompute_barrier` op and writing
`<name>@RECOMPUTE`; the segment's grad ops read those. What the forward
made between two checkpoints is then dead at the segment's end, and what
lives across the forward/backward boundary is the checkpoints and, of an
op whose registry entry declares them (`OpDef.kept_in_replay`: the two
attention ops' Out and LSE, dear to compute again and one activation to
hold; the delta rule's chunk inverses), the outputs it keeps: such an op
stands in its replayed segment all the same, reads the first forward's
values of them through the barrier under `Kept<slot>` inputs and runs
nothing, or only what it does not keep (kda_scan's Out and entering
states, from the kept inverses). The ops after the
last checkpoint are not replayed: the backward starts there. Without
`checkpoints` nothing of this runs and the program is the one it was.

A checkpoint is where the backward MAY cut, not an order: the program
spells every segment's replay, and the executor, which knows the device,
lowers a segment's barrier and replayed ops only if keeping the segment's
forward values would not fit (recompute.py; a device that reports no
memory limit, the CPU, replays every segment as spelt).
"""

from __future__ import annotations

import os
from typing import (Dict, List, NamedTuple, Optional, Sequence, Set,
                    Tuple)

from .framework.desc import BlockRef, BlocksRef, OpDesc
from .framework.framework import (Block, Parameter, Program, Variable,
                                  grad_var_name)
from .ops import registry

__all__ = ["append_backward", "calc_gradient", "replayed_ops",
           "RECOMPUTE_ATTR"]

RECOMPUTE_SUFFIX = "@RECOMPUTE"
# on a replayed forward op: the index of its segment (executor._exec_op
# lowers it under `pd_recompute.<index>`, xplane.recompute_of reads it back)
RECOMPUTE_ATTR = "recompute_segment"


def _collect_no_grad(block: Block, extra: Optional[Set[str]]) -> Set[str]:
    no_grad = set(extra or ())
    for name, v in block.vars.items():
        if getattr(v, "stop_gradient", False) or v.desc.stop_gradient:
            no_grad.add(name)
    return no_grad


def _relevant_op_indices(block: Block, loss_name: str) -> List[int]:
    """Backward slice: ops that (transitively) produce the loss."""
    target = {loss_name}
    idxs = []
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        if target & set(op.output_arg_names):
            idxs.append(i)
            target |= set(op.input_arg_names)
    idxs.reverse()
    return idxs


def _ensure_grad_var(block: Block, gname: str):
    """Declare a grad var mirroring its forward var's shape/dtype."""
    if block.has_var(gname):
        return
    base = gname
    for marker in ("@RENAME@",):
        if marker in base:
            base = base.split(marker)[0]
    if base.endswith("@GRAD"):
        base = base[: -len("@GRAD")]
    if block.has_var_recursive(base):
        fv = block.var_recursive(base)
        block.create_var(name=gname, shape=fv.desc.shape, dtype=fv.dtype,
                         lod_level=fv.lod_level)
    else:
        block.create_var(name=gname)


# NO_GRAD ops that are legitimately gradient-transparent even when their
# outputs' grads are demanded: constants, shape/metadata probes, RNG sources,
# comparisons. NOT in this set: array read/write and other value-carrying
# ops — a zero grad through those is the silent-training-bug the check exists
# to catch.
_ZERO_GRAD_SAFE = frozenset({
    "fill_constant", "fill_constant_batch_size_like", "fill_constant_tensor",
    "fill", "fill_zeros_like", "assign_value", "shape", "lod_rank_table",
    "max_sequence_len", "lod_array_length", "less_than", "less_equal",
    "greater_than", "greater_equal", "equal", "not_equal", "logical_and",
    "logical_or", "logical_not", "logical_xor", "is_empty",
    "one_hot", "uniform_random", "gaussian_random",
    "uniform_random_batch_size_like", "gaussian_random_batch_size_like",
    "sign", "arg_max", "arg_min", "crf_decoding", "ctc_align",
    "sequence_mask", "prior_box", "tensor_stats",
})

_INT_DTYPES = ("bool", "int8", "uint8", "int16", "int32", "int64")


def _check_silent_zero_grad(block: Block, fwd_op, no_grad: Set[str],
                            produced_count: Dict[str, int]):
    """Raise when a NO_GRAD op sits on the loss path with differentiable
    inputs: the reference errors out when no grad op is registered
    (op_registry GradOpMaker check); silently emitting nothing trains
    quietly wrong."""
    if os.environ.get("PADDLE_TPU_ALLOW_ZERO_GRAD", "0") == "1":
        return
    if fwd_op.type in _ZERO_GRAD_SAFE:
        return
    opdef = registry.try_get(fwd_op.type)
    if opdef is None or opdef.grad is not registry.NO_GRAD:
        return
    needed = [o for o in fwd_op.output_arg_names
              if grad_var_name(o) in produced_count]
    if not needed:
        return
    diff_ins = []
    for n in fwd_op.input_arg_names:
        if n in no_grad or not block.has_var_recursive(n):
            continue
        v = block.var_recursive(n)
        dt = getattr(v, "dtype", None) or getattr(v.desc, "dtype", None)
        if dt is None or str(dt) not in _INT_DTYPES:
            diff_ins.append(n)
    if diff_ins:
        raise RuntimeError(
            f"Operator '{fwd_op.type}' lies on the loss path (outputs "
            f"{needed} need gradients) but registers no gradient; its "
            f"differentiable inputs {diff_ins} would silently receive zero "
            f"gradient. Register a grad maker for '{fwd_op.type}', mark the "
            f"inputs stop_gradient, or set PADDLE_TPU_ALLOW_ZERO_GRAD=1 to "
            f"accept zero gradients.")


# forward ops whose second run would not give what the first gave
_RANDOM_OPS = frozenset({
    "uniform_random", "gaussian_random", "uniform_random_batch_size_like",
    "gaussian_random_batch_size_like", "sampling_id", "random_crop"})


def _replay_refusal(block: Block, op) -> Optional[str]:
    """Why `op` may not run a second time in the backward, or None."""
    if any(isinstance(v, (BlockRef, BlocksRef)) for v in op.desc.attrs.values()):
        return "it runs a sub-block (recomputation covers the root block only)"
    if op.type in _RANDOM_OPS or (
            op.type == "dropout" and op.attr("dropout_prob", 0.0)
            and not op.attr("is_test", False)):
        return "it draws random numbers: a replay would draw others"
    for n in op.output_arg_names:
        v = block.var_recursive(n) if block.has_var_recursive(n) else None
        if v is not None and v.persistable:
            return f"it writes the persistable variable {n!r}"
        if v is not None and v.lod_level:
            return f"its output {n!r} carries a LoD"
        if n in op.input_arg_names:
            return f"it overwrites its input {n!r}"
    return None


def replayed_ops(program: Program, handed_on: Optional[bool] = None
                 ) -> Dict[int, List[str]]:
    """{segment: the types of the forward ops replayed in it, in order}
    of the root block, read from the ops' RECOMPUTE_ATTR (the barrier is
    not one of them): what MAY be replayed. Which of the segments a
    device replays is the executor's decision at its trace
    (Executor.recompute_plan, recompute.py). `handed_on`: True, only the ops that stand in their
    segment and are handed outputs the first forward kept (they read
    registry.KEPT_SLOT inputs); False, only the ops that run again, among
    them an op handed some of its outputs and not all (kda_scan, handed
    its Inverse); None, all."""
    found: Dict[int, List[str]] = {}
    for op in program.global_block().ops:
        seg = op.desc.attrs.get(RECOMPUTE_ATTR)
        if seg is None:
            continue
        found.setdefault(seg, [])
        if op.type == "recompute_barrier":
            continue
        kept = {s[len(registry.KEPT_SLOT):] for s in op.desc.inputs
                if s.startswith(registry.KEPT_SLOT)}
        runs = not kept or kept < set(op.desc.outputs)
        if handed_on is None or (bool(kept) if handed_on else runs):
            found[seg].append(op.type)
    return found


class _Segment(NamedTuple):
    """One recomputed segment: `index` among the segments, `ops` the
    block positions of its forward ops (all on the loss path), `ends` the
    checkpoints its last op writes."""
    index: int
    ops: List[int]
    ends: List[str]


def _segments(block: Block, rel: List[int], checkpoints) -> List[_Segment]:
    """The forward ops on the loss path cut at the ops that write a
    checkpoint; what follows the last checkpoint is no segment."""
    names = {c.name if isinstance(c, Variable) else str(c)
             for c in checkpoints}
    writer = {n: i for i in rel for n in block.ops[i].output_arg_names}
    unknown = sorted(n for n in names if not block.has_var_recursive(n))
    if unknown:
        raise ValueError(f"checkpoints {unknown} are not variables of the "
                         f"program")
    cuts = sorted({writer[n] for n in names if n in writer})
    segments, start = [], -1
    for cut in cuts:
        ops = [i for i in rel if start < i <= cut]
        ends = [n for n in block.ops[cut].output_arg_names if n in names]
        segments.append(_Segment(len(segments), ops, ends))
        start = cut
    return segments


def _append_replay(block: Block, seg: _Segment, kept: Set[str],
                   no_grad: Set[str],
                   produced_count: Dict[str, int]) -> Dict[str, str]:
    """Append segment `seg`'s barrier and replayed forward ops and return
    {forward name: the name the segment's grad ops read in its place}.
    `kept`: every checkpoint's name. Only the forward ops whose outputs a
    grad op of the segment reads (or a replayed op between them and the
    segment's inputs) run again; an op that would differ the second time
    is refused by name."""
    written = {n for i in seg.ops for n in block.ops[i].output_arg_names
               if n not in kept}
    # what the segment's grad ops read of the forward pass: a generic
    # grad op (registry.generic_grad_lower) traces its forward op again
    # from the inputs and takes the outputs' names alone
    needed = set()
    for i in seg.ops:
        fwd = block.ops[i].desc
        for g in registry.make_grad_op_descs(fwd, no_grad):
            generic = "__fwd_type__" in g.attrs
            read = fwd.input_arg_names() if generic else g.input_arg_names()
            needed |= {n for n in read if n in written}
    replayed = []
    for i in reversed(seg.ops):
        op = block.ops[i]
        if needed & set(op.output_arg_names):
            replayed.append(i)
            needed |= {n for n in op.input_arg_names if n in written}
    replayed.reverse()
    if not replayed:
        return {}
    for i in replayed:
        why = _replay_refusal(block, block.ops[i])
        if why:
            raise ValueError(
                f"recomputation segment {seg.index}: operator "
                f"'{block.ops[i].type}' (outputs "
                f"{block.ops[i].output_arg_names}) cannot be replayed: {why}. "
                f"Move a checkpoint so that it falls outside every segment.")

    def mirror(name, new):
        fv = block.var_recursive(name)
        block.create_var(name=new, shape=fv.desc.shape, dtype=fv.dtype,
                         lod_level=fv.lod_level, stop_gradient=True)
        return new

    # the segment's inputs: read by a replayed op, written by none of them,
    # and not state (a parameter is read where it lies)
    inner = {n for i in replayed for n in block.ops[i].output_arg_names}
    entering = []
    for i in replayed:
        for n in block.ops[i].input_arg_names:
            if n in inner or n in entering or not block.has_var_recursive(n) \
                    or block.var_recursive(n).persistable:
                continue
            entering.append(n)

    def behind_the_barrier(n):
        return mirror(n, f"{n}{RECOMPUTE_SUFFIX}.{seg.index}")

    rename = {n: behind_the_barrier(n) for n in entering}
    # what the first forward keeps for a replayed op that declares it
    # (registry.OpDef.kept_in_replay) enters the segment as its inputs
    # do, behind the barrier: XLA then neither relays nor converts it
    # for the backward's readers before the backward is there (ahead of
    # the barrier it kept a second and a float32 copy of every kept Out
    # alive across the whole step)
    handed = {n: behind_the_barrier(n) for i in replayed
              for s in registry.get(block.ops[i].type).kept_in_replay
              for n in block.ops[i].desc.output(s)}
    # the cotangents that enter the segment: the replay waits for them
    # behind the barrier, and XLA cannot merge it with the first forward
    cotangents = [grad_var_name(n) for n in seg.ends
                  if produced_count.get(grad_var_name(n))]
    attrs = {"op_role": "backward", RECOMPUTE_ATTR: seg.index}
    block.append_op(
        type="recompute_barrier",
        inputs={"X": entering + list(handed), "Dep": cotangents},
        outputs={"Out": [rename[n] for n in entering] + list(handed.values()),
                 "DepOut": cotangents},
        attrs=dict(attrs))
    for i in replayed:
        fwd = block.ops[i].desc
        for n in fwd.output_arg_names():
            if n not in kept:
                rename[n] = mirror(n, n + RECOMPUTE_SUFFIX)
        inputs = {s: [rename.get(n, n) for n in names]
                  for s, names in fwd.inputs.items()}
        # an op that declares kept outputs stands in its place and is
        # handed them: its lowering returns them and runs nothing, or
        # what it does not keep alone (registry.handed_on)
        for s in registry.get(fwd.type).kept_in_replay:
            if fwd.output(s):
                inputs[registry.KEPT_SLOT + s] = [handed[n]
                                                  for n in fwd.output(s)]
        block.append_op(
            type=fwd.type,
            inputs=inputs,
            outputs={s: [rename.get(n, n) for n in names]
                     for s, names in fwd.outputs.items()},
            attrs={**fwd.attrs, **attrs})
    return rename


def append_backward(loss: Variable, parameter_list: Optional[Sequence] = None,
                    no_grad_set: Optional[Set[str]] = None,
                    callbacks=None,
                    checkpoints: Optional[Sequence] = None
                    ) -> List[Tuple[Parameter, Variable]]:
    """Append grad ops for `loss` and return [(param, grad_var)].

    Only root-block autodiff is supported directly; control-flow ops carry
    their own sub-block grad logic via custom grad makers.

    `checkpoints`: variables (or names) of the forward pass at which the
    backward may cut; the forward ops between two of them are spelt again
    in the backward (module docstring), and run again wherever the
    executor finds that keeping their values would not fit the device
    (recompute.py: on a device that reports no memory limit, always).
    None or an empty list: nothing is. Root block only, as
    the autodiff itself: an op that runs a sub-block, draws random numbers,
    writes state or carries a LoD is refused by name where a segment would
    replay it. replayed_ops(program) reads what each segment replays back
    from the IR.
    """
    program = loss.block.program
    block = program.global_block()
    assert loss.block.idx == 0, "loss must live in the root block"
    no_grad = _collect_no_grad(block, no_grad_set)

    # record the loss var on the program: the inspector's auto probe mode
    # targets loss and grad vars, and grad_info_map alone cannot say which
    # forward var was the differentiation root
    losses = getattr(program, "_loss_names", None)
    if losses is None:
        losses = program._loss_names = []
    if loss.name not in losses:
        losses.append(loss.name)

    rel = _relevant_op_indices(block, loss.name)

    # Seed: d loss / d loss = 1
    loss_g = grad_var_name(loss.name)
    _ensure_grad_var(block, loss_g)
    block.append_op(
        type="fill_constant",
        outputs={"Out": [loss_g]},
        attrs={"shape": list(loss.shape or [1]), "value": 1.0,
               "dtype": loss.dtype, "op_role": "backward"})

    produced_count: Dict[str, int] = {loss_g: 1}
    grad_to_var: Dict[str, str] = {loss_g: loss.name}

    # recomputation: a segment is replayed when the walk reaches its last op
    segments = _segments(block, rel, checkpoints) if checkpoints else []
    segment_ending_at = {seg.ops[-1]: seg for seg in segments if seg.ops}
    kept = {n for seg in segments for n in seg.ends}
    replayed_as: Dict[str, str] = {}

    for i in reversed(rel):
        fwd_op = block.ops[i]
        if i in segment_ending_at:      # the segments tile what lies
            replayed_as = _append_replay(   # before the last checkpoint
                block, segment_ending_at[i], kept, no_grad, produced_count)
        gdescs = registry.make_grad_op_descs(fwd_op.desc, no_grad)
        if not gdescs:
            _check_silent_zero_grad(block, fwd_op, no_grad, produced_count)
        for g in gdescs:
            if replayed_as:
                g.inputs = {s: [replayed_as.get(n, n) for n in names]
                            for s, names in g.inputs.items()}
            # Rename duplicate grad writes, then accumulate with sum ops.
            # Exception: a grad op that CONSUMES n@GRAD and produces n@GRAD
            # mirrors a forward op that read-and-overwrote n (while loop
            # state, conditional_block carries, in-place ops). There the
            # output is the cotangent of the PRE-op value and must replace
            # the post-op cotangent — all fan-out consumers of the post-op
            # value were already summed in, since reverse-topo order visits
            # consumers before producers.
            g_grad_ins = {n for names in g.inputs.values() for n in names
                          if n.endswith("@GRAD")}
            renames: List[Tuple[str, str]] = []
            for slot, names in list(g.outputs.items()):
                new_names = []
                for n in names:
                    c = produced_count.get(n, 0)
                    if c == 0 or n in g_grad_ins:
                        produced_count[n] = max(c, 1)
                        new_names.append(n)
                    else:
                        rn = f"{n}@RENAME@{c}"
                        produced_count[n] = c + 1
                        new_names.append(rn)
                        renames.append((n, rn))
                g.outputs[slot] = new_names

            for slot, names in g.outputs.items():
                for n in names:
                    _ensure_grad_var(block, n)
                    base = n.split("@RENAME@")[0]
                    if base.endswith("@GRAD"):
                        grad_to_var[base] = base[: -len("@GRAD")]
            # role tag (reference OpRole::kBackward): inference slicing
            # (io.get_inference_program) strips these before pruning
            g.attrs.setdefault("op_role", "backward")
            block.desc.ops.append(g)
            from .framework.framework import Operator
            op_obj = Operator(block, g)
            block.ops.append(op_obj)
            program._version += 1
            block._infer_shape(op_obj)

            for orig, rn in renames:
                block.append_op(type="sum", inputs={"X": [orig, rn]},
                                outputs={"Out": [orig]},
                                attrs={"op_role": "backward"})

    program.grad_info_map.update(grad_to_var)

    if parameter_list is not None:
        params = [block.var(p) if isinstance(p, str) else p
                  for p in parameter_list]
    else:
        params = block.all_parameters()
    result = []
    for p in params:
        if not getattr(p, "trainable", True):
            continue
        gname = grad_var_name(p.name)
        if produced_count.get(gname):
            result.append((p, block.var(gname)))
    # record the (param, grad) pairing for the overlap pass
    # (parallel/overlap.py): grad names follow the grad_var_name
    # convention, but only append_backward knows which params actually
    # received a gradient in THIS program
    pairs = getattr(program, "_grad_param_pairs", None)
    if pairs is None:
        pairs = program._grad_param_pairs = []
    for p, g in result:
        ent = (p.name, g.name)
        if ent not in pairs:
            pairs.append(ent)
    # record grads that are SelectedRows by construction (is_sparse
    # lookup_table_grad): the overlap planner (parallel/overlap.py) must
    # not bucket them into dense all-reduces, and can say so at PLAN time
    # instead of discovering a sparse value at flush. Sharded tables force
    # sparse grads too, but sharding may be annotated after backward —
    # the planner cross-checks program._sharded_tables itself.
    sparse_names = getattr(program, "_sparse_grad_names", None)
    if sparse_names is None:
        sparse_names = program._sparse_grad_names = set()
    for op_ in block.ops:
        if op_.type == "lookup_table_grad" and op_.attr("is_sparse", False):
            for n in op_.output_arg_names:
                sparse_names.add(n)
    return result


def calc_gradient(targets, inputs, target_gradients=None,
                  no_grad_set: Optional[Set[str]] = None):
    """Gradients of `targets` w.r.t. `inputs` (reference backward.py:555).

    Supports multiple targets and optional initial cotangents: the combined
    gradient is built by differentiating sum_i reduce_sum(t_i * tg_i)
    (tg_i = ones when absent), which by linearity of the vjp equals the
    reference's multi-target accumulation. Like the reference, the grad ops
    are appended to the targets' program."""
    targets = list(targets) if isinstance(targets, (list, tuple)) else [targets]
    inputs = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
    if target_gradients is None:
        target_gradients = [None] * len(targets)
    elif not isinstance(target_gradients, (list, tuple)):
        target_gradients = [target_gradients]
    assert len(target_gradients) == len(targets), (
        f"{len(targets)} targets but {len(target_gradients)} target grads")
    program = targets[0].block.program
    from .framework.framework import program_guard
    from . import layers
    with program_guard(program):
        parts = []
        for t, tg in zip(targets, target_gradients):
            weighted = t if tg is None else layers.elementwise_mul(t, tg)
            parts.append(layers.reduce_sum(weighted))
        loss = parts[0]
        for p in parts[1:]:
            loss = layers.elementwise_add(loss, p)
    append_backward(loss, no_grad_set=no_grad_set)
    block = program.global_block()
    outs = []
    for v in inputs:
        gname = grad_var_name(v.name)
        outs.append(block.var(gname) if block.has_var(gname) else None)
    return outs
