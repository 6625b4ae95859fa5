"""Operator registry: op type -> (jax lowering, shape inference, grad maker).

TPU-native replacement for the reference's op registry + kernel dispatch
(reference: paddle/fluid/framework/op_registry.h:62-195, op_info.h:68,
operator.cc:479 RunImpl). Where the reference dispatches each op to a
hand-written CPU/CUDA kernel at interpretation time, here every op carries a
*lowering* — a pure function from jax arrays to jax arrays — and the executor
traces a whole block of lowerings into a single jitted XLA computation.

Gradient ops: the reference registers a hand-written grad kernel per op
(grad_op_desc_maker.h). Here the default grad maker emits a `<type>_grad`
OpDesc whose kernel is generic: it re-applies the forward lowering under
`jax.vjp` and feeds in the output cotangents. Because the whole block (forward
+ grad ops) compiles into one XLA computation, XLA CSE merges the re-traced
forward with the original, so no redundant compute survives. Ops needing
structurally different grads (sparse embedding updates, control flow)
register custom grad makers.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.desc import OpDesc
from ..framework.framework import grad_var_name
from . import kernel_choice

# sentinel: op has no gradient (metrics, int ops, assignment of constants…)
NO_GRAD = "no_grad"


@dataclass
class OpDef:
    type: str
    lower: Optional[Callable] = None          # (ctx, op, ins) -> {slot: [values]}
    infer_shape: Optional[Callable] = None    # (op, block) -> None
    grad: Any = None                          # None=generic vjp; NO_GRAD; or maker fn
    no_kernel: bool = False                   # executor-level op (feed/fetch/while…)
    # forward input slots the generic grad should NOT differentiate (indices etc.)
    non_diff_inputs: Sequence[str] = field(default_factory=tuple)
    # output slots whose first value outlives the forward under
    # `append_backward(checkpoints=)`: a replayed segment hands them to the
    # op again under KEPT_SLOT + slot and the lowering returns them
    # (handed_on) where another op would run a second time. For an op
    # whose cost to compute again grows faster than what it leaves: the
    # two attention ops, whose work grows with the (query, key) pairs and
    # whose Out and LSE grow with the tokens; and the delta rule's
    # Inverse, eight full-precision products a chunk and pack of heads to
    # form and 16 KB a head to hold, where the op's other outputs are run
    # again from it. A `mul` or a norm is the opposite (cheap to run
    # again, as wide as its input to hold) and is what recomputation is
    # there not to keep. Python-side only: no forward op's desc carries
    # it, and a slot an op was built without is not kept.
    kept_in_replay: Sequence[str] = field(default_factory=tuple)


_REGISTRY: Dict[str, OpDef] = {}


def register(type: str, *, lower=None, infer_shape=None, grad=None,
             no_kernel=False, non_diff_inputs=(), kept_in_replay=()) -> OpDef:
    assert type not in _REGISTRY, f"op '{type}' registered twice"
    d = OpDef(type=type, lower=lower, infer_shape=infer_shape, grad=grad,
              no_kernel=no_kernel, non_diff_inputs=tuple(non_diff_inputs),
              kept_in_replay=tuple(kept_in_replay))
    _REGISTRY[type] = d
    return d


def op(type: str, *, infer_shape=None, grad=None, no_kernel=False,
       non_diff_inputs=(), kept_in_replay=()):
    """Decorator form: @op("relu") def _(ctx, op, ins): ..."""
    def deco(fn):
        register(type, lower=fn, infer_shape=infer_shape, grad=grad,
                 no_kernel=no_kernel, non_diff_inputs=non_diff_inputs,
                 kept_in_replay=kept_in_replay)
        return fn
    return deco


# a replayed op reads the first forward's value of a kept output slot
# under this prefix and the slot's name (backward._append_replay)
KEPT_SLOT = "Kept"


def handed_on(ctx, op_, ins) -> Optional[Dict[str, list]]:
    """{output slot: its values} of a replayed op that was handed its own
    result (OpDef.kept_in_replay), None for every other: the first line
    of such an op's lowering, ahead of whatever books or counts a
    lowering. An op that keeps all its outputs (the attention ops)
    returns them and runs nothing; one that keeps some (kda_scan's
    Inverse) returns them beside the others, which it runs again from
    them. Books the hand-over, as every lowering books what it chose:
    recompute_kept_total{program, type} and the values' bytes as traced,
    recompute_kept_bytes{program}."""
    slots = get(op_.type).kept_in_replay
    if not slots or KEPT_SLOT + slots[0] not in ins:
        return None
    kept = {slot: list(ins[KEPT_SLOT + slot]) for slot in slots}
    from .. import memory, telemetry
    program = telemetry.program_label(ctx.program)
    telemetry.counter(
        "recompute_kept_total",
        "replayed forward ops handed the outputs their first run kept, a "
        "lowering, by op type", labels=("program", "type")).labels(
            program=program, type=op_.type).inc()
    telemetry.counter(
        "recompute_kept_bytes",
        "bytes of the outputs kept across the forward for a replayed op, "
        "a lowering", labels=("program",)).labels(program=program).inc(
            sum(memory.nbytes_of(v) for values in kept.values()
                for v in values))
    return kept


def get(type: str) -> OpDef:
    d = try_get(type)
    if d is None:
        raise KeyError(f"op '{type}' is not registered")
    return d


def try_get(type: str) -> Optional[OpDef]:
    d = _REGISTRY.get(type)
    if d is None and type.endswith("_grad") and type[: -len("_grad")] in _REGISTRY:
        # Auto-generated grad op backed by the generic vjp kernel; registered
        # lazily so every differentiable forward op gets a grad op for free.
        d = OpDef(type=type, lower=generic_grad_lower,
                  infer_shape=infer_grad_shapes, grad=NO_GRAD)
        _REGISTRY[type] = d
    return d


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


def static_infer(type: str):
    """The shape-inference rule the static analyzer should use for `type`
    (analysis/infer.py keys its shapes pass off this): the registered
    infer_shape when there is one, the generic grad mirror for any
    `<base>_grad` of a registered base — including explicitly registered
    grad ops like dropout_grad whose build-time infer_shape is None — or
    None. Unlike try_get this never mutates the registry, so lints can
    probe coverage without materializing lazy grad entries."""
    d = _REGISTRY.get(type)
    if d is not None and d.infer_shape is not None:
        return d.infer_shape
    if type.endswith("_grad") and type[: -len("_grad")] in _REGISTRY:
        return infer_grad_shapes
    return None


# ---------------------------------------------------------------------------
# Generic gradient machinery
# ---------------------------------------------------------------------------

def make_grad_op_descs(fwd: OpDesc, no_grad_set: set) -> List[OpDesc]:
    """Build grad op desc(s) for a forward op (reference: GradOpDescMakerBase,
    framework/grad_op_desc_maker.h). Custom makers take precedence; the
    default emits one `<type>_grad` op wired by the @GRAD naming convention.
    """
    opdef = get(fwd.type)
    if opdef.grad is NO_GRAD:
        return []
    if callable(opdef.grad):
        return opdef.grad(fwd, no_grad_set)
    return generic_grad_op_descs(fwd, no_grad_set)


def generic_grad_op_descs(fwd: OpDesc, no_grad_set: set) -> List[OpDesc]:
    """The default maker's `<type>_grad` desc, which generic_grad_lower
    runs (`__fwd_type__` marks it): also what a custom maker returns for
    the ops it has nothing of its own for."""
    opdef = get(fwd.type)
    assert opdef.lower is not None, (
        f"op '{fwd.type}' has no lowering and no custom grad maker")
    inputs: Dict[str, List[str]] = {}
    for slot, names in fwd.inputs.items():
        inputs[slot] = list(names)
    for slot, names in fwd.outputs.items():
        inputs[slot] = list(names)
        inputs[slot + "@GRAD"] = [grad_var_name(n) for n in names]
    outputs = {
        slot + "@GRAD": [grad_var_name(n) for n in names]
        for slot, names in fwd.inputs.items()
        if slot not in opdef.non_diff_inputs
        and any(n not in no_grad_set for n in names)
    }
    if not outputs:
        return []
    g = OpDesc(type=fwd.type + "_grad", inputs=inputs, outputs=outputs,
               attrs=dict(fwd.attrs))
    g.attrs["__fwd_type__"] = fwd.type
    return [g]


def _is_diff(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)


def generic_grad_lower(ctx, op, ins):
    """Kernel for auto-generated `<type>_grad` ops: vjp of the forward lowering.

    Grad-op inputs hold the forward inputs (original slot names), forward
    outputs, and `<slot>@GRAD` cotangents; outputs are `<slot>@GRAD` input
    grads. Missing cotangents are treated as zeros (an output unused by the
    loss).
    """
    fwd_type = op.attr("__fwd_type__") or op.type[: -len("_grad")]
    fwd_def = get(fwd_type)

    # Reconstruct the forward op view.
    fwd_in_slots = [s for s in op.desc.inputs
                    if not s.endswith("@GRAD") and s not in op.desc.outputs
                    and s + "@GRAD" not in op.desc.inputs]
    # slots that are forward outputs: those with a matching @GRAD input slot
    fwd_out_slots = [s for s in op.desc.inputs
                     if not s.endswith("@GRAD") and s + "@GRAD" in op.desc.inputs]

    fwd_attrs = {k: v for k, v in op.desc.attrs.items() if k != "__fwd_type__"}
    fwd_desc = OpDesc(type=fwd_type,
                      inputs={s: op.desc.inputs[s] for s in fwd_in_slots + fwd_out_slots
                              if s in fwd_in_slots},
                      outputs={s: [n[: -len("@GRAD")] if n.endswith("@GRAD") else n
                                   for n in op.desc.inputs[s]] for s in fwd_out_slots},
                      attrs=fwd_attrs)
    from ..framework.framework import Operator
    fwd_op_view = Operator.__new__(Operator)
    fwd_op_view.block = getattr(op, "block", None)
    fwd_op_view.desc = fwd_desc

    fwd_ins = {s: ins[s] for s in fwd_in_slots if s in ins}

    # Differentiable leaves: float arrays in slots the op differentiates and
    # for which this grad op wants an output.
    want = set()
    for slot in fwd_in_slots:
        if slot + "@GRAD" in op.desc.outputs and slot not in fwd_def.non_diff_inputs:
            want.add(slot)

    diff_paths = []  # (slot, idx)
    for slot in sorted(want):
        for i, v in enumerate(fwd_ins.get(slot, [])):
            if _is_diff(v):
                diff_paths.append((slot, i))

    out_slots_order = sorted(fwd_out_slots)
    # (slot, idx) for each value fwd_fn actually returns — a lowering may
    # produce fewer outputs than the op declares (e.g. sequence_pool's
    # MaxIndex); populated during the eager vjp trace below
    out_spec: List = []

    def arrives(s, j):
        gvals = ins.get(s + "@GRAD", [])
        return j < len(gvals) and gvals[j] is not None

    def fwd_fn(diff_vals):
        local = {s: list(vs) for s, vs in fwd_ins.items()}
        for (slot, i), v in zip(diff_paths, diff_vals):
            local[slot][i] = v
        outs = fwd_def.lower(ctx, fwd_op_view, local)
        flat = []
        out_spec.clear()
        for s in out_slots_order:
            for j, v in enumerate(outs.get(s, [])):
                # an output nothing downstream differentiates (the loss
                # op's Softmax, a norm's saved statistics) leaves the
                # backward at trace time: fed a zeros cotangent instead,
                # its whole pullback is emitted and XLA keeps it, because
                # x * 0 is not 0 for floats (2 % of the GPT-2 step for the
                # loss's exp(logits) * 0 alone, PERF.md section 6, PR 31)
                flat.append(v if arrives(s, j) else jax.lax.stop_gradient(v))
                out_spec.append((s, j))
        return flat

    primals = [fwd_ins[s][i] for s, i in diff_paths]
    # the vjp re-traces the forward lowering, which counted itself on
    # the forward trace: nothing is booked a second time
    with kernel_choice.retrace():
        out_vals, vjp_fn = jax.vjp(fwd_fn, primals)

    # Cotangents matched to fwd_fn's actual flat output.
    cts = []
    for ov, (s, j) in zip(out_vals, out_spec):
        ov = jnp.asarray(ov)
        if not jnp.issubdtype(ov.dtype, jnp.inexact):
            # integer/bool outputs carry no gradient signal
            cts.append(np.zeros(ov.shape, dtype=jax.dtypes.float0))
        elif arrives(s, j):
            cts.append(jnp.asarray(ins[s + "@GRAD"][j], dtype=ov.dtype))
        else:
            cts.append(jnp.zeros_like(ov))
    (grads,) = vjp_fn(cts)

    outs: Dict[str, List[Any]] = {}
    by_slot: Dict[str, Dict[int, Any]] = {}
    for (slot, i), g in zip(diff_paths, grads):
        by_slot.setdefault(slot, {})[i] = g
    for slot in op.desc.outputs:
        base = slot[: -len("@GRAD")]
        n = len(op.desc.outputs[slot])
        vals = []
        for i in range(n):
            g = by_slot.get(base, {}).get(i)
            if g is None:
                # non-float input that still demanded a grad slot: zeros
                src = fwd_ins.get(base, [None] * (i + 1))[i]
                g = jnp.zeros_like(src) if src is not None else None
            vals.append(g)
        outs[slot] = vals
    return outs


def infer_grad_shapes(op, block):
    """Shape inference for generic grad ops: each input grad mirrors its
    forward var's shape/dtype."""
    for slot, gnames in op.desc.outputs.items():
        base = slot[: -len("@GRAD")]
        fnames = op.desc.inputs.get(base, [])
        for gname, fname in zip(gnames, fnames):
            if block.desc.has_var(gname) and block.desc.has_var(fname):
                f = block.desc.var(fname)
                g = block.desc.var(gname)
                g.shape = list(f.shape) if f.shape is not None else None
                g.dtype = f.dtype
