"""Trace-time kernel fusion pass over the ProgramDesc.

The reference treats Fluid programs as compiler IR (PAPER.md: the
transpilers rewrite ProgramDesc graphs; data_layout_transform rewrites
layouts) — this module is the fusion-pass instance of that idea, applied
at trace time like the NHWC tag pass (ops/layout.py). `plan()` pattern-
matches CONTIGUOUS op windows in the global block:

    conv2d/depthwise_conv2d -> batch_norm [-> activation]
    mul -> elementwise_add(1-D bias) [-> activation]
    elementwise/activation chains (incl. their _grad variants)
    runs of same-type sgd/momentum/adam updates sharing one LR

and the executor lowers each match as ONE fused op instead of N separate
lowerings. Because matches are contiguous windows, executing a group at
its anchor preserves the original op order exactly — no dependency
analysis is needed, and the compose paths below run each member through
the executor's own `_exec_op` (prepass, registry lowering, SEQLEN and
layout-tag bookkeeping), so they are bitwise identical to the unfused
trace. The only value-rewriting path is inference-mode conv+bn: BN folds
into the conv filter/bias (w' = w * scale/sqrt(var+eps), b' = bias -
mean * that) and the conv's own output is elided from the trace when
nothing else consumes it. A training-mode conv+bn[+act] window composes
like every other: between two convolutions XLA sees plain jax.numpy and
chooses layouts, fusions and what the backward keeps (PERF.md, PR 34:
the Mosaic bn+act kernel that stood here cost its own sweeps and a
relayout of every conv output, and went).

An optimizer bucket rewrites no value: it is ONE scope and observer
entry over per-tensor updates, each dense member's own sgd/momentum/adam
arithmetic in its own shape, dtype, layout and sharding, so every output
aliases its donated input. No flat buffer is built: inside one
executable a concatenation saves no launch and costs a relayout of every
tensor both ways (PERF.md, PR 27). SelectedRows grads keep their sparse
scatter-apply buckets.

Gradients stay consistent for free: fused windows only ever cover
forward ops whose `<type>_grad` ops re-trace the UNFUSED forward
lowering (ops/registry.py generic vjp), member-level layout tags are
kept live during compose execution, and backward elementwise chains
fuse through the same compose machinery.

Env-gated by PADDLE_TPU_FUSION=1 (default on); per-reason fallback
counters (`fusion_fallback_total`) mirror executor_window_fallback_total.
Applies to the traced global block only — eager mode and control-flow
sub-blocks run per-op as before.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.desc import OpDesc
from . import layout as layout_mod
from . import optimizer_ops
from .common import SelectedRowsVal
from .math_ops import _activations
from .registry import NO_GRAD, register

# default ON; PADDLE_TPU_FUSION=0 restores the per-op trace
FUSION_OPT = os.environ.get("PADDLE_TPU_FUSION", "1") == "1"

# --- pattern tables (tools/check_registry.py lints these against the
# --- registry so a typo can't silently disable an optimization) ---------

CONV_OPS = frozenset({"conv2d", "depthwise_conv2d"})

# activations fusable as a window tail: unary X->Out, layout-agnostic,
# static attrs only
ACT_OPS = frozenset({
    "relu", "relu6", "leaky_relu", "sigmoid", "tanh", "elu", "swish",
    "brelu", "hard_sigmoid", "soft_relu",
})

# elementwise chain members (matched by base type, so their _grad
# variants ride along): the layout-agnostic pass-through set
CHAIN_OPS = frozenset(
    n for n in layout_mod.AGNOSTIC_OPS if not n.endswith("_grad"))

OPTIMIZER_BUCKET_OPS = frozenset({"sgd", "momentum", "adam"})

FUSED_OP_TYPES = (
    "fused_conv_bn_act", "fused_bn_act", "fused_fc_act", "fused_chain",
    "fused_sgd", "fused_momentum", "fused_adam",
    "fused_sparse_sgd", "fused_sparse_momentum", "fused_sparse_adam",
)

# per-param input slots / shared input slots / per-param output slots
_OPT_SLOTS = {
    "sgd": (("Param", "Grad"), ("LearningRate",), ("ParamOut",)),
    "momentum": (("Param", "Grad", "Velocity"), ("LearningRate",),
                 ("ParamOut", "VelocityOut")),
    "adam": (("Param", "Grad", "Moment1", "Moment2"),
             ("LearningRate", "Beta1Pow", "Beta2Pow"),
             ("ParamOut", "Moment1Out", "Moment2Out")),
}


@dataclass
class Group:
    """One fused window: ops[start:end] of the global block, executed as
    a unit at the anchor index (start)."""
    kind: str                    # conv_bn_act | bn_act | fc_act | chain | opt_bucket
    start: int
    end: int                     # exclusive
    members: Tuple[Any, ...]     # Operators in block order
    op: Any = None               # synthetic fused Operator (non-bucket kinds)
    conv: Any = None
    bn: Any = None
    act: Any = None
    fold: bool = False           # inference-mode BN fold into conv weights
    elide: Tuple[str, ...] = ()  # names the fold path never materializes
    cache: Dict[Any, Any] = field(default_factory=dict)


# --- plan ---------------------------------------------------------------

_PLANS: Dict[Tuple[int, int], Tuple[Any, Optional[Dict[int, Group]]]] = {}


def plan(program) -> Optional[Dict[int, Group]]:
    """anchor index -> Group for the program's global block, or None when
    fusion is off / nothing matches. Cached per (id, version) like the
    executor's jit cache."""
    if not FUSION_OPT:
        return None
    key = (id(program), getattr(program, "_version", 0))
    hit = _PLANS.get(key)
    if hit is not None and hit[0] is program:
        return hit[1]
    if len(_PLANS) > 64:
        _PLANS.clear()
    groups = _build(program.global_block())
    _PLANS[key] = (program, groups)
    return groups


def _build(block) -> Optional[Dict[int, Group]]:
    ops = block.ops
    groups: Dict[int, Group] = {}
    i, n = 0, len(ops)
    while i < n:
        g = (_match_opt_bucket(ops, i) or _match_conv_bn_act(ops, i)
             or _match_fc_act(ops, i) or _match_chain(ops, i))
        if g is not None:
            groups[i] = g
            i = g.end
        else:
            i += 1
    return groups or None


def _first(names: List[str]) -> Optional[str]:
    return names[0] if names else None


def _match_conv_bn_act(ops, i) -> Optional[Group]:
    n = len(ops)
    conv = None
    j = i
    if ops[j].type in CONV_OPS:
        conv = ops[j]
        j += 1
        if j >= n or ops[j].type != "batch_norm":
            return None
    elif ops[j].type != "batch_norm":
        return None
    bn = ops[j]
    if conv is not None and \
            _first(bn.desc.input("X")) != _first(conv.desc.output("Output")):
        return None
    j += 1
    act = None
    if j < n and ops[j].type in ACT_OPS and \
            _first(ops[j].desc.input("X")) == _first(bn.desc.output("Y")):
        act = ops[j]
        j += 1
    if conv is None and act is None:
        return None   # a bare batch_norm is not a window
    members = tuple(m for m in (conv, bn, act) if m is not None)
    fold, elide = False, ()
    if conv is not None and bn.attr("is_test", False):
        out_name = _first(conv.desc.output("Output"))
        fold = _foldable(ops, conv, bn, out_name)
        if fold:
            elide = (out_name,)
    kind = "conv_bn_act" if conv is not None else "bn_act"
    g = Group(kind=kind, start=i, end=j, members=members,
              conv=conv, bn=bn, act=act, fold=fold, elide=elide)
    g.op = _window_synth(
        members, "fused_conv_bn_act" if conv is not None else "fused_bn_act",
        g, elide=elide)
    return g


def _foldable(ops, conv, bn, out_name) -> bool:
    """The conv output can be elided iff bn is its only consumer, no
    later op rewrites the name, and it isn't persistable state."""
    if out_name is None:
        return False
    for o in ops:
        if o is bn or o is conv:
            continue
        if out_name in o.desc.input_arg_names():
            return False
        if out_name in o.desc.output_arg_names():
            return False
    block = getattr(conv, "block", None)
    if block is not None and block.desc.has_var(out_name) and \
            block.desc.var(out_name).persistable:
        return False
    return True


def _match_fc_act(ops, i) -> Optional[Group]:
    n = len(ops)
    if ops[i].type != "mul" or i + 1 >= n or \
            ops[i + 1].type != "elementwise_add":
        return None
    mul, add = ops[i], ops[i + 1]
    if _first(add.desc.input("X")) != _first(mul.desc.output("Out")):
        return None
    # channel-bias form only: a 1-D Y (plan-time shape from the block)
    yname = _first(add.desc.input("Y"))
    block = getattr(mul, "block", None)
    if yname is None or block is None or not block.desc.has_var(yname):
        return None
    yshape = block.desc.var(yname).shape
    if yshape is None or len(yshape) != 1:
        return None
    j = i + 2
    act = None
    if j < n and ops[j].type in ACT_OPS and \
            _first(ops[j].desc.input("X")) == _first(add.desc.output("Out")):
        act = ops[j]
        j += 1
    members = tuple(m for m in (mul, add, act) if m is not None)
    g = Group(kind="fc_act", start=i, end=j, members=members)
    g.op = _window_synth(members, "fused_fc_act", g)
    return g


def _chain_ok(op) -> bool:
    t = op.type
    base = t[: -len("_grad")] if t.endswith("_grad") else t
    return base in CHAIN_OPS


def _match_chain(ops, i) -> Optional[Group]:
    n = len(ops)
    j = i
    while j < n and _chain_ok(ops[j]):
        j += 1
    if j - i < 2:
        return None
    members = tuple(ops[i:j])
    g = Group(kind="chain", start=i, end=j, members=members)
    g.op = _window_synth(members, "fused_chain", g)
    return g


def _opt_key(op):
    lr = tuple(op.desc.input("LearningRate"))
    if op.type == "sgd":
        return (lr,)
    if op.type == "momentum":
        return (lr, op.attr("mu"), bool(op.attr("use_nesterov", False)))
    return (lr, op.attr("beta1", 0.9), op.attr("beta2", 0.999),
            op.attr("epsilon", 1e-8), tuple(op.desc.input("Beta1Pow")),
            tuple(op.desc.input("Beta2Pow")))


def _match_opt_bucket(ops, i) -> Optional[Group]:
    t = ops[i].type
    if t not in OPTIMIZER_BUCKET_OPS:
        return None
    key0 = _opt_key(ops[i])
    n = len(ops)
    j = i + 1
    while j < n and ops[j].type == t and _opt_key(ops[j]) == key0:
        j += 1
    if j - i < 2:
        return None
    return Group(kind="opt_bucket", start=i, end=j, members=tuple(ops[i:j]))


# --- synthetic fused Operators ------------------------------------------

def _synth_operator(block, desc, site):
    from ..framework.framework import Operator
    o = Operator.__new__(Operator)   # view pattern, as registry's grad re-trace
    o.block = block
    o.desc = desc
    o.creation_site = site
    return o


def _window_synth(members, type_, group, elide=()):
    """One fused op spanning the window. Member slots merge under
    per-member prefixes ("<k>:<slot>") so colliding slot names (bn "X" vs
    act "X") stay distinct; only EXTERNAL inputs (not produced inside the
    window) are declared. The compose lowerings read ctx.env directly and
    ignore the gathered ins."""
    produced = set()
    inputs: Dict[str, List[str]] = {}
    outputs: Dict[str, List[str]] = {}
    attrs: Dict[str, Any] = {}
    for k, m in enumerate(members):
        for slot, names in m.desc.inputs.items():
            ext = [x for x in names if x not in produced]
            if ext:
                inputs[f"{k}:{slot}"] = ext
        for slot, names in m.desc.outputs.items():
            keep = [x for x in names if x not in elide]
            if keep:
                outputs[f"{k}:{slot}"] = keep
            produced.update(names)
        for a, v in m.desc.attrs.items():
            attrs[f"{k}:{a}"] = v
    attrs["__fusion_group__"] = group
    role = members[0].desc.attrs.get("op_role")
    if role is not None:
        # the executor's pd_role.<role> scope: a window's device time is
        # booked to the pass its members belong to
        attrs["op_role"] = role
    desc = OpDesc(type=type_, inputs=inputs, outputs=outputs, attrs=attrs)
    return _synth_operator(getattr(members[0], "block", None), desc,
                           getattr(members[0], "creation_site", None))


def _bucket_synth(group, members, t, prefix="fused_"):
    """Fused optimizer op over a bucket's members: slots keep their
    natural names with one entry per member (uniform across members),
    shared slots (LR, beta pows) collapse to one. prefix="fused_sparse_"
    builds the scatter-apply bucket (members re-executed by
    _sparse_bucket_lower under one scope); the prefix is part of the
    cache key because a member set can flip dense<->sparse across traces
    (PADDLE_TPU_SPARSE_APPLY toggles between compiles)."""
    key = (prefix,) + tuple(id(m) for m in members)
    hit = group.cache.get(key)
    if hit is not None:
        return hit
    per_param, shared, outs = _OPT_SLOTS[t]
    inputs = {s: [_first(m.desc.input(s)) for m in members]
              for s in per_param}
    for s in shared:
        inputs[s] = list(members[0].desc.input(s))
    outputs = {s: [_first(m.desc.output(s)) for m in members] for s in outs}
    attrs = dict(members[0].desc.attrs)
    attrs["__fusion_group__"] = group
    if prefix == "fused_sparse_":
        attrs["__sparse_members__"] = tuple(members)
    desc = OpDesc(type=prefix + t, inputs=inputs, outputs=outputs,
                  attrs=attrs)
    op = _synth_operator(getattr(members[0], "block", None), desc,
                         getattr(members[0], "creation_site", None))
    group.cache[key] = op
    return op


# --- execution ----------------------------------------------------------

def _count(ctx, reason: str, amount: int = 1):
    from .. import telemetry
    telemetry.counter(
        "fusion_fallback_total",
        "ops lowered unfused by the trace-time fusion pass, by reason",
        labels=("program", "reason")).labels(
        program=telemetry.program_label(ctx.program), reason=reason).inc(
        amount)


@contextmanager
def _muted_observers():
    """Member ops run through the executor's full _exec_op for bitwise
    parity, but only the FUSED op should reach the cost observers — the
    device-side HLO attribution keys on the outermost pd.* named scope
    (xplane.provenance), so the analytic table must match it."""
    from .. import executor as executor_mod
    saved = executor_mod._op_observers
    executor_mod._op_observers = []
    try:
        yield
    finally:
        executor_mod._op_observers = saved


def execute_group(executor, ctx, group: Group, env, protected=()):
    """Lower one planned group at its anchor. `protected` (fetch names +
    persistable outputs) blocks fold-mode elision at trace time — the
    plan is fetch-agnostic and cached."""
    if group.kind == "opt_bucket":
        _execute_opt_bucket(executor, ctx, group, env)
        return
    if group.elide and (set(group.elide) & set(protected)):
        _count(ctx, "fetched_intermediate", len(group.members))
        for m in group.members:
            executor._exec_op(ctx, m, env)
        return
    executor._exec_op(ctx, group.op, env)


def _execute_opt_bucket(executor, ctx, group: Group, env):
    from . import sparse_ops
    t = group.members[0].type
    tables = getattr(ctx.program, "_sharded_tables", None) or {}
    dense: List[Any] = []
    sparse: List[Any] = []
    for m in group.members:
        gname = _first(m.desc.input("Grad"))
        pname = _first(m.desc.input("Param"))
        if isinstance(env.get(gname), SelectedRowsVal):
            # sparse grads never join the dense bucket (densifying would
            # be O(vocab)); when the op has a scatter-apply kernel they
            # get their own per-dtype fused_sparse bucket below. The
            # reasons distinguish "kept sparse on purpose" (dashboards
            # should not read the sparse path as a perf cliff) from a
            # genuinely unsupported combination.
            if sparse_ops.sparse_apply_enabled() \
                    and t in sparse_ops.SPARSE_APPLY_OPS:
                _count(ctx, "sharded_table_sparse_path" if pname in tables
                       else "sparse_grad_handled")
                sparse.append(m)
            else:
                _count(ctx, "sparse_grad_unsupported")
                executor._exec_op(ctx, m, env)
        else:
            dense.append(m)
    # every dense member joins one bucket whatever its dtype or sharding:
    # the fused lowering updates each tensor on its own
    if len(dense) < 2:
        for m in dense:
            executor._exec_op(ctx, m, env)
    else:
        executor._exec_op(ctx, _bucket_synth(group, dense, t), env)
    # scatter-apply members bucket per param dtype, mirroring the dense
    # buckets: one fused_sparse_<t> unit per dtype (the scatters stay
    # per-table — tables differ in height — but share one scope/observer
    # entry so attribution sees one apply unit, not N stragglers)
    sbuckets: Dict[str, List[Any]] = {}
    for m in sparse:
        p = env.get(_first(m.desc.input("Param")))
        sbuckets.setdefault(str(getattr(p, "dtype", None)), []).append(m)
    for sig in sorted(sbuckets):
        ms = sbuckets[sig]
        if len(ms) < 2:
            for m in ms:
                executor._exec_op(ctx, m, env)
            continue
        executor._exec_op(
            ctx, _bucket_synth(group, ms, t, prefix="fused_sparse_"), env)


# --- compose machinery --------------------------------------------------

def _out_names(op_) -> List[str]:
    return [n for ns in op_.desc.outputs.values() for n in ns]


def _freeze(ctx, env, names):
    """After members ran inside a fused lowering, freeze their layout
    tags and SEQLEN side channels into the OUTER op's override dicts —
    otherwise the executor's post-op tag_outputs/SEQLEN pass (which only
    understands the fused op's merged desc) would clobber member-exact
    state. A None override pops, same as absent."""
    from .. import executor as executor_mod
    ctx.layout_overrides = {n: ctx.layouts.get(n) for n in names}
    seq: Dict[str, Any] = {}
    for n in names:
        seq[n] = env.get(n + executor_mod.SEQLEN_SUFFIX)
        seq[n + executor_mod.SEQLEN2_SUFFIX] = \
            env.get(n + executor_mod.SEQLEN2_SUFFIX)
    ctx.seq_overrides = seq


def _collect(op_, env):
    return {slot: [env.get(n) for n in names]
            for slot, names in op_.desc.outputs.items()}


def _compose_lower(ctx, op_, ins):
    """Generic fused lowering: run every member through the executor's
    own _exec_op (prepass -> registry lowering -> tag/SEQLEN bookkeeping)
    under the fused op's named scope — bitwise identical values to the
    unfused trace, one scope/observer entry for attribution."""
    g: Group = op_.attr("__fusion_group__")
    env = ctx.env
    with _muted_observers():
        for m in g.members:
            ctx.executor._exec_op(ctx, m, env)
    _freeze(ctx, env, _out_names(op_))
    return _collect(op_, env)


# --- conv/bn/act window -------------------------------------------------

def _conv_bn_act_lower(ctx, op_, ins):
    """Inference folds (only a window with a conv can: `_match_conv_bn_act`);
    a training-mode window composes its members."""
    g: Group = op_.attr("__fusion_group__")
    if g.fold:
        return _fold_lower(ctx, op_, g, ctx.env)
    return _compose_lower(ctx, op_, ins)


def _fold_lower(ctx, op_, g: Group, env):
    """Inference-mode conv+bn[+act]: BN folds into the conv filter and a
    channel bias — y = conv(x, w * s) + (bias - mean * s) with
    s = scale/sqrt(var+eps) — and the conv's own output is never
    materialized (the plan guaranteed bn is its only consumer). The
    folded filter goes through the REGISTERED conv lowering (a view of
    the conv op whose Output name is bn's Y), so NHWC layout handling and
    AMP casts stay identical."""
    from .registry import get as reg_get
    conv, bn, act = g.conv, g.bn, g.act
    w = jnp.asarray(env[_first(conv.desc.input("Filter"))])
    scale = jnp.asarray(env[_first(bn.desc.input("Scale"))]).astype(
        jnp.float32)
    bias = jnp.asarray(env[_first(bn.desc.input("Bias"))]).astype(
        jnp.float32)
    mean = jnp.asarray(env[_first(bn.desc.input("Mean"))]).astype(
        jnp.float32)
    var = jnp.asarray(env[_first(bn.desc.input("Variance"))]).astype(
        jnp.float32)
    eps = bn.attr("epsilon", 1e-5)
    s = scale * jax.lax.rsqrt(var + eps)
    # OIHW filter: fold scales the output-channel dim (groups included)
    wf = (w.astype(jnp.float32) * s.reshape((-1,) + (1,) * (w.ndim - 1))
          ).astype(w.dtype)
    bf = bias - mean * s

    y_name = _first(bn.desc.output("Y"))
    view_desc = OpDesc(type=conv.type, inputs=dict(conv.desc.inputs),
                       outputs={"Output": [y_name]},
                       attrs=dict(conv.desc.attrs))
    conv_view = _synth_operator(getattr(conv, "block", None), view_desc,
                                getattr(conv, "creation_site", None))
    conv_ins = {slot: [env.get(n) for n in names]
                for slot, names in conv.desc.inputs.items()}
    conv_ins["Filter"] = [wf]
    y = reg_get(conv.type).lower(ctx, conv_view, conv_ins)["Output"][0]
    tag = ctx.layout_overrides.get(y_name)
    bfc = bf.astype(y.dtype)   # AMP O2: keep bf16 activations bf16
    if tag is not None:
        y = y + bfc.reshape((1,) * (y.ndim - 1) + (-1,))
    else:
        y = y + bfc.reshape((1, -1) + (1,) * (y.ndim - 2))
    env[y_name] = y
    if tag is not None:
        ctx.layouts[y_name] = tag
    # is_test BN passes running stats through all four stat outputs
    env[_first(bn.desc.output("MeanOut"))] = env[_first(bn.desc.input("Mean"))]
    env[_first(bn.desc.output("VarianceOut"))] = \
        env[_first(bn.desc.input("Variance"))]
    env[_first(bn.desc.output("SavedMean"))] = \
        env[_first(bn.desc.input("Mean"))]
    env[_first(bn.desc.output("SavedVariance"))] = \
        env[_first(bn.desc.input("Variance"))]
    if act is not None:
        out = _first(act.desc.output("Out"))
        env[out] = _activations[act.type](y, act)
        if tag is not None:
            ctx.layouts[out] = tag
    _freeze(ctx, env, _out_names(op_))
    return _collect(op_, env)


# --- bucketed optimizer lowerings ---------------------------------------
# One op over the bucket, one update per member: each tensor keeps its own
# shape, dtype, layout and sharding, so its outputs alias the donated
# state element for element and the arithmetic is the per-parameter op's
# own (bitwise; PADDLE_TPU_FUSION=0 yields the same values).

def _members(ctx, op_, ins, *slots):
    """Per member: (param, grad upcast to the param's dtype as
    `optimizer_ops._param_grad` does, then the named state tensors).
    Grads are dense here: SelectedRows members never join this bucket.

    Each gradient passes an optimization barrier on its way in, so the
    update is a pass of its own over the member's tensors. Left free,
    XLA fuses Adam into the epilogue of the matmul that produced the
    gradient, where the same six streams cost 3x the standalone pass
    (gpt2.train-t1024: `mul_grad` +10.4 ms a step against `fused_adam`
    5.1 ms; ResNet-50's convs read the same either way; PERF.md, PR 27)."""
    ps = [jnp.asarray(p) for p in ins["Param"]]
    # the barrier sits before the upcast: what is written out is the
    # gradient in its own (under AMP, half the) width
    gs = _grads_where_params_lie(
        ctx, op_, [jax.lax.optimization_barrier(jnp.asarray(g)).astype(
            p.dtype) for p, g in zip(ps, ins["Grad"])])
    for p, g, *state in zip(ps, gs, *(ins[s] for s in slots)):
        yield (p, g, *(jnp.asarray(v) for v in state))


def _grads_where_params_lie(ctx, op_, gs):
    """On a mesh, the gradients of the members whose parameter lies
    replicated are traced through ONE concatenation pinned replicated.
    It costs nothing on the chip: once the step is partitioned XLA
    forwards the slices to their operands and the compiled step holds
    no concatenate. What it does is keep GSPMD's plan for the rest of
    the step: tied tensor by tensor to their replicated parameters, the
    vectors' gradients pull the activations around every layer norm and
    bias off the tp axis (0.85 GB more temporaries per 4 layers of
    gpt2-large, and at 36 the compiler refuses the step: 2.6K sync
    flags of 2.0K), and a pin on each gradient does the same. Sharded
    members need nothing: GSPMD reduce-scatters their gradients onto
    the parameter's spec (PERF.md, PR 27)."""
    mesh = getattr(ctx.program, "_mesh", None)
    if mesh is None or mesh.size == 1:
        return gs
    specs = getattr(ctx.program, "_param_shardings", None) or {}
    whole = [i for i, n in enumerate(op_.desc.input("Param"))
             if not specs.get(n)]
    if not whole:
        return gs
    from jax.sharding import NamedSharding, PartitionSpec
    flat = jax.lax.with_sharding_constraint(
        jnp.concatenate([gs[i].ravel() for i in whole]),
        NamedSharding(mesh, PartitionSpec()))
    gs, off = list(gs), 0
    for i in whole:
        g, n = gs[i], gs[i].size
        # (members of two dtypes promote the buffer; the way back is exact)
        gs[i] = flat[off:off + n].reshape(g.shape).astype(g.dtype)
        off += n
    return gs


def _by_slot(t, rows):
    """One tuple of outputs per member -> {output slot: [per member]}."""
    return {s: list(col) for s, col in zip(_OPT_SLOTS[t][2], zip(*rows))}


def _lower_fused_sgd(ctx, op_, ins):
    lr = optimizer_ops._lr(ins)
    return {"ParamOut": [optimizer_ops.sgd_dense(p, g, lr)
                         for p, g in _members(ctx, op_, ins)]}


def _lower_fused_momentum(ctx, op_, ins):
    lr = optimizer_ops._lr(ins)
    mu, nesterov = op_.attr("mu"), op_.attr("use_nesterov", False)
    return _by_slot("momentum", [
        optimizer_ops.momentum_dense(p, g, v, lr, mu, nesterov)
        for p, g, v in _members(ctx, op_, ins, "Velocity")])


def _lower_fused_adam(ctx, op_, ins):
    lr = optimizer_ops._lr(ins)
    b1p = jnp.asarray(ins["Beta1Pow"][0]).reshape(())
    b2p = jnp.asarray(ins["Beta2Pow"][0]).reshape(())
    b1, b2 = op_.attr("beta1", 0.9), op_.attr("beta2", 0.999)
    eps = op_.attr("epsilon", 1e-8)
    return _by_slot("adam", [
        optimizer_ops.adam_dense(p, g, m1, m2, lr, b1, b2, eps, b1p, b2p)
        for p, g, m1, m2 in _members(ctx, op_, ins, "Moment1", "Moment2")])


def _sparse_bucket_lower(ctx, op_, ins):
    """Fused scatter-apply bucket: run each member optimizer op (whose
    lowering hits the sparse_ops scatter-apply kernel, including the
    sharded-table pin-back) under ONE fused scope/observer entry — the
    values are bitwise identical to the per-param sparse path, only the
    attribution unit changes, mirroring _compose_lower for dense windows."""
    env = ctx.env
    with _muted_observers():
        for m in op_.attr("__sparse_members__"):
            ctx.executor._exec_op(ctx, m, env)
    _freeze(ctx, env, _out_names(op_))
    return _collect(op_, env)


# --- registration -------------------------------------------------------

register("fused_conv_bn_act", lower=_conv_bn_act_lower, grad=NO_GRAD)
register("fused_bn_act", lower=_compose_lower, grad=NO_GRAD)
register("fused_fc_act", lower=_compose_lower, grad=NO_GRAD)
register("fused_chain", lower=_compose_lower, grad=NO_GRAD)
register("fused_sgd", lower=_lower_fused_sgd, grad=NO_GRAD)
register("fused_momentum", lower=_lower_fused_momentum, grad=NO_GRAD)
register("fused_adam", lower=_lower_fused_adam, grad=NO_GRAD)
register("fused_sparse_sgd", lower=_sparse_bucket_lower, grad=NO_GRAD)
register("fused_sparse_momentum", lower=_sparse_bucket_lower, grad=NO_GRAD)
register("fused_sparse_adam", lower=_sparse_bucket_lower, grad=NO_GRAD)

# fused ops manage layout tags themselves (member-level prepass/
# tag_outputs run inside the lowerings); without this the executor's
# prepass would barrier-canonicalize every tagged input of the window
layout_mod.AWARE_OPS.update(FUSED_OP_TYPES)
