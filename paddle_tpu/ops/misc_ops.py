"""Misc + LoD-array ops: assign_value, fill, minus, modified_huber_loss,
l1_norm, average_accumulates, print, recompute_barrier, save/load(_combine),
lod_tensor_to_array / array_to_lod_tensor, split/merge_lod_tensor,
reorder_lod_tensor_by_rank.

TPU-native lowerings (reference: assign_value_op.cc, fill_op.cc,
minus_op.cc, modified_huber_loss_op.h, l1_norm_op.cc,
average_accumulates_op.h, print_op.cc, save_op.cc, load_op.cc,
save_combine_op.cc, load_combine_op.cc, lod_tensor_to_array_op.cc,
array_to_lod_tensor_op.cc, split_lod_tensor_op.cc, merge_lod_tensor_op.cc,
reorder_lod_tensor_by_rank_op.cc). The reference's row-routing LoD ops
become dense masked selects (rows keep their position; no dynamic shapes),
and the file-I/O ops run as host callbacks sequenced into the trace —
the XLA-compatible form of the reference's host-side kernels."""

from __future__ import annotations

import pickle

import jax
import jax.numpy as jnp
import numpy as np

from .common import in_var, out_var, same_as_input, set_out, to_np_dtype
from .registry import NO_GRAD, op
from .control_flow_ops import TensorArrayVal


# --- small tensor ops ---------------------------------------------------------

def _assign_value_infer(op_, block):
    set_out(op_, block, "Out", list(op_.attr("shape")),
            op_.attr("dtype", "float32"))


@op("assign_value", infer_shape=_assign_value_infer, grad=NO_GRAD)
def _assign_value(ctx, op_, ins):
    """Materialize a compile-time constant (reference assign_value_op.cc)."""
    shape = list(op_.attr("shape"))
    dtype = op_.attr("dtype", "float32")
    vals = op_.attr("fp32_values", None)
    if not vals:
        vals = op_.attr("int32_values", None)
    arr = np.asarray(vals, dtype=to_np_dtype(dtype)).reshape(shape)
    return {"Out": [jnp.asarray(arr)]}


def _fill_infer(op_, block):
    set_out(op_, block, "Out", list(op_.attr("shape")),
            op_.attr("dtype", "float32"))


@op("fill", infer_shape=_fill_infer, grad=NO_GRAD)
def _fill(ctx, op_, ins):
    """Fill Out with the literal `value` list (reference fill_op.cc)."""
    shape = list(op_.attr("shape"))
    dtype = op_.attr("dtype", "float32")
    vals = np.asarray(op_.attr("value"), dtype=to_np_dtype(dtype))
    return {"Out": [jnp.asarray(vals.reshape(shape))]}


@op("minus", infer_shape=same_as_input())
def _minus(ctx, op_, ins):
    return {"Out": [jnp.asarray(ins["X"][0]) - jnp.asarray(ins["Y"][0])]}


def _mhl_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None and xv.shape is not None:
        set_out(op_, block, "IntermediateVal", xv.shape, xv.dtype)
        set_out(op_, block, "Out", [xv.shape[0], 1], xv.dtype)


@op("modified_huber_loss", infer_shape=_mhl_infer, non_diff_inputs=("Y",))
def _modified_huber_loss(ctx, op_, ins):
    """Modified Huber loss for binary classification, labels in {0, 1}
    (reference modified_huber_loss_op.h): with a = x * (2y - 1),
    loss = -4a if a < -1; (1 - a)^2 if -1 <= a < 1; 0 otherwise."""
    x = jnp.asarray(ins["X"][0])
    y = jnp.asarray(ins["Y"][0])
    a = x * (2.0 * y - 1.0)
    loss = jnp.where(a < -1.0, -4.0 * a,
                     jnp.where(a < 1.0, (1.0 - a) ** 2, 0.0))
    return {"IntermediateVal": [a], "Out": [loss.reshape(x.shape[0], 1)]}


def _l1_infer(op_, block):
    xv = in_var(op_, block, "X")
    if xv is not None:
        set_out(op_, block, "Out", [1], xv.dtype)


@op("l1_norm", infer_shape=_l1_infer)
def _l1_norm(ctx, op_, ins):
    return {"Out": [jnp.sum(jnp.abs(jnp.asarray(ins["X"][0]))).reshape(1)]}


def _print_grad_maker(fwd, no_grad_set):
    """Identity pass-through grad: print only observes, so In@GRAD is
    Out@GRAD verbatim (reference print_op.cc registers its grad the same
    way; before this maker a Print on the loss path silently zeroed the
    gradients flowing through it — ADVICE r5)."""
    from ..framework.desc import OpDesc
    from ..framework.framework import grad_var_name
    in_name = fwd.inputs["In"][0]
    if in_name in no_grad_set:
        return []
    out_name = fwd.outputs["Out"][0]
    return [OpDesc(type="assign",
                   inputs={"X": [grad_var_name(out_name)]},
                   outputs={"Out": [grad_var_name(in_name)]})]


@op("print", grad=_print_grad_maker)
def _print(ctx, op_, ins):
    """Debug print-through (reference print_op.cc): logs the tensor each
    step via a host callback (jax.debug.print — fires at RUN time inside
    the compiled block) and forwards the input unchanged. Shows
    message + var name + shape/dtype; summarize > 0 truncates values."""
    x = jnp.asarray(ins["In"][0])
    msg = op_.attr("message", "") or ""
    name = op_.desc.inputs["In"][0]
    summarize = op_.attr("summarize", -1)
    shown = x.ravel()[:summarize] if summarize and summarize > 0 else x
    # user text goes through str.format: escape braces or a message like
    # "loss {step}" aborts tracing with a KeyError
    prefix = (f"{msg}{name} shape={tuple(x.shape)} dtype={x.dtype} "
              .replace("{", "{{").replace("}", "}}"))
    jax.debug.print(prefix + "{v}", v=shown)
    return {"Out": [x]}


# --- ModelAverage accumulators ------------------------------------------------

_K_MAX_ACC = 16384   # reference average_accumulates_op.h kMaxNumAccumulates


def _recompute_barrier_infer(op_, block):
    for src, dst in (("X", "Out"), ("Dep", "DepOut")):
        for name, out in zip(op_.input(src), op_.output(dst)):
            if name != out and block.has_var_recursive(name):
                like = block.var_recursive(name).desc
                desc = block.var_recursive(out).desc
                desc.shape, desc.dtype = like.shape, like.dtype


@op("recompute_barrier", infer_shape=_recompute_barrier_infer, grad=NO_GRAD)
def _recompute_barrier(ctx, op_, ins):
    """What stands between a replayed segment and the first forward pass
    (backward.append_backward(checkpoints=)): Out[i] = X[i], the
    segment's inputs, and DepOut = Dep, the cotangents entering the
    segment (written back under their own names), all behind one
    optimization barrier. The replayed ops read Out, so XLA can neither
    merge them with the forward ops that computed the same from X nor
    start them before the cotangents are there."""
    xs, deps = list(ins.get("X", [])), list(ins.get("Dep", []))
    out = jax.lax.optimization_barrier(tuple(xs + deps))
    return {"Out": list(out[:len(xs)]), "DepOut": list(out[len(xs):])}


@op("average_accumulates", grad=NO_GRAD,
    non_diff_inputs=("param", "in_sum_1", "in_sum_2", "in_sum_3",
                     "in_num_accumulates", "in_old_num_accumulates",
                     "in_num_updates"))
def _average_accumulates(ctx, op_, ins):
    """ModelAverage accumulator update (reference average_accumulates_op.h):
    maintain staged parameter sums (sum_1 fine-grained, sum_2 coarse, sum_3
    snapshot) and window counters; when the window outgrows
    min(max_average_window, num_updates * average_window) the old sums roll
    into sum_3. The C++ if/else becomes jnp.where — same math, one fused
    XLA computation per step."""
    param = jnp.asarray(ins["param"][0])
    s1 = jnp.asarray(ins["in_sum_1"][0])
    s2 = jnp.asarray(ins["in_sum_2"][0])
    s3 = jnp.asarray(ins["in_sum_3"][0])
    num_acc = jnp.asarray(ins["in_num_accumulates"][0]).reshape(()).astype(jnp.int32)
    old_num_acc = jnp.asarray(ins["in_old_num_accumulates"][0]).reshape(()).astype(jnp.int32)
    num_upd = jnp.asarray(ins["in_num_updates"][0]).reshape(()).astype(jnp.int32)

    avg_win = op_.attr("average_window", 0.0)
    max_win = op_.attr("max_average_window", 2 ** 31 - 1)
    min_win = min(op_.attr("min_average_window", 10000), max_win)

    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + param

    spill = (num_upd % _K_MAX_ACC) == 0
    s2 = jnp.where(spill, s2 + s1, s2)
    s1 = jnp.where(spill, jnp.zeros_like(s1), s1)

    window_full = (num_acc >= min_win) & \
        (num_acc >= jnp.minimum(
            jnp.asarray(max_win, jnp.float32),
            num_upd.astype(jnp.float32) * avg_win).astype(jnp.int32))
    s3 = jnp.where(window_full, s1 + s2, s3)
    s1 = jnp.where(window_full, jnp.zeros_like(s1), s1)
    s2 = jnp.where(window_full, jnp.zeros_like(s2), s2)
    old_num_acc = jnp.where(window_full, num_acc, old_num_acc)
    num_acc = jnp.where(window_full, 0, num_acc)

    return {"out_sum_1": [s1], "out_sum_2": [s2], "out_sum_3": [s3],
            "out_num_accumulates": [num_acc.reshape(1)],
            "out_old_num_accumulates": [old_num_acc.reshape(1)],
            "out_num_updates": [num_upd.reshape(1)]}


# --- save / load as ops ---------------------------------------------------------

def _save_payload(path, overwrite, payload):
    import os
    if not overwrite and os.path.exists(path):
        raise IOError(f"save op: '{path}' exists and overwrite is False")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "wb") as f:
        pickle.dump(payload, f)


@op("save", grad=NO_GRAD)
def _save(ctx, op_, ins):
    """Persist one variable to file_path (reference save_op.cc). Runs as an
    ordered host callback inside the trace; the on-disk format matches
    io._save_one so load_vars/load ops interoperate."""
    from jax.experimental import io_callback
    x = jnp.asarray(ins["X"][0])
    path = op_.attr("file_path")
    overwrite = op_.attr("overwrite", True)

    def cb(val):
        _save_payload(path, overwrite,
                      {"tensor": np.asarray(val), "lod": None, "version": 0})
        return np.zeros((), np.int32)

    io_callback(cb, jax.ShapeDtypeStruct((), np.int32), x, ordered=True)
    return {}


@op("save_combine", grad=NO_GRAD)
def _save_combine(ctx, op_, ins):
    """Persist several variables into one file (reference
    save_combine_op.cc); format matches io.save_vars(filename=...)."""
    from jax.experimental import io_callback
    names = op_.desc.inputs["X"]
    vals = [jnp.asarray(v) for v in ins["X"]]
    path = op_.attr("file_path")
    overwrite = op_.attr("overwrite", True)

    def cb(*arrs):
        _save_payload(path, overwrite,
                      {n: (np.asarray(a), None) for n, a in zip(names, arrs)})
        return np.zeros((), np.int32)

    io_callback(cb, jax.ShapeDtypeStruct((), np.int32), *vals, ordered=True)
    return {}


def _out_shape_dtype(op_, slot, idx=0):
    block = getattr(op_, "block", None)
    name = op_.desc.outputs[slot][idx]
    b = block
    while b is not None:
        if b.desc.has_var(name):
            v = b.desc.var(name)
            if v.shape is not None and all(
                    s is not None and s >= 0 for s in v.shape):
                return tuple(v.shape), to_np_dtype(v.dtype or "float32")
        b = b.parent_block
    return None, None


@op("load", grad=NO_GRAD)
def _load(ctx, op_, ins):
    """Load a variable saved by the save op (reference load_op.cc). The
    output shape/dtype must be statically declared on the var desc (true
    for persistables) because XLA needs the callback's result shape."""
    path = op_.attr("file_path")
    shape, dtype = _out_shape_dtype(op_, "Out")
    assert shape is not None, (
        "load op: output var needs a static shape/dtype declaration")

    def cb():
        with open(path, "rb") as f:
            d = pickle.load(f)
        return np.asarray(d["tensor"], dtype=dtype).reshape(shape)

    out = jax.pure_callback(cb, jax.ShapeDtypeStruct(shape, dtype))
    return {"Out": [out]}


@op("load_combine", grad=NO_GRAD)
def _load_combine(ctx, op_, ins):
    path = op_.attr("file_path")
    names = op_.desc.outputs["Out"]
    specs = []
    for i, name in enumerate(names):
        shape, dtype = _out_shape_dtype(op_, "Out", i)
        assert shape is not None, (
            f"load_combine: var '{name}' needs a static shape/dtype")
        specs.append(jax.ShapeDtypeStruct(shape, dtype))

    def cb():
        # one read + unpickle for all outputs (reference load_combine_op.cc
        # reads the stream once)
        with open(path, "rb") as f:
            d = pickle.load(f)
        return tuple(
            np.asarray(d[name][0], dtype=spec.dtype).reshape(spec.shape)
            for name, spec in zip(names, specs))

    outs = jax.pure_callback(cb, tuple(specs))
    return {"Out": list(outs)}


# --- LoD-array ops --------------------------------------------------------------

def _table_lengths(ctx, op_, ins, slot="RankTable"):
    names = op_.desc.inputs.get(slot, [])
    lens = ctx.seq_len(names[0]) if names else None
    if lens is None and names and ins.get(slot) and ins[slot][0] is not None:
        v = jnp.asarray(ins[slot][0])
        if v.ndim == 1:   # the rank-table op outputs the lengths vector
            lens = v
    return None if lens is None else jnp.asarray(lens).astype(jnp.int32)


@op("lod_tensor_to_array", grad=None, non_diff_inputs=("RankTable",))
def _lod_tensor_to_array(ctx, op_, ins):
    """Split a padded sequence batch into a time-major TensorArray
    (reference lod_tensor_to_array_op.cc). The reference shrinks each
    timestep's batch to live sequences via the rank table; the dense
    lowering keeps the full batch per step (masking supplies the same
    semantics downstream), so array[t] = X[:, t]."""
    x = jnp.asarray(ins["X"][0])
    t = x.shape[1]
    buf = jnp.swapaxes(x, 0, 1)
    lens = _table_lengths(ctx, op_, ins)
    out_name = op_.desc.outputs["Out"][0]
    ctx.set_seq_len(out_name, lens)
    return {"Out": [TensorArrayVal(buf, jnp.asarray(t, jnp.int32))]}


@op("array_to_lod_tensor", grad=None, non_diff_inputs=("RankTable",))
def _array_to_lod_tensor(ctx, op_, ins):
    """Inverse of lod_tensor_to_array (reference array_to_lod_tensor_op.cc):
    stack the array back into [batch, T, ...] and restore the lengths."""
    arr = ins["X"][0]
    assert isinstance(arr, TensorArrayVal), "array_to_lod_tensor needs array"
    x = jnp.swapaxes(arr.buffer, 0, 1)
    lens = _table_lengths(ctx, op_, ins)
    if lens is None:
        lens = ctx.seq_len(op_.desc.inputs["X"][0])
    ctx.set_seq_len(op_.desc.outputs["Out"][0], lens)
    return {"Out": [x]}


@op("split_lod_tensor", non_diff_inputs=("Mask",))
def _split_lod_tensor(ctx, op_, ins):
    """Route rows by boolean mask (reference split_lod_tensor_op.cc, used by
    IfElse). The reference compacts selected rows; the dense lowering keeps
    row positions and zeroes the complement, which merge_lod_tensor inverts
    exactly."""
    x = jnp.asarray(ins["X"][0])
    mask = jnp.asarray(ins["Mask"][0]).reshape(-1).astype(bool)
    m = mask.reshape((mask.shape[0],) + (1,) * (x.ndim - 1))
    zero = jnp.zeros_like(x)
    return {"OutTrue": [jnp.where(m, x, zero)],
            "OutFalse": [jnp.where(m, zero, x)]}


@op("merge_lod_tensor", non_diff_inputs=("Mask",))
def _merge_lod_tensor(ctx, op_, ins):
    x_true = jnp.asarray(ins["InTrue"][0])
    x_false = jnp.asarray(ins["InFalse"][0])
    mask = jnp.asarray(ins["Mask"][0]).reshape(-1).astype(bool)
    m = mask.reshape((mask.shape[0],) + (1,) * (x_true.ndim - 1))
    return {"Out": [jnp.where(m, x_true, x_false)]}


@op("reorder_lod_tensor_by_rank", grad=None,
    non_diff_inputs=("RankTable",))
def _reorder_lod_tensor_by_rank(ctx, op_, ins):
    """Reorder sequences into rank-table order — descending length, stable
    (reference reorder_lod_tensor_by_rank_op.cc)."""
    x = jnp.asarray(ins["X"][0])
    lens = _table_lengths(ctx, op_, ins)
    if lens is None:
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    order = jnp.argsort(-lens, stable=True)
    out = jnp.take(x, order, axis=0)
    ctx.set_seq_len(op_.desc.outputs["Out"][0], jnp.take(lens, order))
    return {"Out": [out]}
