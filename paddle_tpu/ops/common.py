"""Shared helpers for op lowerings and shape inference."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SelectedRowsVal:
    """Sparse-rows gradient value: the TPU-native SelectedRows
    (reference: framework/selected_rows.h:19). `rows` may repeat (like the
    reference's unmerged SelectedRows); consumers either scatter-add
    (sparse optimizer update touching only K rows of the table) or
    densify. Static `height` is the dense row count of the full table."""
    rows: Any          # int32 [K]
    values: Any        # [K, D...]
    height: int

    def to_dense(self):
        dense = jnp.zeros((self.height,) + tuple(self.values.shape[1:]),
                          self.values.dtype)
        return dense.at[self.rows].add(self.values)


jax.tree_util.register_pytree_node(
    SelectedRowsVal,
    lambda v: ((v.rows, v.values), v.height),
    lambda h, ch: SelectedRowsVal(ch[0], ch[1], h))


def maybe_dense(v, count_as: Optional[str] = None):
    """Densify a SelectedRowsVal (identity otherwise). Pass `count_as`
    (a site label like "fetch") to record the densification in
    sparse_densify_fallback_total — silent call sites are the perf
    cliffs ISSUE 10's counters exist to surface."""
    if isinstance(v, SelectedRowsVal):
        if count_as is not None:
            from . import sparse_ops
            sparse_ops.count_densify(count_as, "densified_at_" + count_as)
        return v.to_dense()
    from .sibling_products import OpenProducts
    if isinstance(v, OpenProducts):
        return v.close()
    return v


def merge_selected_rows(sr: "SelectedRowsVal"):
    """Merge duplicate rows by summation (reference
    operators/math/selected_rows_functor.cc MergeAdd), keeping shapes
    static: returns (rows [K], values [K, D...]) where duplicates are
    summed into their first slot and freed slots carry row index =
    height (out of range, so scatters drop them and gathers clamp
    harmlessly). Cost O(K log K + K*D) — never materializes the dense
    table, which is the point of the sparse optimizer path."""
    rows = jnp.asarray(sr.rows)
    vals = jnp.asarray(sr.values)
    k = rows.shape[0]
    order = jnp.argsort(rows)
    r_s = rows[order]
    v_s = vals[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), r_s[1:] != r_s[:-1]])
    seg = jnp.cumsum(is_new) - 1                       # [K] in [0, K)
    merged_vals = jax.ops.segment_sum(v_s, seg, num_segments=k)
    merged_rows = jnp.full((k,), sr.height, rows.dtype).at[seg].set(r_s)
    return merged_rows, merged_vals


def to_np_dtype(name: str):
    if name == "bfloat16":
        return jnp.bfloat16
    return np.dtype(name)


def mxu_cast(ctx, *xs):
    """Mixed-precision policy hook for MXU-bound ops (matmul/conv).

    Under AMP (program._amp_dtype, see paddle_tpu/amp.py) float32 operands
    are cast to the compute dtype (bfloat16 → the MXU's native input type);
    the call site casts the op result back via the returned restore dtype,
    so everything downstream (BN statistics, losses, optimizer updates on
    fp32 master weights) stays float32. On TPU the MXU accumulates bf16
    products in fp32 internally, but the op's *stored* output is bf16 and
    is then upcast — each output element is rounded to bf16 once (the same
    rounding the operands already took; `preferred_element_type=f32` is NOT
    used because this jax version's conv transpose rule rejects mixed
    bf16-operand/f32-cotangent convs). The generic vjp-backed grad ops
    re-trace this lowering, so backward matmuls/convs run bf16 too (the
    astype vjp casts cotangents bf16-ward on entry and back to fp32 toward
    the weights).

    TPU-native replacement for the reference's fp16 story
    (reference: paddle/fluid/platform/float16.h:64) — on TPU the low-precision
    type is bf16 and no loss scaling is needed (bf16 keeps f32's exponent).

    Returns (cast_operands_tuple, restore_dtype_or_None); call sites do
    `out = out.astype(restore) if restore is not None else out`.

    Under level O2 the restore dtype is None even after casting: activations
    stay bf16 end-to-end (halving HBM traffic — the dominant cost on
    bandwidth-bound chips); norm/loss lowerings locally upcast where
    statistics need f32. O3 is O2 on this axis (bf16 activations; the
    quantized routing happens downstream of this cast in the matmul/conv
    lowerings), so gating quantization off restores O2 numerics exactly.
    """
    amp = getattr(ctx, "amp_dtype", None)
    if not amp:
        return xs, None
    cd = jnp.dtype(amp)
    casted = tuple(x.astype(cd) if x.dtype == jnp.float32 else x for x in xs)
    if getattr(ctx, "amp_level", "O1") in ("O2", "O3"):
        return casted, None
    any_cast = any(c is not x for c, x in zip(casted, xs))
    return casted, (jnp.float32 if any_cast else None)


def broadcast_y_to_x(x, y, axis: int):
    """Paddle elementwise broadcast: align y's dims to x starting at `axis`
    (reference: operators/elementwise_op_function.h). axis==-1 means align to
    the trailing dims."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if y.ndim == 0 or x.shape == y.shape:
        return y
    # Paddle allows a trailing run of size-1 dims in y beyond the aligned
    # region (e.g. x:(N,C), y:(N,1) with axis=0); squeeze them so the
    # alignment fits.
    if axis == -1:
        axis = x.ndim - y.ndim
        while axis < 0 and y.shape[-1] == 1:
            y = y.reshape(y.shape[:-1])
            axis += 1
    else:
        while axis + y.ndim > x.ndim and y.shape[-1] == 1:
            y = y.reshape(y.shape[:-1])
    assert axis >= 0 and axis + y.ndim <= x.ndim, (
        f"cannot broadcast y{tuple(y.shape)} to x{tuple(x.shape)} at axis {axis}")
    new_shape = (1,) * axis + tuple(y.shape) + (1,) * (x.ndim - axis - y.ndim)
    return y.reshape(new_shape)


def seq_lengths(ctx, op_, slot, batch, cap):
    """Valid per-sequence lengths for a padded input slot: the @SEQLEN side
    channel when the var is a LoD feed, else the full padded extent."""
    names = op_.desc.inputs.get(slot, [])
    lens = ctx.seq_len(names[0]) if names else None
    if lens is None:
        return jnp.full((batch,), cap, dtype=jnp.int32)
    return jnp.asarray(lens).astype(jnp.int32)


# --- shape inference helpers ------------------------------------------------

def out_var(op, block, slot="Out", idx=0):
    names = op.desc.outputs.get(slot, [])
    if idx >= len(names):
        return None
    name = names[idx]
    return block.desc.vars.get(name) or _find_up(block, name)


def in_var(op, block, slot="X", idx=0):
    names = op.desc.inputs.get(slot, [])
    if idx >= len(names):
        return None
    return _find_up(block, names[idx])


def _find_up(block, name):
    b = block
    while b is not None:
        if b.desc.has_var(name):
            return b.desc.var(name)
        b = b.parent_block
    return None


def set_out(op, block, slot, shape, dtype):
    v = out_var(op, block, slot)
    if v is not None:
        v.shape = list(shape) if shape is not None else None
        if dtype is not None:
            v.dtype = dtype


def same_as_input(in_slot="X", out_slot="Out"):
    def infer(op, block):
        iv = in_var(op, block, in_slot)
        if iv is not None:
            set_out(op, block, out_slot, iv.shape, iv.dtype)
    return infer


def elementwise_infer(op, block):
    xv = in_var(op, block, "X")
    if xv is not None:
        set_out(op, block, "Out", xv.shape, xv.dtype)


def matmul_shape(xs: Optional[List[int]], ys: Optional[List[int]],
                 tx: bool, ty: bool) -> Optional[List[int]]:
    if xs is None or ys is None:
        return None
    xs, ys = list(xs), list(ys)
    if len(xs) == 1:
        xs = [1, xs[0]]
    if len(ys) == 1:
        ys = [ys[0], 1]
    if tx:
        xs[-2], xs[-1] = xs[-1], xs[-2]
    if ty:
        ys[-2], ys[-1] = ys[-1], ys[-2]
    batch = xs[:-2] or ys[:-2]
    return batch + [xs[-2], ys[-1]]
