"""Sibling products of one activation give its gradient as ONE contraction.

Where two or more column-parallel products read one activation (q, k and
v of an attention block; a gated FFN's gate and up), each gradient op
forms a partial `dX_s = dY_s . W_s^T` over its chip's share of the output
features, GSPMD all-reduces a partial sum where the dot ends, and the
program's `sum` ops add the REDUCED arrays: `AR(a) + AR(b) + AR(c)`,
three arrays of `[B, T, d]` over the model axis where `AR(a + b + c)` is
the same number with fewer roundings (gpt2-large.train-fsdp2-tp2: 36 ms
of a 357 ms step, PERF.md section 6, PR 48).

One rule, in three places that each do one thing:

- `members(program)` is the gate, and reads only what the program shows:
  gradient ops of SIBLING_OPS whose `X` is the same activation, whose
  weights have equal shapes, and whose planned spec
  (`program._param_shardings`) puts an axis of `planner.model_axes()`
  that the mesh has at size > 1 on the weight's output dimension. No
  mesh, no spec, a model axis of size 1, a product alone: nothing is a
  member and the step is traced as it always was.
- a member's gradient op (`math_ops._mul_grad`) hands on the unreduced
  pair `(dY_s, W_s)` as an `OpenProducts` value where it would have
  written `dX_s`; the program's `sum` folds such values (`fold`).
- the first other op that reads the value closes it (`OpenProducts.close`,
  from the one place where `Executor._exec_op` gathers an op's inputs,
  and `common.maybe_dense` at a fetch): one `dot_general` contracting
  (sibling, output feature) over the stacked pairs, so the partial sums
  are added in the product's float32 accumulator, rounded once, and
  reduced once. `sibling_products_merged_total{program, direction}`
  counts one a closed group of two or more.

The parameters, their names, specs, optimizer slots and gradients are
untouched: the merge is in how the step is traced.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, FrozenSet, Tuple

import jax
import jax.numpy as jnp

from .common import maybe_dense

__all__ = ["SIBLING_OPS", "OpenProducts", "members", "is_member",
           "open_pair", "fold"]

# forward op types whose gradient ops may hand on an open pair: X [.., d]
# times a 2-D weight Y [d, n]. A test empties this to trace the step as
# if the rule were not there.
SIBLING_OPS = frozenset({"mul"})


@dataclass(frozen=True)
class OpenProducts:
    """`sum_s dY_s . W_s^T (+ plus)`, not yet contracted: the gradient of
    one activation through sibling products, as far as the program has
    added it up. `pairs` are (dY [rows, n], W [d, n]) in the dtype the
    product runs in; `like` is the activation's shape and dtype; `plus`
    is what the program added that was no open product."""
    pairs: Tuple[Tuple[Any, Any], ...]
    like: Any                       # jax.ShapeDtypeStruct
    program: str                    # telemetry label, for the counter
    plus: Any = None

    # enough of an array for the executor's bookkeeping and the cost
    # observers, which read shapes and dtypes only
    @property
    def shape(self):
        return self.like.shape

    @property
    def dtype(self):
        return self.like.dtype

    @property
    def ndim(self):
        return len(self.like.shape)

    def close(self):
        """The array: one contraction a class of equal shapes (one, for
        siblings the gate admitted), under the gradient op's scopes so
        that its device time is booked where the separate products'
        was."""
        classes: Dict[Any, list] = {}
        for dy, w in self.pairs:
            classes.setdefault((dy.shape, dy.dtype, w.shape, w.dtype),
                               []).append((dy, w))
        total = self.plus
        with jax.named_scope("pd_role.backward"), \
                jax.named_scope("pd.mul_grad"):
            for group in classes.values():
                dx = jax.lax.dot_general(
                    jnp.stack([dy for dy, _ in group]),
                    jnp.stack([w for _, w in group]),
                    (((0, 2), (0, 2)), ((), ())))
                if len(group) > 1:
                    _count(self.program, "grad_input")
                dx = dx.astype(self.like.dtype).reshape(self.like.shape)
                total = dx if total is None else total + dx
        return total


def _count(program: str, direction: str):
    from .. import telemetry
    telemetry.counter(
        "sibling_products_merged_total",
        "groups of sibling products of one activation traced as one "
        "contraction, by direction (ops/sibling_products.py)",
        labels=("program", "direction")).labels(
        program=program, direction=direction).inc()


def fold(values):
    """The program's `sum` over values of which some are open: the pairs
    in the program's order, everything else added up in `plus`."""
    pairs, plus, first = [], None, None
    for v in values:
        if isinstance(v, OpenProducts):
            first = v if first is None else first
            pairs.extend(v.pairs)
            v = v.plus
            if v is None:
                continue
        v = jnp.asarray(maybe_dense(v))
        plus = v if plus is None else plus + v
    return OpenProducts(tuple(pairs), first.like, first.program, plus)


# --- the gate -----------------------------------------------------------

_MEMBERS: Dict[Tuple, Tuple[Any, FrozenSet[int]]] = {}


def members(program) -> FrozenSet[int]:
    """id() of every gradient op of the global block that the rule
    admits, cached per (program, version) like the fusion plan: a spec
    written later (`tensor_parallel.shard_parameter`) bumps the version."""
    key = (id(program), getattr(program, "_version", 0), SIBLING_OPS)
    hit = _MEMBERS.get(key)
    if hit is not None and hit[0] is program:
        return hit[1]
    if len(_MEMBERS) > 64:
        _MEMBERS.clear()
    found = _match(program)
    _MEMBERS[key] = (program, found)
    return found


def _match(program) -> FrozenSet[int]:
    from ..parallel import planner
    from ..parallel.overlap import _spec_axes

    specs = getattr(program, "_param_shardings", None)
    live = planner.live_model_axes(program)
    if not live or not specs or not SIBLING_OPS:
        return frozenset()
    block = program.global_block()
    by_activation: Dict[Any, list] = {}
    for op in block.ops:
        if not op.type.endswith("_grad") or \
                op.type[:-len("_grad")] not in SIBLING_OPS or \
                "X@GRAD" not in op.desc.outputs or \
                op.attr("y_num_col_dims", 1) != 1:
            continue
        (x,), (w,) = op.desc.input("X"), op.desc.input("Y")
        spec = specs.get(w)
        if not spec or len(spec) != 2 or not live & set(_spec_axes(spec[1:])):
            continue
        if not block.desc.has_var(x) or block.desc.var(x).persistable:
            continue        # an activation, not a second weight
        shape = tuple(block.desc.var(w).shape or ())
        by_activation.setdefault(
            (x, shape, op.attr("x_num_col_dims", 1)), []).append(op)
    return frozenset(id(op) for group in by_activation.values()
                     if len(group) > 1 for op in group)


def is_member(ctx, op_, ins) -> bool:
    """Whether this gradient op hands on an open pair in this trace: the
    gate admitted it, a cotangent arrives, the product is a plain one
    (no quantised route), and the values are traced (run eagerly there
    is no partitioner, and nothing to reduce once)."""
    if id(op_) not in members(ctx.program):
        return False
    dout = (ins.get("Out@GRAD") or [None])[0]
    return dout is not None and not getattr(ctx, "quant_mode", None) \
        and isinstance(dout, jax.core.Tracer)


def open_pair(ctx, x, yf, dout, dtype) -> OpenProducts:
    """One member's `dX = dOut . yf^T`, left open: `yf` is the weight as
    the product reads it ([d, n], cast as the forward cast it) and
    `dtype` the product's."""
    from .. import telemetry
    dy = jnp.asarray(dout).reshape(-1, yf.shape[1]).astype(dtype)
    return OpenProducts(((dy, yf),), jax.ShapeDtypeStruct(x.shape, x.dtype),
                        telemetry.program_label(ctx.program))
