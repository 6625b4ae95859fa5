"""Tensor (model) parallelism via GSPMD parameter sharding.

TPU-native successor of the reference's coarse model parallelism
(reference: gserver/gradientmachines/ParallelNeuralNetwork.h — whole layers
pinned to devices; ModelConfig per-layer `device` attr). Instead of moving
layers, parameters carry `jax.sharding.PartitionSpec` annotations: the
executor passes them as in_shardings and XLA GSPMD partitions every matmul
touching them, inserting the all-gather/reduce-scatter collectives over ICI
(the Megatron column/row-parallel pattern falls out of annotating the fc
weight's output or input dimension).

API:
    mesh = make_mesh((dp, tp), ("dp", "mp"))
    DistributeTranspiler().transpile(trainers=..., mesh=mesh)
    shard_parameter(program, "fc_0.w_0", (None, "mp"))   # column-parallel
    shard_parameter(program, "fc_1.w_0", ("mp", None))   # row-parallel
    # or the sweep helper:
    shard_fc_params(program, axis="mp")
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

__all__ = ["shard_parameter", "param_shardings", "shard_fc_params",
           "shard_all_params_zero", "expected_collectives", "gather_once",
           "GATHER_ONCE_SIDES"]

# What a product under a planned model axis may have constrained, so that
# the value crosses the axis once (`gather_once`). A test empties this to
# trace the step as if the rule were not there.
GATHER_ONCE_SIDES = frozenset({"operand", "cotangent"})


def _specs(program) -> Dict[str, Tuple]:
    if not hasattr(program, "_param_shardings"):
        program._param_shardings = {}
    return program._param_shardings


def shard_parameter(program, param_name: str, spec: Sequence[Optional[str]]):
    """Annotate one parameter with a PartitionSpec (dims -> mesh axis or
    None). The executor turns this into an in_sharding for the jitted
    train step; XLA propagates it through every consumer. This is THE
    spec write path — planner.plan and embedding.shard_table both route
    through here — so the _version bump that invalidates compiled-step
    and overlap-plan caches lives here and nowhere else."""
    specs = _specs(program)
    spec = tuple(spec)
    if specs.get(param_name) != spec:
        specs[param_name] = spec
        program._version = getattr(program, "_version", 0) + 1
    return program


def param_shardings(program) -> Dict[str, Tuple]:
    return dict(getattr(program, "_param_shardings", {}))


def shard_fc_params(program, axis: str = "mp", min_dim: int = 2):
    """Column-shard every 2-D fc/mul weight over `axis` (Megatron
    column-parallel): weight [in, out] splits on out, so each device holds
    a slice of output features and XLA all-gathers activations where
    needed. Biases of matching size shard too."""
    sharded_cols = set()
    for p in program.global_block().all_parameters():
        shape = p.shape
        if shape is not None and len(shape) == 2 and shape[1] >= min_dim:
            shard_parameter(program, p.name, (None, axis))
            sharded_cols.add(shape[1])
    # 1-D biases whose length matches a sharded output dim
    for p in program.global_block().all_parameters():
        shape = p.shape
        if shape is not None and len(shape) == 1 and shape[0] in sharded_cols:
            shard_parameter(program, p.name, (axis,))
    return program


def gather_once(program, weight: str, batch: int):
    """(sides, whole) for the product of an activation whose leading
    dimension is `batch` with the 2-D parameter `weight`: the gate of the
    one-gather-a-value rule, read from the program and its mesh alone.

    GSPMD's own propagation leaves the residual stream sharded along d
    over the model axis and gathers it once a CONSUMER: q, k, v and the
    up projection each gathered the same normed activation, each gradient
    product its operand again (gpt2-large.train-fsdp2-tp2: 8 gathers of a
    whole activation a layer where Megatron's algebra needs 4, PERF.md
    section 6, PR 52). So where the weight's spec carries an axis of
    `planner.model_axes()` that the mesh has at size > 1, `math_ops._mul`
    constrains to `whole(ndim)` (the leading dimension over the plan's
    batch axes, no axis on any other)

    - "operand": the activation as the product reads it, after
      `mxu_cast`, where the axis is on the weight's output dimension
      (column-parallel): the gather moves the product's dtype, and
      siblings and the gradient ops' re-trace constrain the same value,
      which XLA keeps as one;
    - "cotangent": the product's output cotangent where it enters the
      product's gradient, where the axis is on the contraction dimension
      (row-parallel): `dX = dY . W^T` and `dW = X^T . dY` read one dY.

    No mesh, no spec, a model axis of size 1, a batch its axes do not
    divide: `sides` is empty and the step is traced as it always was."""
    from jax.sharding import NamedSharding, PartitionSpec

    from . import planner
    from .overlap import _spec_axes

    spec = (getattr(program, "_param_shardings", None) or {}).get(weight)
    live = planner.live_model_axes(program)
    if not live or not spec or len(spec) != 2:
        return frozenset(), None
    sides = GATHER_ONCE_SIDES & {
        side for side, ent in zip(("cotangent", "operand"), spec)
        if live & set(_spec_axes((ent,)))}
    mesh, plan = program._mesh, getattr(program, "_sharding_plan", None)
    rows = (plan.layout if plan else planner.SpecLayout()).batch_spec(mesh)
    shards = 1
    for a in _spec_axes(rows):
        shards *= int(mesh.shape[a])
    if not sides or batch % shards:
        return frozenset(), None
    return sides, lambda ndim: NamedSharding(
        mesh, PartitionSpec(*rows, *(None,) * (ndim - 1)))


def expected_collectives(program) -> Dict[str, str]:
    """{param_name: predicted GSPMD collective pattern} for every annotated
    parameter — the Megatron algebra in words. Tensor-parallel collectives
    are partitioner-inserted, so no framework line carries a pd.coll
    scope for them; the fleet CLI prints these predictions next to the
    trace's "(gspmd)" rows so an unattributed all-gather still names its
    probable source parameter."""
    out: Dict[str, str] = {}
    for name, spec in param_shardings(program).items():
        spec = tuple(spec)
        ndim = len(spec)
        axes = [a for a in spec if a]
        if not axes:
            continue
        if ndim >= 2 and spec[-1]:
            out[name] = ("column-parallel ({0}): activation gathered once "
                         "for all readers of it, grad reduce-scatter; "
                         "siblings of one activation reduce their input "
                         "gradient once".format(spec[-1]))
        elif ndim >= 2 and spec[0]:
            out[name] = ("row-parallel ({0}): output all-reduce; "
                         "cotangent gathered once for both gradient "
                         "products".format(spec[0]))
        elif ndim == 1:
            out[name] = ("sharded bias ({0}): gathers with its layer"
                         .format(axes[0]))
        else:
            out[name] = ("zero-sharded ({0}): param all-gather on use, "
                         "grad reduce-scatter".format(axes[0]))
    return out


def shard_all_params_zero(program, axis: str = "dp", min_size: int = 1024):
    """ZeRO-ish parameter sharding: every parameter (above min_size
    elements) shards its leading dim over the data axis; XLA all-gathers on
    use and reduce-scatters gradients — the GSPMD stand-in for the
    reference pserver's block-sharded parameter storage
    (distribute_transpiler.py:92 split_dense_variable)."""
    import numpy as np
    for p in program.global_block().all_parameters():
        shape = p.shape
        if shape and all(d is not None for d in shape) and \
                int(np.prod(shape)) >= min_size:
            shard_parameter(program, p.name,
                            (axis,) + (None,) * (len(shape) - 1))
    return program
