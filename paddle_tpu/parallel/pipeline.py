"""Pipeline parallelism: an SPMD GPipe schedule over a 'pp' mesh axis.

The reference's closest ancestor is ParallelNeuralNetwork's layer-to-device
assignment (gserver/gradientmachines/ParallelNeuralNetwork.h) — whole
layers pinned to devices with activations shipped between them. The
TPU-native form is the collective-matmul-style SPMD pipeline: every device
runs the same stage function with ITS shard of the stacked stage
parameters, and activations hop one device per tick with `lax.ppermute`
while microbatches stream in (GPipe schedule, M microbatches over P
stages, M + P - 1 ticks, bubble fraction (P-1)/(M+P-1)).

Pure differentiable JAX: `jax.grad` through the pipeline matches the
sequential stage composition (tested on an 8-device host mesh). Stages
must share one structure (a homogeneous layer stack), which is the
standard GPipe setting."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ._collectives import coll_scope, tree_mark_varying as _pvary

__all__ = ["gpipe", "gpipe_reference"]


def gpipe_reference(stage_fn, stacked_params, x_microbatches):
    """Sequential oracle: apply stages 0..P-1 to every microbatch."""
    p = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def run_one(x):
        h = x
        for i in range(p):
            params_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            h = stage_fn(params_i, h)
        return h

    return jax.vmap(run_one)(x_microbatches)



def gpipe(stage_fn, stacked_params, x_microbatches, mesh, axis: str = "pp"):
    """Run `stage_fn(params_i, h) -> h` as a P-stage pipeline.

    stacked_params: pytree whose leaves stack the per-stage parameters on
    a leading axis of size P (sharded over `axis`, so each device holds
    only its stage's weights). x_microbatches: [M, B, ...] microbatches
    (replicated in; every device sees the stream but only stage 0 consumes
    it). Returns [M, B, ...] final-stage outputs (replicated out)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p_size = mesh.shape[axis]
    m = x_microbatches.shape[0]
    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, P()), out_specs=P())
    def run(params_local, xs):
        # params_local leaves keep a leading axis of size 1 (the shard)
        params = jax.tree_util.tree_map(lambda a: a[0], params_local)
        idx = lax.axis_index(axis)
        ticks = m + p_size - 1
        zero_h = jnp.zeros(xs.shape[1:], xs.dtype)

        def tick(carry, t):
            recv, outs = carry
            # stage 0 injects microbatch t (while available); later stages
            # consume what arrived from the left neighbour last tick
            mb = xs[jnp.minimum(t, m - 1)]
            inp = jnp.where(idx == 0, mb, recv)
            h = stage_fn(params, inp)
            # last stage commits its result for microbatch t - (P-1)
            out_slot = t - (p_size - 1)
            commit = (idx == p_size - 1) & (out_slot >= 0)
            outs = lax.cond(
                commit,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, h, jnp.maximum(out_slot, 0), axis=0),
                lambda o: o, outs)
            # ship h one hop right (device i -> i+1)
            perm = [(i, i + 1) for i in range(p_size - 1)]
            with coll_scope("pipe_send"):
                nxt = lax.ppermute(h, axis, perm)
            return (nxt, outs), None

        outs0 = _pvary(jnp.zeros((m,) + xs.shape[1:], xs.dtype), axis)
        recv0 = _pvary(zero_h, axis)
        (_, outs), _ = lax.scan(tick, (recv0, outs0),
                                jnp.arange(ticks))
        # only the last device holds real outputs; replicate via psum
        with coll_scope("pipe_replicate"):
            return lax.psum(
                jnp.where(idx == p_size - 1, outs, jnp.zeros_like(outs)),
                axis)

    return run(stacked_params, x_microbatches)
