"""Parallelism: mesh-based SPMD replacing the reference's parameter-server /
NCCL / parallel_do stack (SURVEY.md §2.5). See `mesh.py` and `transpiler.py`."""

from . import mesh
from .mesh import get_mesh, set_mesh, data_parallel_mesh
from . import transpiler
from . import multihost
from . import master
from . import tensor_parallel
from .tensor_parallel import (shard_parameter, shard_fc_params,
                              shard_all_params_zero)
from . import ring_attention
from . import planner
from .planner import SpecLayout, mesh_from_env, validate_plan_bytes
from . import embedding
from .embedding import (shard_table, shard_embeddings,
                        per_shard_table_bytes)
from . import emb_cache
from . import pipeline
from .pipeline import gpipe
from . import program_pipeline
from .program_pipeline import PipelineTranspiler
from .ring_attention import ring_attention_sharded


def shard_feed(program, name, spec):
    """Override a feed variable's mesh sharding (dims -> axis name or
    None), e.g. shard_feed(prog, "tokens", (None, "sp")) to split the
    sequence axis for ring attention."""
    if not hasattr(program, "_feed_shardings"):
        program._feed_shardings = {}
    program._feed_shardings[name] = tuple(spec)
    return program


def per_shard_param_bytes(program, scope=None):
    """Per-device parameter bytes under the program's mesh: a parameter
    annotated in `_param_shardings` (shard_parameter/shard_fc_params/
    shard_all_params_zero) occupies size/prod(sharded axis sizes) HBM per
    device under GSPMD; everything else is fully replicated. Complements
    `Executor.static_memory_analysis`, whose memory_analysis() of an SPMD
    program is already per-shard (XLA partitions the module before buffer
    assignment) — this splits the same number into replicated-vs-sharded
    so a reader can see WHY the footprint scales.
    Returns {devices, replicated_bytes, sharded_bytes_per_device,
    per_device_bytes, by_axes, params}. `by_axes` partitions the
    per-device bytes by the axis-name set each param shards over —
    "replicated", "fsdp", "fsdp+tp", ... — the breakdown the planner's
    byte validation reports."""
    from .. import executor as executor_mod
    from .. import memory as memory_mod

    scope = scope if scope is not None else executor_mod.global_scope()
    m = getattr(program, "_mesh", None)
    axis_sizes = dict(m.shape) if m is not None else {}
    n_dev = 1
    for s in axis_sizes.values():
        n_dev *= int(s)
    specs = getattr(program, "_param_shardings", {}) or {}
    replicated = sharded = 0
    detail = {}
    by_axes = {}
    for p in program.global_block().all_parameters():
        v = scope.find_var(p.name)
        b = memory_mod.nbytes_of(v)
        if not b:
            continue
        factor = 1
        spec_axes = set()
        for ent in specs.get(p.name) or ():
            # dim entries may be one axis ("fsdp") or an axis tuple
            # (("fsdp", "tp") — embedding.SpecLayout row sharding)
            axes = (tuple(ent) if isinstance(ent, (tuple, list))
                    else (ent,) if ent else ())
            for ax in axes:
                factor *= int(axis_sizes.get(ax, 1))
                spec_axes.add(str(ax))
        if factor > 1:
            per_dev = -(-b // factor)   # ceil: XLA pads uneven shards
            sharded += per_dev
            key = "+".join(sorted(spec_axes))
            detail[p.name] = {"bytes": b, "per_device": per_dev,
                              "factor": factor, "axes": key}
        else:
            per_dev = b
            replicated += b
            key = "replicated"
            detail[p.name] = {"bytes": b, "per_device": b, "factor": 1,
                              "axes": key}
        by_axes[key] = int(by_axes.get(key, 0) + per_dev)
    return {"devices": n_dev, "replicated_bytes": int(replicated),
            "sharded_bytes_per_device": int(sharded),
            "per_device_bytes": int(replicated + sharded),
            "by_axes": by_axes, "params": detail}
