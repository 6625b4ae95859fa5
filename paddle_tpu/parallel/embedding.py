"""Sharded sparse embedding tables: fsdp-partitioned rows (ISSUE 10).

The reference serves recommender-scale tables from parameter servers
(distributed lookup_table, reference: distribute_transpiler splitting
tables row-wise across pservers). The TPU-native translation is GSPMD:
annotate the table's row dim with mesh axes (SNIPPETS.md [2]
`SpecLayout.embeddings()` — replicated over data, sharded over fsdp×tp)
and let the partitioner turn `lookup_table`'s gather into local gathers
plus one cross-shard combine (`pd.coll.emb_lookup`). This module owns
the annotation side:

  * `SpecLayout` — role map from parameter roles to PartitionSpecs over
    named axes, the planner vocabulary the ROADMAP names.
  * `shard_table` / `shard_embeddings` — row-shard one table / every
    `lookup_table` W in a program; records `program._sharded_tables` so
    the executor, fusion, overlap, and memory layers can tell a sharded
    *table* (sparse path handles it) from a generically sharded param.
  * `resolve_state_spec` — optimizer accumulators (`<param>_<acc>_<n>`,
    optimizer.py naming) of a sharded table inherit the table's row
    sharding, so a 1M×64 adam table's moments shard with it instead of
    replicating.
  * `per_shard_table_bytes` / `state_shard_factor` — per-device HBM
    accounting for tables + their optimizer state (memory.py breakdown).

Shard-axis selection: `PADDLE_TPU_EMB_SHARD_AXIS` (default "fsdp") names
the mesh axis (comma-separated for multi-axis) used when a caller does
not pass one explicitly.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

# The role→spec vocabulary lives in planner.py since ISSUE 15 (one
# data × fsdp × tp vocabulary); re-exported here because PR 10 callers
# import it from this module.
from .planner import SpecLayout

__all__ = [
    "SpecLayout", "default_shard_axes", "shard_table", "shard_embeddings",
    "sharded_tables", "table_accumulators", "resolve_state_spec",
    "state_shard_factor", "per_shard_table_bytes",
]

Axes = Union[str, Sequence[str]]


def default_shard_axes() -> Tuple[str, ...]:
    """PADDLE_TPU_EMB_SHARD_AXIS (comma-separated), default ("fsdp",)."""
    raw = os.environ.get("PADDLE_TPU_EMB_SHARD_AXIS", "fsdp")
    return tuple(a.strip() for a in raw.split(",") if a.strip())


def shard_table(program, param_name: str, axis: Optional[Axes] = None):
    """Row-shard one embedding table over mesh axis/axes (default from
    PADDLE_TPU_EMB_SHARD_AXIS). Writes the same `_param_shardings`
    annotation tensor_parallel.shard_parameter uses — the executor's
    in_shardings and the sparse lookup/apply kernels read it — and marks
    the param in `program._sharded_tables` so fallback dashboards can
    label it "handled by sparse path" rather than "sharded param"."""
    from . import tensor_parallel as tp_mod

    axes = (tuple(axis) if isinstance(axis, (tuple, list))
            else (axis,) if axis else default_shard_axes())
    ndim = None
    blk = program.global_block()
    if blk.has_var(param_name):
        shp = blk.var(param_name).shape
        ndim = len(shp) if shp is not None else None
    first = axes[0] if len(axes) == 1 else tuple(axes)
    spec = (first,) + (None,) * ((ndim or 2) - 1)
    # one vocabulary: the spec write (and its _version bump) goes through
    # tensor_parallel.shard_parameter; only the sparse-path marker is ours
    tp_mod.shard_parameter(program, param_name, spec)
    tables = getattr(program, "_sharded_tables", None)
    if tables is None:
        tables = program._sharded_tables = {}
    tables[param_name] = axes
    return program


def sharded_tables(program) -> Dict[str, Tuple[str, ...]]:
    """{table param name -> row-shard axes} recorded by shard_table."""
    return dict(getattr(program, "_sharded_tables", None) or {})


def shard_embeddings(program, axis: Optional[Axes] = None,
                     mesh=None, layout: Optional[SpecLayout] = None
                     ) -> List[str]:
    """Row-shard every `lookup_table` W parameter in the program. With a
    `layout`, the spec comes from `layout.embeddings()` filtered to the
    mesh's axes; otherwise `axis`/PADDLE_TPU_EMB_SHARD_AXIS. Returns the
    table names annotated."""
    mesh = mesh if mesh is not None else getattr(program, "_mesh", None)
    if layout is not None and mesh is not None:
        ent = layout.filter_axes(layout.embeddings(), mesh)[0]
        axes = (tuple(ent) if isinstance(ent, (tuple, list))
                else (ent,) if ent else ())
        axis = axes or axis
    blk = program.global_block()
    done: List[str] = []
    for op_ in blk.ops:
        if op_.type != "lookup_table":
            continue
        wnames = op_.input("W")
        if not wnames:
            continue
        wname = wnames[0]
        if wname in done or not blk.has_var(wname):
            continue
        shard_table(program, wname, axis)
        done.append(wname)
    return done


def table_accumulators(program, pname: str) -> List[str]:
    """Optimizer accumulators shadowing table `pname`'s shape, by the
    optimizer.py naming convention (`unique_name.generate(f"{param}_
    {acc}")`) plus a shape-equality check that keeps scalar state like
    beta-pow vars (shape [1]) and unlucky name collisions out. Shared
    vocabulary for the sharding resolver below (a sharded table's
    moments shard with it) and for parallel/emb_cache.py (a cached
    table's moments cache — and flush — with it)."""
    blk = program.global_block()
    if not blk.has_var(pname):
        return []
    pshape = tuple(blk.var(pname).shape or ())
    if not pshape:
        return []
    out = []
    for vname in list(blk.vars):
        if vname == pname or not vname.startswith(pname + "_"):
            continue
        if not blk.has_var(vname):
            continue
        if tuple(blk.var(vname).shape or ()) == pshape:
            out.append(vname)
    return sorted(out)


# accumulator→param maps are O(vars × sharded params) to build, and the
# executor asks per state var per compile — cache per (program, version)
_ACCUM_CACHE: Dict[Tuple[int, int], Dict[str, str]] = {}


def _accum_of(program, name: str) -> Optional[str]:
    """Sharded param whose optimizer accumulator `name` is, or None
    (table_accumulators membership over every spec'd param — since the
    planner, ANY sharded parameter's accumulators follow it, not just
    `_sharded_tables` entries)."""
    sharded = set(getattr(program, "_sharded_tables", None) or ())
    sharded.update(getattr(program, "_param_shardings", None) or ())
    if not sharded:
        return None
    key = (id(program), getattr(program, "_version", 0))
    cached = _ACCUM_CACHE.get(key)
    if cached is None:
        if len(_ACCUM_CACHE) > 64:
            _ACCUM_CACHE.clear()
        cached = {}
        for pname in sorted(sharded):
            for aname in table_accumulators(program, pname):
                cached.setdefault(aname, pname)
        _ACCUM_CACHE[key] = cached
    return cached.get(name)


def resolve_state_spec(program, name: str):
    """PartitionSpec entry tuple for a persistable state var: the
    parameter's own `_param_shardings` annotation, or — for an optimizer
    accumulator shadowing a sharded table's shape — the table's row
    sharding. The executor's in_shardings/donated-state pinning and
    memory.py's per-shard accounting both route through here so moments
    and velocity live sharded next to their table."""
    specs = getattr(program, "_param_shardings", {}) or {}
    if name in specs:
        return specs[name]
    pname = _accum_of(program, name)
    return specs.get(pname) if pname else None


def state_shard_factor(program, name: str) -> int:
    """How many devices split state var `name` under the program's mesh
    (1 = replicated). Counts mesh axis sizes over every sharded dim of
    the resolved spec, handling tuple entries like ("fsdp", "tp")."""
    spec = resolve_state_spec(program, name)
    mesh = getattr(program, "_mesh", None)
    if not spec or mesh is None:
        return 1
    sizes = dict(mesh.shape)
    f = 1
    for ent in spec:
        axes = (tuple(ent) if isinstance(ent, (tuple, list))
                else (ent,) if ent else ())
        for a in axes:
            f *= int(sizes.get(a, 1))
    return f


def per_shard_table_bytes(program, scope=None) -> Dict:
    """Per-device HBM for each sharded table and its optimizer state:
    {tables: {name: {rows, dim, bytes, per_shard_bytes, opt_state_bytes,
    opt_state_per_shard_bytes, factor}}, total_bytes,
    total_per_shard_bytes} (acceptance: per-shard ≈ total/devices at 8
    devices). Bytes come from live scope vars when materialized, else
    from the block's static shapes."""
    from .. import executor as executor_mod
    from .. import memory as memory_mod
    import numpy as np

    scope = scope if scope is not None else executor_mod.global_scope()
    blk = program.global_block()
    out: Dict[str, Dict] = {}
    total = total_ps = 0

    def _nbytes(name: str) -> int:
        v = scope.find_var(name)
        b = memory_mod.nbytes_of(v)
        if b:
            return int(b)
        if blk.has_var(name):
            var = blk.var(name)
            shp = tuple(var.shape or ())
            if shp and all(int(s) > 0 for s in shp):
                itemsize = np.dtype(str(var.dtype)).itemsize \
                    if var.dtype else 4
                n = 1
                for s in shp:
                    n *= int(s)
                return n * itemsize
        return 0

    for pname in sharded_tables(program):
        if not blk.has_var(pname):
            continue
        shp = tuple(blk.var(pname).shape or ())
        factor = state_shard_factor(program, pname)
        b = _nbytes(pname)
        opt_b = opt_ps = 0
        for vname in list(blk.vars):
            if vname != pname and _accum_of(program, vname) == pname:
                ab = _nbytes(vname)
                opt_b += ab
                opt_ps += -(-ab // state_shard_factor(program, vname))
        per_shard = -(-b // factor) if factor > 1 else b
        out[pname] = {
            "rows": int(shp[0]) if shp else 0,
            "dim": int(shp[1]) if len(shp) > 1 else 0,
            "bytes": int(b), "per_shard_bytes": int(per_shard),
            "opt_state_bytes": int(opt_b),
            "opt_state_per_shard_bytes": int(opt_ps),
            "factor": int(factor),
        }
        total += b + opt_b
        total_ps += per_shard + opt_ps
    return {"tables": out, "total_bytes": int(total),
            "total_per_shard_bytes": int(total_ps)}
