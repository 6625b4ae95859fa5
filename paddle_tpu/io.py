"""Checkpoint + inference-model save/load
(reference: python/paddle/fluid/io.py:66 save_vars, :145 save_persistables,
:234 load_persistables, :298 save_inference_model, :383 load_inference_model;
serialization of each tensor mirrors save_op.cc/load_op.cc but uses .npy —
the on-disk format is ours to define for the TPU framework).

Model directory layout matches the reference: one file per variable named by
the variable, plus `__model__` holding the serialized program."""

from __future__ import annotations

import os
import pickle
import time
from typing import List, Optional, Sequence

import numpy as np

from .executor import Executor, LoDTensor, Scope, global_scope
from .framework.framework import (Parameter, Program, Variable,
                                  default_main_program, default_startup_program)

__all__ = [
    "save_vars", "save_params", "save_persistables", "load_vars",
    "load_params", "load_persistables", "save_inference_model",
    "load_inference_model", "get_inference_program",
]


def _is_persistable(var: Variable) -> bool:
    return var.persistable


def _is_parameter(var: Variable) -> bool:
    return isinstance(var, Parameter)


def save_vars(executor: Executor, dirname: str, main_program: Optional[Program]
              = None, vars: Optional[Sequence[Variable]] = None,
              predicate=None, save_file_name: Optional[str] = None):
    """Write scope values of selected vars to `dirname` (reference io.py:66).
    The executor argument is kept for API parity; values come from the
    global scope."""
    main_program = main_program or default_main_program()
    t0 = time.perf_counter()
    if vars is None:
        vars = [v for v in main_program.list_vars()
                if predicate is None or predicate(v)]
    os.makedirs(dirname, exist_ok=True)
    scope = global_scope()
    # beyond-HBM cached tables: the scope holds only the [cache_rows, dim]
    # hot-row slab — flush dirty slots to the host-DRAM authoritative
    # store FIRST (crash-consistency barrier: once flushed, the host slab
    # is complete even if the process dies mid-save), then checkpoint the
    # full host table in the slab's place.
    emb_cache = getattr(main_program, "_emb_cache", None)
    if emb_cache is not None:
        emb_cache.flush()
    combine = {}
    total_bytes = n_saved = 0
    for v in vars:
        val = scope.find_var(v.name)
        if emb_cache is not None:
            host = emb_cache.host_value(v.name)
            if host is not None:
                val = host
        if val is None:
            continue
        lod = None
        if isinstance(val, LoDTensor):
            lod, val = val.lod, val.array()
        arr = np.asarray(val)
        total_bytes += arr.nbytes
        n_saved += 1
        if save_file_name is None:
            _save_one(os.path.join(dirname, v.name), arr, lod)
        else:
            combine[v.name] = (arr, lod)
    if save_file_name is not None:
        with open(os.path.join(dirname, save_file_name), "wb") as f:
            pickle.dump({k: (np.asarray(a), l) for k, (a, l)
                         in combine.items()}, f)
    _record_checkpoint("save", dirname, total_bytes, n_saved,
                       time.perf_counter() - t0)


def _record_checkpoint(op: str, dirname: str, nbytes: int, n_vars: int,
                       seconds: Optional[float] = None):
    """Checkpoint size telemetry: one gauge series per direction plus a
    step-event record, so telemetry logs show how much state each
    save/load moved (ISSUE: memory observability covers disk-bound state
    too, not just HBM). `seconds` is the wall duration of the transfer —
    the goodput ledger prices checkpoint badput from it when the run
    checkpoints through io.py directly rather than multihost."""
    try:
        from . import telemetry
        telemetry.gauge(
            "checkpoint_bytes",
            "tensor payload bytes of the last save_vars/load_vars",
            labels=("op",)).labels(op=op).set(nbytes)
        fields = {"dirname": dirname, "bytes": nbytes, "vars": n_vars}
        if seconds is not None:
            fields["seconds"] = seconds
        telemetry.log_event(f"checkpoint_{op}", **fields)
        from . import tracing
        if tracing.enabled() and seconds is not None:
            t_end = time.monotonic()
            tracing.record_span(
                f"checkpoint_{op}", t_end - seconds, t_end,
                attrs={"dirname": dirname, "bytes": nbytes,
                       "vars": n_vars})
    except Exception:
        pass


def _save_one(path: str, arr: np.ndarray, lod):
    with open(path, "wb") as f:
        pickle.dump({"tensor": arr, "lod": lod, "version": 0}, f)


def _load_one(path: str):
    with open(path, "rb") as f:
        d = pickle.load(f)
    return d["tensor"], d.get("lod")


def save_params(executor, dirname, main_program=None, filename=None):
    save_vars(executor, dirname, main_program, predicate=_is_parameter,
              save_file_name=filename)


def save_persistables(executor, dirname, main_program=None, filename=None):
    save_vars(executor, dirname, main_program, predicate=_is_persistable,
              save_file_name=filename)


def load_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              load_file_name: Optional[str] = None):
    main_program = main_program or default_main_program()
    t0 = time.perf_counter()
    if vars is None:
        vars = [v for v in main_program.list_vars()
                if predicate is None or predicate(v)]
    scope = global_scope()
    total_bytes = n_loaded = 0
    # cached tables restore into the host-DRAM authoritative slab (the
    # checkpoint holds the FULL table) and invalidate residency — the
    # scope keeps the cache slab, whose slots re-stage on first touch
    emb_cache = getattr(main_program, "_emb_cache", None)

    def _restore(name, arr, lod):
        if emb_cache is not None and emb_cache.load_host(
                name, np.asarray(arr)):
            return
        scope.set_var(name, LoDTensor(arr, lod) if lod else arr)

    if load_file_name is not None:
        with open(os.path.join(dirname, load_file_name), "rb") as f:
            blob = pickle.load(f)
        for v in vars:
            if v.name in blob:
                arr, lod = blob[v.name]
                total_bytes += np.asarray(arr).nbytes
                n_loaded += 1
                _restore(v.name, arr, lod)
        _record_checkpoint("load", dirname, total_bytes, n_loaded,
                           time.perf_counter() - t0)
        return
    for v in vars:
        path = os.path.join(dirname, v.name)
        if not os.path.exists(path):
            continue
        arr, lod = _load_one(path)
        total_bytes += np.asarray(arr).nbytes
        n_loaded += 1
        _restore(v.name, arr, lod)
    _record_checkpoint("load", dirname, total_bytes, n_loaded,
                       time.perf_counter() - t0)


def load_params(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, predicate=_is_parameter,
              load_file_name=filename)


def load_persistables(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, predicate=_is_persistable,
              load_file_name=filename)


def _strip_training_ops(program):
    """Drop backward/optimize-role ops before inference pruning (reference
    inference_optimize + OpRole attr, prune.cc:187): without this, a fetch
    var built AFTER minimize() (e.g. a crf_decoding path) sees the
    optimizer's in-place ParamOut as the parameter's producer and the
    reverse prune drags the whole training tail into the inference slice."""
    p = program.clone()
    for b in p.blocks:
        b.desc.ops = [d for d in b.desc.ops
                      if d.attrs.get("op_role") not in ("backward",
                                                        "optimize")]
        b._sync_ops()
    return p


def get_inference_program(target_vars, main_program=None):
    main_program = main_program or default_main_program()
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    forward = _strip_training_ops(main_program)
    pruned = forward.prune([], [t.name for t in target_vars])
    out = pruned.clone(for_test=True)
    emb_cache = getattr(main_program, "_emb_cache", None)
    if emb_cache is not None:     # shares the source scope's cache slabs
        out._emb_cache = emb_cache
    return out


def save_inference_model(dirname: str, feeded_var_names: List[str],
                         target_vars: List[Variable], executor: Executor,
                         main_program: Optional[Program] = None,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None):
    """Prune to the inference slice and persist program + params
    (reference io.py:298)."""
    main_program = main_program or default_main_program()
    if isinstance(feeded_var_names, str):
        feeded_var_names = [feeded_var_names]
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    os.makedirs(dirname, exist_ok=True)
    pruned = _strip_training_ops(main_program).prune(
        feeded_var_names, [t.name for t in target_vars])
    inference_program = pruned.clone(for_test=True)
    # a hot-row emb cache lives on the PROGRAM but its slabs live in the
    # SCOPE, which the pruned clone shares — without propagating it, the
    # save below would checkpoint the [cache_rows, dim] device slab as if
    # it were the full table (and running the clone would feed global ids
    # into slot-indexed lookups)
    emb_cache = getattr(main_program, "_emb_cache", None)
    if emb_cache is not None:
        inference_program._emb_cache = emb_cache
    # feeds the targets do not depend on were pruned away; drop them from
    # the recorded feed list so inference callers need not supply them
    # (e.g. the label input of a training program)
    from .framework.framework import op_external_reads
    block = inference_program.global_block()
    live = set()
    for op_ in block.ops:
        live |= op_external_reads(inference_program, op_)
    feed_names = [n for n in feeded_var_names if n in live]
    # serving admission starts here: a saved model must never carry the
    # training tail (prune's training-role skip + the strip above make
    # this unreachable unless a grad var was requested as a target)
    leaked = [op_.type for op_ in block.ops
              if op_.desc.attrs.get("op_role") in ("backward", "optimize")
              or op_.type.endswith("_grad")]
    if leaked:
        raise ValueError(
            f"save_inference_model: training-only ops {leaked} survived "
            f"pruning — a target var appears to be a gradient/optimizer "
            f"output, which is not an inference fetch")
    # a gradient target doesn't leak its producer (the strip above removed
    # it) — it leaves an UNCOMPUTABLE fetch instead: no surviving op writes
    # it and it's neither a feed nor a persistable, so the saved model
    # would only fail at first serve compile. Refuse at export time.
    produced = {n for op_ in block.ops for n in op_.output_arg_names}
    for t in target_vars:
        v = block.desc.vars.get(t.name)
        if (t.name not in produced and t.name not in feeded_var_names
                and not (v is not None and v.persistable)):
            what = ("a gradient" if t.name.endswith("@GRAD")
                    or t.name.endswith("_grad") else "not computable")
            raise ValueError(
                f"save_inference_model: target '{t.name}' is {what} — "
                f"its producer was stripped with the training tail, so "
                f"the inference program cannot compute it from the feeds")
    meta = {
        "program": inference_program.to_json(),
        "feed_names": feed_names,
        "fetch_names": [t.name for t in target_vars],
    }
    model_path = os.path.join(dirname, model_filename or "__model__")
    with open(model_path, "wb") as f:
        pickle.dump(meta, f)
    save_persistables(executor, dirname, inference_program,
                      filename=params_filename)
    return inference_program


def load_inference_model(dirname: str, executor: Executor,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None):
    """Returns (program, feed_target_names, fetch_targets)
    (reference io.py:383)."""
    model_path = os.path.join(dirname, model_filename or "__model__")
    with open(model_path, "rb") as f:
        meta = pickle.load(f)
    program = Program.from_json(meta["program"])
    load_persistables(executor, dirname, program, filename=params_filename)
    fetch_targets = [program.global_block().var(n)
                     for n in meta["fetch_names"]]
    return program, meta["feed_names"], fetch_targets
