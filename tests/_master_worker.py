"""Spawn-context worker for test_master_queue: lives in its own module so
the spawned child imports ONLY this file (stdlib + master.py loaded by
path), never the paddle_tpu package __init__ (which imports jax). Spawn
instead of fork because forking a jax-initialized parent is the documented
deadlock hazard."""

import os


def _load_master_standalone():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "parallel", "master.py")
    spec = importlib.util.spec_from_file_location("_master_standalone", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def worker(d, wid, die_after, out_q):
    """Consume the elastic stream; optionally crash (os._exit) mid-task."""
    master = _load_master_standalone()
    q = master.TaskQueue(d, timeout_s=2.0)
    seen = []
    consumed = 0
    for s in master.elastic_reader(q, chunk_fetch=lambda c: c,
                                   worker=wid)():
        seen.append(s)
        consumed += 1
        if die_after is not None and consumed >= die_after:
            os._exit(17)               # crash WITHOUT finishing the task
    out_q.put((wid, seen))
