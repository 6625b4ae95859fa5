"""Shared helpers for shard_map-based collectives and cross-host reduces."""

from __future__ import annotations

import numpy as np

import jax
from jax import lax


def coll_scope(site: str):
    """Named scope tagging a framework collective call site. The scope
    lands in HLO metadata op_name as 'pd.coll.<site>', which
    xplane.hlo_instructions reads as the collective's `site`, so fleet.py
    can attribute collective cost to the emitting layer (ring-attention
    rotate, pipeline send, dp grad psum) instead of a bare HLO name."""
    return jax.named_scope(f"pd.coll.{site}")


def mark_varying(x, axis_name: str):
    """Mark a replicated value as varying over `axis_name` for shard_map's
    varying-manifest-axis typechecker (scan carries initialized from
    replicated constants need this). Tries the current API first and
    degrades gracefully on jax versions without one."""
    pcast = getattr(lax, "pcast", None)
    if pcast is not None:
        try:
            return pcast(x, (axis_name,), to="varying")
        except TypeError:
            pass
    pvary = getattr(lax, "pvary", None)
    if pvary is not None:
        return pvary(x, (axis_name,))
    return x


def tree_mark_varying(tree, axis_name: str):
    return jax.tree_util.tree_map(lambda a: mark_varying(a, axis_name), tree)


# ---------------------------------------------------------------------------
# Cross-host (multi-controller) reduces — the DCN-level collectives backing
# telemetry.snapshot(reduce=True) and any other host-scalar aggregation.
# Single-process runs short-circuit without touching jax.distributed.
# ---------------------------------------------------------------------------

def host_allreduce_sum(values) -> np.ndarray:
    """Elementwise sum of a same-shaped float array across every process
    (allgather + local sum — semantically an allreduce; the gather rides
    the same DCN collective). Callers must pass identical shapes on every
    host."""
    local = np.asarray(values, dtype=np.float64)
    if jax.process_count() <= 1:
        return local
    from jax.experimental import multihost_utils
    gathered = np.asarray(multihost_utils.process_allgather(local))
    return gathered.reshape((jax.process_count(),) + local.shape).sum(axis=0)


_kv_gen = [0]


def _coordination_client():
    """The jax.distributed coordination-service client (None when the
    runtime isn't multi-process or the internal layout moved)."""
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except Exception:  # noqa: BLE001 - internal API, degrade to collective
        return None


def process_allgather_bytes(payload: bytes) -> list:
    """Gather one variable-length bytes payload per process, returned in
    process order — the transport for per-host metadata (serialized
    telemetry snapshots, JSON).

    Preferred path: the jax.distributed coordination service's KV store
    (control plane, DCN) — telemetry is low-rate and must not depend on
    the accelerator backend supporting multiprocess computations (the CPU
    backend does not). Fallback: a size-equalized uint8 device allgather."""
    if jax.process_count() <= 1:
        return [payload]
    client = _coordination_client()
    if client is not None:
        import base64
        gen, _kv_gen[0] = _kv_gen[0], _kv_gen[0] + 1
        base = f"paddle_tpu/allgather_bytes/{gen}"
        client.key_value_set(f"{base}/{jax.process_index()}",
                             base64.b64encode(payload).decode("ascii"))
        return [base64.b64decode(client.blocking_key_value_get(
                    f"{base}/{i}", 60_000))
                for i in range(jax.process_count())]
    from jax.experimental import multihost_utils
    data = np.frombuffer(payload, dtype=np.uint8)
    with coll_scope("host_allgather"):
        sizes = np.asarray(multihost_utils.process_allgather(
            np.array([data.size], dtype=np.int64))).reshape(-1)
        padded = np.zeros(int(sizes.max()), dtype=np.uint8)
        padded[: data.size] = data
        rows = np.asarray(multihost_utils.process_allgather(padded))
    rows = rows.reshape(jax.process_count(), -1)
    return [rows[i, : int(sizes[i])].tobytes()
            for i in range(jax.process_count())]
