"""Host seconds a step of the window spent blocked on a device value
the caller did not ask for: the window's sum of the program's
`executor_host_wait_seconds` histogram over every site (`dynamics`,
`side_fetch`, `check_nan_inf`, `profiler_sync`, `lod_writeback`; booked
by `telemetry.host_wait` around the blocking call alone) over the
window's `executor_steps_total`, in ms. 0.0 is right: a pipelined loop
with the defaults reaches no site with a value still in flight, and a
watcher that syncs shows here as `compiles_in_window.train` shows a
compile. None where no step ran, or the program declares no such family
(a parent: its waits are not counted, not absent)."""

from benchmarks import evidence

LAYER = "executor"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "program_counter"
FAMILY = "executor_host_wait_seconds"


def compute(ev):
    from paddle_tpu import telemetry

    counters = ev.get("counters") or {}
    steps = evidence.family_total(counters, "executor_steps_total")
    if not steps or FAMILY not in telemetry.METRIC_CATALOG:
        return None
    waited = evidence.family_total(counters, FAMILY, "sum") or 0.0
    return 1e3 * waited / steps
