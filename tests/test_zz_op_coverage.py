"""Op-coverage gate, run LAST (zz prefix; pytest collects test files in
alphabetical order): every registered op type must have been executed by
some earlier test in this session — the continuous-enforcement form of the
reference's one-OpTest-file-per-op discipline (reference
tests/unittests/op_test.py:212). Skips on partial runs (-k / single-file
invocations) so it only gates full-suite sessions. Under xdist no worker
runs the whole suite, so the gate is held on the union of what every
worker executed (tests/conftest.py `pytest_sessionfinish`).
"""

import os
import time

from paddle_tpu import executor as executor_mod
from paddle_tpu.ops import registry

import pytest

import conftest

# executor-level plumbing with no kernel of its own
STRUCTURAL = {"feed", "fetch"}
# a full-suite run executes far more distinct op types than this; partial
# runs (single files, -k filters) stay below it and skip the gate
FULL_RUN_THRESHOLD = 150
# xdist holds a worker's last test back until every other worker is on its
# own last test, so the wait below is one test long, not one file
WORKER_WAIT_S = 300.0


def _other_workers_ops(worker_id):
    """Op types the other xdist workers executed: each leaves
    `ops-<worker>.txt` in the shared directory when its session ends.
    Returns the set and the workers that never reported."""
    shared = conftest.shared_ops_dir()
    others = [f"gw{i}"
              for i in range(int(os.environ["PYTEST_XDIST_WORKER_COUNT"]))
              if f"gw{i}" != worker_id]

    def reported(w):
        return os.path.exists(os.path.join(shared, f"ops-{w}.txt"))

    deadline = time.monotonic() + WORKER_WAIT_S
    while not all(map(reported, others)) and time.monotonic() < deadline:
        time.sleep(0.2)
    executed, late = set(), []
    for w in others:
        if reported(w):
            with open(os.path.join(shared, f"ops-{w}.txt")) as f:
                executed.update(f.read().split())
        else:
            late.append(w)
    return executed, late


def test_every_registered_op_executed():
    executed, late = set(executor_mod._RECORDED_OPS), []
    worker_id = os.environ.get("PYTEST_XDIST_WORKER")
    if worker_id:
        theirs, late = _other_workers_ops(worker_id)
        executed |= theirs
    if len(executed) < FULL_RUN_THRESHOLD and not late:
        pytest.skip(f"partial run ({len(executed)} op types executed); "
                    "coverage gate applies to full-suite sessions")
    registered = set(registry.registered_ops())
    missing = sorted(registered - executed - STRUCTURAL)
    assert not missing, (
        f"{len(missing)} registered ops never executed by the suite"
        f"{' (workers that never reported: %s)' % late if late else ''}: "
        f"{missing}")
