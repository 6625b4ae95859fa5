"""Elastic end-to-end training proof: multi-process
training over the shared TaskQueue where one worker is SIGKILLed mid-pass
and the job finishes with a DIFFERENT worker count — no sample lost, no
duplicate beyond the failure budget (the killed worker's in-flight task),
and the final parameters/loss match an uninterrupted single-process
oracle. Mirrors the Go master contract: go/master/service.go:341
timeout-requeue, :455 failure budget; trainers stateless, work
re-dispatched."""

import json
import multiprocessing as mp
import os
import signal
import time

import numpy as np
import pytest

from paddle_tpu.parallel.master import TaskQueue

N, D = 64, 4
TASKS = 8
PASSES = 3
LR = 0.01


def _spawn(ctx, wid, qdir, data, params, grads, log, **kw):
    from _elastic_worker import worker
    p = ctx.Process(target=worker,
                    args=(qdir, wid, data, params, grads, log),
                    kwargs=kw)
    p.start()
    return p


def test_sigkill_mid_pass_job_finishes_and_matches_oracle(tmp_path):
    rng = np.random.RandomState(3)
    x = rng.randn(N, D).astype(np.float64)
    w_true = rng.randn(D).astype(np.float64)
    y = x @ w_true
    data_path = str(tmp_path / "data.npz")
    np.savez(data_path, x=x, y=y)

    qdir = str(tmp_path / "queue")
    grads = str(tmp_path / "grads")
    os.makedirs(qdir)
    os.makedirs(grads)
    params_path = str(tmp_path / "params.npy")
    w = np.zeros(D)
    np.save(params_path, w)

    sample_ids = [list(range(i, N, TASKS)) for i in range(TASKS)]
    chunk_of = {str(t): set(ids) for t, ids in enumerate(sample_ids)}

    q = TaskQueue(qdir, timeout_s=2.0)
    q.partition(sample_ids, chunks_per_task=1)

    ctx = mp.get_context("spawn")
    logs = []
    killed_task_samples = None
    for pass_no in range(PASSES):
        procs = {}
        if pass_no == 0:
            # three workers; w0 is slowed so the parent can SIGKILL it
            # reliably mid-task (a real preemption, not a clean exit).
            # w0 starts ALONE and the parent waits for its lease marker
            # before spawning the fast workers — on a 1-core box the
            # fast pair can otherwise drain the whole pass before the
            # slow worker's spawn even finishes (observed in-suite).
            marker = str(tmp_path / "w0_started")
            log0 = str(tmp_path / f"log_w0_{pass_no}.json")
            procs["w0"] = _spawn(ctx, "w0", qdir, data_path, params_path,
                                 grads, log0, slow_s=30.0,
                                 marker_path=marker)
            logs.append(("w0", log0))
            deadline = time.time() + 60
            while not os.path.exists(marker) and time.time() < deadline:
                time.sleep(0.02)
            assert os.path.exists(marker), "w0 never leased a task"
            for wid in ("w1", "w2"):
                log = str(tmp_path / f"log_{wid}_{pass_no}.json")
                procs[wid] = _spawn(ctx, wid, qdir, data_path,
                                    params_path, grads, log)
                logs.append((wid, log))
            os.kill(procs["w0"].pid, signal.SIGKILL)
            procs["w0"].join(timeout=30)
            assert procs["w0"].exitcode == -signal.SIGKILL
            # which task did w0 die holding? (for the duplicate bound)
            state = json.load(open(os.path.join(qdir, "queue.json")))
            w0_pending = [t for t, lease in state["pending"].items()
                          if lease["worker"] == "w0"]
            assert len(w0_pending) <= 1
            if w0_pending:
                killed_task_samples = chunk_of[w0_pending[0]]
            del procs["w0"]
        else:
            # the job CONTINUES with a different worker count (2 not 3)
            for wid in ("w1", "w2"):
                log = str(tmp_path / f"log_{wid}_{pass_no}.json")
                procs[wid] = _spawn(ctx, wid, qdir, data_path,
                                    params_path, grads, log)
                logs.append((wid, log))
        for wid, p in procs.items():
            p.join(timeout=120)
            assert p.exitcode == 0, (wid, p.exitcode)
        assert q.pass_done()

        # reduce: per-task gradient files are idempotent, so the requeued
        # task contributes exactly once no matter how many times it ran
        files = sorted(os.listdir(grads))
        assert files == [f"task_{t}.npy" for t in range(TASKS)], files
        grad = sum(np.load(os.path.join(grads, f)) for f in files)
        w = w - LR * grad
        np.save(params_path, w)
        for f in files:
            os.remove(os.path.join(grads, f))
        q.reset_pass()

    # 1) parameters match the uninterrupted single-process oracle exactly
    #    (same full-batch GD, same reduction order)
    w_oracle = np.zeros(D)
    for _ in range(PASSES):
        order = sorted(range(TASKS), key=lambda t: f"task_{t}.npy")
        grad = sum(x[sample_ids[t]].T @ (x[sample_ids[t]] @ w_oracle
                                         - y[sample_ids[t]])
                   for t in order)
        w_oracle = w_oracle - LR * grad
    np.testing.assert_allclose(w, w_oracle, rtol=1e-12)
    loss = 0.5 * np.mean((x @ w - y) ** 2)
    loss_oracle = 0.5 * np.mean((x @ w_oracle - y) ** 2)
    assert abs(loss - loss_oracle) < 1e-12
    assert loss < 0.5 * np.mean(y ** 2)            # it actually trained

    # 2) per-pass sample accounting: every sample covered every pass; any
    #    duplicate consumption is confined to the killed worker's
    #    in-flight task (the at-least-once failure budget)
    for pass_no in range(PASSES):
        seen = []
        for wid, log in logs:
            if log.endswith(f"_{pass_no}.json") and os.path.exists(log):
                seen.extend(json.load(open(log)))
        covered = set(seen)
        assert covered == set(range(N)), f"pass {pass_no} lost samples"
        dupes = {s for s in covered if seen.count(s) > 1}
        if pass_no == 0 and killed_task_samples is not None:
            assert dupes <= killed_task_samples, (
                "duplicates outside the requeued task", dupes)
        else:
            assert not dupes
