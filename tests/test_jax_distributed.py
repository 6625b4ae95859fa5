"""Real multi-process jax.distributed smoke: two spawned processes, CPU
backend, localhost coordinator, multihost.initialize + a cross-process
psum + one dp-sharded train step (multi-host
was previously simulated-only). Reference analogue: the localhost pserver
test, python/paddle/fluid/tests/unittests/test_recv_op.py:26-36."""

import os
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_initialize_psum_and_sharded_step():
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # children get exactly one CPU device each (2-device global mesh)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE), env.get("PYTHONPATH", "")])
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "_distributed_worker.py"),
         coordinator, "2", str(pid)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"
        assert "RESULT" in out, out
