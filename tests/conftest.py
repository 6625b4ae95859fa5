"""Test harness config: run on a virtual 8-device CPU platform so sharding
paths are exercised without TPU hardware (SURVEY.md §4.1 TPU-build
translation)."""

import os

# Force the CPU platform whatever the ambient environment points jax at:
# the suite's multi-device tests need the 8 virtual host devices. jax may
# be preloaded by the environment, in which case JAX_PLATFORMS was already
# read at import time — jax.config.update is the reliable path; XLA_FLAGS
# is read later, at backend init, so the env var suffices for it.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite compiles ~100 distinct
# programs (book chapters dominate); caching them across runs cuts warm
# wall time substantially. The one helper every entry point shares.
from paddle_tpu import chip  # noqa: E402

chip.enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs, scope and name generator."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import unique_name
    from paddle_tpu import executor as executor_mod

    main, startup = fluid.Program(), fluid.Program()
    old_main = fluid.switch_main_program(main)
    old_startup = fluid.switch_startup_program(startup)
    gen = unique_name.switch()
    old_scope = executor_mod._scope_stack[:]
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    yield
    fluid.switch_main_program(old_main)
    fluid.switch_startup_program(old_startup)
    unique_name.switch(gen)
    executor_mod._scope_stack[:] = old_scope


def shared_ops_dir(uid=None):
    """Where the xdist workers of run `uid` (default: this worker's run)
    leave the op types they executed; None outside xdist."""
    import tempfile

    uid = uid or os.environ.get("PYTEST_XDIST_TESTRUNUID")
    return uid and os.path.join(tempfile.gettempdir(), f"paddle_tpu_ops_{uid}")


_run_uid = None     # on the xdist controller: the run its workers share


@pytest.hookimpl(optionalhook=True)     # xdist's hook; absent under no:xdist
def pytest_configure_node(node):
    global _run_uid
    _run_uid = node.workerinput["testrunuid"]


def pytest_sessionfinish(session):
    """Under xdist no process runs the whole suite: each worker leaves
    what it executed in the run's shared directory when it is done,
    tests/test_zz_op_coverage.py asserts on the union, and the
    controller, which finishes last, removes the directory."""
    worker = getattr(session.config, "workerinput", {}).get("workerid")
    if worker is None:
        if _run_uid is not None:
            import shutil
            shutil.rmtree(shared_ops_dir(_run_uid), ignore_errors=True)
        return
    from paddle_tpu import executor as executor_mod

    os.makedirs(shared_ops_dir(), exist_ok=True)
    part = os.path.join(shared_ops_dir(), f"ops-{worker}.part")
    with open(part, "w") as f:
        f.write("\n".join(sorted(executor_mod._RECORDED_OPS)))
    os.replace(part, part[:-len(".part")] + ".txt")
