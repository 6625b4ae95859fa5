#!/usr/bin/env python
"""Op-coverage report: which registered ops does the test suite execute?

The suite itself enforces coverage continuously (tests/test_zz_op_coverage.py
reads the in-process record); this tool is the offline report form:

    rm -f /tmp/op_coverage.txt
    PADDLE_TPU_RECORD_OPS=/tmp/op_coverage.txt python -m pytest tests/ -q
    python tools/op_coverage.py /tmp/op_coverage.txt

(reference test discipline: tests/unittests has one OpTest file per op —
op_test.py:212; this report proves the same property for the new corpus.)
"""

import os
import sys

# force the host platform BEFORE importing jax/paddle_tpu: reading a
# registry needs no accelerator, and must not take the chip from a process
# that does
os.environ["JAX_PLATFORMS"] = "cpu"

# `python tools/op_coverage.py` puts tools/ (not the repo root) on
# sys.path; make the tool runnable from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def inventory():
    """Scriptable surface counts (self-reported inventory
    must come from dir(), not prose): fluid layer functions, v2 layer
    wrappers, v2 networks composites, registered ops."""
    import inspect
    import paddle_tpu  # noqa: F401
    from paddle_tpu import layers as fluid_layers
    from paddle_tpu.ops import registry
    from paddle_tpu.v2 import layer as v2_layer
    from paddle_tpu.v2 import networks as v2_networks

    def _public_callables(mod):
        out = []
        for n in dir(mod):
            if n.startswith("_"):
                continue
            obj = getattr(mod, n)
            if callable(obj) and not inspect.ismodule(obj):
                out.append(n)
        return sorted(out)

    counts = {
        "fluid_layer_fns": len(_public_callables(fluid_layers)),
        "v2_layer_wrappers": len(_public_callables(v2_layer)),
        "v2_networks_composites": len(_public_callables(v2_networks)),
        "registered_ops": len(registry.registered_ops()),
    }
    import json
    print(json.dumps(counts))
    return 0


def probe_compat():
    """Report which registered op types the inspector's tensor-stat probe
    pass can instrument (inspector.probe_compatible): structural and
    no-kernel ops are excluded, everything else gets on-device stats."""
    import paddle_tpu  # noqa: F401  (registers all ops)
    from paddle_tpu import inspector
    from paddle_tpu.ops import registry

    registered = sorted(registry.registered_ops())
    compat = [t for t in registered if inspector.probe_compatible(t)]
    incompat = [t for t in registered if not inspector.probe_compatible(t)]
    print(f"registered ops   : {len(registered)}")
    print(f"probe-compatible : {len(compat)}")
    print(f"not probeable    : {len(incompat)}")
    for t in incompat:
        print(f"  NOT-PROBEABLE {t}")
    return 0


def main(path):
    if path == "--inventory":
        return inventory()
    if path == "--probe-compat":
        return probe_compat()
    if not os.path.exists(path):
        print(f"no record file at {path} — run the suite with "
              f"PADDLE_TPU_RECORD_OPS={path} first (see module docstring)")
        return 2
    import paddle_tpu  # noqa: F401  (registers all ops)
    from paddle_tpu.ops import registry

    executed = set()
    with open(path) as f:
        for line in f:
            executed.add(line.strip())
    registered = set(registry.registered_ops())
    # executor-level ops with no kernel of their own
    structural = {"feed", "fetch"}
    covered = sorted(registered & executed)
    missing = sorted(registered - executed - structural)
    grad_only = sorted(e for e in executed if e.endswith("_grad")
                       and e not in registered)
    print(f"registered ops : {len(registered)}")
    print(f"executed       : {len(covered)} "
          f"(+{len(grad_only)} auto-generated grad ops)")
    print(f"missing        : {len(missing)}")
    for m in missing:
        print(f"  UNCOVERED {m}")
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "/tmp/op_coverage.txt"))
