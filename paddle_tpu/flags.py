"""Central flag registry (reference: paddle/utils/Flags.cpp's 28 gflags +
the fluid DEFINE_* flags scattered near use, forwarded via
core.init_gflags). Flags are declared here with defaults/help, read from
`PADDLE_TPU_<NAME>` environment variables (the TPU-native analogue of
gflags' --name=value), and queryable at runtime:

    from paddle_tpu import flags
    flags.get("check_nan_inf")      # -> bool
    flags.set("check_nan_inf", True)  # runtime toggle (writes the env var)
    flags.dump()                    # -> {name: (value, help)}

Most modules keep reading their flags at import time for zero overhead; this
registry is the single catalogue of what exists (reference Flags.cpp role).
A growing set of flags is *live* — re-read through get() on every use, so
set() changes behavior at runtime: `vlog`, `check_nan_inf`,
`nonfinite_attribution`, `flight_recorder` (executor.py / inspector.py).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

_REGISTRY: Dict[str, Tuple[Any, type, str]] = {}
# value seen when this module was imported: consuming modules read their
# PADDLE_TPU_* vars at import time, so THIS is what live code is acting on
_IMPORT_SNAPSHOT: Dict[str, Any] = {}


def define(name: str, default, help_str: str, type_=None):
    t = type_ or type(default)
    _REGISTRY[name] = (default, t, help_str)
    _IMPORT_SNAPSHOT[name] = get(name)
    return _IMPORT_SNAPSHOT[name]


def _parse(raw: str, t: type, default):
    # match exactly how the modules read their env vars: bool flags are on
    # only for "1" (executor.py etc. test == "1"), numeric flags tolerate
    # an empty value by falling back to the default
    if t is bool:
        return raw == "1"
    if raw == "":
        return default
    return t(raw)


def get(name: str):
    """Current environment value. NOTE: most consuming modules snapshot
    their flag at import time, so an env var changed after import shows
    here without changing live behavior — compare against snapshot()."""
    default, t, _ = _REGISTRY[name]
    raw = os.environ.get(f"PADDLE_TPU_{name.upper()}")
    if raw is None:
        return default
    return _parse(raw, t, default)


def set(name: str, value):
    """Set a flag at runtime by writing its `PADDLE_TPU_<NAME>` env var (so
    child processes inherit it, matching how gflags values propagate through
    the environment in the reference's distributed launchers). Live-read
    flags (vlog, check_nan_inf, nonfinite_attribution, flight_recorder)
    react immediately; import-snapshot consumers keep their old value —
    dump() annotates the divergence. `value=None` unsets the env var,
    restoring the registered default. Returns the new effective value."""
    default, t, _ = _REGISTRY[name]
    env = f"PADDLE_TPU_{name.upper()}"
    if value is None:
        os.environ.pop(env, None)
        return default
    if t is bool:
        raw = "1" if value not in (False, 0, "0", "") else "0"
    else:
        raw = str(t(value))
    os.environ[env] = raw
    return get(name)


def snapshot(name: str):
    """The value at import time — what live modules are actually using."""
    return _IMPORT_SNAPSHOT[name]


def dump() -> Dict[str, Tuple[Any, str]]:
    """{name: (value, help)}; when the current env differs from the
    import-time snapshot the help is annotated, since live modules act on
    the snapshot, not the new env value."""
    out = {}
    for n, (_, _, h) in sorted(_REGISTRY.items()):
        cur = get(n)
        if cur != _IMPORT_SNAPSHOT.get(n, cur):
            h = (f"{h} [env changed after import: active="
                 f"{_IMPORT_SNAPSHOT[n]!r}, env={cur!r}]")
        out[n] = (cur, h)
    return out


# --- the catalogue (reference Flags.cpp / executor.cc DEFINE_bool etc.) ----
define("eager", False,
       "op-by-op interpretation instead of whole-block jit "
       "(reference executor.cc interpreter semantics; debugging)")
define("check_nan_inf", False,
       "scan op outputs for NaN/Inf each step "
       "(reference FLAGS_check_nan_inf, executor.cc:325)")
define("trap_fp", False,
       "raise at the op producing NaN/Inf via jax debug-nans "
       "(reference TrainerMain.cpp:49 feenableexcept)")
define("benchmark", False,
       "eager mode: wait for device completion after every op and log "
       "per-op wall time (reference FLAGS_benchmark, executor.cc:321)")
define("allow_zero_grad", False,
       "permit NO_GRAD ops with differentiable inputs on the loss path "
       "instead of raising (append_backward safety check)")
define("vlog", 0,
       "verbose logging level; >0 enables paddle_tpu.vlog output "
       "(reference glog VLOG levels)")
define("record_ops", "",
       "file path: append every executed op type (tools/op_coverage.py)")
define("max_loop_iters", 128,
       "default while-loop step-scope recording capacity "
       "(While(max_iters=...) overrides per loop)")
define("nonfinite_attribution", True,
       "on NaN/Inf detection, replay the step with bisection probes to "
       "name the first offending op (inspector.attribute_nonfinite); "
       "live-read, 0 disables the extra replay runs")
define("flight_recorder", "",
       "path: enable the inspector flight recorder; a JSON crash report "
       "is written there on executor exception or fatal signal "
       "(inspector.enable_flight_recorder)")
define("step_log", "",
       "JSONL step-event log path (telemetry.enable_step_log; read back "
       "with telemetry.read_step_log / the `telemetry` CLI)")
define("telemetry_fetch", True,
       "fetch program._telemetry_fetch_extra side-outputs (e.g. the clip "
       "pass's global norm) alongside user fetches; 0 skips the per-step "
       "device->host read for latency-critical loops")
