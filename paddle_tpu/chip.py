"""What the framework takes for granted about the device, in one place:
the published peaks by `device_kind`, the device description every
benchmark line carries, and the one persistent compilation cache.

Nothing here is assumed for a device that is not in the table: an
unknown `device_kind` on a non-CPU platform is an error, not a v5e.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional

__all__ = ["PEAKS", "Peaks", "UnknownDeviceError", "describe",
           "enable_compile_cache", "peaks"]


class Peaks(NamedTuple):
    bf16_tflops: float
    int8_tops: float
    hbm_bytes: int
    hbm_gbps: float


# Per chip, keyed by jax's `device_kind`.
PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
    "TPU v5 lite": Peaks(197.0, 393.0, 16 * 1024 ** 3, 819.0),
}


class UnknownDeviceError(RuntimeError):
    """The device is an accelerator whose peaks nobody wrote down."""


def peaks(device=None) -> Optional[Peaks]:
    """The table row of `device` (default: the first jax device); None on
    the CPU, which has no nominal peak worth a utilization figure."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no peak FLOP/s, HBM size or bandwidth on record for "
            f"device_kind {device.device_kind!r} (platform "
            f"{device.platform!r}); add its published figures to "
            f"paddle_tpu.chip.PEAKS") from None


def describe() -> Dict[str, object]:
    """platform / device_kind / device_count as jax reports them — the
    three fields every measured line names its device by."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def enable_compile_cache() -> Optional[str]:
    """Point jax's persistent compilation cache at `<checkout>/.xla_cache`
    and return that path — unless JAX_COMPILATION_CACHE_DIR is set, in
    which case jax already reads it and no directory is set in code
    (returns None). The path is part of the cache key's stability: it is
    fixed, never a temporary, pid- or time-derived directory. Every entry
    point that compiles (chip_smoke.py, the benchmark, the CLI, the test
    harness) calls this once before its first compile.

    Either way, MLIR locations keep only the frame that emitted the op,
    not the Python traceback above it (10 frames by default): jax strips
    locations from a module before it keys the cache, but a Mosaic
    kernel's serialized payload carries its own, so the key of any step
    that holds a Pallas kernel depended on the Python stack that traced
    it — the executor's jit call, its compile-only memory analysis and
    Executor.compiled_hlo each compiled the same ResNet-50 step under a
    key of their own (chip run, PR 21). (Turning tracebacks off
    altogether would also drop the pd.<op> name stack from HLO op_name
    metadata, which the profiler's per-op table joins on.)"""
    import jax
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
