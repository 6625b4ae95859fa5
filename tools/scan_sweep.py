"""The Mamba-2 scan alone on the chip: ssd_scan_chunked (plain jax.numpy
under a jax.checkpoint, as the op runs it where a shape does not tile)
beside the Pallas kernels of ops/pallas_scan.py.

    chiprun -- python3 tools/scan_sweep.py [B T H P G N chunk] [--dtype bfloat16]
                                           [--chunks 128,256] [--heads 8,16]

Times the forward and forward + gradient (jax.vjp on a random cotangent)
of the scan's core, (x, dt, a, B, C) -> y, at one shape, each kernel
alone, and the largest error of the kernels' output and five gradients
against the chunked path in float32. x, y, B and C enter and leave as
the mixer holds them, [B, T, H x P] and [B, T, G x N] row-major, and
are reshaped inside the timed function as layers.mamba2_mixer reshapes
them; the kernels want time last, so here both turns of x and y are
real transposes that the hybrid cell's step does not pay (XLA lays its
[1, T, C] activations out with T minor). The table in
ops/pallas_scan.py's docstring is written from it (PERF.md section 6,
PR 40: [1, 4096, 64, 64], G 8, N 128, chunk 128, the hybrid cell's
shape). `--chunks` and `--heads` time the kernels at other chunks than
the shape's and at other heads a grid step than
pallas_scan.heads_a_step gives (one group of 64 heads, PR 49:
`1 8192 64 64 1 128 256 --chunks 128,256 --heads 8,16`; Mosaic refuses
32 heads at chunk 256 for VMEM and the run ends there); the result
does not depend on either. One JSON line per reading goes to
chiprun_out/scan_sweep.jsonl.
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_scan
from paddle_tpu.ops.hybrid_ops import ssd_scan_chunked, ssd_scan_ineligible
from paddle_tpu.ops.pallas_attention import _interpret
from tools.flash_sweep import bench, report

OUT = "chiprun_out/scan_sweep.jsonl"


def mixer_view(scan, h, g):
    """`scan` over x [B, T, H x P], B and C [B, T, G x N] -> [B, T, H x P]."""
    def run(x, dt, a, b, c):
        heads, groups = x.shape[:2] + (h, -1), b.shape[:2] + (g, -1)
        return scan(x.reshape(heads), dt, a, b.reshape(groups),
                    c.reshape(groups)).reshape(x.shape)
    return run


def fwd_bwd(scan):
    def run(x, dt, a, b, c, dy):
        y, vjp = jax.vjp(scan, x, dt, a, b, c)
        return (y,) + vjp(dy)
    return run


def rel_err(got, want):
    """Per array: the largest difference over the largest value."""
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                  / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30))
            for g, w in zip(got, want)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="*", type=int,
                    default=[1, 4096, 64, 64, 8, 128, 128])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--heads", default="")
    ns = ap.parse_args()
    bsz, t, h, p, g, n, chunk = ns.shape
    dtype = jnp.dtype(ns.dtype)
    reason = ssd_scan_ineligible(chunk, h // g, p, n)
    assert reason is None, f"the kernels decline this shape: {reason}"
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    log = open(OUT, "a")
    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0, to=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape) * scale, to)

    # dt and A as the published initialisation leaves them: dt in
    # [0.001, 0.1], A in [-16, -1]
    x = normal(bsz, t, h * p, to=dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (bsz, t, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    b, c = (normal(bsz, t, g * n, scale=0.5, to=dtype) for _ in range(2))
    dy = normal(bsz, t, h * p)
    args = (x, dt, a, b, c)
    base = dict(shape=ns.shape, dtype=str(dtype),
                device=jax.devices()[0].device_kind)

    def ints(text, default):
        return [int(v) for v in text.split(",")] if text else [default]

    exact = jax.jit(fwd_bwd(mixer_view(functools.partial(
        ssd_scan_chunked, chunk=chunk, dtype=jnp.float32), h, g)))(
            *(v.astype(jnp.float32) for v in args), dy)

    def read(path, scan, **form):
        got = jax.jit(fwd_bwd(scan))(*args, dy)
        report(log, **base, **form, path=path, fwd_ms=bench(scan, *args),
               fwd_bwd_ms=bench(fwd_bwd(scan), *args, dy),
               rel_err_vs_float32=dict(zip(
                   ("y", "dx", "ddt", "da", "dB", "dC"),
                   rel_err(got, exact))))

    read("chunked", mixer_view(jax.checkpoint(functools.partial(
        ssd_scan_chunked, chunk=chunk, dtype=dtype)), h, g), chunk=chunk)
    turned = [jnp.asarray(v.swapaxes(1, 2))
              for v in (x, dy.astype(dtype), b, c)]
    for chunk_ in ints(ns.chunks, chunk):
        for heads in ints(ns.heads,
                          pallas_scan.heads_a_step(h // g, chunk_)):
            form = dict(chunk=chunk_, heads_a_step=heads)
            read("kernels", mixer_view(functools.partial(
                pallas_scan.ssd_scan_kernels, chunk=chunk_, dtype=dtype,
                interpret=_interpret(), heads=heads), h, g), **form)
            # each kernel alone, on the operands the rule hands it (time
            # last)
            cum = pallas_scan._cum_rows(dt * a, chunk_, h // heads)
            rows = pallas_scan._head_rows(dt, h // heads)
            static = dict(chunk=chunk_, r=heads, p=p, groups=g,
                          interpret=_interpret())
            forward = functools.partial(pallas_scan._forward, **static)
            operands = (cum, rows, turned[0], b, turned[3])
            _, entering = forward(*operands)
            report(log, **base, **form, path="kernels",
                   kernel="ssd_scan_fwd", ms=bench(forward, *operands))
            report(log, **base, **form, path="kernels",
                   kernel="ssd_scan_bwd",
                   ms=bench(functools.partial(pallas_scan._backward,
                                              **static),
                            cum, rows, turned[0], turned[1], b, turned[2],
                            c, turned[3], entering))


if __name__ == "__main__":
    main()
