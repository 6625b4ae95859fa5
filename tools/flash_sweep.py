"""Attention alone on the chip: einsum, the flash kernels over a tile
sweep, and jax's bundled Pallas flash attention as an outside yardstick.

    chiprun -- python3 tools/flash_sweep.py [B T H D] [--parent DIR]

Times the forward and forward+backward (jax.vjp on a random cotangent)
of causal bf16 attention at one [B, T, H, D]; `_TILE` and the selection
rule in ops/nn_ops.py::_flash_wins are written from this table (PERF.md
section 6, PR 29). `--parent DIR` also times the kernels of another
checkout (its ops/pallas_attention.py) on the head counts it admits.
One JSON line per reading goes to chiprun_out/flash_sweep.jsonl.
"""

import argparse
import importlib.util
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.parallel.ring_attention import attention_reference

ROWS = (128, 256, 512, 1024)
OUT = "chiprun_out/flash_sweep.jsonl"


def bench(fn_, *args, iters=20, reps=3):
    fn = jax.jit(fn_)
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def fwd_bwd(attn):
    def run(q, k, v, do):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(do)
    return run


def report(log, **row):
    print(json.dumps(row), flush=True)
    log.write(json.dumps(row) + "\n")
    log.flush()


def max_err(got, want):
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32))))
               for g, w in zip(got, want))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="*", type=int, default=[16, 1024, 12, 64])
    ap.add_argument("--parent")
    ap.add_argument("--kernels", default="fwd,dq,dkv",
                    help="kernels to sweep tiles of ('' for none)")
    ap.add_argument("--bundled", type=int, default=1)
    ns = ap.parse_args()
    b, t, h, d = ns.shape
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    log = open(OUT, "a")
    rng = np.random.default_rng(0)

    def operands(heads):
        return [jnp.asarray(rng.standard_normal((b, t, heads, d)),
                            jnp.bfloat16) for _ in range(4)]

    q, k, v, do = operands(h)
    base = dict(shape=[b, t, h, d], device=jax.devices()[0].device_kind)

    def einsum(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, True)

    want = jax.jit(fwd_bwd(einsum))(q, k, v, do)
    report(log, **base, path="einsum", fwd_ms=bench(einsum, q, k, v),
           fwd_bwd_ms=bench(fwd_bwd(einsum), q, k, v, do))

    # the kernels at the tiles pallas_attention has
    got = jax.jit(fwd_bwd(flash))(q, k, v, do)
    report(log, **base, path="flash", tiles=list(pa._TILE),
           fwd_ms=bench(flash, q, k, v),
           fwd_bwd_ms=bench(fwd_bwd(flash), q, k, v, do),
           max_abs_err_vs_einsum=max_err(got, want))

    # each kernel alone over (resident rows, walked block rows)
    lse = jax.jit(lambda q, k, v: pa._forward(q, k, v, True, True)[1])(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * got[0].astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)
    scale = 1.0 / d ** 0.5

    # the wrappers take the tiles as static arguments: one jit entry a
    # tile, nothing to clear between them
    kernels = {
        "fwd": (lambda tile: lambda q, k, v: pa._fwd_call(
            q, k, v, 0, 0, scale, True, normalize=True, tile=tile)[0],
            (q, k, v)),
        "dq": (lambda tile: lambda *a: pa.flash_attention_bwd_block(
            *a, 0, 0, scale, True, dq_tile=tile)[0],
            (q, k, v, do, lse, delta)),
        "dkv": (lambda tile: lambda *a: pa.flash_attention_bwd_block(
            *a, 0, 0, scale, True, dkv_tile=tile)[1:],
            (q, k, v, do, lse, delta)),
    }
    rows = [r for r in ROWS if t % r == 0]
    for kernel in filter(None, ns.kernels.split(",")):
        at, args = kernels[kernel]
        for tile in itertools.product(rows, rows):
            try:
                ms = bench(at(tile), *args)
            except Exception as e:      # a tile Mosaic refuses
                ms = None
                print(kernel, tile, "refused:", str(e)[:200], flush=True)
            report(log, **base, path="flash", kernel=kernel,
                   tile=list(tile), ms=ms)

    # jax's bundled kernel, head-major operands (its layout), its default
    # tiles and two larger sets
    from jax.experimental.pallas.ops.tpu import flash_attention as bundled
    qh, kh, vh, doh = (x.transpose(0, 2, 1, 3) for x in (q, k, v, do))
    for blk in (None, 256, 512, 1024) if ns.bundled else ():
        sizes = None if blk is None else bundled.BlockSizes(
            block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
            block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
            block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk,
            block_q_dq=blk)

        def attn(q, k, v):
            return bundled.flash_attention(q, k, v, causal=True,
                                           sm_scale=scale, block_sizes=sizes)
        try:
            report(log, **base, path="jax_bundled", block=blk or "default",
                   fwd_ms=bench(attn, qh, kh, vh),
                   fwd_bwd_ms=bench(fwd_bwd(attn), qh, kh, vh, doh))
        except Exception as e:
            print("bundled", blk, "refused:", str(e)[:300], flush=True)

    if ns.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_pallas_attention",
            os.path.join(ns.parent, "paddle_tpu/ops/pallas_attention.py"))
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)
        for heads in (h, 8):
            qo, ko, vo, doo = operands(heads)
            if old.ineligible(qo, ko, vo) is not None:
                print("parent declines", heads, "heads:",
                      old.ineligible(qo, ko, vo), flush=True)
                continue
            row = dict(base, shape=[b, t, heads, d])

            def parent(q, k, v):
                return old.flash_attention(q, k, v, True)
            for path, fn in (("parent_flash", parent), ("flash", flash),
                             ("einsum", einsum)):
                report(log, **row, path=path, fwd_ms=bench(fn, qo, ko, vo),
                       fwd_bwd_ms=bench(fwd_bwd(fn), qo, ko, vo, doo))


if __name__ == "__main__":
    main()
