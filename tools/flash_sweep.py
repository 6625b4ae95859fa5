"""Attention alone on the chip: einsum, the flash kernels over a tile
sweep, and jax's bundled Pallas flash attention as an outside yardstick.

    chiprun -- python3 tools/flash_sweep.py [B T H D] [--parent DIR]
        [--rows 256,512] [--major 4096] [--block 4 [--q-off -4]]
        [--window 4096]

Times the forward and forward+backward (jax.vjp on a random cotangent)
of causal bf16 attention at one [B, T, H, D]; `_TILE` and the selection
rule in ops/nn_ops.py::_flash_wins are written from this table (PERF.md
section 6, PR 29: [16, 1024, 12, 64]; PR 33: [1, 4096, 20, 256], the
head size of latent attention's expanded heads; PR 43: those two,
[1, 4096, 32, 128] and the last under `--block 4` at both offsets).
`--rows` limits the tile rows swept, `--major` sets the rows of the
walked side that stay in VMEM at once (pallas_attention._MAJOR) for the
per-kernel sweep. The backward is timed in both forms: `fused` (one
K/V-resident call that also accumulates dQ) beside `dq` and `dkv`, the
split form's two calls, each alone. `--block N` masks at the grain of N
positions, `--window N` gives the causal mask a far edge of N keys (the
sliding-window cell's layers: 1 8192 28 128 --window 4096) and `--q-off`
shifts the queries (block-diffusion attention's
two kernel parts: 0 and -N); einsum, the bundled kernel and the parent
are then left out, the kernels alone are timed. `--parent DIR` also
times the kernels of another checkout (its ops/pallas_attention.py) on
the head counts it admits. One JSON line per reading goes to
chiprun_out/flash_sweep.jsonl.
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
    ap.add_argument("--kernels", default="fwd,dq,dkv,fused",
                    help="kernels to sweep tiles of ('' for none)")
    ap.add_argument("--bundled", type=int, default=1)
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--major", type=int, default=pa._MAJOR)
    ap.add_argument("--block", type=int, default=1)
    ap.add_argument("--q-off", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ns = ap.parse_args()
    plain = ns.block == 1 and ns.q_off == 0 and not ns.window
    b, t, h, d = ns.shape
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    log = open(OUT, "a")
    rng = np.random.default_rng(0)

    def operands(heads):
        return [jnp.asarray(rng.standard_normal((b, t, heads, d)),
                            jnp.bfloat16) for _ in range(4)]

    q, k, v, do = operands(h)
    base = dict(shape=[b, t, h, d], device=jax.devices()[0].device_kind,
                major=ns.major)
    if not plain:
        base.update(block=ns.block, q_off=ns.q_off, window=ns.window)

    def einsum(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, True)

    scale = 1.0 / d ** 0.5
    if plain:
        want = jax.jit(fwd_bwd(einsum))(q, k, v, do)
        report(log, **base, path="einsum", fwd_ms=bench(einsum, q, k, v),
               fwd_bwd_ms=bench(fwd_bwd(einsum), q, k, v, do))

        # the kernels at the tiles pallas_attention has
        got = jax.jit(fwd_bwd(flash))(q, k, v, do)
        report(log, **base, path="flash", tiles=list(pa._TILE),
               fwd_ms=bench(flash, q, k, v),
               fwd_bwd_ms=bench(fwd_bwd(flash), q, k, v, do),
               max_abs_err_vs_einsum=max_err(got, want))
        out = got[0]
        lse = jax.jit(
            lambda q, k, v: pa._forward(q, k, v, True, True)[1])(q, k, v)
    elif ns.window:
        out, lse = jax.jit(lambda q, k, v: pa._forward(
            q, k, v, True, True, window=ns.window))(q, k, v)
    else:
        # one part of block-diffusion attention: the raw (acc, l, m); a
        # row that sees no key (the first block under q_off = -block)
        # gets an LSE that makes its P zero, as the merged one does
        acc, l, m = jax.jit(lambda q, k, v: pa.flash_attention_block(
            q, k, v, ns.q_off, 0, scale, True, block=ns.block))(q, k, v)
        seen = m > -1e29
        lse = jnp.where(seen, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]

    # each kernel alone over (resident rows, walked block rows)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)
    mask = dict(major=ns.major, block=ns.block, window=ns.window)

    def bwd(fused, **tiles):
        return lambda *a: pa._bwd_call(*a, ns.q_off, 0, scale, True,
                                       fused=fused, **tiles, **mask)

    # the wrappers take the tiles as static arguments: one jit entry a
    # tile, nothing to clear between them
    kernels = {
        "fwd": (lambda tile: lambda q, k, v: pa._fwd_call(
            q, k, v, ns.q_off, 0, scale, True, normalize=plain, tile=tile,
            **mask)[0],
            (q, k, v)),
        # the split form's two calls, each alone (XLA drops the other),
        # and the fused form's one
        "dq": (lambda tile: lambda *a: bwd(False, dq_tile=tile)(*a)[0],
               (q, k, v, do, lse, delta)),
        "dkv": (lambda tile: lambda *a: bwd(False, dkv_tile=tile)(*a)[1:],
                (q, k, v, do, lse, delta)),
        "fused": (lambda tile: bwd(True, dkv_tile=tile),
                  (q, k, v, do, lse, delta)),
    }
    split = jax.jit(bwd(False))(q, k, v, do, lse, delta)
    report(log, **base, path="flash", tiles=list(pa._TILE),
           fused_max_abs_err_vs_split=max_err(
               jax.jit(bwd(True))(q, k, v, do, lse, delta), split),
           split_reason=pa._split_reason(
               t, t, pa._lane_block(h, d)[0], q.dtype.itemsize, pa._TILE,
               ns.major))
    rows = [r for r in map(int, ns.rows.split(",")) if t % r == 0]
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
    for blk in (None, 256, 512, 1024) if ns.bundled and plain else ():
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

    if ns.parent and plain:
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
