"""Attention alone on the chip: einsum, the flash kernels over a tile
sweep, and jax's bundled Pallas flash attention as an outside yardstick.

    chiprun -- python3 tools/flash_sweep.py [B T H D] [--parent DIR]
        [--rows 256,512] [--grain 128,256,512] [--major 4096]
        [--block 4 [--q-off -4]] [--window 4096]

Times the forward and forward+backward (jax.vjp on a random cotangent)
of causal bf16 attention at one [B, T, H, D]; `_TILE` and the selection
rule in ops/nn_ops.py::_flash_wins are written from this table (PERF.md
section 6, PR 29: [16, 1024, 12, 64]; PR 33: [1, 4096, 20, 256], the
head size of latent attention's expanded heads; PR 43: those two,
[1, 4096, 32, 128] and the last under `--block 4` at both offsets).
`--rows` limits the tile rows swept, `--grain` gives the rows a side of
the sub-tiles a crossed tile is visited in (pallas_attention._GRAIN is
written from it, PR 63: a grain of 512 is the whole-tile walk of before;
each reading of a masked kernel carries `walked_over_live`, the pairs it
computes over the pairs the mask keeps, from
pallas_attention.edge_subtiles), `--major` sets the rows of the
walked side that stay in VMEM at once (pallas_attention._MAJOR) for the
per-kernel sweep. A kernel alone is timed as sixteen calls chained in one
executable (each call's offset waits on the one before; the kernels are
told the offsets as plain ints beside it, as the attention ops tell
them). The backward is timed in both forms: `fused` (one
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

`--kv-heads N` (PR 61) gives K and V N heads under Q's H and times the
three forms of grouped-query attention at the tiles the kernels have,
in place of the tile sweep: `repeated` (the form of before: K/V widened
to H heads, the equal-heads kernels, dK/dV summed over each group in
float32, all inside the timed jit), `kernel` (K/V read at their own
heads, a group's dK/dV summed inside the fused `flash_dkv`) and
`kernel_per_head` (K/V read at their own heads, dK/dV written a query
head and summed behind the call): the forward, the backward from the
saved (Out, LSE) as the gradient op runs it, both in one jit, and
`flash_dkv` alone (no repeat and no sum around it), with each form's
largest error against dense float32 attention, a query head at a time.
"""

import argparse
import functools
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

from paddle_tpu.ops import nn_ops
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.parallel.ring_attention import attention_reference

ROWS = (128, 256, 512, 1024)
OUT = "chiprun_out/flash_sweep.jsonl"
CHAIN = 16


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


def chained(fn, q_off):
    """ms a call of fn(q_off, *args), `CHAIN` calls in one executable:
    each call's query offset is the given one plus a zero that hangs on
    the call before."""
    def run(*args):
        def body(_, seen):
            out = fn(q_off + (seen > 1e30).astype(jnp.int32), *args)
            return jax.tree.leaves(out)[0].ravel()[0].astype(jnp.float32)
        return jax.lax.fori_loop(0, CHAIN, body, jnp.float32(0))
    return lambda *args: bench(run, *args, iters=4) / CHAIN


def walked_over_live(kernel, ns, tile, grain):
    """Pairs a masked kernel computes over pairs the mask keeps."""
    t = ns.shape[1]
    walk = pa.edge_subtiles(
        {"fwd": "flash_fwd", "dq": "flash_dq"}.get(kernel, "flash_dkv"), t,
        t, tile, grain, ns.major, ns.block, ns.window, (ns.q_off, 0))
    return round(walk["walked_pairs"] / walk["live_pairs"], 4)


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


def dense_head(scale, block, q_off, window):
    """Dense float32 attention of ONE query head under the sweep's mask,
    [T, D] operands: (out, dq, dk, dv). A row that sees no key (the first
    block under q_off = -block) gives zeros and sends nothing back."""
    @jax.jit
    def run(q, k, v, do):
        q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
        qp = jnp.arange(q.shape[0])[:, None] + q_off
        kp = jnp.arange(k.shape[0])[None, :]
        keep = qp // block >= kp // block
        if window:
            keep &= qp - kp < window
        seen = keep.any(-1, keepdims=True)

        def attend(q, k, v):
            p = jax.nn.softmax(jnp.where(keep, q @ k.T * scale, -1e30), -1)
            return jnp.where(seen, p @ v, 0.0)

        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(do)
    return run


def grouped(ns, log, base, operands):
    """The three forms of grouped-query attention at one shape (the
    module docstring's `--kv-heads`)."""
    b, t, h, d = ns.shape
    groups = h // ns.kv_heads
    scale = 1.0 / d ** 0.5
    q, do = operands(h)[:2]
    k, v = operands(ns.kv_heads)[:2]
    mask = dict(major=ns.major, block=ns.block, window=ns.window,
                grain=ns.grains[0], at=(ns.q_off, 0))

    def forward(q, k, v, q_off=ns.q_off):
        """(Out, LSE) as the op's forward (block 1) or as one part of
        block-diffusion attention, whose raw (acc, m, l) the op merges."""
        out, stats = pa._fwd_call(q, k, v, q_off, 0, scale, True,
                                  normalize=ns.block == 1, tile=pa._TILE,
                                  **mask)
        if ns.block == 1:
            return out, stats[0]
        m, l = stats
        l, seen = jnp.maximum(l, 1e-30), m > -1e29
        out = jnp.where(seen.transpose(0, 2, 1)[..., None],
                        out / l.transpose(0, 2, 1)[..., None], 0.0)
        return out.astype(q.dtype), jnp.where(seen, m + jnp.log(l), 1e30)

    def backward(q, k, v, do, out, lse, q_off=ns.q_off, **form):
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1)
        return pa._bwd_call(q, k, v, do, lse, delta, q_off, 0, scale,
                            True, **form, **mask)

    # the repeated form is the attention op's own, with its helpers
    widen = functools.partial(nn_ops._repeat_kv, groups=groups)
    summed = functools.partial(nn_ops._sum_kv_groups, groups=groups)

    def repeated_fwd(q, k, v, **at):
        return forward(q, widen(k), widen(v), **at)

    def repeated_bwd(q, k, v, do, out, lse, **at):
        dq, dk, dv = backward(q, widen(k), widen(v), do, out, lse, **at)
        return dq, summed(dk), summed(dv)

    forms = {
        "repeated": (repeated_fwd, repeated_bwd,
                     lambda *a, **at: backward(*a, **at)[1:],
                     (widen(k), widen(v))),
        "kernel": (forward, backward,
                   lambda *a, **at: backward(*a, **at)[1:], (k, v)),
        "kernel_per_head": (
            forward, functools.partial(backward, summed=False),
            lambda *a, **at: backward(*a, summed=False, **at)[1:], (k, v)),
    }
    dense = dense_head(scale, ns.block, ns.q_off, ns.window)
    want = [jnp.stack([jnp.stack(x, 1) for x in zip(*(
        dense(q[i, :, j], k[i, :, j // groups], v[i, :, j // groups],
              do[i, :, j]) for j in range(h)))]) for i in range(b)]
    want = [jnp.stack([w[n] for w in want]) for n in range(4)]
    want[2:] = [x.reshape(b, t, ns.kv_heads, groups, d).sum(3)
                for x in want[2:]]
    out, lse = jax.jit(forward)(q, k, v)
    reason = pa._split_reason(t, t, pa._lane_block(h, d)[0],
                              q.dtype.itemsize, pa._TILE, ns.major,
                              groups=groups)
    for name in filter(None, ns.forms.split(",")):
        fwd, bwd, dkv, kv = forms[name]

        def both(q_off, q, k, v, do):
            out, lse = fwd(q, k, v, q_off=q_off)
            return (out,) + tuple(bwd(q, k, v, do, out, lse, q_off=q_off))

        def at(fn):
            return lambda q_off, *a: fn(*a, q_off=q_off)

        got = jax.jit(functools.partial(both, ns.q_off))(q, k, v, do)
        report(log, **base, kv_heads=ns.kv_heads, path="flash", form=name,
               backward="split" if reason else "fused", grain=ns.grains[0],
               walked_over_live=walked_over_live("fwd", ns, pa._TILE,
                                                 ns.grains[0]),
               fwd_ms=chained(at(fwd), ns.q_off)(q, k, v),
               bwd_ms=chained(at(bwd), ns.q_off)(q, k, v, do, out, lse),
               fwd_bwd_ms=chained(both, ns.q_off)(q, k, v, do),
               dkv_alone_ms=chained(at(dkv), ns.q_off)(q, *kv, do, out, lse),
               max_abs_err_vs_dense=dict(zip(
                   ("out", "dq", "dk", "dv"),
                   (max_err([g], [w]) for g, w in zip(got, want)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="*", type=int, default=[16, 1024, 12, 64])
    ap.add_argument("--parent")
    ap.add_argument("--kernels", default="fwd,dq,dkv,fused",
                    help="kernels to sweep tiles of ('' for none)")
    ap.add_argument("--bundled", type=int, default=1)
    ap.add_argument("--einsum", type=int, default=1,
                    help="0: leave out einsum, whose [B, H, T, T] float32 "
                         "scores pass the chip at 48 heads of 8192 rows")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--grain", default=str(pa._GRAIN),
                    help="rows a side of a crossed tile's sub-tiles, "
                         "each swept (--kv-heads: the first alone)")
    ap.add_argument("--major", type=int, default=pa._MAJOR)
    ap.add_argument("--block", type=int, default=1)
    ap.add_argument("--q-off", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--forms", default="repeated,kernel,kernel_per_head",
                    help="the forms --kv-heads times")
    ns = ap.parse_args()
    ns.grains = [int(g) for g in ns.grain.split(",")]
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
    if ns.kv_heads:
        return grouped(ns, log, base, operands)

    def einsum(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, True)

    scale = 1.0 / d ** 0.5
    if plain:
        # the kernels at the tiles pallas_attention has
        got = jax.jit(fwd_bwd(flash))(q, k, v, do)
        row = dict(fwd_ms=bench(flash, q, k, v),
                   fwd_bwd_ms=bench(fwd_bwd(flash), q, k, v, do))
        if ns.einsum:
            report(log, **base, path="einsum",
                   fwd_ms=bench(einsum, q, k, v),
                   fwd_bwd_ms=bench(fwd_bwd(einsum), q, k, v, do))
            row.update(max_abs_err_vs_einsum=max_err(
                got, jax.jit(fwd_bwd(einsum))(q, k, v, do)))
        report(log, **base, path="flash", tiles=list(pa._TILE), **row)
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
    mask = dict(major=ns.major, block=ns.block, window=ns.window,
                at=(ns.q_off, 0))

    def bwd(fused, **tiles):
        return lambda q_off, *a: pa._bwd_call(*a, q_off, 0, scale, True,
                                              fused=fused, **tiles, **mask)

    # the wrappers take the tiles and the grain as static arguments: one
    # jit entry a form, nothing to clear between them
    kernels = {
        "fwd": (lambda **form: lambda q_off, q, k, v: pa._fwd_call(
            q, k, v, q_off, 0, scale, True, normalize=plain, **form,
            **mask)[0],
            "tile", (q, k, v)),
        # the split form's two calls, each alone (XLA drops the other),
        # and the fused form's one
        "dq": (lambda **form: lambda *a: bwd(False, **form)(*a)[0],
               "dq_tile", (q, k, v, do, lse, delta)),
        "dkv": (lambda **form: lambda *a: bwd(False, **form)(*a)[1:],
                "dkv_tile", (q, k, v, do, lse, delta)),
        "fused": (lambda **form: bwd(True, **form),
                  "dkv_tile", (q, k, v, do, lse, delta)),
    }
    split = jax.jit(functools.partial(bwd(False), ns.q_off))(
        q, k, v, do, lse, delta)
    report(log, **base, path="flash", tiles=list(pa._TILE),
           fused_max_abs_err_vs_split=max_err(
               jax.jit(functools.partial(bwd(True), ns.q_off))(
                   q, k, v, do, lse, delta), split),
           split_reason=pa._split_reason(
               t, t, pa._lane_block(h, d)[0], q.dtype.itemsize, pa._TILE,
               ns.major))
    rows = [r for r in map(int, ns.rows.split(",")) if t % r == 0]
    for kernel in filter(None, ns.kernels.split(",")):
        at, tiled, args = kernels[kernel]
        timed = set()
        for tile, grain in itertools.product(
                itertools.product(rows, rows), ns.grains):
            grain = min(grain, max(tile))       # past it: the whole tile
            if (tile, grain) in timed:
                continue
            timed.add((tile, grain))
            try:
                ms = chained(at(grain=grain, **{tiled: tile}),
                             ns.q_off)(*args)
            except Exception as e:      # a tile Mosaic refuses
                ms = None
                print(kernel, tile, "refused:", str(e)[:200], flush=True)
            report(log, **base, path="flash", kernel=kernel,
                   tile=list(tile), grain=grain, ms=ms,
                   walked_over_live=walked_over_live(kernel, ns, tile,
                                                     grain))

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
