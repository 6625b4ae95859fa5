"""The gated delta rule alone on the chip: hybrid_ops.kda_chunked over
chunk lengths, which is what a configuration's `kda_chunk_size` is
written from.

    chiprun -- python3 tools/kda_sweep.py [B T H K V] [--chunks 32,64,128]
                        [--dtype bfloat16] [--errors 1]

Times the forward and forward + gradient (jax.vjp on a random cotangent)
of (q, k, v, g, beta) -> o at one shape (default the Kimi-Linear cell's,
[1, 8192, 32, 128, 128]) for each chunk length, with unit q and k and
decays drawn as the mixer's initial A_log and dt_bias give them, and
with `--errors 1` the largest error of the output and of the five
gradients against the same function with float32 operands at chunk 64,
over the largest entry. Sixteen calls are chained in one executable (a
call alone is host dispatch on that machine, as tools/pair_sum_sweep.py
found). One JSON line per reading goes to chiprun_out/kda_sweep.jsonl;
chipless (`JAX_PLATFORMS=cpu`) give a tiny shape: `1 256 2 16 16
--chunks 16,32`.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.hybrid_ops import kda_chunked
from tools.flash_sweep import bench, report

OUT = "chiprun_out/kda_sweep.jsonl"
CHAINED = 16


def inputs(bsz, t, h, k, v, seed=0):
    """Unit q and k, v ~ N(0, 1), g = -A softplus(N(0, 0.5) + dt_bias)
    with A in U(1, 16) a head and the step log-uniform in [0.001, 0.1] a
    channel, beta in (0, 1), and a cotangent for o."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    q, key = (x / np.linalg.norm(x, axis=-1, keepdims=True)
              for x in (normal(bsz, t, h, k), normal(bsz, t, h, k)))
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (h, k)))
    raw = 0.5 * normal(bsz, t, h, k) + np.log(np.expm1(step))
    g = -rng.uniform(1, 16, (h, 1)) * np.logaddexp(0.0, raw)
    beta = 1 / (1 + np.exp(-normal(bsz, t, h)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q, key, normal(bsz, t, h, v), g, beta, normal(bsz, t, h, v)))


def chained(fn, with_gradient):
    """CHAINED calls of `fn` in one executable, each reading the last
    one's output through v so that none is dropped or merged."""
    def run(q, k, v, g, beta, do):
        def once(v_, _):
            if with_gradient:
                out, vjp = jax.vjp(fn, q, k, v_, g, beta)
                grads = vjp(do)
                return v_ + 1e-6 * grads[2], out.sum() + sum(
                    x.sum() for x in grads)
            out = fn(q, k, v_, g, beta)
            return v_ + 1e-6 * out, out.sum()
        return jax.lax.scan(once, v, None, length=CHAINED)
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="*", type=int,
                    default=[1, 8192, 32, 128, 128])
    ap.add_argument("--chunks", default="32,64,128")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--errors", type=int, default=1)
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)
    args_ = inputs(*args.shape)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def both(fn):
        out, vjp = jax.vjp(fn, *args_[:5])
        return (out,) + vjp(args_[5])

    with open(OUT, "a") as log:
        want = jax.jit(lambda: both(
            lambda *a: kda_chunked(*a, 64)))() if args.errors else None
        for chunk in map(int, args.chunks.split(",")):
            fn = lambda *a, c=chunk: kda_chunked(*a, c, dtype=dtype)
            row = dict(shape=args.shape, chunk=chunk, dtype=args.dtype,
                       device=jax.devices()[0].device_kind)
            for name, with_gradient in (("fwd_ms", False),
                                        ("fwd_bwd_ms", True)):
                row[name] = bench(chained(fn, with_gradient), *args_,
                                  iters=3) / CHAINED
            if want is not None:
                got = jax.jit(lambda f=fn: both(f))()
                row["rel_err"] = [
                    float(jnp.abs(a - b).max() / jnp.abs(b).max())
                    for a, b in zip(got, want)]
            report(log, **row)


if __name__ == "__main__":
    main()
