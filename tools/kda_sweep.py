"""The op kda_scan alone on the chip, from the operands the mixer hands it
to its output: XLA's chunked form (hybrid_ops.kda_scan_chunked) over chunk
lengths, which is what a configuration's `kda_chunk_size` is written
from, beside the kernels of ops/pallas_kda.py over the heads one grid
step owns, which is what pallas_kda._HEADS is written from.

    chiprun -- python3 tools/kda_sweep.py [B T H K V] [--chunks 32,64,128]
                        [--path chunked,kernel,given_inverse]
                        [--heads-a-step 2,4,8]
                        [--dtype bfloat16] [--errors 1]
                        [--decay head --key-heads 16
                         --path chunked,kernel,broadcast]

Times the forward and forward + gradient (jax.vjp on a random cotangent)
of (q, k, v, gate, A_log, dt_bias, beta) -> o at one shape (default the
Kimi-Linear cell's, [1, 8192, 32, 128, 128]) for each path, chunk length
and (the kernels) heads a step, with q, k, v, the gate and beta in
`--dtype` as the projections write them, and decays drawn as the mixer's
initial A_log and dt_bias give them; with `--errors 1` the largest error
of the output and of the seven gradients against XLA's form with float32
operands at chunk 64, over the largest entry. Sixteen calls are chained
in one executable (a call alone is host dispatch on that machine, as
tools/pair_sum_sweep.py found). One JSON line per reading goes to
chiprun_out/kda_sweep.jsonl; chipless (`JAX_PLATFORMS=cpu`) give a tiny
shape, which the kernels take interpreted where the gate admits it: `1
256 2 16 16 --chunks 16,32 --path chunked`, `1 256 2 128 128 --chunks 64`.

`--decay head` gives the Gated DeltaNet form: the gate [B, T, H] and
dt_bias [H], and with `--key-heads N` q and k at N heads under v's H (the
Qwen3-Next cell: `1 16384 32 128 128 --decay head --key-heads 16`). The
path `kernel` is then the kernels' own form (`gdn_scan_fwd` / `_bwd`: one
exponent a row and head, q and k at their own head count, a key head's dq
and dk summed in the walk) and `broadcast` the channel form's kernels
behind a gate broadcast to [B, T, H, K] and q, k repeated to H heads, the
widening and its pull-back inside the timed call: what the op would run
had it kept one form (ISSUE 64 keeps the broadcast only if the direct
form is no faster).

The path `given_inverse` is the forward kernel a replayed op runs
(pallas_kda.kda_scan_forward handed `inverse=`): the forward alone, the
chunk inverses formed by a plain forward outside the timed call and read
where the plain kernel forms them (they hang on k, beta and the decays,
not on v, which the chain moves); beside it `forward` is the plain
forward through the same function, all three results written. No
gradient column: what the two differ by is what keeping the inverses
across a replayed segment saves a layer and step. On a v5e, bf16, chunk
64, 8 heads a step, `forward` | `given_inverse` ms (my chip run, PR 65,
call 1; the outputs equal to the last bit): the Kimi-Linear shape 5.68 |
2.05, the Qwen3-Next shape (`--decay head --key-heads 16`) 11.97 | 5.13;
inside the cells' steps the kernels read 5.16 | 1.52 and 10.24 | 3.40.

On a v5e at the default shape, bf16, forward | forward + gradient ms (my
chip runs, PR 56): XLA's form 10.35 | 27.79 at chunk 64 (its core alone,
kda_chunked on float32 unit operands, read 8.87 | 25.43 in PR 55); the
kernels 6.11 | 11.13 at 4 heads a step and 5.93 | 10.80 at 8; 16 are
refused for VMEM. The errors are in ops/pallas_kda.py's docstring.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import hybrid_ops, pallas_attention, pallas_kda
from tools.flash_sweep import bench, report

OUT = "chiprun_out/kda_sweep.jsonl"
CHAINED = 16
# the paths that time pallas_kda.kda_scan_forward, which has no gradient
FORWARD_ALONE = ("forward", "given_inverse")
SLOTS = ("q", "k", "v", "gate", "a_log", "dt_bias", "beta")


def inputs(bsz, t, h, k, v, dtype, seed=0, key_heads=None, per_head=False):
    """The op's seven operands and a cotangent for o: q, k, v ~ N(0, 1),
    the gate ~ N(0, 0.5) and beta ~ N(0, 1) in `dtype`; A in U(1, 16) a
    head and dt_bias the softplus' inverse of a step log-uniform in
    [0.001, 0.1] a channel, float32. `key_heads`: q's and k's heads
    (default h); `per_head`: the gate and dt_bias a head, not a channel."""
    rng = np.random.default_rng(seed)
    hk = key_heads or h
    decays = (h,) if per_head else (h, k)

    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), dtype)

    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), int(np.prod(decays))))
    return (normal(bsz, t, hk, k), normal(bsz, t, hk, k), normal(bsz, t, h, v),
            normal(bsz, t, *decays, scale=0.5),
            jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32),
            jnp.asarray(np.log(np.expm1(step)), jnp.float32),
            normal(bsz, t, h), normal(bsz, t, h, v))


def chained(fn, with_gradient):
    """CHAINED calls of `fn` in one executable, each reading the last
    one's output through v so that none is dropped or merged. `more`:
    operands behind the op's seven that `fn` takes and the chain leaves
    as they are (the inverses handed to the given-inverse forward)."""
    def run(q, k, v, gate, a_log, dt_bias, beta, do, *more):
        def once(v_, _):
            if with_gradient:
                out, vjp = jax.vjp(fn, q, k, v_, gate, a_log, dt_bias, beta)
                grads = vjp(do)
                return v_ + (1e-6 * grads[2]).astype(v_.dtype), sum(
                    x.astype(jnp.float32).sum() for x in (out,) + grads)
            out = fn(q, k, v_, gate, a_log, dt_bias, beta, *more)
            return v_ + (1e-6 * out).astype(v_.dtype), \
                out.astype(jnp.float32).sum()
        return jax.lax.scan(once, v, None, length=CHAINED)
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="*", type=int,
                    default=[1, 8192, 32, 128, 128])
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--path", default="chunked,kernel")
    ap.add_argument("--heads-a-step", default="2,4,8")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--errors", type=int, default=1)
    ap.add_argument("--decay", default="channel", choices=("channel", "head"))
    ap.add_argument("--key-heads", type=int, default=0)
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)
    per_head = args.decay == "head"
    heads, width = args.shape[2], args.shape[3]
    ratio = heads // (args.key_heads or heads)
    args_ = inputs(*args.shape, dtype, key_heads=args.key_heads,
                   per_head=per_head)
    eps = 1e-6

    def widened(fn):
        """`fn` on the channel form's operands, made here from a head's."""
        def run(q, k, v, gate, a_log, dt_bias, beta):
            q, k = (jnp.repeat(x, ratio, axis=2) for x in (q, k))
            gate = jnp.broadcast_to(gate[..., None], v.shape[:3] + (width,))
            return fn(q, k, v, gate, a_log, jnp.repeat(dt_bias, width), beta)
        return run
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def both(fn, operands):
        out, vjp = jax.vjp(fn, *operands[:7])
        return (out,) + vjp(operands[7].astype(out.dtype))

    forms = []
    for chunk in map(int, args.chunks.split(",")):
        for path in args.path.split(","):
            if path == "chunked":
                forms.append((dict(chunk=chunk, path=path), lambda *a, c=chunk:
                              hybrid_ops.kda_scan_chunked(*a, c, eps, dtype)))
                continue
            direct = path != "broadcast"
            reason = hybrid_ops.kda_scan_ineligible(
                chunk, *args.shape[3:], ratio if direct else 1,
                per_head and direct)
            if reason is not None or (path == "broadcast") > per_head:
                print(f"chunk {chunk}, {path}: the gate declines ({reason})")
                continue
            for r in map(int, args.heads_a_step.split(",")):
                if heads % r or (direct and r % ratio):
                    continue

                def kernels(*a, c=chunk, r=r):
                    return pallas_kda.kda_scan_kernels(
                        *a, c, eps, dtype=dtype, heads=r,
                        interpret=pallas_attention._interpret())
                def forward(*a, c=chunk, r=r):
                    """The plain forward, or handed the inverses behind
                    the op's seven operands the given-inverse one; all it
                    writes held behind a barrier, so that no result is
                    pruned."""
                    return jax.lax.optimization_barrier(
                        pallas_kda.kda_scan_forward(
                            *a[:7], c, eps, dtype=dtype, heads=r,
                            interpret=pallas_attention._interpret(),
                            inverse=a[7] if a[7:] else None))[0]
                forms.append((dict(chunk=chunk, path=path, heads_a_step=r),
                              forward if path in FORWARD_ALONE else
                              kernels if direct else widened(kernels)))
    with open(OUT, "a") as log:
        want = None
        if args.errors:
            full = tuple(x.astype(jnp.float32) for x in args_)
            want = jax.jit(lambda: both(
                lambda *a: hybrid_ops.kda_scan_chunked(*a, 64, eps), full))()
        for labels, fn in forms:
            row = dict(shape=args.shape, dtype=args.dtype, decay=args.decay,
                       key_heads=args.key_heads or heads, device=jax.devices()[0].device_kind, **labels)
            more = ()
            if labels["path"] == "given_inverse":
                plain, _, inverse = jax.jit(
                    lambda *a: pallas_kda.kda_scan_forward(
                        *a, labels["chunk"], eps, dtype=dtype,
                        heads=labels["heads_a_step"],
                        interpret=pallas_attention._interpret()))(*args_[:7])
                more = (inverse,)
                row["equals_forward"] = bool(jnp.array_equal(
                    plain, jax.jit(fn)(*args_[:7], inverse)))
            alone = labels["path"] in FORWARD_ALONE
            for name, with_gradient in [("fwd_ms", False)] + [
                    ("fwd_bwd_ms", True)] * (not alone):
                row[name] = bench(chained(fn, with_gradient), *args_, *more,
                                  iters=3) / CHAINED
            if want is not None and alone:
                got = jax.jit(fn)(*args_[:7], *more).astype(jnp.float32)
                row["rel_err"] = dict(out=float(
                    jnp.abs(got - want[0]).max() / jnp.abs(want[0]).max()))
            elif want is not None:
                got = jax.jit(lambda f=fn: both(f, args_))()
                row["rel_err"] = dict(zip(("out",) + SLOTS, (
                    float(jnp.abs(a.astype(jnp.float32) - b).max()
                          / jnp.abs(b).max()) for a, b in zip(got, want))))
            report(log, **row)


if __name__ == "__main__":
    main()
