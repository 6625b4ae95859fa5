"""The op causal_conv1d alone on the chip, from the projection's rows to
the activation's: XLA's statement (hybrid_ops.causal_conv1d_reference and
autodiff's gradient of it) beside the kernels of ops/pallas_conv1d.py in
both orientations, over the blocks and chunks a grid step walks, which is
what pallas_conv1d._TILES is written from.

    chiprun -- python3 tools/conv1d_sweep.py [--cells kimi,granite,hybrid]
                        [--forms 512x512/64x128,...] [--lane-forms ...]
                        [--dtype bfloat16] [--errors 1]

Times the forward and forward + gradient (X, Filter, Bias from a random
cotangent) at the three cells' shapes ([1, 8192, 4096] K 4 no bias;
[1, 8192, 4352] and [1, 4096, 6144] K 4 with bias) with X and the
cotangent in `--dtype` as the projections write them; `--cells lfm2` is
the gated form at LFM2's [1, 16384, 2048], K 3, no bias, no activation,
a gate ahead of the taps and a gate behind them (three operands read,
their three gradients written). A form is
`time x channels / time x channels` of a block and of a chunk; with time
on the lanes the kernels are handed [B, C, T] (the swapaxes around them
cancel against the sweep's own, as they are bitcasts in a Mamba cell's
step: the op alone on row-major rows would pay two transposes a call
that the cell does not). With `--errors 1` the largest difference of Out,
dX, dFilter and dBias from the statement on the same operands, over the
largest entry. Sixteen calls are chained in one executable (a call alone
is host dispatch on that machine, as tools/pair_sum_sweep.py found). One
JSON line per reading goes to chiprun_out/conv1d_sweep.jsonl; chipless
(`JAX_PLATFORMS=cpu`) give a tiny shape, which the kernels take
interpreted: `--shape 1 384 256 4 1 --forms 128x128/64x128 --lane-forms
128x128/128x16` (`--gated identity`: that shape in the gated form).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import hybrid_ops, pallas_attention, pallas_conv1d
from tools.flash_sweep import bench, report

OUT = "chiprun_out/conv1d_sweep.jsonl"
CHAINED = 16
# B, T, C, K, Bias
CELLS = {"kimi": (1, 8192, 4096, 4, 0), "granite": (1, 8192, 4352, 4, 1),
         "hybrid": (1, 4096, 6144, 4, 1), "lfm2": (1, 16384, 2048, 3, 0)}
# the cells whose op is the gated form: its activation (both gates)
GATED = {"lfm2": "identity"}


def inputs(bsz, t, c, k, bias, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((bsz, t, c)), dtype),
            jnp.asarray(rng.standard_normal((c, k)) * k ** -0.5, jnp.float32),
            jnp.asarray(0.1 * rng.standard_normal(c), jnp.float32)
            if bias else None,
            jnp.asarray(rng.standard_normal((bsz, t, c)), dtype))


def both_of(fwd, bwd):
    """(Out, dX, dFilter[, dBias][, dPreGate, dPostGate]) of one call."""
    def run(x, w, b, do):
        return (fwd(x, w, b),) + tuple(
            g for g in bwd(x, w, b, do) if g is not None)
    return run


def gated_form(shape, activation, dtype, seed=1):
    """The gated form's keywords at `shape`: both gates, seeded."""
    rng = np.random.default_rng(seed)
    return dict(activation=activation, **{
        side: jnp.asarray(rng.standard_normal(shape[:3]), dtype)
        for side in ("pre_gate", "post_gate")})


def statement_of(form):
    """(forward, gradient) of the statement under the gated form's
    keywords (none: the plain op), the gradients in the kernels' order."""
    gates = {k: v for k, v in form.items() if k != "activation"}
    rest = {k: v for k, v in form.items() if k == "activation"}

    def fwd(x, w, b):
        return hybrid_ops.causal_conv1d_reference(x, w, b, **form)

    def bwd(x, w, b, do):
        primals = dict(x=x, w=w, **gates, **({} if b is None else {"bias": b}))
        grads, = jax.vjp(lambda kw: hybrid_ops.causal_conv1d_reference(
            **kw, **rest), primals)[1](do)
        return tuple(grads.get(key) for key in (
            "x", "w", "bias", "pre_gate", "post_gate"))
    return fwd, bwd


def chained(fwd, bwd, lanes):
    """CHAINED calls in one executable, each reading the last one's
    result as its X so that none is dropped or merged; with `lanes` the
    chain carries [B, C, T]."""
    def turned(a):
        return jnp.swapaxes(a, 1, 2) if lanes else a

    def run(x, w, b, do):
        def once(x_, _):
            y = fwd(turned(x_), w, b)
            if bwd is None:
                return turned(y), y.astype(jnp.float32).sum()
            grads = [g for g in bwd(turned(x_), w, b, do) if g is not None]
            return turned((y + grads[0]).astype(x_.dtype)), sum(
                g.astype(jnp.float32).sum() for g in grads[1:])
        return jax.lax.scan(once, turned(x), None, length=CHAINED)
    return run


def parsed(forms):
    """'512x512/64x128,...' -> [((512, 512), (64, 128)), ...]"""
    return [tuple(tuple(map(int, part.split("x"))) for part in f.split("/"))
            for f in forms.split(",") if f]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="kimi,granite,hybrid")
    ap.add_argument("--shape", nargs=5, type=int, default=None,
                    metavar=("B", "T", "C", "K", "BIAS"))
    ap.add_argument("--forms", default="512x512/64x128")
    ap.add_argument("--lane-forms", default="512x512/512x16")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--errors", type=int, default=1)
    ap.add_argument("--gated", default=None, metavar="ACTIVATION",
                    help="--shape in the gated form: silu or identity")
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)
    interpret = pallas_attention._interpret()
    shapes = {"shape": tuple(args.shape)} if args.shape else {
        name: CELLS[name] for name in args.cells.split(",")}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as log:
        for cell, shape in shapes.items():
            operands = inputs(*shape, dtype)
            activation = GATED.get(cell, args.gated)
            form = gated_form(shape, activation, dtype) if activation else {}
            statement = statement_of(form)
            forms = [(dict(path="statement"), False) + statement]
            for lanes, given in ((False, args.forms), (True, args.lane_forms)):
                for tile, chunk in parsed(given):
                    kw = dict(lanes=lanes, tile=tile, chunk=chunk,
                              interpret=interpret, **form)
                    forms.append((
                        dict(path="kernel", time_on="lanes" if lanes else
                             "sublanes", tile=tile, chunk=chunk), lanes,
                        lambda x, w, b, kw=kw:
                        pallas_conv1d.causal_conv1d_fwd(x, w, b, **kw),
                        lambda x, w, b, do, kw=kw:
                        pallas_conv1d.causal_conv1d_bwd(x, w, b, do, **kw)))
            want = jax.jit(both_of(*statement))(*operands) \
                if args.errors else None
            for labels, lanes, fwd, bwd in forms:
                row = dict(cell=cell, shape=shape, dtype=args.dtype,
                           device=jax.devices()[0].device_kind, **labels)
                try:
                    row["fwd_ms"] = bench(chained(fwd, None, lanes),
                                          *operands, iters=3) / CHAINED
                    row["fwd_bwd_ms"] = bench(chained(fwd, bwd, lanes),
                                              *operands, iters=3) / CHAINED
                    if want is not None and labels["path"] == "kernel":
                        got = jax.jit(both_of(fwd, bwd))(*operands)
                        row["rel_err"] = dict(zip(
                            ["out", "dx", "dfilter"] + ["dbias"] * shape[4]
                            + ["dpre", "dpost"] * bool(form),
                            (float(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)).max()
                                   / jnp.abs(b.astype(jnp.float32)).max())
                             for a, b in zip(got, want))))
                except Exception as e:      # a form Mosaic refuses
                    row["refused"] = str(e)[:300]
                report(log, **row)


if __name__ == "__main__":
    main()
