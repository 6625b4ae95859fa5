"""The expert layer's token side alone: hybrid_ops._sum_of_pairs (top_k
gathers of [N, D] and an add, XLA's) beside the kernel of
ops/pallas_pair_sum.py over its tiles and windows.

    chiprun -- python3 tools/pair_sum_sweep.py [--cells NAME,...]
        [--forms 128x16,256x32,...] [--live N] [--burst SHARE]

At each cell's shape (N, top_k, D, C, live pairs) it times the two maps
the layer runs: `forward`, weighted, the grouped product's bf16 rows ->
float32 (_handle_rows), and `pulled_back`, unweighted, the bf16
cotangent's rows -> float32 -> bf16 (_pull_rows_back), and prints ms
(on the device: sixteen calls chained in one executable) and GB/s a
form, the bytes being the live rows read once and [N, D] written once;
and the largest difference from the XLA form. The routing
is drawn so that `live` pairs fall on 8 held experts, at most one a token
and expert; `--burst` sends that share of the tokens (drawn from the
first half, as the block-diffusion cell's mask token sits) to one held
expert together. Chipless (`JAX_PLATFORMS=cpu`) it compiles each form for
a described v5e and times nothing. `_TILE` and `_WINDOW` of
ops/pallas_pair_sum.py are written from its table (PERF.md section 6,
PR 47). One JSON line per reading goes to chiprun_out/pair_sum_sweep.jsonl.
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_pair_sum as ps
from paddle_tpu.ops.hybrid_ops import _sum_of_pairs
from paddle_tpu.ops.pallas_attention import _interpret
from jax import lax
from tools.flash_sweep import bench, report

OUT = "chiprun_out/pair_sum_sweep.jsonl"
HELD = 8
# cell -> (N, top_k, D, C, live pairs to draw): the rung the cell's steps
# take and the pairs its router sends (PERF.md section 5)
CELLS = {
    "sdar": (8192, 8, 2048, 16384, 4300),
    "smallthinker": (8192, 6, 2560, 49152, 6150),
    "nemotron-low": (4096, 6, 2688, 6144, 800),
    "nemotron-high": (4096, 6, 2688, 6144, 5800),
    "glm-low": (4096, 4, 2048, 16384, 3400),
    "glm-high": (4096, 4, 2048, 16384, 15400),
}
# tokens a grid step x rows of one expert's window a fetch
FORMS = ("128x16", "256x16", "512x16", "128x32", "256x32")


def parse(form):
    return tuple(map(int, form.split("x")))


def draw(rng, n, k, live, burst):
    """group [N x top_k]: each pair's held expert, HELD for an absent
    one; a token's held experts are distinct."""
    hits = np.minimum(rng.binomial(k, live / (n * k), n), HELD)
    experts = np.argsort(rng.random((n, HELD)), axis=1)[:, :k]
    if k > HELD:
        experts = np.pad(experts, ((0, 0), (0, k - HELD)))
    group = np.where(np.arange(k) < hits[:, None], experts, HELD)
    if burst:
        en_bloc = rng.permutation(n // 2)[:int(burst * n)]
        others = group[en_bloc, 1:]
        group[en_bloc, 1:] = np.where(others == 3, HELD, others)
        group[en_bloc, 0] = 3
    return jnp.asarray(group.reshape(-1), jnp.int32)


def maps(form, windows, interpret):
    """(forward, pulled_back) of one form: None is XLA's."""
    if form is None:
        def forward(rows, pos, live, weight):
            return _sum_of_pairs(rows, pos, live, weight)

        def pulled_back(rows, pos, live, weight):
            return _sum_of_pairs(rows, pos, live).astype(rows.dtype)
        return forward, pulled_back
    kernel = functools.partial(ps.pair_sum, tile=form[0], window=form[1],
                               interpret=interpret)

    def forward(rows, pos, live, weight):
        return kernel(rows, pos, live, windows, weight)

    def pulled_back(rows, pos, live, weight):
        return kernel(rows, pos, live, windows, out_dtype=rows.dtype)
    return forward, pulled_back


REPS = 16


def on_device(fn, rows, pos, live, weight):
    """ms a call of `fn` with the host out of it: REPS calls in one
    executable, each waiting for the one before through `live` (a call
    alone is 0.2 ms of dispatch here whatever it does)."""
    def chain(rows, pos, live, weight):
        def body(_, carry):
            seen, out = carry
            out = fn(rows, pos, live - (seen > 1e30).astype(live.dtype),
                     weight)
            return out[0, 0].astype(jnp.float32), out
        first = fn(rows, pos, live, weight)
        return lax.fori_loop(0, REPS, body, (jnp.float32(0), first))[1]
    return bench(chain, rows, pos, live, weight, iters=4) / (REPS + 1)


def describe(fn, args):
    """Compile `fn` for a described v5e: what Mosaic refuses shows here."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.jit(fn).lower(*(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
                        for a in args)).compile()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--live", type=int, default=0,
                    help="pairs to route to the held experts (the cell's)")
    ap.add_argument("--burst", type=float, default=0.0)
    ap.add_argument("--dtype", default="bfloat16")
    ns = ap.parse_args()
    forms = [None] + [f for f in ns.forms.split(",") if f]
    chipless = jax.default_backend() != "tpu"
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    log = open(OUT, "a")
    rng = np.random.default_rng(0)
    for cell in ns.cells.split(","):
        n, k, d, c, live_pairs = CELLS[cell]
        group = draw(rng, n, k, ns.live or live_pairs, ns.burst)
        order = jnp.argsort(group, stable=True)
        pos = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
        live = jnp.minimum((group < HELD).sum(), c).astype(jnp.int32)
        rows = rng.standard_normal((c, d), np.float32)
        rows[int(live):] = np.nan            # what the grouped product leaves
        args = (jnp.asarray(rows, ns.dtype), pos, live,
                jnp.asarray(rng.uniform(0.05, 0.5, (n, k)), jnp.float32))
        base = dict(cell=cell, n=n, top_k=k, d=d, c=c, live=int(live),
                    burst=ns.burst, dtype=ns.dtype,
                    device=jax.devices()[0].device_kind)
        item = jnp.dtype(ns.dtype).itemsize
        moved = {"forward": int(live) * d * item + n * d * 4,
                 "pulled_back": int(live) * d * item + n * d * item}
        want = {}
        for name in forms:
            form = name and parse(name)
            name = name or "xla"
            if form is not None:
                reason = ps.ineligible(n, c, d, HELD, *form)
                if reason:
                    report(log, **base, form=name, declined=reason)
                    continue
                windows = ps.pair_windows(group, HELD, k, form[0])
            else:
                windows = None
            for which, fn in zip(moved, maps(form, windows, _interpret())):
                if chipless:
                    describe(fn, args)
                    report(log, **base, form=name, map=which, compiled=True)
                    continue
                got = jax.jit(fn)(*args).astype(jnp.float32)
                want.setdefault(which, got)
                ms = on_device(fn, *args)
                report(log, **base, form=name, map=which, ms=round(ms, 4),
                       gb_per_s=round(moved[which] / ms / 1e6, 1),
                       max_diff=float(jnp.max(jnp.abs(got - want[which]))),
                       max_abs=float(jnp.max(jnp.abs(want[which]))))
        if not chipless:
            for tile in sorted({parse(f)[0] for f in forms if f}):
                def windows_of(_, pos, live, weight, tile=tile):
                    return ps.pair_windows(
                        jnp.where(live < 0, 0, group), HELD, k, tile)[0]
                report(log, **base, form=f"pair_windows tile {tile}",
                       ms=round(on_device(windows_of, *args), 4))


if __name__ == "__main__":
    main()
