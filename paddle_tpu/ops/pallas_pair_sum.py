"""The expert layer's token side as one Pallas TPU kernel: each token's
sum over its top_k pairs of their sorted rows, in one pass over the live
rows (hybrid_ops._sum_of_pairs states the map in plain jax.numpy, stays
the path for shapes the gate declines and is the tests' reference).

    Out[n] = sum_j (weight[n, j] *) rows[pos[n, j]]  over pos[n, j] < live_rows

What the op's sort gives: the pairs are numbered n * top_k + j and sorted
stably by held expert, so inside one expert's group the sorted rows
ascend by token, and the rows of expert e that belong to a TILE of
tokens are one contiguous window of `rows`. `windows` (pair_windows: a
compare-and-sum of the groups over [N / tile, held] and two cumulative
sums, once a layer) holds where each window starts and ends.

Grid over tiles of tokens, sequential. A grid step fetches, for each held
expert, `window` rows from the aligned row (a multiple of `window`: whole
packed tiles) at or before its window's start: `held` contiguous DMAs
into one [held x window, D] VMEM buffer, never one a pair, and nothing
for a dead pair. It then places the
buffer's rows on the tile's tokens with ONE product on the MXU,
[tile, held x window] x [held x window, D], whose left side is built on
the VPU from the tile's `pos` block: entry (t, c) is 1 (or the pair's
weight) where one of token t's top_k places is the row that buffer row c
holds, inside its own expert's window and under `live_rows`, else 0.
`pos` is a permutation, so a row has at most one taker and an entry at
most one pair. A window longer than what one fetch covers (a burst, or
every pair live) takes further rounds of the same, added in float32; the
output block [tile, D] is written once.

Precision: the rows enter the product in the dtype they have. A 0/1 left
side times bf16 rows accumulated in float32 is exact, so the unweighted
map (the gradient's) differs from _sum_of_pairs only in the order of a
token's at most top_k additions. The forward's float32 weight is split
into three bf16 pieces that sum to it exactly (hi + mid + lo), one
product each: every partial product is exact in float32 and their sum
lies within two ulp of the float32 multiply. float32 rows (no AMP) take
Precision.HIGHEST. Rows at or past `live_rows` are undefined (the
grouped product leaves them so): a fetch that crosses that row zeroes
its tail in VMEM, select and never multiply, and a buffer slot no fetch
has touched is zero from the first step. A non-finite LIVE row reaches
every token of its tile (0 x inf), where _sum_of_pairs gave it to its
own token only; such a step has failed either way.

The fetches of a round fly while the round before it is multiplied: two
buffers, and a tile's last round starts the next tile's first (the grid
is sequential), which took the map from 0.26-0.51 ms to 0.17-0.34 at
the two larger shapes below. The loops over the held experts and over
the rounds are lax.fori_loops inside the kernel: unrolled in Python the
map ran a fifth faster (0.34, 0.20 and 0.30, 0.23 at those shapes, call
98) but each distinct kernel took 0.65 s to trace here, three times that
on the chip's host, and a cell lowers two of them twice at set-up.

Tile and window, from the map alone on a v5e (tools/pair_sum_sweep.py,
my chip run, PR 47, call 103, this kernel: ms a call on the device,
sixteen calls chained in one executable; forward: the weighted map from
bf16 rows to float32, back: the unweighted one from bf16 rows to bf16;
N, top_k, D, C and the live pairs are the four expert cells'):
  N, top_k, D, C, live         XLA's    128x16    256x16    512x16    256x32
  8192, 8, 2048, 16384,  4274  .94 .88   .42 .25   .44 .24   .55 .27   .51 .25
  8192, 6, 2560, 49152,  6061 2.82 2.74  .36 .27   .45 .30   .64 .37   .48 .29
  4096, 6, 2688,  6144,   792  .42 .38   .23 .14   .23 .13   .23 .12   .30 .15
  4096, 6, 2688,  6144,  5819  .42 .38   .33 .20   .40 .22   .58 .29   .43 .21
  4096, 4, 2048, 16384,  3356  .20 .17   .21 .13   .24 .13   .32 .16   .25 .13
  4096, 4, 2048, 16384, 15407  .20 .17   .42 .25   .60 .32   .95 .45   .52 .25
(ms a call; each pair: forward, back; columns: tile x window.) A window
of 16 rows is one packed bf16 tile and makes the product's left side 128
lanes at 8 held experts, one pass of the MXU's depth; 32 doubles the
product for fewer rounds and wins only where nearly every pair is live
(128 x 32 there: .37 .21). Tiles of 128 tokens win forward, where a round
is three products, and are level pulled back. With a quarter of the
tokens sent to one expert en bloc (the block-diffusion cell's mask
token; 5454 live; unrolled form): 0.49, 0.27 at 128 x 16. With nearly
every pair live (last row) the gathers move at their bytes and the
kernel loses alone; but inside a step XLA's form costs two to four times
what it costs alone (a gather a slot writes [N, D] out in float32
there: PERF.md section 6, PR 47), which is what decides. pair_windows:
0.014 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel_cost

__all__ = ["ineligible", "pair_sum", "pair_windows"]

# tokens a grid step, rows of one expert's window a fetch (the table above)
_TILE = 128
_WINDOW = 16
# widest left side of the placing product: held x window lanes
_MAX_LANES = 512
# scoped VMEM asked for: the output block twice, the buffer and the
# float32 partial products of one round (16.6 MB at [256, 2688], over the
# 16 MB a call gets unasked)
_VMEM_LIMIT = 48 * 1024 * 1024


def ineligible(tokens: int, rows: int, d: int, held: int,
               tile: int = _TILE, window: int = _WINDOW):
    """None when the kernel takes [rows, d] -> [tokens, d] over `held`
    experts' windows, else the reason hybrid_ops._sum_of_pairs keeps the
    map (kernel_choice.REASONS["pair_sum"]): a row is whole 128-lane
    blocks (`width`), the tokens whole tiles (`tokens`), the rows whole
    aligned fetches (`rows`), and the placing product's left side
    [tile, held x window] at most _MAX_LANES wide (`experts`)."""
    if d % 128:
        return "width"
    if tokens % tile:
        return "tokens"
    if rows % window:
        return "rows"
    if held * window > _MAX_LANES:
        return "experts"
    return None


def pair_windows(group, held: int, top_k: int, tile: int = _TILE):
    """[2, N / tile, held] int32: where in the sorted order the rows of
    held expert e that belong to tile i of the tokens start, and where
    they end. `group` [N x top_k]: each pair's held expert, `held` for an
    absent one, pairs numbered token-major (what moe_experts sorts by)."""
    per_tile = group.reshape(-1, tile * top_k, 1) == jnp.arange(held)
    counts = per_tile.sum(1, dtype=jnp.int32)               # [tiles, held]
    sizes = counts.sum(0)
    start = (jnp.cumsum(sizes) - sizes) + (jnp.cumsum(counts, 0) - counts)
    return jnp.stack([start, start + counts])


def _lanes(held, window):
    return -(-held * window // 128) * 128


def _kernel(first_ref, rounds_ref, live_ref, table_ref, pos_ref, *refs,
            held, window, weighted, exact_f32):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    weight_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, buf, sem, step_ref, *own = refs
    acc = own[0] if own else out_ref     # float32 scratch under a bf16 output
    i, tiles = pl.program_id(0), pl.num_programs(0)
    live = live_ref[0]
    pos = pos_ref[...]
    weight = weight_ref[...] if weighted else None
    base, lo, hi = (table_ref[0, r:r + 1, :] for r in range(3))

    # (the loops over the held experts and over the rounds are loops in
    # the kernel too, not unrolled: each distinct kernel is traced and
    # lowered at a cell's set-up, and unrolled that took 0.65 s a kernel
    # here and three times that on the chip's host)
    def each_expert(do):
        lax.fori_loop(0, held, lambda e, _: do(e), None)

    def fetch(tile, e, c, half):
        at = pl.multiple_of(first_ref[tile * held + e] + c * window, window)
        slot = buf.at[half, pl.ds(pl.multiple_of(e * window, window), window)]
        return at, slot, pltpu.make_async_copy(
            rows_ref.at[pl.ds(at, window)], slot, sem.at[half, e])

    def start(tile, c, half):
        def one(e):
            @pl.when(c < rounds_ref[tile * held + e])
            def _():
                fetch(tile, e, c, half)[2].start()
        each_expert(one)

    @pl.when(i == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        step_ref[0] = 0
        start(0, 0, 0)

    rounds = lax.fori_loop(
        0, held, lambda e, most: jnp.maximum(most, rounds_ref[i * held + e]),
        jnp.int32(0))

    def one_round(c, _):
        half = step_ref[0] % 2
        step_ref[0] += 1

        def land(e):
            @pl.when(c < rounds_ref[i * held + e])
            def _():
                at, slot, copy = fetch(i, e, c, half)
                copy.wait()

                @pl.when(at + window > live)
                def _():
                    row = at + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
                    slot[...] = jnp.where(row < live, slot[...], 0)
        each_expert(land)

        # the next round's rows, or the next tile's first, fly meanwhile
        more = c + 1 < rounds

        @pl.when(more | (i + 1 < tiles))
        def _():
            start(jnp.where(more, i, i + 1), jnp.where(more, c + 1, 0),
                  1 - half)

        at = base + c * window
        key = jnp.where((at >= lo) & (at < hi), at, -1)      # [1, lanes]
        left = jnp.zeros((pos.shape[0], key.shape[1]), jnp.float32)
        for j in range(pos.shape[1]):
            left = jnp.where(pos[:, j:j + 1] == key,
                             weight[:, j:j + 1] if weighted else 1.0, left)
        data = buf[half]

        def dot(a, **kw):
            return jnp.dot(a, data, preferred_element_type=jnp.float32, **kw)

        if exact_f32:
            part = dot(left, precision=lax.Precision.HIGHEST)
        elif weighted:
            high = left.astype(jnp.bfloat16)
            rest = left - high.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            part = dot(high) + dot(mid) + dot(low)
        else:
            part = dot(left.astype(data.dtype))

        @pl.when(c == 0)
        def _():
            acc[...] = part

        @pl.when(c > 0)
        def _():
            acc[...] += part

    # the first round always runs: a tile with no live pair writes zeros
    lax.fori_loop(0, jnp.maximum(rounds, 1), one_round, None)
    if acc is not out_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _call(c, n, k, d, held, dtype, weighted, out_dtype, tile, window,
          interpret):
    """One traced kernel a (C, N, top_k, D, held, dtype, weighted, ...):
    the ladder's branches, a model's layers and the gradient's re-trace
    of a forward lower the same jitted function."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = _lanes(held, window)
    f32_out = jnp.dtype(out_dtype) == jnp.float32
    block = pl.BlockSpec((tile, k), lambda i, *_: (i, 0))
    in_specs = [pl.BlockSpec((1, 3, lanes), lambda i, *_: (i, 0, 0)), block]
    if weighted:
        in_specs.append(block)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    scratch = [pltpu.VMEM((2, lanes, d), dtype),
               pltpu.SemaphoreType.DMA((2, held)), pltpu.SMEM((1,), jnp.int32)]
    if not f32_out:
        scratch.append(pltpu.VMEM((tile, d), jnp.float32))
    kernel = functools.partial(
        _kernel, held=held, window=window, weighted=weighted,
        exact_f32=jnp.dtype(dtype).itemsize == 4)
    # What the call declares (ops/kernel_cost.py) is what EVERY run of it
    # does, since the rounds a tile takes are the routing's and no trace
    # knows them: one round a tile (the first always runs), its placing
    # product [tile, lanes] by [lanes, d] at the passes its form costs (a
    # float32 one 6, a weighted bf16 one three products), the tables, the
    # positions and the weights read once and the result written once.
    # The rows themselves, fetched by the kernel's own copies a window at
    # a time, and every round past a tile's first are work the
    # declaration leaves out: its floor stands under what runs.
    exact = kernel_cost.passes(dtype, highest=True)
    rounds = exact if exact > 1 else 3 if weighted else 1
    index = jax.ShapeDtypeStruct((n, k), jnp.int32)
    cost = kernel_cost.estimate(
        n // tile * rounds * 2 * tile * lanes * d, 0,
        kernel_cost.array_bytes(
            jax.ShapeDtypeStruct((n // tile, 3, lanes), jnp.int32),
            *[index] * (1 + weighted),
            jax.ShapeDtypeStruct((n, d), out_dtype)))
    call = pl.pallas_call(
        kernel, name="pair_sum", cost_estimate=cost,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tile,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT))
    return jax.jit(call)


def pair_sum(rows, pos, live_rows, windows, weight=None, *,
             out_dtype=jnp.float32, tile=_TILE, window=_WINDOW,
             interpret=False):
    """hybrid_ops._sum_of_pairs(rows, pos, live_rows, weight) on the
    kernel, accumulated in float32 and written as `out_dtype`. `windows`
    = pair_windows(group, held, top_k, tile) of the sort that `pos`
    inverts. The tables below are a few integer ops over
    [N / tile, held] and [N / tile, held x window]."""
    (c, d), (n, k) = rows.shape, pos.shape
    held = windows.shape[-1]
    assert ineligible(n, c, d, held, tile, window) is None
    live = jnp.minimum(jnp.asarray(live_rows, jnp.int32), c)
    lo, hi = jnp.minimum(windows, live)
    first = lo // window * window
    rounds = jnp.where(hi > lo, -(-(hi - first) // window), 0)
    lanes = _lanes(held, window)
    slot, row = jnp.divmod(jnp.arange(lanes), window)
    real = slot < held
    slot = jnp.minimum(slot, held - 1)
    table = jnp.stack([jnp.where(real, first[:, slot] + row, -1),
                       jnp.where(real, lo[:, slot], 0),
                       jnp.where(real, hi[:, slot], 0)], axis=1)
    operands = (pos,) if weight is None else (pos, weight)
    return _call(c, n, k, d, held, jnp.dtype(rows.dtype), weight is not None,
                 jnp.dtype(out_dtype), tile, window, interpret)(
        first.reshape(-1), rounds.reshape(-1), live.reshape(1),
        table.astype(jnp.int32), *operands, rows)
