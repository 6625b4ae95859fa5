"""What a Pallas call says of its own work: the `cost_estimate` every
`pl.pallas_call` of the package passes, worked out in Python while the
call is traced from what its wrapper already holds (grid, BlockSpecs,
tiles, the live sub-tiles of a mask).

The declaration is the work AS IMPLEMENTED, not the work the model
requires (`roofline.op_cost` has that): MXU FLOPs of every product the
kernel issues, at the lanes it is handed and the passes its precision
costs; transcendentals; and the bytes its pipeline moves, each operand
block times the grid steps that fetch it plus each result once. Work on
the vector unit beside a product (decays, masks, softmax arithmetic) is
time and not floor and is left out, so max(FLOPs / peak, bytes /
bandwidth) of a declaration never exceeds what runs: a declared floor
over the measured time is a bug in the declaration. The compiled text
carries the three numbers in the custom call's `backend_config`, where
`xplane.hlo_instructions` reads them into `Instr.declared_*`; jax's own
megablox `gmm` / `tgmm` declare theirs the same way (every row of the
buffer: an upper bound, `declared_by` "jax").
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["array_bytes", "div", "estimate", "fetched_bytes", "fetches",
           "maximum", "passes"]

# MXU passes of one product by its operands: a float32 product at
# Precision.HIGHEST runs as 6 bf16 passes (xplane's `mxu_flops` rule); at
# default precision Mosaic rounds the operands and runs one.
_HIGHEST_F32 = 6


def passes(dtype, highest: bool = False) -> int:
    """bf16 passes of one product of `dtype` operands."""
    return _HIGHEST_F32 if highest and jnp.dtype(dtype).itemsize == 4 else 1


def maximum(x, y):
    """max on plain ints (an index map walked by `fetches`), jnp.maximum
    on the traced values a lowering hands the same map."""
    if isinstance(x, int) and isinstance(y, int):
        return max(x, y)
    return jnp.maximum(x, y)


def array_bytes(*structs) -> int:
    """Bytes of whole arrays (anything with shape and dtype)."""
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in structs)


def _walk(grid, index_map, prefetch):
    """Fetches over `grid` in the order the pipeline steps it: the last
    axis fastest, a block fetched where its index differs from the step
    before."""
    count, last = 0, None
    for ids in itertools.product(*(range(n) for n in grid)):
        at = tuple(int(i) for i in index_map(*ids, *prefetch))
        if at != last:
            count, last = count + 1, at
    return count


def fetches(grid, index_map, *prefetch) -> int:
    """How many grid steps fetch the block `index_map` names: Pallas'
    pipeline copies an operand's block in where its block index changes
    from one step to the next, and leaves it where it holds still (a
    clamped index of a dead tile, a resident operand). The map is called
    with plain ints (and `prefetch`, the scalar-prefetch operands as
    ints), so it must run on them; jax.numpy inside one is evaluated
    eagerly here, slowly. A leading axis longer than two is walked twice
    and extended: its steps repeat (a batch row's walk is every row's)."""
    grid = tuple(int(n) for n in grid)
    with jax.ensure_compile_time_eval():
        if len(grid) > 1 and grid[0] > 2:
            one = _walk((1,) + grid[1:], index_map, prefetch)
            two = _walk((2,) + grid[1:], index_map, prefetch)
            return one + (grid[0] - 1) * (two - one)
        return _walk(grid, index_map, prefetch)


def fetched_bytes(grid, specs, operands, *prefetch) -> int:
    """Bytes the pipeline reads for `operands` under their BlockSpecs:
    each block's bytes times `fetches` of its index map. An operand whose
    spec has no block (`memory_space=pl.ANY`: the kernel copies what it
    needs itself) counts nothing here."""
    total, seen = 0, {}
    for spec, operand in zip(specs, operands):
        shape = getattr(spec, "block_shape", None)
        if shape is None or spec.index_map is None:
            continue
        block = math.prod(1 if n is None else int(n) for n in shape) \
            * jnp.dtype(operand.dtype).itemsize
        if id(spec) not in seen:      # one spec serves several operands
            seen[id(spec)] = fetches(grid, spec.index_map, *prefetch)
        total += block * seen[id(spec)]
    return total


def estimate(flops=0, transcendentals=0, bytes_accessed=0):
    """The `cost_estimate` of a pallas_call, in whole numbers."""
    from jax.experimental import pallas as pl
    assert min(flops, transcendentals, bytes_accessed) >= 0, (
        flops, transcendentals, bytes_accessed)
    return pl.CostEstimate(flops=int(flops),
                           transcendentals=int(transcendentals),
                           bytes_accessed=int(bytes_accessed))


def div(x, n: int):
    """x // n on a plain int, lax.div on a traced grid index (as the
    index maps of grouped heads spell it)."""
    if isinstance(x, int):
        return x // n
    return lax.div(x, jnp.int32(n))
