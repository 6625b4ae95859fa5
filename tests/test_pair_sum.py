"""ops/pallas_pair_sum.py: the expert layer's token side as one kernel
(PR 47), interpreted on the CPU, against hybrid_ops._sum_of_pairs, the
map in plain jax.numpy that stays the path where the gate declines."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import telemetry
from paddle_tpu.ops import hybrid_ops, pallas_pair_sum

from test_nemotron_h import EXPERTS, HELD, close, experts_op

N, K, D, HELD_HERE = 512, 4, 128, 4
TILE, WINDOW = 128, 16


def sorted_pairs(group, n, k, held):
    """(pos, windows, routed) of moe_experts' sort of `group` [n x k]."""
    order = jnp.argsort(group, stable=True)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    return pos, pallas_pair_sum.pair_windows(group, held, k, TILE), \
        int((group < held).sum())


def draw(rng, live, n=N, k=K, held=HELD_HERE):
    """group [n x k] with exactly `live` pairs on held experts."""
    group = np.full(n * k, held, np.int32)
    here = rng.permutation(n * k)[:live]
    group[here] = rng.integers(0, held, live)
    return jnp.asarray(group)


def both(rows, pos, live, windows, weight, **kw):
    want = hybrid_ops._sum_of_pairs(rows, pos, live, weight)
    got = pallas_pair_sum.pair_sum(rows, pos, live, windows, weight,
                                   tile=TILE, window=WINDOW, interpret=True,
                                   **kw)
    return np.asarray(got, np.float32), np.asarray(want)


def some_rows(rng, c, live, dtype):
    """[c, D] rows, NaN from `live` on: what the grouped product leaves
    past the routed rows is undefined and must be selected away."""
    rows = rng.standard_normal((c, D)).astype(np.float32)
    rows[live:] = np.nan
    return jnp.asarray(rows, dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["pulled_back", "forward"])
@pytest.mark.parametrize("live", [0, 1, N * K // 16, N * K // 8, N * K],
                         ids=["none", "one_pair", "sixteenth", "eighth",
                              "all"])
def test_the_kernel_gives_what_the_gathers_give(live, weighted, dtype):
    """No pair live, one, a sixteenth, an eighth and every pair (rounds
    upon rounds of one tile's windows): each token's sum of its live
    pairs' rows, weighted or not, from bf16 or float32 rows. Unweighted
    bf16 rows are placed exactly and only the order of a token's
    additions may differ; a float32 weight is three bf16 pieces."""
    rng = np.random.default_rng(live + 7 * weighted)
    pos, windows, routed = sorted_pairs(draw(rng, live), N, K, HELD_HERE)
    assert routed == live
    weight = jnp.asarray(rng.uniform(0.05, 1, (N, K)), jnp.float32) \
        if weighted else None
    got, want = both(some_rows(rng, N * K, live, dtype), pos, live, windows,
                     weight)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    if not live:
        assert not got.any()
    if live == 1:
        assert (np.abs(got).sum(-1) > 0).sum() == 1


@pytest.mark.parametrize("capacity", [256, 64, 16])
def test_a_pair_past_the_rung_adds_nothing(capacity):
    """[C, D] rows for C under the routed pairs: a pair whose place lies
    past C (or past `live_rows` inside C) is left out, as _sum_of_pairs
    leaves it out, and a token none of whose pairs is live gets zeros."""
    rng = np.random.default_rng(capacity)
    pos, windows, routed = sorted_pairs(draw(rng, 300), N, K, HELD_HERE)
    assert routed > capacity
    live = capacity - 3
    weight = jnp.asarray(rng.uniform(0.05, 1, (N, K)), jnp.float32)
    got, want = both(some_rows(rng, capacity, live, "bfloat16"), pos, live,
                     windows, weight)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    untouched = ~np.asarray((pos < live).any(-1))
    assert untouched.sum() > N - capacity and not got[untouched].any()


def test_the_output_may_be_written_in_the_rows_dtype():
    """The gradient's map: float32 sums rounded once to bf16, bit for bit
    _sum_of_pairs(...).astype(bf16) where no token has two live pairs."""
    rng = np.random.default_rng(5)
    group = np.full(N * K, HELD_HERE, np.int32)
    group[::K][rng.permutation(N)[:200]] = 2      # at most one pair a token
    pos, windows, routed = sorted_pairs(jnp.asarray(group), N, K, HELD_HERE)
    rows = some_rows(rng, N * K, routed, "bfloat16")
    got = pallas_pair_sum.pair_sum(rows, pos, routed, windows, tile=TILE,
                                   window=WINDOW, out_dtype=jnp.bfloat16,
                                   interpret=True)
    want = hybrid_ops._sum_of_pairs(rows, pos, routed).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()


def test_windows_are_each_tiles_rows_of_each_expert():
    """pair_windows against a count by hand: the rows of expert e between
    its window's start and end are exactly the tile's pairs on e."""
    rng = np.random.default_rng(11)
    group = draw(rng, 700)
    order = np.asarray(jnp.argsort(group, stable=True))
    start, end = np.asarray(pallas_pair_sum.pair_windows(
        group, HELD_HERE, K, TILE))
    group = np.asarray(group)
    for tile in range(N // TILE):
        for e in range(HELD_HERE):
            pairs = order[start[tile, e]:end[tile, e]]
            assert (group[pairs] == e).all()
            assert (pairs // (TILE * K) == tile).all()
    assert (end - start).sum() == 700


@pytest.mark.parametrize("shape,reason", [
    ((512, 2048, 128, 8), None),
    ((512, 2048, 120, 8), "width"),
    ((500, 2000, 128, 8), "tokens"),
    ((512, 2040, 128, 8), "rows"),
    ((512, 2048, 128, 64), "experts"),
])
def test_the_gate_gives_each_reason(shape, reason):
    assert pallas_pair_sum.ineligible(*shape) == reason


def _booked():
    """(lowerings of moe_experts on gmm, of them on pair_sum, declined)"""
    took = telemetry.read_series("pallas_kernel_total")
    return np.array([took.get("op=moe_experts", 0), took.get("op=pair_sum", 0),
                     telemetry.read_series("pallas_fallback_total").get(
                         "op=pair_sum,reason=tokens", 0)])


@pytest.mark.parametrize("held", [HELD, 4], ids=["a_sixteenth", "an_eighth"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("held_pairs", [100, 200, 600],
                         ids=["rung0", "between", "rung1"])
def test_the_layers_gradient_through_both_rungs(held_pairs, gated, held,
                                                monkeypatch):
    """moe_experts over its ladder of 1024 pairs (128 | 256 | 1024 at a
    sixteenth: twice its share, four times it, every pair; 256 | 1024
    at an eighth: twice its share) with the kernel on both of the token
    side's maps, forward and pulled back, the first rung, the one
    between (the first still, at an eighth) and the full one taken by
    overflow, against the same op with the gate declined: Out and every
    gradient to 1e-6 in float32, the choice booked once a forward
    lowering of the op (run_op lowers it in two programs) and never by
    the gradient op's."""
    n, d, f, k = 256, 128, 128, 4
    wrt = ("X", "TopkWeight", "WGate", "W1", "W2")
    rungs = hybrid_ops._capacity_ladder(n * k, held, EXPERTS)
    assert rungs == ((128, 256, 1024) if held == HELD else (256, 1024))
    before = _booked()
    outs, grads, _ = experts_op(np.random.default_rng(held_pairs), n, d, f, k,
                                held_pairs, gated, wrt, held)
    lowered, took, declined = _booked() - before
    assert lowered == took > 0 == declined
    assert outs["RowsRouted"][0] == outs["RowsCombined"][0] == held_pairs
    assert outs["RowsHandled"][0] == min(c for c in rungs if c >= held_pairs)

    monkeypatch.setattr(pallas_pair_sum, "ineligible",
                        lambda *shape: "tokens")
    before = _booked()
    plain, plain_grads, _ = experts_op(np.random.default_rng(held_pairs), n,
                                       d, f, k, held_pairs, gated, wrt, held)
    lowered, took, declined = _booked() - before
    assert lowered == declined > 0 == took
    close(outs["Out"], plain["Out"], tol=1e-6)
    assert set(grads) == set(plain_grads)
    for slot in grads:
        close(grads[slot], plain_grads[slot], tol=1e-6)


def test_one_traced_kernel_a_shape():
    """The ladder's branches, a model's layers and the gradient's
    re-trace reach one jitted call a (C, N, top_k, D, held, dtype,
    weighted, ...)."""
    rng = np.random.default_rng(3)
    pos, windows, routed = sorted_pairs(draw(rng, 90), N, K, HELD_HERE)
    rows = some_rows(rng, 256, routed, "bfloat16")
    pallas_pair_sum._call.cache_clear()
    for _ in range(3):
        pallas_pair_sum.pair_sum(rows, pos, routed, windows, tile=TILE,
                                 window=WINDOW, interpret=True)
    info = pallas_pair_sum._call.cache_info()
    assert (info.misses, info.hits) == (1, 2)
