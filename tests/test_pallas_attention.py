"""Pallas flash-attention kernel (ops/pallas_attention.py): online-softmax
VMEM kernel vs the XLA reference. On the CPU test platform the kernel runs
under the Pallas interpreter — the same code Mosaic compiles on TPU
(tests/test_tpu_compile.py compiles it for a described v5e at the
benchmark cells' shapes; PERF.md section 6, PR 29 has its chip times)."""

import numpy as np
import re
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.ops import pallas_attention
from paddle_tpu.ops.pallas_attention import flash_attention, supports
from paddle_tpu.parallel.ring_attention import attention_reference

RNG = np.random.default_rng(7)


def _qkv(b, t, h, d):
    return tuple(jnp.asarray(RNG.standard_normal((b, t, h, d))
                             .astype(np.float32)) for _ in range(3))


class TestFlashKernel:
    # 12 and 10 heads of 64 are the benchmark's GPT-2 cells (10 = GPT-2
    # large's 20 over tp=2): six and five lane blocks of two heads
    @pytest.mark.parametrize("shape", [(2, 64, 2, 32), (1, 128, 4, 64),
                                       (2, 256, 2, 64), (1, 256, 12, 64),
                                       (1, 256, 10, 64), (1, 256, 8, 32)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, shape, causal):
        q, k, v = _qkv(*shape)
        # ambient default matmul precision on this platform is bf16-class;
        # compare the algorithms at full precision
        with jax.default_matmul_precision("highest"):
            got = flash_attention(q, k, v, causal)
            want = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("shape", [(1, 128, 2, 32), (2, 256, 2, 64),
                                       (1, 256, 12, 64), (1, 256, 10, 64),
                                       (1, 256, 8, 32)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, shape, causal):
        """The Pallas flash backward (dQ/dK/dV kernels recomputing from the
        saved logsumexp) vs autodiff through the einsum reference. A
        different algorithm at f32: tolerance 1e-3 abs (grads are O(1)
        here)."""
        q, k, v = _qkv(*shape)
        with jax.default_matmul_precision("highest"):
            g1 = jax.grad(lambda a, b, c: jnp.sum(
                flash_attention(a, b, c, causal) ** 2), argnums=(0, 1, 2))(
                    q, k, v)
            g2 = jax.grad(lambda a, b, c: jnp.sum(
                attention_reference(a, b, c, causal=causal) ** 2),
                argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_supports_gating(self):
        # T <= 128 takes the block = T path (works untiled, but must be
        # sublane-aligned: T % 8); larger T must tile by 128; rank-3
        # inputs are rejected
        assert supports(*_qkv(1, 104, 2, 32))
        assert supports(*_qkv(1, 256, 1, 64))
        assert supports(*_qkv(1, 64, 1, 64))
        assert not supports(*_qkv(1, 100, 2, 32))   # 100 % 8 != 0
        assert not supports(*_qkv(1, 257, 1, 64))
        q3 = jnp.zeros((2, 64, 32))
        assert not supports(q3, q3, q3)
        # heads ride the grid in 128-lane blocks: any count that fills
        # them passes, D must divide 128 or be a multiple of it
        assert supports(*_qkv(1, 256, 12, 64))
        assert supports(*_qkv(1, 256, 10, 64))
        assert supports(*_qkv(1, 256, 12, 32))
        assert pallas_attention.ineligible(*_qkv(1, 256, 3, 64)) == "heads"
        assert pallas_attention.ineligible(*_qkv(1, 256, 6, 32)) == "heads"
        assert pallas_attention.ineligible(*_qkv(1, 256, 4, 96)) == \
            "head_dim"

    def test_sub128_untiled_path_matches(self):
        q, k, v = _qkv(1, 104, 1, 32)       # block = T = 104 (untiled)
        with jax.default_matmul_precision("highest"):
            got = flash_attention(q, k, v, True)
            want = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("shape", [(1, 128, 2, 32), (1, 256, 12, 64),
                                       (1, 256, 10, 64)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_saved_lse_matches_reference(self, shape, causal):
        """The forward's saved logsumexp equals log-sum-exp of the scaled
        (masked) scores — the invariant the backward kernels rely on."""
        from paddle_tpu.ops.pallas_attention import _forward
        q, k, v = _qkv(*shape)
        t = shape[1]
        with jax.default_matmul_precision("highest"):
            _, lse = _forward(q, k, v, causal, return_lse=True)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
            want = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_major_tiles_carry_across_the_grid(self, causal):
        """Past `major` rows the walked side is a grid axis: the carries
        live in scratch, a causally dead major tile is clamped in the
        index map and never walked. Cut to 128 rows here (the wrappers
        take tiles and `major` as static arguments) so that T=512 walks
        four major tiles of one block each."""
        q, k, v = _qkv(1, 512, 2, 64)
        scale = 1.0 / np.sqrt(q.shape[-1])

        def loss(a, b, c):
            return jnp.sum(attention_reference(a, b, c, causal=causal) ** 2)

        with jax.default_matmul_precision("highest"):
            got, (lse,) = pallas_attention._fwd_call(
                q, k, v, 0, 0, scale, causal, normalize=True,
                tile=(256, 128), major=128)
            want = attention_reference(q, k, v, causal=causal)
            do = 2 * got
            delta = jnp.sum(do * got, axis=-1).transpose(0, 2, 1)
            g1 = pallas_attention.flash_attention_bwd_block(
                q, k, v, do, lse, delta, 0, 0, scale, causal,
                dq_tile=(128, 128), dkv_tile=(256, 128), major=128)
            g2 = jax.grad(loss, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)


def _dense_part(q, k, v, do, q_off, k_off, causal, block, window=0):
    """One part of an attention, dense in float32: the gradients of
    softmax(mask(QK^T / sqrt(D))) V over this part's keys alone, its
    LSE (-inf on a row that sees no key of the part) and delta, as the
    ring's backward and block-diffusion attention's hand them to the
    kernels. keep: floor(q_pos / block) >= floor(k_pos / block), and
    under a window q_pos - k_pos < window."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    scale = 1.0 / np.sqrt(q.shape[-1])
    keep = np.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        q_pos = np.arange(q.shape[1])[:, None] + q_off
        k_pos = np.arange(k.shape[1])[None, :] + k_off
        keep = q_pos // block >= k_pos // block
        if window:
            keep &= q_pos - k_pos < window
    seen = jnp.asarray(keep.any(-1))
    f32[3] = f32[3] * seen[None, :, None, None]

    def scores(q, k):
        return jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale

    def attend(q, k, v):
        p = jax.nn.softmax(jnp.where(keep, scores(q, k), -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out, vjp = jax.vjp(attend, *f32[:3])
    lse = jax.scipy.special.logsumexp(
        jnp.where(keep, scores(*f32[:2]), -jnp.inf), axis=-1)
    delta = jnp.sum(f32[3] * out, axis=-1).transpose(0, 2, 1)
    return f32[3].astype(do.dtype), lse, delta, vjp(f32[3])


# (H, D) of the benchmark's cells: GPT-2's 12 heads of 64 and GPT-2
# large's 10 a tensor-parallel shard (two heads a lane block), the hybrid
# and block-diffusion cells' 32 of 128 after the K/V repeat, latent
# attention's 20 of 256 (one head a 256-lane block)
CELL_HEADS = [(12, 64), (10, 64), (32, 128), (20, 256)]
# (id, causal, block, q_off, k_off, Tq, Tk): the plain masks; the block
# mask as block-diffusion attention's two kernel parts ask for it (the
# clean stream, and the noisy stream's view of strictly earlier blocks);
# a Q shard against another shard's keys as the ring hands them in, one
# wholly behind the queries and one the diagonal crosses
MASKS = [("causal", True, 1, 0, 0, 512, 512),
         ("full", False, 1, 0, 0, 512, 512),
         ("block4", True, 4, 0, 0, 512, 512),
         ("block4_earlier", True, 4, -4, 0, 512, 512),
         ("ring_shard_behind", True, 1, 512, 0, 256, 512),
         ("ring_shard_across", True, 1, 256, 384, 512, 384)]


def _backward_cases():
    """Every (heads, mask) pair once, at tiles of 128 rows; the major
    tile (256 rows: a Tq of 512 walks two of them; 1024: one) and the
    dtype alternate, so each appears with every head shape and every
    mask. (A major tile of ONE block is left to
    test_major_tiles_carry_across_the_grid, in float32: the CPU
    backend has no bf16 x bf16 = f32 dot for the loop of one trip that
    the interpreter then makes of a 128-lane head's walk.)"""
    for a, (h, d) in enumerate(CELL_HEADS):
        for b, mask in enumerate(MASKS):
            major = (256, 1024)[(a + b) % 2]
            dtype = (jnp.float32, jnp.bfloat16)[(a + b // 2) % 2]
            yield pytest.param(
                h, d, mask[1:], major, dtype,
                id=f"{h}x{d}-{mask[0]}-major{major}-{dtype.__name__}")


def _backwards_booked():
    """(fused, {split series: n}) of flash_backward_total so far."""
    from paddle_tpu import telemetry
    booked = dict(telemetry.read_series("flash_backward_total"))
    return (booked.pop("form=fused,reason=", 0), booked)


def _repeat(x, groups):
    return jnp.repeat(x, groups, axis=2)


def _sum_groups(x, groups):
    b, t, h, d = x.shape
    return x.astype(jnp.float32).reshape(b, t, h // groups, groups, d).sum(3)


# (id, (B, T, H, D), K/V heads, block, q_off, window): the grouped shapes
# of ISSUE 61 under the masks of the three cells that run them (causal,
# a window, block-diffusion attention's two kernel parts)
GROUPED = [
    pytest.param(shape, kv, block, q_off, window, dtype, id=(
        f"{shape[2]}over{kv}-t{shape[1]}-{name}-{dtype.__name__}"))
    for (shape, kv), dtype in (
        (((1, 256, 8, 128), 2), jnp.float32),
        (((1, 512, 6, 128), 2), jnp.bfloat16))
    for name, block, q_off, window in (
        ("causal", 1, 0, 0), ("window128", 1, 0, 128),
        ("block4", 4, 0, 0), ("block4_earlier", 4, -4, 0))]


class TestKVHeads:
    """K and V of fewer heads than Q, read at their own head count (PR
    61): the kernels' K/V index maps take lane block g // groups, and the
    fused `flash_dkv` sums a group's dK and dV in float32 scratch on the
    grid (B, H_kv, members, Tk/bk, Tq/mq). Against the equal-heads
    kernels on repeated K/V (the form of before) and a dense float32
    gradient; two blocks a major tile, two major tiles at T = 512."""

    TILES = dict(tile=(128, 128), major=256)

    @pytest.mark.parametrize("shape,kv,block,q_off,window,dtype", GROUPED)
    def test_forward_is_the_repeated_one_bit_for_bit(self, shape, kv, block,
                                                     q_off, window, dtype):
        rng = np.random.default_rng(sum(shape) + kv + block)
        b, t, h, d = shape
        q = jnp.asarray(rng.standard_normal(shape), dtype)
        k, v = (jnp.asarray(rng.standard_normal((b, t, kv, d)), dtype)
                for _ in range(2))
        assert pallas_attention.ineligible(q, k, v, block=block) is None
        for normalize in (True, False) if not window else (True,):
            with jax.default_matmul_precision("highest"):
                run = lambda k, v: pallas_attention._fwd_call(
                    q, k, v, q_off, 0, d ** -0.5, True, normalize=normalize,
                    block=block, window=window, **self.TILES)
                got, want = run(k, v), run(_repeat(k, h // kv),
                                           _repeat(v, h // kv))
            for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b_, np.float32))

    @pytest.mark.parametrize("shape,kv,block,q_off,window,dtype", GROUPED)
    def test_backward_sums_a_group_in_float32(self, shape, kv, block, q_off,
                                              window, dtype):
        """dQ is the repeated form's to the bit in every form; dK and dV
        written a query head and summed behind the call are the repeated
        form's to the bit, and summed inside the fused call they are no
        further (root mean square) from the dense float32 gradient than
        those."""
        rng = np.random.default_rng(sum(shape) + kv + block + 1)
        b, t, h, d = shape
        groups = h // kv
        q, do = (jnp.asarray(rng.standard_normal(shape), dtype)
                 for _ in range(2))
        k, v = (jnp.asarray(rng.standard_normal((b, t, kv, d)), dtype)
                for _ in range(2))
        kr, vr = _repeat(k, groups), _repeat(v, groups)
        tiles = dict(dq_tile=(128, 128), dkv_tile=(128, 128), major=256,
                     block=block, window=window)
        with jax.default_matmul_precision("highest"):
            do, lse, delta, (_, dk_ref, dv_ref) = _dense_part(
                q, kr, vr, do, q_off, 0, True, block, window)
            rest = (do, lse, delta, q_off, 0, d ** -0.5, True)
            dq_r, dk_r, dv_r = pallas_attention._bwd_call(
                q, kr, vr, *rest, fused=True, **tiles)
            forms = {name: pallas_attention._bwd_call(q, k, v, *rest, **form,
                                                      **tiles)
                     for name, form in (
                         ("summed", dict(fused=True)),
                         ("per_head", dict(fused=True, summed=False)),
                         ("split", dict(fused=False)))}
        want = [_sum_groups(x, groups) for x in (dk_ref, dv_ref)]
        repeated = [_sum_groups(x, groups).astype(dtype).astype(jnp.float32)
                    for x in (dk_r, dv_r)]
        for name, (dq, dk, dv) in forms.items():
            assert dk.shape == k.shape and dv.shape == v.shape
            if name != "split":     # the split form's dQ sums another way
                np.testing.assert_array_equal(np.asarray(dq, np.float32),
                                              np.asarray(dq_r, np.float32))
            for got, rep, ref in zip((dk, dv), repeated, want):
                got = np.asarray(got, np.float32)
                if name != "summed":
                    np.testing.assert_array_equal(got, np.asarray(rep))
                # root mean square: the largest error is one rounding
                # of the largest value either way
                far, before = (float(jnp.sqrt(jnp.mean((x - ref) ** 2)))
                               for x in (got, rep))
                assert far <= before * 1.001 + 1e-6, (name, far, before)

    def test_the_gate_takes_a_group_only_at_one_head_a_lane_block(self):
        def reason(h, kv, d):
            q = jax.ShapeDtypeStruct((1, 256, h, d), jnp.bfloat16)
            k = jax.ShapeDtypeStruct((1, 256, kv, d), jnp.bfloat16)
            return pallas_attention.ineligible(q, k, k)

        assert reason(8, 2, 128) is None and reason(6, 2, 256) is None
        assert reason(8, 3, 128) == "shape"     # 3 does not divide 8
        assert reason(4, 2, 64) == "shape"      # two heads a lane block
        assert reason(2, 1, 32) == "shape"      # one block of two heads
        assert reason(3, 3, 64) == "heads"      # equal counts: as before

    @pytest.mark.parametrize("tq,groups,reason", [
        (8192, 8, None), (8192, 6, None), (8192, 7, None), (4096, 8, None),
        (16384, 1, None), (16384, 2, "vmem")])
    def test_the_rule_counts_the_sums_of_a_group(self, tq, groups, reason):
        """The cells' grouped calls (8192 rows: Laguna's 8 and 6 query
        heads a K/V head, the sliding window's 7; 4096: block diffusion's
        8) run fused with dK's and dV's float32 sums over the whole K
        sequence counted; past 8192 rows a group keeps two calls."""
        assert pallas_attention._split_reason(
            tq, tq, 128, 2, pallas_attention._TILE, pallas_attention._MAJOR,
            groups=groups) == reason


# (id, block, window, q_off, k_off, Tq, Tk): the masks whose edges the
# kernels walk in sub-tiles (PR 63), at the tiles they have (512 rows, so
# the sequences are two tiles and more): the diagonal alone; a window of
# one tile (no tile is open), of eight, and one that is no multiple of
# the grain; block-diffusion attention's two kernel parts; a ring shard
# whose keys start at a multiple of 128 that is no multiple of the tile
EDGES = [("causal", 1, 0, 0, 0, 1024, 1024),
         ("window512", 1, 512, 0, 0, 1536, 1536),
         ("window4096", 1, 4096, 0, 0, 4608, 4608),
         ("window384", 1, 384, 0, 0, 1536, 1536),
         ("block4", 4, 0, 0, 0, 1024, 1024),
         ("block4_earlier", 4, 0, -4, 0, 1024, 1024),
         ("ring_shard", 1, 0, 640, 384, 512, 1024)]
# (id, H, K/V heads, D)
EDGE_HEADS = [("two_heads_a_lane_block", 2, 2, 64), ("groups8", 8, 1, 128),
              ("one_head_of_256_lanes", 1, 1, 256)]


def _kept(tq, tk, q_off, k_off, block, window):
    """The mask on positions, [Tq, Tk]."""
    q_pos = np.arange(tq)[:, None] + q_off
    k_pos = np.arange(tk)[None, :] + k_off
    keep = q_pos // block >= k_pos // block
    return keep & (q_pos - k_pos < window) if window else keep


def _dense_by_head(q, k, v, do, keep):
    """Dense float32 attention under `keep`, one [Tq, Tk] square of a
    query head at a time against K/V head j // groups: (out, lse, (dq, dk,
    dv)), dK and dV summed over each group. A row that sees no key gives
    an output of 0 and an LSE of -inf and sends nothing back."""
    scale = q.shape[-1] ** -0.5
    groups = q.shape[2] // k.shape[2]
    seen = jnp.asarray(keep.any(-1))[:, None]

    @jax.jit
    def head(q, k, v, do):
        def attend(q, k, v):
            s = q @ k.T * scale
            out = jax.nn.softmax(jnp.where(keep, s, -1e30), -1) @ v
            return jnp.where(seen, out, 0.0), jax.scipy.special.logsumexp(
                jnp.where(keep, s, -jnp.inf), axis=-1)
        out, vjp, lse = jax.vjp(attend, q, k, v, has_aux=True)
        return (out, lse) + vjp(do * seen)

    heads = [head(q[0, :, j], k[0, :, j // groups], v[0, :, j // groups],
                  do[0, :, j]) for j in range(q.shape[2])]
    out, lse, dq, dk, dv = (jnp.stack(x, 1) for x in zip(*heads))
    dk, dv = (x.reshape(x.shape[0], -1, groups, x.shape[-1]).sum(2)
              for x in (dk, dv))
    return out[None], lse.T[None], (dq[None], dk[None], dv[None])


@pytest.mark.parametrize("name,h,kv,d", EDGE_HEADS,
                         ids=[c[0] for c in EDGE_HEADS])
@pytest.mark.parametrize("mask,block,window,q_off,k_off,tq,tk", EDGES,
                         ids=[c[0] for c in EDGES])
def test_edges_walked_in_subtiles_match_dense(mask, block, window, q_off,
                                              k_off, tq, tk, name, h, kv, d):
    """All three kernels and both backward forms at the tiles and the
    grain they have, where a crossed tile's dead sub-tiles are skipped and
    its open ones carry no predicate: outputs, LSE and the three
    gradients against dense float32 attention on positions."""
    rng = np.random.default_rng(tq + window + block + d)
    q, do = (jnp.asarray(rng.standard_normal((1, tq, h, d)), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, tk, kv, d)), jnp.float32)
            for _ in range(2))
    for kernel in ("flash_fwd", "flash_dkv"):
        walk = pallas_attention.edge_subtiles(
            kernel, tq, tk, block=block, window=window, at=(q_off, k_off))
        assert walk["dead"] > 0 and walk["held"] > 0, walk
    keep = _kept(tq, tk, q_off, k_off, block, window)
    seen = keep.any(-1)
    with jax.default_matmul_precision("highest"):
        want, want_lse, want_grads = _dense_by_head(q, k, v, do, keep)
        out, stats = pallas_attention._fwd_call(
            q, k, v, q_off, k_off, d ** -0.5, True, normalize=seen.all(),
            block=block, window=window, at=(q_off, k_off))
        if seen.all():
            lse, = stats
        else:       # one part of an attention: the raw (acc, m, l)
            m, l = stats
            out = out / l.transpose(0, 2, 1)[..., None]
            lse = m + jnp.log(l)
        np.testing.assert_allclose(np.asarray(out)[:, seen],
                                   np.asarray(want)[:, seen],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse)[..., seen],
                                   np.asarray(want_lse)[..., seen],
                                   rtol=2e-5, atol=2e-5)
        delta = jnp.sum(do * want, axis=-1).transpose(0, 2, 1)
        for fused in (True, False):
            grads = pallas_attention._bwd_call(
                q, k, v, do * seen[None, :, None, None], want_lse, delta,
                q_off, k_off, d ** -0.5, True, block=block, window=window,
                fused=fused, at=(q_off, k_off))
            for got, ref in zip(grads, want_grads):
                assert got.shape == ref.shape
                # under one key dQ and dK are zero: held to the
                # cotangent's size
                assert float(jnp.abs(got - ref).max()) <= 1e-4 * max(
                    1.0, float(jnp.abs(ref).max())), (fused, got.shape)


# (block, window, q_off, k_off, Tq, Tk, tile): the masks of EDGES on
# smaller sequences, windows that fall inside one sub-tile and across
# three, a shard at odd multiples of 128, and tiles of unequal sides
WALKS = [(1, 0, 0, 0, 1024, 1024, (512, 512)),
         (1, 512, 0, 0, 1536, 1536, (512, 512)),
         (1, 384, 0, 0, 1536, 1536, (512, 512)),
         (1, 100, 0, 0, 1024, 1024, (512, 512)),
         (1, 700, 0, 0, 2048, 2048, (512, 512)),
         (1, 1536, 0, 0, 2560, 2560, (512, 512)),
         (4, 0, 0, 0, 1024, 1024, (512, 512)),
         (4, 0, -4, 0, 1024, 1024, (512, 512)),
         (128, 0, -128, 0, 1024, 1024, (512, 512)),
         (1, 0, 640, 384, 512, 1024, (512, 512)),
         (1, 0, 128, 896, 1024, 512, (512, 512)),
         (1, 640, 0, 0, 2048, 2048, (512, 256)),
         (1, 0, 0, 0, 1024, 1024, (256, 512))]


@pytest.mark.parametrize("grain", [128, 256])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dkv"])
@pytest.mark.parametrize("block,window,q_off,k_off,tq,tk,tile", WALKS, ids=[
    "-".join(map(str, w[:6])) + "-" + "x".join(map(str, w[6]))
    for w in WALKS])
def test_the_classifier_by_brute_force(block, window, q_off, k_off, tq, tk,
                                       tile, kernel, grain):
    """Every pair the mask keeps lies in exactly one block the walk
    visits; a block visited with no predicate holds kept pairs alone; a
    held block's predicate, applied to its pairs, is the mask (the edge
    it does not test drops none of them); every visited block holds a
    kept pair (the dead ones are in no range); and `edge_subtiles` counts
    those blocks. Two major tiles, so that a range is cut by one."""
    from paddle_tpu.ops.pallas_attention import _DIAG, _EDGE, _OPEN
    major = 1024
    keep = _kept(tq, tk, q_off, k_off, block, window)
    q_pos = np.arange(tq)[:, None] + q_off
    k_pos = np.arange(tk)[None, :] + k_off
    diag = q_pos // block >= k_pos // block
    edge = q_pos - k_pos < window if window else np.ones_like(keep)
    visits = np.zeros(keep.shape, np.int32)
    count = dict(open=0, held=0)
    for q0, q1, k0, k1, kind, sub in pallas_attention._tiles_walked(
            kernel, tq, tk, tile, grain, major, block, window,
            (q_off, k_off)):
        at = np.s_[q0:q1, k0:k1]
        visits[at] += 1
        assert keep[at].any(), (q0, k0, kind)
        if kind == _OPEN:
            assert keep[at].all(), (q0, k0)
        held = np.ones_like(keep[at])
        if kind & _DIAG:
            held &= diag[at]
        if kind & _EDGE:
            held &= edge[at]
        np.testing.assert_array_equal(held, keep[at])
        if sub or kind != _OPEN:
            count["held" if kind != _OPEN else "open"] += 1
    assert visits.max() == 1 and (visits[keep] == 1).all()
    got = pallas_attention.edge_subtiles(
        kernel, tq, tk, tile, grain, major, block, window, (q_off, k_off))
    assert {k: got[k] for k in count} == count
    assert got["walked_pairs"] == int(visits.sum())
    assert got["live_pairs"] == int(keep.sum())
    # a crossed tile's blocks are dead, open or held, and whole tiles
    # walk all of them
    assert got["tile_pairs"] >= got["walked_pairs"] >= got["live_pairs"]
    bq, bk = (int(x) for x in got["grain"].split("x")[::-1])
    assert (got["tile_pairs"] - got["walked_pairs"]) == got["dead"] * bq * bk


# sha256 (16 hex digits) of str(jax.make_jaxpr(call)) for the kernels'
# calls at equal head counts, taken from the tree BEFORE PR 61
# (ops/pallas_attention.py of commit 1853025): grid, BlockSpecs with their
# index maps, scratch and kernel body are in that string. (call, shape,
# dtype, static arguments). The causal calls are asked for without `at`,
# as a ring shard's are (offsets the lowering cannot see): whole tiles,
# the walk of before PR 63. The calls with no mask (`causal=False`) were
# taken from the tree before PR 63 (commit 80fa22e), and are asked for
# with the offsets known: no mask, so no sub-tile.
PARENTS_CALLS = [
    ("fwd", (1, 256, 12, 64), "float32", {}, "0d8319c853a45065"),
    ("fwd", (1, 1024, 2, 128), "bfloat16", {}, "c8ecd8c84e1f5b46"),
    ("fwd", (1, 512, 2, 128), "bfloat16", dict(block=4, normalize=False),
     "cfa5d35e35ae872c"),
    ("fwd", (1, 1024, 2, 128), "bfloat16", dict(window=256, major=512),
     "7966706ff094cb47"),
    ("fwd", (1, 512, 1, 256), "bfloat16", {}, "f48e4dec5755ae3f"),
    ("bwd", (1, 256, 12, 64), "float32", dict(fused=True), "aba3668d5c66787e"),
    ("bwd", (1, 1024, 2, 128), "bfloat16", dict(fused=True), "8425e0ce358f44f8"),
    ("bwd", (1, 1024, 2, 128), "bfloat16", dict(fused=False), "3a91e4f0b99b6c90"),
    ("bwd", (1, 512, 2, 128), "bfloat16", dict(fused=True, block=4), "71a55f4189e80c80"),
    ("bwd", (1, 1024, 2, 128), "bfloat16",
     dict(fused=True, window=256, major=512), "1a89a86a1ae0f8ac"),
    ("bwd", (1, 512, 1, 256), "bfloat16", dict(fused=True), "bc613535c5255d9a"),
    ("fwd", (1, 1024, 12, 64), "bfloat16", dict(causal=False, at=(0, 0)),
     "0a1e848c0afabdd6"),
    ("fwd", (1, 1024, 2, 128), "bfloat16", dict(causal=False, at=(0, 0)),
     "e36cca13af12a037"),
    ("fwd", (1, 4096, 2, 128), "bfloat16",
     dict(causal=False, normalize=False, at=(0, 0)), "183e9690613fbea9"),
    ("fwd", (1, 512, 1, 256), "bfloat16", dict(causal=False, at=(0, 0)),
     "18f0263fd5cd72cf"),
    ("bwd", (1, 1024, 12, 64), "bfloat16",
     dict(causal=False, fused=True, at=(0, 0)), "e31f3390f750e596"),
    ("bwd", (1, 1024, 2, 128), "bfloat16",
     dict(causal=False, fused=True, at=(0, 0)), "815c41a394a78152"),
    ("bwd", (1, 1024, 2, 128), "bfloat16",
     dict(causal=False, fused=False, at=(0, 0)), "675c33f4cc12ba28"),
    ("bwd", (1, 4096, 2, 128), "bfloat16",
     dict(causal=False, fused=True, at=(0, 0)), "d4b8ea3d50c5c05c"),
    ("bwd", (1, 512, 1, 256), "bfloat16",
     dict(causal=False, fused=True, at=(0, 0)), "798983b32c7ecf15"),
]
# The causal calls as the attention ops make them since PR 63, the
# offsets told as plain ints: re-taken, so that a change to the sub-tiled
# walk shows as one. (1, 256, 12, 64): a tile no larger than the grain is
# the call of before.
SUBTILED_CALLS = [
    ("fwd", (1, 1024, 2, 128), "bfloat16", {}, "19bfbd00190d11f0"),
    ("fwd", (1, 512, 2, 128), "bfloat16",
     dict(block=4, normalize=False, at=(-4, 0)), "6bfe03cd6229a5d0"),
    ("fwd", (1, 1024, 2, 128), "bfloat16", dict(window=256, major=512),
     "d611cc237e4fd127"),
    ("fwd", (1, 512, 1, 256), "bfloat16", {}, "ed567a75bc1a8403"),
    ("bwd", (1, 1024, 2, 128), "bfloat16", dict(fused=True), "725dd67c59364a5d"),
    ("bwd", (1, 1024, 2, 128), "bfloat16", dict(fused=False), "1674eb5d91392e69"),
    ("bwd", (1, 512, 2, 128), "bfloat16", dict(fused=True, block=4), "5b4ef9a549504f79"),
    ("bwd", (1, 1024, 2, 128), "bfloat16",
     dict(fused=True, window=256, major=512), "82bbf0f402e1122e"),
    ("bwd", (1, 512, 1, 256), "bfloat16", dict(fused=True), "2e3a2b1e21c2c4ac"),
    ("fwd", (1, 1024, 12, 64), "bfloat16", {}, "faa943bf7ceecc66"),
    ("bwd", (1, 1024, 12, 64), "bfloat16", dict(fused=True), "0039c71179522978"),
]


def call_digest(module, call, shape, dtype, statics):
    """The digest PARENTS_CALLS holds, of `module`'s kernels."""
    import hashlib
    b, t, h, d = shape
    x = jax.ShapeDtypeStruct(shape, dtype)
    stat = jax.ShapeDtypeStruct((b, h, t), jnp.float32)
    statics = dict(statics)
    causal = statics.pop("causal", True)
    if call == "fwd":
        statics = dict(dict(normalize=True), **statics)
        jaxpr = jax.make_jaxpr(lambda q, k, v: module._fwd_call(
            q, k, v, 0, 0, d ** -0.5, causal, **statics))(x, x, x)
    else:
        jaxpr = jax.make_jaxpr(lambda q, k, v, do, lse, dl: module._bwd_call(
            q, k, v, do, lse, dl, 0, 0, d ** -0.5, causal, **statics))(
                x, x, x, x, stat, stat)
    # the parent's calls declared no work (PR 66): the digests hold the
    # rest of the call, the declaration set aside
    text = [re.sub(r"cost_estimate=CostEstimate\([^)]*\)",
                   "cost_estimate=None", str(jaxpr))]

    def index_maps(jaxpr):
        """A pallas_call prints its BlockSpecs without their index maps."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                text.extend(str(m.index_map_jaxpr) for m in
                            eqn.params["grid_mapping"].block_mappings)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                index_maps(sub)

    index_maps(jaxpr.jaxpr)
    assert len(text) > 1
    return hashlib.sha256("\n".join(text).encode()).hexdigest()[:16]


def _call_ids(calls):
    return [f"{c[0]}-{'x'.join(map(str, c[1]))}-" + "-".join(
        f"{k}{v}" for k, v in c[3].items()) for c in calls]


@pytest.mark.parametrize("call,shape,dtype,statics,digest", PARENTS_CALLS,
                         ids=_call_ids(PARENTS_CALLS))
def test_equal_head_counts_lower_to_the_calls_of_before(call, shape, dtype,
                                                        statics, digest):
    """With groups == 1 every kernel call is the one of before PR 61, and
    since PR 63 wherever no sub-tile is built: a masked call whose offsets
    are traced values, and a call with no mask whatever it is told."""
    assert call_digest(pallas_attention, call, shape, dtype,
                       statics) == digest


@pytest.mark.parametrize("call,shape,dtype,statics,digest", SUBTILED_CALLS,
                         ids=_call_ids(SUBTILED_CALLS))
def test_masked_calls_walk_their_edges_in_subtiles(call, shape, dtype,
                                                   statics, digest):
    told = dict(dict(at=(0, 0)), **statics)
    got = call_digest(pallas_attention, call, shape, dtype, told)
    assert got == digest
    untold = {k: v for k, v in told.items() if k != "at"}
    assert got != call_digest(pallas_attention, call, shape, dtype, untold)


def test_a_masked_lowering_books_its_subtiles_and_an_unmasked_one_nothing():
    """flash_edge_subtiles_total{kernel, mask, grain, state}: once a
    lowering, the blocks of `edge_subtiles` a lane block of one batch
    row; a traced offset (a ring shard's) counts as 0."""
    from paddle_tpu import telemetry

    def booked():
        return dict(telemetry.read_series("flash_edge_subtiles_total"))

    def added(before):
        return {k: v - before.get(k, 0) for k, v in booked().items()
                if v != before.get(k, 0)}

    x = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((1, 2, 1024), jnp.float32)
    before = booked()
    jax.eval_shape(lambda q: pallas_attention._forward(q, q, q, False), x)
    jax.eval_shape(lambda q, s: pallas_attention.flash_attention_bwd_block(
        q, q, q, q, s, s, 0, 0, 0.125, False), x, stat)
    assert added(before) == {}
    jax.eval_shape(lambda q: pallas_attention._forward(q, q, q, True,
                                                       window=512), x)
    want = pallas_attention.edge_subtiles("flash_fwd", 1024, 1024,
                                          window=512)
    assert want["dead"] > 0
    assert added(before) == {
        f"kernel=flash_fwd,mask=window,grain={want['grain']},state={state}":
        want[state] for state in ("dead", "open", "held")}
    before = booked()
    jax.eval_shape(lambda q, s, off: pallas_attention.flash_attention_bwd_block(
        q, q, q, q, s, s, off, 0, 0.125, True, block=4), x, stat,
        jax.ShapeDtypeStruct((), jnp.int32))
    want = pallas_attention.edge_subtiles("flash_dkv", 1024, 1024, block=4,
                                          at=None)
    assert want["dead"] == want["open"] == 0 and want["grain"] == "512x512"
    assert added(before) == {
        f"kernel=flash_dkv,mask=block,grain=512x512,state=held": want["held"]}


class TestFusedBackward:
    """flash_attention_bwd_block as one kernel (PR 43): `flash_dkv`,
    which holds K/V resident, also accumulates dQ for the whole Q
    sequence of the call, against the split form's `flash_dq` +
    `flash_dkv` at equal tiles and against a dense float32 gradient."""

    @pytest.mark.parametrize("h,d,mask,major,dtype", _backward_cases())
    def test_fused_matches_split_and_dense(self, h, d, mask, major, dtype):
        causal, block, q_off, k_off, tq, tk = mask
        rng = np.random.default_rng(h * d + tq + tk + block)
        q, do = (jnp.asarray(rng.standard_normal((1, tq, h, d)), dtype)
                 for _ in range(2))
        k, v = (jnp.asarray(rng.standard_normal((1, tk, h, d)), dtype)
                for _ in range(2))
        scale = 1.0 / np.sqrt(d)
        tiles = dict(dq_tile=(128, 128), dkv_tile=(128, 128), major=major,
                     block=block)
        with jax.default_matmul_precision("highest"):
            do, lse, delta, want = _dense_part(q, k, v, do, q_off, k_off,
                                               causal, block)
            args = (q, k, v, do, lse, delta, q_off, k_off, scale, causal)
            fused = pallas_attention._bwd_call(*args, fused=True, **tiles)
            split = pallas_attention._bwd_call(*args, fused=False, **tiles)
        # dK and dV are the same instructions on the same blocks
        np.testing.assert_array_equal(np.asarray(fused[1], np.float32),
                                      np.asarray(split[1], np.float32))
        np.testing.assert_array_equal(np.asarray(fused[2], np.float32),
                                      np.asarray(split[2], np.float32))
        # dQ: the same K^T dS^T terms, summed tile by tile in float32
        # (one bf16 rounding of the sum may then fall the other way)
        order = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
        np.testing.assert_allclose(np.asarray(fused[0], np.float32),
                                   np.asarray(split[0], np.float32),
                                   rtol=order, atol=order)
        tol = 1e-3 if dtype == jnp.float32 else 0.1
        for got, ref in zip(fused, want):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(ref), rtol=tol, atol=tol)

    # the cells' calls, per device: (Tq, lanes, bytes an element)
    @pytest.mark.parametrize("tq,lanes,itemsize", [
        (1024, 128, 2), (4096, 128, 2), (4096, 256, 2), (16384, 128, 2),
        (8192, 128, 4)])
    def test_cell_shapes_run_fused(self, tq, lanes, itemsize):
        assert pallas_attention._split_reason(
            tq, tq, lanes, itemsize, pallas_attention._TILE,
            pallas_attention._MAJOR) is None

    @pytest.mark.parametrize("tq,lanes,itemsize", [
        (32768, 128, 2), (16384, 256, 2), (8192, 512, 4)])
    def test_long_sequences_keep_two_calls(self, tq, lanes, itemsize):
        reason = pallas_attention._split_reason(
            tq, 128, lanes, itemsize, pallas_attention._TILE,
            pallas_attention._MAJOR)
        assert reason == "vmem"
        assert reason in pallas_attention.BACKWARD_SPLIT_REASONS

    def test_split_by_shape_is_booked_and_right(self):
        """A Q shard of 8192 rows of one 512-wide head in float32 is a
        48 MB accumulator and output block: the lowering keeps
        `flash_dq` + `flash_dkv`, books form="split" with the ground,
        and the gradients are the dense ones. A shape that fits books
        form="fused"."""
        rng = np.random.default_rng(3)
        q, do = (jnp.asarray(rng.standard_normal((1, 8192, 1, 512)),
                             jnp.float32) for _ in range(2))
        k, v = (jnp.asarray(rng.standard_normal((1, 128, 1, 512)),
                            jnp.float32) for _ in range(2))
        scale = 1.0 / np.sqrt(512)
        fused, split = _backwards_booked()
        with jax.default_matmul_precision("highest"):
            do, lse, delta, want = _dense_part(q, k, v, do, 0, 0, False, 1)
            got = pallas_attention.flash_attention_bwd_block(
                q, k, v, do, lse, delta, 0, 0, scale, False)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)
        key = "form=split,reason=vmem"
        assert _backwards_booked() == (
            fused, dict(split, **{key: split.get(key, 0) + 1}))
        # the same call with the operands swapped fits: Tq = 128
        with jax.default_matmul_precision("highest"):
            pallas_attention.flash_attention_bwd_block(
                k, q, do, v, lse[..., :128], delta[..., :128], 0, 0, scale,
                False)
        now_fused, now_split = _backwards_booked()
        assert now_fused == fused + 1 and now_split[key] == split.get(key, 0) + 1
        assert {k.split("reason=")[1] for k in now_split} <= \
            pallas_attention.BACKWARD_SPLIT_REASONS


class TestFlashThroughProgram:
    def test_layer_flash_matches_plain(self):
        """fused_attention(use_flash=True) through the executor equals the
        plain path on the same feed."""
        from paddle_tpu import executor as executor_mod
        outs = {}
        qv = RNG.standard_normal((2, 64, 2, 32)).astype(np.float32)
        kv = RNG.standard_normal((2, 64, 2, 32)).astype(np.float32)
        vv = RNG.standard_normal((2, 64, 2, 32)).astype(np.float32)
        for flash in (False, True):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                q = fluid.layers.data(name="q", shape=[-1, 64, 2, 32],
                                      dtype="float32",
                                      append_batch_size=False)
                k = fluid.layers.data(name="k", shape=[-1, 64, 2, 32],
                                      dtype="float32",
                                      append_batch_size=False)
                v = fluid.layers.data(name="v", shape=[-1, 64, 2, 32],
                                      dtype="float32",
                                      append_batch_size=False)
                out = fluid.layers.fused_attention(q, k, v, causal=True,
                                                   use_flash=flash)
            exe = fluid.Executor(fluid.CPUPlace())
            sc = executor_mod.Scope()
            with executor_mod.scope_guard(sc):
                exe.run(startup)
                with jax.default_matmul_precision("highest"):
                    r, = exe.run(main, feed={"q": qv, "k": kv, "v": vv},
                                 fetch_list=[out])
            outs[flash] = np.asarray(r)
        np.testing.assert_allclose(outs[True], outs[False],
                                   rtol=2e-5, atol=2e-6)


class TestGroupedThroughProgram:
    """The attention op under grouped-query attention (PR 61): K and V
    reach the flash kernels at their own head count where one head is a
    lane block, repeated elsewhere, and `attention_kv_groups_total` books
    which."""

    @staticmethod
    def _grads(heads, kv_heads, d, use_flash, feed):
        from paddle_tpu import executor as executor_mod
        from paddle_tpu.framework.framework import grad_var_name
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q, k, v = (fluid.layers.data(
                name=n, shape=[1, 256, h, d], dtype="float32",
                append_batch_size=False)
                for n, h in (("q", heads), ("k", kv_heads), ("v", kv_heads)))
            for var in (q, k, v):
                var.stop_gradient = False
                var.desc.stop_gradient = False
            out = fluid.layers.fused_attention(q, k, v, causal=True,
                                               use_flash=use_flash)
            loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
            fluid.backward.append_backward(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            with jax.default_matmul_precision("highest"):
                return exe.run(main, feed=feed, fetch_list=[out] + [
                    grad_var_name(n) for n in ("q", "k", "v")])

    @pytest.mark.parametrize("heads,kv_heads,d,series", [
        (8, 2, 128, "groups=4,form=kernel,ground="),
        (4, 2, 64, "groups=2,form=repeated,ground=lanes")],
        ids=["one_head_a_lane_block", "two_heads_a_lane_block"])
    def test_the_op_books_its_form_and_matches_einsum(self, heads, kv_heads,
                                                      d, series):
        from paddle_tpu import telemetry
        from paddle_tpu.ops import kernel_choice, nn_ops

        def booked():
            return dict(telemetry.read_series("attention_kv_groups_total"))

        rng = np.random.default_rng(heads)
        feed = {n: rng.standard_normal((1, 256, h, d)).astype(np.float32)
                for n, h in (("q", heads), ("k", kv_heads), ("v", kv_heads))}
        series = "op=scaled_dot_product_attention," + series
        path = f"op=scaled_dot_product_attention," \
            f"groups={heads // kv_heads},form=repeated,ground=path"
        before = booked()
        flash = self._grads(heads, kv_heads, d, True, feed)
        # one for the forward op's lowering, none for the gradient op's
        after = booked()
        assert after.get(series, 0) == before.get(series, 0) + 1
        assert sum(after.values()) == sum(before.values()) + 1
        einsum = self._grads(heads, kv_heads, d, False, feed)
        assert booked().get(path, 0) == after.get(path, 0) + 1
        for got, want in zip(flash, einsum):
            assert got.shape == want.shape
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-3, atol=1e-5)
        # a generic gradient's re-trace of a forward lowering is silent
        with kernel_choice.retrace():
            nn_ops._count_kv_groups("scaled_dot_product_attention", 4, None)
        assert booked() == dict(after, **{path: after.get(path, 0) + 1})


class TestFlashRingComposition:
    def test_flash_within_shard_ring_across(self):
        """ring_attention_sharded(use_flash=True): the Pallas block kernels
        compute each shard's contribution in BOTH directions (forward
        online-softmax; backward dQ/dK/dV from saved LSE, with the dK/dV
        accumulators riding the ring) — output and gradients must match
        plain attention. 2-device mesh: interpret-mode pallas inside
        shard_map compiles slowly, and the composition logic is
        device-count independent."""
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        q, k, v = _qkv(1, 64, 1, 16)
        with jax.default_matmul_precision("highest"):
            from paddle_tpu.parallel.ring_attention import (
                attention_reference, ring_attention_sharded)
            for causal in (False, True):
                got = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                             use_flash=True)
                want = attention_reference(q, k, v, causal=causal)
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(want),
                                           rtol=2e-5, atol=2e-6)
            fused, split = _backwards_booked()
            g1 = jax.grad(lambda a, b, c: jnp.sum(ring_attention_sharded(
                a, b, c, mesh, causal=True, use_flash=True) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            # a ring step's backward is one fused call (PR 43): the scan
            # over the visiting shards lowers it once
            now_fused, now_split = _backwards_booked()
            assert now_fused > fused and now_split == split
            g2 = jax.grad(lambda a, b, c: jnp.sum(attention_reference(
                a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        # flash backward recomputes from LSE — a different algorithm at
        # f32, so 1e-3-class tolerance (same bar as the kernel tests)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)
