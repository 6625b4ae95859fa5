"""Pallas flash-attention kernel (ops/pallas_attention.py): online-softmax
VMEM kernel vs the XLA reference. On the CPU test platform the kernel runs
under the Pallas interpreter — the same code Mosaic compiles on TPU
(tests/test_tpu_compile.py compiles it for a described v5e at the
benchmark cells' shapes; PERF.md section 6, PR 29 has its chip times)."""

import numpy as np
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


def _dense_part(q, k, v, do, q_off, k_off, causal, block):
    """One part of an attention, dense in float32: the gradients of
    softmax(mask(QK^T / sqrt(D))) V over this part's keys alone, its
    LSE (-inf on a row that sees no key of the part) and delta, as the
    ring's backward and block-diffusion attention's hand them to the
    kernels. keep: floor(q_pos / block) >= floor(k_pos / block)."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    scale = 1.0 / np.sqrt(q.shape[-1])
    keep = np.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        keep = ((np.arange(q.shape[1])[:, None] + q_off) // block
                >= (np.arange(k.shape[1])[None, :] + k_off) // block)
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
