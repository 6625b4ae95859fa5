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
            g1 = jax.grad(lambda a, b, c: jnp.sum(ring_attention_sharded(
                a, b, c, mesh, causal=True, use_flash=True) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(lambda a, b, c: jnp.sum(attention_reference(
                a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        # flash backward recomputes from LSE — a different algorithm at
        # f32, so 1e-3-class tolerance (same bar as the kernel tests)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)
