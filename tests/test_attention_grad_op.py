"""Pins for the attention op's explicit-backward machinery: the op emits
a correct LSE residual, append_backward selects the EXPLICIT grad op
(scaled_dot_product_attention_grad) rather than the generic vjp maker —
the property that keeps pallas forwards from running twice per step
(XLA does not CSE duplicated custom calls) — and the grad op's outputs
match autodiff through the einsum reference."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod


def _build(use_flash):
    q = fluid.layers.data(name="q", shape=[2, 64, 2, 16], dtype="float32",
                          append_batch_size=False)
    k = fluid.layers.data(name="k", shape=[2, 64, 2, 16], dtype="float32",
                          append_batch_size=False)
    v = fluid.layers.data(name="v", shape=[2, 64, 2, 16], dtype="float32",
                          append_batch_size=False)
    for var in (q, k, v):
        # data vars default to no-grad on BOTH the py Variable and desc
        var.stop_gradient = False
        var.desc.stop_gradient = False
    out = fluid.layers.fused_attention(q, k, v, causal=True,
                                       use_flash=use_flash)
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
    return (q, k, v), out, loss


def _feed(seed=5):
    rng = np.random.default_rng(seed)
    return {n: rng.standard_normal((2, 64, 2, 16)).astype(np.float32)
            for n in ("q", "k", "v")}


@pytest.mark.parametrize("use_flash", [False, True])
def test_lse_output_matches_logsumexp(use_flash):
    (q, k, v), out, _loss = _build(use_flash)
    main = fluid.framework.framework.default_main_program()
    sdpa_op, = [op for op in main.global_block().ops
                if op.type == "scaled_dot_product_attention"]
    lse_name = sdpa_op.output("LSE")[0]
    feed = _feed()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        lse, = exe.run(main, feed=feed, fetch_list=[lse_name])
    d = 16
    s = np.einsum("bqhd,bkhd->bhqk", feed["q"], feed["k"]) / np.sqrt(d)
    mask = np.tril(np.ones((64, 64), bool))
    s = np.where(mask, s, -np.inf)
    want = np.log(np.sum(np.exp(s - s.max(-1, keepdims=True)), -1)) + \
        s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), want, rtol=1e-4, atol=1e-4)


def test_backward_uses_explicit_grad_op():
    (q, k, v), out, loss = _build(True)
    fluid.backward.append_backward(loss)
    main = fluid.framework.framework.default_main_program()
    types = [op.type for op in main.global_block().ops]
    assert "scaled_dot_product_attention_grad" in types, types
    # exactly one forward attention op: the grad op must NOT have cloned it
    assert types.count("scaled_dot_product_attention") == 1, types


@pytest.mark.parametrize("use_flash", [False, True])
def test_grads_match_einsum_autodiff(use_flash):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.ring_attention import attention_reference
    from paddle_tpu.framework.framework import grad_var_name

    (q, k, v), out, loss = _build(use_flash)
    fluid.backward.append_backward(loss)
    main = fluid.framework.framework.default_main_program()
    feed = _feed()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        grads = exe.run(main, feed=feed,
                        fetch_list=[grad_var_name(n)
                                    for n in ("q", "k", "v")])

    def loss_fn(a, b, c):
        o = attention_reference(a, b, c, causal=True)
        return jnp.mean(o * o)

    want = jax.grad(loss_fn, argnums=(0, 1, 2))(
        *[jnp.asarray(feed[n]) for n in ("q", "k", "v")])
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-3, atol=1e-4)


class TestAutoSelection:
    """use_flash defaults to 'auto': einsum below the
    threshold T (fuses into neighboring HLO), flash at/above it; explicit
    True/False always wins."""

    class _Op:
        def __init__(self, attrs):
            self._attrs = attrs

        def attr(self, name, default=None):
            return self._attrs.get(name, default)

    class _Ctx:
        class _P:
            _mesh = None
        program = _P()

    def _mode(self, t, attrs, threshold=None, dtype="float32"):
        import os
        import jax
        import paddle_tpu.ops.nn_ops as nn_ops
        probe = jax.ShapeDtypeStruct((2, t, 4, 64), dtype)
        prev = os.environ.get("PADDLE_TPU_FLASH_AUTO_T")
        if threshold is not None:
            os.environ["PADDLE_TPU_FLASH_AUTO_T"] = str(threshold)
        try:
            mode, _ = nn_ops._sdpa_paths(self._Ctx(), self._Op(attrs),
                                         probe, probe, probe)
        finally:
            if threshold is not None:
                if prev is None:
                    del os.environ["PADDLE_TPU_FLASH_AUTO_T"]
                else:
                    os.environ["PADDLE_TPU_FLASH_AUTO_T"] = prev
        return mode

    def test_auto_short_t_takes_einsum(self):
        assert self._mode(512, {"use_flash": "auto"},
                          threshold=2048) == "einsum"

    def test_auto_long_t_takes_flash(self):
        assert self._mode(4096, {"use_flash": "auto"},
                          threshold=2048) == "flash"

    def test_explicit_true_forces_flash_below_threshold(self):
        assert self._mode(512, {"use_flash": True},
                          threshold=2048) == "flash"

    def test_explicit_false_forces_einsum_above_threshold(self):
        assert self._mode(8192, {"use_flash": False},
                          threshold=2048) == "einsum"

    def test_untileable_shape_falls_back_to_einsum(self):
        assert self._mode(100, {"use_flash": True}) == "einsum"

    def test_default_attr_is_auto(self):
        import paddle_tpu as fluid
        _build(use_flash="auto")  # layer default; explicit for clarity
        main = fluid.framework.framework.default_main_program()
        sdpa_op, = [op for op in main.global_block().ops
                    if op.type == "scaled_dot_product_attention"]
        assert sdpa_op.attr("use_flash") == "auto"
