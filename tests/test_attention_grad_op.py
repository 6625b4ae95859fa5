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
    """use_flash defaults to 'auto': the rule (nn_ops._flash_wins) reads
    the per-device shape the lowering sees, einsum below the sequence
    length at which the kernels won on the chip, flash from it on;
    explicit True/False always wins."""

    class _Op:
        def __init__(self, attrs):
            self._attrs = attrs

        def attr(self, name, default=None):
            return self._attrs.get(name, default)

    class _Ctx:
        class _P:
            _mesh = None
        program = _P()

    def _mode(self, t, attrs, heads=4, op_=None):
        """The path of one [2, t, heads, 64] lowering; with `op_`, as
        the forward op's lowering, which books its decision."""
        import jax
        import paddle_tpu.ops.nn_ops as nn_ops
        probe = jax.ShapeDtypeStruct((2, t, heads, 64), "bfloat16")
        mode, _, _ = nn_ops._sdpa_paths(
            self._Ctx(), op_ or self._Op(attrs), probe, probe, probe,
            count=op_ is not None)
        return mode

    @pytest.mark.parametrize("t,attrs,heads,mode", [
        (256, {"use_flash": "auto"}, 4, "einsum"),      # below the rule
        (512, {"use_flash": "auto"}, 4, "flash"),       # from it on
        (1024, {"use_flash": "auto"}, 12, "flash"),     # GPT-2's shape
        (1024, {"use_flash": "auto"}, 10, "flash"),     # GPT-2 large, tp=2
        (4096, {"use_flash": "auto"}, 4, "flash"),
        (256, {"use_flash": True}, 4, "flash"),         # explicit wins
        (8192, {"use_flash": False}, 4, "einsum"),
        (100, {"use_flash": True}, 4, "einsum"),        # untileable
        (1024, {"use_flash": "auto"}, 3, "einsum"),     # fills no block
        (1024, {}, 12, "flash"),                        # default is auto
    ])
    def test_rule_reads_shape_and_attr(self, t, attrs, heads, mode):
        assert self._mode(t, attrs, heads=heads) == mode

    def test_rule_reads_the_per_device_shard(self):
        """Under a mesh the rule and the gate see one device's block:
        20 heads over tp=2 are 10 a chip (five lane blocks); 6 heads of
        64 over tp=2 are 3 a chip, which fill no block."""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        import paddle_tpu.ops.nn_ops as nn_ops

        class Ctx:
            class program:
                _mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                             ("fsdp", "tp"))
                _sharding_plan = None

        def mode(heads):
            probe = jax.ShapeDtypeStruct((16, 1024, heads, 64), "bfloat16")
            return nn_ops._sdpa_paths(Ctx(), self._Op({}), probe, probe,
                                      probe)

        took, placement, _ = mode(20)
        assert took == "flash" and placement.mesh is Ctx.program._mesh
        assert mode(6) == ("einsum", None, None)

    def test_lowering_books_hit_and_fallback(self):
        """pallas_kernel_total for a lowering on the kernels (12 heads),
        pallas_fallback_total with the gate's reason for one that asked
        and was declined (3 heads of 64), nothing for einsum by rule or
        by request. The counters count lowerings, as the conv gates'
        do: a second trace of the same op books again."""
        from paddle_tpu import telemetry
        op = "op=scaled_dot_product_attention"

        def read():
            return (telemetry.read_series("pallas_kernel_total").get(op, 0),
                    telemetry.read_series("pallas_fallback_total").get(
                        op + ",reason=heads", 0))

        hits, declined = read()
        flash_op = self._Op({"use_flash": "auto"})
        assert self._mode(1024, None, 12, op_=flash_op) == "flash"
        assert read() == (hits + 1, declined)
        assert self._mode(1024, None, 12, op_=flash_op) == "flash"
        assert read() == (hits + 2, declined)
        declined_op = self._Op({"use_flash": "auto"})
        assert self._mode(1024, None, 3, op_=declined_op) == "einsum"
        assert read() == (hits + 2, declined + 1)
        for attrs, t in (({"use_flash": False}, 1024),
                         ({"use_flash": "auto"}, 256)):
            assert self._mode(t, None, 12, op_=self._Op(attrs)) == "einsum"
        # the grad op's lowering decides again and books nothing
        assert self._mode(1024, {"use_flash": "auto"}, 12) == "flash"
        assert read() == (hits + 2, declined + 1)

    def test_default_attr_is_auto(self):
        import paddle_tpu as fluid
        _build(use_flash="auto")  # layer default; explicit for clarity
        main = fluid.framework.framework.default_main_program()
        sdpa_op, = [op for op in main.global_block().ops
                    if op.type == "scaled_dot_product_attention"]
        assert sdpa_op.attr("use_flash") == "auto"


def test_program_books_kernels_and_fallbacks():
    """Through the executor: a 12-head program lowers onto the kernels
    (pallas_kernel_total 1, no fallback: its step is traced once, the
    analysis after the compile included), a program whose T does not tile
    asks for flash and keeps einsum (pallas_fallback_total{reason=seq})."""
    from paddle_tpu import telemetry
    op = "op=scaled_dot_product_attention"

    def run(shape):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name="q", shape=list(shape),
                                  dtype="float32", append_batch_size=False)
            out = fluid.layers.fused_attention(q, q, q, causal=True,
                                               use_flash=True)
        exe = fluid.Executor(fluid.CPUPlace())
        x = np.random.default_rng(3).standard_normal(shape).astype(
            np.float32)
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(main, feed={"q": x}, fetch_list=[out])

    def read():
        return (telemetry.read_series("pallas_kernel_total").get(op, 0),
                sum(v for k, v in telemetry.read_series(
                    "pallas_fallback_total").items() if k.startswith(op)),
                telemetry.read_series("pallas_fallback_total").get(
                    op + ",reason=seq", 0))

    hits, declined, seq = read()
    run((1, 128, 12, 64))
    assert read() == (hits + 1, declined, seq)
    run((1, 100, 12, 64))
    assert read() == (hits + 1, declined + 1, seq + 1)
