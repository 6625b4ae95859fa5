"""CPU-side guards of the bring-up on the chip: none of these describes a
topology (tests/test_tpu_compile.py does), so they count wherever the
suite runs. chip_smoke.py's phases run here at tiny sizes with the Pallas
kernels interpreted — the guide's first rehearsal — and the pieces that
keep a CPU from passing for a chip are pinned: the device check, the
accelerator place, the peak table, the one compile cache."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu import chip, executor as executor_mod, memory, roofline  # noqa: E402
from paddle_tpu.ops import kernel_choice, pallas_attention, pallas_conv  # noqa: E402


# --- chip_smoke.py ----------------------------------------------------------

def test_smoke_refuses_the_cpu():
    """No accelerator: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "TPU" in r.stderr


@pytest.fixture(scope="module")
def trained():
    """train_phase at a tiny size (ResNet-18, 32x32): its record and the
    trained program serve_phase saves."""
    return chip_smoke.train_phase(batch=2, side=32, classes=10, depth=18,
                                  steps=1, window=2, compiled=False)


def test_train_phase_tiny(trained):
    record, _ = trained
    assert record["phase"] == "train" and len(record["losses"]) == 3
    assert all(np.isfinite(record["losses"]))
    # every conv is XLA's: no conv kernel counted, none declined (the
    # counter is the process's: an attention test that ran earlier in
    # this worker has booked its own series in it)
    assert not [k for k in record["pallas_kernel_total"] if "conv" in k]
    assert not [k for k in record["pallas_fallback_total"] if "conv2d" in k]
    json.dumps(record)                     # one JSON line


def test_serve_phase_tiny(trained):
    _, state = trained
    record = chip_smoke.serve_phase(state, side=32, max_batch=4,
                                    request_rows=(1, 3))
    assert record["buckets"] == [1, 4]
    assert record["max_abs_diff_vs_executor"] <= record["atol"]


def test_serve_phase_wants_two_buckets(trained):
    _, state = trained
    with pytest.raises(AssertionError, match="one bucket"):
        chip_smoke.serve_phase(state, side=32, max_batch=4,
                               request_rows=(3, 4))


def test_lm_phase_tiny():
    record = chip_smoke.lm_phase(batch=2, seqlen=128, d_model=64, n_head=2,
                                 n_layer=1, vocab=128, steps=2,
                                 compiled=False)
    assert record["first_loss_rel_diff"] < record["rtol"]
    assert record["flash_declined"] == {}


def test_recompute_phase_tiny():
    """One group of 16 heads on the scan kernels, interpreted: with and
    without checkpoints the first loss is the same, and the compile with
    checkpoints booked its segments."""
    record = chip_smoke.recompute_phase(
        seqlen=256, d_model=64, heads=16, head_dim=16, state=128, width=96,
        vocab=128, steps=2, compiled=False)
    assert record["first_loss_rel_diff"] < 1e-3
    assert sum(record["recompute_segments_total"].values()) >= 2


def test_multichip_phase_on_virtual_devices():
    """The guide's second rehearsal: the 4-chip phase on four of the
    harness's virtual CPU devices — planner mesh, flash under shard_map,
    loss parity with one device, a parameter spread over all four."""
    record = chip_smoke.multichip_phase(
        jax.devices()[:4], batch=4, seqlen=128, d_model=64, n_head=4,
        n_layer=1, vocab=128, steps=2, compiled=False)
    assert record["sharded_param"]["devices"] == 4
    assert sum(record["collectives"].values()) > 0
    # the TPU scheduler options are not for the CPU: counted, not hidden
    assert record["overlap_options_accepted"] is False
    assert any("reason=platform" in k
               for k in record["overlap_fallback_total"])


# --- no fallback that hides the device --------------------------------------

def test_tpu_place_needs_an_accelerator(monkeypatch):
    assert executor_mod._cpu_forced()          # the harness forces it
    assert executor_mod.place_device(
        executor_mod.TPUPlace(0)).platform == "cpu"
    monkeypatch.setattr(executor_mod, "_cpu_forced", lambda: False)
    with pytest.raises(RuntimeError, match="found none"):
        executor_mod.place_device(executor_mod.TPUPlace(0))
    # a CPUPlace is always honest
    assert executor_mod.place_device(
        executor_mod.CPUPlace()).platform == "cpu"


@pytest.mark.parametrize("meshed", [False, True],
                         ids=["device_put_feed", "fsdp_tp_mesh"])
def test_second_step_does_not_recompile(meshed):
    """What the first chip runs showed: state that starts out as the
    startup program's uncommitted outputs made the second call of a step
    another computation than the first — a committed feed changed the
    argument mapping, a mesh changed the avals — and ResNet-50 compiled
    twice. The executor places the state before the first call."""
    import paddle_tpu as fluid
    from paddle_tpu import telemetry
    from paddle_tpu.parallel import planner
    from paddle_tpu.parallel.mesh import make_mesh

    main, startup, loss = chip_smoke._build_lm(
        seqlen=32, d_model=32, n_head=2, n_layer=1, vocab=64,
        use_flash=False)
    feed = chip_smoke._lm_feed(4, 32, 64)
    exe = fluid.Executor(fluid.CPUPlace())
    if meshed:
        planner.plan(main, make_mesh((2, 2), ("fsdp", "tp"),
                                     devices=jax.devices()[:4]))
    else:
        feed = {k: jax.device_put(v, exe.device) for k, v in feed.items()}

    def compiles():
        return sum(telemetry.read_series(
            "jax_backend_compiles_total").values())

    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        after_first = compiles()
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert compiles() == after_first


def _device(platform, kind, stats=None):
    return types.SimpleNamespace(platform=platform, device_kind=kind,
                                 memory_stats=lambda: stats)


def test_peak_table_knows_v5e_and_nothing_else():
    row = chip.peaks(_device("tpu", "TPU v5 lite"))
    assert (row.bf16_tflops, row.int8_tops, row.hbm_gbps) == (
        197.0, 393.0, 819.0)
    assert row.hbm_bytes == 16 * 1024 ** 3
    with pytest.raises(chip.UnknownDeviceError, match="TPU v99"):
        chip.peaks(_device("tpu", "TPU v99"))
    assert chip.peaks(_device("cpu", "cpu")) is None
    assert roofline.nominal_tflops() is None   # this process is on the CPU


def test_peak_overrides_are_gone(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "1")
    assert roofline.nominal_tflops() is None


def test_default_budget_assumes_no_hbm_size():
    gib = 1024 ** 3
    assert memory.default_budget(_device("cpu", "cpu")) == 16 * gib
    assert memory.default_budget(
        _device("tpu", "TPU v5 lite", {"bytes_limit": 7})) == 7
    assert memory.default_budget(_device("tpu", "TPU v5 lite")) == 16 * gib
    with pytest.raises(chip.UnknownDeviceError):
        memory.default_budget(_device("tpu", "TPU v99"))


def test_describe_names_the_device():
    assert chip.describe() == {"platform": "cpu", "device_kind": "cpu",
                               "device_count": len(jax.devices())}


# --- one compile cache ------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_cache_helper_leaves_the_variable_alone(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip.enable_compile_cache() is None
    # no directory, no threshold: only the key-stability setting
    assert config_updates == {"jax_traceback_in_locations_limit": 1}


def test_cache_helper_uses_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = chip.enable_compile_cache()
    assert path == os.path.join(REPO, ".xla_cache")
    assert config_updates["jax_compilation_cache_dir"] == path
    assert chip.enable_compile_cache() == path   # fixed, not derived


def test_harness_uses_the_same_cache():
    """conftest.py calls the helper: no private variable, no second
    directory (unless the environment placed the cache itself)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".xla_cache")
    src = open(os.path.join(REPO, "tests", "conftest.py")).read()
    assert "enable_compile_cache()" in src
    assert "PADDLE_TPU_XLA_CACHE" not in src


# --- kernels and gates agree ------------------------------------------------

def _lax_conv_i32(x, w, s, p, d):
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)), window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


# (H, W, K, stride, padding, dilation): the strides Mosaic refused as
# strided value slices, now de-interleaved — every phase combination
@pytest.mark.parametrize("h,w_,k,s,p,d", [
    (8, 8, 3, 2, 1, 1),      # ResNet basic block 3x3 s2: phases {0, 1}
    (8, 8, 1, 2, 0, 1),      # ResNet shortcut 1x1 s2: phase {0} only
    (9, 11, 3, 2, 1, 2),     # dilation 2 at stride 2: one phase, offsets
    (10, 10, 3, 3, 1, 1),    # stride 3: three phases
    (7, 7, 3, 1, 1, 1),      # stride 1: the identity case
])
def test_strided_conv_gate_and_kernels_agree(h, w_, k, s, p, d):
    """The gate passes each stride's geometry and the kernel that
    survives (the int8 conv of AMP O3) reads the right taps of the
    de-interleaved row: equal to XLA's integer conv on the same int8
    operands under the same dequantization scales."""
    rng = np.random.default_rng(0)
    args = ((s, s), (p, p), (d, d))
    # the gate is asked before quantization, with the bf16 operands
    assert pallas_conv.ineligible(
        jax.ShapeDtypeStruct((2, h, w_, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((128, 128, k, k), jnp.bfloat16), *args) is None
    x = jnp.asarray(rng.integers(-127, 128, (2, h, w_, 128)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (128, 128, k, k)), jnp.int8)
    dq = jnp.asarray(rng.uniform(0.5, 2.0, 128) * 1e-4, jnp.float32)
    want = _lax_conv_i32(x, w, *args).astype(jnp.float32) * dq
    got = pallas_conv.conv2d_q8(x, w, *args, dq, out_dtype=jnp.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_deinterleave_is_identity_at_stride_one():
    x = jnp.arange(2 * 3 * 7 * 4, dtype=jnp.float32).reshape(2, 3, 7, 4)
    assert pallas_conv._deinterleave(x, 3, 1, 1) is x
    # stride 2, 3 taps: phase 0 then phase 1, each ceil(7/2) = 4 wide
    y = pallas_conv._deinterleave(x, 3, 1, 2)
    assert y.shape == (2, 3, 8, 4)
    np.testing.assert_array_equal(y[:, :, :4], x[:, :, 0::2])
    np.testing.assert_array_equal(y[:, :, 4:7], x[:, :, 1::2])


def test_conv_gate_declines_a_partitioned_step():
    """XLA cannot partition a Mosaic call and the conv kernels are not
    under shard_map: a mesh of more than one device keeps lax.conv."""
    from paddle_tpu.parallel.mesh import make_mesh
    x = jax.ShapeDtypeStruct((8, 14, 14, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 128, 3, 3), jnp.bfloat16)
    args = ((1, 1), (1, 1), (1, 1))
    many = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    one = make_mesh((1,), ("dp",), devices=jax.devices()[:1])
    assert pallas_conv.ineligible(x, w, *args, mesh=many) == "mesh"
    assert pallas_conv.ineligible(x, w, *args, mesh=one) is None
    assert "mesh" in kernel_choice.REASONS["conv2d"]


def test_declined_flash_is_counted_and_takes_einsum():
    """Three heads of 64 fill no 128-lane block: use_flash=True keeps the
    einsum path and books pallas_fallback_total{reason="heads"}."""
    import paddle_tpu as fluid
    from paddle_tpu import telemetry

    def run(use_flash):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name="q", shape=[-1, 16, 3, 64],
                                  append_batch_size=False)
            out = fluid.layers.fused_attention(q, q, q, causal=True,
                                               use_flash=use_flash)
        exe = fluid.Executor(fluid.CPUPlace())
        x = np.random.default_rng(1).standard_normal(
            (2, 16, 3, 64)).astype(np.float32)
        return exe.run(main, feed={"q": x}, fetch_list=[out])[0]

    def declined():
        return telemetry.read_series("pallas_fallback_total").get(
            "op=scaled_dot_product_attention,reason=heads", 0)

    before = declined()
    einsum = run(False)
    assert declined() == before            # nobody asked for flash
    np.testing.assert_array_equal(run(True), einsum)
    assert declined() == before + 1        # one op, lowered once


def test_flash_head_blocks_match_reference():
    """16 heads of 32 walk the grid in four 128-lane blocks of four
    heads; the answer is the reference's, forward and backward."""
    from paddle_tpu.parallel.ring_attention import attention_reference
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 16, 32)),
                           jnp.float32) for _ in range(3))
    assert pallas_attention._lane_block(16, 32) == (128, 4)
    assert pallas_attention._lane_block(12, 64) == (128, 2)
    assert pallas_attention._lane_block(6, 8) == (48, 6)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    flash = lambda q, k, v: pallas_attention.flash_attention(q, k, v, True)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                    jax.grad(loss(ref), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


# --- the smoke's own check: no kernel in the compiled ResNet step ------------

def _hlo(**calls):
    """Optimized-HLO lines as the chip's compiler writes a Mosaic call."""
    return "\n".join(
        f'  %c.{i} = bf16[8]{{0}} custom-call(%a), custom_call_target='
        f'"tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, '
        f'metadata={{op_name="jit(fn)/{name}/pallas_call" stack_frame_id=3}}'
        for name, n in calls.items() for i in range(n))


def test_smoke_counts_kernel_families():
    text = _hlo(**{"pd.moe_experts/gmm": 3,
                   "pd.conv2d/conv2d_q8": 1,
                   "pd.scaled_dot_product_attention_grad/shard_map/"
                   "transpose(jvp(flash_dq))": 2})
    calls = chip_smoke._mosaic_calls(text + "\n  %d = f32[] add(%x, %y)")
    assert calls == {"conv2d/conv2d_q8": 1,
                     "moe_experts/gmm": 3,
                     "scaled_dot_product_attention_grad/flash_dq": 2}
    # the ResNet step's own census is empty (PR 34), and that passes
    chip_smoke._check_no_kernels({}, chip_smoke._mosaic_calls(
        "  %d = f32[] add(%x, %y)"))


@pytest.mark.parametrize("hits,held,match", [
    # a conv kernel in the step the counters did not see
    ({}, "conv2d_grad/conv2d_grad_filter", "conv2d_grad_filter"),
    ({}, "fused_conv_bn_act/conv2d_stats", "conv2d_stats"),
    # fusion's bn+act kernel come back (deleted in PR 34 on the chip's
    # evidence: a Mosaic call in this step costs a relayout each way)
    ({}, "fused_conv_bn_act/bn_act", "bn_act"),
    # a counted hit, whatever the step holds
    ({"op=conv2d_grad": 6}, None, "op=conv2d_grad"),
])
def test_smoke_refuses_a_kernel_in_the_train_step(hits, held, match):
    calls = {held: 3} if held else {}
    with pytest.raises(AssertionError, match=match):
        chip_smoke._check_no_kernels(hits, calls)


def test_smoke_wants_two_flash_kernels_per_layer():
    """The forward and the fused backward (PR 43); a flash_dq call is the
    split backward, which the smoke's sequence length never takes."""
    calls = {"scaled_dot_product_attention/flash_fwd": 4,
             "scaled_dot_product_attention_grad/flash_dkv": 4}
    chip_smoke._check_flash_kernels(calls, 4)
    with pytest.raises(AssertionError, match="4 flash_dq"):
        chip_smoke._check_flash_kernels(
            dict(calls, **{"scaled_dot_product_attention_grad/flash_dq": 4}),
            4)
    calls.pop("scaled_dot_product_attention_grad/flash_dkv")
    with pytest.raises(AssertionError, match="0 flash_dkv"):
        chip_smoke._check_flash_kernels(calls, 4)
