"""The main path's Pallas kernels, compiled at real widths for a TPU v5e
that is described and not attached (on-chip-measurement guide, section 2).

Interpret mode cannot see what Mosaic refuses: a strided value slice, a
tile that outgrows VMEM. Every shape the gates pass must compile
here; every shape a gate declines is pinned by its reason instead. This
is the only file that describes a chip: the topology call lives in a
module-scoped fixture (never at import, in a skipif or in parametrize),
the compiles run in the test's own process, and the kernels are steered
out of the interpreter with monkeypatch, not through an option of the
program.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from benchmarks import run
from paddle_tpu import xplane
from paddle_tpu.ops import (hybrid_ops, kernel_choice, pallas_attention,
                            pallas_conv)
from tools import describe_step

BF16 = jnp.bfloat16
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; conftest.py turns the cache
    on, so it goes off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    """Kernels lower for Mosaic, not the interpreter. pallas_conv imports
    `_interpret` by name, so both modules are patched."""
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_conv, "_interpret", lambda: False)


@pytest.fixture
def no_room(monkeypatch):
    """The described chip with room to keep no recomputation segment (its
    limit read as the estimate's margin alone): every segment a
    checkpointed step spells is replayed, as the full-depth Kimi-Linear
    and Qwen3-Next cells' are. A cut of two or three layers would keep
    them all under the chip's own 15.75 GiB (recompute.py), and the
    tests of what a replayed segment compiles to would read nothing."""
    from paddle_tpu import memory, recompute
    monkeypatch.setattr(memory, "device_limit",
                        lambda device: recompute.MARGIN_BYTES)


def _float32_under_the_conv(text):
    """The instructions under pd.causal_conv1d or its gradient op that
    write a float32 array of a whole [T, C] activation (2**24 elements
    and more: the cells' are 2.5e7 to 3.6e7)."""
    return [(i.name, i.shape) for i in xplane.hlo_instructions(text)
            if re.search(r"pd\.causal_conv1d(_grad)?/", i.op_name or "")
            and any(np.prod([int(d) for d in dims.split(",")]) >= 2 ** 24
                    for dims in re.findall(r"f32\[([\d,]+)\]", i.shape))]


def _compile(fn, one_chip, *shapes):
    """Names of the Mosaic calls in `fn` compiled for the described chip:
    each pallas_call's `name`, which the optimized HLO keeps in op_name
    (autodiff wraps it: transpose(jvp(flash_dq))) and chip_smoke.py
    counts kernel families by."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sorted(
        re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
        for line in text.splitlines() if KERNEL in line)


# ResNet-50 widths under AMP O2 (C % 128 == 0 from stage 2's outputs on),
# batch cut to 32: (name, x NHWC, w OIHW, stride, padding)
CONV = [
    ("3x3_s1", (32, 28, 28, 128), (128, 128, 3, 3), 1, 1),
    ("1x1_s1", (32, 56, 56, 256), (128, 256, 1, 1), 1, 0),
    ("3x3_s2", (32, 28, 28, 256), (256, 256, 3, 3), 2, 1),
    ("1x1_s2", (32, 56, 56, 256), (512, 256, 1, 1), 2, 0),
]
CONV_IDS = [c[0] for c in CONV]


@pytest.mark.parametrize("name,x,w,s,p", CONV, ids=CONV_IDS)
def test_conv_forward_compiles(mosaic, one_chip, name, x, w, s, p):
    """Every shape the gate passes (asked with the bf16 operands, as the
    O3 route asks before it quantizes) compiles as the int8 kernel."""
    args = ((s, s), (p, p), (1, 1))
    assert pallas_conv.ineligible(jax.ShapeDtypeStruct(x, BF16),
                                  jax.ShapeDtypeStruct(w, BF16),
                                  *args) is None
    assert _compile(
        lambda a, b, dq: pallas_conv.conv2d_q8(a, b, *args, dq), one_chip,
        (x, jnp.int8), (w, jnp.int8), ((w[0],), jnp.float32)) == ["conv2d_q8"]


def test_conv_q8_stride2_compiles(mosaic, one_chip):
    _, x, w, s, p = CONV[2]
    assert _compile(
        lambda a, b, dq: pallas_conv.conv2d_q8(a, b, (s, s), (p, p), (1, 1),
                                               dq),
        one_chip, (x, jnp.int8), (w, jnp.int8),
        ((w[0],), jnp.float32)) == ["conv2d_q8"]


def test_bottleneck_block_is_xla_alone(mosaic, one_chip):
    """One bottleneck block at stage-2 width (28 x 28, 128 -> 512, batch
    32) behind a 1 x 1 stem, forward, backward and Momentum under AMP O2:
    the compiled step holds no Mosaic call, and its entry computation no
    `reshape` or `copy` as wide as the block's narrowest activation. The
    bn+act kernel that PR 34 deleted put three of each around every
    window (a conv's output is laid out {3,0,2,1}; a kernel wants the
    row-major [N*H*W, C] view, and 28 rows fill no bf16 tile) and kept a
    `pred` mask per relu for the backward: a quarter of ResNet-50's step
    on the chip (PERF.md section 6, PR 34)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.framework import unique_name
    from paddle_tpu.models import resnet

    batch, side, mid = 32, 28, 128
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 2 * side, 2 * side],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        stem = resnet.conv_bn_layer(img, 4 * mid, 1, 2, 0)
        block = resnet.bottleneck(stem, 4 * mid, mid, 1)
        pooled = fluid.layers.pool2d(input=block, global_pooling=True,
                                     pool_type="avg")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=pooled, size=10), label))
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
            loss, startup_program=startup)
    fluid.amp.enable(main, level="O2")
    feed = {"img": np.zeros((batch, 3, 2 * side, 2 * side), np.float32),
            "label": np.zeros((batch, 1), np.int64)}
    text = describe_step.compile_program(main, startup, loss, feed,
                                         one_chip).as_text()
    assert KERNEL not in text
    wide = describe_step.wide_instructions(text, batch * side * side * mid - 1)
    assert len(wide) >= 8, wide     # the activations themselves are seen
    moved = [row for row in wide
             if re.match(r"%(reshape|copy)[.\d]*$", row[2])
             or row[1].startswith("pred[")]
    assert not moved, moved


def _dead_subtiles_booked():
    """flash_edge_subtiles_total's dead sub-tiles so far, by kernel: what
    a lowering's walk leaves uncomputed of the tiles an edge crosses."""
    from paddle_tpu import telemetry
    dead = {}
    for series, n in telemetry.read_series(
            "flash_edge_subtiles_total").items():
        labels = dict(pair.split("=") for pair in series.split(","))
        if labels["state"] == "dead":
            dead[labels["kernel"]] = dead.get(labels["kernel"], 0) + n
    return dead


def _walks_in_subtiles(before):
    """Both kernels of a forward and a fused backward lowered since
    `before` skip dead sub-tiles (PR 63)."""
    now = _dead_subtiles_booked()
    return all(now.get(kernel, 0) > before.get(kernel, 0)
               for kernel in ("flash_fwd", "flash_dkv"))


def _flash_fwd_bwd(q, k, v):
    def loss(q, k, v):
        out = pallas_attention.flash_attention(q, k, v, True)
        return out.astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# The cells' per-device shapes first: GPT-2 (16 sequences, 12 heads) and
# GPT-2 large on fsdp=2 x tp=2 (8 sequences, 10 of 20 heads a chip). Heads
# ride a grid axis in blocks of 128 lanes, so the count no longer grows
# VMEM (16 heads in one unrolled loop were refused at T=2048 before PR
# 29); D=256 is a lane block of its own; T=4096 walks two major tiles.
@pytest.mark.parametrize("shape", [(16, 1024, 12, 64), (8, 1024, 10, 64),
                                   (1, 1024, 8, 64), (1, 2048, 16, 64),
                                   (1, 2048, 4, 256), (1, 4096, 8, 32),
                                   (1, 4096, 32, 128), (1, 4096, 20, 256)],
                         ids=["gpt2", "gpt2_large_shard", "8_heads",
                              "16_heads", "head_dim_256", "two_major_d32",
                              "nemotron_h_after_kv_repeat",
                              "latent_attention_expanded"])
def test_flash_fwd_bwd_compiles(mosaic, one_chip, shape):
    """Causal, so since PR 63 the diagonal's tiles are walked in
    sub-tiles: GPT-2's two heads a lane block and the latent cell's head
    of 256 lanes among them."""
    q = jax.ShapeDtypeStruct(shape, BF16)
    assert pallas_attention.ineligible(q, q, q) is None
    before = _dead_subtiles_booked()
    assert _compile(_flash_fwd_bwd, one_chip, *[(shape, BF16)] * 3) == [
        "flash_dkv", "flash_fwd"]
    assert _walks_in_subtiles(before)


def _flash_bwd(q, k, v, do, lse, delta):
    return pallas_attention.flash_attention_bwd_block(
        q, k, v, do, lse, delta, 0, 0, 0.125, True)


@pytest.mark.parametrize("shape,kernels", [
    ((1, 16384, 2, 128), ["flash_dkv"]),
    ((1, 8192, 1, 256), ["flash_dkv"]),
    ((1, 32768, 2, 128), ["flash_dkv", "flash_dq"])],
    ids=["fused_at_the_rule_s_edge", "fused_at_the_edge_at_256_lanes",
         "split_past_it"])
def test_flash_backward_form_by_shape(mosaic, one_chip, shape, kernels):
    """The fused backward's dQ accumulator is float32 over the whole Q
    sequence of a call: the longest one `_split_reason` lets through
    (16384 rows at 128 lanes in bf16: 8 MB of scratch, 8 MB of output
    buffers; 8192 rows at 256 lanes the same) must fit Mosaic's scoped
    VMEM beside the tiles, and twice that keeps the two calls."""
    b, t, h, _ = shape
    stat = ((b, h, t), jnp.float32)
    assert _compile(_flash_bwd, one_chip, *[(shape, BF16)] * 4,
                    stat, stat) == kernels


def _block_diffusion_fwd_bwd(q, k, v):
    from paddle_tpu.ops import nn_ops

    out, lse = nn_ops._bd_flash(q, k, v, 4)
    return nn_ops._bd_flash_grad(q, k, v, out, lse, out, 4)


def test_flash_kernels_compile_under_the_block_mask(mosaic, one_chip):
    """The block-diffusion cell's attention op at its shape, both streams
    of one 4096-token sequence, 32 heads of 128 at equal head counts, in
    blocks of 4: each kernel twice (the clean half block-causal, the
    noisy half against the clean keys of strictly earlier blocks)."""
    shape = (2, 4096, 32, 128)
    one = jax.ShapeDtypeStruct((1,) + shape[1:], BF16)
    assert pallas_attention.ineligible(one, one, one, block=4) is None
    assert _compile(_block_diffusion_fwd_bwd, one_chip,
                    *[(shape, BF16)] * 3) == [
        "flash_dkv", "flash_dkv", "flash_fwd", "flash_fwd"]


def _masked_fwd_bwd(window, block, q_off, q, k, v):
    scale = q.shape[-1] ** -0.5
    if block == 1:
        out, lse = pallas_attention._forward(q, k, v, True, return_lse=True,
                                             window=window)
    else:       # one part of block-diffusion attention, merged by the op
        out, _, lse = pallas_attention.flash_attention_block(
            q, k, v, q_off, 0, scale, True, block=block)
        out = out.astype(q.dtype)
    delta = jnp.sum(out.astype(jnp.float32) ** 2, -1).transpose(0, 2, 1)
    return pallas_attention.flash_attention_bwd_block(
        q, k, v, out, lse, delta, q_off, 0, scale, True, block=block,
        window=window)


@pytest.mark.parametrize("window", [4096, 4000, 600],
                         ids=["the_cells", "no_multiple_of_a_tile",
                              "shorter_than_two_tiles"])
def test_flash_kernels_compile_under_a_window(mosaic, one_chip, window):
    """The sliding-window cell's attention op at its shape at equal head
    counts, one 8192-token sequence, 28 heads of 128, four major
    tiles of 2048 rows: the forward and the fused backward (dQ's
    accumulator over 8192 rows is 4 MB a lane block) whose walk ranges
    and index maps take the window's far edge; a window that makes the
    edge tile's predicate partial, and one so short that a block crosses
    both edges."""
    shape = (1, 8192, 28, 128)
    one = jax.ShapeDtypeStruct(shape, BF16)
    assert pallas_attention.ineligible(one, one, one) is None
    assert _compile(functools.partial(_masked_fwd_bwd, window, 1, 0),
                    one_chip, *[(shape, BF16)] * 3) == ["flash_dkv",
                                                         "flash_fwd"]


@pytest.mark.parametrize("t,heads,kv_heads,window,block,q_off", [
    (8192, 64, 8, 512, 1, 0), (8192, 48, 8, 0, 1, 0),
    (8192, 28, 4, 4096, 1, 0), (4096, 32, 4, 0, 4, 0),
    (4096, 32, 4, 0, 4, -4)],
    ids=["laguna_window", "laguna_full", "sliding_window",
         "block_diffusion_clean", "block_diffusion_earlier"])
def test_flash_kernels_read_kv_at_their_own_heads(mosaic, one_chip, t, heads,
                                                  kv_heads, window, block,
                                                  q_off):
    """The three grouped-query cells' attention calls at their shapes
    (PR 61): K, V, dK and dV of the K/V heads' count beside a Q of 6 to 8
    times as many heads of 128, the forward and the backward as ONE fused
    `flash_dkv` on the grid (B, K/V heads, members, K tiles, Q major
    tiles) with dQ's accumulator and a group's float32 dK / dV sums over
    8192 rows in VMEM: the rule sends none of them to the two calls."""
    from paddle_tpu import telemetry
    q, kv = (1, t, heads, 128), (1, t, kv_heads, 128)
    assert pallas_attention.ineligible(
        *(jax.ShapeDtypeStruct(s, BF16) for s in (q, kv, kv)),
        block=block) is None
    before = dict(telemetry.read_series("flash_backward_total"))
    dead = _dead_subtiles_booked()
    assert _compile(
        functools.partial(_masked_fwd_bwd, window, block, q_off), one_chip,
        (q, BF16), (kv, BF16), (kv, BF16)) == ["flash_dkv", "flash_fwd"]
    fused = "form=fused,reason="
    assert dict(telemetry.read_series("flash_backward_total")) == dict(
        before, **{fused: before.get(fused, 0) + 1})
    # Laguna's window of one tile, where no tile is open, and the noisy
    # stream's q_off = -block among them: the edges walked in sub-tiles
    assert _walks_in_subtiles(dead)


def _flash_calls(text):
    """(kernel, operand shapes, result shape) of each flash call in an
    optimized HLO: the operands from the call's layout constraints."""
    calls = []
    for line in text.splitlines():
        name = re.search(r"(flash_\w+)\)*/pallas_call", line)
        if KERNEL in line and name:
            operands = re.search(
                r"operand_layout_constraints=\{(.*?\})\}", line).group(1)
            calls.append((name.group(1),
                          re.findall(r"\w+\[[\d,]*\]", operands),
                          line.split(" custom-call(")[0]))
    return calls


def _kv_groups_booked():
    from paddle_tpu import telemetry
    return dict(telemetry.read_series("attention_kv_groups_total"))


@pytest.mark.parametrize("rows,dtype", [
    (4096 * 6, BF16), (4096 * 6, jnp.float32), (6144, BF16), (3072, BF16)],
    ids=["bf16", "float32_no_amp", "second_rung", "first_rung"])
def test_grouped_expert_products_compile(mosaic, one_chip, rows, dtype):
    """The hybrid cell's expert layer: 4096 tokens x top-6 rows of 2688
    (and the rungs of its capacity ladder before them: 6144, PR 36's
    first and the second since PR 69, and 3072, the first) through 8 held
    experts of width 1856 and back, at the tiles the sweep chose: the up
    product forward (the down product's result is not needed for a
    gradient of its sum), and for each of the two its backward products
    (gmm on the rows, tgmm on the weights)."""
    d, f, held = 2688, 1856, 8
    assert hybrid_ops.gmm_ineligible(rows, d, f) is None
    assert rows in hybrid_ops._capacity_ladder(4096 * 6, held, 128)

    def grads(x, w1, w2, sizes):
        return jax.grad(lambda *a: hybrid_ops._grouped_products(
            *a, sizes, False)[0].astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(x, w1, w2)

    assert _compile(grads, one_chip, ((rows, d), dtype), ((held, d, f), dtype),
                    ((held, f, d), dtype), ((held,), jnp.int32)) == [
        "gmm"] * 3 + ["tgmm"] * 2


@pytest.mark.parametrize("under_switch", [False, True],
                         ids=["alone", "in_a_switch_branch"])
@pytest.mark.parametrize("n,k,d,c,held,experts", [
    (8192, 8, 2048, 16384, 8, 128), (8192, 6, 2560, 49152, 8, 64),
    (8192, 8, 2048, 8192, 8, 128), (16384, 10, 2048, 20480, 32, 512)],
    ids=["block_diffusion_cell", "sliding_window_cell",
         "block_diffusion_cell_first_rung", "head_decay_cell_first_rung"])
def test_pair_sum_compiles(mosaic, one_chip, n, k, d, c, held, experts,
                           under_switch):
    """The token side of the two cells without a shared expert (PR 47):
    8192 positions x top 8 of 2048 over the 16384-row rung, and 8192
    tokens x top 6 of 2560 over all 49152 pairs, 8 experts held: the
    forward's weighted map from the grouped product's bf16 rows to
    float32 and the gradient's from the bf16 cotangent to bf16, as
    hybrid_ops hands them to the kernel, alone and as the work of a
    lax.switch branch (the ladder's). Since PR 69 also the rungs at
    twice a uniform router's share: the first cell's 8192 rows, and the
    20480 of 16384 tokens x top 10 over 32 held experts (the head-decay
    delta-rule cell's), 32 x 16 lanes being the kernel's limit."""
    from paddle_tpu.ops import pallas_pair_sum
    assert c in hybrid_ops._capacity_ladder(n * k, held, experts)
    assert pallas_pair_sum.ineligible(n, c, d, held) is None
    tiles = n // pallas_pair_sum._TILE

    def maps(rows, ct, pos, windows, live, weight):
        return (hybrid_ops._pairs_summed(rows, pos, windows, live, weight),
                hybrid_ops._pairs_summed(ct, pos, windows, live,
                                         out_dtype=ct.dtype))

    def switched(rung, *operands):
        return jax.lax.switch(rung, [maps, lambda *a: maps(*a)[::-1][::-1]],
                              *operands)

    shapes = (((c, d), BF16), ((c, d), BF16), ((n, k), jnp.int32),
              ((2, tiles, held), jnp.int32), ((), jnp.int32),
              ((n, k), jnp.float32))
    if under_switch:
        assert _compile(switched, one_chip, ((), jnp.int32), *shapes) == [
            "pair_sum"] * 4
    else:
        assert _compile(maps, one_chip, *shapes) == ["pair_sum"] * 2


def _scan_fwd_bwd(chunk, dtype):
    from paddle_tpu.ops import pallas_scan

    def grads(x, dt, a, b, c):
        return jax.grad(lambda *v: pallas_scan.ssd_scan_kernels(
            *v, chunk, dtype).sum(), argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    return grads


@pytest.mark.parametrize("t,chunk,dtype", [
    (4096, 128, BF16), (4096, 128, jnp.float32), (2048, 256, BF16)],
    ids=["hybrid_cell", "float32_no_amp", "chunk_256"])
def test_scan_kernels_compile(mosaic, one_chip, t, chunk, dtype):
    """The hybrid cell's scan, [1, 4096, 64 heads x 64] over 8 groups of
    state 128 in chunks of 128: the forward kernel and the gradient's,
    each inside the 16 MB of scoped VMEM a Mosaic call has by default
    (ops/pallas_scan.py asks for no more); without AMP the operands are
    float32 and the blocks twice the size; a chunk of 256 makes the
    score and mask blocks [256, 256]."""
    h, p, g, n = 64, 64, 8, 128
    assert hybrid_ops.ssd_scan_ineligible(chunk, h // g, p, n) is None
    assert _compile(_scan_fwd_bwd(chunk, dtype), one_chip,
                    ((1, t, h, p), dtype), ((1, t, h), jnp.float32),
                    ((h,), jnp.float32), ((1, t, g, n), dtype),
                    ((1, t, g, n), dtype)) == ["ssd_scan_bwd", "ssd_scan_fwd"]


@pytest.mark.parametrize("chunk", [256, 128])
def test_scan_kernels_compile_at_one_group_of_64_heads(mosaic, one_chip,
                                                       chunk):
    """The granite cell's scan, [1, 8192, 64 heads x 64] over ONE group of
    state 128: a step that owned the group's 64 heads would want x, y, dy
    and dx blocks of [4096, chunk] and a 2 MB state, and Mosaic refuses
    it for VMEM (PR 49); as 4 head blocks of 16, or 2 of 32 at chunk 128,
    that read the same B and C
    both kernels compile at the published chunk of 256 and at the 128 the
    cell is lowered with, no fallback."""
    from paddle_tpu.ops import pallas_scan
    t, h, p, g, n = 8192, 64, 64, 1, 128
    assert hybrid_ops.ssd_scan_ineligible(chunk, h // g, p, n) is None
    assert pallas_scan.heads_a_step(h // g, chunk) == {256: 16, 128: 32}[chunk]
    assert _compile(_scan_fwd_bwd(chunk, BF16), one_chip,
                    ((1, t, h, p), BF16), ((1, t, h), jnp.float32),
                    ((h,), jnp.float32), ((1, t, g, n), BF16),
                    ((1, t, g, n), BF16)) == ["ssd_scan_bwd", "ssd_scan_fwd"]


@pytest.mark.parametrize("chunk,dtype,heads", [
    (64, BF16, 8), (64, jnp.float32, 4), (128, BF16, 4), (32, BF16, 8)],
    ids=["kimi_cell", "float32_no_amp", "chunk_128", "chunk_32"])
def test_delta_rule_kernels_compile(mosaic, one_chip, chunk, dtype, heads):
    """The Kimi-Linear cell's delta rule, [1, 8192, 32 heads x 128] in
    chunks of 64 with bf16 operands: the forward kernel and the
    gradient's, each inside the 16 MB of scoped VMEM a Mosaic call has by
    default at the heads a step pallas_kda.heads_a_step gives (sixteen
    heads a step compile and are refused for VMEM when the gradient's
    kernel is loaded on the chip: PR 56); without AMP the operands are
    float32 and a step owns half the heads; a chunk of 128 is one head a
    pack (its [128, 128] system fills a lane block alone), one of 32 two
    heads in half a lane block."""
    from paddle_tpu.ops import pallas_kda
    t, h, k, v = 8192, 32, 128, 128
    assert hybrid_ops.kda_scan_ineligible(chunk, k, v) is None
    assert pallas_kda.heads_a_step(h, chunk, jnp.dtype(dtype).itemsize) \
        == heads

    def grads(*operands):
        return jax.grad(lambda *a: pallas_kda.kda_scan_kernels(
            *a, chunk, 1e-6, dtype=dtype).astype(jnp.float32).sum(),
            argnums=tuple(range(7)))(*operands)

    assert _compile(grads, one_chip, ((1, t, h, k), dtype),
                    ((1, t, h, k), dtype), ((1, t, h, v), dtype),
                    ((1, t, h, k), dtype), ((h,), jnp.float32),
                    ((h * k,), jnp.float32), ((1, t, h), dtype)) \
        == ["kda_scan_bwd", "kda_scan_fwd"]


@pytest.mark.parametrize("key_heads,dtype,heads", [
    (16, BF16, 8), (32, BF16, 8), (16, jnp.float32, 4)],
    ids=["qwen3_next_cell", "equal_heads", "float32_no_amp"])
def test_head_decay_delta_rule_kernels_compile(mosaic, one_chip, key_heads,
                                               dtype, heads):
    """The Qwen3-Next cell's delta rule, [1, 16384, 16 key | 32 value
    heads x 128] in chunks of 64 under a decay a head: the forward kernel
    and the gradient's (`gdn_scan_fwd` / `gdn_scan_bwd`: the channel
    form's keep their names) at the heads a step
    pallas_kda.heads_a_step gives, whole groups of value heads; q, k and
    the gate reach the kernels at their own sizes (no repeat, no
    broadcast: the calls' operands are [1, T, 16 x 128] and [1, 4, T,
    8])."""
    from paddle_tpu.ops import pallas_kda
    t, h, k, v, chunk = 16384, 32, 128, 128, 64
    ratio = h // key_heads
    assert hybrid_ops.kda_scan_ineligible(chunk, k, v, ratio, True) is None
    assert pallas_kda.heads_a_step(h, chunk, jnp.dtype(dtype).itemsize,
                                   ratio) == heads

    def grads(*operands):
        return jax.grad(lambda *a: pallas_kda.kda_scan_kernels(
            *a, chunk, 1e-6, dtype=dtype).astype(jnp.float32).sum(),
            argnums=tuple(range(7)))(*operands)

    assert _compile(grads, one_chip, ((1, t, key_heads, k), dtype),
                    ((1, t, key_heads, k), dtype), ((1, t, h, v), dtype),
                    ((1, t, h), dtype), ((h,), jnp.float32),
                    ((h,), jnp.float32), ((1, t, h), dtype)) \
        == ["gdn_scan_bwd", "gdn_scan_fwd"]


@pytest.mark.parametrize("t,key_heads,per_head,dtype,name", [
    (8192, 32, False, BF16, "kda_scan"), (16384, 16, True, BF16, "gdn_scan"),
    (8192, 32, False, jnp.float32, "kda_scan")],
    ids=["kimi_cell", "qwen3_next_cell", "float32_no_amp"])
def test_given_inverse_delta_rule_kernels_compile(mosaic, one_chip, t,
                                                  key_heads, per_head, dtype,
                                                  name):
    """The forward kernel a replayed kda_scan op runs at the two cells'
    shapes (PR 65): handed the float32 inverses of an earlier run as its
    last operand, it is one Mosaic call under the forward's name +
    `_given` inside the default scoped VMEM, beside the plain forward and
    the backward alone: the three calls a checkpointed layer's step
    holds."""
    from paddle_tpu.ops import pallas_kda
    h, k, v, chunk = 32, 128, 128, 64
    gate = ((1, t, h), dtype) if per_head else ((1, t, h, k), dtype)
    bias = ((h,), jnp.float32) if per_head else ((h * k,), jnp.float32)
    shapes = (((1, t, key_heads, k), dtype), ((1, t, key_heads, k), dtype),
              ((1, t, h, v), dtype), gate, ((h,), jnp.float32), bias,
              ((1, t, h), dtype))

    def layer(*a):
        out, entering, inverse = pallas_kda.kda_scan_forward(
            *a, chunk, 1e-6, dtype=dtype)
        again, entering, inverse = pallas_kda.kda_scan_forward(
            *a, chunk, 1e-6, dtype=dtype, inverse=inverse)
        return pallas_kda.kda_scan_backward(
            *a, entering, inverse, out + again, chunk, 1e-6, dtype=dtype)

    assert _compile(layer, one_chip, *shapes) == [
        name + "_bwd", name + "_fwd", name + "_fwd_given"]


@pytest.mark.parametrize("t,c,bias,lanes", [
    (8192, 4096, False, False), (8192, 4352, True, True),
    (4096, 6144, True, True), (8192, 4352, True, False)],
    ids=["kimi_cell", "granite_cell", "hybrid_cell", "rows_at_4352"])
@pytest.mark.parametrize("dtype", [BF16, jnp.float32],
                         ids=["bf16", "float32_no_amp"])
def test_short_conv_kernels_compile(mosaic, one_chip, dtype, t, c, bias,
                                    lanes):
    """The three cells' short convolutions, [1, T, C] under four taps:
    the forward kernel and the gradient's of ops/pallas_conv1d.py at the
    blocks and chunks its table gives, inside the 16 MB of scoped VMEM a
    Mosaic call has by default; time on the sublanes as kda_mixer builds
    it (no Bias), on the lanes as mamba2_mixer does; C = 4352 = 34 lane
    blocks takes blocks of 256 channels; without AMP the operands are
    float32."""
    from paddle_tpu.ops import pallas_conv1d
    assert pallas_conv1d.ineligible(t, c, 4, dtype) is None

    def both(x, w, b, d_out):
        b = b if bias else None
        return (pallas_conv1d.causal_conv1d_fwd(x, w, b, lanes=lanes),
                pallas_conv1d.causal_conv1d_bwd(x, w, b, d_out, lanes=lanes))

    assert _compile(both, one_chip, ((1, t, c), dtype),
                    ((c, 4), jnp.float32), ((c,), jnp.float32),
                    ((1, t, c), dtype)) \
        == ["causal_conv1d_bwd", "causal_conv1d_fwd"]


@pytest.mark.parametrize("dtype", [BF16, jnp.float32],
                         ids=["bf16", "float32_no_amp"])
@pytest.mark.parametrize("lanes", [False, True],
                         ids=["time_on_sublanes", "time_on_lanes"])
def test_gated_short_conv_kernels_compile(mosaic, one_chip, dtype, lanes):
    """LFM2's operator at the new cell's [1, 16384, 2048] under three
    taps, no bias, no activation (PR 62): the gated forward kernel (X and
    two gates in, Out out) and the gated gradient's (X and the gate ahead
    twice each, the gate behind, the cotangent; three gradients and the
    taps' sums out) at the plain form's blocks and chunks, inside the
    16 MB of scoped VMEM a Mosaic call has by default, under names of
    their own; rows as short_conv_mixer builds it, and the other
    orientation."""
    from paddle_tpu.ops import pallas_conv1d
    t, c = 16384, 2048
    assert pallas_conv1d.ineligible(t, c, 3, dtype) is None

    def both(x, pre, post, w, d_out):
        form = dict(pre_gate=pre, post_gate=post, activation="identity",
                    lanes=lanes)
        return (pallas_conv1d.causal_conv1d_fwd(x, w, None, **form),
                pallas_conv1d.causal_conv1d_bwd(x, w, None, d_out, **form))

    rows = ((1, t, c), dtype)
    assert _compile(both, one_chip, rows, rows, rows, ((c, 3), jnp.float32),
                    rows) == ["gated_conv1d_bwd", "gated_conv1d_fwd"]


_HYBRID_ME = {}


def _hybrid_mixer_and_expert_step(one_chip):
    """The hybrid cell's step at its own 4096 tokens and published widths,
    the depth cut to one mixer and one expert layer, compiled once for
    the tests that read it (call under the `mosaic` fixture)."""
    if not _HYBRID_ME:
        cell = run.load_json("workloads", "nemotron3-nano.train-ep16-share")
        config = dict(run.load_json("configs", cell["config"]),
                      hybrid_override_pattern="ME", num_hidden_layers=2)
        _HYBRID_ME["text"] = describe_step.compile_step(
            cell, config, one_chip).as_text()
    return _HYBRID_ME["text"]


def test_hybrid_expert_layer_step_holds_one_switch_each_way(mosaic, one_chip):
    """One mixer and one expert layer of the hybrid cell: the expert
    layer's forward and its gradient op are one conditional each, three
    branches (the rungs 3072, 6144 and 24576); a forward branch runs the two
    grouped products and a gradient branch their two partners on the rows
    and two on the weights and NO forward product (PR 58: it reads the up
    product's rows the forward kept); the token side's kernel runs once in
    each of the six branches (PR 47: the forward's weighted map, the
    gradient's pulled-back one). The mixer's scan is one forward kernel
    (the one the gradient op traces again merged with it) and one gradient
    kernel."""
    text = _hybrid_mixer_and_expert_step(one_chip)
    # (the step's one other conditional is the executor's own, outside
    # any op: `jit(fn)/cond`)
    switches = [line for line in text.splitlines()
                if " conditional(" in line and line.count("%region") == 3
                and 'op_name="jit(fn)/cond"' not in line]
    assert len(switches) == 2, switches
    assert sum("pd.moe_experts/cond" in line for line in switches) == 1
    assert sum("pd.moe_experts_grad/cond" in line for line in switches) == 1
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    assert {k: kernels.count(k) for k in set(kernels)} == {
        "gmm": 3 * (2 + 2), "tgmm": 3 * 2, "pair_sum": 3 * 2,
        # each smaller rung's kept product at the head of a buffer nothing
        # fills (hybrid_ops._over_all_pairs)
        "unwritten_rows": 2,
        "ssd_scan_fwd": 1, "ssd_scan_bwd": 1,
        "causal_conv1d_fwd": 1, "causal_conv1d_bwd": 1}
    assert not _float32_under_the_conv(text)


def test_hybrid_mixer_step_holds_no_chunk_by_chunk_block(mosaic, one_chip):
    """The same step: no instruction under the scan or its gradient
    writes a [chunks, heads, chunk, chunk] array (32 chunks of 128 steps,
    64 heads in 8 groups: ssd_scan_chunked's mask and decayed scores,
    134 MB each in float32), in any dtype; the blocks live in the
    kernels' VMEM. What the two ops do hold is the entering states."""
    text = _hybrid_mixer_and_expert_step(one_chip)
    under = [line for line in text.splitlines()
             if re.search(r"pd\.ssd_scan(_grad)?/", line)]
    assert any(KERNEL in line for line in under)
    blocks = [line for line in under
              if re.search(r"\[(1,)?32,(8,8|64),128,128\]", line)]
    assert not blocks, blocks[:3]
    assert any("bf16[1,32,4096,128]" in line for line in under)


_GPT2_LAYER = {}


def _gpt2_one_layer_step(one_chip):
    """One layer of the `gpt2` cell's train step at its batch of 16,
    compiled once for the tests that read it (call under the `mosaic`
    fixture): (cell, config, compiled)."""
    if not _GPT2_LAYER:
        cell = run.load_json("workloads", "gpt2.train-t1024")
        config = dict(run.load_json("configs", cell["config"]), n_layer=1)
        _GPT2_LAYER["step"] = (
            cell, config, describe_step.compile_step(cell, config, one_chip))
    return _GPT2_LAYER["step"]


def test_gpt2_step_holds_no_float32_logits(mosaic, one_chip):
    """One layer of the `gpt2` cell's train step at its batch of 16: the
    loss reads the head's bf16 logits where they lie. A float32 array of
    the logits' size (the convert hoisted into the relayout copy, or the
    log-probabilities written out for a gather) is 3.3 GB written and
    read again, 10 ms each on the chip, and only the whole step shows it
    (head and loss alone fuse well); with either, the temporaries were
    6.62 GB against 3.73 (PERF.md section 6, PR 31)."""
    cell, config, compiled = _gpt2_one_layer_step(one_chip)
    text = compiled.as_text()
    rows = cell["batch"] * config["n_positions"]
    wide = describe_step.wide_instructions(text, rows * 1024)
    logits = [shape for elements, shape, _, _ in wide
              if elements == rows * config["vocab_size"]]
    assert logits and all(s.startswith("bf16[") for s in logits), wide
    gathers = [line for line in text.splitlines()
               if " gather(" in line and "50257" in line]
    assert not gathers, gathers
    assert compiled.memory_analysis().temp_size_in_bytes < 4.2e9


def test_gpt2_step_evaluates_gelu_outside_every_products_operand(
        mosaic, one_chip):
    """The same step: erf gelu is 144 float32 instructions an element on
    a v5e, and a product that takes them into an operand runs at the
    vector unit's pace (38-42 % of the MXU's for the down projection and
    its grad-weight product, PERF.md section 6, PR 44). The op's erfc is
    pinned (`math_ops.KEPT_ACTS`), so no fused computation that is an
    operand inside a fusion holding a convolution carries it, the
    polynomial lives in at most two computations a layer (one: the up
    projection's epilogue; the parent held it in three more: both of
    those operands and the grad-input product's epilogue), and the kept
    array (100.7 MB in bf16, beside the pre-activation) costs under
    0.25 GB of temporaries a layer over the parent's 3.73."""
    _, _, compiled = _gpt2_one_layer_step(one_chip)
    text = compiled.as_text()
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(2)
            comps[name] = (bool(head.group(1)), [])
        elif name is not None:
            comps[name][1].append(line)
    erfc = {n for n, (entry, lines) in comps.items()
            if not entry and any("pd.gelu/erfc" in line for line in lines)}
    assert 1 <= len(erfc) <= 2, sorted(erfc)
    products = [lines for _, lines in comps.values()
                if any(" convolution(" in line for line in lines)]
    assert products
    operands = {m.group(1) for lines in products for line in lines
                for m in [re.search(r" fusion\(.*calls=%([\w.\-]+)", line)]
                if m}
    assert operands
    assert not operands & erfc, sorted(operands & erfc)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.73e9 + 0.25e9


def test_gpt2_step_keeps_no_parameter_of_before_its_update(mosaic, one_chip):
    """The same step, the observatory on as every cell runs it: the
    sampled table takes an update's norm from the rule's own step
    (`dynamics._resolve_step`), so nothing reads a parameter's old value
    behind the update that overwrites its donated buffer, and the entry
    computation holds no `copy` without an `op_name` in a parameter's
    shape (the parent copied every float32 master ahead of its update,
    sampled step or not: 308.8 MB each for the table and the head, PERF.md
    section 6, PR 50); its temporaries are within 1 % of the step compiled
    with the observatory off."""
    from paddle_tpu import dynamics

    cell, config, compiled = _gpt2_one_layer_step(one_chip)
    main, _, _ = run.load_module("families", config["family"]).build(config)
    shapes = {tuple(sorted(p.shape))
              for p in main.global_block().all_parameters()}
    kept = [i.name + " " + i.shape
            for i in xplane.hlo_instructions(compiled.as_text())
            if i.entry and i.opcode == "copy" and not i.op_name
            for dims in [re.match(r"f32\[([\d,]+)\]", i.shape)]
            if dims and tuple(sorted(map(int, dims.group(1).split(","))))
            in shapes]
    assert not kept, kept
    with dynamics.override(False):
        bare = describe_step.compile_step(cell, config, one_chip)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - bare.memory_analysis().temp_size_in_bytes) \
        < 0.01 * temp


PLANNED_CELL = "gpt2-large.train-fsdp2-tp2"
_PLANNED_LAYER = {}


def _planned_layer_step(topo):
    """One layer of `gpt2-large.train-fsdp2-tp2`, planned fsdp=2 x tp=2
    over the described chips and compiled as the executor compiles a
    planned step, once for the tests that read it (call under the
    `mosaic` fixture): (cell, compiled text, what the trace added to
    each telemetry series)."""
    from paddle_tpu import telemetry

    def series():
        return {name + "{" + labels + "}": v
                for name in ("sibling_products_merged_total",
                             "tp_gather_pinned_total")
                for labels, v in telemetry.read_series(name).items()}

    if not _PLANNED_LAYER:
        cell = run.load_json("workloads", PLANNED_CELL)
        config = dict(run.load_json("configs", cell["config"]), n_layer=1)
        sizes = tuple(cell["mesh"].values())
        mesh = Mesh(np.array(topo.devices[:4]).reshape(sizes),
                    tuple(cell["mesh"]))
        before = series()
        text = describe_step.compile_step(cell, config, mesh).as_text()
        _PLANNED_LAYER["step"] = (cell, text, {
            k: v - before.get(k, 0) for k, v in series().items()})
    return _PLANNED_LAYER["step"]


def _counted(added, name, label=""):
    return sum(v for k, v in added.items()
               if k.startswith(name) and label in k)


def test_planned_gpt2_large_layer_reduces_qkv_input_gradient_once(
        mosaic, topo):
    """One layer of the planned cell: the input gradients of q, k and v
    are one contraction (`ops/sibling_products.py`), so the tp axis
    reduces 5 arrays of a whole `bf16[8192, 1280]` activation a step and
    chip, none of them in a tuple (Megatron's 4 a layer and the head's
    input gradient; the parent reduced 7, three of them in one tuple,
    147.6 MB where this reads 105.6), and the cotangents' stack is an
    operand of the product, not an array of its own (PERF.md section 6,
    PR 48)."""
    cell, text, added = _planned_layer_step(topo)
    assert _counted(added, "sibling_products_merged_total") == 1
    whole = re.compile(r"bf16\[(8192,1280|8,1024,1280)\]")
    carried = [len(whole.findall(i.shape))
               for i in xplane.hlo_instructions(text, mesh=cell["mesh"])
               if i.kind == "all-reduce" and i.axis == "tp"]
    assert sum(carried) == 5 and max(carried) == 1, carried
    rows = describe_step.collective_rows(text, cell["mesh"], 8192)
    assert sum(row[2] for (axis, kind, _, _), row in rows.items()
               if (axis, kind) == ("tp", "all-reduce")) < 110e6
    entry = text[text.index("ENTRY "):]
    assert "bf16[3,8192,640]" in text and \
        not re.search(r"= bf16\[3,8192,640\]", entry)


def test_planned_gpt2_large_layer_gathers_each_value_once(mosaic, topo):
    """The same step: what a column-parallel product reads and what a
    row-parallel product's gradient reads crosses the tp axis once
    (`tensor_parallel.gather_once`). The rule constrained 4 operands (q,
    k, v, up) and 2 cotangents (out, down); the tp axis gathers a whole
    `[8192, 1280]` activation in at most 2 + 2 collectives a layer and
    the head's one each way (counted by `channel_id`: an asynchronous
    gather is an instruction in every computation that holds a piece of
    it), every one in bf16 (a pin on the norm's output gathers float32,
    PERF.md section 7), nothing crosses it as an all-to-all (what a pin
    of T over tp gives), and the all-reduces are PR 48's five. The
    operand is pinned in the op's own shape, so the up projection is the
    3-D product it was and gelu's erfc still rides in its epilogue
    (pinned as `[8192, 1280]` it took a pass of its own, 10.7 ms more
    busy time a step on the chip, PERF.md section 6, PR 52)."""
    cell, text, added = _planned_layer_step(topo)
    assert _counted(added, "tp_gather_pinned_total", "side=operand") == 4
    assert _counted(added, "tp_gather_pinned_total", "side=cotangent") == 2
    rows = describe_step.collective_rows(text, cell["mesh"], 8192)
    gathers = {dtype: row for (axis, kind, what, dtype), row in rows.items()
               if (axis, kind, what) == ("tp", "all-gather", "activation")}
    assert set(gathers) == {"bf16"}, rows
    assert gathers["bf16"][1] <= 2 + 2 + 2, rows
    assert not [key for key in rows if key[:2] == ("tp", "all-to-all")], rows
    assert rows[("tp", "all-reduce", "activation", "bf16")][:2] == [5, 5]
    erfc = [comp for comp in re.split(r"\n(?=%|ENTRY )", text)
            if "pd.gelu/erfc" in comp and not comp.startswith("ENTRY")]
    assert erfc and all(" convolution(" in comp for comp in erfc), len(erfc)


MLA_CELL = "glm-4.7-flash.train-mla-mtp-ep8-share"


def test_latent_attention_step_compiles_with_one_cast_of_the_shared_head(
        mosaic, one_chip):
    """The latent-attention cell's step at its own 4096 tokens and
    published widths, the depth cut to the dense block and the prediction
    module (the whole depth takes eight minutes here: the `slow` test
    below): both attention ops on the flash kernels at head size 256,
    the gated experts on gmm / tgmm, and ONE bf16 cast of the head the
    main model and the module both read (the compiler merges the two
    readers' converts; it may rematerialise that one, `.remat` clones of
    the same instruction), made by the main model's product."""
    cell = run.load_json("workloads", MLA_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=1)
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    # the module's expert layer, an eighth of the experts held: two rungs
    # (4096 | 16384, PR 58) under one switch in the op and one in its
    # gradient op. A forward branch runs the three grouped products, a
    # gradient branch each one's two partners (gmm for the rows', tgmm for
    # the weights') and no forward product: it reads the forward's Up and
    # GateUp. The token side's kernel, forward and pulled back, a rung.
    count = {k: kernels.count(k) for k in set(kernels)}
    assert count == {"flash_fwd": 2, "flash_dkv": 2, "gmm": 2 * (3 + 3),
                     "tgmm": 2 * 3, "pair_sum": 2 * 2,
                     # the small rung's two kept products, each at the head
                     # of a buffer nothing fills (_over_all_pairs)
                     "unwritten_rows": 2}
    assert "pd.moe_experts/cond" in text
    assert "pd.moe_experts_grad/cond" in text
    under_grad = [line for line in text.splitlines() if KERNEL in line
                  and "pd.moe_experts_grad/" in line]
    assert len(under_grad) == 2 * (3 + 3 + 1)
    width = "[%d,%d]" % (config["hidden_size"], config["vocab_size"])
    casts = re.findall(
        r"%([\w.\-]+) = bf16" + re.escape(width) + r"[^=]*? convert\("
        r"[^\n]*op_name=\"([^\"]*)\"", entry)
    assert {name.split(".remat")[0] for name, _ in casts} and \
        len({name.split(".remat")[0] for name, _ in casts}) == 1, casts
    assert all("pd_scope.mtp_block" not in op_name for _, op_name in casts)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


@pytest.mark.slow
def test_latent_attention_cell_fits_the_chip_at_4096_tokens(mosaic, one_chip):
    """The sizing rule's compile (PERF.md section 4): the whole step. Its
    Mosaic calls: the six attention ops' flash_fwd and fused flash_dkv
    (PR 43), and since PR 58 the five expert layers' two rungs each, a
    forward branch its three grouped products and a gradient branch their
    three partners on the rows and three on the weights (no forward
    product: before PR 58 the generic gradient traced 15 again, which
    this harness's XLA_FLAGS merged with the originals and
    tools/describe_step.py's bare environment and the chip did not),
    a rung's `pair_sum` forward and pulled back (PR 47) and the small
    rung's two empty-bodied calls (_over_all_pairs): 12 + 5 x (2 x (6 + 3
    + 2) + 2) in either environment; 6.15 GB of temporaries + 8.48 GB of
    aliased state before the ladder."""
    cell = run.load_json("workloads", MLA_CELL)
    config = run.load_json("configs", cell["config"])
    compiled = describe_step.compile_step(cell, config, one_chip)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert text.count(KERNEL) == 12 + 5 * (2 * (6 + 3 + 2) + 2)
    assert text.count("flash_fwd") and "flash_dq" not in text
    assert mem.alias_size_in_bytes > 8.4e9
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


BD_CELL = "sdar-30b-a3b.train-bd4-t4096-ep16-share"


def test_block_diffusion_step_compiles_with_no_square_of_scores(
        mosaic, one_chip):
    """The block-diffusion cell's step at its own 4096-token sequence and
    published widths, the depth cut to one block (the six take eight
    minutes here; tools/describe_step.py sized them: 7.53 GB of
    temporaries + 5.03 GB of aliased state): the attention op on the
    flash kernels, two runs of each (the clean half and the noisy half's
    view of the clean keys), the experts on gmm / tgmm behind the ladder's
    switch (8 of 128 held: three rungs), and under the attention's scope no
    array larger than the op's own operands: nothing of [., L, L] or
    [., 2L, 2L] reaches HBM."""
    from paddle_tpu import xplane
    cell = run.load_json("workloads", BD_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=1)
    length = config["sequence_length"]
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    assert flash == {"flash_fwd": 2, "flash_dkv": 2}
    assert "gmm" in kernels and "tgmm" in kernels
    # the token side, forward and pulled back, in each of the three rungs
    # (PR 47; PR 69: 8192 | 16384 | 65536)
    assert kernels.count("pair_sum") == 2 * 3
    assert "pd.moe_experts/cond" in text
    scoped = [i for i in xplane.hlo_instructions(text)
              if "block_diffusion_attention" in (i.op_name or "")]
    assert len(scoped) > 20
    # (H x D = 4096 = L here, so an operand's flat view [1, L, H D] has
    # two dims of L: the test is on size. One head's [L, L] scores of all
    # 32 heads are 16 times the op's own [2, L, H, D] operand, the two
    # streams' square 64 times.)
    operand = 2 * length * config["num_attention_heads"] * config["head_dim"]
    for instr in scoped:
        assert xplane.first_array(instr.shape)[0] <= operand, (
            instr.name, instr.shape)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


SWA_CELL = "smallthinker-21b-a3b.train-swa-t8192-ep8-share"


def test_window_step_compiles_with_both_kinds_of_layer(mosaic, one_chip):
    """The sliding-window cell's step at its own 8192-token sequence and
    published widths, the depth cut to the first two blocks, one of each
    kind (the four take four minutes here; tools/describe_step.py sized
    them: 7.44 GB of temporaries + 4.45 GB of aliased state): each
    attention op on the flash kernels with the fused backward, the
    experts on gmm / tgmm behind the ladder's switch (8 of 64 held, an
    eighth: 12288 | 49152, PR 58), three products a forward branch and
    their six partners a gradient branch, none of them a forward's, the
    rotations under the windowed layer's scope alone, and under either
    attention scope no array larger than the op's own operands: nothing
    of [., T, T] reaches HBM."""
    from paddle_tpu import xplane
    cell = run.load_json("workloads", SWA_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=2)
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    assert flash == {"flash_fwd": 2, "flash_dkv": 2}
    # two layers of two rungs: the forward's three products, the gradient
    # op's three on the rows and three on the weights
    assert kernels.count("gmm") == 2 * 2 * (3 + 3)
    assert kernels.count("tgmm") == 2 * 2 * 3
    # the token side, forward and pulled back, in both layers' rungs
    assert kernels.count("pair_sum") == 2 * 2 * 2
    assert "pd.moe_experts/cond" in text
    scoped = {kind: [i for i in xplane.hlo_instructions(text)
                     if "pd_scope." + kind in (i.op_name or "")]
              for kind in ("window_attention", "global_attention")}
    assert any("rotary_embedding" in i.op_name
               for i in scoped["window_attention"])
    assert not any("rotary_embedding" in i.op_name
                   for i in scoped["global_attention"])
    operand = (config["sequence_length"] * config["num_attention_heads"]
               * config["head_dim"])
    for kind, instrs in scoped.items():
        assert len(instrs) > 4, kind
        for instr in instrs:
            assert xplane.first_array(instr.shape)[0] <= operand, (
                instr.name, instr.shape)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


LAGUNA_CELL = "laguna-xs.2.train-gated-swa512-ep8-share"


def test_gated_window_step_keeps_no_product_of_a_forward_that_is_replayed(
        mosaic, one_chip, no_room):
    """The gated window cell's step at its own 8192-token sequence and
    published widths, the depth cut to the dense layer and two expert
    layers under a checkpoint a layer, the first expert layer replayed
    and the last not (the five take two minutes here): 32 of 256 experts
    held, an eighth, so each expert op and its gradient op are one switch
    over 16384 | 65536 pairs (PR 58). What a gradient op reads of its
    forward is Up and GateUp, [65536, 512] bf16 each: the replayed layer's
    FIRST forward leaves its conditional with Out alone (nothing reads
    the pair: the compiler drops it and the small rung's fill), its
    replayed forward with the pair alone (nothing reads that Out: the
    down product goes, two grouped products a branch where three), and
    the last layer's forward, which nothing replays, with all three. A
    gradient branch runs its three products on the rows and three on the
    weights, no forward's."""
    cell = run.load_json("workloads", LAGUNA_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=3)
    booked = _kv_groups_booked()
    text = describe_step.compile_step(cell, config, one_chip).as_text()
    _no_kv_repeat_around_the_flash_calls(text, booked)
    kernels = [(i.op, i.recompute is not None,
                re.search(r"(\w+)\)*/pallas_call", i.op_name).group(1))
               for i in xplane.hlo_instructions(text)
               if i.opcode == "custom-call" and "pallas_call" in i.op_name
               and (i.op or "").startswith("moe_experts")]
    assert {k: kernels.count(k) for k in set(kernels)} == {
        ("moe_experts", False, "gmm"): 2 * 2 * 3,
        ("moe_experts", False, "pair_sum"): 2 * 2,
        # the two kept products of the small rung, where they are read:
        # the last layer's forward and the replay (_over_all_pairs)
        ("moe_experts", False, "unwritten_rows"): 2,
        ("moe_experts", True, "unwritten_rows"): 2,
        ("moe_experts", True, "gmm"): 2 * 2,
        ("moe_experts_grad", False, "gmm"): 2 * 2 * 3,
        ("moe_experts_grad", False, "tgmm"): 2 * 2 * 3,
        ("moe_experts_grad", False, "pair_sum"): 2 * 2}
    kept = "bf16[65536,512]"
    switches = sorted(
        (i.at, i.recompute is not None, i.shape.count(kept))
        for i in xplane.hlo_instructions(text)
        if i.opcode == "conditional" and i.op == "moe_experts")
    assert [s[1:] for s in switches] == [(False, 0), (False, 2), (True, 2)]


def _no_kv_repeat_around_the_flash_calls(text, booked):
    """The same step's attention ops (PR 61), a causal layer of 48 query
    heads and two windowed ones of 64 over 8 K/V heads of 128: every
    flash call takes K and V as `bf16[1, 8192, 1024]`, the gradient
    calls give dK and dV so, and under the two ops nothing of
    `bf16[8192, 8, groups, 128]` is broadcast and nothing reduced to
    `bf16[8192, 8, 128]`: no K or V at the query's width (`bf16[1, 8192,
    8192]` or `[1, 8192, 6144]`) is made ahead of a Mosaic call and no
    group sum runs behind one."""
    calls = _flash_calls(text)
    assert sorted(c[0] for c in calls) == ["flash_dkv"] * 3 + ["flash_fwd"] * 3
    kv = "bf16[1,8192,1024]"
    for kernel, operands, result in calls:
        assert operands.count(kv) == 2, (kernel, operands)
        assert result.count(kv) == (2 if kernel == "flash_dkv" else 0)
    ops = ("scaled_dot_product_attention", "scaled_dot_product_attention_grad")
    around = [i for i in xplane.hlo_instructions(text) if i.op in ops]
    assert len(around) > 20
    for i in around:
        assert not (i.opcode == "broadcast" and i.shape.startswith("bf16[")
                    ), (i.name, i.shape)
        assert not (i.opcode == "reduce"
                    and i.shape.startswith("bf16[8192,8,128]")), i.name
    now = _kv_groups_booked()
    added = {k: v - booked.get(k, 0) for k, v in now.items()
             if v != booked.get(k, 0)}
    op = "op=scaled_dot_product_attention,groups="
    assert added == {op + "6,form=kernel,ground=": 1,
                     op + "8,form=kernel,ground=": 2}


KDA_CELL = "kimi-linear.train-kda-t8192-ep32-share"


def test_delta_rule_step_compiles_with_both_kinds_of_mixer(
        mosaic, one_chip, no_room):
    """The Kimi-Linear cell's step at its own 8192-token sequence and
    published widths, the depth cut to two layers, one of each kind (a
    KDA mixer before the dense feed-forward, latent attention before an
    expert layer; the five take five minutes here; tools/describe_step.py
    sized them, PR 55: 7.91e9 B of temporaries + 7.23e9 B of aliased
    state): latent attention at keys 192 wide beside values 128 wide on
    the flash kernels at one 256-lane block a head with the fused
    backward; the experts on gmm / tgmm under the ladder's one switch
    each way (8 of 256 held: three rungs); the delta rule on the kernels of
    ops/pallas_kda.py (PR 56): under the op and its gradient the forward
    kernel once (the first forward's, whose inverses are kept), the
    given-inverse forward kernel once (the replayed op's, which reads them
    and writes Out and the entering states: PR 65; the gradient op traces
    no forward) and the backward kernel once, NO loop, and nothing of [.,
    T, T] under either mixer;
    the three short convolutions on the kernels of ops/pallas_conv1d.py
    (PR 60): three forward calls, three replayed ones and three of the
    explicit gradient op's, which traces no forward;
    under the KDA mixer a float32 array of the projections' [T, H K]
    reaches HBM from the gated norm alone (and the maps' float32 weight
    gradients' side), none from the convolutions, the op or their
    gradients."""
    from paddle_tpu import xplane
    cell = run.load_json("workloads", KDA_CELL)
    config = run.load_json("configs", cell["config"])
    config = dict(config, num_hidden_layers=2, linear_attn_config=dict(
        config["linear_attn_config"], kda_layers=[1], full_attn_layers=[2]))
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    assert flash == {"flash_fwd": 1, "flash_dkv": 1}
    assert "gmm" in kernels and "tgmm" in kernels
    # the token side, forward and pulled back, in each of the three rungs
    # (PR 69: 4096 | 8192 | 65536)
    assert kernels.count("pair_sum") == 2 * 3
    assert "pd.moe_experts/cond" in text
    delta = {k: kernels.count(k) for k in set(kernels) if "kda" in k}
    assert delta == {"kda_scan_fwd": 1, "kda_scan_fwd_given": 1,
                     "kda_scan_bwd": 1}, delta
    conv = {k: kernels.count(k) for k in set(kernels) if "conv1d" in k}
    assert conv == {"causal_conv1d_fwd": 3 + 3, "causal_conv1d_bwd": 3}, conv
    tokens = config["sequence_length"]
    instrs = list(xplane.hlo_instructions(text))
    under_op = [i for i in instrs if "pd.kda_scan" in (i.op_name or "")]
    assert under_op and not [i.name for i in under_op if i.opcode == "while"]
    widest = tokens * config["num_attention_heads"] * 256
    for kind in ("kda_mixer", "latent_attention"):
        scoped = [i for i in instrs if "pd_scope." + kind in (i.op_name or "")]
        assert len(scoped) > 4, kind
        for instr in scoped:
            assert xplane.first_array(instr.shape)[0] <= 4 * widest, (
                instr.name, instr.shape)
    lin = config["linear_attn_config"]
    wide = f"f32[1,{tokens},{lin['num_heads'] * lin['head_dim']}]"
    heads = f"f32[1,{tokens},{lin['num_heads']},{lin['head_dim']}]"
    float32 = {re.search(r"pd\.(\w+)", i.op_name.split("kda_mixer")[1]).group(1)
               for i in instrs if "pd_scope.kda_mixer" in (i.op_name or "")
               and any(x in i.shape.replace(" ", "") for x in (wide, heads))}
    # the gated norm works in float32; the short convolutions and the
    # delta rule read and write bf16 and widen in VMEM
    assert float32 <= {"mul", "rms_norm", "rms_norm_grad"}, float32
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


LFM2_CELL = "lfm2-24b-a2b.train-shortconv-ep8-share"


def test_shortconv_step_keeps_no_float32_rows_around_the_conv(
        mosaic, one_chip, no_room):
    """The LFM2 cell's step at its own 16,384-token sequence and published
    widths, the layers held cut to published layers 0, 2 and 3 (conv +
    dense, attention + experts, conv + experts; the five take four minutes
    here and tools/describe_step.py sized them, PR 62: 5.19e9 B of
    temporaries + 5.63e9 B of aliased state) under a checkpoint a layer:
    the gated short convolution on the gated kernels of
    ops/pallas_conv1d.py, two forward calls, one replayed (the last
    segment is not) and two of the explicit gradient op's, which traces no
    forward; every operand and result of them a bf16 [1, 16384, 2048]
    array that a product wrote or reads, with no float32 array of a whole
    [T, 2048] or [T, 6144] activation and no copy under the op or its
    gradient op; attention at 32 query heads over 8 of 64 with K and V
    widened to the query heads (two heads share a lane block: `lanes`), on
    the flash kernels with the FUSED backward at 16,384 rows, the longest
    `_split_reason` lets through at 128 lanes, booked once a lowering; the
    experts on gmm / tgmm under the ladder's one switch each way."""
    from paddle_tpu import telemetry
    cell = run.load_json("workloads", LFM2_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  layers_held=[0, 2, 3], num_hidden_layers=3)
    tokens = config["sequence_length"]
    assert tokens == 16384 and pallas_attention._split_reason(
        tokens, tokens, 128, 2, pallas_attention._TILE,
        pallas_attention._MAJOR) is None
    assert pallas_attention._split_reason(
        2 * tokens, 2 * tokens, 128, 2, pallas_attention._TILE,
        pallas_attention._MAJOR) == "vmem"
    booked = {name: dict(telemetry.read_series(name)) for name in (
        "flash_backward_total", "attention_kv_groups_total")}
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    conv = {k: kernels.count(k) for k in set(kernels) if "conv1d" in k}
    assert conv == {"gated_conv1d_fwd": 2 + 1, "gated_conv1d_bwd": 2}, conv
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    assert flash == {"flash_fwd": 1, "flash_dkv": 1}, flash
    assert "gmm" in kernels and "tgmm" in kernels
    assert "pd.moe_experts/cond" in text
    added = {name: {k: v - booked[name].get(k, 0) for k, v in dict(
        telemetry.read_series(name)).items() if v != booked[name].get(k, 0)}
        for name in booked}
    assert added == {
        "flash_backward_total": {"form=fused,reason=": 1},
        "attention_kv_groups_total": {
            "op=scaled_dot_product_attention,groups=4,form=repeated,"
            "ground=lanes": 1}}, added
    rows = f"bf16[1,{tokens},{config['hidden_size']}]"
    for line in text.splitlines():
        if KERNEL in line and "conv1d" in line:
            operands = re.findall(r"\w+\[[\d,]*\]", re.search(
                r"operand_layout_constraints=\{(.*?\})\}", line).group(1))
            gated_bwd = "gated_conv1d_bwd" in line
            assert operands.count(rows) == (6 if gated_bwd else 3), operands
            assert line.split(" custom-call(")[0].count(rows) == (
                3 if gated_bwd else 1)
    assert not _float32_under_the_conv(text)
    under = [i for i in xplane.hlo_instructions(text)
             if re.search(r"pd\.causal_conv1d(_grad)?/", i.op_name or "")]
    assert len(under) >= 5
    assert not [i.name for i in under if i.opcode in ("copy", "transpose")]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


GDN_CELL = "qwen3-next-80b-a3b.train-gdn-t16k-ep16-share"


def test_gated_delta_net_step_reads_q_k_and_the_gate_as_they_are(
        mosaic, one_chip, no_room):
    """The Qwen3-Next cell's step at its own 16,384-token sequence and
    published widths, the layers held cut to published layers 2 and 3 (a
    Gated DeltaNet layer and the gated attention layer, each with its
    expert layer; the four take five minutes here and
    tools/describe_step.py sized them, PR 64: 7.47e9 B of temporaries +
    7.51e9 B of aliased state) under a checkpoint a layer: the delta rule
    on the head-decay kernels of ops/pallas_kda.py, the forward kernel
    once (the first forward's, whose inverses are kept), the given-inverse
    one once (the replayed op's: PR 65; its last operand the float32 [1,
    256, 16, 64, 128] inverses) and the backward kernel once, the
    channel form's kernels nowhere; q and k reach them as the bf16 [1, T,
    16 x 128] arrays their convolutions wrote and the gate as float32 [1,
    4, T, 8] running sums: NOTHING of [T, 32 x 128] is broadcast or
    repeated ahead of a call and no dq or dk is reduced behind one;
    attention at 16 query heads over 2 of 256 on the flash kernels with K
    and V at their own 2 heads (`form=kernel`) and the backward in two
    calls, dq and dkv (`form=split`, `reason=vmem`: at 16,384 rows of 256
    lanes the fused call's dQ accumulator does not fit); 32 of 512
    experts held on gmm / tgmm under the ladder's three rungs (20,480 |
    40,960 | 163,840: PR 69), the token side's kernel in each, forward
    and pulled back."""
    from paddle_tpu import telemetry
    cell = run.load_json("workloads", GDN_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  layers_held=[2, 3], num_hidden_layers=2)
    tokens = config["sequence_length"]
    assert tokens == 16384
    booked = {name: dict(telemetry.read_series(name)) for name in (
        "flash_backward_total", "attention_kv_groups_total",
        "kda_scan_head_decay_total")}
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    delta = {k: kernels.count(k) for k in set(kernels)
             if "kda" in k or "gdn" in k}
    assert delta == {"gdn_scan_fwd": 1, "gdn_scan_fwd_given": 1,
                     "gdn_scan_bwd": 1}, delta
    conv = {k: kernels.count(k) for k in set(kernels) if "conv1d" in k}
    assert conv == {"causal_conv1d_fwd": 3 + 3, "causal_conv1d_bwd": 3}, conv
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    # 16,384 rows of 256 lanes: the fused backward's dQ accumulator does
    # not fit VMEM (`_split_reason`), so dq and dkv are two calls
    assert pallas_attention._split_reason(
        tokens, tokens, 256, 2, pallas_attention._TILE,
        pallas_attention._MAJOR) == "vmem"
    assert flash == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}, flash
    assert "gmm" in kernels and "tgmm" in kernels
    # two expert layers: forward and pulled back in each of three rungs
    assert kernels.count("pair_sum") == 2 * 2 * 3
    assert "pd.moe_experts/cond" in text
    added = {name: {k: v - booked[name].get(k, 0) for k, v in dict(
        telemetry.read_series(name)).items() if v != booked[name].get(k, 0)}
        for name in booked}
    assert added == {
        "flash_backward_total": {"form=split,reason=vmem": 1},
        "attention_kv_groups_total": {
            "op=scaled_dot_product_attention,groups=8,form=kernel,"
            "ground=": 1},
        "kda_scan_head_decay_total": {
            "path=kernel,groups=2": 1,
            "path=kernel_given_inverse,groups=2": 1}}, added
    keys, values = f"bf16[1,{tokens},2048]", f"bf16[1,{tokens},4096]"
    sums = f"f32[1,4,{tokens},8]"
    inverses = f"f32[1,{tokens // 64},16,64,128]"
    for line in text.splitlines():
        if KERNEL in line and "gdn_scan" in line:
            operands = re.findall(r"\w+\[[\d,]*\]", re.search(
                r"operand_layout_constraints=\{(.*?\})\}", line).group(1))
            backward = "gdn_scan_bwd" in line
            assert operands.count(keys) == 2, operands      # q and k
            assert operands.count(values) == 1 + backward   # v (and do)
            assert operands.count(sums) == 2                # G and beta
            results = line.split(" custom-call(")[0]
            assert results.count(keys) == (2 if backward else 0)
            # read by the given-inverse forward and the backward, written
            # by the first forward alone
            assert operands.count(inverses) == (
                backward or "gdn_scan_fwd_given" in line), operands
            assert results.count(inverses) == (
                not backward and "gdn_scan_fwd_given" not in line)
    under = [i for i in xplane.hlo_instructions(text)
             if re.search(r"pd\.kda_scan(_grad)?/", i.op_name or "")]
    assert under and not [i.name for i in under if i.opcode == "while"]
    # nothing as wide as [T, 32 heads x 128] under the op or its gradient
    # (a broadcast gate, repeated keys or their pulled-back sums would be)
    # but the kernels' own v, o, do, dv and entering states
    wide = tokens * 32 * 128

    def widest(i, dtype):
        return max([int(np.prod([int(d) for d in dims.split(",")]))
                    for dims in re.findall(dtype + r"\[([\d,]+)\]", i.shape)]
                   or [0])

    theirs = ("custom-call", "get-tuple-element")   # the kernels' results
    assert not [(i.name, i.shape) for i in under if i.opcode not in theirs
                and max(widest(i, "f32"), widest(i, "bf16")) >= wide]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


GRANITE_CELL = "granite-4.0-h-micro.train-ssm-recompute"


def test_granite_step_holds_fewer_temporaries_with_checkpoints(
        mosaic, one_chip, no_room):
    """The granite cell's step at its own 8192-token sequence and published
    widths, the depth cut to two Mamba layers and the attention layer (the
    ten take five minutes here; tools/describe_step.py sized them: 7.08e9
    B of temporaries with the checkpoints, 7.64e9 without, beside 9.27e9
    of aliased state): with the residual stream kept at the layers'
    inputs the two Mamba layers' forward ops run again in the backward,
    their scan kernels with them (XLA merges the forward the gradient op
    traces again with the replayed one, not with the first: the barrier
    stands between), and the compiler's own memory analysis holds
    measurably fewer temporary bytes than the same step without
    checkpoints: the replay survived the compiler. The short convolutions
    run on their kernels in the replayed segments too (PR 60: a forward
    call a layer, one more replayed, one call of the explicit gradient
    op), with time along the lanes as the scan's, and write no float32
    [T, C] array. No scan and no convolution falls back."""
    from paddle_tpu import telemetry, xplane
    cell = run.load_json("workloads", GRANITE_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=3,
                  layer_types=["mamba", "mamba", "attention"])
    before = dict(telemetry.read_series("pallas_fallback_total"))
    booked = _kv_groups_booked()
    temps, kernels, replayed = {}, {}, {}
    for recompute in (True, False):
        compiled = describe_step.compile_step(
            cell, dict(config, recompute=recompute), one_chip)
        text = compiled.as_text()
        assert not _float32_under_the_conv(text)
        # 32 query heads over 8 K/V heads of 64, two heads a lane block:
        # the kernels get K and V repeated to the query's width (PR 61)
        for kernel, operands, _ in _flash_calls(text):
            assert operands.count("bf16[1,8192,2048]") >= 3, (kernel,
                                                              operands)
        temps[recompute] = compiled.memory_analysis().temp_size_in_bytes
        names = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call',
                           line).group(1)
                 for line in text.splitlines() if KERNEL in line]
        kernels[recompute] = {k: names.count(k) for k in set(names)}
        replayed[recompute] = {i.recompute for i in
                               xplane.hlo_instructions(text)
                               if i.recompute is not None}
    assert dict(telemetry.read_series("pallas_fallback_total")) == before
    lanes = "op=scaled_dot_product_attention,groups=4,form=repeated," \
        "ground=lanes"
    assert _kv_groups_booked() == dict(booked, **{
        lanes: booked.get(lanes, 0) + 2})
    assert kernels[False] == {"ssd_scan_fwd": 2, "ssd_scan_bwd": 2,
                              "causal_conv1d_fwd": 2, "causal_conv1d_bwd": 2,
                              "flash_fwd": 1, "flash_dkv": 1}
    assert kernels[True] == dict(kernels[False], ssd_scan_fwd=4,
                                 causal_conv1d_fwd=4)
    # (segment 0 replays the embedding's lookup alone: XLA drops it)
    assert replayed[False] == set() and {1, 2} <= replayed[True] <= {0, 1, 2}
    # 2.78e9 against 3.57e9 when this was written
    assert temps[True] < 0.9 * temps[False], temps


@pytest.mark.parametrize("shape,reason", [
    ((1, 2048, 12, 64), None),           # six blocks of two heads
    ((1, 2048, 3, 64), "heads"),         # 192 lanes fill no 128-lane block
    ((1, 2048, 8, 96), "head_dim"),      # 96 neither divides 128 nor is
                                         # a multiple of it
    ((1, 2048, 8, 256), None),
    ((1, 2048, 2, 512), None),
    ((1, 2050, 8, 64), "seq"),
    ((2048, 8, 64), "shape"),
])
def test_flash_gate_declines(shape, reason):
    """What the gate declines takes the einsum path with a counted reason
    (pallas_fallback_total); nothing here reaches the compiler."""
    q = jax.ShapeDtypeStruct(shape, BF16)
    assert pallas_attention.ineligible(q, q, q) == reason
    assert reason is None or \
        reason in kernel_choice.REASONS["scaled_dot_product_attention"]
    # the ring path's per-shard check declines the same shapes
    assert pallas_attention.block_supports(q, q) == (reason is None)


OURO_CELL = "ouro-2.6b.train-loop4-t4096-pp6-stage"


def test_looped_step_reads_one_set_of_weights_four_times(mosaic, one_chip,
                                                         no_room):
    """The Ouro cell's step at its own 4096-token sequence and published
    widths, the depth cut to ONE layer run four times (the eight take
    five minutes here; tools/describe_step.py sized them, PR 59:
    6.74e9 B of temporaries + 7.35e9 B of aliased state, 64 Mosaic
    calls): four flash forward calls and four fused backward calls for
    four applications of which three are replayed (a replayed attention
    op is handed its first output and row statistics and runs no
    kernel); each float32 master weight is converted to bf16 ONCE for its
    four readers and their three replays (the weights are read where
    they lie, not through a segment's barrier, so the compiler shares
    the copy); each weight's gradient leaves the backward as ONE float32
    array (the fan-in's `sum` ops are fused with the four products'
    converts); an exit's logits reach HBM in bf16 alone."""
    cell = run.load_json("workloads", OURO_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=1)
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    assert {k: kernels.count(k) for k in set(kernels)} == {
        "flash_fwd": 4, "flash_dkv": 4}
    instrs = [i for i in xplane.hlo_instructions(text) if i.entry]

    def writes(dtype, *dims):
        shape = "%s[%s]" % (dtype, ",".join(map(str, dims)))
        return [i for i in instrs if i.opcode in ("fusion", "convert")
                and i.shape.replace(" ", "").lstrip("(").startswith(shape)]

    d, f, v, t = (config["hidden_size"], config["intermediate_size"],
                  config["vocab_size"], config["sequence_length"])
    # the forward's converts: gate and up, down, the head: one each
    for dims, count in (((d, f), 2), ((f, d), 1), ((d, v), 1)):
        converts = [i for i in writes("bf16", *dims) if i.op == "mul"
                    and i.opcode == "convert"]
        assert len(converts) == count, (dims, converts)
    # the backward's float32 gradients: one array a weight
    for dims, count in (((d, f), 2), ((f, d), 1), ((d, v), 1)):
        grads = [i for i in writes("f32", *dims) if i.op == "mul_grad"]
        assert len(grads) == count, (dims, [g.name for g in grads])
    logits = {dtype: [i for i in instrs if i.opcode != "parameter"
                      and re.match(r"\(?%s\[(1,)?%d,%d\]" % (dtype, t, v),
                                   i.shape.replace(" ", ""))]
              for dtype in ("f32", "bf16")}
    assert logits["bf16"] and not logits["f32"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75e9


XING4_CELL = "xing4.0-29b-a4b.train-mhc4-mla-ep8-share"


def test_hyper_connection_step_keeps_the_streams_in_bf16(mosaic, one_chip,
                                                         no_room):
    """The Xing4.0 cell's step at its own 4096 tokens and published
    widths, the depth cut to the dense block and one expert block (the
    five take six minutes here: the `slow` test below; tools/
    describe_step.py sized them, PR 68), every segment replayed: both
    attention ops on the flash kernels at 256 lanes for 192 | 128 with
    the replayed one handed its first outputs (PR 54), the experts on gmm
    / tgmm under the ladder's two rungs; under the four hyper-connection
    scopes, forward, replayed and gradient, no instruction writes a
    float32 array of the streams' [4096, 4, 3584] but the cotangent of
    X_0, the float32 embedding rows' copy (the mixes sum in float32
    inside their fusions and hand bf16 on behind their operands'
    barriers; the maps' projection reads the bf16 streams as they are),
    the sweeps' arrays are [4, 4096]
    and [16, 4096]-sized float32, and the whole step's bytes are under
    the chip's limit."""
    from paddle_tpu import memory, recompute
    cell = run.load_json("workloads", XING4_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  num_hidden_layers=2)
    tokens, n, width = config["sequence_length"], config["hc_mult"], \
        config["hidden_size"]
    assert (tokens, n, width) == (4096, 4, 3584)
    compiled = describe_step.compile_step(cell, config, one_chip)
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    flash = {k: kernels.count(k) for k in set(kernels) if "flash" in k}
    assert flash == {"flash_fwd": 2, "flash_dkv": 2}, flash
    assert "gmm" in kernels and "tgmm" in kernels
    instrs = xplane.hlo_instructions(text)
    scopes = ("hyper_connection_maps", "hc_pre_mix", "hc_post_res_mix")
    under = [i for i in instrs if i.scope
             and set(scopes) & set(i.scope.split("."))]
    assert under
    for op in ("hyper_connection_maps", "sinkhorn_knopp", "hc_pre_mix",
               "hc_post_res_mix"):
        for op_type in (op, op + "_grad"):
            assert any(i.op == op_type for i in under), op_type
        assert any(i.op == op and i.recompute is not None for i in under), op
    wide = [(i.name, i.op, i.shape) for i in under
            if any(np.prod([int(d) for d in dims.split(",")])
                   >= tokens * n * width
                   for dims in re.findall(r"f32\[([\d,]+)\]", i.shape))]
    # but one: the first sublayer reads X_0, the float32 master table's
    # rows copied into four streams, and its cotangent is X_0's dtype
    assert [op for _, op, _ in wide] in ([], ["hyper_connection_maps_grad"]), \
        wide
    sweeps = [i for i in under if i.op and i.op.startswith("sinkhorn_knopp")]
    assert sweeps and all(
        np.prod([int(d) for d in dims.split(",")]) <= tokens * n * n
        for i in sweeps for dims in re.findall(r"f32\[([\d,]+)\]", i.shape))
    mem = compiled.memory_analysis()
    # the fixture's limit is the margin alone: the chip's own is read here
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 16_909_336_064 - recompute.MARGIN_BYTES // 2


@pytest.mark.slow
def test_hyper_connection_cell_fits_the_chip_at_4096_tokens(mosaic,
                                                            one_chip):
    """The sizing rule's compile (PERF.md section 4): the whole step of
    the Xing4.0 cell at full depth for the described v5e, six minutes
    here, so not tier-1's (CHANGES.md, PR 68, says so): 9.11e9 B of
    aliased state and temporaries that leave the step inside
    memory.device_limit with the replay plan
    test_a_checkpointed_cell_keeps_the_segments_that_fit_a_v5e pins
    (the lookup and the fourth block kept, blocks 0 to 2 replayed, the
    fifth behind the last checkpoint)."""
    from paddle_tpu import memory
    cell = run.load_json("workloads", XING4_CELL)
    compiled = describe_step.compile_step(
        cell, run.load_json("configs", cell["config"]), one_chip)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 9.1e9
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < memory.device_limit(next(iter(one_chip.device_set)))


# what the executor decides for a described v5e at each checkpointed cell's
# full depth (recompute.py; PERF.md section 6, PR 67): the segments kept,
# of how many
DECIDED = {
    LFM2_CELL: ([2, 3, 4], 4),
    GRANITE_CELL: ([0, 6], 10),
    KDA_CELL: ([1], 4),
    LAGUNA_CELL: ([1, 4], 4),
    GDN_CELL: ([], 3),
    OURO_CELL: ([26, 27, 28, 29, 30, 31], 31),
    # segment 0 is the lookup (117 MB: its copy into four streams); a
    # block is 1.31e9 B (the dense one 1.40e9) beside 12.15e9 of state
    # and gradients: the last of the four behind checkpoints is kept
    XING4_CELL: ([0, 4], 5),
}


@pytest.mark.parametrize("name", list(DECIDED))
def test_a_checkpointed_cell_keeps_the_segments_that_fit_a_v5e(mosaic,
                                                               one_chip,
                                                               name):
    """Each checkpointed cell's step at full depth, traced for the
    described chip and not compiled: the executor's decision is made from
    the chip's own limit (15.75 GiB: what tools/describe_step.py shows is
    the step the chip runs), the smallest segments first and of equal
    ones the later, the estimate and the margin under the limit, and a
    segment more would not be. The cell whose segments are 3.56e9 B
    (Qwen3-Next) keeps nothing: its step is the one the IR spells."""
    from paddle_tpu import memory, recompute
    kept, segments = DECIDED[name]
    cell = run.load_json("workloads", name)
    made = describe_step.replay_plan(
        cell, run.load_json("configs", cell["config"]), one_chip)
    assert made.limit == 16_909_336_064 \
        == memory.device_limit(next(iter(one_chip.device_set)))
    assert len(made.decisions) == segments
    assert sorted(i for i, d in made.decisions.items() if d.kept) == kept
    assert {d.reason for d in made.decisions.values()} <= {"fits", "budget"}
    assert all(d.reason == "fits" for d in made.decisions.values()
               if d.kept)
    assert made.estimate + recompute.MARGIN_BYTES <= made.limit
    sizes = {i: d.nbytes for i, d in made.decisions.items()}
    assert made.kept_bytes == sum(sizes[i] for i in kept)
    replayed = sorted((i for i in sizes if i not in kept),
                      key=lambda i: (sizes[i], -i))
    assert not kept or max(sizes[i] for i in kept) <= sizes[replayed[0]]
    assert recompute.estimate(sizes, made.held, kept + replayed[:1]) \
        + recompute.MARGIN_BYTES > made.limit


def test_shortconv_step_keeps_its_segments_where_they_fit(mosaic, one_chip):
    """The LFM2 cut of test_shortconv_step_keeps_no_float32_rows_around_
    the_conv under the described chip's own limit: both segments that
    may be replayed fit and are kept, so the compiled step holds one
    gated forward kernel a conv layer and none under a `pd_recompute`
    scope, no barrier stands in it, the gradient kernels are the replayed
    step's, and the bytes it holds are under the limit."""
    from paddle_tpu import memory, telemetry
    cell = run.load_json("workloads", LFM2_CELL)
    config = dict(run.load_json("configs", cell["config"]),
                  layers_held=[0, 2, 3], num_hidden_layers=3)
    before = dict(telemetry.read_series("recompute_segments_total"))
    compiled = describe_step.compile_step(cell, config, one_chip)
    added = {k.split(",", 1)[1]: v - before.get(k, 0) for k, v in
             telemetry.read_series("recompute_segments_total").items()
             if v != before.get(k, 0)}
    assert added == {"decision=kept,reason=fits": 2}
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line).group(1)
               for line in text.splitlines() if KERNEL in line]
    assert {k: kernels.count(k) for k in set(kernels) if "conv1d" in k} == {
        "gated_conv1d_fwd": 2, "gated_conv1d_bwd": 2}
    assert {k: kernels.count(k) for k in set(kernels) if "flash" in k} == {
        "flash_fwd": 1, "flash_dkv": 1}
    assert "pd_recompute." not in text and "recompute_barrier" not in text
    assert not [i for i in xplane.hlo_instructions(text)
                if i.recompute is not None]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < memory.device_limit(next(iter(one_chip.device_set)))


@pytest.mark.slow
@pytest.mark.parametrize("name", [LFM2_CELL, GDN_CELL, OURO_CELL])
def test_a_checkpointed_cell_compiles_the_first_time_at_full_depth(
        mosaic, one_chip, name):
    """The claimed cell and the two tightest at full depth (four to five
    minutes each here, so not tier-1's; tools/describe_step.py sized them,
    PR 67: LFM2 15.71e9 B with three of four segments kept, Ouro 14.61e9
    with six of 31, Qwen3-Next the parent's step): the chip's compiler
    takes the step as decided, with room, so the net under the estimate
    (recompute_fallback_total) is not what a cell runs on."""
    from paddle_tpu import memory
    cell = run.load_json("workloads", name)
    compiled = describe_step.compile_step(
        cell, run.load_json("configs", cell["config"]), one_chip)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < memory.device_limit(next(iter(one_chip.device_set)))
