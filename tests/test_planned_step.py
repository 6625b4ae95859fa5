"""What the first planned model at published widths (gpt2-large on a
2 x 2 mesh, PR 26) made the executor and the fusion pass do, at a tiny
size on four of the harness's virtual devices: a feed committed to one
device is moved to the step's sharding, and the fused optimizer
bucket updates every tensor, sharded or whole, where it lies."""

import jax
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import telemetry
from paddle_tpu.parallel import planner
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

VOCAB = 101          # odd, as 50257 is: neither mesh axis divides it


def _planned_lm(n_layer=1):
    main, startup, loss = chip_smoke._build_lm(
        seqlen=32, d_model=32, n_head=2, n_layer=n_layer, vocab=VOCAB,
        use_flash=False)
    planner.plan(main, make_mesh((2, 2), ("fsdp", "tp"),
                                 devices=jax.devices()[:4]))
    return main, startup, loss


def _compiles():
    return sum(telemetry.read_series("jax_backend_compiles_total").values())


def _losses(main, startup, loss, feeds):
    exe = fluid.Executor(fluid.CPUPlace())
    out = []
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        for i, feed in enumerate(feeds):
            out.append(float(np.ravel(exe.run(
                main, feed=feed, fetch_list=[loss])[0])[0]))
            if i == 0:
                after_first = _compiles()
    return out, _compiles() - after_first


@pytest.mark.parametrize("placed", ["one_device", "feeder", "step_sharding"])
def test_committed_feed_is_accepted_on_a_mesh(placed):
    """jit refuses a committed argument whose sharding is not the one it
    was given; DoubleBufferedFeeder(device=exe.device) commits every
    batch to one device. The executor moves such a feed, once, without
    another compile, and the step computes what a numpy feed gives."""
    from jax.sharding import NamedSharding, PartitionSpec

    host = chip_smoke._lm_feed(4, 32, VOCAB)
    main, startup, loss = _planned_lm()
    want, _ = _losses(main, startup, loss, [host] * 3)

    main, startup, loss = _planned_lm()
    device = jax.devices()[0]
    if placed == "one_device":
        feeds = [{k: jax.device_put(v, device) for k, v in host.items()}
                 for _ in range(3)]
    elif placed == "step_sharding":
        where = NamedSharding(main._mesh, PartitionSpec("fsdp", None))
        feeds = [{k: jax.device_put(v, where) for k, v in host.items()}
                 for _ in range(3)]
    else:
        feeder = DoubleBufferedFeeder(lambda: iter([host] * 3),
                                      device=device, capacity=2)
        feeds = iter(feeder)
    got, recompiles = _losses(main, startup, loss, feeds)
    assert got == want
    assert recompiles == 0


def test_startup_program_under_the_plan_creates_the_state_sharded():
    """plan(main, mesh, startup=startup): the same values as a start-up
    on one device, each parameter and its Adam moments on the mesh with
    the planned spec from the start; without `startup` everything sits
    on one device until the first step."""
    from jax.sharding import NamedSharding, PartitionSpec

    def started(with_startup):
        main, startup, _loss = chip_smoke._build_lm(
            seqlen=32, d_model=32, n_head=2, n_layer=1, vocab=VOCAB,
            use_flash=False)
        mesh = make_mesh((2, 2), ("fsdp", "tp"), devices=jax.devices()[:4])
        plan = planner.plan(main, mesh,
                            startup=startup if with_startup else None)
        scope = executor_mod.Scope()
        with executor_mod.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
        return plan, scope, startup

    plan, scope, startup = started(True)
    _, loose, untouched = started(False)
    assert getattr(untouched, "_mesh", None) is None
    qkv = next(p for p in plan.params.values() if p.role == "attn_qkv")
    for name, p in plan.params.items():
        got = scope.find_var(name)
        assert np.array_equal(np.asarray(got),
                              np.asarray(loose.find_var(name))), name
        assert got.sharding.mesh == startup._mesh
        assert got.sharding.is_equivalent_to(
            NamedSharding(startup._mesh, PartitionSpec(*p.spec)),
            got.ndim), name
        assert len(loose.find_var(name).sharding.device_set) == 1
    for slot in ("moment1", "moment2"):
        moment = scope.find_var("%s_%s_0" % (qkv.name, slot))
        assert moment.sharding.spec == PartitionSpec("fsdp", "tp")
        assert moment.addressable_shards[0].data.size * 4 == moment.size


def test_feed_off_mesh_passes_through():
    exe = fluid.Executor(fluid.CPUPlace())
    main, _startup, _loss = chip_smoke._build_lm(
        seqlen=32, d_model=32, n_head=2, n_layer=1, vocab=VOCAB,
        use_flash=False)
    feed = {"tok": jax.device_put(np.zeros((4, 32), np.int32),
                                  jax.devices()[0])}
    assert exe._commit_feeds(main, feed) is feed
    main, _startup, _loss = _planned_lm()
    host = {"tok": np.zeros((4, 32), np.int32)}
    assert exe._commit_feeds(main, host) is host        # numpy: jit's own


def test_fused_bucket_updates_each_tensor_where_it_lies():
    """On the 2 x 2 plan the bucket holds every dense member, sharded or
    replicated, and updates each in its own shape and sharding: no flat
    buffer of parameters or moments (PR 26 had to pin four replicated:
    left free they were 37.8 GB a chip on gpt2-large), nothing for GSPMD
    to split, pad or permute, and no per-parameter `adam` op left
    outside it. The gradients of the members whose parameter lies
    replicated are traced through one pinned concatenation
    (fusion._grads_where_params_lie): GSPMD's plan for the rest of the
    step hangs on it (without it gpt2-large's 36 layers are refused by
    the compiler for sync flags), and once the step is partitioned XLA
    forwards its slices to their operands, so the compiled step holds
    no concatenate under pd.fused_adam either."""
    main, startup, loss = _planned_lm(n_layer=2)
    feed = chip_smoke._lm_feed(4, 32, VOCAB)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        text = exe.compiled_hlo(main, feed=feed, fetch_list=[loss])
    plan = main._sharding_plan
    whole = [p for p in plan.params.values() if p.factor == 1]
    assert any(p.role == "embedding" for p in whole)
    sharded = [p for p in plan.params.values() if p.factor == 4]
    assert sharded
    bucket = [ln for ln in text.split("\n") if "pd.fused_adam" in ln]
    assert bucket
    for kind in ("concatenate", "collective-permute", "dynamic-slice",
                 "pad"):
        assert not [ln for ln in bucket if " %s(" % kind in ln], kind
    assert "pd.adam/" not in text
    # the sharded tensors' updates are the bucket's: a quarter shard of
    # an attention projection is written under pd.fused_adam
    qkv = next(p for p in sharded if p.role == "attn_qkv")
    shard = ",".join(str(d // 2) for d in qkv.shape)
    assert [ln for ln in bucket if "f32[%s]" % shard in ln], shard
    # GSPMD's plan for the step is the one PR 26 measured: activations
    # split over tp around the layer norms, no resharding permute
    assert [ln for ln in text.split("\n")
            if " all-reduce(" in ln and "pd.layer_norm/" in ln]
    assert " collective-permute(" not in text
    series = telemetry.read_series("fusion_fallback_total")
    assert not any("reason=sharded_param" in k for k in series), series


def test_donation_audit_counts_what_one_device_holds():
    """XLA's alias_size is per device; the audit compared it with the
    donated state's global bytes, so every sharded program 'lost' the
    other devices' share (gpt2-large: 6.30 of 9.37 GiB, on a step whose
    state aliases in full). The donated side is per device too."""
    from paddle_tpu import memory

    main, startup, loss = _planned_lm()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=chip_smoke._lm_feed(4, 32, VOCAB),
                fetch_list=[loss])
        rec = memory.latest_record(telemetry.program_label(main))
    plan = main._sharding_plan
    qkv = next(p for p in plan.params.values() if p.role == "attn_qkv")
    held = sum(p.per_shard_bytes for p in plan.params.values())
    total = sum(p.bytes for p in plan.params.values())
    # parameters and two moments each, plus a few scalars
    assert 3 * held <= rec.donated_bytes < 3 * held + 4096
    assert rec.donated_bytes < 3 * total
    assert memory._shard_nbytes(scope.find_var(qkv.name)) * 4 == qkv.bytes
    assert memory._shard_nbytes(np.zeros((3, 5), np.float32)) == 60
