#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls — layers
DSL -> Program -> Executor(TPUPlace(0)) / ServingEngine — at the published
width of the models the repo benchmarks, with random weights from a seed:

  train   ResNet-50, 3x224x224, 1000 classes, batch 256, AMP O2, Momentum:
          a few exe.run steps and one exe.run_steps window of K=4.
  lm      transformer LM train step (d_model 512 / 8 heads / 4 layers /
          T=2048, AMP O2) with use_flash=True, and the same step with
          use_flash=False on the same seed: first-step losses agree.
  recompute
          a two-layer granite_hybrid_lm (one group of 64 heads of 64,
          state 128, chunk 128, T=2048, AMP O2) whose first layer's
          forward is replayed in the backward, and the same step without
          checkpoints on the same seed: first-step losses agree, the scan
          runs on its kernels, the replay holds one more forward kernel.
  serve   save_inference_model of that ResNet-50, ServingEngine on the
          saved dir (max batch 8), requests in two batch buckets, answers
          equal to Executor inference on the same inputs.

`--multichip` needs 4 TPU devices and runs only the sharded LM: the `lm`
program planned over a fsdp=2 x tp=2 mesh against the same program and
seed on one device of the same process.

Each phase prints one JSON line; any failure exits non-zero at once. The
last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Without a TPU the script exits non-zero and prints no result. One process:
nothing here starts a child, and a chip belongs to one process at a time.

The phases are plain functions of their sizes (tests/test_chip_smoke.py
calls each at a tiny size on the CPU with the kernels interpreted); only
main() holds the device check and the real sizes.
"""

import argparse
import collections
import json
import re
import sys
import tempfile
import time

import numpy as np

SEED = 0


def _emit(record):
    print(json.dumps(record), flush=True)


def _counters(*names, since=None):
    """{family: {labels: value}} of the gate counters, as counted so far,
    or with `since` (an earlier reading) what they grew by since: the
    counters are the process's, and another program lowered before this
    phase has booked its own."""
    from paddle_tpu import telemetry
    now = {n: {k: int(v) for k, v in
               sorted(telemetry.read_series(n).items())} for n in names}
    if since is None:
        return now
    return {n: {k: v - since[n].get(k, 0) for k, v in series.items()
                if v > since[n].get(k, 0)} for n, series in now.items()}


# jax's own persistent-compilation-cache events, counted since
# _watch_compile_cache(): requests that consulted the cache, hits, and
# entries written after a miss
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes"}
_cache_traffic = collections.Counter()


def _watch_compile_cache():
    import jax.monitoring

    def on_event(name, **_):
        if name in _CACHE_EVENTS:
            _cache_traffic[_CACHE_EVENTS[name]] += 1

    jax.monitoring.register_event_listener(on_event)


class _Compiles:
    """What a phase spent compiling since this was made: XLA backend
    seconds (cache retrievals included) and cache traffic."""

    def __init__(self):
        from paddle_tpu import telemetry
        self._seconds = telemetry.jax_compile_seconds
        self._s0, self._t0 = self._seconds(), _cache_traffic.copy()

    def record(self):
        return {"xla_compile_s": round(self._seconds() - self._s0, 2),
                "compile_cache": {k: _cache_traffic[k] - self._t0[k]
                                  for k in _CACHE_EVENTS.values()}}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _finite_scalar(x, what):
    v = float(np.ravel(np.asarray(x))[0])
    if not np.isfinite(v):
        raise AssertionError(f"{what}: loss {v} is not finite")
    return v


def _is_hbm_overflow(e):
    """HBM ran out (compile- or run-time) — not a Mosaic kernel's VMEM,
    which raises the same status and is never cured by a smaller batch."""
    from paddle_tpu import memory
    return memory.is_oom(e) and "vmem" not in str(e)


# a layer's kernels: the forward and the fused backward, which keeps the
# K/V-resident kernel's name; flash_dq is the split backward's, for a Q
# sequence longer than the smoke's (pallas_attention._split_reason)
_FLASH_KERNELS = {"flash_fwd": 1, "flash_dkv": 1, "flash_dq": 0}


def _mosaic_calls(text):
    """{"<op>/<kernel>": n} over the Mosaic calls of optimized HLO
    `text`: the pd.<op> scope whose lowering issued the call and the
    pallas_call's name, both from its op_name metadata (autodiff wraps
    the name: transpose(jvp(flash_dq)))."""
    calls = collections.Counter()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        op = re.search(r"pd\.(\w+)", op_name)
        kernel = re.search(r"(\w+)\)*/pallas_call", op_name)
        calls[f"{op.group(1) if op else '?'}/"
              f"{kernel.group(1) if kernel else '?'}"] += 1
    return dict(sorted(calls.items()))


def _step_hlo(exe, prog, feed, fetch, scope):
    from paddle_tpu.executor import scope_guard
    with scope_guard(scope):
        return exe.compiled_hlo(prog, feed=feed, fetch_list=[fetch])


def _check_no_kernels(hits, calls):
    """The ResNet step is XLA's alone (PERF.md section 6: PR 25 took the
    convs off the Pallas suite, PR 34 deleted fusion's bn+act kernel):
    the conv gates counted no kernel and the compiled step holds no
    Mosaic call. `hits` is what pallas_kernel_total grew by in the phase,
    of which only the conv ops' series are judged; `calls` the step's
    Mosaic calls. One that came back came back
    without a price."""
    convs = {k: n for k, n in hits.items() if k.startswith("op=conv")}
    if convs or calls:
        raise AssertionError(
            f"the train step lowered to a Pallas kernel: "
            f"pallas_kernel_total {convs}, Mosaic calls {calls}")


def _check_flash_kernels(calls, n_layer):
    """One forward and one backward kernel per attention layer."""
    for kernel, a_layer in _FLASH_KERNELS.items():
        n = sum(v for k, v in calls.items() if k.endswith("/" + kernel))
        if n != a_layer * n_layer:
            raise AssertionError(
                f"{n_layer} flash attention layers passed the gate but "
                f"the compiled step holds {n} {kernel} Mosaic calls, not "
                f"{a_layer * n_layer}: "
                f"{calls}")


def _build_resnet(side, classes, depth):
    import functools

    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED + 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, side, side],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, predict, _ = models.build_image_classifier(
            functools.partial(models.resnet_imagenet, depth=depth), img,
            label, class_dim=classes)
        opt = fluid.amp.decorate(
            fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9),
            level="O2")
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss, predict


def train_phase(batch, side, classes, depth=50, steps=3, window=4,
                compiled=True):
    """ResNet train steps through Executor(TPUPlace(0)), per step and as
    one run_steps window (depth 50 is models.resnet50, the flagship).
    Returns (record, state) — `state` hands the trained program to
    serve_phase. `compiled` says the step is compiled for the chip, not
    for the CPU rehearsal: its tpu_custom_calls are then read too, and
    there may be none (_check_no_kernels)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod

    main, startup, loss, predict = _build_resnet(side, classes, depth)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = executor_mod.Scope()
    rng = np.random.default_rng(SEED)

    def batch_of(*lead):
        return {"img": rng.standard_normal(lead + (3, side, side),
                                           dtype=np.float32),
                "label": rng.integers(0, classes, lead + (1,))
                .astype(np.int32)}

    probe = "fc_0.w_0"
    compiles = _Compiles()
    gates = ("pallas_kernel_total", "pallas_fallback_total",
             "fusion_fallback_total")
    counted = _counters(*gates)
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        before = np.asarray(scope.find_var(probe)).copy()
        feed = {k: jax.device_put(v, exe.device)
                for k, v in batch_of(batch).items()}
        losses, step_s = [], []
        for i in range(steps):
            out, dt = _timed(lambda: np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss])[0]))
            losses.append(_finite_scalar(out, f"train step {i}"))
            step_s.append(dt)
        counters = _counters(*gates, since=counted)
        # shapes are all the compile-only check below needs: the batch
        # leaves the device before the window four times its size arrives
        feed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=v.sharding)
                for k, v in feed.items()}
        win = {k: jax.device_put(v, exe.device)
               for k, v in batch_of(window, batch).items()}
        stack, first_window_s = _timed(lambda: np.asarray(exe.run_steps(
            main, feed_window=win, steps=window, fetch_list=[loss],
            fetch_mode="stack")[0]))
        stack, window_s = _timed(lambda: np.asarray(exe.run_steps(
            main, feed_window=win, steps=window, fetch_list=[loss],
            fetch_mode="stack")[0]))
        for i, v in enumerate(np.ravel(stack)):
            losses.append(_finite_scalar(v, f"run_steps step {i}"))
        after = np.asarray(scope.find_var(probe))
    if np.array_equal(before, after):
        raise AssertionError(f"parameter {probe} did not change in "
                             f"{steps} + {window} train steps")

    mosaic = {}
    if compiled:
        mosaic = _mosaic_calls(_step_hlo(exe, main, feed, loss, scope))
    _check_no_kernels(counters["pallas_kernel_total"], mosaic)
    record = {
        "phase": "train", "model": f"resnet{depth}", "batch": batch,
        "image": [3, side, side], "classes": classes, "amp": "O2",
        "steps": steps, "window": window, "losses": losses,
        "step_s": [round(s, 4) for s in step_s],
        "first_window_s": round(first_window_s, 3),
        "window_step_s": round(window_s / window, 4),
        **compiles.record(), "tpu_custom_calls": mosaic, **counters,
    }
    return record, (exe, scope, main, predict)


def _build_train(seqlen, seed, loss_of):
    """(main, startup, loss) of an AMP O2 Adam train step of the language
    model `loss_of(tok, lab)` builds over [B, seqlen] ids: its loss and
    the checkpoints its backward keeps (None: none)."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import unique_name

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        loss, checkpoints = loss_of(tok, lab)
        opt = fluid.amp.decorate(fluid.optimizer.Adam(learning_rate=1e-4),
                                 level="O2")
        opt.minimize(loss, startup_program=startup, checkpoints=checkpoints)
    return main, startup, loss


def _build_lm(seqlen, d_model, n_head, n_layer, vocab, use_flash):
    from paddle_tpu import models

    return _build_train(
        seqlen, SEED + 2, lambda tok, lab: (models.transformer_lm(
            tok, lab, vocab_size=vocab, d_model=d_model, n_head=n_head,
            n_layer=n_layer, use_flash=use_flash), None))


def _lm_feed(batch, seqlen, vocab):
    rng = np.random.default_rng(SEED)
    return {"tok": rng.integers(0, vocab, (batch, seqlen)).astype(np.int32),
            "lab": rng.integers(0, vocab, (batch, seqlen)).astype(np.int32)}


def _lm_steps(main, startup, loss, feed, steps, place=None):
    """(losses, seconds of each step, exe, scope) of `steps` train steps
    in a scope of their own; the first step holds the compile."""
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod

    exe = fluid.Executor(place or fluid.TPUPlace(0))
    scope = executor_mod.Scope()
    losses, step_s = [], []
    with executor_mod.scope_guard(scope):
        exe.run(startup)
        for i in range(steps):
            out, dt = _timed(lambda: np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss])[0]))
            losses.append(_finite_scalar(out, f"lm step {i}"))
            step_s.append(dt)
    return losses, [round(s, 4) for s in step_s], exe, scope


def lm_phase(batch, seqlen, d_model, n_head, n_layer, vocab, steps=3,
             rtol=2e-2, compiled=True):
    """Transformer LM train steps with the flash kernels, and the einsum
    path on the same seed: the first-step losses agree within `rtol` (a
    bf16 tolerance — both run AMP O2), which is what shows the compiled
    kernel computes what the interpreter did."""
    feed = _lm_feed(batch, seqlen, vocab)
    out = {}
    compiles = _Compiles()
    declined_before = _counters("pallas_fallback_total")[
        "pallas_fallback_total"]
    for name, use_flash in (("flash", True), ("einsum", False)):
        main, startup, loss = _build_lm(seqlen, d_model, n_head, n_layer,
                                        vocab, use_flash)
        losses, step_s, exe, scope = _lm_steps(main, startup, loss, feed,
                                               steps)
        out[name] = {"losses": losses, "step_s": step_s}
        if use_flash:
            declined = _counters("pallas_fallback_total")[
                "pallas_fallback_total"]
            # this phase's own: the counter is the process's
            declined = {k: v - declined_before.get(k, 0)
                        for k, v in declined.items()
                        if "scaled_dot_product_attention" in k
                        and v > declined_before.get(k, 0)}
            mosaic = {}
            if compiled:
                mosaic = _mosaic_calls(_step_hlo(exe, main, feed, loss,
                                                 scope))
                if not declined:
                    _check_flash_kernels(mosaic, n_layer)
    a, b = out["flash"]["losses"][0], out["einsum"]["losses"][0]
    if abs(a - b) > rtol * abs(b):
        raise AssertionError(
            f"first-step loss: flash {a} vs einsum {b} differ by more "
            f"than rtol {rtol}")
    return {
        "phase": "lm", "model": "transformer_lm", "batch": batch,
        "seqlen": seqlen, "d_model": d_model, "heads": n_head,
        "layers": n_layer, "vocab": vocab, "amp": "O2", "steps": steps,
        "flash": out["flash"], "einsum": out["einsum"],
        "first_loss_rel_diff": abs(a - b) / abs(b), "rtol": rtol,
        "flash_declined": declined, "tpu_custom_calls": mosaic,
        # the process's own: empty unless a model under a sliding window
        # was lowered in it
        **_counters("attention_window_total"),
        **compiles.record(),
    }


def _build_hybrid(seqlen, d_model, heads, head_dim, state, width, vocab,
                  recompute):
    from paddle_tpu import models

    return _build_train(
        seqlen, SEED + 3, lambda tok, lab: models.granite_hybrid_lm(
            tok, lab, vocab_size=vocab, hidden_size=d_model,
            layer_types=["mamba", "mamba"], mamba_n_heads=heads,
            mamba_d_head=head_dim, mamba_n_groups=1, mamba_d_state=state,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=width, embedding_multiplier=12,
            residual_multiplier=0.22, logits_scaling=8,
            mamba_chunk_size=128, recompute=recompute))


def recompute_phase(seqlen, d_model, heads, head_dim, state, width, vocab,
                    steps=3, rtol=1e-2, compiled=True):
    """Two Mamba-2 layers of ONE group of `heads` heads with the residual
    stream kept at the layers' inputs, so that the first layer's forward
    ops run a second time in the backward
    (backward.append_backward(checkpoints=)), and the same step without
    checkpoints on the same seed: the first-step losses agree within
    `rtol`, no scan falls back, and the compiled step with checkpoints
    holds one forward scan kernel more for each scan the executor chose
    to replay (recompute.py: none where the device has room to keep the
    segment; where it replays, XLA merged the replay's kernel with
    neither the first forward nor dropped it) and the same gradient
    kernels."""
    from paddle_tpu import backward

    feed = _lm_feed(1, seqlen, vocab)
    compiles = _Compiles()
    before = _counters("pallas_fallback_total")["pallas_fallback_total"]
    out, mosaic, scans_again = {}, {}, 0
    for name, recompute in (("replayed", True), ("kept", False)):
        main, startup, loss = _build_hybrid(seqlen, d_model, heads, head_dim,
                                            state, width, vocab, recompute)
        assert bool(backward.replayed_ops(main)) == recompute
        losses, step_s, exe, scope = _lm_steps(main, startup, loss, feed,
                                               steps)
        out[name] = {"losses": losses, "step_s": step_s}
        if recompute:
            decided = exe.recompute_plan(main).decisions
            scans_again = sum(
                types.count("ssd_scan") for segment, types in
                backward.replayed_ops(main).items()
                if not decided[segment].kept)
        if compiled:
            mosaic[name] = _mosaic_calls(_step_hlo(exe, main, feed, loss,
                                                   scope))
    declined = {k: v - before.get(k, 0) for k, v in _counters(
        "pallas_fallback_total")["pallas_fallback_total"].items()
        if "ssd_scan" in k and v > before.get(k, 0)}
    if declined:
        raise AssertionError(f"the scan fell back: {declined}")
    a, b = out["replayed"]["losses"][0], out["kept"]["losses"][0]
    if abs(a - b) > rtol * abs(b):
        raise AssertionError(
            f"first-step loss: with checkpoints {a} vs without {b} differ "
            f"by more than rtol {rtol}")
    if compiled:
        want = dict(mosaic["kept"])
        want["ssd_scan/ssd_scan_fwd"] = want.get("ssd_scan/ssd_scan_fwd",
                                                 0) + scans_again
        if mosaic["replayed"] != want:
            raise AssertionError(
                f"the replayed layer's scan: Mosaic calls "
                f"{mosaic['replayed']} with checkpoints, {mosaic['kept']} "
                f"without; expected {scans_again} ssd_scan_fwd more")
    return {
        "phase": "recompute", "model": "granite_hybrid_lm", "seqlen": seqlen,
        "d_model": d_model, "heads_in_one_group": heads, "amp": "O2",
        "steps": steps, "replayed": out["replayed"], "kept": out["kept"],
        "first_loss_rel_diff": abs(a - b) / abs(b), "rtol": rtol,
        "tpu_custom_calls": mosaic,
        **_counters("recompute_segments_total"), **compiles.record(),
    }


def serve_phase(state, side, max_batch=8, request_rows=(3, 8, 5),
                rtol=2e-2, atol=2e-3):
    """save_inference_model of the trained ResNet-50, ServingEngine on
    the saved dir, one request per entry of `request_rows` (they must
    land in at least two batch buckets); every answer equals Executor
    inference of the loaded model on the same rows."""
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.serving import ServingEngine

    exe, scope, main, predict = state
    rng = np.random.default_rng(SEED + 3)
    requests = [rng.standard_normal((n, 3, side, side), dtype=np.float32)
                for n in request_rows]
    compiles = _Compiles()
    with tempfile.TemporaryDirectory() as model_dir:
        with executor_mod.scope_guard(scope):
            fluid.io.save_inference_model(model_dir, ["img"], [predict],
                                          exe, main_program=main)
        engine = ServingEngine(model_dir, max_batch=max_batch)
        try:
            buckets = sorted({engine.bucket_for(n) for n in request_rows})
            if len(buckets) < 2:
                raise AssertionError(
                    f"requests of {request_rows} rows land in one bucket "
                    f"{buckets}")
            answers, request_s = [], []
            for x in requests:
                (out,), dt = _timed(lambda: engine.infer({"img": x}))
                answers.append(np.asarray(out))
                request_s.append(dt)
            # a second pass over warm buckets: the time a request takes
            warm_s = [_timed(lambda: engine.infer({"img": x}))[1]
                      for x in requests]
            stats = {"cache_hits": engine.cache_hits,
                     "cache_misses": engine.cache_misses}
        finally:
            engine.close()
        ref_exe = fluid.Executor(fluid.TPUPlace(0))
        with executor_mod.scope_guard(executor_mod.Scope()):
            prog, feeds, fetches = fluid.io.load_inference_model(
                model_dir, ref_exe)
            ref, = ref_exe.run(prog,
                               feed={feeds[0]: np.concatenate(requests)},
                               fetch_list=fetches)
    ref = np.asarray(ref)
    max_diff, row = 0.0, 0
    for n, got in zip(request_rows, answers):
        want = ref[row:row + n]
        row += n
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            raise AssertionError(
                f"serving answer of shape {got.shape} for {n} rows "
                f"(want {want.shape}), finite={np.all(np.isfinite(got))}")
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)
        max_diff = max(max_diff, float(np.max(np.abs(got - want))))
    return {
        "phase": "serve", "model": "resnet50", "image": [3, side, side],
        "max_batch": max_batch, "request_rows": list(request_rows),
        "buckets": buckets, "first_request_s": [round(s, 3)
                                                for s in request_s],
        "warm_request_s": [round(s, 4) for s in warm_s],
        "max_abs_diff_vs_executor": max_diff, "rtol": rtol, "atol": atol,
        **compiles.record(), **stats,
    }


def multichip_phase(devices, batch, seqlen, d_model, n_head, n_layer, vocab,
                    steps=3, rtol=2e-2, compiled=True):
    """The `lm` program sharded by the planner over a fsdp=2 x tp=2 mesh
    of `devices`, against the same program and seed on the first of them:
    losses agree within `rtol`, a sharded parameter really lives on four
    devices, a quarter on each, and the step holds collectives."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.parallel import overlap, planner
    from paddle_tpu.parallel.mesh import make_mesh

    if len(devices) != 4:
        raise AssertionError(f"need 4 devices, got {len(devices)}")
    feed = _lm_feed(batch, seqlen, vocab)
    compiles = _Compiles()
    place = (fluid.CPUPlace() if devices[0].platform == "cpu"
             else fluid.TPUPlace(0))

    main, startup, loss = _build_lm(seqlen, d_model, n_head, n_layer,
                                    vocab, True)
    one_losses, one_step_s, _, _ = _lm_steps(main, startup, loss, feed,
                                             steps, place)

    main, startup, loss = _build_lm(seqlen, d_model, n_head, n_layer,
                                    vocab, True)
    mesh = make_mesh((2, 2), ("fsdp", "tp"), devices=list(devices))
    plan = planner.plan(main, mesh, startup=startup)
    options = overlap.compiler_options(main)
    losses, step_s, exe, scope = _lm_steps(main, startup, loss, feed,
                                           steps, place)
    for i, (a, b) in enumerate(zip(losses, one_losses)):
        if abs(a - b) > rtol * abs(b):
            raise AssertionError(
                f"step {i}: sharded loss {a} vs one-device loss {b} "
                f"differ by more than rtol {rtol}")

    # a parameter the plan shards over both axes: all four devices hold
    # a quarter of it — code that never saw two chips may put it all on
    # the first
    name = next(n for n, p in sorted(plan.params.items())
                if p.factor == 4 and p.role == "attn_qkv")
    arr = scope.find_var(name)
    spread = len(arr.sharding.device_set)
    shard = arr.addressable_shards[0].data
    if spread != 4 or shard.size * 4 != arr.size:
        raise AssertionError(
            f"parameter {name} {arr.shape}: on {spread} devices, shard "
            f"{shard.shape} — expected a quarter on each of 4")
    text = _step_hlo(exe, main, feed, loss, scope)
    collectives = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                   for k in ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute")}
    if not any(collectives.values()):
        raise AssertionError("the sharded step holds no collective")
    mosaic = _mosaic_calls(text)
    if compiled:    # the flash kernels reached the partitioned step
        _check_flash_kernels(mosaic, n_layer)
    return {
        "phase": "multichip", "model": "transformer_lm",
        "mesh": {"fsdp": 2, "tp": 2}, "batch": batch, "seqlen": seqlen,
        "d_model": d_model, "heads": n_head, "layers": n_layer,
        "steps": steps, "losses": losses, "one_device_losses": one_losses,
        "rtol": rtol, "step_s": step_s, "one_device_step_s": one_step_s,
        "sharded_param": {"name": name, "shape": list(arr.shape),
                          "devices": spread,
                          "shard_shape": list(shard.shape)},
        "collectives": collectives, "tpu_custom_calls": mosaic,
        "overlap_options_accepted": options is not None,
        "overlap_options": sorted(overlap.TPU_OVERLAP_OPTIONS),
        **compiles.record(),
        **_counters("pallas_fallback_total", "overlap_fallback_total"),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="run only the 4-chip sharded LM phase")
    args = parser.parse_args(argv)

    import jax
    from paddle_tpu import chip
    from paddle_tpu.ops import pallas_attention

    cache_dir = chip.enable_compile_cache()
    _watch_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    want = 4 if args.multichip else 1
    if device["platform"] != "tpu" or device["count"] < want:
        sys.stderr.write(f"chip_smoke needs {want} TPU device(s); jax "
                         f"found {device}\n")
        return 1
    if pallas_attention._interpret():
        sys.stderr.write("the Pallas kernels would be interpreted on a "
                         "TPU platform\n")
        return 1
    peaks = chip.peaks(devices[0])       # an unknown kind is an error
    _emit({"phase": "device", **device, "peaks": peaks._asdict(),
           "compile_cache": cache_dir or "JAX_COMPILATION_CACHE_DIR"})

    lm = dict(batch=8, seqlen=2048, d_model=512, n_head=8, n_layer=4,
              vocab=8192)
    if args.multichip:
        _emit(multichip_phase(devices[:4], **lm))
    else:
        batch = 256
        while True:
            try:
                record, state = train_phase(batch, side=224, classes=1000)
                break
            except Exception as e:  # noqa: BLE001 - re-raised unless HBM
                if not _is_hbm_overflow(e) or batch <= 32:
                    raise
                _emit({"phase": "train", "batch": batch,
                       "hbm_refused": str(e)[:300],
                       "retrying_with_batch": batch // 2})
                batch //= 2
        _emit(record)
        _emit(lm_phase(**lm))
        _emit(recompute_phase(seqlen=2048, d_model=512, heads=64, head_dim=64,
                              state=128, width=1024, vocab=8192))
        _emit(serve_phase(state, side=224))
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
