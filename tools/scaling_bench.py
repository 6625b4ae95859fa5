#!/usr/bin/env python
"""Data-parallel scaling-efficiency benchmark — the measurement apparatus
for the reference's distributed headline (reference
benchmark/cluster/vgg16/README.md:40-49: VGG-16 CIFAR-10 over the gRPC
parameter server scaled at 78.6% efficiency on 20 trainers falling to
60.9% at 100; BASELINE.md §5 sets >= 90% on ICI as the target this
design must beat).

Runs the same config (VGG-16, 32x32 inputs, per-device batch 128) over dp
meshes of growing size and reports samples/sec + efficiency vs linear
scaling from the 1-device point. On a real TPU slice this measures the
ICI AllReduce target directly:

    python tools/scaling_bench.py                 # all local devices
    python tools/scaling_bench.py 1 4 8           # specific mesh sizes
    python tools/scaling_bench.py --steps-per-call 8 1 4 8
                                  # fused K-step windows (Executor.run_steps)

`--steps-per-call K` (or SCALE_STEPS_PER_CALL) drives each mesh size
through Executor.run_steps — K steps per dispatch via one lax.scan window,
state shardings riding the scan carry — so the sweep captures the
dispatch-overhead trend next to the scaling trend; every per-mesh JSON
line carries a `steps_per_call` column.

SCALE_MODEL=embedding swaps the image model for the criteo-style sparse
embedding net (ISSUE 10): a [SCALE_EMB_ROWS x SCALE_EMB_DIM] table looked
up by SCALE_EMB_SLOTS features per example, fsdp-row-sharded over the
mesh, Adam scatter-apply end-to-end. Its per-mesh lines add
rows_touched_per_sec and table_bytes_per_shard — the memory column falls
~1/n while throughput holds. SCALE_EMB_BUDGET=<MB> swaps the sharding
for the beyond-HBM hot-row cache (ISSUE 14): the table stays unsharded,
only a budget-sized slab is device-resident, and the lines add
cache_rows / cache_hit_rate / prefetch_overlap_fraction /
flush_bytes_per_step (null when the cache is off).

SCALE_MODEL=lm swaps in the planner-sharded transformer LM (ISSUE 15):
each mesh size is factored into data x fsdp x tp named axes
(SCALE_LM_TP picks the tp degree, default 2 when it divides) and
`paddle_tpu.parallel.planner.plan` writes every spec — no hand
annotation. Its per-mesh lines always carry `param_bytes_per_shard`
(per-device param HBM under the plan — falls as fsdp x tp grows),
`overlap_fraction` and `busbw` (null when the trace shows no
collectives, e.g. 1-device runs). SCALE_LM_VOCAB / SCALE_LM_DMODEL /
SCALE_LM_LAYERS / SCALE_LM_SEQLEN size the model (defaults are a smoke
config; scale them up on a real slice).

On a CPU host it exercises the identical GSPMD path over virtual devices
— mechanism check only; the shared core makes the timings say nothing
about ICI. Use JAX_PLATFORMS=cpu with
XLA_FLAGS=--xla_force_host_platform_device_count=8, plus
SCALE_MODEL=smallnet_mnist_cifar SCALE_BS=16 to keep 1-core compiles
quick.

Prints one JSON line per mesh size plus a summary line. Each per-mesh
line also carries roofline attribution (`top_ops`, `bound`,
`device_duty_cycle` — see paddle_tpu/roofline.py) from a short traced
re-run of the compiled step; SCALE_PERF=0 skips that pass.
"""

import json
import os
import sys
import time

import numpy as np

# `python tools/scaling_bench.py` puts tools/ (not the repo root) on
# sys.path; make the tool runnable from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _parse_steps_per_call(v):
    v = str(v).strip().lower()
    return "auto" if v == "auto" else int(v)


def _auto_steps_per_call(exe, prog, run_step, feed, fetch):
    """`--steps-per-call auto` (ISSUE 9): probe the already-compiled K=1
    path for per-dispatch Python overhead and per-step device time, bound
    the window by the HBM headroom over the K=1 footprint, and let
    overlap.choose_steps_per_call pick K. Probe failures degrade to
    whatever signals remain — the sweep must never die here."""
    from paddle_tpu.parallel import overlap as overlap_mod

    step_ms = overhead_ms = None
    try:
        out = run_step()
        float(np.asarray(out).ravel()[0])         # compile + drain
        n = 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = run_step()
        float(np.asarray(out).ravel()[0])
        step_ms = (time.perf_counter() - t0) / n * 1e3
        t0 = time.perf_counter()
        for _ in range(n):
            out = run_step()              # enqueue-only: host-side cost
        overhead_ms = (time.perf_counter() - t0) / n * 1e3
        float(np.asarray(out).ravel()[0])
    except Exception as e:  # noqa: BLE001 - probe is best-effort
        print(f"auto steps-per-call timing probe failed: {e}",
              file=sys.stderr)
    peak = budget = feed_bytes = None
    try:
        from paddle_tpu import memory as memory_mod
        rec = exe.static_memory_analysis(prog, feed=feed,
                                         fetch_list=[fetch])
        peak = rec.total_bytes
        budget = memory_mod.default_budget(exe.device)
        feed_bytes = int(sum(np.asarray(v).nbytes for v in feed.values()))
    except Exception as e:  # noqa: BLE001 - probe is best-effort
        print(f"auto steps-per-call memory probe failed: {e}",
              file=sys.stderr)
    k = overlap_mod.choose_steps_per_call(
        python_overhead_ms=overhead_ms, step_time_ms=step_ms,
        feed_bytes_per_step=feed_bytes, peak_bytes=peak,
        budget_bytes=budget)
    print(f"steps-per-call auto -> {k}", file=sys.stderr)
    return k


def measure(n_devices, steps=None, warmup=None, per_device_batch=None,
            steps_per_call=None):
    # SCALE_BS/SCALE_STEPS shrink the config for mechanism checks on CPU
    # hosts (VGG jit compiles cost minutes per mesh size on 1-core boxes);
    # real-slice measurements should keep the reference bs128
    if steps is None:
        steps = int(os.environ.get("SCALE_STEPS", "10"))
    if warmup is None:
        warmup = int(os.environ.get("SCALE_WARMUP", "8"))
    if per_device_batch is None:
        per_device_batch = int(os.environ.get("SCALE_BS", "128"))
    if steps_per_call is None:
        steps_per_call = _parse_steps_per_call(
            os.environ.get("SCALE_STEPS_PER_CALL", "1"))
    if steps < 1 or per_device_batch < 1 or (
            steps_per_call != "auto" and steps_per_call < 1):
        raise SystemExit(
            "SCALE_STEPS, SCALE_BS and SCALE_STEPS_PER_CALL must be >= 1")
    warmup = max(warmup, 1)   # the sync readback needs at least one run
    model_name = os.environ.get("SCALE_MODEL", "vgg16")
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as fluid
    from paddle_tpu import executor as em
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    batch = per_device_batch * n_devices
    emb_cfg = lm_cfg = None
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        rng = np.random.default_rng(0)
        if model_name == "embedding":
            # sparse-embedding scaling family (ISSUE 10): the table and its
            # adam moments shard ROW-wise over an fsdp mesh, so the sweep's
            # memory column shows per-shard table HBM falling ~1/n while
            # rows_touched_per_sec holds — the recommender-model motivation
            # for fsdp-partitioned tables
            emb_cfg = {
                "rows": int(os.environ.get("SCALE_EMB_ROWS", "100000")),
                "dim": int(os.environ.get("SCALE_EMB_DIM", "64")),
                "slots": int(os.environ.get("SCALE_EMB_SLOTS", "26"))}
            with fluid.program_guard(main, startup):
                ids = fluid.layers.data(name="img",
                                        shape=[emb_cfg["slots"]],
                                        dtype="int64")
                label = fluid.layers.data(name="label", shape=[1],
                                          dtype="int64")
                emb = fluid.layers.embedding(
                    ids, size=[emb_cfg["rows"], emb_cfg["dim"]],
                    is_sparse=True,
                    param_attr=fluid.ParamAttr(name="emb_table"))
                flat = fluid.layers.reshape(
                    emb, shape=[-1, emb_cfg["slots"] * emb_cfg["dim"]])
                h = fluid.layers.fc(input=flat, size=256, act="relu")
                logits = fluid.layers.fc(input=h, size=2)
                avg_cost = fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(logits, label))
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(
                    avg_cost, startup_program=startup)
            # SCALE_EMB_BUDGET=<MB> mirrors bench.py's BENCH_EMB_BUDGET:
            # the beyond-HBM hot-row cache instead of fsdp row-sharding
            # (mutually exclusive per table) — the table stays unsharded
            # at every mesh size and only a budget-sized slab is
            # device-resident; extra columns report cache behavior
            emb_cfg["budget_mb"] = os.environ.get("SCALE_EMB_BUDGET")
            if n_devices > 1 and emb_cfg["budget_mb"] is None:
                from paddle_tpu.parallel import embedding as emb_mod
                main._mesh = Mesh(np.array(jax.devices()[:n_devices]),
                                  ("fsdp",))
                emb_mod.shard_table(main, "emb_table", "fsdp")
            x = rng.integers(0, emb_cfg["rows"],
                             (batch, emb_cfg["slots"])).astype(np.int64)
            y = rng.integers(0, 2, (batch, 1)).astype(np.int64)
        elif model_name == "lm":
            # planner-sharded LM family (ISSUE 15): the mesh size under
            # test is factored into data x fsdp x tp named axes and every
            # spec comes from planner.plan's role classification — the
            # sweep shows param_bytes_per_shard falling with fsdp x tp
            # while the planned collectives stay hidden (overlap_fraction)
            lm_cfg = {
                "vocab": int(os.environ.get("SCALE_LM_VOCAB", "512")),
                "d_model": int(os.environ.get("SCALE_LM_DMODEL", "64")),
                "layers": int(os.environ.get("SCALE_LM_LAYERS", "2")),
                "seqlen": int(os.environ.get("SCALE_LM_SEQLEN", "64"))}
            # feeds reuse the sweep's img/label plumbing (ids-as-img, like
            # the embedding family)
            with fluid.program_guard(main, startup):
                tok = fluid.layers.data(name="img",
                                        shape=[lm_cfg["seqlen"]],
                                        dtype="int64")
                lab = fluid.layers.data(name="label",
                                        shape=[lm_cfg["seqlen"]],
                                        dtype="int64")
                avg_cost = models.transformer_lm(
                    tok, lab, vocab_size=lm_cfg["vocab"],
                    d_model=lm_cfg["d_model"], n_head=4,
                    n_layer=lm_cfg["layers"])
                fluid.optimizer.Momentum(learning_rate=0.01,
                                         momentum=0.9).minimize(
                    avg_cost, startup_program=startup)
            if n_devices > 1:
                from paddle_tpu.parallel import planner as planner_mod
                tp = int(os.environ.get("SCALE_LM_TP", "2"))
                tp = tp if tp > 0 and n_devices % tp == 0 else 1
                rest = n_devices // tp
                fsdp = 2 if rest % 2 == 0 else 1
                dp = rest // fsdp
                mesh = Mesh(np.array(jax.devices()[:n_devices]).reshape(
                    dp, fsdp, tp), ("dp", "fsdp", "tp"))
                planner_mod.plan(main, mesh)
            x = rng.integers(0, lm_cfg["vocab"],
                             (batch, lm_cfg["seqlen"])).astype(np.int64)
            y = rng.integers(0, lm_cfg["vocab"],
                             (batch, lm_cfg["seqlen"])).astype(np.int64)
        else:
            with fluid.program_guard(main, startup):
                img = fluid.layers.data(name="img", shape=[3, 32, 32],
                                        dtype="float32")
                label = fluid.layers.data(name="label", shape=[1],
                                          dtype="int64")
                avg_cost, _, _ = models.build_image_classifier(
                    getattr(models, model_name), img, label, class_dim=10)
                fluid.optimizer.Momentum(learning_rate=0.001,
                                         momentum=0.9).minimize(
                    avg_cost, startup_program=startup)
            if n_devices > 1:
                main._mesh = Mesh(np.array(jax.devices()[:n_devices]),
                                  ("dp",))
            x = rng.standard_normal((batch, 3, 32, 32), dtype=np.float32)
            y = rng.integers(0, 10, (batch, 1)).astype(np.int64)

        exe = fluid.Executor(fluid.TPUPlace(0))
        k = steps_per_call
        # per-step feed is always built: the k=1 path runs on it (also the
        # probe path for `auto`), and static_memory_analysis below reports
        # the per-STEP footprint
        feed = {"img": jax.device_put(x), "label": jax.device_put(y)}

        def run_step():
            out, = exe.run(main, feed=feed, fetch_list=[avg_cost],
                           return_numpy=False)
            return out

        with em.scope_guard(em.Scope()):
            exe.run(startup)
            emb_cache = None
            if emb_cfg is not None and emb_cfg.get("budget_mb"):
                from paddle_tpu.parallel import emb_cache as emb_cache_mod
                emb_cache = emb_cache_mod.enable(
                    main, budget_bytes=int(
                        float(emb_cfg["budget_mb"]) * (1 << 20)))
            if k == "auto":
                # probe the compiled K=1 path for dispatch overhead, step
                # time and HBM headroom, then let the overlap pass pick K
                k = _auto_steps_per_call(exe, main, run_step, feed,
                                         avg_cost)
            if k > 1:
                # fused window: one [K, B, ...] feed, K steps per
                # dispatch; the dp state shardings ride the scan carry
                window = {"img": jax.device_put(np.stack([x] * k)),
                          "label": jax.device_put(np.stack([y] * k))}

                def run_one():
                    out, = exe.run_steps(main, feed_window=window,
                                         steps=k, fetch_list=[avg_cost],
                                         fetch_mode="last",
                                         return_numpy=False)
                    return out
            else:
                run_one = run_step

            warm_calls = max(1, -(-warmup // k))
            calls = max(1, steps // k)
            for _ in range(warm_calls):
                out = run_one()
            float(np.asarray(out).ravel()[0])
            cache_base = emb_cache.stats() if emb_cache else None
            t0 = time.perf_counter()
            for _ in range(calls):
                out = run_one()
            final = float(np.asarray(out).ravel()[0])
            dt = time.perf_counter() - t0
            steps = calls * k   # actual device steps timed
            peak_hbm = None
            try:
                # per-shard static footprint (memory_analysis of an SPMD
                # program is post-partitioning) — the memory column of the
                # memory/throughput trade-off this sweep exists to show
                rec = exe.static_memory_analysis(
                    main, feed=feed, fetch_list=[avg_cost])
                peak_hbm = rec.total_bytes
            except Exception:
                pass
            perf = _perf_fields(run_one)
            if emb_cfg is not None:
                perf.update(_embedding_fields(
                    main, emb_cfg, batch * steps / dt))
                perf.update(_emb_cache_fields(emb_cache, cache_base,
                                              steps))
            if lm_cfg is not None:
                # lm lines always carry the three planner columns;
                # overlap_fraction/busbw stay whatever the trace showed
                # (null when it had no collectives — 1-device runs)
                perf.update(_lm_fields(main))
                perf.setdefault("overlap_fraction", None)
                perf.setdefault("busbw", None)
            perf.update(_analyze_fields(main))
    assert np.isfinite(final)
    return batch * steps / dt, peak_hbm, perf, k


def measure_serving(n_devices):
    """SCALE_MODEL=serving (ISSUE 13): serve the criteo-style DLRM scorer
    with its table fsdp-row-sharded over an n-device mesh, through
    ServingEngine (per-bucket AOT executables) + DynamicBatcher under
    concurrent clients, and return the serving-trajectory line for this
    mesh size: p50_ms/p99_ms/qps/shed_fraction/bucket_hits/
    goodput_fraction (+ the 2x overload phase) — the serve-side companion
    to the training sweep's samples_per_sec."""
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as fluid
    from paddle_tpu import executor as em
    from paddle_tpu import telemetry
    from paddle_tpu.framework import unique_name
    from paddle_tpu.serving import DynamicBatcher, ServingEngine, run_load

    rows = int(os.environ.get("SCALE_EMB_ROWS", "100000"))
    dim = int(os.environ.get("SCALE_EMB_DIM", "64"))
    slots = int(os.environ.get("SCALE_EMB_SLOTS", "26"))
    clients = int(os.environ.get("SCALE_SERVE_CLIENTS", "4"))
    requests = int(os.environ.get("SCALE_SERVE_REQUESTS", "16"))
    max_batch = int(os.environ.get("SCALE_SERVE_MAX_BATCH", "16"))
    delay_ms = float(os.environ.get("SCALE_SERVE_DELAY_MS", "3.0"))

    with unique_name.guard():
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            ids = fluid.layers.data(name="ids", shape=[slots],
                                    dtype="int64")
            emb = fluid.layers.embedding(
                ids, size=[rows, dim], is_sparse=True,
                param_attr=fluid.ParamAttr(name="emb_table"))
            flat = fluid.layers.reshape(emb, shape=[-1, slots * dim])
            h = fluid.layers.fc(input=flat, size=256, act="relu")
            prob = fluid.layers.softmax(fluid.layers.fc(input=h, size=2))
        if n_devices > 1:
            from paddle_tpu.parallel import embedding as emb_mod
            main_prog._mesh = Mesh(np.array(jax.devices()[:n_devices]),
                                   ("fsdp",))
            emb_mod.shard_table(main_prog, "emb_table", "fsdp")

        scope = em.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        with em.scope_guard(scope):
            exe.run(startup)
        engine = ServingEngine(main_prog, feed_names=["ids"],
                               fetch_names=[prob.name], scope=scope,
                               max_batch=max_batch)
        rng = np.random.default_rng(0)
        choices = [1, 2, 3, max(1, max_batch // 4)]

        def make_feed(ci, ri):
            n = choices[(ci + ri) % len(choices)]
            return {"ids": rng.integers(0, rows, (n, slots))
                    .astype(np.int64)}

        batcher = DynamicBatcher(engine, max_delay_ms=delay_ms,
                                 max_queue_depth=32).start()
        try:
            # compile the buckets the load will hit outside the timed phase
            for b in sorted({engine.bucket_for(c) for c in choices}):
                engine.run_batch({"ids": rng.integers(0, rows, (b, slots))
                                  .astype(np.int64)})
            normal = run_load(batcher, make_feed, clients=clients,
                              requests_per_client=requests, label="normal")
            overload = run_load(batcher, make_feed, clients=2 * clients,
                                requests_per_client=requests,
                                deadline_ms=max(delay_ms * 8, 50.0),
                                label="overload")
        finally:
            batcher.stop()
        densify = telemetry.read_series("sparse_densify_fallback_total")
        line = {
            "devices": n_devices,
            "p50_ms": normal["p50_ms"], "p99_ms": normal["p99_ms"],
            "qps": round(normal["qps"], 1),
            "shed_fraction": normal["shed_fraction"],
            "bucket_hits": normal["bucket_hits"],
            "goodput_fraction": normal["goodput_fraction"],
            "overload": {k: overload[k] for k in
                         ("p50_ms", "p99_ms", "qps", "shed_fraction",
                          "bucket_hits", "goodput_fraction")},
            "table_rows": rows, "max_batch": max_batch,
            "compile_cache": {"hits": engine.cache_hits,
                              "misses": engine.cache_misses},
            "densify_fallbacks": sum(densify.values()),
        }
        engine.close()
    return line


def _analyze_fields(main):
    """analyze_errors / analyze_warnings for the per-mesh JSON line (same
    contract as bench.py): one static-verifier pass over the measured
    program. SCALE_ANALYZE=0 skips; failures degrade to no fields."""
    if os.environ.get("SCALE_ANALYZE", "1") != "1":
        return {}
    try:
        from paddle_tpu.analysis import analyze_program

        counts = analyze_program(main).counts()
        return {"analyze_errors": counts.get("error", 0),
                "analyze_warnings": counts.get("warning", 0)}
    except Exception as e:  # noqa: BLE001 - advisory, never kills the line
        print(f"static analysis skipped: {e}", file=sys.stderr)
        return {}


def _lm_fields(main):
    """Planner columns for the lm family: per-device parameter HBM under
    the written specs (`memory.per_shard_param_bytes` — the same number
    planner.validate_plan_bytes pins the plan against), null if the
    accounting fails. The 1-device run has no plan, so the column reads
    the full replicated footprint — the sweep's falling trend starts
    from it."""
    try:
        from paddle_tpu.parallel import per_shard_param_bytes
        return {"param_bytes_per_shard":
                per_shard_param_bytes(main)["per_device_bytes"]}
    except Exception:  # noqa: BLE001 - bytes column is best-effort
        return {"param_bytes_per_shard": None}


def _embedding_fields(main, emb_cfg, examples_per_sec):
    """Extra per-mesh columns for the embedding family: sparse-path
    throughput in rows touched (ids presented to the table) per second,
    the table geometry, whether scatter-apply was live, and per-shard
    table bytes — the 1/n memory trend the fsdp sharding buys."""
    from paddle_tpu.ops import sparse_ops
    out = {"rows_touched_per_sec": round(
               examples_per_sec * emb_cfg["slots"], 1),
           "table_rows": emb_cfg["rows"],
           "sparse_apply": sparse_ops.sparse_apply_enabled()}
    try:
        from paddle_tpu.parallel import embedding as emb_mod
        t = emb_mod.per_shard_table_bytes(main)["tables"].get("emb_table")
        if t is None:     # 1-device run: table never sharded
            t = {"bytes": emb_cfg["rows"] * emb_cfg["dim"] * 4,
                 "per_shard_bytes": emb_cfg["rows"] * emb_cfg["dim"] * 4}
        out["table_bytes"] = t["bytes"]
        out["table_bytes_per_shard"] = t["per_shard_bytes"]
    except Exception:  # noqa: BLE001 - bytes columns are best-effort
        pass
    return out


def _emb_cache_fields(emb_cache, base, steps):
    """bench.py-mirrored columns for the SCALE_EMB_BUDGET config: hit
    rate / flush bytes are deltas over the timed phase only (the warmup
    phase pays the compulsory misses), prefetch overlap is cumulative
    (null-equivalent 0.0 here — the sweep's fixed-feed loop issues no
    explicit prefetches; bench.py's BENCH_MODE=embedding drives that
    path). Columns emit null when the cache is off so the sweep's CSV
    stays rectangular across configs."""
    if emb_cache is None:
        return {"cache_rows": None, "cache_hit_rate": None,
                "prefetch_overlap_fraction": None,
                "flush_bytes_per_step": None}
    s = emb_cache.stats()
    d_hit = s["hits"] - base["hits"]
    d_miss = s["misses"] - base["misses"]
    t = next(iter(emb_cache.tables().values()))
    return {
        "cache_rows": t.cache_rows,
        "cache_hit_rate": round(d_hit / max(d_hit + d_miss, 1), 4),
        "prefetch_overlap_fraction": round(s["overlap_fraction"], 4),
        "flush_bytes_per_step": round(
            (s["flush_bytes"] - base["flush_bytes"]) / max(steps, 1), 1),
    }


def _perf_fields(run_one):
    """`top_ops` / `bound` / `device_duty_cycle` for the per-mesh JSON line
    (same contract as bench.py): re-run the already-compiled step a few
    times under a silent traced session and join the roofline report, so
    the sweep shows WHERE each mesh size spends its step next to how fast
    it goes. SCALE_PERF=0 skips it; any failure degrades to no extra
    fields — the scaling line itself must never die here."""
    if os.environ.get("SCALE_PERF", "1") != "1":
        return {}
    try:
        from paddle_tpu import roofline

        def step():
            float(np.asarray(run_one()).ravel()[0])

        report = roofline.capture(step, steps=3)
        if not report:
            return {}
        out = {"top_ops": roofline.top_ops(report),
               "device_duty_cycle": report.get("device_duty_cycle")}
        hc = report.get("kernel_counts")
        if hc:
            out["hlo_instructions"] = hc["instructions"]
            out["hlo_fusions"] = hc["fusions"]
        attributed = [r for r in report["rows"]
                      if r["bound"] != "unattributed"]
        out["bound"] = (attributed[0]["bound"] if attributed
                        else "unattributed")
        # per-kernel scoreboard + input-bound verdict (ISSUE 11), same
        # columns as bench.py
        ke = report.get("kernel_efficiency")
        if ke:
            out["kernel_efficiency"] = ke[:5]
        if report.get("input_bound") is not None:
            out["input_bound"] = report["input_bound"]
            if report.get("input_bound_remedy"):
                out["input_bound_remedy"] = report["input_bound_remedy"]
        try:
            # fleet fields (ISSUE 8): per-kind busbw for the mesh size
            # under test, cross-host skew, goodput — scaling regressions
            # show up here as busbw flatlining while devices grow
            from paddle_tpu import fleet
            bus = fleet.busbw_by_kind(report.get("collectives"))
            if bus:
                out["busbw"] = bus
            # overlap fields (ISSUE 9): exposed collective seconds and
            # the hidden fraction, per mesh size
            es = fleet.exposed_summary(report.get("collectives"))
            if es:
                out.update(es)
            snap = fleet.fleet_snapshot()
            out["fleet_skew"] = round(snap["step_skew"], 4)
            gp = fleet.goodput_report()
            if gp:
                out["goodput"] = round(gp["goodput_fraction"], 4)
        except Exception:  # noqa: BLE001 - fleet fields are best-effort
            pass
        return out
    except Exception as e:  # noqa: BLE001 - attribution is best-effort
        print(f"perf attribution skipped: {e}", file=sys.stderr)
        return {}


def main(argv):
    import jax
    argv = list(argv)
    steps_per_call = None
    if "--steps-per-call" in argv:
        i = argv.index("--steps-per-call")
        try:
            steps_per_call = _parse_steps_per_call(argv[i + 1])
        except (IndexError, ValueError):
            raise SystemExit(
                "--steps-per-call needs an integer argument or 'auto'")
        del argv[i:i + 2]
    if steps_per_call is None:
        steps_per_call = int(os.environ.get("SCALE_STEPS_PER_CALL", "1"))
    sizes = sorted({int(a) for a in argv}) or sorted(
        {1, 2, len(jax.devices())} & set(range(1, len(jax.devices()) + 1)))
    too_big = [s for s in sizes if s > len(jax.devices())]
    if too_big:
        raise SystemExit(
            f"requested mesh sizes {too_big} exceed the "
            f"{len(jax.devices())} available devices")
    if os.environ.get("SCALE_MODEL") == "serving":
        # serving sweep: one line per mesh size carrying the serving
        # trajectory keys instead of samples_per_sec
        last = None
        for n in sizes:
            line = measure_serving(n)
            last = line
            print(json.dumps(line), flush=True)
        if last is not None:
            print(json.dumps({
                "metric": "serving_qps", "value": last["qps"],
                "unit": "requests/sec", "devices": last["devices"],
                "p99_ms": last["p99_ms"],
                "goodput_fraction": last["overload"]["goodput_fraction"],
            }))
        return
    results = {}
    for n in sizes:
        sps, peak_hbm, perf, k = measure(n, steps_per_call=steps_per_call)
        results[n] = sps
        base = results[min(results)]
        eff = sps / (base / min(results) * n)
        # `steps_per_call` is the K that actually ran (auto resolves
        # per mesh size); the summary line keeps the requested value
        line = {"devices": n,
                "samples_per_sec": round(sps, 2),
                "scaling_efficiency": round(eff, 4),
                "steps_per_call": k,
                "peak_hbm_bytes": peak_hbm}
        line.update(perf)
        print(json.dumps(line), flush=True)
    if len(results) > 1:
        top = max(results)
        base = results[min(results)]
        eff = results[top] / (base / min(results) * top)
        model_name = os.environ.get("SCALE_MODEL", "vgg16")
        print(json.dumps({
            "metric": f"{model_name}_dp_scaling_efficiency",
            "value": round(eff, 4), "unit": "fraction",
            "devices": top, "steps_per_call": steps_per_call,
            "vs_baseline": round(eff / 0.6089, 3),  # ref 60.89% @ 100 tr
        }))


if __name__ == "__main__":
    main(sys.argv[1:])
