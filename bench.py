#!/usr/bin/env python
"""Benchmarks vs the reference's published table (BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

BENCH_MODE selects the config family:
  resnet (default)   ResNet-50 train bs256 AMP-O2, vs 81.69 img/s
                     (reference's best published ResNet-50 train,
                     MKL-DNN 2x Xeon 6148, BASELINE.md §4)
  alexnet            AlexNet train, vs 626.53 img/s (§4 bs256)
  googlenet          GoogleNet train, vs 250.46 img/s (§4 bs64)
  vgg19              VGG-19 train, vs 28.46 img/s (§4 bs64)
  resnet_infer       ResNet-50 inference bs16, vs 217.69 img/s (§4)
  alexnet_infer      AlexNet inference bs16, vs 850.51 img/s (§4)
  googlenet_infer    GoogleNet inference bs16, vs 600.94 img/s (§4)
  vgg19_infer        VGG-19 inference bs16, vs 96.75 img/s (§4)
  lstm               2xLSTM+fc h512 bs64 seqlen100 IMDB config, ms/batch
                     vs 184 ms/batch (K40m, §3; benchmark/paddle/rnn/rnn.py)
  attention          flash-attention (Pallas, fwd+bwd) vs XLA einsum
                     attention at T=4096 causal — the long-context kernel
                     the 2018 reference has no counterpart for;
                     vs_baseline is the speedup over the XLA path
  smallnet           SmallNet (CIFAR-quick) train, vs 8122 img/s (§1 bs512)
  transformer        transformer-LM train step with use_flash attention
                     (models/transformer.py), tokens/sec + MFU
  ring_attention     transformer-LM T=32k train step, flash ring over an
                     'sp' mesh of all visible devices; vs a 1.58 s/step
                     regression anchor
  embedding          criteo-DLRM-style sparse embedding train step: a
                     [BENCH_EMB_ROWS x BENCH_EMB_DIM] table fsdp-sharded
                     over all visible devices, SelectedRows gradients and
                     Adam scatter-apply end-to-end; rows_touched_per_sec
                     plus per-shard HBM table bytes (ISSUE 10)

`--steps-per-call K` (or BENCH_STEPS_PER_CALL) drives the CNN families
through Executor.run_steps — K device steps per Python dispatch via one
lax.scan window — and every JSON line carries `steps_per_call` plus a
`python_overhead_per_step_ms` probe so the dispatch-overhead win is
measurable against the K=1 baseline. TPU-hosts only for conv families:
XLA:CPU compiles GRADIENT convolutions inside loop bodies with the naive
expander instead of the Eigen path (~60x, measured: a conv train step in
a scan runs 28s vs 0.47s for 8 top-level steps), so on a CPU host the
knob only shows its win on conv-free configs.

One attempt per family: a device error (a Mosaic refusal, an HBM overflow,
a lost chip) is a result, not noise — it propagates, the family's line
reads value=null with the error, and the exit code is 1. Every line names
the device it ran on (`platform`, `device_kind`, `device_count`); the
nominal peak behind `mfu` comes from paddle_tpu.chip.PEAKS by device kind
(an accelerator that is not in the table is an error; on the CPU `mfu` is
null). Every mode also reports the session's sustained-TF/s roofline and
MFU against it (BENCH_ROOFLINE=0 skips).
"""

import json
import os
import sys
import time

import numpy as np

BATCH = os.environ.get("BENCH_BATCH")
STEPS = int(os.environ.get("BENCH_STEPS", "20"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "25"))
AMP = os.environ.get("BENCH_AMP", "1") == "1"
# fused multi-step loop (Executor.run_steps): K device steps per Python
# dispatch. `--steps-per-call K` on the command line or the env var; 1 =
# the classic per-step path; `auto` measures dispatch overhead + HBM
# headroom on the compiled step and lets overlap.choose_steps_per_call
# pick K (ISSUE 9). Every JSON line reports the resolved value so the
# dispatch-overhead trend can be read off the history.


def _parse_steps_per_call(v):
    v = str(v).strip().lower()
    if v == "auto":
        return "auto"
    return int(v)


STEPS_PER_CALL = _parse_steps_per_call(
    os.environ.get("BENCH_STEPS_PER_CALL", "1"))
# O2 = bf16 end-to-end; O3 = O2 + int8/fp8 quantized matmul/conv compute
# (quant.py). An O3 line carries quant_hits/quant_fallbacks; the serving
# family quantizes with BENCH_QUANT=int8|fp8 (ServingEngine(quantize=)).
AMP_LEVEL = os.environ.get("BENCH_AMP_LEVEL", "O2")


def _peak_tflops():
    """Per-chip bf16 datasheet peak of the device this process runs on
    (paddle_tpu.chip.PEAKS, keyed by device_kind); None on the CPU."""
    from paddle_tpu import roofline
    return roofline.nominal_tflops()


def _mfu(flops_per_sec):
    """Model FLOP/s over the nominal peak; null where there is none."""
    peak = _peak_tflops()
    return round(flops_per_sec / (peak * 1e12), 4) if peak else None


# Per-family config. flops = forward GFLOPs/image at 224x224 (mul+add as 2);
# training step ~ 3x forward (fwd + input grad + weight grad). Baselines are
# the reference's best published number for the family (BASELINE.md §4;
# img/s, higher is better). train_bs: the reference's batch per family;
# VGG-19's larger activations favor a smaller batch.
CNN = {
    "resnet": dict(builder="resnet50", fwd_flops=4.09e9, train_bs=256,
                   train_base=81.69, infer_base=217.69, lr=0.1),
    # nets without batch norm diverge to NaN at lr=0.1 within the warmup
    # steps (the assert on the final loss is the guard); throughput is
    # lr-independent, so run them at a stable rate
    "alexnet": dict(builder="alexnet", fwd_flops=1.43e9, train_bs=256,
                    train_base=626.53, infer_base=850.51, lr=0.01),
    "googlenet": dict(builder="googlenet", fwd_flops=3.0e9, train_bs=256,
                      train_base=250.46, infer_base=600.94, lr=0.005),
    "vgg19": dict(builder="vgg19", fwd_flops=39.0e9, train_bs=128,
                  train_base=28.46, infer_base=96.75, lr=0.005),
    # SmallNet = CIFAR-quick (BASELINE.md §1: 63.039 ms/batch at bs512 on
    # K40m = 8122 img/s best published; no §4 inference row — reuse the
    # train anchor)
    "smallnet": dict(builder="smallnet_mnist_cifar", fwd_flops=2.05e7,
                     train_bs=512, train_base=8122.0, infer_base=8122.0,
                     lr=0.01, img=32, classes=10),
}
INFER_BS = 16  # the reference's §4 inference batch


def _make_batch(batch, shapes_dtypes, rng):
    out = {}
    for name, shape, dtype in shapes_dtypes:
        if dtype == "img":
            out[name] = rng.standard_normal((batch,) + shape,
                                            dtype=np.float32)
        else:
            out[name] = rng.integers(0, dtype, (batch,) + shape,
                                     ).astype(np.int32)
    return out


def _feeds(exe, batch, shapes_dtypes, rng):
    """Rotating pre-staged HBM batches through the DoubleBufferedFeeder
    (reader/pipeline.py; reference create_double_buffer_reader_op.cc).
    Pre-staged by default, so the step is measured without its input
    pipeline; BENCH_HOST_PIPELINE=1 switches to true per-step host
    uploads. The overlap path itself is correctness-tested in
    tests/test_input_pipeline.py."""
    import jax
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

    host_uploads = os.environ.get("BENCH_HOST_PIPELINE", "0") == "1"
    n_bufs = 3 if host_uploads else 2

    def make_batch():
        return _make_batch(batch, shapes_dtypes, rng)

    host = [make_batch() for _ in range(n_bufs)]
    if not host_uploads:
        host = [{k: jax.device_put(v, exe.device) for k, v in b.items()}
                for b in host]

    def reader():
        i = 0
        while True:
            yield host[i % len(host)]
            i += 1

    return iter(DoubleBufferedFeeder(
        reader, device=exe.device if host_uploads else None, capacity=1))


def _windows(exe, batch, shapes_dtypes, rng, k):
    """[K, B, ...] stacked windows for Executor.run_steps. Pre-staged in
    HBM and rotated by default (same rationale as _feeds);
    BENCH_HOST_PIPELINE=1 instead pulls each window through
    DoubleBufferedFeeder.next_window — per-batch host conversion overlapped
    with device compute, ONE stacked device_put per window."""
    import jax
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

    if os.environ.get("BENCH_HOST_PIPELINE", "0") == "1":
        def reader():
            while True:
                yield _make_batch(batch, shapes_dtypes, rng)

        feeder = DoubleBufferedFeeder(reader, device=None, capacity=2)

        def gen():
            while True:
                yield feeder.next_window(k, device=exe.device)
        return gen()

    windows = []
    for _ in range(2):
        batches = [_make_batch(batch, shapes_dtypes, rng) for _ in range(k)]
        windows.append({
            name: jax.device_put(np.stack([b[name] for b in batches]),
                                 exe.device)
            for name, _, _ in shapes_dtypes})

    def gen():
        i = 0
        while True:
            yield windows[i % len(windows)]
            i += 1
    return gen()


def _dispatch_overhead_ms(run_step, k, n=10):
    """Host-side Python cost of driving ONE device step: time n
    enqueue-only calls (no host sync between them — async dispatch means
    the host returns as soon as the work is queued) and divide by the n*k
    device steps they drive. This is the number run_steps exists to
    shrink: the same model at --steps-per-call 8 should read ~8x lower.
    Never allowed to kill the bench line."""
    try:
        out = run_step()
        float(np.asarray(out).ravel()[0])            # drain the pipeline
        t0 = time.perf_counter()
        for _ in range(n):
            out = run_step()
        dt = time.perf_counter() - t0
        float(np.asarray(out).ravel()[0])            # leave it drained
        return round(dt / (n * k) * 1e3, 4)
    except Exception as e:  # noqa: BLE001 - metric is best-effort
        sys.stderr.write(f"dispatch-overhead probe failed: {e}\n")
        return None


def _dynamics_overhead_fraction(run_step, n=12, reps=3, warm=16):
    """Measured cost of the training-dynamics observatory's fused
    on-device reduction (dynamics.py), as a fraction of step time:
    per-step wall with dynamics on vs off, alternating `reps` A/B rounds
    and keeping each arm's MINIMUM (the same noise discipline as
    bench_diff's better-of-N). Flipping dynamics.override changes the
    executor's jit cache token, so the arms are distinct executables,
    and each arm drains `warm` steps before its first timed round;
    without that the off-arm inherits the main loop's warmth and the
    comparison reads pure warmup as overhead. Best-effort — never kills the bench line. The
    acceptance bar is < 0.02 (ISSUE 19)."""
    try:
        from paddle_tpu import dynamics as dynamics_mod

        warmed = set()

        def _arm(enabled):
            with dynamics_mod.override(enabled):
                out = run_step()
                float(np.asarray(out).ravel()[0])    # compile + drain
                if enabled not in warmed:
                    warmed.add(enabled)
                    for _ in range(warm):
                        out = run_step()
                    float(np.asarray(out).ravel()[0])
                t0 = time.perf_counter()
                for _ in range(n):
                    out = run_step()
                float(np.asarray(out).ravel()[0])
                return (time.perf_counter() - t0) / n

        offs, ons = [], []
        for _ in range(reps):
            offs.append(_arm(False))
            ons.append(_arm(True))
        t_off, t_on = min(offs), min(ons)
        return round(max(t_on - t_off, 0.0) / t_off, 4)
    except Exception as e:  # noqa: BLE001 - metric is best-effort
        sys.stderr.write(f"dynamics-overhead probe failed: {e}\n")
        return None


def _auto_steps_per_call(exe, prog, run_step, feed, fetch):
    """`--steps-per-call auto`: measure the per-dispatch Python overhead
    and per-step device time on the already-compiled K=1 path, bound the
    window by the HBM headroom left over the K=1 footprint (HeadroomModel
    over the stacked feed window's linear growth), and let
    overlap.choose_steps_per_call pick K. Any probe failure degrades to
    whatever signals remain — the choice must never kill the bench."""
    from paddle_tpu.parallel import overlap as overlap_mod

    step_ms = None
    try:
        out = run_step()
        float(np.asarray(out).ravel()[0])        # compile + drain
        n = 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = run_step()
        float(np.asarray(out).ravel()[0])
        step_ms = (time.perf_counter() - t0) / n * 1e3
    except Exception as e:  # noqa: BLE001 - probe is best-effort
        sys.stderr.write(f"auto steps-per-call timing probe failed: {e}\n")
    overhead_ms = _dispatch_overhead_ms(run_step, 1)
    peak = budget = feed_bytes = None
    try:
        from paddle_tpu import memory as memory_mod
        rec = exe.static_memory_analysis(prog, feed=feed,
                                         fetch_list=[fetch])
        peak = rec.total_bytes
        budget = memory_mod.default_budget(exe.device)
        feed_bytes = int(sum(np.asarray(v).nbytes for v in feed.values()))
    except Exception as e:  # noqa: BLE001 - probe is best-effort
        sys.stderr.write(f"auto steps-per-call memory probe failed: {e}\n")
    k = overlap_mod.choose_steps_per_call(
        python_overhead_ms=overhead_ms, step_time_ms=step_ms,
        feed_bytes_per_step=feed_bytes, peak_bytes=peak,
        budget_bytes=budget)
    sys.stderr.write(
        f"steps-per-call auto -> {k} (dispatch {overhead_ms}ms/step, "
        f"step {None if step_ms is None else round(step_ms, 3)}ms, "
        f"feed {feed_bytes}B, peak {peak}B, budget {budget}B)\n")
    return k


def _timed_loop(run_step, warmup, steps):
    """Warm, then time `steps` back-to-back enqueues with ONE final sync
    (a mid-loop sync would serialize dispatch with execution). run_step()
    must return an on-device scalar (return_numpy=False). One attempt:
    whatever the device raises propagates. Returns (dt_seconds, steps)."""
    out = None
    for _ in range(max(warmup, 1)):
        out = run_step()
    float(np.asarray(out).ravel()[0])  # sync
    t0 = time.perf_counter()
    for _ in range(steps):
        out = run_step()
    final = float(np.asarray(out).ravel()[0])  # sync
    dt = time.perf_counter() - t0
    assert np.isfinite(final), f"non-finite fetch {final}"
    return dt, steps


_ROOFLINE = None


def _roofline_cached():
    """Same-session sustained bf16 matmul TF/s.

    A jitted lax.scan of data-dependent [n,n] bf16 matmuls (each depends on
    the previous, so the chain cannot be elided or reordered) with a scalar
    readback as the fence. Best-of-3 rounds of back-to-back calls. The
    result is the second MFU denominator: nominal peak is the datasheet;
    what a dense matmul chain sustains in this session is what a program
    can use."""
    global _ROOFLINE
    if _ROOFLINE is not None:
        return _ROOFLINE or None
    if os.environ.get("BENCH_ROOFLINE", "1") != "1":
        _ROOFLINE = False
        return None
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax

        n = int(os.environ.get("BENCH_ROOFLINE_N", "4096"))
        iters, calls = 16, 10
        rng = np.random.default_rng(0)
        scale = 1.0 / np.sqrt(n)  # variance-preserving: no bf16 overflow
        w = jnp.asarray(rng.standard_normal((n, n)) * scale, jnp.bfloat16)
        x = jnp.asarray(rng.standard_normal((n, n)) * scale, jnp.bfloat16)

        @jax.jit
        def chain(x, w):
            y, _ = lax.scan(lambda c, _: (c @ w, None), x, None,
                            length=iters)
            return (y[0, 0]).astype(jnp.float32)

        for _ in range(25):
            out = chain(x, w)
        float(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(calls):
                out = chain(x, w)
            float(out)  # device runs are ordered: last sync fences all
            best = min(best, time.perf_counter() - t0)
        tflops = 2.0 * iters * n ** 3 * calls / best / 1e12
        _ROOFLINE = {"tflops": round(tflops, 2), "n": n}
    except Exception as e:  # noqa: BLE001 - probe must never kill the bench
        _ROOFLINE = False
        sys.stderr.write(f"roofline probe failed: {e}\n")
        return None
    return _ROOFLINE


# step thunk of the family currently being measured; each main_* sets it so
# _emit can attach per-op roofline attribution to the JSON line. Cleared
# after every emit (a failed family must not reuse the previous one's step).
_PERF_STEP = [None]

# program of the family currently being measured (same lifecycle as
# _PERF_STEP): _emit runs the static verifier over it so every bench
# line carries analyze_errors/analyze_warnings (ISSUE 12) — a perf
# regression can be cross-read against new analyzer findings
_ANALYZE_PROG = [None]


def _analyze_fields():
    """analyze_errors / analyze_warnings for the JSON line. The analysis
    is abstract (no tracing, no device), so it adds milliseconds;
    BENCH_ANALYZE=0 skips it and any failure degrades to no fields."""
    prog = _ANALYZE_PROG[0]
    if prog is None or os.environ.get("BENCH_ANALYZE", "1") != "1":
        return {}
    try:
        from paddle_tpu.analysis import analyze_program

        counts = analyze_program(prog).counts()
        return {"analyze_errors": counts.get("error", 0),
                "analyze_warnings": counts.get("warning", 0)}
    except Exception as e:  # noqa: BLE001 - advisory, never kills the line
        sys.stderr.write(f"static analysis skipped: {e}\n")
        return {}


def _perf_fields(probe=None):
    """`top_ops` / `bound` / `device_duty_cycle` for the JSON line (ISSUE 6:
    every bench line carries the evidence the MFU campaign needs): runs the
    family's step 3 more times under a silent traced session and joins the
    roofline report. BENCH_PERF=0 skips it; any failure degrades to no
    extra fields — the bench line itself must never die here."""
    step = _PERF_STEP[0]
    if step is None or os.environ.get("BENCH_PERF", "1") != "1":
        return {}
    try:
        from paddle_tpu import roofline

        if probe:
            # reuse the session's sustained-matmul measurement instead of
            # probing twice (the ridge only needs the HBM probe on top)
            roofline._PROBES.setdefault("sustained_tflops", probe["tflops"])
        report = roofline.capture(step, steps=3)
        if not report:
            return {}
        out = {"top_ops": roofline.top_ops(report),
               "device_duty_cycle": report.get("device_duty_cycle")}
        hc = report.get("kernel_counts")
        if hc:
            # per-step kernel-count trend: fusion wins show up as fewer
            # HLO instructions/fusions at the same img/s (ISSUE 7)
            out["hlo_instructions"] = hc["instructions"]
            out["hlo_fusions"] = hc["fusions"]
        attributed = [r for r in report["rows"]
                      if r["bound"] != "unattributed"]
        out["bound"] = (attributed[0]["bound"] if attributed
                        else "unattributed")
        # per-kernel scoreboard (ISSUE 11): measured vs roofline-minimum
        # device time per op+shape
        ke = report.get("kernel_efficiency")
        if ke:
            out["kernel_efficiency"] = ke[:5]
        if report.get("input_bound") is not None:
            out["input_bound"] = report["input_bound"]
            if report.get("input_bound_remedy"):
                out["input_bound_remedy"] = report["input_bound_remedy"]
        try:
            # fleet fields (ISSUE 8): per-kind bus bandwidth, cross-host
            # step skew (1.0 single-host) and the goodput fraction
            from paddle_tpu import fleet
            bus = fleet.busbw_by_kind(report.get("collectives"))
            if bus:
                out["busbw"] = bus
            # overlap fields (ISSUE 9): collective time NOT hidden by
            # compute, and the hidden fraction — the tentpole's own metric
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
        sys.stderr.write(f"perf attribution failed: {e}\n")
        return {}


_DEVICE = None


def _device_fields():
    """platform / device_kind / device_count of this process, asked once:
    main() asks before the family runs, so the line of a failed attempt
    need not ask a device that may be the one that failed."""
    global _DEVICE
    if _DEVICE is None:
        from paddle_tpu import chip
        _DEVICE = chip.describe()
    return _DEVICE


def _emit(payload, errors=()):
    """Print the family's ONE JSON line: the device it ran on, the error
    of a failed attempt, and the session roofline (sustained TF/s + MFU
    against it)."""
    payload.update(_device_fields())
    # families that resolved `auto` set the chosen K explicitly; the rest
    # (LoD families can't window) effectively ran the per-step path
    payload.setdefault("steps_per_call",
                       STEPS_PER_CALL if isinstance(STEPS_PER_CALL, int)
                       else 1)
    if errors:
        payload["errors"] = list(errors)
    # no device probe behind a failed attempt: the device may be the fault
    probe = None if payload.get("value") is None else _roofline_cached()
    if probe:
        payload["sustained_tflops"] = probe["tflops"]
        mfu = payload.get("mfu")
        if mfu is not None and probe["tflops"] > 0:
            payload["mfu_nominal"] = mfu
            payload["mfu_vs_sustained"] = round(
                mfu * _peak_tflops() / probe["tflops"], 4)
    try:  # memory alongside images/sec; must never kill the bench line
        from paddle_tpu import memory as memory_mod
        mem = memory_mod.bench_summary()
        if mem:
            payload.setdefault("peak_hbm_bytes", mem["peak_hbm_bytes"])
            payload.setdefault("hbm_utilization", mem["hbm_utilization"])
    except Exception:
        pass
    if payload.get("value") is not None:
        payload.update(_perf_fields(probe))
    payload.update(_analyze_fields())
    try:  # quantization scoreboard (ISSUE 20): only on runs that could
        # quantize (O3 training or quantized serving), so older families'
        # lines keep their schema. quant_fallbacks is the acceptance
        # gate — a benched family must hit zero.
        from paddle_tpu import telemetry as _tel
        qh = _tel.read_series("quant_kernel_total")
        qf = _tel.read_series("quant_fallback_total")
        if AMP_LEVEL == "O3" or os.environ.get("BENCH_QUANT") or qh or qf:
            payload.setdefault("quant_hits", int(sum(qh.values())))
            payload.setdefault("quant_fallbacks", int(sum(qf.values())))
    except Exception:
        pass
    _PERF_STEP[0] = None
    _ANALYZE_PROG[0] = None
    print(json.dumps(payload))
    sys.stdout.flush()
    _append_history(payload)


def _append_history(payload):
    """Append the emitted line to the standing BENCH_HISTORY.jsonl ledger
    (ISSUE 17 satellite) — the series `tools/bench_diff.py --history`
    gates against. Ledger metadata (git sha,
    timestamp) is passed in via BENCH_GIT_SHA/BENCH_TS by the driver, not
    computed here — the bench process stays subprocess-free. BENCH_HISTORY
    names the file (default: BENCH_HISTORY.jsonl next to bench.py);
    0/off/none disables. Never kills the bench line."""
    raw = os.environ.get("BENCH_HISTORY", "").strip()
    if raw.lower() in ("0", "off", "none", "no", "false"):
        return
    path = raw or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl")
    mode = os.environ.get("BENCH_MODE", "resnet")
    record = {"ts": float(os.environ.get("BENCH_TS") or time.time()),
              "git_sha": os.environ.get("BENCH_GIT_SHA") or None,
              "mode": mode, "family": mode.partition("_")[0]}
    record.update(payload)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(record, default=str) + "\n")
    except OSError:
        pass


def main_cnn(family, train=True):
    import paddle_tpu as fluid
    from paddle_tpu import models

    cfg = CNN[family]
    builder = getattr(models, cfg["builder"])
    batch = int(BATCH) if BATCH else (cfg["train_bs"] if train else INFER_BS)
    side = cfg.get("img", 224)
    classes = cfg.get("classes", 1000)

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        img = fluid.layers.data(name="img", shape=[3, side, side],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if train:
            avg_cost, _, _ = models.build_image_classifier(
                builder, img, label, class_dim=classes)
            opt = fluid.optimizer.Momentum(learning_rate=cfg["lr"],
                                           momentum=0.9)
            if AMP:
                # bf16 matmul/conv compute on the MXU, fp32 master weights;
                # O2 keeps activations bf16 end-to-end (halves HBM traffic)
                opt = fluid.amp.decorate(opt, level=AMP_LEVEL)
            opt.minimize(avg_cost, startup_program=startup)
            fetch = avg_cost
        else:
            logits = builder(img, class_dim=classes, is_test=True)
            predict = fluid.layers.softmax(logits)
            # a scalar fetch keeps the timed loop sync-free; argmax-sum is
            # data-dependent so XLA cannot dead-code the network
            fetch = fluid.layers.reduce_sum(
                fluid.layers.reduce_max(predict, dim=-1))

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)

    rng = np.random.default_rng(0)
    shapes = [("img", (3, side, side), "img")]
    if train:
        shapes.append(("label", (1,), classes))  # infer programs take no label
    k = STEPS_PER_CALL
    if k == "auto":
        probe_feeds = _feeds(exe, batch, shapes, rng)

        def step1():
            out, = exe.run(main_prog, feed=next(probe_feeds),
                           fetch_list=[fetch], return_numpy=False)
            return out

        k = _auto_steps_per_call(exe, main_prog, step1, next(probe_feeds),
                                 fetch)
    if k > 1:
        windows = _windows(exe, batch, shapes, rng, k)

        def step():
            out, = exe.run_steps(main_prog, feed_window=next(windows),
                                 steps=k, fetch_list=[fetch],
                                 fetch_mode="last", return_numpy=False)
            return out

        # STEPS/WARMUP stay denominated in device steps; the loop counts
        # CALLS, each driving k steps through one lax.scan dispatch
        calls, warm = max(1, STEPS // k), max(1, -(-WARMUP // k))
    else:
        feeds = _feeds(exe, batch, shapes, rng)

        def step():
            out, = exe.run(main_prog, feed=next(feeds), fetch_list=[fetch],
                           return_numpy=False)
            return out

        calls, warm = STEPS, WARMUP

    _PERF_STEP[0] = step
    _ANALYZE_PROG[0] = main_prog
    dt, done = _timed_loop(step, warm, calls)
    done *= k
    overhead_ms = _dispatch_overhead_ms(step, k)
    img_s = batch * done / dt
    flops_per_img = (3 if train else 1) * cfg["fwd_flops"]
    mfu = _mfu(img_s * flops_per_img)
    base = cfg["train_base"] if train else cfg["infer_base"]
    job = "train" if train else "infer"
    _emit({
        "metric": f"{cfg['builder']}_{job}_images_per_sec",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_s / base, 3),
        "batch": batch,
        "amp": AMP if train else False,
        "amp_level": (AMP_LEVEL if AMP else None) if train else None,
        "steps_timed": done,
        "steps_per_call": k,
        "steps_per_call_mode": ("auto" if STEPS_PER_CALL == "auto"
                                else "fixed"),
        "python_overhead_per_step_ms": overhead_ms,
        "mfu": mfu,
    })


def main_fc():
    """Conv-free 3-layer MLP classifier (784-1024-1024-10, Momentum): the
    portable attribution family. No convolutions means no XLA:CPU
    grad-conv cliff inside scan bodies, so `--families fc` runs the full
    timed-loop + roofline-attribution path on any host — the CI smoke for
    the bench-side perf fields (ISSUE 6 acceptance)."""
    import paddle_tpu as fluid

    bsz = int(BATCH) if BATCH else 256
    hid = int(os.environ.get("BENCH_FC_HIDDEN", "1024"))
    classes = 10

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        x = fluid.layers.data(name="x", shape=[784], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=hid, act="relu")
        h = fluid.layers.fc(input=h, size=hid, act="relu")
        logits = fluid.layers.fc(input=h, size=classes, act="softmax")
        cost = fluid.layers.cross_entropy(input=logits, label=label)
        avg_cost = fluid.layers.mean(cost)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        if AMP:
            opt = fluid.amp.decorate(opt, level=AMP_LEVEL)
        opt.minimize(avg_cost, startup_program=startup)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)

    rng = np.random.default_rng(0)
    shapes = [("x", (784,), "img"), ("label", (1,), classes)]
    k = STEPS_PER_CALL
    if k == "auto":
        probe_feeds = _feeds(exe, bsz, shapes, rng)

        def step1():
            out, = exe.run(main_prog, feed=next(probe_feeds),
                           fetch_list=[avg_cost], return_numpy=False)
            return out

        k = _auto_steps_per_call(exe, main_prog, step1, next(probe_feeds),
                                 avg_cost)
    if k > 1:
        windows = _windows(exe, bsz, shapes, rng, k)

        def step():
            out, = exe.run_steps(main_prog, feed_window=next(windows),
                                 steps=k, fetch_list=[avg_cost],
                                 fetch_mode="last", return_numpy=False)
            return out

        calls, warm = max(1, STEPS // k), max(1, -(-WARMUP // k))
    else:
        feeds = _feeds(exe, bsz, shapes, rng)

        def step():
            out, = exe.run(main_prog, feed=next(feeds),
                           fetch_list=[avg_cost], return_numpy=False)
            return out

        calls, warm = STEPS, WARMUP

    _PERF_STEP[0] = step
    _ANALYZE_PROG[0] = main_prog
    dt, done = _timed_loop(step, warm, calls)
    done *= k
    ex_s = bsz * done / dt
    fwd_flops = 2 * (784 * hid + hid * hid + hid * classes)
    mfu = _mfu(3 * ex_s * fwd_flops)
    _emit({
        "metric": "fc_mlp_train_examples_per_sec",
        "value": round(ex_s, 1),
        "unit": "examples/sec",
        "vs_baseline": None,   # no reference-published MLP anchor
        "batch": bsz, "hidden": hid, "amp": AMP,
        "amp_level": AMP_LEVEL if AMP else None,
        "steps_timed": done,
        "steps_per_call": k,
        "steps_per_call_mode": ("auto" if STEPS_PER_CALL == "auto"
                                else "fixed"),
        "python_overhead_per_step_ms": _dispatch_overhead_ms(step, k),
        "dynamics_overhead_fraction": _dynamics_overhead_fraction(step),
        "mfu": mfu,
    })


def main_lstm():
    """2xLSTM+fc h512 bs64 seqlen100 (reference benchmark/paddle/rnn/rnn.py:
    embedding 128, simple_lstm = fc(4h)+lstmemory with peepholes, Adam)."""
    import paddle_tpu as fluid

    import jax

    vocab, emb_dim, hid = 30000, 128, int(os.environ.get("BENCH_HIDDEN",
                                                         "512"))
    bsz = int(os.environ.get("BENCH_LSTM_BATCH", "64"))
    seqlen = 100
    steps, warmup = STEPS, WARMUP
    baseline_ms = 184.0   # K40m, BASELINE.md §3

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        data = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                 lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(input=data, size=[vocab, emb_dim])
        h = emb
        for _ in range(2):
            proj = fluid.layers.fc(input=h, size=hid * 4,
                                    num_flatten_dims=2)
            h, _c = fluid.layers.dynamic_lstm(input=proj, size=hid * 4,
                                              use_peepholes=True)
        last = fluid.layers.sequence_last_step(h)
        logits = fluid.layers.fc(input=last, size=2, act="softmax")
        cost = fluid.layers.cross_entropy(input=logits, label=label)
        avg_cost = fluid.layers.mean(cost)
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(
            avg_cost, startup_program=startup)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)

    rng = np.random.default_rng(0)
    # fixed-length (pad_seq=True in the reference run): dense [B, T] ids
    ids = rng.integers(0, vocab, (bsz, seqlen)).astype(np.int32)
    labs = rng.integers(0, 2, (bsz, 1)).astype(np.int32)
    feed = {"words": jax.device_put(ids, exe.device),
            "label": jax.device_put(labs, exe.device)}

    def step():
        loss, = exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                        return_numpy=False)
        return loss

    _PERF_STEP[0] = step
    _ANALYZE_PROG[0] = main_prog
    dt, done = _timed_loop(step, warmup, steps)
    ms_batch = dt / done * 1000
    # fwd FLOPs/batch: input projections (emb->4H, H->4H) + recurrent gemm
    # (H->4H per step) for both layers; train step ~ 3x forward
    gemm = (emb_dim * 4 * hid + hid * 4 * hid    # layer1 proj + recur
            + hid * 4 * hid + hid * 4 * hid)     # layer2 proj + recur
    fwd_flops = 2 * bsz * seqlen * gemm
    mfu = _mfu(3 * fwd_flops / (dt / done))
    _emit({
        "metric": "lstm2_h512_train_ms_per_batch",
        "value": round(ms_batch, 2),
        "unit": "ms/batch",
        "vs_baseline": round(baseline_ms / ms_batch, 3),
        "batch": bsz, "seqlen": seqlen, "hidden": hid,
        "steps_timed": done,
        "mfu": mfu,
    })


def main_attention():
    """Pallas flash attention (fwd+bwd, O(T) memory) vs the XLA einsum
    reference at T=4096 causal — the kernel behind fused_attention
    (use_flash=True) and the in-shard blocks of ring attention. The 2018
    reference has no attention op at all (SURVEY.md §2.5 last row), so
    vs_baseline is the measured speedup over the XLA attention path on the
    same chip: >1 means the Pallas kernels beat the compiler."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_attention import flash_attention
    from paddle_tpu.parallel.ring_attention import attention_reference

    b = int(os.environ.get("BENCH_ATTN_BATCH", "1"))
    t = int(os.environ.get("BENCH_ATTN_SEQLEN", "4096"))
    h, d = 8, 64
    steps, warmup = STEPS, WARMUP
    rng = np.random.default_rng(1)
    q, k, v = [jax.device_put(rng.standard_normal((b, t, h, d))
                              .astype(np.float32)) for _ in range(3)]

    def make(fn):
        return jax.jit(jax.grad(
            lambda a, bb, c: jnp.sum(fn(a, bb, c) ** 2), argnums=(0, 1, 2)))

    def time_once(g, n):
        # a scalar readback is the fence
        r = g(q, k, v)
        float(np.asarray(r[0]).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(n):
            r = g(q, k, v)
        float(np.asarray(r[0]).ravel()[0])
        return (time.perf_counter() - t0) / n

    g_flash = make(lambda a, bb, c: flash_attention(a, bb, c, True))
    g_xla = make(lambda a, bb, c: attention_reference(a, bb, c, causal=True))
    # raw-jax family: no executor, no account, so attribution degrades to
    # duty cycle + unattributed rows — still worth carrying on the line
    _PERF_STEP[0] = lambda: float(
        np.asarray(g_flash(q, k, v)[0]).ravel()[0])
    # BENCH_ATTN_XLA=0 skips the einsum side entirely — at long T its
    # [T, T] residuals exhaust HBM, which is exactly flash's point
    run_xla = os.environ.get("BENCH_ATTN_XLA", "1") == "1"

    for g in ((g_flash, g_xla) if run_xla else (g_flash,)):
        r = None
        for _ in range(warmup):
            r = g(q, k, v)
        float(np.asarray(r[0]).ravel()[0])
    # alternate measurement rounds and take each side's best, so drift
    # between rounds hits both sides
    flash_ts, xla_ts = [], []
    for _ in range(3):
        flash_ts.append(time_once(g_flash, steps))
        if run_xla:
            xla_ts.append(time_once(g_xla, steps))
    flash_s = min(flash_ts)
    xla_s = min(xla_ts) if run_xla else None
    _emit({
        "metric": f"flash_attention_fwd_bwd_ms_T{t}_causal",
        "value": round(flash_s * 1e3, 3),
        "unit": "ms/step",
        "vs_baseline": round(xla_s / flash_s, 3) if run_xla else None,
        "xla_reference_ms": round(xla_s * 1e3, 3) if run_xla else None,
        "shape": [b, t, h, d],
    })


def _transformer_flops_per_token(n_layer, d_model, seqlen, vocab):
    """Forward FLOPs/token: per layer 2*(attn qkvo 4*d^2 + mlp 8*d^2) +
    attention scores 2*2*T*d, plus the vocab projection."""
    return n_layer * (2 * 12 * d_model ** 2
                      + 4 * seqlen * d_model) + 2 * vocab * d_model


def main_transformer():
    """Transformer-LM training step (models/transformer.py) with flash
    attention: tokens/sec + MFU. No reference counterpart (2018);
    vs_baseline is the ratio against the same model on the XLA einsum
    attention path (use_flash=False): attention alone, the kernels win
    from T=512 a device up on a v5e, on top of their O(T) memory (end to
    end T=1024 is what a benchmark cell covers: PERF.md section 6, PR
    29), and auto-selection keeps the einsum path below that
    (ops/nn_ops._flash_wins)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models

    bsz = int(BATCH) if BATCH else 8
    seqlen = int(os.environ.get("BENCH_SEQLEN", "2048"))
    n_layer = int(os.environ.get("BENCH_LAYERS", "4"))
    d_model = int(os.environ.get("BENCH_DMODEL", "512"))
    n_head = d_model // 64
    vocab = 8192
    steps, warmup = STEPS, WARMUP

    def build_and_time(use_flash):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            tok = fluid.layers.data(name="tok", shape=[-1, seqlen],
                                    dtype="int64", append_batch_size=False)
            lab = fluid.layers.data(name="lab", shape=[-1, seqlen],
                                    dtype="int64", append_batch_size=False)
            loss = models.transformer_lm(
                tok, lab, vocab_size=vocab, d_model=d_model,
                n_head=n_head, n_layer=n_layer, use_flash=use_flash)
            opt = fluid.optimizer.Adam(learning_rate=1e-4)
            if AMP:
                opt = fluid.amp.decorate(opt, level=AMP_LEVEL)
            opt.minimize(loss, startup_program=startup)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, vocab, (bsz, seqlen)).astype(np.int32)
        labs = rng.integers(0, vocab, (bsz, seqlen)).astype(np.int32)
        feed = {"tok": jax.device_put(ids, exe.device),
                "lab": jax.device_put(labs, exe.device)}

        def step():
            out, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            return out

        if use_flash:
            _PERF_STEP[0] = step
            _ANALYZE_PROG[0] = main_prog
        dt, done = _timed_loop(step, warmup, steps)
        return dt / done  # seconds per step

    sps = build_and_time(True)
    sps_xla = build_and_time(False)
    tok_s = bsz * seqlen / sps
    flops_tok = _transformer_flops_per_token(n_layer, d_model, seqlen, vocab)
    mfu = _mfu(3 * tok_s * flops_tok)  # train ~ 3x fwd
    _emit({
        "metric": "transformer_lm_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(sps_xla / sps, 3),
        "xla_attention_tokens_per_sec": round(bsz * seqlen / sps_xla, 1),
        "batch": bsz, "seqlen": seqlen, "layers": n_layer,
        "d_model": d_model, "amp": AMP, "mfu": mfu,
    })


def main_ring_attention():
    """Long-context flagship: transformer-LM train step at
    T=32k with sequence_parallel=True — ring attention over an 'sp' mesh
    spanning every visible device (on one chip the ring degenerates to
    the flash kernels + shard_map, which is exactly the single-chip
    long-context path; 8 on a CPU host mesh). The einsum
    path cannot run here at all: its [T, T] residuals are ~4 GB/head.
    vs_baseline guards an early regression number, 1.58 s/step."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models
    from jax.sharding import Mesh

    bsz = int(BATCH) if BATCH else 1
    seqlen = int(os.environ.get("BENCH_SEQLEN", "32768"))
    n_layer = int(os.environ.get("BENCH_LAYERS", "4"))
    d_model = int(os.environ.get("BENCH_DMODEL", "512"))
    n_head = d_model // 64
    vocab = 8192
    baseline_s = 1.58            # an early single-chip T=32k step
    # steps are ~1.5s each: a lighter default than the global 20/25
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    warmup = int(os.environ.get("BENCH_WARMUP", "8"))

    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(len(devs)), ("sp",))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        loss = models.transformer_lm(
            tok, lab, vocab_size=vocab, d_model=d_model, n_head=n_head,
            n_layer=n_layer, use_flash=True, sequence_parallel=True)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if AMP:
            opt = fluid.amp.decorate(opt, level=AMP_LEVEL)
        opt.minimize(loss, startup_program=startup)
    main_prog._mesh = mesh

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (bsz, seqlen)).astype(np.int32)
    labs = rng.integers(0, vocab, (bsz, seqlen)).astype(np.int32)
    feed = {"tok": jax.device_put(ids, exe.device),
            "lab": jax.device_put(labs, exe.device)}

    def step():
        out, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
        return out

    _PERF_STEP[0] = step
    _ANALYZE_PROG[0] = main_prog
    dt, done = _timed_loop(step, warmup, steps)
    s_step = dt / done
    tok_s = bsz * seqlen / s_step
    flops_tok = _transformer_flops_per_token(n_layer, d_model, seqlen, vocab)
    mfu = _mfu(3 * tok_s * flops_tok)
    _emit({
        "metric": f"ring_attention_transformer_T{seqlen}_sec_per_step",
        "value": round(s_step, 3),
        "unit": "sec/step",
        "vs_baseline": round(baseline_s / s_step, 3),
        "tokens_per_sec": round(tok_s, 1),
        "batch": bsz, "seqlen": seqlen, "layers": n_layer,
        "d_model": d_model, "sp_devices": len(devs), "amp": AMP,
        "steps_timed": done, "mfu": mfu,
    })


def main_embedding():
    """Criteo-DLRM-style sparse embedding family (ISSUE 10 + 14): one
    shared [ROWS, DIM] table looked up by SLOTS categorical features per
    example, trained with Adam through the SelectedRows scatter-apply
    path (no dense [ROWS, DIM] gradient or moment update ever
    materializes). The JSON line reports rows_touched_per_sec — the
    sparse-path throughput unit: ids presented to the table per second —
    next to the table geometry, whether scatter-apply was live, the
    densify-fallback count (must stay 0), and HBM table/opt-state bytes.
    No AMP: the table and its moments stay f32.

    Default config: table row-sharded over an fsdp mesh of every visible
    device (the cache columns emit null). BENCH_EMB_BUDGET=<MB> instead
    runs the beyond-HBM hot-row cache (ISSUE 14): the table stays
    UNSHARDED (cache and row-sharding are mutually exclusive per table),
    only a budget-sized slab is device-resident, ids draw from a zipf
    law (skew BENCH_EMB_ZIPF, default 1.3 — the criteo-like regime where
    a small hot set covers most lookups), training runs fused
    BENCH_EMB_WINDOW-step windows through DoubleBufferedFeeder with the
    NEXT window's rows prefetched behind the in-flight window's compute,
    and three more columns report steady-state (post-warmup) cache
    behavior: cache_hit_rate, prefetch_overlap_fraction, and
    flush_bytes_per_step. A rows>budget table trains fine — that is the
    point — and densify_fallbacks must still be 0: the cache feeds the
    same scatter-apply kernels, just slab-indexed."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import telemetry
    from paddle_tpu.ops import sparse_ops
    from paddle_tpu.parallel import emb_cache as emb_cache_mod
    from paddle_tpu.parallel import embedding as emb_mod
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

    bsz = int(BATCH) if BATCH else 256
    rows = int(os.environ.get("BENCH_EMB_ROWS", "1000000"))
    dim = int(os.environ.get("BENCH_EMB_DIM", "64"))
    slots = int(os.environ.get("BENCH_EMB_SLOTS", "26"))
    budget_mb = os.environ.get("BENCH_EMB_BUDGET")   # MB; enables cache
    zipf_a = float(os.environ.get("BENCH_EMB_ZIPF", "1.3"))
    k_window = int(os.environ.get("BENCH_EMB_WINDOW", "8"))
    devs = jax.devices()

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        ids = fluid.layers.data(name="ids", shape=[slots], dtype="int64")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[rows, dim], is_sparse=True,
            param_attr=fluid.ParamAttr(name="emb_table"))
        flat = fluid.layers.reshape(emb, shape=[-1, slots * dim])
        h = fluid.layers.fc(input=flat, size=256, act="relu")
        h = fluid.layers.fc(input=h, size=64, act="relu")
        logits = fluid.layers.fc(input=h, size=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(
            loss, startup_program=startup)
    if budget_mb is None:
        main_prog._mesh = make_mesh((len(devs),), ("fsdp",))
        emb_mod.shard_table(main_prog, "emb_table", "fsdp")

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    rng = np.random.default_rng(0)
    lab_np = rng.integers(0, 2, (bsz, 1)).astype(np.int64)

    def draw_ids():
        if zipf_a > 1.0:
            z = rng.zipf(zipf_a, (bsz, slots)).astype(np.int64) - 1
            return np.minimum(z, rows - 1)
        return rng.integers(0, rows, (bsz, slots)).astype(np.int64)

    cache = None
    if budget_mb is not None:
        cache = emb_cache_mod.enable(
            main_prog, budget_bytes=int(float(budget_mb) * (1 << 20)))
        if cache is None:
            raise RuntimeError(
                f"BENCH_EMB_BUDGET={budget_mb}MB covers the whole "
                f"{rows}x{dim} table (or PADDLE_TPU_EMB_CACHE=0) — "
                f"nothing beyond-HBM to measure")
        sparse_names = cache.feed_id_names()

        def batches():
            while True:
                yield {"ids": draw_ids(), "label": lab_np}

        feeder = DoubleBufferedFeeder(batches, window_prefetch=2)
        pending = {"win": None, "handle": None}
        calls = [0]
        steady = {}        # stats snapshot at the warmup->timed boundary

        def step():
            # overlapped driver: dispatch window i, pull + prefetch
            # window i+1 while i computes, then block on i's loss
            if pending["win"] is None:
                pending["win"], _ = feeder.next_window(
                    k_window, device=exe.device, sparse_slots=sparse_names)
            out = exe.run_steps(
                main_prog, feed_window=pending["win"], fetch_list=[loss],
                fetch_mode="last", return_numpy=False)
            nwin, nuniq = feeder.next_window(
                k_window, device=exe.device, sparse_slots=sparse_names)
            handle = cache.prefetch(nuniq)
            val = out[0]
            np.asarray(val)            # block: compute hides the prefetch
            handle.wait()
            pending["win"] = nwin
            calls[0] += 1
            if calls[0] == max(WARMUP, 1):    # steady-state boundary
                steady.update(cache.stats(), calls=calls[0])
            return val

        rows_per_call = bsz * slots * k_window
    else:
        ids_np = draw_ids()
        feed = {"ids": jax.device_put(ids_np),
                "label": jax.device_put(lab_np)}

        def step():
            out, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            return out

        rows_per_call = bsz * slots

    _PERF_STEP[0] = step
    _ANALYZE_PROG[0] = main_prog
    dt, done = _timed_loop(step, WARMUP, STEPS)
    s_call = dt / done

    cache_hit_rate = overlap_frac = flush_per_step = None
    if cache is not None:
        s = cache.stats()
        base = steady or {"hits": 0, "misses": 0, "flush_bytes": 0,
                          "calls": 0}
        d_hit = s["hits"] - base["hits"]
        d_miss = s["misses"] - base["misses"]
        d_steps = max((calls[0] - base.get("calls", 0)) * k_window, 1)
        cache_hit_rate = round(d_hit / max(d_hit + d_miss, 1), 4)
        overlap_frac = round(s["overlap_fraction"], 4)
        flush_per_step = round(
            (s["flush_bytes"] - base["flush_bytes"]) / d_steps, 1)

    per = emb_mod.per_shard_table_bytes(main_prog)
    t = per["tables"].get("emb_table") if per.get("tables") else None
    densify = telemetry.read_series("sparse_densify_fallback_total")
    cache_spec = (next(iter(cache.tables().values()))
                  if cache is not None else None)
    _emit({
        "metric": "embedding_rows_touched_per_sec",
        "value": round(rows_per_call / s_call, 1),
        "unit": "rows/sec",
        "vs_baseline": None,   # no reference-published criteo anchor
        "examples_per_sec": round(
            bsz * (k_window if cache is not None else 1) / s_call, 1),
        "batch": bsz, "table_rows": rows, "emb_dim": dim, "slots": slots,
        "zipf_skew": zipf_a if zipf_a > 1.0 else None,
        "sparse_apply": sparse_ops.sparse_apply_enabled(),
        "fsdp_devices": len(devs) if budget_mb is None else None,
        "table_bytes": t["bytes"] if t else rows * dim * 4,
        "table_bytes_per_shard": t["per_shard_bytes"] if t else None,
        "opt_state_bytes_per_shard":
            t["opt_state_per_shard_bytes"] if t else None,
        "cache_rows": cache_spec.cache_rows if cache_spec else None,
        "cache_hit_rate": cache_hit_rate,
        "prefetch_overlap_fraction": overlap_frac,
        "flush_bytes_per_step": flush_per_step,
        "densify_fallbacks": sum(densify.values()),
        "steps_timed": done,
    })
    if cache is not None:
        # only AFTER _emit: _perf_fields re-runs step() for roofline
        # attribution, and step() pulls from the feeder — stopping it
        # earlier deadlocks that capture on next_window
        feeder.stop()


def main_serving():
    """Inference serving family (ISSUE 13): ServingEngine (AOT per-bucket
    executables) + DynamicBatcher under concurrent client threads, a
    normal phase at N clients then a 2x overload phase against the
    bounded queue. The JSON line is the serving trajectory's unit record:
    p50_ms/p99_ms (end-to-end request latency), qps, shed_fraction,
    bucket_hits (which ladder rungs actually ran), and goodput_fraction
    under overload — reject-not-collapse means the overload phase should
    show shed_fraction > 0 with accepted requests still completing,
    rather than p99 exploding. BENCH_SERVE_MODEL picks fc (default),
    dlrm (fsdp-sharded sparse table; densify must stay 0 at serve time),
    or transformer (token-level latency)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod, models, telemetry
    from paddle_tpu.serving import DynamicBatcher, ServingEngine, run_load

    model = os.environ.get("BENCH_SERVE_MODEL", "fc")
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "4"))
    requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "16"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "16"))
    delay_ms = float(os.environ.get("BENCH_SERVE_DELAY_MS", "3.0"))
    queue_depth = int(os.environ.get("BENCH_SERVE_QUEUE_DEPTH", "32"))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        if model == "dlrm":
            rows, dim, slots = 100000, 32, 26
            ids = fluid.layers.data(name="ids", shape=[slots],
                                    dtype="int64")
            emb = fluid.layers.embedding(
                ids, size=[rows, dim], is_sparse=True,
                param_attr=fluid.ParamAttr(name="emb_table"))
            flat = fluid.layers.reshape(emb, shape=[-1, slots * dim])
            h = fluid.layers.fc(input=flat, size=256, act="relu")
            h = fluid.layers.fc(input=h, size=64, act="relu")
            out = fluid.layers.softmax(fluid.layers.fc(input=h, size=2))
            feeds, fetches = ["ids"], [out.name]
        elif model == "transformer":
            seqlen, vocab = 128, 1024
            tok = fluid.layers.data(name="tok", shape=[-1, seqlen],
                                    dtype="int64",
                                    append_batch_size=False)
            lab = fluid.layers.data(name="lab", shape=[-1, seqlen],
                                    dtype="int64",
                                    append_batch_size=False)
            _loss, logits = models.transformer_lm(
                tok, lab, vocab_size=vocab, d_model=128, n_head=2,
                n_layer=2, is_test=True, return_logits=True)
            feeds, fetches = ["tok"], [logits.name]
        else:
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            h = fluid.layers.fc(input=x, size=256, act="relu")
            h = fluid.layers.fc(input=h, size=64, act="relu")
            out = fluid.layers.fc(input=h, size=8)
            feeds, fetches = ["x"], [out.name]
    if model == "dlrm":
        from paddle_tpu.parallel import embedding as emb_mod
        from paddle_tpu.parallel.mesh import make_mesh
        main_prog._mesh = make_mesh((len(jax.devices()),), ("fsdp",))
        emb_mod.shard_table(main_prog, "emb_table", "fsdp")

    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with executor_mod.scope_guard(scope):
        exe.run(startup)
    quantize = os.environ.get("BENCH_QUANT", "").strip() or None
    if quantize and quantize.lower() in ("0", "off", "none", "f32"):
        quantize = None
    engine = ServingEngine(main_prog, feed_names=feeds,
                           fetch_names=fetches, scope=scope,
                           max_batch=max_batch, quantize=quantize)
    rng = np.random.default_rng(0)
    rows_choices = [1, 2, 3, max(1, max_batch // 4)]

    def rand_feed(n):
        feed = {}
        for name, (shape, dtype) in engine._feed_meta.items():
            dims = (n,) + tuple(8 if d == -1 else d for d in shape[1:])
            if np.issubdtype(dtype, np.integer):
                feed[name] = rng.integers(0, 8, dims).astype(dtype)
            else:
                feed[name] = rng.standard_normal(dims).astype(dtype)
        return feed

    def make_feed(ci, ri):
        return rand_feed(rows_choices[(ci + ri) % len(rows_choices)])

    batcher = DynamicBatcher(engine, max_delay_ms=delay_ms,
                             max_queue_depth=queue_depth).start()
    try:
        # bucket warm-up outside the timed phases: compile, don't measure
        for n in sorted({engine.bucket_for(r) for r in rows_choices}):
            engine.run_batch(rand_feed(n))
        normal = run_load(batcher, make_feed, clients=clients,
                          requests_per_client=requests, label="normal")
        overload = run_load(batcher, make_feed, clients=2 * clients,
                            requests_per_client=requests,
                            deadline_ms=max(delay_ms * 8, 50.0),
                            label="overload")
    finally:
        batcher.stop()
    densify = telemetry.read_series("sparse_densify_fallback_total")
    slo_report = batcher.slo_monitor.report()
    _emit({
        "metric": "serving_p50_ms",
        "value": normal["p50_ms"],
        "unit": "ms",
        "vs_baseline": None,
        "p50_ms": normal["p50_ms"], "p99_ms": normal["p99_ms"],
        "qps": round(normal["qps"], 1),
        "shed_fraction": normal["shed_fraction"],
        "bucket_hits": normal["bucket_hits"],
        "goodput_fraction": normal["goodput_fraction"],
        "timeouts": normal["timeouts"] + overload["timeouts"],
        "overload": {k: overload[k] for k in
                     ("p50_ms", "p99_ms", "qps", "shed_fraction",
                      "bucket_hits", "goodput_fraction")},
        "slo_burn_fast": slo_report["windows"]["fast"]["burn_rate"],
        "slo_burn_slow": slo_report["windows"]["slow"]["burn_rate"],
        "model": model, "clients": clients, "max_batch": max_batch,
        "quant": quantize,
        "compile_cache": {"hits": engine.cache_hits,
                          "misses": engine.cache_misses},
        "densify_fallbacks": sum(densify.values()),
    })
    engine.close()


def _dispatch(mode):
    if mode == "fc":
        return main_fc()
    if mode == "lstm":
        return main_lstm()
    if mode == "attention":
        return main_attention()
    if mode == "transformer":
        return main_transformer()
    if mode == "ring_attention":
        return main_ring_attention()
    if mode == "embedding":
        return main_embedding()
    if mode == "serving":
        return main_serving()
    family, _, job = mode.partition("_")
    if family not in CNN or job not in ("", "infer"):
        raise SystemExit(f"unknown BENCH_MODE={mode}")
    return main_cnn(family, train=(job != "infer"))


def main():
    """Run the selected family once. A failure still prints the family's
    JSON line — value=null plus the error — and returns 1: whatever the
    device raised is the result, so nothing is retried or rebuilt."""
    from paddle_tpu import chip
    chip.enable_compile_cache()
    _device_fields()
    mode = os.environ.get("BENCH_MODE", "resnet")
    try:
        return _dispatch(mode)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 - reported on the line, rc 1
        import traceback
        traceback.print_exc()
        _emit({"metric": mode, "value": None, "unit": None,
               "vs_baseline": None}, [f"{type(e).__name__}: {e}"[:300]])
        return 1


if __name__ == "__main__":
    args = sys.argv[1:]
    if "--steps-per-call" in args:
        STEPS_PER_CALL = _parse_steps_per_call(
            args[args.index("--steps-per-call") + 1])
    if "--families" in args:
        # run several families back-to-back, one JSON line each
        # (e.g. `bench.py --families fc,resnet,lstm`); exit code is the
        # worst of the runs
        rc = 0
        for fam in args[args.index("--families") + 1].split(","):
            fam = fam.strip()
            if not fam:
                continue
            os.environ["BENCH_MODE"] = fam
            rc = max(rc, main() or 0)
        sys.exit(rc)
    sys.exit(main())
