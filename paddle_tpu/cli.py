"""`paddle train`-style command line (reference:
paddle/trainer/TrainerMain.cpp:32-64 — jobs train/test/time driven by
--config; paddle/scripts/submit_local.sh.in:3-13 the `paddle` wrapper).

Usage:
    python -m paddle_tpu train --config=conf.py [--epochs N] [--save-dir D]
                               [--checkpoint-dir C] [--resume]
    python -m paddle_tpu time  --config=conf.py [--steps N]
    python -m paddle_tpu infer --model-dir=D --input=batch.npz
    python -m paddle_tpu telemetry [--log step.jsonl [--tail N]]
                                   [--prometheus] [--reduce]
    python -m paddle_tpu obs [--port P] [--steps N] [--hold]
    python -m paddle_tpu version

The config file is a Python module (the reference's --config was a Python
DSL file too, parsed by config_parser.py) defining:

    def build():
        ...build programs, apply an optimizer...
        return {"main_program": main, "startup_program": startup,
                "feed_order": ["x", "y"], "loss": loss_var,
                # optional: "fetch": [vars], "feed_targets": [vars]}

    def train_reader():   # yields per-sample tuples matching feed_order
        ...
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
import time as time_mod


def _load_config(path):
    spec = importlib.util.spec_from_file_location("paddle_tpu_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not hasattr(mod, "build"):
        raise SystemExit(f"config '{path}' must define build()")
    return mod


def _feeder(fluid, cfg, spec):
    feed_targets = spec.get("feed_targets")
    if feed_targets is None:
        block = spec["main_program"].global_block()
        feed_targets = [block.var(n) for n in spec["feed_order"]]
    return fluid.DataFeeder(feed_list=feed_targets, place=fluid.TPUPlace(0))


def cmd_train(args):
    import paddle_tpu as fluid
    from paddle_tpu.parallel import multihost

    cfg = _load_config(args.config)
    spec = cfg.build()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(spec["startup_program"])

    start_epoch = 0
    if args.checkpoint_dir and args.resume:
        meta = multihost.load_checkpoint(exe, args.checkpoint_dir,
                                         main_program=spec["main_program"])
        if meta:
            start_epoch = meta["step"] + 1
            print(f"resumed from checkpoint epoch {meta['step']}")

    feeder = _feeder(fluid, cfg, spec)
    import paddle_tpu.minibatch as minibatch
    batched = minibatch.batch(cfg.train_reader, batch_size=args.batch_size)

    loss_name = spec["loss"].name
    for epoch in range(start_epoch, args.epochs):
        t0 = time_mod.perf_counter()
        last = None
        n = 0
        for data in batched():
            last, = exe.run(spec["main_program"], feed=feeder.feed(data),
                            fetch_list=[loss_name])
            n += 1
        dt = time_mod.perf_counter() - t0
        import numpy as np
        print(f"epoch {epoch}: loss={float(np.asarray(last).ravel()[0]):.6f}"
              f" ({n} steps, {dt:.1f}s)")
        if args.checkpoint_dir:
            multihost.save_checkpoint(exe, args.checkpoint_dir, epoch,
                                      main_program=spec["main_program"])
    if args.save_dir:
        fetch = spec.get("fetch") or [spec["loss"]]
        fluid.io.save_inference_model(args.save_dir, spec["feed_order"],
                                      fetch, exe,
                                      main_program=spec["main_program"])
        print(f"saved inference model to {args.save_dir}")
    return 0


def cmd_time(args):
    """--job=time parity (reference TrainerBenchmark.cpp): steps/sec over
    synthetic repeats of the first batch."""
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.minibatch as minibatch

    cfg = _load_config(args.config)
    spec = cfg.build()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(spec["startup_program"])
    feeder = _feeder(fluid, cfg, spec)
    batched = minibatch.batch(cfg.train_reader, batch_size=args.batch_size)
    data = next(iter(batched()))
    feed = feeder.feed(data)
    loss_name = spec["loss"].name
    for _ in range(3):
        exe.run(spec["main_program"], feed=feed, fetch_list=[loss_name])
    t0 = time_mod.perf_counter()
    for _ in range(args.steps):
        out, = exe.run(spec["main_program"], feed=feed,
                       fetch_list=[loss_name], return_numpy=False)
    float(np.asarray(out).ravel()[0])
    dt = time_mod.perf_counter() - t0
    print(f"{args.steps} steps in {dt:.2f}s -> {args.steps / dt:.2f} steps/s")
    return 0


def cmd_checkgrad(args):
    """--job=checkgrad parity (reference TrainerMain.cpp:36): numeric
    central-difference gradients of the config's loss w.r.t. every
    parameter, compared against the analytic grads the IR backward pass
    emits. Optimizer-role ops are stripped so repeated loss evaluations
    never mutate the parameters."""
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.minibatch as minibatch
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.framework.framework import grad_var_name

    from paddle_tpu.io import _strip_training_ops

    cfg = _load_config(args.config)
    spec = cfg.build()
    main = spec["main_program"]
    block = main.global_block()
    # forward + backward (no optimizer updates) for the analytic grads;
    # forward-only for the many numeric loss evaluations — the executor
    # compiles whole programs regardless of fetch list, so evaluating the
    # loss on the fwd+bwd program would recompute every gradient 2*samples
    # times per parameter
    check = main.clone()
    cb = check.global_block()
    cb.desc.ops = [d for d in cb.desc.ops
                   if d.attrs.get("op_role") != "optimize"]
    cb._sync_ops()
    fwd_only = _strip_training_ops(main)

    params = sorted(p.name for p in block.all_parameters())
    grads = [grad_var_name(p) for p in params]
    missing = [g for g in grads if not cb.has_var(g)]
    if missing:
        raise SystemExit(
            f"checkgrad needs analytic grads in the program; missing "
            f"{missing} (did build() call minimize()?)")

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(spec["startup_program"])
    feeder = _feeder(fluid, cfg, spec)
    batched = minibatch.batch(cfg.train_reader, batch_size=args.batch_size)
    feed = feeder.feed(next(iter(batched())))
    loss_name = spec["loss"].name
    scope = executor_mod.global_scope()

    def run_loss():
        # pin the PRNG stream: the executor advances __rng_counter__ every
        # run, so without this a config with random ops (dropout,
        # uniform_random) would draw different noise per evaluation and
        # the central difference would measure noise, not gradient
        scope.set_var("__rng_counter__", 0)
        out, = exe.run(fwd_only, feed=feed, fetch_list=[loss_name])
        return float(np.ravel(out)[0])

    scope.set_var("__rng_counter__", 0)
    outs = exe.run(check, feed=feed, fetch_list=[loss_name] + grads)
    analytic = {p: np.asarray(g) for p, g in zip(params, outs[1:])}

    rng = np.random.RandomState(0)
    delta, worst, failed = args.delta, 0.0, []
    for p in params:
        w = np.array(scope.find_var(p), np.float64)
        flat = w.reshape(-1)
        k = min(args.samples, flat.size)
        idxs = rng.choice(flat.size, size=k, replace=False)
        for i in idxs:
            orig = flat[i]
            flat[i] = orig + delta
            scope.set_var(p, w.astype(np.float32))
            lp = run_loss()
            flat[i] = orig - delta
            scope.set_var(p, w.astype(np.float32))
            lm = run_loss()
            flat[i] = orig
            scope.set_var(p, w.astype(np.float32))
            num = (lp - lm) / (2 * delta)
            ana = float(analytic[p].reshape(-1)[i])
            err = abs(num - ana) / max(abs(num), abs(ana), 1.0)
            worst = max(worst, err)
            if err > args.rtol:
                failed.append((p, int(i), num, ana, err))
        print(f"checkgrad {p}: {k} elements ok "
              f"(max rel err so far {worst:.2e})")
    if failed:
        for p, i, num, ana, err in failed:
            print(f"FAIL {p}[{i}]: numeric {num:.6g} vs analytic "
                  f"{ana:.6g} (rel err {err:.2e})")
        return 1
    print(f"checkgrad PASSED: {len(params)} parameters, "
          f"max rel err {worst:.2e}")
    return 0


def cmd_infer(args):
    import numpy as np
    import paddle_tpu as fluid

    exe = fluid.Executor(fluid.TPUPlace(0))
    prog, feed_names, fetch_targets = fluid.io.load_inference_model(
        args.model_dir, exe)
    data = np.load(args.input)
    feed = {n: data[n] for n in feed_names}
    outs = exe.run(prog, feed=feed, fetch_list=fetch_targets)
    for name, val in zip([v.name for v in fetch_targets], outs):
        arr = np.asarray(val)
        print(f"{name} shape={list(arr.shape)}")
        np.savetxt(sys.stdout, arr.reshape(arr.shape[0], -1), fmt="%.6f")
    return 0


def cmd_telemetry(args):
    """Pretty-print a telemetry snapshot or tail/summarize a JSONL step log
    (the scrape-less half of the ISSUE's observability story: the same data
    prometheus_text() exports, readable from a shell)."""
    import json

    from paddle_tpu import telemetry

    if args.log:
        recs = telemetry.read_step_log(args.log)
        if args.tail:
            for r in recs[-args.tail:]:
                print(json.dumps(r, sort_keys=True))
            return 0
        by_kind = {}
        for r in recs:
            by_kind.setdefault(r.get("kind", "?"), []).append(r)
        print(f"{args.log}: {len(recs)} events")
        for kind in sorted(by_kind):
            rs = by_kind[kind]
            secs = [r["seconds"] for r in rs if "seconds" in r]
            line = f"  {kind:12s} {len(rs):6d}"
            if secs:
                line += (f"  total {sum(secs):.3f}s"
                         f"  mean {sum(secs) / len(secs) * 1e3:.2f}ms"
                         f"  max {max(secs) * 1e3:.2f}ms")
            print(line)
        misses = by_kind.get("cache_miss", [])
        if misses:
            sig = misses[-1].get("signature")
            print(f"  last retrace signature: {sig}")
        return 0

    snap = telemetry.snapshot(reduce=args.reduce)
    if args.prometheus:
        print(telemetry.prometheus_text(snap), end="")
        return 0
    scope = "fleet" if args.reduce else f"host {snap.get('host', 0)}"
    print(f"telemetry snapshot ({scope})")
    for kind in ("counters", "gauges"):
        series = snap.get(kind, {})
        if not series:
            continue
        print(f"{kind}:")
        for name in sorted(series):
            for lk in sorted(series[name]):
                label = f"{{{lk}}}" if lk else ""
                print(f"  {name}{label} = {_fmt_num(series[name][lk])}")
    hists = snap.get("histograms", {})
    if hists:
        print("histograms:")
        for name in sorted(hists):
            for lk in sorted(hists[name]):
                h = hists[name][lk]
                label = f"{{{lk}}}" if lk else ""
                n = h["count"]
                mean = h["sum"] / n if n else 0.0
                print(f"  {name}{label}: count={n:g} sum={h['sum']:.4f}s "
                      f"mean={mean * 1e3:.3f}ms")
    return 0


def cmd_memory(args):
    """HBM observability console (memory.py): static per-program footprint
    (Compiled.memory_analysis + the peak-liveness walk), live accounting
    after a real step and the donation audit — on the built-in smoke
    programs, a --config model, or a crash report's memory section."""
    import json

    from paddle_tpu import inspector, memory, telemetry

    if args.report:
        report = inspector.read_crash_report(args.report)
        section = {"memory": report.get("memory"),
                   "error": report.get("error")}
        if args.json:
            print(json.dumps(section, indent=2, sort_keys=True))
        else:
            print(inspector.format_crash_report(report))
        return 0

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod

    out = []

    def probe(label, main, loss, feed_fn, data_fn):
        exe = fluid.Executor(fluid.TPUPlace(0))
        entry = {"program": label, "batch": args.batch}
        rec = exe.static_memory_analysis(
            main, feed=feed_fn(args.batch), fetch_list=[loss])
        entry["static"] = rec.to_dict()
        if data_fn is not None:
            run_b = min(args.batch, 8)
            exe.run(main, feed=data_fn(run_b), fetch_list=[loss])
            entry["live"] = memory.tracker().last
        out.append(entry)

    with executor_mod.scope_guard(executor_mod.Scope()):
        if args.config:
            import paddle_tpu.minibatch as minibatch
            cfg = _load_config(args.config)
            spec = cfg.build()
            exe0 = fluid.Executor(fluid.TPUPlace(0))
            exe0.run(spec["startup_program"])
            feeder = _feeder(fluid, cfg, spec)
            batched = minibatch.batch(cfg.train_reader,
                                      batch_size=args.batch)
            feed = feeder.feed(next(iter(batched())))
            arrs = {n: np.asarray(v.array() if hasattr(v, "array") else v)
                    for n, v in feed.items()}

            def feed_fn(b):
                import jax
                return {n: jax.ShapeDtypeStruct((b,) + a.shape[1:], a.dtype)
                        for n, a in arrs.items()}

            probe(os.path.basename(args.config), spec["main_program"],
                  spec["loss"], feed_fn, lambda b: feed)
        else:
            for name in args.smoke.split(","):
                spec = memory.build_smoke(name.strip())
                exe0 = fluid.Executor(fluid.TPUPlace(0))
                exe0.run(spec["startup"])
                probe(spec["label"], spec["main"], spec["loss"],
                      spec["feed_fn"], spec["data_fn"])

    if args.json:
        print(json.dumps({"programs": out,
                          "report": memory.memory_report()},
                         indent=2, sort_keys=True, default=str))
        return 0

    fmt = memory._fmt_bytes
    for entry in out:
        s = entry["static"]
        print(f"== {entry['program']} (batch {entry['batch']}) ==")
        print(f"static: args={fmt(s['argument_bytes'])} "
              f"out={fmt(s['output_bytes'])} temp={fmt(s['temp_bytes'])} "
              f"alias={fmt(s['alias_bytes'])} "
              f"code={fmt(s['generated_code_bytes'])} "
              f"total={fmt(s['total_bytes'])}")
        if s.get("donated_bytes"):
            print(f"donation: donated={fmt(s['donated_bytes'])} "
                  f"aliased={fmt(s['alias_bytes'])} "
                  f"lost={fmt(s['donation_lost_bytes'])}")
        peak = s.get("peak") or {}
        if peak:
            print(f"liveness walk: peak={fmt(peak['peak_bytes'])} at "
                  f"instruction {peak['peak_pos']}/{peak['n_instructions']}"
                  f" ({peak['live_at_peak']} buffers live)")
            for row in peak.get("top") or []:
                print(f"  {fmt(row['bytes']):>12s}  {row['instruction']}"
                      f"  <- {row['op']}")
        live = entry.get("live")
        if live:
            print(f"live after 1 step: in_use={fmt(live['bytes_in_use'])} "
                  f"peak={fmt(live['peak_bytes'])} "
                  f"(source={live['source']})"
                  + ("".join(f" {k}={fmt(v)}"
                             for k, v in (live.get("classes") or {}).items())))
    if args.prometheus:
        print(telemetry.prometheus_text(), end="")
    return 0


def cmd_inspect(args):
    """Read back a flight-recorder crash report (inspector.py): the JSON a
    crashed run leaves behind, rendered as the post-mortem a human wants —
    error + attributed origin + last recorded steps."""
    import json

    from paddle_tpu import inspector

    report = inspector.read_crash_report(args.dump)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0
    print(inspector.format_crash_report(report, show_program=args.program))
    return 0


def _fmt_num(v: float) -> str:
    return f"{int(v)}" if float(v).is_integer() else f"{v:.6g}"


def _analyze_programs(args):
    """-> [(label, program, feeds, fetches)] from --config / --example /
    --smoke (exactly one)."""
    import paddle_tpu as fluid  # noqa: F401 - registers ops/layers

    if args.config:
        cfg = _load_config(args.config)
        spec = cfg.build()
        fetches = [spec["loss"].name] if spec.get("loss") is not None else []
        for v in spec.get("fetch") or []:
            n = v if isinstance(v, str) else v.name
            if n not in fetches:
                fetches.append(n)
        return [(os.path.basename(args.config), spec["main_program"],
                 list(spec.get("feed_order") or []), fetches)]
    if args.example:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        name = args.example
        path = name if os.path.exists(name) else os.path.join(
            root, "examples", "fluid", f"train_{name}.py")
        if not os.path.exists(path):
            raise SystemExit(f"no such example: {args.example} "
                             f"(looked for {path})")
        spec_ = importlib.util.spec_from_file_location("paddle_tpu_example",
                                                       path)
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        if not hasattr(mod, "build_programs"):
            raise SystemExit(f"example '{path}' has no build_programs()")
        built = mod.build_programs()
        return [(os.path.basename(path), built["main"],
                 list(built.get("feeds") or []),
                 list(built.get("fetches") or []))]
    from . import memory
    out = []
    for name in (args.smoke or "fit_a_line").split(","):
        b = memory.build_smoke(name.strip())
        feeds = sorted(k for k, _ in b["feed_fn"](1).items()) \
            if callable(b.get("feed_fn")) else []
        out.append((b.get("label", name), b["main"], feeds,
                    [b["loss"].name]))
    return out


def cmd_analyze(args):
    """Static verification of a program: `python -m paddle_tpu analyze
    --example fit_a_line` / `--config conf.py --strict` / `--smoke resnet
    --json`. Exit 1 under --strict when error-severity diagnostics exist.
    `analyze --threads` runs the thread-safety lockset lint over the
    paddle_tpu source tree instead (exit 1 on any error finding)."""
    import json

    from .analysis import analyze_program

    if args.threads:
        from .analysis.threads import analyze_threads
        report = analyze_threads()
        if args.json:
            print(json.dumps(report.to_dict(), indent=2))
        else:
            print(report.format(show_info=not args.no_info))
        return 0 if report.ok else 1

    rc = 0
    payloads = []
    for label, program, feeds, fetches in _analyze_programs(args):
        report = analyze_program(program, feeds=feeds or None,
                                 fetches=fetches or None)
        if args.json:
            payloads.append({"program": label, **report.to_dict()})
        else:
            print(f"== {label} ==")
            print(report.format(show_info=not args.no_info))
        if args.strict and not report.ok:
            rc = 1
    if args.json:
        print(json.dumps(payloads if len(payloads) > 1 else payloads[0],
                         indent=2))
    return rc


def _serve_engine(args):
    """-> (engine, label) from --model-dir / --example / --smoke. Examples
    must export infer_feeds/infer_fetches from build_programs() (the
    serving surface the two flagship examples ship); --smoke builds a tiny
    in-process fc scorer so the command works on a bare checkout."""
    import numpy as np  # noqa: F401
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.serving import ServingEngine

    if args.model_dir:
        return ServingEngine(args.model_dir, max_batch=args.max_batch), \
            args.model_dir
    if args.example:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        name = args.example
        path = name if os.path.exists(name) else os.path.join(
            root, "examples", "fluid", f"train_{name}.py")
        if not os.path.exists(path):
            raise SystemExit(f"no such example: {args.example} "
                             f"(looked for {path})")
        spec_ = importlib.util.spec_from_file_location(
            "paddle_tpu_serve_example", path)
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        built = mod.build_programs()
        if not built.get("infer_feeds") or not built.get("infer_fetches"):
            raise SystemExit(
                f"example '{path}' exports no serving surface "
                f"(build_programs() must return infer_feeds/infer_fetches)")
        scope = executor_mod.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        with executor_mod.scope_guard(scope):
            exe.run(built["startup"])
        return ServingEngine(built["main"],
                             feed_names=built["infer_feeds"],
                             fetch_names=built["infer_fetches"],
                             scope=scope, max_batch=args.max_batch), \
            os.path.basename(path)
    # --smoke: x[16] -> fc(32, relu) -> fc(4): compiles in well under a
    # second per bucket, exercises the whole ladder/batcher/shed stack
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        pred = fluid.layers.fc(input=h, size=4)
    scope = executor_mod.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with executor_mod.scope_guard(scope):
        exe.run(startup)
    return ServingEngine(main, feed_names=["x"], fetch_names=[pred.name],
                         scope=scope, max_batch=args.max_batch), "smoke"


def _serve_random_feed(engine, rng, rows):
    """Feed generator off the engine's declared feed geometry: ints draw
    from a small id range (valid for any vocab/table), floats from N(0,1);
    -1 inner dims (rare) default to 8."""
    import numpy as np
    feed = {}
    for name, (shape, dtype) in engine._feed_meta.items():
        dims = (rows,) + tuple(8 if d == -1 else d for d in shape[1:])
        if np.issubdtype(dtype, np.integer):
            feed[name] = rng.integers(0, 8, dims).astype(dtype)
        else:
            feed[name] = rng.standard_normal(dims).astype(dtype)
    return feed


def cmd_serve(args):
    """Concurrent-client serving benchmark: `python -m paddle_tpu serve
    --smoke` (or --example criteo_dlrm / --model-dir DIR). Spins up the
    ServingEngine + DynamicBatcher, drives a normal phase at N clients and
    an overload phase at 2N against the bounded queue, and prints one JSON
    line per phase with p50_ms/p99_ms/qps/shed_fraction/bucket_hits/
    goodput_fraction, plus an engine/batcher summary line."""
    import json

    import numpy as np
    from paddle_tpu.serving import DynamicBatcher, run_load

    engine, label = _serve_engine(args)
    rng = np.random.default_rng(0)
    rows_choices = [1, 2, 3, max(1, args.max_batch // 4)]

    def make_feed(ci, ri):
        rows = rows_choices[(ci + ri) % len(rows_choices)]
        return _serve_random_feed(engine, rng, rows)

    batcher = DynamicBatcher(engine, max_delay_ms=args.max_delay_ms,
                             max_queue_depth=args.max_queue_depth).start()
    try:
        for phase, clients in (("normal", args.clients),
                               ("overload", 2 * args.clients)):
            payload = run_load(batcher, make_feed, clients=clients,
                               requests_per_client=args.requests,
                               deadline_ms=args.deadline_ms, label=phase)
            payload["model"] = label
            print(json.dumps(payload, sort_keys=True))
    finally:
        batcher.stop()
        summary = {"model": label, "engine": engine.stats(),
                   "batcher": batcher.stats()}
        print(json.dumps(summary, sort_keys=True))
        engine.close()
    return 0


def cmd_obs(args):
    """Live observability plane smoke: start the scrapeable HTTP server
    (obs_server.py), enable request/step tracing, run a small training
    loop so the endpoints have live data, then self-scrape /metrics,
    /healthz and /spans over real HTTP and print one JSON summary line.
    With --hold the server keeps running after the loop (Ctrl-C exits) so
    an external Prometheus/curl can scrape a long-lived process."""
    import http.client
    import json

    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu import memory, obs_server, tracing

    if not args.no_trace:
        tracing.enable()
    srv = obs_server.start(port=args.port)
    print(f"obs: serving http://127.0.0.1:{srv.port} "
          f"(/metrics /healthz /spans /report)", file=sys.stderr)

    with executor_mod.scope_guard(executor_mod.Scope()):
        spec = memory.build_smoke(args.smoke)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(spec["startup"])
        feed = spec["data_fn"](args.batch)
        for _ in range(args.steps):
            exe.run(spec["main"], feed=feed, fetch_list=[spec["loss"]])
            if args.interval_ms:
                time_mod.sleep(args.interval_ms / 1000.0)

    def get(route):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        try:
            conn.request("GET", route)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    st_metrics, metrics_body = get("/metrics")
    _st_health, health_body = get("/healthz")
    st_spans, spans_body = get("/spans?n=8")
    st_dyn, dyn_body = get("/dynamics?n=4")
    dyn = json.loads(dyn_body)
    summary = {
        "port": srv.port,
        "steps": args.steps,
        "metrics": {"status": st_metrics, "bytes": len(metrics_body)},
        "healthz": json.loads(health_body),
        "spans": {"status": st_spans,
                  "returned": len(json.loads(spans_body)["spans"]),
                  "buffered": len(tracing.recent_spans())},
        "dynamics": {"status": st_dyn, "enabled": dyn.get("enabled"),
                     "samples": dyn.get("samples_recorded"),
                     "programs": len(dyn.get("programs") or {})},
    }
    if args.export_trace:
        n = tracing.export_chrome_trace(args.export_trace)
        summary["chrome_trace"] = {"path": args.export_trace,
                                   "events": n}
    print(json.dumps(summary, sort_keys=True, default=str))
    if args.hold:
        print("obs: holding — Ctrl-C to exit", file=sys.stderr)
        try:
            while True:
                time_mod.sleep(1.0)
        except KeyboardInterrupt:
            pass
    obs_server.stop()
    return 0 if st_metrics == 200 and st_spans == 200 \
        and st_dyn == 200 else 1


def cmd_sentinel(args):
    """Run-sentinel drill: start the supervisor (sentinel.py), inject a
    planted step-time regression, a loss spike, and a short hang
    (--smoke), then print the alert ledger and one JSON summary line.
    Without --smoke, starts the sentinel and holds, supervising whatever
    the process's telemetry shows (Ctrl-C exits)."""
    import json

    from paddle_tpu import sentinel as sentinel_mod

    sent = sentinel_mod.start(
        report_path=args.report,
        interval_s=args.interval) if sentinel_mod.active() is None \
        else sentinel_mod.active()

    if not args.smoke:
        print("sentinel: supervising — Ctrl-C to exit", file=sys.stderr)
        try:
            while True:
                time_mod.sleep(1.0)
        except KeyboardInterrupt:
            pass
        return 0

    # 1) anomaly drill: healthy baselines, then a planted step-time
    #    regression and a loss spike — each must raise exactly one alert
    for i in range(16):
        sent.feed("step_time_regression", 0.1 + 0.001 * (i % 3))
        sentinel_mod.observe_loss(2.5 + 0.01 * (i % 3))
        sent.feed("loss_spike", 2.5 + 0.01 * (i % 3))
    a1 = sent.feed("step_time_regression", 0.35)
    a2 = sent.feed("loss_spike", 30.0)

    # 2) hang drill: a dispatch that sleeps past its deadline, then
    #    recovers — watchdog must fire AND clear
    drill = sent.inject_stall(0.8, budget_s=0.3)
    hang = None
    deadline = time_mod.time() + 5.0
    while hang is None and time_mod.time() < deadline:
        hang = sent.hang_state()
        time_mod.sleep(0.05)
    drill.join(timeout=5.0)
    recovered = sent.hang_state() is None

    for a in sent.alerts():
        print(f"[alert] {a['rule']} severity={a['severity']} "
              f"value={a['value']:.4g} z={a['zscore']:.1f} "
              f"x{a['count']}", file=sys.stderr)
    if hang is not None:
        print(f"[hang] program={hang['program']} "
              f"report={hang['report_path']} "
              f"recovered={recovered}", file=sys.stderr)

    summary = {
        "alerts": len(sent.alerts()),
        "rules_fired": sorted({a["rule"] for a in sent.alerts()}),
        "hang": {"fired": hang is not None,
                 "report": hang.get("report_path") if hang else None,
                 "recovered": recovered},
    }
    print(json.dumps(summary, sort_keys=True, default=str))
    ok = (a1 is not None and a2 is not None
          and hang is not None and recovered)
    return 0 if ok else 1


def cmd_dynamics(args):
    """Training-dynamics observatory (dynamics.py).

    --smoke trains a small program with a PLANTED dead layer (an fc whose
    output is multiplied by 0.0, so its grads are exactly zero) and a
    PLANTED update spike (the feed magnitude jumps late in the run, the
    moral equivalent of an LR spike), polling the run sentinel each step
    and serving /dynamics over real HTTP. Exits 0 iff the dead-layer
    verdict fires, the dynamics_update_ratio_spike sentinel alert fires,
    and /dynamics serves the series. --json prints the full observatory
    payload; --watch reprints the verdict table every --interval s."""
    import json

    from paddle_tpu import dynamics as dynamics_mod

    if args.json and not args.smoke:
        print(json.dumps(dynamics_mod.payload(recent=args.recent),
                         sort_keys=True, default=str))
        return 0
    if args.watch and not args.smoke:
        try:
            while True:
                p = dynamics_mod.payload(recent=1)
                verd = p.get("verdicts") or []
                print(f"dynamics: {p['samples_recorded']} samples, "
                      f"{len(verd)} non-ok verdict(s)", file=sys.stderr)
                for v in verd:
                    print(f"  {v['program']}/{v['series']}: {v['code']}",
                          file=sys.stderr)
                time_mod.sleep(args.interval)
        except KeyboardInterrupt:
            return 0

    import http.client

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu import obs_server
    from paddle_tpu import sentinel as sentinel_mod
    from paddle_tpu.framework import unique_name

    with unique_name.guard():
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            live = fluid.layers.fc(input=x, size=8, act="relu")
            dead = fluid.layers.fc(input=x, size=8, act="relu")
            # the planted dead layer: x0.0 kills its gradient exactly
            h = live + fluid.layers.scale(dead, scale=0.0)
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(
                loss, startup_program=startup)

    sent = sentinel_mod.active() or sentinel_mod.start(interval_s=3600.0)
    srv = obs_server.start(port=args.port)
    print(f"dynamics: serving http://127.0.0.1:{srv.port}/dynamics",
          file=sys.stderr)

    rng = np.random.RandomState(7)
    spike_at = args.steps - 4
    with dynamics_mod.override(True, 1), \
            executor_mod.scope_guard(executor_mod.Scope()):
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        for i in range(args.steps):
            xb = rng.randn(args.batch, 8).astype(np.float32)
            if i >= spike_at:
                xb = xb * 8.0       # the planted update spike
            yb = rng.randn(args.batch, 1).astype(np.float32)
            exe.run(main_prog, feed={"x": xb, "y": yb},
                    fetch_list=[loss])
            sent.poll()

    verd = dynamics_mod.verdicts()
    dead_fired = any(v["code"] == "dead-layer" for v in verd)
    rules_fired = sorted({a["rule"] for a in sent.alerts()
                          if a["rule"].startswith("dynamics_")})
    spike_fired = "dynamics_update_ratio_spike" in rules_fired

    def get(route):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        try:
            conn.request("GET", route)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    st_dyn, dyn_body = get("/dynamics?n=4")
    served = json.loads(dyn_body) if st_dyn == 200 else {}
    http_ok = st_dyn == 200 and bool(served.get("programs"))

    for v in verd:
        print(f"[verdict] {v['program']}/{v['series']} [{v['role']}]: "
              f"{v['code']}", file=sys.stderr)
    for a in sent.alerts():
        if a["rule"].startswith("dynamics_"):
            print(f"[alert] {a['rule']} severity={a['severity']} "
                  f"value={a['value']:.4g} z={a['zscore']:.1f}",
                  file=sys.stderr)

    summary = {
        "steps": args.steps,
        "dead_layer_verdict": dead_fired,
        "update_ratio_alert": spike_fired,
        "dynamics_rules_fired": rules_fired,
        "verdicts": [f"{v['program']}/{v['series']}:{v['code']}"
                     for v in verd],
        "http": {"status": st_dyn,
                 "programs": len(served.get("programs") or {}),
                 "samples": served.get("samples_recorded")},
    }
    if args.json:
        summary["payload"] = dynamics_mod.payload(recent=args.recent)
    print(json.dumps(summary, sort_keys=True, default=str))
    obs_server.stop()
    return 0 if dead_fired and spike_fired and http_ok else 1


def cmd_version(_args):
    import paddle_tpu
    import jax
    print(f"paddle_tpu {getattr(paddle_tpu, '__version__', '0.2.0')} "
          f"(jax {jax.__version__}, "
          f"devices: {[d.platform for d in jax.local_devices()]})")
    return 0


def cmd_perf(args):
    """Roofline performance report (roofline.py): run a smoke program (or
    read an existing trace dir) and print the per-op attribution table —
    device time, analytic FLOPs/bytes, achieved TF/s, arithmetic
    intensity, and the compute/memory/unattributed bound verdict — plus
    the step-time waterfall and MFU/duty-cycle summary."""
    import json

    from paddle_tpu import roofline

    probe = not args.no_probe
    if args.trace_dir:
        report = roofline.collect_report(args.trace_dir, probe=probe)
    else:
        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod, memory

        with executor_mod.scope_guard(executor_mod.Scope()):
            spec = memory.build_smoke(args.smoke or "fit_a_line")
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(spec["startup"])
            feed = spec["data_fn"](args.batch)

            def run():
                return exe.run(spec["main"], feed=feed,
                               fetch_list=[spec["loss"]])

            run()   # warm compile OUTSIDE the trace: attribute steps,
                    # not the one-off XLA compile
            report = roofline.capture(run, steps=args.steps, probe=probe)

    if report is None:
        print("perf: no report (trace empty or capture failed)")
        return 1
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True, default=str)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True, default=str))
    else:
        for line in roofline.format_report(report):
            print(line)
    return 0


def cmd_fleet(args):
    """Fleet observability report (fleet.py): per-collective bandwidth
    attribution (kind, call site, bytes, busbw, % of link roofline,
    exposed ms), the goodput ledger, and the cross-host skew line. With
    --smoke the smoke program runs on a dp mesh over every local device
    (forcing 4 host devices on CPU) so the trace actually contains
    collectives; with --trace-dir an existing trace is attributed."""
    import json
    import os

    probe = not args.no_probe
    if args.trace_dir:
        from paddle_tpu import fleet
        result = {
            "collectives": fleet.collective_table(args.trace_dir,
                                                  probe=probe),
            "goodput": fleet.goodput_report(),
            "snapshot": None,
        }
    else:
        # more than one device makes the smoke's dp mesh real — must be
        # set before first backend touch, harmless when already decided
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

        import numpy as np
        import jax

        import paddle_tpu as fluid
        from paddle_tpu import executor as executor_mod, fleet, memory

        with executor_mod.scope_guard(executor_mod.Scope()):
            spec = memory.build_smoke(args.smoke or "fit_a_line")
            ndev = max(jax.local_device_count(), 1)
            spec["main"]._mesh = jax.sharding.Mesh(
                np.array(jax.local_devices()), ("dp",))
            batch = max(args.batch, ndev)
            batch -= batch % ndev     # dp-shardable batch
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(spec["startup"])
            feed = spec["data_fn"](batch)

            def run():
                return exe.run(spec["main"], feed=feed,
                               fetch_list=[spec["loss"]])

            run()   # warm compile OUTSIDE the trace
            result = fleet.capture(run, steps=args.steps, probe=probe)

    if result is None:
        print("fleet: no report (trace empty or capture failed)")
        return 1
    if args.report:
        with open(args.report, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True, default=str)
    if args.json:
        print(json.dumps(result, indent=2, sort_keys=True, default=str))
        return 0

    from paddle_tpu import fleet
    colls = result.get("collectives")
    if colls and colls.get("rows"):
        print(f"{'Collective':20s} {'Call site':22s} {'MB':>9s} "
              f"{'busbw GB/s':>11s} {'% link':>7s} {'Exposed(ms)':>12s}"
              f"  Axis")
        for r in colls["rows"]:
            bus = ("{:11.2f}".format(r["busbw_gbps"])
                   if r.get("busbw_gbps") is not None else
                   "          -")
            pct = ("{:6.1%}".format(r["pct_link"])
                   if r.get("pct_link") is not None else "     -")
            print("[coll] {:13s} {:22s} {:9.2f} {} {} {:12.3f}  {}".format(
                r["kind"], r["site"], r["bytes"] / 1e6, bus, pct,
                r["exposed_ms"], r.get("axis") or "-"))
        if colls.get("ici_gbps"):
            print("[coll] link roofline {:.1f} GB/s ({} participants)"
                  .format(colls["ici_gbps"],
                          colls.get("participants") or "?"))
    else:
        print("[coll] no collective events in the trace")
    for line in fleet.format_goodput(result.get("goodput")):
        print(line)
    snap = result.get("snapshot")
    if snap:
        print(fleet.format_fleet(snap))
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="paddle_tpu",
        description="TPU-native trainer CLI (reference `paddle train`)")
    sub = parser.add_subparsers(dest="job", required=True)

    p_train = sub.add_parser("train", help="train a --config model")
    p_train.add_argument("--config", required=True)
    p_train.add_argument("--epochs", type=int, default=1)
    p_train.add_argument("--batch-size", type=int, default=32)
    p_train.add_argument("--save-dir", default=None)
    p_train.add_argument("--checkpoint-dir", default=None)
    p_train.add_argument("--resume", action="store_true")
    p_train.set_defaults(fn=cmd_train)

    p_time = sub.add_parser("time", help="steps/sec benchmark of a config")
    p_time.add_argument("--config", required=True)
    p_time.add_argument("--steps", type=int, default=20)
    p_time.add_argument("--batch-size", type=int, default=32)
    p_time.set_defaults(fn=cmd_time)

    p_cg = sub.add_parser(
        "checkgrad", help="numeric-vs-analytic gradient check of a config")
    p_cg.add_argument("--config", required=True)
    p_cg.add_argument("--batch-size", type=int, default=8)
    p_cg.add_argument("--delta", type=float, default=5e-3)
    p_cg.add_argument("--samples", type=int, default=4,
                      help="elements checked per parameter")
    p_cg.add_argument("--rtol", type=float, default=5e-2)
    p_cg.set_defaults(fn=cmd_checkgrad)

    p_infer = sub.add_parser("infer", help="run a saved inference model")
    p_infer.add_argument("--model-dir", required=True)
    p_infer.add_argument("--input", required=True,
                         help=".npz with one array per feed name")
    p_infer.set_defaults(fn=cmd_infer)

    p_tel = sub.add_parser(
        "telemetry", help="print a metrics snapshot or tail a step log")
    p_tel.add_argument("--log", default=None,
                       help="JSONL step log to summarize (see "
                            "telemetry.enable_step_log / PADDLE_TPU_STEP_LOG)")
    p_tel.add_argument("--tail", type=int, default=0,
                       help="with --log: print the last N raw events")
    p_tel.add_argument("--prometheus", action="store_true",
                       help="emit Prometheus text exposition format")
    p_tel.add_argument("--reduce", action="store_true",
                       help="allreduce the snapshot across hosts first")
    p_tel.set_defaults(fn=cmd_telemetry)

    p_ins = sub.add_parser(
        "inspect", help="read a flight-recorder crash report")
    p_ins.add_argument("dump", help="crash-report JSON written by the "
                                    "inspector flight recorder")
    p_ins.add_argument("--json", action="store_true",
                       help="print the raw report JSON instead of a summary")
    p_ins.add_argument("--program", action="store_true",
                       help="include the recorded program dump")
    p_ins.set_defaults(fn=cmd_inspect)

    p_mem = sub.add_parser(
        "memory", help="HBM footprint: static analysis, live accounting")
    p_mem.add_argument("--smoke", default="fit_a_line,resnet",
                       help="comma list of built-in smoke programs "
                            "(fit_a_line, resnet)")
    p_mem.add_argument("--config", default=None,
                       help="measure a --config model instead of the smokes")
    p_mem.add_argument("--batch", type=int, default=32,
                       help="base batch size for the static analysis")
    p_mem.add_argument("--report", default=None,
                       help="print the memory/OOM section of a crash report "
                            "instead of measuring")
    p_mem.add_argument("--json", action="store_true",
                       help="emit JSON instead of the human summary")
    p_mem.add_argument("--prometheus", action="store_true",
                       help="append the Prometheus exposition (hbm_*/"
                            "memory_* gauges) after the summary")
    p_mem.set_defaults(fn=cmd_memory)

    p_perf = sub.add_parser(
        "perf", help="roofline report: per-op FLOPs/bytes attribution, "
                     "bound verdicts, waterfall, MFU")
    p_perf.add_argument("--smoke", nargs="?", const="fit_a_line",
                        default=None,
                        help="run a built-in smoke program under a traced "
                             "session (fit_a_line or resnet; default "
                             "fit_a_line)")
    p_perf.add_argument("--trace-dir",
                        help="attribute an existing jax.profiler trace dir "
                             "instead of running anything")
    p_perf.add_argument("--steps", type=int, default=3,
                        help="traced steps for --smoke (default 3)")
    p_perf.add_argument("--batch", type=int, default=16,
                        help="smoke-program batch size (default 16)")
    p_perf.add_argument("--json", action="store_true",
                        help="print the full report as JSON")
    p_perf.add_argument("--report", metavar="PATH",
                        help="also write the JSON report to PATH")
    p_perf.add_argument("--no-probe", action="store_true",
                        help="skip the matmul/HBM roofline probes")
    p_perf.set_defaults(fn=cmd_perf)

    p_fleet = sub.add_parser(
        "fleet", help="fleet observability: per-collective busbw "
                      "attribution, goodput ledger, cross-host skew")
    p_fleet.add_argument("--smoke", nargs="?", const="fit_a_line",
                         default=None,
                         help="run a built-in smoke program on a dp mesh "
                              "under a traced session (fit_a_line or "
                              "resnet; default fit_a_line)")
    p_fleet.add_argument("--trace-dir",
                         help="attribute an existing jax.profiler trace "
                              "dir instead of running anything")
    p_fleet.add_argument("--steps", type=int, default=3,
                         help="traced steps for --smoke (default 3)")
    p_fleet.add_argument("--batch", type=int, default=16,
                         help="smoke-program batch size, rounded to a "
                              "multiple of the device count (default 16)")
    p_fleet.add_argument("--json", action="store_true",
                         help="print the full report as JSON")
    p_fleet.add_argument("--report", metavar="PATH",
                         help="also write the JSON report to PATH")
    p_fleet.add_argument("--no-probe", action="store_true",
                         help="skip the ICI/matmul/HBM probes")
    p_fleet.set_defaults(fn=cmd_fleet)

    p_an = sub.add_parser(
        "analyze", help="static program verification: shape/dtype/"
                        "dataflow checks + fast-path preflight, no "
                        "tracing or execution")
    p_an.add_argument("--config", default=None,
                      help="a train-style --config module; analyzes its "
                           "build() main program")
    p_an.add_argument("--example", default=None,
                      help="a shipped example: fit_a_line, criteo_dlrm, "
                           "transformer_long_context, or a path to any "
                           "module with build_programs()")
    p_an.add_argument("--smoke", nargs="?", const="fit_a_line",
                      default=None,
                      help="built-in smoke program(s), comma-separated "
                           "(fit_a_line, resnet; default fit_a_line)")
    p_an.add_argument("--json", action="store_true",
                      help="machine-readable report (counts + "
                           "diagnostics)")
    p_an.add_argument("--strict", action="store_true",
                      help="exit 1 when any error-severity diagnostic "
                           "is reported")
    p_an.add_argument("--no-info", action="store_true",
                      help="hide info-severity advisories")
    p_an.add_argument("--threads", action="store_true",
                      help="thread-safety lint over the paddle_tpu "
                           "source tree: lockset discipline, lock-order "
                           "cycles, blocking-under-lock, thread hygiene "
                           "+ census (exit 1 on any error)")
    p_an.set_defaults(fn=cmd_analyze)

    p_srv = sub.add_parser(
        "serve", help="serving benchmark: AOT bucket cache + dynamic "
                      "batcher + load shedding under concurrent clients "
                      "(normal phase, then 2x overload); JSON line per "
                      "phase with p50/p99/qps/shed/goodput")
    p_srv.add_argument("--smoke", action="store_true",
                       help="serve a tiny built-in fc scorer (default "
                            "when neither --example nor --model-dir)")
    p_srv.add_argument("--example", default=None,
                       help="a shipped example exporting a serving "
                            "surface: criteo_dlrm or "
                            "transformer_long_context")
    p_srv.add_argument("--model-dir", default=None,
                       help="a save_inference_model directory")
    p_srv.add_argument("--clients", type=int, default=4,
                       help="concurrent client threads in the normal "
                            "phase (overload runs 2x; default 4)")
    p_srv.add_argument("--requests", type=int, default=16,
                       help="requests per client per phase (default 16)")
    p_srv.add_argument("--max-batch", type=int, default=16,
                       help="top of the padded-bucket ladder (default 16)")
    p_srv.add_argument("--max-delay-ms", type=float, default=3.0,
                       help="batch-close deadline in ms (default 3)")
    p_srv.add_argument("--max-queue-depth", type=int, default=32,
                       help="bounded queue: requests beyond this shed "
                            "with ServingOverloadError (default 32)")
    p_srv.add_argument("--deadline-ms", type=float, default=None,
                       help="per-request deadline; expired requests are "
                            "shed instead of executed (default none)")
    p_srv.set_defaults(fn=cmd_serve)

    p_obs = sub.add_parser(
        "obs", help="live observability plane: scrapeable /metrics "
                    "/healthz /spans /report HTTP server + traced "
                    "training smoke; prints one JSON summary line")
    p_obs.add_argument("--port", type=int,
                       default=int(os.environ.get("PADDLE_TPU_OBS_PORT")
                                   or 0),
                       help="bind port (default $PADDLE_TPU_OBS_PORT "
                            "or 0 = ephemeral)")
    p_obs.add_argument("--smoke", default="fit_a_line",
                       help="smoke program driving the live data "
                            "(fit_a_line or resnet; default fit_a_line)")
    p_obs.add_argument("--steps", type=int, default=20,
                       help="smoke steps to run (default 20)")
    p_obs.add_argument("--batch", type=int, default=16,
                       help="smoke batch size (default 16)")
    p_obs.add_argument("--interval-ms", type=float, default=0.0,
                       help="sleep between smoke steps in ms (default 0)")
    p_obs.add_argument("--no-trace", action="store_true",
                       help="leave span tracing off (default: enabled "
                            "for the smoke)")
    p_obs.add_argument("--export-trace", default=None,
                       help="write the span ring as chrome-trace JSON "
                            "here before exiting")
    p_obs.add_argument("--hold", action="store_true",
                       help="keep serving after the smoke until Ctrl-C")
    p_obs.set_defaults(fn=cmd_obs)

    p_sent = sub.add_parser(
        "sentinel", help="run sentinel: statistical anomaly alerts + "
                         "hang watchdog; --smoke injects a stall and a "
                         "loss spike and prints the alert ledger")
    p_sent.add_argument("--smoke", action="store_true",
                        help="inject a planted regression, loss spike "
                             "and short hang, print the ledger, exit")
    p_sent.add_argument("--report", default=None,
                        help="hang report path (default "
                             "$PADDLE_TPU_SENTINEL_REPORT or "
                             "paddle_tpu_hang.json)")
    p_sent.add_argument("--interval", type=float, default=5.0,
                        help="live poll interval seconds (default 5)")
    p_sent.set_defaults(fn=cmd_sentinel)

    p_dyn = sub.add_parser(
        "dynamics", help="training-dynamics observatory: per-layer "
                         "weight/grad/update-ratio health; --smoke "
                         "plants a dead layer + update spike and "
                         "checks the verdicts, alerts and /dynamics")
    p_dyn.add_argument("--smoke", action="store_true",
                       help="train the planted-failure program, print "
                            "verdicts/alerts, exit 0 iff all fire")
    p_dyn.add_argument("--json", action="store_true",
                       help="print the observatory payload as JSON "
                            "(with --smoke: appended to the summary)")
    p_dyn.add_argument("--watch", action="store_true",
                       help="reprint the verdict table every --interval "
                            "seconds until Ctrl-C")
    p_dyn.add_argument("--steps", type=int, default=24,
                       help="smoke steps (default 24; the last 4 carry "
                            "the planted spike)")
    p_dyn.add_argument("--batch", type=int, default=16,
                       help="smoke batch size (default 16)")
    p_dyn.add_argument("--port", type=int, default=0,
                       help="obs-server port for /dynamics (default 0 = "
                            "ephemeral)")
    p_dyn.add_argument("--recent", type=int, default=16,
                       help="rows per series in --json output")
    p_dyn.add_argument("--interval", type=float, default=2.0,
                       help="--watch refresh seconds (default 2)")
    p_dyn.set_defaults(fn=cmd_dynamics)

    p_ver = sub.add_parser("version")
    p_ver.set_defaults(fn=cmd_version)

    args = parser.parse_args(argv)
    from . import chip
    chip.enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
