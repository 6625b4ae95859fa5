"""Traffic kind `train_steps`: a training job as a user runs one. Host
numpy batches (a seeded pool, cycled) go through
reader.pipeline.DoubleBufferedFeeder into Executor.run one step at a
time, with at most `steps_in_flight` steps dispatched and not yet
fetched; every loss is fetched and must be finite, and the first step
(its loss, the gradient its optimizer applied and the parameters it left)
must agree with the family's plain float32 reference on the same weights
and batch (reference_check.py), before the window.

The window runs until `seconds` have passed and the steps in flight have
been fetched; the rate is all items of all steps over all of that time.
A traced run measures the same window and then traces `trace_steps`
further steps, fed and pipelined the same way.
"""

import collections
import time

import numpy as np

from benchmarks import evidence, reference_check


def run(cell, config, family, seconds, seed, trace_dir):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.reader.pipeline import DoubleBufferedFeeder

    clock = {"start": time.perf_counter()}
    rng = np.random.default_rng(seed)
    main, startup, loss = family.build(config)
    rule = reference_check.rule_of(config)
    pool = [family.make_batch(config, cell["batch"], rng)
            for _ in range(cell["pool_batches"])]
    items_per_step = family.items_per_batch(pool[0])

    def reader():
        i = 0
        while True:
            yield pool[i % len(pool)]
            i += 1

    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = executor_mod.Scope()
    spans = evidence.Spans()
    in_flight = collections.deque()
    losses = []
    devices = jax.devices()[:cell["chips"]]
    # the highest sample of the bytes held on a chip, a step in flight
    result = {"memory_peak_bytes": 0}

    def fetch_oldest():
        with spans.span("fetch"):
            losses.append(float(np.ravel(np.asarray(in_flight.popleft()))[0]))

    def step(feeds):
        with spans.span("feed"):
            feed = next(feeds)
        with spans.span("dispatch"):
            out, = exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)
        in_flight.append(out)
        result["memory_peak_bytes"] = max(
            result["memory_peak_bytes"], evidence.memory_bytes_now(devices))
        if len(in_flight) >= cell["steps_in_flight"]:
            fetch_oldest()

    def drain():
        while in_flight:
            fetch_oldest()

    feeder = DoubleBufferedFeeder(reader, device=exe.device,
                                  capacity=cell["feeder_capacity"])
    try:
        with executor_mod.scope_guard(scope):
            clock["built"] = time.perf_counter()
            # the seed makes the weights through the executor's run-time
            # PRNG counter, not through program.random_seed: that one is
            # folded into the compiled programs, so every new seed would
            # compile the startup program anew (20 s of set-up, chip run)
            scope.set_var("__rng_counter__", seed % 2 ** 32)
            exe.run(startup)
            clock["startup"] = time.perf_counter()
            names = [p.name for p in main.global_block().all_parameters()
                     if p.trainable]
            params = [scope.find_var(n) for n in names]
            start_params = [np.asarray(p) for p in params]
            ref_loss, ref_grads = reference_check.reference_step(
                family, config, params, pool[0])
            del params
            clock["reference"] = time.perf_counter()
            feeds = iter(feeder)
            step(feeds)
            drain()
            clock["first_step"] = time.perf_counter()
            slots = reference_check.state_names(main, rule)
            result["reference"] = reference_check.compare(
                rule, config, names, start_params,
                [np.asarray(scope.find_var(n)) for n in names],
                {n: {s: np.asarray(scope.find_var(v))
                     for s, v in slots[n].items()} for n in names},
                losses[0], ref_loss, ref_grads, cell["reference"])
            del start_params, ref_grads
            clock["compared"] = time.perf_counter()
            for _ in range(cell["warmup_steps"] - 1):
                step(feeds)
                drain()
            losses.clear()
            spans.seconds.clear()

            before = evidence.counters_now()
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                step(feeds)
            drain()
            window_s = time.perf_counter() - t0
            result["counters"] = evidence.counters_delta(
                before, evidence.counters_now())
            result["spans"] = {k: list(v) for k, v in spans.seconds.items()}
            steps = len(losses)

            if trace_dir is not None:
                jax.profiler.start_trace(trace_dir)
                try:
                    for _ in range(cell["trace_steps"]):
                        step(feeds)
                    drain()
                finally:
                    jax.profiler.stop_trace()
    finally:
        feeder.stop()

    ok = result["reference"]["ok"]
    failed = sum(1 for v in losses[:steps] if not np.isfinite(v))
    marks = list(clock) + ["window"]
    clock["window"] = t0
    result["setup_phases_s"] = {b: clock[b] - clock[a]
                                for a, b in zip(marks, marks[1:])}
    result.update(
        window_start=t0, window_s=window_s, steps=steps,
        items=steps * items_per_step, items_per_step=items_per_step,
        attempted=steps, failed=failed, correct=bool(ok and not failed),
        metrics={"train_items_per_s": {
            "value": steps * items_per_step / window_s, "unit": "items/s"}})
    return result
