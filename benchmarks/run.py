#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is `workloads/<cell>.json`; it names its configuration
(`configs/<config>.json`, whose `family` is `families/<family>.py`), its
traffic kind (`traffic/<kind>.py`) and the metrics it reports; each
per-layer metric is `layer_metrics/<name>.py`. This file holds no table
of any of these: a later PR adds files and entries in BENCHMARK.json.

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` and, traced,
`breakdown`. --trace 0 reports the cell's end-to-end metrics, --trace 1
its per-layer metrics. Without a TPU, or with fewer chips than the cell
asks for, the exit code is not 0 and no result is printed.
"""

_PROCESS_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(kind, name, data_dir=HERE):
    with open(os.path.join(data_dir, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind, name):
    """The module `<kind>/<name>.py` of this directory, found by name
    (a name may hold dots and dashes, so it is loaded by path)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.%s.%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def say(**record):
    """Anything but the result goes on an earlier line."""
    print(json.dumps(record), flush=True)


def measure(name, seed, seconds, trace, data_dir=HERE, process_start=None):
    """Run cell `name` once and return the `evidence` dict the metrics
    are read from. `data_dir` holds `workloads/` and `configs/` (the
    tests keep tiny ones of their own). No device check here: the caller
    decides what it may run on.

    evidence: what the traffic kind returns (`correct`, `attempted`,
    `failed`, `metrics` = its end-to-end metrics, `window_s`, `items`,
    `memory_peak_bytes` = the most it saw held on a chip,
    `counters` = deltas of the program's counters over the window,
    `spans` = seconds of the benchmark's own host spans by name, ...)
    and `setup_s`, `cell`, `config`, `device`, `required_flops_per_item`
    and, traced, `trace` = trace_reduce.reduce_dir() of the traced steps
    (None if the trace holds no device operation)."""
    import jax
    from benchmarks import trace_reduce

    process_start = time.perf_counter() if process_start is None \
        else process_start
    cell = load_json("workloads", name, data_dir)
    config = load_json("configs", cell["config"], data_dir)
    family = load_module("families", config["family"])
    traffic = load_module("traffic", cell["traffic"])
    devices = jax.devices()[:cell["chips"]]

    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ev = traffic.run(cell, config, family, seconds, seed, trace_dir)
    ev["setup_s"] = ev.pop("window_start") - process_start
    say(cell=name, seed=seed,
        **{k: v for k, v in ev.items() if k not in ("counters", "spans")})
    ev.update(
        cell=cell, config=config, trace=None,
        required_flops_per_item=family.required_flops_per_item(config),
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": ev.pop("memory_peak_bytes")})
    if trace:
        ev["trace"] = trace_reduce.reduce_dir(trace_dir)
    return ev


def result_line(ev, trace):
    """The result object of a run from its evidence: the cell's
    end-to-end metrics, or traced its per-layer metrics, each read by
    `layer_metrics/<name>.py` (a reader that finds nothing returns None
    and its metric is left out)."""
    cell, device = ev["cell"], dict(ev["device"])
    result = {"correct": ev["correct"], "attempted": ev["attempted"],
              "failed": ev["failed"], "metrics": {}, "device": device}
    if not trace:
        measured = dict(ev["metrics"],
                        setup_s={"value": ev["setup_s"], "unit": "s"})
        result["metrics"] = {m: measured[m] for m in cell["end_to_end"]}
        return result
    for metric in cell["per_layer"]:
        reader = load_module("layer_metrics", metric)
        value = reader.compute(ev)
        if value is not None:
            result["metrics"][metric] = {"value": value,
                                         "unit": reader.UNIT}
    if ev["trace"] is not None:
        device.update(busy_s=ev["trace"]["busy_s"],
                      window_s=ev["trace"]["window_s"])
        result["breakdown"] = {
            "device_ops": ev["trace"]["device_ops"][:10],
            "idle_gaps": ev["trace"]["idle_gaps"][:10]}
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    import jax
    from paddle_tpu import chip
    from benchmarks import trace_reduce

    chip.enable_compile_cache()
    chips = load_json("workloads", args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            "%s needs %d TPU chip(s); jax found %d x %s (%s)\n" % (
                args.workload, chips, len(devices), devices[0].platform,
                devices[0].device_kind))
        return 1
    trace_reduce.peak_flops(devices[0].device_kind)  # unknown: an error
    result = result_line(
        measure(args.workload, args.seed, args.seconds, bool(args.trace),
                process_start=_PROCESS_START), bool(args.trace))
    if args.trace and not result["device"].get("busy_s"):
        sys.stderr.write("the traced run saw no operation on the device\n")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
