"""The benchmark's command rehearsed on the CPU: each traffic kind through
run.py's functions at the tiny configurations of data/, and main()'s
refusal to measure without a TPU. Nothing here is a speed."""

import glob
import json
import os

import pytest

from benchmarks import run, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

# every tiny cell under data/workloads/ is rehearsed: a later PR gives a
# new traffic kind or family its rehearsal by adding a file there
TINY_CELLS = sorted(os.path.splitext(os.path.basename(p))[0] for p in
                    glob.glob(os.path.join(DATA, "workloads", "*.json")))


@pytest.fixture(scope="module")
def evidence_of(tmp_path_factory):
    """name -> evidence of one traced run of that tiny cell (run once:
    both result lines are read from the same evidence). The CPU gets a
    row in the peaks table and the conv kernels are left to XLA: the
    interpreted kernels are tests/test_chip_smoke.py's business and
    would take most of a minute here."""
    from paddle_tpu.ops import pallas_conv

    found = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(trace_reduce.PEAKS, "cpu", {"bf16_flops_per_s": 1e12})
        patch.setattr(pallas_conv, "PALLAS_CONV", False)
        patch.setattr(run, "TRACE_DIR",
                      str(tmp_path_factory.mktemp("bench_trace")))

        def get(name):
            if name not in found:
                found[name] = run.measure(name, seed=2 ** 31 + 11,
                                          seconds=0.5, trace=True,
                                          data_dir=DATA)
            return found[name]

        yield get


@pytest.mark.parametrize("name", TINY_CELLS)
def test_end_to_end_line(evidence_of, name):
    ev = evidence_of(name)
    line = json.loads(json.dumps(run.result_line(ev, trace=False)))
    assert set(line) == RESULT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert list(line["metrics"]) == ev["cell"]["end_to_end"]
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0


@pytest.mark.parametrize("name", TINY_CELLS)
def test_per_layer_line(evidence_of, name):
    ev = evidence_of(name)
    line = json.loads(json.dumps(run.result_line(ev, trace=True)))
    # a CPU trace holds no device plane: the readers of the device trace
    # find nothing and their metrics are left out, as the harness must
    from_trace = {m for m in ev["cell"]["per_layer"]
                  if run.load_module("layer_metrics", m).SOURCE
                  == "device_trace"}
    assert ev["trace"] is None
    assert set(line) == RESULT_KEYS
    assert set(line["metrics"]) == set(ev["cell"]["per_layer"]) - from_trace
    # what the tiny cell's own file expects of its metrics on the CPU
    for metric, (low, high) in ev["cell"]["rehearsal_expects"].items():
        assert low <= line["metrics"][metric]["value"] <= high, metric


def test_main_without_tpu_prints_no_result(capsys):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "TPU" in err
