"""The two readers of the `collectives` layer on hand-made `device_ops`
(trace_reduce's rows: [instruction name, seconds a chip]), and the
published gpt2-large program under the planner's fsdp=2 x tp=2 layout,
built and planned but never run."""

import pytest

from benchmarks import run

PCT = run.load_module("layer_metrics", "collective_time_pct.train")
MS = run.load_module("layer_metrics", "exposed_collective_ms.train")


def _evidence(device_ops, busy_s, trace_steps=4):
    return {"cell": {"trace_steps": trace_steps},
            "trace": {"busy_s": busy_s, "device_ops": device_ops}}


def test_start_done_pairs_and_fusions_count_once_each():
    ev = _evidence([["fusion", 0.600], ["all-gather-done", 0.120],
                    ["all-reduce", 0.080], ["all-gather-start", 0.010],
                    ["all-reduce-scatter", 0.030],
                    ["collective-permute-done", 0.006],
                    ["collective-permute-start", 0.004],
                    ["copy", 0.150]], busy_s=1.0)
    assert PCT.compute(ev) == pytest.approx(25.0)
    # 0.25 s of collectives over 4 traced steps
    assert MS.compute(ev) == pytest.approx(62.5)
    assert [name for name, _ in PCT.collective_ops(ev)] == [
        "all-gather-done", "all-reduce", "all-gather-start",
        "all-reduce-scatter", "collective-permute-done",
        "collective-permute-start"]


@pytest.mark.parametrize("name", [
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
    "all-gather-start", "all-gather-done", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-reduce-scatter", "all-gather_fusion"])
def test_names_that_are_collectives(name):
    ev = _evidence([[name, 0.5], ["fusion", 0.5]], busy_s=1.0,
                   trace_steps=2)
    assert PCT.compute(ev) == pytest.approx(50.0)
    assert MS.compute(ev) == pytest.approx(250.0)


@pytest.mark.parametrize("name", ["fusion", "copy-done", "slice-start",
                                  "reduce", "gather", "scatter", "bn_act",
                                  "dynamic-update-slice"])
def test_names_that_are_not(name):
    ev = _evidence([[name, 1.0]], busy_s=1.0)
    assert PCT.compute(ev) is None and MS.compute(ev) is None


def test_one_chip_trace_and_no_trace_report_nothing():
    one_chip = _evidence([["fusion", 1.05], ["copy", 0.05],
                          ["convert_reduce_fusion", 0.1]], busy_s=1.2)
    for ev in (one_chip, {"cell": {"trace_steps": 4}, "trace": None}):
        assert PCT.compute(ev) is None
        assert MS.compute(ev) is None


# ---- the published configuration under the planner ---------------------

@pytest.fixture(scope="module")
def large_plan():
    """(plan, program, fallbacks booked by this plan) of gpt2-large at
    its published sizes on four of the harness's virtual devices."""
    import jax
    from paddle_tpu import telemetry
    from paddle_tpu.parallel import planner
    from paddle_tpu.parallel.mesh import make_mesh

    config = run.load_json("configs", "gpt2-large")
    family = run.load_module("families", config["family"])
    main, _startup, _loss = family.build(config)
    mesh = make_mesh((2, 2), ("fsdp", "tp"), devices=jax.devices()[:4])
    before = telemetry.read_series("planner_fallback_total")
    plan = planner.plan(main, mesh)
    label = telemetry.program_label(main)
    booked = {
        k.split("reason=")[1]: v - before.get(k, 0)
        for k, v in telemetry.read_series("planner_fallback_total").items()
        if "program=%s," % label in k}
    return plan, main, booked, config


@pytest.mark.parametrize("role,count,shape", [
    ("attn_qkv", 108, (1280, 1280)), ("attn_out", 36, (1280, 1280)),
    ("ffn_up", 36, (1280, 5120)), ("ffn_down", 36, (5120, 1280))])
def test_block_matrices_split_four_ways(large_plan, role, count, shape):
    plan = large_plan[0]
    found = [p for p in plan.params.values() if p.role == role]
    assert len(found) == count
    for p in found:
        assert p.shape == shape and p.factor == 4 and not p.notes
        assert p.per_shard_bytes * 4 == p.bytes


def test_vocabulary_tensors_degrade_and_are_counted(large_plan):
    plan, _main, booked, config = large_plan
    vocab = config["vocab_size"]
    assert vocab % 2 == 1                       # 29 x 1733
    emb, = (p for p in plan.params.values() if p.role == "embedding")
    head, = (p for p in plan.params.values() if p.role == "lm_head")
    # rows over fsdp x tp: neither divides 50257, the table stays whole
    assert emb.shape == (vocab, 1280) and emb.factor == 1
    assert emb.spec == (None, None) and len(emb.notes) == 2
    # the head keeps fsdp on its 1280 rows and loses tp on its columns
    assert head.shape == (1280, vocab) and head.factor == 2
    assert head.spec == ("fsdp", None) and len(head.notes) == 1
    assert "dropped axis 'tp'" in head.notes[0]
    assert booked == {"indivisible": 3, "replicated": 1}
    assert {p.name for p in plan.params.values() if p.notes} == {
        emb.name, head.name}


def test_state_bytes_a_chip_match_the_arithmetic(large_plan):
    """838M parameters: per block 12 d^2 of matrices over four chips and
    13 d of vectors on every chip; the position table over fsdp (a
    `dense` role), the head over fsdp, the token embedding whole."""
    from paddle_tpu import telemetry

    plan, main, _booked, config = large_plan
    d, layers = config["n_embd"], config["n_layer"]
    vocab, t = config["vocab_size"], config["n_positions"]
    total = layers * (12 * d * d + 13 * d) + 2 * vocab * d + vocab \
        + t * d + 2 * d
    assert plan.total_bytes == 4 * total
    assert 837e6 < total < 839e6
    a_chip = layers * (12 * d * d // 4 + 13 * d) + vocab * d \
        + vocab * d // 2 + vocab + t * d // 2 + 2 * d
    assert plan.per_shard_bytes == pytest.approx(4 * a_chip, rel=0.01)
    # 16 bytes a parameter (f32 master, gradient, two moments): 4.4 GB
    assert 4.3e9 < 4 * plan.per_shard_bytes < 4.5e9
    # the gauges hold the same rows
    label = telemetry.program_label(main)
    held = {k: v for k, v in
            telemetry.read_series("planner_shard_bytes").items()
            if "program=%s," % label in k}
    assert sum(held.values()) == plan.per_shard_bytes
    assert held["program=%s,role=embedding,factor=1" % label] == \
        4 * vocab * d
    counts = telemetry.read_series("planner_params")
    assert counts["program=%s,role=attn_qkv,factor=4" % label] == 108


# ---- the manifest's form, as the driver holds it before any run --------
# (PR 26 was refused once for a 204-character `why` on its configuration:
# test_manifest.py holds a cell's `why` to 200 and not a configuration's)

def _manifest():
    import json
    import os
    path = os.path.join(run.ROOT, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f), os.path.getsize(path)


def _lines_of_text():
    manifest, _size = _manifest()
    out = [("command[%d]" % i, w) for i, w in enumerate(manifest["command"])]
    for c in manifest["configs"]:
        out += [("configs.%s.%s" % (c["name"], k), c[k])
                for k in ("source", "why")]
    out += [("workloads.%s.why" % w["name"], w["why"])
            for w in manifest["workloads"]]
    out += [("per_layer.%s.layer" % m["name"], m["layer"])
            for m in manifest["per_layer"]]
    return out


@pytest.mark.parametrize("where,text", _lines_of_text(),
                         ids=[w for w, _ in _lines_of_text()])
def test_manifest_text_is_one_printable_line_of_200(where, text):
    assert 1 <= len(text) <= 200, (where, len(text))
    assert text.isprintable() and "\t" not in text, where


def test_manifest_keys_names_and_shares():
    import re
    manifest, size = _manifest()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert size <= 64 * 1024
    cells = manifest["workloads"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(name.match(k) for k in c["reduced"])
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}
    for kind in (manifest["configs"], cells,
                 manifest["end_to_end"] + manifest["per_layer"]):
        assert len({e["name"] for e in kind}) == len(kind)
    # at most a quarter of the cells, and always one, may ask for four chips
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
