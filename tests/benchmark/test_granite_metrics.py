"""The three readers that the granite-4.0-h-micro cell brought (PR 49), on
hand-written operations of a trace and hand-written reductions; the
family's arithmetic they price by; and the manifest, the configuration and
the cell as ISSUE 49 states them. The cell's rehearsal on the CPU is
test_run_cpu.py's (data/workloads/tiny-granite-hybrid.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import program_trace, rooflines, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

RECOMPUTE = run.load_module("layer_metrics", "recompute_time_pct.train")
MIXER = run.load_module("layer_metrics", "ssm_mixer_time_pct.train")
SCAN = run.load_module("layer_metrics", "ssd_scan_g1_roofline_pct.train")
READERS = (RECOMPUTE, MIXER, SCAN)
CELL = run.load_json("workloads", "granite-4.0-h-micro.train-ssm-recompute")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

REPLAY = ("jit(fn)/pd_at.310/pd_role.backward/pd_recompute.8/"
          "pd_scope.mamba2_mixer/pd.ssd_scan/ssd_scan_fwd/pallas_call")


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# by program op, and the same two steps by name scope
STEPS = [step(0.500, {("forward", "mul"): 0.100,
                      ("forward", "ssd_scan"): 0.020,
                      ("backward", "ssd_scan"): 0.018,     # the replay
                      ("backward", "ssd_scan_grad"): 0.042,
                      ("backward", "mul_grad"): 0.200,
                      ("optimize", "fused_adam"): 0.030})] * 2
SCOPED = [step(0.500, {("forward", "mamba2_mixer"): 0.060,
                       ("backward", "mamba2_mixer"): 0.190,
                       ("forward", "gated_mlp"): 0.050,
                       ("backward", "gated_mlp"): 0.150,
                       ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    monkeypatch.setattr(program_trace, "of_evidence",
                        lambda ev: {"device_steps": STEPS})
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.500, {("backward", RECOMPUTE.REPLAYED): 0.110,
                     ("backward", "(fusion)"): 0.250,
                     ("forward", "(fusion)"): 0.140})] * 2)
    return {"cell": {"name": "x", "trace_steps": 4, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 8192, "counters": {},
            "trace": {"busy_s": 1.0, "device_ops": [["fusion", 0.8]]}}


@pytest.mark.parametrize("op_name,segment", [
    (REPLAY, True),
    ("jit(fn)/pd_at.3/pd_role.forward/pd_scope.mamba2_mixer/pd.ssd_scan/"
     "ssd_scan_fwd/pallas_call", False),
    ("jit(fn)/pd_at.700/pd_role.backward/pd_scope.mamba2_mixer/"
     "pd.ssd_scan_grad/transpose(jvp())/ssd_scan_bwd/pallas_call", False),
    (None, False)])
def test_a_replayed_op_is_told_from_the_first_by_its_scope(op_name, segment):
    assert RECOMPUTE.replayed(op_name) == (RECOMPUTE.REPLAYED if segment
                                           else None)
    if op_name:   # and the new scope hides neither role, layer nor op
        assert program_trace.provenance_of(op_name)[0] in ("forward",
                                                           "backward")
        assert rooflines.scope_of(op_name) == "mamba2_mixer"


def test_a_replayed_operation_is_counted_once():
    """One step of 10 ms: a replayed while loop of 4 ms that encloses two
    body operations of 1 ms each (the profile shows all three), a first
    forward op of 3 ms, a gradient op of 3 ms. Replayed: 4 of 10 ms, not
    6."""
    role = "backward"
    ops = [("fusion", "forward", None, 0.000, 0.003),
           ("while", role, RECOMPUTE.REPLAYED, 0.003, 0.007),
           ("fusion", role, RECOMPUTE.REPLAYED, 0.004, 0.005),
           ("fusion", role, RECOMPUTE.REPLAYED, 0.005, 0.006),
           ("fusion", role, None, 0.007, 0.010)]
    steps = RECOMPUTE.steps_of({"/device:TPU:0": {
        "modules": [("jit_fn", 0.0, 0.010)], "ops": ops}})
    assert len(steps) == 1 and steps[0]["busy_s"] == pytest.approx(0.010)
    assert steps[0]["by_op"][(role, RECOMPUTE.REPLAYED)] \
        == pytest.approx(0.004)
    assert sum(steps[0]["by_op"].values()) == pytest.approx(0.010)


def test_time_shares(evidence):
    assert RECOMPUTE.compute(evidence) == pytest.approx(22.0)
    assert MIXER.compute(evidence) == pytest.approx(50.0)


def test_scan_against_the_roofline_counts_the_replay_as_time(evidence):
    """One forward and one gradient a Mamba layer are the work; the time is
    everything under the op, the replayed forward with it."""
    flops, bytes_ = FAMILY.scan_cost(CONFIG, 8192)
    least = 9 * max(flops / 197e12, bytes_ / 819e9)
    assert SCAN.compute(evidence) == pytest.approx(100 * least / 0.080)
    assert 0 < SCAN.compute(evidence) < 100


def test_scan_cost_is_the_four_products_at_the_lowered_chunk():
    chunk = CONFIG.get("scan_chunk", CONFIG["mamba_chunk_size"])
    di, n, heads = 4096, 128, 64
    per_token = chunk * n + chunk * di + 4 * di * n
    flops, bytes_ = FAMILY.scan_cost(CONFIG, 8192)
    assert flops == pytest.approx(3 * 8192 * per_token)
    row = 2 * (di + 2 * n) + 4 * heads
    assert bytes_ == pytest.approx(8192 * ((row + 2 * di)
                                           + (2 * row + 2 * di)))
    assert FAMILY.scan_layers(CONFIG) == 9


def test_required_flops_count_every_product_three_times_and_no_replay():
    per = FAMILY.layer_flops_per_item(CONFIG)
    d, f, v, t = 2048, 8192, 12544, 8192
    assert per["mlp"] == 6 * d * f
    assert per["head"] == 2 * d * v
    assert per["attention"] == 2 * d * (2 * 2048 + 2 * 512) + 2 * t * 2048
    assert per["mamba"] == 2 * d * 8512 + 2 * 4096 * d \
        + FAMILY.scan_cost(CONFIG, 1)[0] / 3
    assert FAMILY.required_flops_per_item(CONFIG) == pytest.approx(3 * (
        9 * per["mamba"] + per["attention"] + 10 * per["mlp"] + per["head"]))
    # the same whether the program recomputes or not
    assert FAMILY.required_flops_per_item(dict(CONFIG, recompute=False)) \
        == FAMILY.required_flops_per_item(CONFIG)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__[-34:])
def test_a_parent_program_reports_nothing(reader, evidence, monkeypatch):
    """No replayed op, no such scope, no op lowered from the scan: None,
    not an error; None without a trace."""
    quiet = [step(0.1, {("forward", "(fusion)"): 0.06,
                        ("backward", "gated_mlp"): 0.04})]
    monkeypatch.setattr(RECOMPUTE, "replayed_steps", lambda ev: quiet)
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: quiet)
    monkeypatch.setattr(program_trace, "of_evidence", lambda ev: {
        "device_steps": [step(0.1, {("forward", "mul"): 0.1})]})
    assert reader.compute(evidence) is None
    monkeypatch.setattr(RECOMPUTE, "replayed_steps", lambda ev: None)
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    monkeypatch.setattr(program_trace, "of_evidence", lambda ev: None)
    evidence["trace"] = None
    assert reader.compute(evidence) is None


def test_an_untraced_run_reads_no_file(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    assert RECOMPUTE.replayed_steps({"cell": {"name": "x"},
                                     "trace": None}) is None
    assert RECOMPUTE.compute({"cell": {"name": "x"}, "trace": None}) is None


@pytest.mark.parametrize("reader,layer,better", [
    (RECOMPUTE, "recomputation", "lower"),
    (MIXER, "state-space mixer", "lower"),
    (SCAN, "kernels", "higher")], ids=["recompute", "mixer", "scan"])
def test_the_manifest_lists_the_readers_for_the_new_cell(reader, layer,
                                                         better):
    name = os.path.basename(reader.__file__)[:-3]
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL["name"]]
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"]) == (layer, "device_trace", "train_items_per_s",
                                 "%", better)
    assert name in CELL["per_layer"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        layer, "%", "train_items_per_s", "device_trace")


def test_the_entries_follow_the_accepted_ones_in_order():
    """Behind PR 46's, not in their midst; a later PR's entries may follow
    (no test of this file holds these to be the last)."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("smallthinker-21b-a3b-instruct")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index(
            "smallthinker-21b-a3b.train-swa-t8192-ep8-share")
    mine = ["recompute_time_pct.train", "ssm_mixer_time_pct.train",
            "ssd_scan_g1_roofline_pct.train"]
    at = [names("per_layer").index(m) for m in mine]
    assert at == sorted(at) and at[0] \
        > names("per_layer").index("window_flash_roofline_pct.train")
    # every metric that lists no cells, and the three of this cell; the
    # hybrid cell's two scan metrics stay the hybrid cell's
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(CELL["per_layer"]) == set(unlisted) | set(mine)
    for m in MANIFEST["per_layer"]:
        if m["name"] in ("ssm_time_pct.train", "ssd_scan_roofline_pct.train"):
            assert m["workloads"] == ["nemotron3-nano.train-ep16-share"]


def test_the_configuration_is_the_published_one_cut_as_stated():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published, = [r["config"] for r in rows
                  if r["source_url"] == CONFIG["source"]]
    assert set(published) <= set(CONFIG)
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    # layer_types is the published list's first period: cut with the depth
    assert differs == set(CONFIG["reduced"]) | {"layer_types"} == {
        "num_hidden_layers", "vocab_size", "layer_types"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["layer_types_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["layer_types"],
        published["vocab_size"])
    assert CONFIG["layer_types"] == published["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["family"] == "granite_hybrid"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("initialisation", "time_step_limits", "feed_forward",
                "attention", "multipliers", "mamba", "scan_chunk",
                "tie_word_embeddings", "optimizer", "sequence_length",
                "recompute"):
        assert key in CONFIG["assumed"], key


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["recompute"]) == (1, 8192, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 3, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"))
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["learning_rate"], CONFIG["adam_beta1"],
            CONFIG["adam_beta2"], CONFIG["adam_epsilon"]) == (
        1e-4, 0.9, 0.999, 1e-8)
    assert (CONFIG["embedding_multiplier"], CONFIG["residual_multiplier"],
            CONFIG["attention_multiplier"], CONFIG["logits_scaling"]) == (
        12, 0.22, 0.015625, 8)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, 8192)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 12544
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 8192


def test_the_parameters_here_are_the_programs_own_count():
    """772,160,448, ISSUE 49's count: nine Mamba layers, one attention
    layer, the tied matrix once, the final norm; and nine layers and the
    embedding's lookup replayed, one scan a Mamba segment."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    mlp, norms = 2048 * 16384 + 8192 * 2048, 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (mixer, mlp, attention) == (25847232, 50331648, 10485760)
    assert count == 9 * (mixer + mlp + norms) + (attention + mlp + norms) \
        + 12544 * 2048 + 2048 == 772160448
    assert "772,160,448" in CONFIG["deployment"]["parameters_here"]
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == list(range(10))
    assert replayed[0] == ["lookup_table"]
    scans = [types.count("ssd_scan") for _, types in sorted(replayed.items())]
    assert scans == [0, 1, 1, 1, 1, 1, 0, 1, 1, 1]
    assert replayed[6].count("scaled_dot_product_attention") == 1
    chunks = {op.attr("chunk_size") for op in main.global_block().ops
              if op.type == "ssd_scan"}
    assert chunks == {CONFIG.get("scan_chunk", CONFIG["mamba_chunk_size"])}
