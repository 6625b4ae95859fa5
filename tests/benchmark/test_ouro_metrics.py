"""The four readers the Ouro cell brought (PR 59: the looped layers'
attention and its flash kernels at [1, 4096, 16, 128], the exits that the
depth cut inflates, and what the 31 replayed layer applications cost,
each the reduction of an accepted reader under a name of its own), on
hand-written reductions of a trace; the family's arithmetic they price
by, against hand counts; the manifest, the configuration against the
catalog's row, and the cell against ISSUE 59's parameters. The cell's
rehearsal on the CPU is test_run_cpu.py's
(data/workloads/tiny-ouro.train.json). No test here counts the
manifest's lists: a later PR's entries may follow."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("loop_attention_time_pct.train", "loop_exit_time_pct.train",
         "loop_recompute_time_pct.train", "loop_flash_roofline_pct.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
ATTENTION, EXITS, REPLAYED, FLASH = READERS.values()
# the module whose `compute` the third of them hands on (load_module
# makes a new one a call)
RECOMPUTE = REPLAYED.compute.__globals__
CELL = run.load_json("workloads", "ouro-2.6b.train-loop4-t4096-pp6-stage")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-ouro", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, better
SAID = {NAMES[0]: ("full attention", "lower"),
        NAMES[1]: ("loop exits", "lower"),
        NAMES[2]: ("recomputation", "lower"),
        NAMES[3]: ("kernels", "higher")}


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the rotations
# under their own scope nested in the attention's
SCOPED = [step(0.800, {
    ("forward", "loop_attention"): 0.030,
    ("forward", "loop_attention.rotary_embedding"): 0.006,
    ("backward", "loop_attention"): 0.070,
    ("backward", "loop_attention.rotary_embedding"): 0.014,
    ("forward", "loop_exit"): 0.040,
    ("backward", "loop_exit"): 0.120,
    ("forward", "gated_mlp"): 0.100,
    ("backward", "gated_mlp"): 0.300,
    ("forward", "(fusion)"): 0.090,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.800, {("backward", RECOMPUTE["REPLAYED"]): 0.168,
                     ("backward", "(fusion)"): 0.432,
                     ("forward", "(fusion)"): 0.200})] * 2)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 4096, "counters": {},
            "trace": {"busy_s": 1.6, "device_ops": [
                ["fusion", 1.200], ["flash_fwd", 0.064],
                ["flash_dkv", 0.136]]}}


def test_time_shares_by_scope(evidence):
    """Of 800 ms: the attention ops' 100 and their rotations' 20; the
    exits' 160; the feed-forwards under neither."""
    assert ATTENTION.compute(evidence) == pytest.approx(15.0)
    assert EXITS.compute(evidence) == pytest.approx(20.0)


def test_replayed_applications_share(evidence):
    """Of 800 ms a step, 168 under a `pd_recompute` scope."""
    assert REPLAYED.compute(evidence) == pytest.approx(21.0)


def test_flash_kernels_against_the_roofline_at_the_cells_shape(evidence):
    """32 ops (4 passes x 8 layers; a replayed op runs no kernel and is
    not counted) of the causal mask's live pairs x 16 heads x six
    products at 128, bound by the MXU, over the 100 ms a step the kernels
    took."""
    live = 4096 * 4097 // 2
    assert FAMILY.live_pairs(4096) == live == 8390656
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == 6 * 2 * live * 128 * 16 == 206208761856
    assert bytes_ == 2 * 4096 * 128 * (5 * 16 + 4 * 16) == 150994944
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 32
    least = 32 * flops / 197e12
    assert least == pytest.approx(33.5e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.100)
    assert 0 < FLASH.compute(evidence) < 100
    # another length on request; the passes and the layers both count
    assert FAMILY.attention_kernel_cost(CONFIG, 2048)[0] \
        == 6 * 2 * (2048 * 2049 // 2) * 128 * 16
    assert FAMILY.attention_ops_per_step(
        dict(CONFIG, total_ut_steps=2, num_hidden_layers=3)) == 6


def test_required_flops_by_hand():
    """4.63 GFLOPs a token forward: 32 layer applications of 119.5M (the
    four maps 33.6M, the live pairs 16.8M, the feed-forward 69.2M) and
    four heads of 201.3M, 17 % of it where the whole model's are 3 %;
    times 3: ISSUE 59's 13.9 GFLOPs a token."""
    per = FAMILY.part_flops_per_item(CONFIG)
    d, f, v, t = 2048, 5632, 49152, 4096
    assert per["projections"] == 2 * 4 * d * d == 33554432
    assert per["attention"] == pytest.approx(4 * (t + 1) / 2 * 16 * 128)
    assert per["attention"] == 16781312
    assert per["feed_forward"] == 6 * d * f == 69206016
    assert per["head"] == 2 * d * v == 201326592
    assert per["gate"] == 2 * d
    layer = per["projections"] + per["attention"] + per["feed_forward"]
    assert layer == 119541760
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == 3 * (32 * layer + 4 * per["head"] + 3 * per["gate"])
    assert total == pytest.approx(13.9e9, rel=2e-3)
    assert total * t == pytest.approx(56.9e12, rel=2e-3)
    assert 4 * per["head"] / (total / 3) == pytest.approx(0.174, abs=1e-3)
    whole = FAMILY.required_flops_per_item(dict(CONFIG, num_hidden_layers=48))
    assert 3 * 4 * per["head"] / whole == pytest.approx(0.034, abs=1e-3)
    # the same whether the program recomputes or not; one pass is a
    # quarter of the layers' and of the heads' and no gate's
    assert FAMILY.required_flops_per_item(
        dict(CONFIG, recompute=False)) == total
    assert FAMILY.required_flops_per_item(
        dict(CONFIG, total_ut_steps=1)) == 3 * (8 * layer + per["head"])


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_program_reports_nothing(name, evidence, monkeypatch):
    """No such scope, no such kernel, or a family that prices no
    attention: None, not an error; None without a trace."""
    reader = READERS[name]
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "mamba2_mixer"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.1, {("forward", "(fusion)"): 0.1})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    assert reader.compute(evidence) is None
    resnet = run.load_json("configs", "resnet50")
    with_kernels = dict(evidence, config=resnet, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: None)
    evidence["trace"] = None
    assert reader.compute(evidence) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_the_readers_for_the_new_cell(name):
    reader = READERS[name]
    layer, better = SAID[name]
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL["name"]]
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == (layer, "%", better, "device_trace",
                                "train_items_per_s")
    assert name in CELL["per_layer"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        layer, "%", "train_items_per_s", "device_trace")
    # a layer the manifest already names keeps its name, letter for letter
    assert layer == "loop exits" or any(
        m["layer"] == layer for m in MANIFEST["per_layer"]
        if m["name"] not in NAMES)


def test_the_entries_follow_the_accepted_ones_in_order():
    """Behind PR 55's, not in their midst; a later PR's entries may
    follow (nothing here counts the lists or holds these to be the
    last). The cell reports every metric that lists no cells and its
    four; every other listed metric stays its cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("kimi-linear-48b-a3b-instruct")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index("kimi-linear.train-kda-t8192-ep32-share")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == list(range(at[0], at[0] + 4)) and at[0] > names(
        "per_layer").index("kda_recompute_time_pct.train")
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(CELL["per_layer"]) == set(unlisted) | set(NAMES)
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/ouro-2.6b.json"
    assert entry["source"] == CONFIG["source"]
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        CONFIG["name"], "train_steps", 1, CELL["why"])


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published, = [r["config"] for r in rows
                  if r["source_url"] == CONFIG["source"]]
    assert set(published) <= set(CONFIG)
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["num_hidden_layers_published"] \
        == published["num_hidden_layers"] == 48
    # one of six stages of eight; every width as published
    assert CONFIG["num_hidden_layers"] == 8 and 48 % 8 == 0
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["head_dim"], CONFIG["vocab_size"],
            CONFIG["total_ut_steps"]) == (2048, 5632, 16, 16, 128, 49152, 4)
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert (CONFIG["rope_theta"], CONFIG["rms_norm_eps"],
            CONFIG["early_exit_threshold"]) == (1000000, 1e-6, 1)
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 1
    assert CONFIG["family"] == "ouro"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("sandwich_norms", "final_norm_in_the_loop", "gate", "loss",
                "early_exit_threshold", "biases", "initialisation",
                "sequence_length", "recompute", "amp", "optimizer"):
        assert key in CONFIG["assumed"], key
    # each reading is one key the program and the reference read
    assert CONFIG["exit_entropy_weight"] == 0.1
    assert "exit_entropy_weight 0.1" in CONFIG["assumed"]["loss"]
    assert "sqrt(192)" in CONFIG["assumed"]["initialisation"]
    assert "unread" in CONFIG["assumed"]["early_exit_threshold"]


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["recompute"]) == (1, 4096, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    for said in ("32 applications", "4 exits", "stage 1 of 6", "17 %"):
        assert said in CELL["why"], said
    held = {k: v for k, v in CELL["reference"].items() if k != "measured"}
    assert set(held) == {"loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"}
    assert sum(v is not None for v in held.values()) >= 3
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TO BE" not in CELL[key] and "PR 59" in CELL[key]
    assert "TO BE" not in CELL["reference"]["measured"]
    assert "PR 59" in CELL["reference"]["measured"]
    assert "O3" in CELL["reference"]["measured"]
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["adam_beta1"], CONFIG["adam_beta2"],
            CONFIG["adam_epsilon"], CONFIG["learning_rate"]) == (
        0.9, 0.999, 1e-8, 1e-6)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, 4096)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and 40000 < feed["tok"].max() < 49152
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 4096


def test_the_parameters_here_are_the_programs_own_count():
    """612,438,017, ISSUE 59's count, from the program's parameters: 8
    layers of 51,388,416, embedding and head, the final norm, the gate
    and its bias: ONE set for the four passes; 32 checkpoints, 31
    replayed applications each with its attention op handed on and one
    feed-forward, exits 1 to 3 replayed with the next pass's first
    application, and only the fourth exit behind the last checkpoint."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    block = main.global_block()
    shapes = {p.name: tuple(p.shape) for p in block.all_parameters()
              if p.trainable}
    count = sum(int(np.prod(s)) for s in shapes.values())
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51388416
    assert count == 8 * layer + 2 * 100663296 + 2048 + 2049 == 612438017
    assert len(shapes) == 3 + 8 * 11 + 2
    assert "612,438,017" in CONFIG["deployment"]["parameters_here"]
    assert 48 * layer + 2 * 100663296 + 4097 == 2667974657
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == list(range(1, 32))
    for segment, types in replayed.items():
        assert types.count("scaled_dot_product_attention") == 1
        assert types.count("silu") == 1
        with_exit = segment in (9, 17, 25)
        assert types.count("softmax_with_cross_entropy") == with_exit
        assert types.count("mul") == 7 + with_exit
    forward = [op for op in block.ops
               if backward.RECOMPUTE_ATTR not in op.desc.attrs]
    assert sum(op.type == "scaled_dot_product_attention"
               for op in forward) == 32
    assert sum(op.type == "softmax_with_cross_entropy"
               for op in forward) == 4
    # a layer's weight: four readers and, behind checkpoints, four
    # replays (the first layer's: every application of it is replayed)
    readers = [op for op in block.ops if op.type == "mul"
               and "looped_lm.layer_0.q" in op.input("Y")]
    assert len(readers) == 8
    # the fan-in: three `sum`s a tensor read four times (88 layer
    # tensors, the final norm and the head), two for the gate's pair
    # (three readers)
    sums = [op for op in block.ops if op.type == "sum"
            and op.output("Out")[0] in {n + "@GRAD" for n in shapes}]
    assert len(sums) == 3 * (88 + 2) + 2 * 2


def test_the_tiny_preset_is_the_same_family():
    assert TINY["family"] == CONFIG["family"]
    assert (TINY["num_hidden_layers"], TINY["hidden_size"],
            TINY["num_attention_heads"], TINY["head_dim"],
            TINY["total_ut_steps"], TINY["vocab_size"]) == (
        2, 64, 4, 16, 3, 128)
    assert set(TINY) - {"source"} <= set(CONFIG)
