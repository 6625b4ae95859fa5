"""The seven readers the Laguna-XS.2 cell brought (PR 53: the two kinds
of attention layer, the flash kernels' roofline at the live pairs of
layers that differ in head count, and the expert layer at the
many-small-experts end, each the reduction of an accepted reader under a
second name), on hand-written reductions of a trace and hand-written
counters; the family's arithmetic they price by, against hand counts and
an explicit mask; the manifest, the configuration against the catalog's
row, and the cell against ISSUE 53's parameters. The cell's rehearsal on
the CPU is test_run_cpu.py's (data/workloads/tiny-laguna.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("gated_window_attention_time_pct.train",
         "gated_global_attention_time_pct.train",
         "gated_flash_roofline_pct.train",
         "small_expert_time_pct.train",
         "small_expert_matmul_roofline_pct.train",
         "small_expert_load_max_over_mean.train",
         "small_expert_rows_handled_over_routed.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
WINDOW, GLOBAL, FLASH, EXPERTS, GMM, LOAD, HANDLED = READERS.values()
CELL = run.load_json("workloads", "laguna-xs.2.train-gated-swa512-ep8-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-laguna", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, unit, better, source
SAID = {
    NAMES[0]: ("windowed attention", "%", "lower", "device_trace"),
    NAMES[1]: ("full attention", "%", "lower", "device_trace"),
    NAMES[2]: ("kernels", "%", "higher", "device_trace"),
    NAMES[3]: ("experts", "%", "lower", "device_trace"),
    NAMES[4]: ("kernels", "%", "higher", "device_trace"),
    NAMES[5]: ("experts", "x", "lower", "program_counter"),
    NAMES[6]: ("experts", "x", "lower", "program_counter")}


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: a layer's
# rotations under their own scope nested in the kind's, the attention op
# and the gate under the kind's own; the shared expert nested in the
# expert layer's
SCOPED = [step(0.400, {
    ("forward", "window_attention.rotary_embedding"): 0.006,
    ("backward", "window_attention.rotary_embedding"): 0.010,
    ("forward", "window_attention"): 0.024,
    ("backward", "window_attention"): 0.048,
    ("forward", "global_attention.rotary_embedding"): 0.004,
    ("forward", "global_attention"): 0.020,
    ("backward", "global_attention"): 0.048,
    ("forward", "moe_block"): 0.030,
    ("backward", "moe_block"): 0.050,
    ("forward", "moe_block.gated_mlp"): 0.004,
    ("backward", "moe_block.gated_mlp"): 0.012,
    ("forward", "gated_mlp"): 0.010,
    ("forward", "(fusion)"): 0.104,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [
        {"metric": "moe_rows_routed", "values": [8000.0, 8400.0, 8192.0,
                                                 8176.0]}] * 4)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 8192,
            "counters": {
                "moe_rows_handled": {"layer=0": {"sum": 65536.0 * 4,
                                                 "count": 4}},
                "moe_rows_routed": {"layer=0": {"sum": 8192.0 * 4,
                                                "count": 4}},
                "moe_load_max_over_mean": {
                    "layer=0": {"sum": 6.0, "count": 4},
                    "layer=1": {"sum": 10.0, "count": 4}}},
            "trace": {"busy_s": 0.8, "device_ops": [
                ["fusion", 0.300], ["flash_fwd", 0.120],
                ["flash_dkv", 0.100], ["gmm", 0.024], ["tgmm", 0.008]]}}


def test_time_shares_by_scope(evidence):
    """Of 400 ms: the windowed layers' rotations 16 and op and gate 72;
    the full layers' 4 and 68; the expert layers' own 80 and their shared
    expert's 16, the dense layer's feed-forward not among them."""
    assert WINDOW.compute(evidence) == pytest.approx(22.0)
    assert GLOBAL.compute(evidence) == pytest.approx(18.0)
    assert EXPERTS.compute(evidence) == pytest.approx(24.0)


def test_counters(evidence):
    assert HANDLED.compute(evidence) == pytest.approx(8.0)
    assert LOAD.compute(evidence) == pytest.approx(2.0)


def test_flash_kernels_against_the_roofline_at_the_live_pairs(evidence):
    """Five ops of six products of live pairs x 128 x the layer's heads
    (two causal layers of 48 heads, three of 64 under the window), bound
    by the MXU, over the 110 ms a step the kernels took."""
    causal, windowed = 8192 * 8193 // 2, 512 * 513 // 2 + 7680 * 512
    assert FAMILY.live_pairs(8192, 0) == causal == 33558528
    assert FAMILY.live_pairs(8192, 512) == windowed == 4063488
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(
        6 * 2 * 128 * (2 * causal * 48 + 3 * windowed * 64) / 5)
    assert bytes_ == pytest.approx(
        2 * 8192 * 128 * (2 * (5 * 48 + 32) + 3 * (5 * 64 + 32)) / 5)
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 5
    least = 5 * flops / 197e12
    assert least == pytest.approx(31.2e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.110)
    assert 0 < FLASH.compute(evidence) < 100
    # the forward of the live pairs: ISSUE 53's 2.0 TFLOP a step
    assert 5 * flops / 3 == pytest.approx(2.05e12, rel=5e-3)


def test_grouped_products_against_the_roofline_at_the_traced_rows(evidence):
    """Four layers of nine products of 8192 rows x 2048 x 512 over the
    16 ms a step of gmm + tgmm; at 256 rows an expert the weights' bytes
    bound it, not the MXU."""
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 8192.0)
    assert flops == pytest.approx(9 * 2 * 8192 * 2048 * 512)
    assert bytes_ == pytest.approx(
        9 * 2 * (8192 * 2048 + 8192 * 512 + 32 * 2048 * 512))
    assert bytes_ / 819e9 > flops / 197e12
    assert FAMILY.expert_layers(CONFIG) == 4
    least = 4 * bytes_ / 819e9
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.016)
    assert 0 < GMM.compute(evidence) < 100


@pytest.mark.parametrize("length,window", [
    (64, 16), (64, 0), (48, 48), (48, 100), (96, 1), (80, 37)])
def test_the_cost_is_the_live_pairs_of_an_explicit_mask(length, window):
    pos = np.arange(length)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[:, None] - pos[None, :] < window
    assert FAMILY.live_pairs(length, window) == keep.sum()
    # a model whose layers are all sliding under this window, 8 heads
    config = dict(TINY, sliding_window=window or length,
                  layer_types=["sliding_attention"] * 5)
    flops, _ = FAMILY.attention_kernel_cost(config, tokens=length)
    heads = np.mean(TINY["num_attention_heads_per_layer"])
    assert flops == pytest.approx(
        6 * 2 * keep.sum() * TINY["head_dim"] * heads)


def test_required_flops_by_hand():
    """6.57 TFLOP a step forward: the four maps 2.82 (and the gates'
    0.01), the live pairs 2.05, the dense layer 0.82, routers 0.03,
    shared experts 0.21, the expected routed rows 0.21, the head 0.42;
    times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    d, t = 2048, 8192
    assert per["projections.full_attention"] == 2 * (29360128 + d * 48)
    assert per["projections.sliding_attention"] == 2 * (37748736 + d * 64)
    assert per["attention.full_attention"] == pytest.approx(
        4 * 33558528 / t * 48 * 128)
    assert per["attention.sliding_attention"] == pytest.approx(
        4 * 4063488 / t * 64 * 128)
    assert per["dense"] == 6 * d * 8192 == 2 * 50331648
    assert per["experts"] == pytest.approx(
        2 * d * 256 + 6 * d * 512 + 8 * 32 / 256 * 6 * d * 512)
    assert per["head"] == 2 * d * 12544
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * (
        2 * (per["projections.full_attention"]
             + per["attention.full_attention"])
        + 3 * (per["projections.sliding_attention"]
               + per["attention.sliding_attention"])
        + per["dense"] + 4 * per["experts"] + per["head"]))
    assert total * t / 3 == pytest.approx(6.57e12, rel=2e-3)
    maps = 2 * per["projections.full_attention"] \
        + 3 * per["projections.sliding_attention"]
    assert maps * t == pytest.approx(2.82e12, rel=5e-3)
    # the same whether the program recomputes or not
    assert FAMILY.required_flops_per_item(dict(CONFIG, recompute=False)) \
        == total


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_program_reports_nothing(name, evidence, monkeypatch):
    """No such scope, no such kernel, no such counter, or a family that
    prices no attention: None, not an error; None without a trace."""
    reader = READERS[name]
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "mamba2_mixer"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
    monkeypatch.setattr(rooflines, "traced_rows_routed", lambda ev: None)
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    evidence["counters"] = {}
    assert reader.compute(evidence) is None
    granite = run.load_json("configs", "granite-4.0-h-micro")
    with_kernels = dict(evidence, config=granite, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    evidence["trace"] = None
    assert reader.compute(evidence) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_the_readers_for_the_new_cell(name):
    reader = READERS[name]
    layer, unit, better, source = SAID[name]
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL["name"]]
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == (layer, unit, better, source,
                                "train_items_per_s")
    assert name in CELL["per_layer"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        layer, unit, "train_items_per_s", source)


def test_the_entries_follow_the_accepted_ones_in_order():
    """Behind PR 49's, not in their midst; a later PR's entries may follow
    (no test of this file holds these to be the last). The cell reports
    every metric that lists no cells and its seven; the accepted expert
    and window metrics stay their cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("granite-4.0-h-micro")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index("granite-4.0-h-micro.train-ssm-recompute")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == list(range(at[0], at[0] + 7)) and at[0] \
        > names("per_layer").index("ssd_scan_g1_roofline_pct.train")
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 18
    assert set(CELL["per_layer"]) == set(unlisted) | set(NAMES)
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert len(MANIFEST["workloads"]) == 9 and sum(
        w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


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
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["num_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_experts"],
        published["vocab_size"])
    # the floors of a cut: the dense layer once and a whole period of
    # four layers behind it, 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["layer_types"][:5] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(CONFIG["layer_types"]) == 40              # kept whole
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["num_experts"] * 8 == published["num_experts"]
    assert CONFIG["family"] == "laguna"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("gating", "gate_input", "router",
                "qk_norm_and_shared_expert_gate", "rotated_half",
                "window_counts_own_key", "auxiliary_loss", "router_balance",
                "optimizer", "initialisation", "sequence_length",
                "recompute", "amp", "max_position_embeddings"):
        assert key in CONFIG["assumed"], key
    # each of the three readings is one key the program and the reference
    # read
    assert (CONFIG["gating_granularity"], CONFIG["router_scoring"],
            CONFIG["norm_topk_prob"], CONFIG["qk_norm"],
            CONFIG["shared_expert_gate"]) == ("per-head", "sigmoid", True,
                                              False, False)
    with pytest.raises(NotImplementedError):
        FAMILY.required_flops_per_item(dict(CONFIG, qk_norm=True))


def test_the_routers_balancing_rule_is_stated_as_assumed():
    rate = CONFIG["router_balance_rate"]
    assert 0 < rate <= 1
    said = CONFIG["assumed"]["router_balance"]
    assert "router_balance_rate" in said and "2408.15664" in said
    assert str(rate) in said
    main, _, _ = FAMILY.build(CONFIG)
    rules = [op for op in main.global_block().ops
             if op.type == "moe_balance_bias"]
    assert len(rules) == 4 and all(op.attr("rate") == rate for op in rules)


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"], CONFIG["recompute"],
            CONFIG["sliding_window"]) == (1, 8192, True, 512)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"))
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TO BE" not in CELL[key] and "PR 53" in CELL[key]
    assert "PR 53" in CELL["reference"]["measured"]
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["adam_beta1"], CONFIG["adam_beta2"],
            CONFIG["adam_epsilon"]) == (0.9, 0.999, 1e-8)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, 8192)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 12544
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 8192


def test_the_parameters_here_are_the_programs_own_count():
    """691,623,936, ISSUE 53's count, from the program's parameters: the
    dense layer, three sliding expert layers, the full expert layer,
    embedding and head and the final norm; four layers replayed, each
    with its flash call and three of them with their expert layer."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    d, norms = 2048, 2 * 2048
    expert_ffn = d * 256 + 32 * 3145728 + 3145728
    layer_0 = 29360128 + d * 48 + 50331648 + norms
    sliding = 37748736 + d * 64 + expert_ffn + norms
    full = 29360128 + d * 48 + expert_ffn + norms
    assert (layer_0, sliding, full) == (79794176, 142217216, 133795840)
    assert count == layer_0 + 3 * sliding + full + 2 * 25690112 + d \
        == 691623936
    assert "691,623,936" in CONFIG["deployment"]["parameters_here"]
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4]
    assert [(types.count("scaled_dot_product_attention"),
             types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [
        (1, 0), (1, 1), (1, 1), (1, 1)]
    windows = [(op.desc.attrs.get("window", 0),
                main.global_block().var(op.input("Q")[0]).shape[2])
               for op in main.global_block().ops
               if op.type == "scaled_dot_product_attention"
               and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    assert windows == [(0, 48), (512, 64), (512, 64), (512, 64), (0, 48)]
