"""The nine readers the LFM2 cell brought (PR 62: the gated
short-convolution mixer's share and its kernels' roofline, the
grouped-query attention layer's share and its flash kernels at 16,384
positions, the expert layer under top 4 of 64 with no shared expert, its
grouped products' roofline, its busiest expert and its rungs, and what
the replayed layers cost, seven of them the reduction of an accepted
reader under a second name), on hand-written reductions of a trace and
hand-written counters; the family's arithmetic they price by, against
hand counts; the manifest, the configuration against the catalog's row,
and the cell against ISSUE 62's parameters. No test here counts the
manifest's lists or holds these entries to be the last. The cell's
rehearsal on the CPU is test_run_cpu.py's
(data/workloads/tiny-lfm2-moe.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("shortconv_mixer_time_pct.train",
         "shortconv_kernel_roofline_pct.train",
         "shortconv_attention_time_pct.train",
         "shortconv_flash_roofline_pct.train",
         "top4_expert_time_pct.train",
         "top4_expert_matmul_roofline_pct.train",
         "top4_expert_load_max_over_mean.train",
         "top4_expert_rows_handled_over_routed.train",
         "shortconv_recompute_time_pct.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
MIXER, CONV, ATTENTION, FLASH, EXPERTS, GMM, LOAD, HANDLED, REPLAYED = \
    READERS.values()
# the module whose `compute` the last of them hands on (load_module makes
# a new one a call)
RECOMPUTE = REPLAYED.compute.__globals__
CELL = run.load_json("workloads", "lfm2-24b-a2b.train-shortconv-ep8-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-lfm2-moe", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, unit, better, source
SAID = {
    NAMES[0]: ("short-convolution mixer", "%", "lower", "device_trace"),
    NAMES[1]: ("kernels", "%", "higher", "device_trace"),
    NAMES[2]: ("full attention", "%", "lower", "device_trace"),
    NAMES[3]: ("kernels", "%", "higher", "device_trace"),
    NAMES[4]: ("experts", "%", "lower", "device_trace"),
    NAMES[5]: ("kernels", "%", "higher", "device_trace"),
    NAMES[6]: ("experts", "x", "lower", "program_counter"),
    NAMES[7]: ("experts", "x", "lower", "program_counter"),
    NAMES[8]: ("recomputation", "%", "lower", "device_trace")}
T, D = 16384, 2048


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the attention
# layer's norms and rotations under their own scopes nested in its scope
SCOPED = [step(0.250, {
    ("forward", "short_conv_mixer"): 0.020,
    ("backward", "short_conv_mixer"): 0.050,
    ("forward", "gqa_attention.scaled_dot_product_attention"): 0.015,
    ("backward", "gqa_attention.scaled_dot_product_attention"): 0.035,
    ("backward", "gqa_attention.rotary_embedding"): 0.002,
    ("backward", "gqa_attention.rms_norm"): 0.003,
    ("forward", "moe_block"): 0.012,
    ("backward", "moe_block"): 0.028,
    ("forward", "gated_mlp"): 0.015,
    ("forward", "(fusion)"): 0.040,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [
        {"metric": "moe_rows_routed", "values": [8000.0, 8400.0, 8192.0,
                                                 8176.0]}] * 4)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.250, {("backward", RECOMPUTE["REPLAYED"]): 0.045,
                     ("backward", "(fusion)"): 0.125,
                     ("forward", "(fusion)"): 0.080})] * 2)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": T,
            "counters": {
                "moe_rows_handled": {"layer=0": {"sum": 16384.0 * 4,
                                                 "count": 4}},
                "moe_rows_routed": {"layer=0": {"sum": 8192.0 * 4,
                                                "count": 4}},
                "moe_load_max_over_mean": {
                    "layer=0": {"sum": 5.0, "count": 4},
                    "layer=1": {"sum": 7.0, "count": 4}}},
            "trace": {"busy_s": 0.5, "device_ops": [
                ["fusion", 0.300], ["gated_conv1d_fwd", 0.006],
                ["gated_conv1d_bwd", 0.006], ["causal_conv1d_fwd", 0.5],
                ["flash_fwd", 0.030], ["flash_dkv", 0.060],
                ["gmm", 0.030], ["tgmm", 0.010]]}}


def test_time_shares_by_scope(evidence):
    """Of 250 ms: the gated mixers' 70; the attention layer's op 50 and
    its norms and rotations 5; the expert layers' 40, the dense layer's
    feed-forward not among them."""
    assert MIXER.compute(evidence) == pytest.approx(28.0)
    assert ATTENTION.compute(evidence) == pytest.approx(22.0)
    assert EXPERTS.compute(evidence) == pytest.approx(16.0)


def test_counters(evidence):
    assert HANDLED.compute(evidence) == pytest.approx(2.0)
    assert LOAD.compute(evidence) == pytest.approx(1.5)


def test_replayed_layers_share(evidence):
    """Of 250 ms a step, 45 under a `pd_recompute` scope."""
    assert REPLAYED.compute(evidence) == pytest.approx(18.0)


def test_the_gated_kernels_against_the_roofline(evidence):
    """Four layers of eleven bf16 [16384, 2048] arrays (three read and
    one written forward, four read and three written backward) over the
    6 ms a step the GATED kernels took (the plain kernels' half second is
    another op's); the bytes bound it, not the VPU's multiply-adds."""
    flops, bytes_ = FAMILY.conv_kernel_cost(CONFIG, T)
    assert bytes_ == 11 * 2 * T * D == 738197504
    assert flops == 3 * (2 * 3 + 2) * T * D
    assert bytes_ / 819e9 > flops / 197e12
    assert FAMILY.conv_layers(CONFIG) == 4
    # ISSUE 62's floor: 268 MB forward and 470 MB backward a layer
    assert 4 * 2 * T * D == 268435456 and 7 * 2 * T * D == 469762048
    least = 4 * bytes_ / 819e9
    assert least == pytest.approx(3.605e-3, rel=1e-3)
    assert CONV.compute(evidence) == pytest.approx(100 * least / 0.006)
    assert 0 < CONV.compute(evidence) < 100
    # a trace with the plain kernels alone is another family's
    evidence["trace"]["device_ops"] = [["causal_conv1d_fwd", 0.5]]
    assert CONV.compute(evidence) is None


def test_flash_kernels_against_the_roofline_at_the_live_pairs(evidence):
    """One op of the causal mask's 134,225,920 live pairs x 32 heads x
    six products at 64, K and V counted at their published 8 heads,
    bound by the MXU, over the 45 ms a step the kernels took."""
    live = T * (T + 1) // 2
    assert live == FAMILY.live_pairs(T) == 134225920
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(6 * 2 * live * 64 * 32)
    assert bytes_ == pytest.approx(2 * T * 64 * (5 * 32 + 4 * 8))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 1
    least = flops / 197e12
    assert least == pytest.approx(16.74e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.045)
    assert 0 < FLASH.compute(evidence) < 100
    # the forward of the live pairs a token: ISSUE 62's 67.1M
    assert flops / 3 / T == pytest.approx(67.1e6, rel=2e-3)


def test_grouped_products_against_the_roofline_at_the_traced_rows(evidence):
    """Four layers of nine products of 8192 rows x 2048 x 1536 over the
    20 ms a step of gmm + tgmm; at 1024 rows an expert the MXU bounds
    it, not the weights' bytes."""
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 8192.0)
    assert flops == pytest.approx(9 * 2 * 8192 * 2048 * 1536)
    assert bytes_ == pytest.approx(
        9 * 2 * (8192 * 2048 + 8192 * 1536 + 8 * 2048 * 1536))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.expert_layers(CONFIG) == 4
    least = 4 * flops / 197e12
    assert least == pytest.approx(9.42e-3, rel=5e-3)
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.020)
    assert 0 < GMM.compute(evidence) < 100


def test_required_flops_by_hand():
    """ISSUE 62's count, forward a token: conv mixers 4 x 33.6M,
    attention 21.0M of maps + 67.1M of scores, the dense feed-forward
    144.7M, held experts 4 x 9.4M (with the router 9.7M), the head 33.6M:
    439M; times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    assert per["conv"] == 8 * D * D + 8 * D == 33570816
    assert per["full_attention"] == pytest.approx(
        2 * D * 64 * (2 * 32 + 2 * 8) + 4 * (T + 1) / 2 * 32 * 64)
    assert 2 * D * 64 * 80 == 20971520
    assert per["dense"] == 6 * D * 11776 == 144703488
    assert per["experts"] == pytest.approx(
        2 * D * 64 + 4 * 8 / 64 * 6 * D * 1536) == 9699328
    assert per["head"] == 2 * D * 8192 == 33554432
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * (
        4 * per["conv"] + per["full_attention"] + per["dense"]
        + 4 * per["experts"] + per["head"]))
    assert total / 3 == pytest.approx(439.4e6, rel=2e-3)
    assert total == pytest.approx(1.318e9, rel=2e-3)
    # the same whether the program recomputes or not
    assert FAMILY.required_flops_per_item(
        dict(CONFIG, recompute=False)) == total


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_program_reports_nothing(name, evidence, monkeypatch):
    """No such scope, no such kernel, no such counter, or a family that
    prices neither: None, not an error; None without a trace."""
    reader = READERS[name]
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "mamba2_mixer"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [])
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.1, {("forward", "(fusion)"): 0.1})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    evidence["counters"] = {}
    assert reader.compute(evidence) is None
    granite = run.load_json("configs", "granite-4.0-h-micro")
    with_kernels = dict(evidence, config=granite, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01],
                                      ["gated_conv1d_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    assert CONV.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: None)
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
    """Behind PR 59's, not in their midst; a later PR's entries may
    follow (no test of this file counts the lists or holds these to be
    the last). The cell reports every metric that lists no cells and its
    nine; the accepted metrics that list their cells stay their cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("ouro-2.6b")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index("ouro-2.6b.train-loop4-t4096-pp6-stage")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == list(range(at[0], at[0] + 9)) and at[0] > names(
        "per_layer").index("loop_flash_roofline_pct.train")
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(CELL["per_layer"]) == set(unlisted) | set(NAMES)
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        CONFIG["name"], "train_steps", 1, CELL["why"])


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
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["num_dense_layers_published"],
            CONFIG["num_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_dense_layers"],
        published["num_experts"], published["vocab_size"]) == (
        40, 2, 64, 65536)
    # every published width stands
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["num_experts_per_tok"],
            CONFIG["conv_L_cache"],
            CONFIG["rope_parameters"]["rope_theta"]) == (
        2048, 32, 8, 11776, 1536, 4, 3, 1000000)
    # the floors of a cut: a dense layer once and a whole period of four
    # layers behind the leading ones, 8 routed experts, an eighth of the
    # vocabulary
    assert CONFIG["layers_held"] == [0, 2, 3, 4, 5]
    assert [CONFIG["layer_types"][l] for l in CONFIG["layers_held"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert CONFIG["layer_types"] == published["layer_types"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == (5, 1)
    assert CONFIG["num_experts"] == 8 and CONFIG["expert_offset"] == 0
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["num_experts"] * 8 == published["num_experts"]
    assert CONFIG["family"] == "lfm2_moe"
    assert CONFIG["tie_word_embeddings"] is True
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("tied_head", "rotary", "in_proj_thirds", "conv", "router",
                "router_balance", "optimizer", "dropout", "initialisation",
                "sequence_length", "recompute", "amp",
                "max_position_embeddings"):
        assert key in CONFIG["assumed"], key
    for key in ("tie_word_embeddings", "router_balance_rate",
                "initializer_range"):
        assert any(key in said for said in CONFIG["assumed"].values()), key


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
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["recompute"]) == (1, T, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    assert "8 times" in CELL["why"] or "8x" in CELL["why"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_tail_rtol",
                         "update_rtol"))
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TBD" not in CELL[key] and "PR 62" in CELL[key]
    assert "PR 62" in CELL["reference"]["measured"]
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["adam_beta1"], CONFIG["adam_beta2"],
            CONFIG["adam_epsilon"], CONFIG["learning_rate"]) == (
        0.9, 0.999, 1e-8, 1e-6)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, T)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 8192
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == T


def test_the_parameters_here_are_the_programs_own_count():
    """469,284,992, ISSUE 62's count, from the program's parameters: the
    conv + dense layer, the attention expert layer, three conv expert
    layers, the tied embedding (once: the head is the same matrix) and
    the embedding norm; four layers replayed, three of them with their
    gated convolution, one with its flash call, three with their expert
    layer."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    norms = 2 * D
    conv = 3 * D * D + D * 3 + D * D
    attention = 2 * D * D + 2 * D * 512 + 2 * 64
    dense = 3 * D * 11776
    experts = D * 64 + 8 * 3 * D * 1536
    assert (conv, attention, dense, experts) == (
        16783360, 10485888, 72351744, 75628544)
    layer_0 = conv + dense + norms
    attention_expert = attention + experts + norms
    conv_expert = conv + experts + norms
    assert (layer_0, attention_expert, conv_expert) == (
        89139200, 86118528, 92416000)
    assert count == layer_0 + attention_expert + 3 * conv_expert \
        + 8192 * D + D == 469284992
    assert "469,284,992" in CONFIG["deployment"]["parameters_here"]
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4]
    assert [(types.count("causal_conv1d"),
             types.count("scaled_dot_product_attention"),
             types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [
        (1, 0, 0), (0, 1, 1), (1, 0, 1), (1, 0, 1)]
    shapes = [tuple(main.global_block().var(op.input(slot)[0]).shape[2:]
                    for slot in ("Q", "K", "V"))
              for op in main.global_block().ops
              if op.type == "scaled_dot_product_attention"
              and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    assert shapes == [((32, 64), (8, 64), (8, 64))]
    # the tiny preset is the same family at the same pattern
    assert TINY["family"] == CONFIG["family"]
    assert TINY["layers_held"] == CONFIG["layers_held"]
    assert TINY["layer_types"] == CONFIG["layer_types"]
