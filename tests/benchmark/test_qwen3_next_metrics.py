"""The nine readers the Qwen3-Next cell brought (PR 64: the Gated
DeltaNet mixer's share and its delta rule's roofline at a decay a head
and 16 key heads under 32 value heads, the gated attention layer's share
and its flash kernels at 16 over 2 heads of 256 and 16,384 positions, the
expert layer under top 10 of 512 with a gated shared expert, its grouped
products' roofline, its busiest expert and its rungs, and what the
replayed layers cost, seven of them the reduction of an accepted reader
under a second name), on hand-written reductions of a trace and
hand-written counters; the family's arithmetic they price by, against
hand counts; the manifest, the configuration against the catalog's row,
and the cell against ISSUE 64's parameters. No test here counts the
manifest's lists or holds these entries to be the last. The cell's
rehearsal on the CPU is test_run_cpu.py's
(data/workloads/tiny-qwen3-next.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("gdn_mixer_time_pct.train", "gdn_scan_roofline_pct.train",
         "gdn_attention_time_pct.train", "gdn_flash_roofline_pct.train",
         "top10_expert_time_pct.train",
         "top10_expert_matmul_roofline_pct.train",
         "top10_expert_load_max_over_mean.train",
         "top10_expert_rows_handled_over_routed.train",
         "gdn_recompute_time_pct.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
MIXER, SCAN, ATTENTION, FLASH, EXPERTS, GMM, LOAD, HANDLED, REPLAYED = \
    READERS.values()
# the modules whose `compute` two of them hand on (load_module makes a
# new one a call)
RECOMPUTE = REPLAYED.compute.__globals__
CELL = run.load_json("workloads",
                     "qwen3-next-80b-a3b.train-gdn-t16k-ep16-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-qwen3-next", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, unit, better, source
SAID = {
    NAMES[0]: ("delta-rule mixer", "%", "lower", "device_trace"),
    NAMES[1]: ("kernels", "%", "higher", "device_trace"),
    NAMES[2]: ("full attention", "%", "lower", "device_trace"),
    NAMES[3]: ("kernels", "%", "higher", "device_trace"),
    NAMES[4]: ("experts", "%", "lower", "device_trace"),
    NAMES[5]: ("kernels", "%", "higher", "device_trace"),
    NAMES[6]: ("experts", "x", "lower", "program_counter"),
    NAMES[7]: ("experts", "x", "lower", "program_counter"),
    NAMES[8]: ("recomputation", "%", "lower", "device_trace")}
T, D = CONFIG["sequence_length"], 2048


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the attention
# layer's norms and rotations under their own scopes nested in its scope
SCOPED = [step(0.500, {
    ("forward", "gdn_mixer"): 0.060,
    ("backward", "gdn_mixer"): 0.090,
    ("forward", "gated_attention.scaled_dot_product_attention"): 0.030,
    ("backward", "gated_attention.scaled_dot_product_attention"): 0.060,
    ("backward", "gated_attention.rotary_embedding"): 0.004,
    ("backward", "gated_attention.rms_norm"): 0.006,
    ("forward", "moe_block"): 0.040,
    ("backward", "moe_block"): 0.085,
    ("forward", "kda_mixer"): 0.015,
    ("forward", "(fusion)"): 0.060,
    ("optimize", "(fusion)"): 0.050})] * 2
# seconds under the program op kda_scan and its gradient, a traced step
UNDER_THE_OP = [0.070, 0.072, 0.068]


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(
        rooflines, "op_seconds",
        lambda ev, ops: UNDER_THE_OP if ops == ("kda_scan",) else None)
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [
        {"metric": "moe_rows_routed", "values": [10000.0, 10480.0, 10240.0,
                                                 10240.0]}] * 4)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.500, {("backward", RECOMPUTE["REPLAYED"]): 0.080,
                     ("backward", "(fusion)"): 0.260,
                     ("forward", "(fusion)"): 0.160})] * 2)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": T,
            "counters": {
                "moe_rows_handled": {"layer=0": {"sum": 40960.0 * 4,
                                                 "count": 4}},
                "moe_rows_routed": {"layer=0": {"sum": 10240.0 * 4,
                                                "count": 4}},
                "moe_load_max_over_mean": {
                    "layer=0": {"sum": 5.0, "count": 4},
                    "layer=1": {"sum": 7.0, "count": 4}}},
            "trace": {"busy_s": 1.0, "device_ops": [
                ["fusion", 0.600], ["gdn_scan_fwd", 0.080],
                ["gdn_scan_bwd", 0.060], ["flash_fwd", 0.050],
                ["flash_dkv", 0.110], ["gmm", 0.040], ["tgmm", 0.020]]}}


def test_time_shares_by_scope(evidence):
    """Of 500 ms: the Gated DeltaNet mixers' 150 (another family's
    delta-rule mixer is not among them); the attention layer's op 90 and
    its norms and rotations 10; the expert layers' 125."""
    assert MIXER.compute(evidence) == pytest.approx(30.0)
    assert ATTENTION.compute(evidence) == pytest.approx(20.0)
    assert EXPERTS.compute(evidence) == pytest.approx(25.0)


def test_counters(evidence):
    assert HANDLED.compute(evidence) == pytest.approx(4.0)
    assert LOAD.compute(evidence) == pytest.approx(1.5)


def test_replayed_layers_share(evidence):
    """Of 500 ms a step, 80 under a `pd_recompute` scope."""
    assert REPLAYED.compute(evidence) == pytest.approx(16.0)


def test_the_delta_rule_against_the_roofline(evidence):
    """Three layers of the recurrence's three [128, 128] products a
    token a VALUE head, forward and twice that backward, with q and k
    counted at their own 16 heads and the gate at one number a head, over
    the median 70 ms a step under the op and its gradient: the bytes
    bound it (0.99 ms a layer against 0.78 of products), and a broadcast
    gate or repeated keys would be time, not work."""
    flops, bytes_ = FAMILY.kda_scan_cost(CONFIG, T)
    assert flops == 3 * T * 3 * 2 * 32 * 128 * 128 == 154618822656
    assert bytes_ == 2 * 2 * T * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32)
    assert bytes_ == 809500672 and bytes_ / 819e9 > flops / 197e12
    assert FAMILY.kda_layers(CONFIG) == 3
    least = 3 * bytes_ / 819e9
    assert least == pytest.approx(2.965e-3, rel=1e-3)
    assert SCAN.compute(evidence) == pytest.approx(100 * least / 0.070)
    assert 0 < SCAN.compute(evidence) < 100
    # the channel form's bytes at these heads would be 1.6 times these
    kimi = run.load_module("families", "kimi_linear")
    assert 2 * 2.0 * T * (5 * 32 * 128 + 32) / bytes_ == pytest.approx(
        1.66, rel=0.01)
    assert hasattr(kimi, "kda_scan_cost")


def test_flash_kernels_against_the_roofline_at_the_live_pairs(evidence):
    """One op of the causal mask's 134,225,920 live pairs x 16 heads x
    six products at 256, K and V counted at their published 2 heads,
    bound by the MXU, over the 80 ms a step the kernels took."""
    live = T * (T + 1) // 2
    assert live == 134225920
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(6 * 2 * live * 256 * 16)
    assert bytes_ == pytest.approx(2 * T * 256 * (5 * 16 + 4 * 2))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 1
    least = flops / 197e12
    assert least == pytest.approx(33.49e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.080)
    assert 0 < FLASH.compute(evidence) < 100
    # about as much arithmetic as the three delta-rule layers' MAPS and
    # rule together (ISSUE 64): forward a token 134.2M against 3 x 70.6M
    per = FAMILY.part_flops_per_item(CONFIG)
    assert per["attn_pairs"] == pytest.approx(134.2e6, rel=1e-3)
    assert 3 * (per["gdn_maps"] + per["gdn_rule"]) == pytest.approx(
        211.7e6, rel=1e-3)


def test_grouped_products_against_the_roofline_at_the_traced_rows(evidence):
    """Four layers of nine products of 10,240 rows x 2048 x 512 over the
    30 ms a step of gmm + tgmm; at 320 rows an expert the weights' bytes
    are most of the traffic and the MXU still bounds it."""
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 10240.0)
    assert flops == pytest.approx(9 * 2 * 10240 * 2048 * 512)
    assert bytes_ == pytest.approx(
        9 * 2 * (10240 * 2048 + 10240 * 512 + 32 * 2048 * 512))
    assert FAMILY.expert_layers(CONFIG) == 4
    least = 4 * max(flops / 197e12, bytes_ / 819e9)
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.030)
    assert 0 < GMM.compute(evidence) < 100


def test_required_flops_by_hand():
    """Forward a token: a Gated DeltaNet mixer 67.4M of maps and
    convolution + 3.1M of rule, the attention layer 54.5M of maps + 134.2M
    of scores, an expert layer 12.3M (router 2.1M, shared expert and its
    gate 6.3M, the held share of ten experts 3.9M), the head 77.8M:
    527.6M; times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    assert per["gdn_maps"] == 2 * (
        D * 12288 + 2 * D * 32 + 4 * 8192 + 4096 * D) == 67436544
    assert per["gdn_rule"] == 3 * 2 * 32 * 128 * 128 == 3145728
    assert per["attn_maps"] == 2 * (2 * D * 4096 + 2 * D * 512
                                    + 4096 * D) == 54525952
    assert per["attn_pairs"] == pytest.approx(2 * (T + 1) / 2 * 16 * 512)
    assert per["experts"] == pytest.approx(
        2 * D * 512 + 6 * D * 512 + 2 * D + 10 * 32 / 512 * 6 * D * 512) \
        == 12324864
    assert per["head"] == 2 * D * 18992 == 77791232
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * (
        3 * (per["gdn_maps"] + per["gdn_rule"]) + per["attn_maps"]
        + per["attn_pairs"] + 4 * per["experts"] + per["head"]))
    assert total / 3 == pytest.approx(527.6e6, rel=2e-3)
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
    monkeypatch.setattr(rooflines, "op_seconds", lambda ev, ops: None)
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [])
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.1, {("forward", "(fusion)"): 0.1})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    evidence["counters"] = {}
    assert reader.compute(evidence) is None
    granite = run.load_json("configs", "granite-4.0-h-micro")
    monkeypatch.setattr(rooflines, "op_seconds",
                        lambda ev, ops: UNDER_THE_OP)
    with_kernels = dict(evidence, config=granite, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    assert SCAN.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    monkeypatch.setattr(rooflines, "op_seconds", lambda ev, ops: None)
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
    """Behind PR 62's, not in their midst; a later PR's entries may
    follow (no test of this file counts the lists or holds these to be
    the last). The cell reports every metric that lists no cells and its
    nine; the accepted metrics that list their cells stay their cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("lfm2-24b-a2b")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index("lfm2-24b-a2b.train-shortconv-ep8-share")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == list(range(at[0], at[0] + 9)) and at[0] > names(
        "per_layer").index("shortconv_recompute_time_pct.train")
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(CELL["per_layer"]) == set(unlisted) | set(NAMES)
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG["name"]
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
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["num_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_experts"],
        published["vocab_size"]) == (48, 512, 151936)
    # every published width stands
    assert (CONFIG["hidden_size"], CONFIG["linear_num_key_heads"],
            CONFIG["linear_num_value_heads"], CONFIG["linear_key_head_dim"],
            CONFIG["linear_value_head_dim"],
            CONFIG["linear_conv_kernel_dim"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["partial_rotary_factor"], CONFIG["rope_theta"],
            CONFIG["moe_intermediate_size"], CONFIG["num_experts_per_tok"],
            CONFIG["shared_expert_intermediate_size"],
            CONFIG["norm_topk_prob"], CONFIG["full_attention_interval"]) == (
        2048, 16, 32, 128, 128, 4, 16, 2, 256, 0.25, 10000000, 512, 10, 512,
        True, 4)
    # the floors of a cut: one whole period of four layers (no leading
    # dense layer exists), a sixteenth of the experts, an eighth of the
    # vocabulary
    assert CONFIG["layers_held"] == [0, 1, 2, 3]
    assert CONFIG["num_hidden_layers"] == 4
    assert CONFIG["num_experts"] == 32 and CONFIG["expert_offset"] == 0
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    assert CONFIG["num_experts"] * 16 == published["num_experts"]
    assert CONFIG["family"] == "qwen3_next"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("in_proj", "decay_initial", "l2_norm", "chunk", "rotary",
                "router", "router_balance", "prediction_module",
                "optimizer", "dropout", "initialisation", "sequence_length",
                "recompute", "amp", "unread"):
        assert key in CONFIG["assumed"], key
    for key in ("router_balance_rate", "initializer_range",
                "gdn_chunk_size", "l2_norm_epsilon"):
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
    assert CONFIG["sequence_length"] in (16384, 8192)
    assert (CELL["batch"], CONFIG["recompute"]) == (1, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    assert "16x" in CELL["why"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_tail_rtol",
                         "update_rtol"))
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TBD" not in CELL[key] and "PR 64" in CELL[key]
    assert "PR 64" in CELL["reference"]["measured"]
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["adam_beta1"], CONFIG["adam_beta2"],
            CONFIG["adam_epsilon"], CONFIG["learning_rate"]) == (
        0.9, 0.999, 1e-8, 1e-6)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, T)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 18992
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == T


def test_the_parameters_here_are_the_programs_own_count():
    """625,667,136, ISSUE 64's count, from the program's parameters:
    three Gated DeltaNet expert layers, the attention expert layer, the
    embedding, the final norm and the untied head; three layers replayed,
    each with its delta rule and its expert layer; the delta rule's op
    reads q and k at 16 heads and a gate a head."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    block = main.global_block()
    count = sum(int(np.prod(p.shape))
                for p in block.all_parameters() if p.trainable)
    norms = 2 * D
    gdn = D * 12288 + 2 * D * 32 + 8192 * 4 + 32 + 32 + 128 + 4096 * D
    attention = 2 * D * 4096 + 2 * D * 512 + 2 * 256 + 4096 * D
    experts = D * 512 + 32 * 3 * D * 512 + 3 * D * 512 + D
    assert (gdn, attention, experts) == (33718464, 27263488, 104859648)
    assert count == 3 * (gdn + experts + norms) + attention + experts \
        + norms + 2 * 18992 * D + D == 625667136
    assert "625,667,136" in CONFIG["deployment"]["parameters_here"]
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3]
    assert [(types.count("kda_scan"), types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [(1, 1)] * 3
    shapes = {tuple(tuple(block.var(op.input(slot)[0]).shape[2:])
                    for slot in ("Q", "K", "V", "Gate"))
              for op in block.ops if op.type == "kda_scan"}
    assert shapes == {((16, 128), (16, 128), (32, 128), (32,))}
    # the tiny preset is the same family at the same pattern
    assert TINY["family"] == CONFIG["family"]
    assert TINY["layers_held"] == CONFIG["layers_held"]
    assert TINY["full_attention_interval"] \
        == CONFIG["full_attention_interval"]
