"""The nine readers the Kimi-Linear cell brought (PR 55: the
Kimi-Delta-Attention mixer's share and its delta rule's roofline, latent
attention without positions and its flash kernels at 192 beside 128, the
expert layer of a rank that holds a thirty-second, its grouped products'
roofline and its busiest expert, and what the replayed layers cost, seven
of them the reduction of an accepted reader under a second name), on
hand-written
reductions of a trace and hand-written counters; the family's arithmetic
they price by, against hand counts; the manifest, the configuration
against the catalog's row, and the cell against ISSUE 55's parameters.
The cell's rehearsal on the CPU is test_run_cpu.py's
(data/workloads/tiny-kimi-linear.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("kda_mixer_time_pct.train", "kda_scan_roofline_pct.train",
         "nope_mla_time_pct.train", "nope_mla_flash_roofline_pct.train",
         "ep32_expert_time_pct.train",
         "ep32_expert_rows_handled_over_routed.train",
         "ep32_expert_matmul_roofline_pct.train",
         "ep32_expert_load_max_over_mean.train",
         "kda_recompute_time_pct.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
MIXER, RULE, LATENT, FLASH, EXPERTS, HANDLED, GMM, LOAD, REPLAYED = \
    READERS.values()
# the module whose `compute` the last of them hands on (load_module makes
# a new one a call)
RECOMPUTE = REPLAYED.compute.__globals__
CELL = run.load_json("workloads", "kimi-linear.train-kda-t8192-ep32-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-kimi-linear", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, unit, better, source
SAID = {
    NAMES[0]: ("delta-rule mixer", "%", "lower", "device_trace"),
    NAMES[1]: ("kernels", "%", "higher", "device_trace"),
    NAMES[2]: ("latent attention", "%", "lower", "device_trace"),
    NAMES[3]: ("kernels", "%", "higher", "device_trace"),
    NAMES[4]: ("experts", "%", "lower", "device_trace"),
    NAMES[5]: ("experts", "x", "lower", "program_counter"),
    NAMES[6]: ("kernels", "%", "higher", "device_trace"),
    NAMES[7]: ("experts", "x", "lower", "program_counter"),
    NAMES[8]: ("recomputation", "%", "lower", "device_trace")}


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the mixer's
# norm under its own scope nested in the mixer's, the shared expert
# nested in the expert layer's
SCOPED = [step(0.400, {
    ("forward", "kda_mixer"): 0.050,
    ("backward", "kda_mixer"): 0.110,
    ("backward", "kda_mixer.rms_norm"): 0.004,
    ("forward", "latent_attention"): 0.012,
    ("backward", "latent_attention"): 0.028,
    ("forward", "moe_block"): 0.020,
    ("backward", "moe_block"): 0.040,
    ("backward", "moe_block.gated_mlp"): 0.012,
    ("forward", "gated_mlp"): 0.010,
    ("forward", "(fusion)"): 0.084,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [
        {"metric": "moe_rows_routed", "values": [1900.0, 2200.0, 2048.0,
                                                 2044.0]}] * 4)
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.400, {("backward", RECOMPUTE["REPLAYED"]): 0.046,
                     ("backward", "(fusion)"): 0.224,
                     ("forward", "(fusion)"): 0.130})] * 2)
    monkeypatch.setattr(
        rooflines, "op_seconds",
        lambda ev, ops: [0.060, 0.050, 0.070] if ops == ("kda_scan",)
        else None)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 8192,
            "counters": {
                "moe_rows_handled": {"layer=0": {"sum": 8192.0 * 4,
                                                 "count": 4}},
                "moe_rows_routed": {"layer=0": {"sum": 2048.0 * 4,
                                                "count": 4}},
                "moe_load_max_over_mean": {
                    "layer=0": {"sum": 5.0, "count": 4},
                    "layer=1": {"sum": 7.0, "count": 4}}},
            "trace": {"busy_s": 0.8, "device_ops": [
                ["fusion", 0.300], ["flash_fwd", 0.016],
                ["flash_dkv", 0.034], ["gmm", 0.034], ["tgmm", 0.012]]}}


def test_time_shares_by_scope(evidence):
    """Of 400 ms: the delta-rule mixers' 160 and their norm's 4; latent
    attention's 40; the expert layers' own 60 and their shared expert's
    12, the dense layer's feed-forward not among them."""
    assert MIXER.compute(evidence) == pytest.approx(41.0)
    assert LATENT.compute(evidence) == pytest.approx(10.0)
    assert EXPERTS.compute(evidence) == pytest.approx(18.0)


def test_counters(evidence):
    assert HANDLED.compute(evidence) == pytest.approx(4.0)
    assert LOAD.compute(evidence) == pytest.approx(1.5)


def test_replayed_layers_share(evidence):
    """Of 400 ms a step, 46 under a `pd_recompute` scope."""
    assert REPLAYED.compute(evidence) == pytest.approx(11.5)


def test_the_delta_rule_against_the_roofline(evidence):
    """Four layers of three [128, 128] products a token a head, forward
    and twice that backward, over the 60 ms the op and its gradient took
    in the median traced step; q, k, v, the gate and o and their
    gradients once in bf16 bound it, not the MXU."""
    flops, bytes_ = FAMILY.kda_scan_cost(CONFIG, 8192)
    assert flops == 3 * 8192 * 3 * 2 * 32 * 128 * 128 == 77309411328
    assert bytes_ == 2 * 2 * 8192 * (5 * 32 * 128 + 32) == 672137216
    assert bytes_ / 819e9 > flops / 197e12
    assert FAMILY.kda_layers(CONFIG) == 4
    least = 4 * bytes_ / 819e9
    assert least == pytest.approx(3.28e-3, rel=5e-3)
    assert RULE.compute(evidence) == pytest.approx(100 * least / 0.060)
    assert 0 < RULE.compute(evidence) < 100
    # no chunk length moves the count
    assert FAMILY.kda_scan_cost(dict(CONFIG, kda_chunk_size=32), 8192) \
        == (flops, bytes_)


def test_flash_kernels_against_the_roofline_at_the_published_widths(
        evidence):
    """One op of the causal mask's live pairs x 32 heads x three products
    at 192 and three at 128, whatever lanes the layer hands the kernels,
    bound by the MXU, over the 25 ms a step the kernels took."""
    live = 8192 * 8193 // 2
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(2 * live * 32 * 3 * (192 + 128))
    assert bytes_ == pytest.approx(2 * 8192 * 32 * (4 * 192 + 5 * 128))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 1
    least = flops / 197e12
    assert least == pytest.approx(10.47e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.025)
    assert 0 < FLASH.compute(evidence) < 100
    # the forward of the live pairs: ISSUE 55's 0.7 TFLOP a step
    assert flops / 3 == pytest.approx(0.687e12, rel=5e-3)
    # at 256 lanes a head the same pairs would count 1.6 times as much
    assert 2 * live * 32 * 6 * 256 / flops == pytest.approx(1.6)


def test_grouped_products_against_the_roofline_at_the_traced_rows(evidence):
    """Four layers of nine products of 2048 rows x 2304 x 1024 over the
    23 ms a step of gmm + tgmm; at 256 rows an expert the weights' bytes
    bound it, not the MXU."""
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 2048.0)
    assert flops == pytest.approx(9 * 2 * 2048 * 2304 * 1024)
    assert bytes_ == pytest.approx(
        9 * 2 * (2048 * 2304 + 2048 * 1024 + 8 * 2304 * 1024))
    assert bytes_ / 819e9 > flops / 197e12
    assert FAMILY.expert_layers(CONFIG) == 4
    least = 4 * bytes_ / 819e9
    assert least == pytest.approx(2.26e-3, rel=5e-3)
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.023)
    assert 0 < GMM.compute(evidence) < 100


def test_required_flops_by_hand():
    """6.29 TFLOP a step forward: the four KDA mixers' maps 2.59 and
    their delta rule 0.10, the latent layer's maps 0.48 and live pairs
    0.69, the dense layer 1.04, routers, shared experts and the expected
    routed rows 0.62, the head 0.77; times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    d, t = 2304, 8192
    maps = 4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32
    assert maps + 32 + 4096 + 128 + 3 * 4 * 4096 == 39514272
    assert per["kda_maps"] == 2 * (maps + 3 * 4 * 4096) == 79020032
    assert per["kda_rule"] == 3 * 2 * 32 * 128 * 128 == 3145728
    assert per["mla_maps"] == 2 * (14155776 + 1327104 + 4194304 + 9437184)
    assert per["mla_pairs"] == pytest.approx(
        2 * (8193 / 2) * 32 * (192 + 128))
    assert per["dense"] == 6 * d * 9216 == 2 * 63700992
    assert per["experts"] == pytest.approx(
        2 * d * 256 + 6 * d * 1024 + 8 * 8 / 256 * 6 * d * 1024)
    assert per["head"] == 2 * d * 20480
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * (
        4 * (per["kda_maps"] + per["kda_rule"]) + per["mla_maps"]
        + per["mla_pairs"] + per["dense"] + 4 * per["experts"]
        + per["head"]))
    assert total * t / 3 == pytest.approx(6.292e12, rel=1e-3)
    assert 4 * per["kda_maps"] * t == pytest.approx(2.59e12, rel=5e-3)
    assert 4 * per["kda_rule"] * t == pytest.approx(0.103e12, rel=5e-3)
    # the same whether the program recomputes or not, at any chunk
    assert FAMILY.required_flops_per_item(
        dict(CONFIG, recompute=False, kda_chunk_size=32)) == total


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_program_reports_nothing(name, evidence, monkeypatch):
    """No such scope, no such op, no such kernel, no such counter, or a
    family that prices neither: None, not an error; None without a
    trace."""
    reader = READERS[name]
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "mamba2_mixer"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "op_seconds", lambda ev, ops: None)
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [])
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.1, {("forward", "(fusion)"): 0.1})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    evidence["counters"] = {}
    assert reader.compute(evidence) is None
    granite = run.load_json("configs", "granite-4.0-h-micro")
    with_kernels = dict(evidence, config=granite, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "op_seconds", lambda ev, ops: [0.01])
    assert RULE.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "op_seconds", lambda ev, ops: None)
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
    """Behind PR 53's, not in their midst; a later PR's entries may
    follow (no test of this file counts the lists or holds these to be
    the last). The cell reports every metric that lists no cells and its
    nine; the accepted expert, latent and recomputation metrics stay their
    cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("laguna-xs.2")
    assert names("workloads").index(CELL["name"]) \
        > names("workloads").index("laguna-xs.2.train-gated-swa512-ep8-share")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == list(range(at[0], at[0] + 9)) and at[0] > names(
        "per_layer").index("small_expert_rows_handled_over_routed.train")
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(CELL["per_layer"]) == set(unlisted) | set(NAMES)
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
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
        published["vocab_size"]) == (27, 256, 163840)
    # the floors of a cut: the dense layer once and a whole period of
    # four layers behind it, 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["linear_attn_config"] == published["linear_attn_config"]
    from paddle_tpu.models import kda_moe
    linear = CONFIG["linear_attn_config"]
    assert kda_moe.mixer_kinds(5, linear["kda_layers"],
                               linear["full_attn_layers"]) == [
        kda_moe.KDA] * 3 + [kda_moe.FULL, kda_moe.KDA]
    assert CONFIG["first_k_dense_replace"] == 1
    assert CONFIG["num_experts"] == 8
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 32
    assert CONFIG["num_experts"] * 32 == published["num_experts"]
    assert CONFIG["family"] == "kimi_linear"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("low_rank_gates", "biases", "l2_norm", "decay_initial",
                "chunk", "k_pe", "router", "router_balance", "optimizer",
                "initialisation", "sequence_length", "recompute", "amp",
                "dropout", "model_max_length", "head_dim"):
        assert key in CONFIG["assumed"], key
    # each reading is one key the program and the reference read
    assert (CONFIG["kda_gate_rank"], CONFIG["kda_chunk_size"],
            CONFIG["l2_norm_epsilon"]) == (128, 64, 1e-6)
    for key in ("kda_gate_rank", "kda_chunk_size", "l2_norm_epsilon",
                "router_balance_rate"):
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
            CONFIG["recompute"]) == (1, 8192, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_tail_rtol",
                         "update_rtol"))
    # left out, and said with its readings: the lower precision reads
    # under three times the largest sound one, so no limit separates them
    assert CELL["reference"]["grad_norm_rtol"] is None
    assert "grad_norm_rtol is left out" in CELL["reference"]["measured"]
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TO BE" not in CELL[key] and "PR 55" in CELL[key]
    assert "PR 55" in CELL["reference"]["measured"]
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["adam_beta1"], CONFIG["adam_beta2"],
            CONFIG["adam_epsilon"], CONFIG["learning_rate"]) == (
        0.9, 0.999, 1e-8, 1e-6)
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, 8192)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 20480
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 8192


def test_the_parameters_here_are_the_programs_own_count():
    """602,433,408, ISSUE 55's count, from the program's parameters: the
    dense KDA layer, three KDA expert layers, the latent expert layer,
    embedding and head and the final norm; four layers replayed, three of
    them with their delta rule, one with its flash call, three with their
    expert layer."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    d, norms = 2304, 2 * 2304
    kda, latent = 39514272, 29114880
    expert_ffn = d * 256 + 8 * 7077888 + 7077888
    assert expert_ffn == 64290816
    layer_1 = kda + 63700992 + norms
    kda_expert = kda + expert_ffn + norms
    latent_expert = latent + expert_ffn + norms
    assert (layer_1, kda_expert, latent_expert) == (
        103219872, 103809696, 93410304)
    assert count == layer_1 + 3 * kda_expert + latent_expert \
        + 2 * 47185920 + d == 602433408
    assert "602,433,408" in CONFIG["deployment"]["parameters_here"]
    replayed = backward.replayed_ops(main)
    assert sorted(replayed) == [1, 2, 3, 4]
    assert [(types.count("kda_scan"),
             types.count("scaled_dot_product_attention"),
             types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [
        (1, 0, 0), (1, 0, 1), (1, 0, 1), (0, 1, 1)]
    chunks = [op.attr("chunk_size") for op in main.global_block().ops
              if op.type == "kda_scan"
              and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    assert chunks == [CONFIG["kda_chunk_size"]] * 4
    widths = [tuple(main.global_block().var(op.input(slot)[0]).shape[3]
                    for slot in ("Q", "K", "V"))
              for op in main.global_block().ops
              if op.type == "scaled_dot_product_attention"
              and backward.RECOMPUTE_ATTR not in op.desc.attrs]
    assert widths == [(256, 256, 256)]
