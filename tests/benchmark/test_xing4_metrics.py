"""The eleven readers the Xing4.0 cell brought (PR 68: the
hyper-connections' share of a step, their sweeps' share, their share of
the HBM roofline and the residual maps' distance from doubly stochastic;
latent attention at 192 beside 128 under YaRN and its flash kernels, the
expert layer of a rank that holds an eighth, its grouped products'
roofline, its busiest expert and its rungs, and what the replayed blocks
cost, seven of them the reduction of an accepted reader under a second
name), on hand-written reductions of a trace and hand-written counters;
the family's arithmetic they price by, against hand counts; the manifest
by MEMBERSHIP (no list's length is held here), the configuration against
the catalog's row, and the cell against ISSUE 68's parameters. The cell's
rehearsal on the CPU is test_run_cpu.py's
(data/workloads/tiny-xing4.train.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

NAMES = ("hc_time_pct.train", "hc_sinkhorn_time_pct.train",
         "hc_roofline_pct.train", "hc_res_sum_error.train",
         "hc_mla_time_pct.train", "hc_mla_flash_roofline_pct.train",
         "hc_expert_time_pct.train", "hc_expert_matmul_roofline_pct.train",
         "hc_expert_load_max_over_mean.train",
         "hc_expert_rows_handled_over_routed.train",
         "hc_recompute_time_pct.train")
READERS = {name: run.load_module("layer_metrics", name) for name in NAMES}
(PATH, SWEEPS, ROOFLINE, ERROR, LATENT, FLASH, EXPERTS, GMM, LOAD, HANDLED,
 REPLAYED) = READERS.values()
# the module whose `compute` the last of them hands on (load_module makes
# a new one a call)
RECOMPUTE = REPLAYED.compute.__globals__
CELL = run.load_json("workloads", "xing4.0-29b-a4b.train-mhc4-mla-ep8-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-xing4", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
# what the manifest says of each: layer, unit, better, source
SAID = {
    NAMES[0]: ("residual path", "%", "lower", "device_trace"),
    NAMES[1]: ("residual path", "%", "lower", "device_trace"),
    NAMES[2]: ("kernels", "%", "higher", "device_trace"),
    NAMES[3]: ("residual path", "abs", "lower", "program_counter"),
    NAMES[4]: ("latent attention", "%", "lower", "device_trace"),
    NAMES[5]: ("kernels", "%", "higher", "device_trace"),
    NAMES[6]: ("experts", "%", "lower", "device_trace"),
    NAMES[7]: ("kernels", "%", "higher", "device_trace"),
    NAMES[8]: ("experts", "x", "lower", "program_counter"),
    NAMES[9]: ("experts", "x", "lower", "program_counter"),
    NAMES[10]: ("recomputation", "%", "lower", "device_trace")}


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# three steps by the name scope their ops were built under: the sweeps
# nested in the maps' scope, the shared expert in the expert layer's
def scoped(maps_s):
    return step(0.250, {
        ("forward", "hyper_connection_maps"): maps_s,
        ("backward", "hyper_connection_maps"): 0.010,
        ("forward", "hyper_connection_maps.sinkhorn_knopp"): 0.002,
        ("backward", "hyper_connection_maps.sinkhorn_knopp"): 0.004,
        ("forward", "hc_pre_mix"): 0.003,
        ("backward", "hc_pre_mix"): 0.005,
        ("forward", "hc_post_res_mix"): 0.006,
        ("backward", "hc_post_res_mix"): 0.012,
        ("forward", "latent_attention"): 0.020,
        ("backward", "latent_attention"): 0.040,
        ("forward", "moe_block"): 0.020,
        ("backward", "moe_block"): 0.040,
        ("backward", "moe_block.gated_mlp"): 0.010,
        ("forward", "gated_mlp"): 0.010,
        ("optimize", "(fusion)"): 0.030})


SCOPED = [scoped(0.008), scoped(0.006), scoped(0.010)]


def side_fetches(errors):
    return [{"metric": "moe_rows_routed",
             "values": [1900.0, 2200.0, 2048.0, 2044.0]}] * 4 + [
        {"metric": "hc_res_sum_error", "values": [e]} for e in errors]


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    monkeypatch.setattr(
        telemetry, "recent_events",
        lambda kind=None: side_fetches([0.5, 2e-6, 3e-6, 9e-6, 4e-6]))
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"counters": {
        "hyper_connection_sublayers_total": {"program=p1": 20.0},
        "hyper_connection_replays_total": {"program=p1": 12.0}}})
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.250, {("backward", RECOMPUTE["REPLAYED"]): 0.030,
                     ("backward", "(fusion)"): 0.120,
                     ("forward", "(fusion)"): 0.100})] * 2)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 4096, "steps": 2,
            "counters": {
                "moe_rows_handled": {"layer=0": {"sum": 4096.0 * 4,
                                                 "count": 4}},
                "moe_rows_routed": {"layer=0": {"sum": 2048.0 * 4,
                                                "count": 4}},
                "moe_load_max_over_mean": {
                    "layer=0": {"sum": 5.0, "count": 4},
                    "layer=1": {"sum": 7.0, "count": 4}}},
            "trace": {"busy_s": 0.5, "device_ops": [
                ["fusion", 0.300], ["flash_fwd", 0.030],
                ["flash_dkv", 0.062], ["gmm", 0.020], ["tgmm", 0.010]]}}


def test_time_shares_by_scope(evidence):
    """Of 750 ms in three steps: under the four hyper-connection scopes
    24 + 3 x 42 = 150, of which the sweeps (nested in the maps' scope) 18;
    latent attention's 180; the expert layers' own 180 and their shared
    expert's 30, the dense block's feed-forward not among them."""
    assert PATH.compute(evidence) == pytest.approx(100 * 150 / 750)
    assert SWEEPS.compute(evidence) == pytest.approx(100 * 18 / 750)
    assert LATENT.compute(evidence) == pytest.approx(100 * 180 / 750)
    assert EXPERTS.compute(evidence) == pytest.approx(100 * 210 / 750)
    under, busy = PATH.scoped_seconds(evidence)
    assert under == pytest.approx([0.050, 0.048, 0.052])
    assert busy == [0.250] * 3


def test_counters(evidence):
    assert HANDLED.compute(evidence) == pytest.approx(2.0)
    assert LOAD.compute(evidence) == pytest.approx(1.5)


def test_replayed_blocks_share(evidence):
    """Of 250 ms a step, 30 under a `pd_recompute` scope."""
    assert REPLAYED.compute(evidence) == pytest.approx(12.0)


def test_the_largest_sum_error_of_the_window_and_the_traced_steps(evidence):
    """The window's 2 steps and the 2 traced ones are the last four
    publications: the warm-up's 0.5 is not among them."""
    assert ERROR.compute(evidence) == 9e-6


def test_the_hyper_connections_against_the_roofline(evidence):
    """Ten sublayers of (3 n + 2) C s a pass and (4 n + 3) C s backward a
    token, the maps' own bytes besides, with 12 of 20 traced sublayers
    replayed (two traces of ten; a kept segment lowers no replay), over
    the median 50 ms a step under the four scopes."""
    a_pass, backward = 14 * 3584 * 2, 19 * 3584 * 2
    maps, phi = 24 * 4, 14336 * 24 * 4
    bytes_ = FAMILY.hyper_connection_cost(CONFIG, 4096, 0.6)
    assert bytes_ == pytest.approx(10 * (
        4096 * (1.6 * (a_pass + 2 * maps) + backward + 4 * maps)
        + 3.6 * phi))
    least = bytes_ / 819e9
    assert least == pytest.approx(14.9e-3, rel=1e-2)
    assert ROOFLINE.compute(evidence) == pytest.approx(100 * least / 0.050)
    assert 0 < ROOFLINE.compute(evidence) < 100
    assert ROOFLINE.replayed_share() == pytest.approx(0.6)
    # nothing kept: the family's default, every block but the last
    assert FAMILY.hyper_connection_cost(CONFIG) \
        == FAMILY.hyper_connection_cost(CONFIG, 4096, 0.8) > bytes_


def test_flash_kernels_against_the_roofline_at_the_published_widths(
        evidence):
    """Five ops of the causal mask's live pairs x 32 heads x three
    products at 192 and three at 128, whatever lanes the layer hands the
    kernels, bound by the MXU, over the 46 ms a step the kernels took."""
    live = 4096 * 4097 // 2
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(2 * live * 32 * 3 * (192 + 128))
    assert bytes_ == pytest.approx(2 * 4096 * 32 * (4 * 192 + 5 * 128))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 5
    least = 5 * flops / 197e12
    assert least == pytest.approx(13.08e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.046)
    assert 0 < FLASH.compute(evidence) < 100
    # at 256 lanes a head the same pairs would count 1.6 times as much
    assert 2 * live * 32 * 6 * 256 / flops == pytest.approx(1.6)


def test_grouped_products_against_the_roofline_at_the_traced_rows(evidence):
    """Four layers of nine products of 2048 rows x 3584 x 1024 over the
    15 ms a step of gmm + tgmm; at 256 rows an expert the weights' bytes
    bound it, not the MXU."""
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 2048.0)
    assert flops == pytest.approx(9 * 2 * 2048 * 3584 * 1024)
    assert bytes_ == pytest.approx(
        9 * 2 * (2048 * 3584 + 2048 * 1024 + 8 * 3584 * 1024))
    assert bytes_ / 819e9 > flops / 197e12
    assert FAMILY.expert_layers(CONFIG) == 4
    least = 4 * bytes_ / 819e9
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.015)
    assert 0 < GMM.compute(evidence) < 100


def test_required_flops_by_hand():
    """Forward, a token: latent attention's five maps 56.8 MFLOP and its
    live pairs 42.0, a hyper-connection 0.86 (its projection 0.69, the two
    mixes 0.17), the dense feed-forward 198, an expert layer's router,
    shared expert and expected rows 33.5, the head 117; times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    d = 3584
    maps = (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256
            + 32 * 128 * d)
    assert maps == 28411136 - 1280
    assert per["mla"] == pytest.approx(
        2 * maps + 2 * (4097 / 2) * 32 * (192 + 128))
    assert per["hyper"] == 2 * 4 * d * 24 + 2 * d * (4 + 16 + 4) == 860160
    assert per["dense"] == 6 * d * 9216
    assert per["experts"] == pytest.approx(
        2 * d * 64 + 6 * d * 1024 + 4 * 8 / 64 * 6 * d * 1024)
    assert per["head"] == 2 * d * 16384
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * (
        5 * (per["mla"] + 2 * per["hyper"]) + per["dense"]
        + 4 * per["experts"] + per["head"]))
    # the hyper-connections are FLOPs of no note: under 1 % of a step's
    assert 10 * per["hyper"] * 3 / total < 0.01
    # the same whether the program recomputes or not
    assert FAMILY.required_flops_per_item(dict(CONFIG, recompute=False)) \
        == total


def test_the_reference_and_the_program_read_one_file():
    """Every number of the equations is a key of the file, read by the
    program's builder and the reference alike; the tiny file of the
    tests holds the same keys."""
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
                "rope_scaling", "rms_norm_eps", "routed_scaling_factor",
                "router_balance_rate", "recompute"):
        assert key in CONFIG and key in TINY, key
    assert (CONFIG["hc_mult"], CONFIG["hc_sinkhorn_iters"], CONFIG["hc_eps"],
            CONFIG["mhc_h_res_clamp_min"], CONFIG["mhc_h_res_clamp_max"]) \
        == (4, 20, 1e-6, -30, 30)
    assert FAMILY.parameters_here(TINY) == 204250


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_program_reports_nothing(name, evidence, monkeypatch):
    """No such scope, no such kernel, no such counter or gauge, or a
    family that prices neither: None, not an error; None without a
    trace."""
    reader = READERS[name]
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "mamba2_mixer"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "recent_events", lambda kind=None: [])
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"counters": {}})
    monkeypatch.setitem(RECOMPUTE, "replayed_steps", lambda ev: [
        step(0.1, {("forward", "(fusion)"): 0.1})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    evidence["counters"] = {}
    assert reader.compute(evidence) is None
    granite = run.load_json("configs", "granite-4.0-h-micro")
    with_kernels = dict(evidence, config=granite, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    assert ROOFLINE.compute(with_kernels) is None       # prices none
    assert ROOFLINE.replayed_share() is None
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


def test_the_entries_follow_the_accepted_ones():
    """Behind PR 64's, not in their midst; a later PR's entries may
    follow: membership and order are held, no list's length. The cell
    reports every metric that lists no cells and its eleven; the accepted
    expert, latent and recomputation metrics stay their cells'."""
    def names(key):
        return [e["name"] for e in MANIFEST[key]]
    assert names("configs").index(CONFIG["name"]) \
        > names("configs").index("qwen3-next-80b-a3b-instruct")
    assert names("workloads").index(CELL["name"]) > names("workloads").index(
        "qwen3-next-80b-a3b.train-gdn-t16k-ep16-share")
    at = [names("per_layer").index(m) for m in NAMES]
    assert at == sorted(at) and at[0] > names("per_layer").index(
        "gdn_recompute_time_pct.train")
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert unlisted | set(NAMES) == set(CELL["per_layer"])
    for m in MANIFEST["per_layer"]:
        if "workloads" in m and m["name"] not in NAMES:
            assert CELL["name"] not in m["workloads"], m["name"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmarks/configs/xing4.0-29b-a4b.json"
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
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert [CONFIG[k + "_published"] for k in CONFIG["reduced"]] \
        == [published[k] for k in CONFIG["reduced"]] \
        == [40, 2, 64, 131072, 1]
    # no width is cut, and the nested group is the published one whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "hc_mult", "num_attention_heads"):
        assert CONFIG[key] == published[key], key
    assert CONFIG["rope_scaling"] == published["rope_scaling"]
    # the floors of a cut: the dense blocks once and four blocks behind
    # them (every one of the same kind), 8 routed experts, an eighth of
    # the vocabulary
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"]) \
        == (5, 1)
    assert CONFIG["n_routed_experts"] == 8
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["num_nextn_predict_layers"] == 0
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["n_routed_experts"] * 8 == published["n_routed_experts"]
    assert CONFIG["family"] == "xing4"
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("sweep_order", "hc_eps", "hc_norm", "hc_parameters",
                "hc_initial", "hc_ends", "hc_precision", "rope_scaling",
                "rotary_pairing", "lanes", "e_score_correction_bias",
                "router", "optimizer", "initialisation", "sequence_length",
                "recompute", "unread"):
        assert key in CONFIG["assumed"], key
    assert "8 times" in CONFIG["deployment"]["distortion"]
    assert "913.5M" in CONFIG["deployment"]["pipeline"]


def test_the_routers_balancing_rule_is_stated_as_assumed():
    rate = CONFIG["router_balance_rate"]
    assert rate == 0.02
    said = CONFIG["assumed"]["e_score_correction_bias"]
    assert "router_balance_rate" in said and str(rate) in said
    main, _, _ = FAMILY.build(CONFIG)
    rules = [op for op in main.global_block().ops
             if op.type == "moe_balance_bias"]
    assert len(rules) == 4 and all(op.attr("rate") == rate for op in rules)


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["recompute"]) == (1, 4096, True)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["trace_steps"]) == (4, 2, 2, 17)
    assert CELL["warmup_steps"] in (16, 32, 64)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert len(CELL["why"]) <= 200 and "8x" in CELL["why"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_tail_rtol",
                         "update_rtol"))
    for key in ("batch_sizing", "warmup_sizing"):
        assert "TO FILL" not in CELL[key] and "PR 68" in CELL[key]
    assert "PR 68" in CELL["reference"]["measured"]
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
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 16384
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 4096


def test_the_blocks_are_replayed_with_their_hyper_connections():
    """Four segments between the five checkpoints (the last block lies
    behind the last one) and the lookup ahead of the first: each replays
    its two sublayers' maps, sweeps and mixes (but the second write-back,
    which IS the next checkpoint), its flash call (handed its first
    outputs) and, behind the dense block, its expert layer; the attention
    op reads 256 lanes a head for 192 | 128."""
    from paddle_tpu import backward

    main, _, _ = FAMILY.build(CONFIG)
    replayed = backward.replayed_ops(main)
    # segment 0: the lookup and its copy into the four streams
    assert sorted(replayed) == [0, 1, 2, 3, 4]
    assert "lookup_table" in replayed.pop(0)
    assert [(types.count("hyper_connection_maps"),
             types.count("sinkhorn_knopp"), types.count("hc_pre_mix"),
             types.count("hc_post_res_mix"),
             types.count("scaled_dot_product_attention"),
             types.count("moe_experts"))
            for _, types in sorted(replayed.items())] == [
        (2, 2, 2, 1, 1, 0)] + [(2, 2, 2, 1, 1, 1)] * 3
    widths = {tuple(main.global_block().var(op.input(slot)[0]).shape[3]
                    for slot in ("Q", "K", "V"))
              for op in main.global_block().ops
              if op.type == "scaled_dot_product_attention"}
    assert widths == {(256, 256, 256)}
    streams = {tuple(main.global_block().var(op.input("X")[0]).shape[1:])
               for op in main.global_block().ops
               if op.type == "hc_post_res_mix"}
    assert streams == {(4096, 4, 3584)}
