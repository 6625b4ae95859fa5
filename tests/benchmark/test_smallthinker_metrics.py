"""The two readers the sliding-window cell brought (the layer "windowed
attention" and the flash kernels' share of their roofline at the live
pairs of layers of two kinds), on a hand-written reduction of a trace by
name scope and trace_reduce's `device_ops` rows; the family's arithmetic
they price by, against an explicit mask; the configuration against the
catalog's row; the cell against ISSUE 46's parameters."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

ATTENTION = run.load_module("layer_metrics",
                            "window_attention_time_pct.train")
FLASH = run.load_module("layer_metrics", "window_flash_roofline_pct.train")
READERS = (ATTENTION, FLASH)
CELL = run.load_json("workloads",
                     "smallthinker-21b-a3b.train-swa-t8192-ep8-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-smallthinker", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
LISTED_ELSEWHERE = (
    "moe_time_pct.train", "moe_load_max_over_mean.train",
    "expert_matmul_roofline_pct.train", "moe_rows_handled_over_routed.train",
    "bd_attention_time_pct.train", "bd_flash_roofline_pct.train")


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the rotations
# under their own layer's scope nested in the kind's, the attention op
# under the kind's own
SCOPED = [step(0.250, {
    ("forward", "window_attention.rotary_embedding"): 0.004,
    ("backward", "window_attention.rotary_embedding"): 0.006,
    ("forward", "window_attention"): 0.015,
    ("backward", "window_attention"): 0.025,
    ("forward", "global_attention"): 0.007,
    ("backward", "global_attention"): 0.012,
    ("forward", "moe_block"): 0.040,
    ("forward", "(fusion)"): 0.055,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 8192,
            "trace": {"busy_s": 0.5, "device_ops": [
                ["fusion", 0.300], ["flash_dkv", 0.064],
                ["flash_fwd", 0.036], ["gmm", 0.012], ["tgmm", 0.008]]}}


def test_the_windowed_share_leaves_the_full_attention_layers_out(evidence):
    """The rotations' 10 and the op's 40 of 250 ms; the full-attention
    layers' 19 are another scope's."""
    assert ATTENTION.compute(evidence) == pytest.approx(20.0)


def test_flash_kernels_against_the_roofline_at_the_live_pairs(evidence):
    """Four ops of six products of the MEAN live pairs x 128 x 28 (one
    causal layer and three under the window), bound by the MXU, over the
    50 ms a step the kernels took."""
    causal, windowed = 8192 * 8193 // 2, 25167872
    assert FAMILY.live_pairs(8192, 0) == causal == 33558528
    assert FAMILY.live_pairs(8192, 4096) == windowed
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(
        6 * 2 * (causal + 3 * windowed) / 4 * 128 * 28)
    assert bytes_ == pytest.approx(2 * 8192 * 128 * (5 * 28 + 4 * 4))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 4
    least = 4 * flops / 197e12
    assert least == pytest.approx(23.8e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.050)
    assert 0 < FLASH.compute(evidence) < 100
    # a kernel that walked the tiles before the window would run a fifth
    # more pairs than are counted
    assert 4 * causal / (causal + 3 * windowed) == pytest.approx(1.23,
                                                                 abs=0.01)


@pytest.mark.parametrize("length,window", [
    (64, 16), (64, 0), (48, 48), (48, 100), (96, 1), (80, 37)])
def test_the_cost_is_the_live_pairs_of_an_explicit_mask(length, window):
    pos = np.arange(length)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[:, None] - pos[None, :] < window
    assert FAMILY.live_pairs(length, window) == keep.sum()
    # a model whose layers are all of this one kind
    config = dict(TINY, sliding_window_size=window,
                  sliding_window_layout=[int(bool(window))] * 8)
    flops, _ = FAMILY.attention_kernel_cost(config, tokens=length)
    assert flops == 6 * 2 * keep.sum() * TINY["head_dim"] \
        * TINY["num_attention_heads"]


def test_required_flops_are_issue_46s():
    """492.5 MFLOP a token forward at 8192 = projections 167.8 + full
    attention 58.7 + three windowed 132.1 + routers 1.3 + experts 35.4 +
    head 97.2, times 3."""
    per = FAMILY.part_flops_per_item(CONFIG)
    assert per["projections"] == 2 * 20971520
    assert 4 * per["projections"] == pytest.approx(167.8e6, rel=1e-3)
    assert per["global_attention"] == pytest.approx(58.7e6, rel=1e-3)
    assert 3 * per["window_attention"] == pytest.approx(132.1e6, rel=1e-3)
    assert per["experts"] == pytest.approx(
        2 * 2560 * 64 + 6 * 8 / 64 * 6 * 2560 * 768)
    assert 4 * 2 * 2560 * 64 == pytest.approx(1.3e6, rel=0.01)
    assert 4 * (per["experts"] - 2 * 2560 * 64) == pytest.approx(35.4e6,
                                                                 rel=1e-3)
    assert per["head"] == 2 * 2560 * 18992
    assert per["head"] == pytest.approx(97.2e6, rel=1e-3)
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(3 * 492.5e6, rel=1e-3)
    attention = per["global_attention"] + 3 * per["window_attention"]
    assert 3 * attention / total == pytest.approx(0.387, abs=0.005)


def test_expert_costs_are_ready_for_the_cell_to_be_listed():
    assert FAMILY.expert_layers(CONFIG) == 4
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 6144.0)
    assert flops == pytest.approx(9 * 2 * 6144 * 2560 * 768)
    assert bytes_ == pytest.approx(
        9 * 2 * (6144 * 2560 + 6144 * 768 + 8 * 2560 * 768))


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__[-32:])
def test_a_parent_program_reports_nothing(reader, evidence, monkeypatch):
    """No such scope, no such kernel, or a family that prices no
    attention: None, not an error; None without a trace."""
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "moe_block"): 0.05,
                   ("forward", "global_attention"): 0.01,
                   ("unattributed", "(fusion)"): 0.04})])
    evidence["trace"]["device_ops"] = [["fusion", 0.1], ["gmm", 0.02]]
    assert reader.compute(evidence) is None
    hybrid = run.load_json("configs", "nemotron3-nano-30b-a3b")
    with_kernels = dict(evidence, config=hybrid, trace={
        "busy_s": 0.2, "device_ops": [["flash_fwd", 0.01]]})
    assert FLASH.compute(with_kernels) is None
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    evidence["trace"] = None
    assert reader.compute(evidence) is None


@pytest.mark.parametrize("reader,layer,better", [
    (ATTENTION, "windowed attention", "lower"),
    (FLASH, "kernels", "higher")], ids=["attention", "flash"])
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


def test_the_cell_reports_the_unlisted_metrics_and_its_two():
    """The 18 metrics that list no cells and the two new ones. The four
    expert metrics and the two block-diffusion ones are held to exactly
    their cells by tests this PR may not edit (test_mla_metrics.py,
    test_sdar_metrics.py): the cell is a third expert cell they cannot
    list until a `benchmark` PR loosens them (ROADMAP Reach 0k)."""
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 18
    assert set(CELL["per_layer"]) == set(unlisted) | {
        "window_attention_time_pct.train", "window_flash_roofline_pct.train"}
    assert not set(LISTED_ELSEWHERE) & set(CELL["per_layer"])
    for m in MANIFEST["per_layer"]:
        if m["name"] in LISTED_ELSEWHERE:
            assert CELL["name"] not in m["workloads"]
    # the new entries are the last of their lists
    assert MANIFEST["configs"][-1]["name"] == CONFIG["name"]
    assert MANIFEST["workloads"][-1]["name"] == CELL["name"]
    assert [m["name"] for m in MANIFEST["per_layer"][-2:]] == [
        "window_attention_time_pct.train", "window_flash_roofline_pct.train"]


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
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["moe_num_primary_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["moe_num_primary_experts"],
        published["vocab_size"])
    # the floors of a cut: one whole period of four layers (none is
    # dense), 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 4
    assert CONFIG["sliding_window_layout"][:4] == \
        CONFIG["rope_layout"][:4] == [0, 1, 1, 1]
    assert len(CONFIG["sliding_window_layout"]) == 52    # kept whole
    assert CONFIG["moe_num_primary_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["moe_num_primary_experts"] * 8 == \
        published["moe_num_primary_experts"]
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("router_input", "rotary_pairing", "window_counts_own_key",
                "attention_bias_and_qk_norm", "auxiliary_loss",
                "secondary_experts", "router_balance", "optimizer",
                "initialisation"):
        assert key in CONFIG["assumed"], key


def test_the_routers_balancing_rule_is_stated_as_assumed():
    rate = CONFIG["router_balance_rate"]
    assert 0 < rate <= 1
    said = CONFIG["assumed"]["router_balance"]
    assert "router_balance_rate" in said and "2408.15664" in said
    main, _, _ = FAMILY.build(CONFIG)
    rules = [op for op in main.global_block().ops
             if op.type == "moe_balance_bias"]
    assert len(rules) == CONFIG["num_hidden_layers"]
    assert all(op.attr("rate") == rate for op in rules)


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["sliding_window_size"]) == (1, 8192, 4096)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["warmup_steps"],
            CELL["trace_steps"]) == (4, 2, 2, 32, 17)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"))
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    assert (CONFIG["learning_rate"], CONFIG["adam_beta1"],
            CONFIG["adam_beta2"], CONFIG["adam_epsilon"]) == (
        1e-6, 0.9, 0.999, 1e-8)
    # ISSUE 46's start but for the embedding (`assumed.initialisation`
    # says what the layer without positions does to the routers at 0.02)
    assert CONFIG["embedding_std"] == 0.4
    assert "embedding_std" in CONFIG["assumed"]["initialisation"]
    assert "1e-6" in CONFIG["assumed"]["optimizer"]
    feed = FAMILY.make_batch(CONFIG, CELL["batch"],
                             np.random.default_rng(2 ** 31 + 7))
    assert feed["tok"].shape == feed["lab"].shape == (1, 8192)
    assert feed["tok"].dtype == np.int32
    assert 0 <= feed["tok"].min() and feed["tok"].max() < 18992
    np.testing.assert_array_equal(feed["tok"][:, 1:], feed["lab"][:, :-1])
    assert FAMILY.items_per_batch(feed) == 8192


def test_the_parameters_here_are_the_programs_own_count():
    """370.5M: four blocks of 68,326,400 and 2 x 48,619,520 of embedding
    and head and the final norm, from the program's parameters."""
    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    block = 20971520 + 2 * 2560 + 163840 + 8 * 5898240
    assert block == 68326400
    assert count == 4 * block + 2 * 48619520 + 2560 == 370547200
    assert "370.5M" in CONFIG["deployment"]["parameters_here"]
    assert "370,547,200" in CONFIG["deployment"]["parameters_here"]
    # the four attention ops: one full, three under the window
    windows = [op.desc.attrs.get("window", 0)
               for op in main.global_block().ops
               if op.type == "scaled_dot_product_attention"]
    assert windows == [0, 4096, 4096, 4096]
