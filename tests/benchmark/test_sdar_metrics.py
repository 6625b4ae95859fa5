"""The two readers the block-diffusion cell brought (the layer
"block-diffusion attention" and the flash kernels' share of their roofline
at the mask's live pairs), on a hand-written reduction of a trace by name
scope and trace_reduce's `device_ops` rows; the family's arithmetic they
price by, against an explicit mask; the configuration against the
catalog's row; the cell against ISSUE 42's parameters."""

import json
import os

import numpy as np
import pytest

from benchmarks import rooflines, run

ATTENTION = run.load_module("layer_metrics", "bd_attention_time_pct.train")
FLASH = run.load_module("layer_metrics", "bd_flash_roofline_pct.train")
READERS = (ATTENTION, FLASH)
CELL = run.load_json("workloads", "sdar-30b-a3b.train-bd4-t4096-ep16-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = run.load_json("configs", "tiny-sdar-moe", DATA)
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
EXPERT_METRICS = ("moe_time_pct.train", "moe_load_max_over_mean.train",
                  "expert_matmul_roofline_pct.train",
                  "moe_rows_handled_over_routed.train")


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: QK-norm is
# built under the model's scope, the rotation and the attention op under
# their own layers' scopes nested in it
SCOPED = [step(0.250, {
    ("forward", "block_diffusion_attention"): 0.010,
    ("backward", "block_diffusion_attention"): 0.015,
    ("forward", "block_diffusion_attention.rotary_embedding"): 0.005,
    ("backward", "block_diffusion_attention.rotary_embedding"): 0.005,
    ("forward", "block_diffusion_attention.block_diffusion_attention"): 0.030,
    ("backward",
     "block_diffusion_attention.block_diffusion_attention"): 0.060,
    ("forward", "moe_block"): 0.040,
    ("forward", "(fusion)"): 0.055,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 4096,
            "trace": {"busy_s": 0.5, "device_ops": [
                ["fusion", 0.300], ["flash_dkv", 0.060], ["flash_dq", 0.048],
                ["flash_fwd", 0.036], ["gmm", 0.012], ["tgmm", 0.008]]}}


def test_the_attention_share_counts_every_scope_nested_in_the_layer(evidence):
    """The norm's 25, the rotation's 10 and the op's 90 of 250 ms."""
    assert ATTENTION.compute(evidence) == pytest.approx(50.0)


def test_flash_kernels_against_the_roofline_at_the_live_pairs(evidence):
    """Six ops of six products of L (L + 4) pairs x 128 x 32, bound by
    the MXU, over the 72 ms a step the three kernels took."""
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(6 * 2 * 4096 * 4100 * 128 * 32)
    assert bytes_ == pytest.approx(2 * 8192 * 128 * (5 * 32 + 4 * 4))
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 6
    least = 6 * flops / 197e12
    assert least == pytest.approx(25.1e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.072)
    assert 0 < FLASH.compute(evidence) < 100
    # the square of both streams would be four times the work
    assert 4 * 4096 ** 2 / FAMILY.live_pairs(4096, 4) == \
        pytest.approx(4.0, rel=2e-3)


@pytest.mark.parametrize("length,block", [(32, 4), (64, 16), (48, 1)])
def test_the_cost_is_the_live_pairs_of_an_explicit_mask(length, block):
    """[2L, 2L] over rows and columns [noisy ; clean], from block ids."""
    bid = np.arange(length) // block
    keep = np.zeros((2 * length, 2 * length), bool)
    keep[:length, :length] = bid[:, None] == bid[None, :]
    keep[:length, length:] = bid[:, None] > bid[None, :]
    keep[length:, length:] = bid[:, None] >= bid[None, :]
    assert FAMILY.live_pairs(length, block) == keep.sum()
    config = dict(TINY, block_length=block)
    flops, _ = FAMILY.attention_kernel_cost(config, tokens=length)
    assert flops == 6 * 2 * keep.sum() * TINY["head_dim"] \
        * TINY["num_attention_heads"]


def test_required_flops_are_issue_42s():
    """About 2.99 GFLOP a data token, attention 40 % of it; the head on
    the noisy position alone, everything else on two."""
    per = FAMILY.part_flops_per_item(CONFIG)
    assert per["projections"] == 2 * 2 * 18874368
    assert per["attention"] == 4 * 4100 * 32 * 128
    assert per["experts"] == pytest.approx(
        2 * (2 * 2048 * 128 + 8 * 8 / 128 * 6 * 2048 * 768))
    assert per["head"] == 2 * 2048 * 18992
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(2.99e9, rel=5e-3)
    assert 3 * 6 * per["attention"] / total == pytest.approx(0.40, abs=0.01)


def test_expert_costs_are_ready_for_the_cell_to_be_listed():
    assert FAMILY.expert_layers(CONFIG) == 6
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 4096.0)
    assert flops == pytest.approx(9 * 2 * 4096 * 2048 * 768)
    assert bytes_ == pytest.approx(
        9 * 2 * (4096 * 2048 + 4096 * 768 + 8 * 2048 * 768))


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__[-28:])
def test_a_parent_program_reports_nothing(reader, evidence, monkeypatch):
    """No such scope, no such kernel, or a family that prices no
    attention: None, not an error; None without a trace."""
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("forward", "moe_block"): 0.05,
                   ("unattributed", "(fusion)"): 0.05})])
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
    (ATTENTION, "block-diffusion attention", "lower"),
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


def test_the_cell_reports_the_unlisted_metrics_and_its_two():
    """The 18 metrics that list no cells (ISSUE 42 counted 19) and the
    two new ones; the four
    expert metrics list exactly the two older expert cells until a
    `benchmark` PR loosens tests/benchmark/test_mla_metrics.py (ROADMAP
    Reach 0)."""
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 18
    assert set(CELL["per_layer"]) == set(unlisted) | {
        "bd_attention_time_pct.train", "bd_flash_roofline_pct.train"}
    assert not set(EXPERT_METRICS) & set(CELL["per_layer"])


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
    # the floors of a cut: four layers (the period is one layer, none is
    # dense), 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] >= 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    assert CONFIG["num_experts"] * 16 == published["num_experts"]
    assert all(CONFIG["assumed"].values())
    assert all(CONFIG["deployment"].values())
    for key in ("block_length", "noise_schedule", "two_stream_training",
                "qk_norm", "mask_token_id"):
        assert key in CONFIG["assumed"]


def test_the_routers_balancing_rule_is_stated_as_assumed():
    """The config gives no balancing rule: the rate the family hands the
    builder is a key of the file, said under `assumed` with its rule and
    why the cut needs one, and every block's router gets it."""
    rate = CONFIG["router_balance_rate"]
    assert 0 < rate <= 1
    said = CONFIG["assumed"]["router_balance"]
    assert "router_balance_rate" in said and "2408.15664" in said
    assert "router_balance" in CONFIG["assumed"]["auxiliary_loss"]
    main, _, _ = FAMILY.build(CONFIG)
    rules = [op for op in main.global_block().ops
             if op.type == "moe_balance_bias"]
    assert len(rules) == CONFIG["num_hidden_layers"]
    assert all(op.attr("rate") == rate for op in rules)


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"],
            CONFIG["block_length"], CONFIG["mask_epsilon"]) == (
        1, 4096, 4, 0.001)
    assert (CELL["traffic"], CELL["chips"]) == ("train_steps", 1)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["trace_steps"]) == (4, 2, 2, 17)
    assert CELL["warmup_steps"] in (16, 32, 64)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"))
    assert (CONFIG["amp_level"], CONFIG["optimizer"], CONFIG["use_flash"],
            CONFIG["item"]) == ("O2", "adam", "auto", "token")
    # ISSUE 42's Adam but for the rate: 1e-6, a warm-up's rate at the
    # benchmark's steps (`assumed.optimizer` says why 1e-4 runs away)
    assert (CONFIG["learning_rate"], CONFIG["adam_beta1"],
            CONFIG["adam_beta2"], CONFIG["adam_epsilon"]) == (
        1e-6, 0.9, 0.999, 1e-8)
    assert "1e-6" in CONFIG["assumed"]["optimizer"]
    assert "embedding_std" in CONFIG["assumed"]["initialisation"]
    assert CONFIG["embedding_std"] == 0.4


def test_the_parameters_here_are_the_programs_own_count():
    """419.1M: six blocks of 56,889,600 and 2 x 38,895,616 of embedding
    and head and the final norm, from the program's parameters."""
    main, _, _ = FAMILY.build(CONFIG)
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters() if p.trainable)
    block = 18874368 + 2 * 2048 + 2 * 128 + 262144 + 8 * 4718592
    assert block == 56889600
    assert count == 6 * block + 2 * 38895616 + 2048 == 419130880
    assert "419.1M" in CONFIG["deployment"]["parameters_here"]
