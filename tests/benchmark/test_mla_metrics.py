"""The three readers the latent-attention cell brought (the layers
"latent attention" and "multi-token prediction", and the flash kernels'
share of their roofline at head size 256), on a hand-written reduction
of a trace by name scope and trace_reduce's `device_ops` rows; the
family's arithmetic they price by; and the configuration against the
catalog's row."""

import json
import os

import pytest

from benchmarks import rooflines, run

MLA = run.load_module("layer_metrics", "mla_time_pct.train")
MTP = run.load_module("layer_metrics", "mtp_time_pct.train")
FLASH = run.load_module("layer_metrics", "mla_flash_roofline_pct.train")
MOE = run.load_module("layer_metrics", "moe_time_pct.train")
GMM = run.load_module("layer_metrics", "expert_matmul_roofline_pct.train")
READERS = (MLA, MTP, FLASH)
CELL = run.load_json("workloads", "glm-4.7-flash.train-mla-mtp-ep8-share")
CONFIG = run.load_json("configs", CELL["config"])
FAMILY = run.load_module("families", CONFIG["family"])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
EXPERT_CELLS = ["nemotron3-nano.train-ep16-share", CELL["name"]]


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


# two steps by the name scope their ops were built under: the module's
# own latent attention and expert layer are nested in its scope
SCOPED = [step(0.200, {
    ("forward", "latent_attention"): 0.020,
    ("backward", "latent_attention"): 0.036,
    ("forward", "latent_attention.rotary_embedding"): 0.002,
    ("backward", "latent_attention.rotary_embedding"): 0.002,
    ("forward", "mtp_block.latent_attention"): 0.004,
    ("backward", "mtp_block.latent_attention.rotary_embedding"): 0.006,
    ("forward", "mtp_block"): 0.006,
    ("backward", "mtp_block.moe_block.gated_mlp"): 0.014,
    ("forward", "moe_block"): 0.030,
    ("forward", "gated_mlp"): 0.010,
    ("forward", "(fusion)"): 0.040,
    ("optimize", "(fusion)"): 0.030})] * 2


@pytest.fixture
def evidence(monkeypatch):
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    return {"cell": {"name": "x", "trace_steps": 2, "steps_in_flight": 2},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"},
            "items_per_step": 4096,
            "trace": {"busy_s": 0.4, "device_ops": [
                ["fusion", 0.200], ["flash_dkv", 0.032], ["flash_dq", 0.024],
                ["flash_fwd", 0.020], ["gmm", 0.012], ["tgmm", 0.008]]}}


def test_latent_attention_counts_every_block_the_modules_too(evidence):
    """Any scope with `latent_attention` among its parts: 60 + 10 of 200
    ms, the module's nested one among them."""
    assert MLA.compute(evidence) == pytest.approx(35.0)


def test_the_expert_share_counts_the_modules_layer_too(evidence):
    """`moe_block` and `mtp_block.moe_block.gated_mlp`: 30 + 14 of 200
    ms; the dense block's own `gated_mlp` is no expert layer."""
    assert MOE.compute(evidence) == pytest.approx(22.0)


def test_the_module_counts_everything_nested_in_it(evidence):
    assert MTP.compute(evidence) == pytest.approx(15.0)


def test_flash_kernels_against_the_roofline(evidence):
    """Six ops of six half-square products of 4096^2 x 256 x 20, bound
    by the MXU, over the 38 ms a step the three kernels took."""
    flops, bytes_ = FAMILY.attention_kernel_cost(CONFIG)
    assert flops == pytest.approx(6 * 4096 * 4096 * 256 * 20)
    assert bytes_ == pytest.approx(9 * 2 * 4096 * 20 * 256)
    assert flops / 197e12 > bytes_ / 819e9
    assert FAMILY.attention_ops_per_step(CONFIG) == 6
    least = 6 * flops / 197e12
    assert least == pytest.approx(15.7e-3, rel=5e-3)
    assert FLASH.compute(evidence) == pytest.approx(100 * least / 0.038)
    assert 0 < FLASH.compute(evidence) < 100
    # the cost follows the sequence it is asked about
    assert FAMILY.attention_kernel_cost(CONFIG, tokens=2048)[0] == \
        pytest.approx(flops / 4)


def test_gated_expert_products_cost_nine_products():
    """Gate, up and down forward and two backward products each, of
    rows x d x f; each reads two operands and writes one result in bf16,
    the held experts' weights once a product; linear in the rows beside
    the weights' bytes. By hand at the tiny sizes: d 64, f 48, 4 held."""
    tiny = run.load_json("configs", "tiny-glm-moe-lite", DATA)
    flops, bytes_ = FAMILY.expert_product_cost(tiny, 10)
    assert flops == 9 * 2 * 10 * 64 * 48 == 552960
    assert bytes_ == 9 * 2 * (10 * 64 + 10 * 48 + 4 * 64 * 48) == 241344
    more_flops, more_bytes = FAMILY.expert_product_cost(tiny, 30)
    assert more_flops == 3 * flops
    assert more_bytes - bytes_ == 9 * 2 * 20 * (64 + 48)
    # the cell's: 9 x 2 x rows x 2048 x 1536, the hybrid form's 6 x
    flops, _ = FAMILY.expert_product_cost(CONFIG, 8000.0)
    assert flops == pytest.approx(9 * 2 * 8000 * 2048 * 1536)


@pytest.mark.parametrize("config,layers", [
    (CONFIG, 5),
    (run.load_json("configs", "tiny-glm-moe-lite", DATA), 3),
    ({"num_hidden_layers": 47, "first_k_dense_replace": 1,
      "num_nextn_predict_layers": 1}, 47)],
    ids=["the-cell", "tiny", "published"])
def test_expert_layers_are_the_blocks_behind_the_dense_one_and_the_modules(
        config, layers):
    assert FAMILY.expert_layers(config) == layers


def test_expert_products_against_the_roofline(evidence, monkeypatch):
    """Five layers of nine products at the rows the traced steps routed
    (the last trace_steps - steps_in_flight = 2 publications of 4), over
    the 19.4 ms a step `gmm` and `tgmm` took."""
    from paddle_tpu import telemetry
    log = [{"kind": "side_fetch", "metric": "moe_rows_routed",
            "values": [rows] * 5} for rows in (2000, 4000, 7000, 9000)]
    monkeypatch.setattr(telemetry, "recent_events",
                        lambda n=None, kind=None: log)
    evidence["cell"]["trace_steps"] = 4
    evidence["trace"]["device_ops"] = [["fusion", 0.2], ["gmm", 0.048],
                                       ["tgmm", 0.0296]]
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 8000.0)
    assert flops / 197e12 > bytes_ / 819e9      # the MXU bounds it here
    least = 5 * flops / 197e12
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.0194)
    assert 0 < GMM.compute(evidence) < 100
    # no rows published (a parent program): nothing to read
    monkeypatch.setattr(telemetry, "recent_events",
                        lambda n=None, kind=None: [])
    assert GMM.compute(evidence) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__[-28:])
def test_a_parent_program_reports_nothing(reader, evidence, monkeypatch):
    """No such scope, no such kernel, or a family without the cost
    function (the parent's): None, not an error; None without a trace."""
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


@pytest.mark.parametrize("reader,layer", [
    (MLA, "latent attention"), (MTP, "multi-token prediction"),
    (FLASH, "kernels")], ids=["mla", "mtp", "flash"])
def test_the_manifest_lists_the_readers_for_the_new_cell_alone(reader, layer):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    name = os.path.basename(reader.__file__)[:-3]
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL["name"]]
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        layer, "device_trace", "train_items_per_s")
    assert name in CELL["per_layer"]
    # the expert metrics are both expert cells' since PR 37
    for shared in ("moe_time_pct.train", "moe_load_max_over_mean.train",
                   "expert_matmul_roofline_pct.train",
                   "moe_rows_handled_over_routed.train"):
        both, = [m for m in manifest["per_layer"] if m["name"] == shared]
        assert both["workloads"] == EXPERT_CELLS
        assert shared in CELL["per_layer"]


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
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["n_routed_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["n_routed_experts"],
        published["vocab_size"])
    # the floors of a cut: four blocks behind the dense one, 8 routed
    # experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert all(CONFIG["assumed"].values())


def test_the_cell_is_the_issues():
    assert (CELL["batch"], CONFIG["sequence_length"]) == (1, 4096)
    assert (CELL["pool_batches"], CELL["feeder_capacity"],
            CELL["steps_in_flight"], CELL["trace_steps"]) == (4, 2, 2, 17)
    assert CELL["warmup_steps"] in (16, 32, 64)
    assert CELL["end_to_end"] == ["train_items_per_s", "setup_s"]
    assert all(CELL["reference"][k] is not None
               for k in ("loss_rtol", "grad_rtol", "grad_norm_rtol",
                         "grad_tail_rtol", "update_rtol"))
