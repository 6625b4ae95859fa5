"""The five readers of the state-space, experts and kernels layers that
the hybrid cell brought, on a hand-written reduction of a trace
(program_trace's `device_steps`, by program op and by name scope),
trace_reduce's `device_ops` rows, a hand-written set of counters and a
hand-written step log; and the family's arithmetic they price by."""

import os

import pytest

from benchmarks import program_trace, rooflines, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

SSM = run.load_module("layer_metrics", "ssm_time_pct.train")
MOE = run.load_module("layer_metrics", "moe_time_pct.train")
LOAD = run.load_module("layer_metrics", "moe_load_max_over_mean.train")
GMM = run.load_module("layer_metrics", "expert_matmul_roofline_pct.train")
SCAN = run.load_module("layer_metrics", "ssd_scan_roofline_pct.train")
READERS = (SSM, MOE, LOAD, GMM, SCAN)
CONFIG = run.load_json("configs", "nemotron3-nano-30b-a3b")
FAMILY = run.load_module("families", CONFIG["family"])


def step(busy_s, by_op):
    return {"device": "/device:TPU:0", "window_s": busy_s, "busy_s": busy_s,
            "by_role": {}, "by_op": by_op}


STEPS = [step(0.100, {("forward", "mul"): 0.030,
                      ("forward", "ssd_scan"): 0.004,
                      ("backward", "ssd_scan_grad"): 0.012,
                      ("forward", "causal_conv1d"): 0.001,
                      ("backward", "causal_conv1d_grad"): 0.003,
                      ("forward", "moe_router"): 0.001,
                      ("forward", "moe_experts"): 0.005,
                      ("backward", "moe_experts_grad"): 0.012,
                      ("forward", "relu2"): 0.001,
                      ("backward", "relu2_grad"): 0.001,
                      ("optimize", "fused_adam"): 0.030})] * 2

# the same two steps by the name scope their ops were built under: the
# mixer's and the shared expert's `mul`s are their layers' here
SCOPED = [step(0.100, {("forward", "mamba2_mixer"): 0.012,
                       ("backward", "mamba2_mixer"): 0.028,
                       ("forward", "moe_block"): 0.010,
                       ("backward", "moe_block.shared"): 0.015,
                       ("forward", "(fusion)"): 0.005,
                       ("optimize", "(fusion)"): 0.030})] * 2

COUNTERS = {
    "moe_load_max_over_mean": {
        "layer=0,program=p1": {"sum": 1.2 * 50, "count": 50},
        "layer=1,program=p1": {"sum": 1.4 * 50, "count": 50}}}


@pytest.fixture
def evidence(monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(program_trace, "of_evidence",
                        lambda ev: {"device_steps": STEPS})
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: SCOPED)
    # the router drifts: 7 steps published, the last 4 - 2 of them traced
    log = [{"kind": "side_fetch", "metric": metric, "values": values}
           for rows in (900, 1000, 1100, 1200, 1300, 1436, 1536)
           for metric, values in (("moe_rows_routed", [rows, rows + 100.0]),
                                  ("moe_load_max_over_mean", [1.2, 1.4]))]
    monkeypatch.setattr(
        telemetry, "recent_events",
        lambda n=None, kind=None: [e for e in log if e["kind"] == kind])
    return {"cell": {"name": "x", "trace_steps": 4, "steps_in_flight": 2},
            "config": CONFIG,
            "device": {"kind": "TPU v5 lite"}, "items_per_step": 4096,
            "counters": COUNTERS,
            "trace": {"busy_s": 0.2, "device_ops": [
                ["fusion", 0.120], ["gmm", 0.024], ["tgmm", 0.016],
                ["flash_fwd", 0.004]]}}


def test_time_shares_by_name_scope(evidence):
    """Everything a layer built, nested scopes too, its `mul`s among it."""
    assert SSM.compute(evidence) == pytest.approx(40.0)
    assert MOE.compute(evidence) == pytest.approx(25.0)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(fn)/pd_role.backward/pd_scope.moe_block/pd.mul_grad/dot_general",
     "moe_block"),
    ("jit(fn)/pd_role.forward/pd_scope.block.mamba2_mixer/pd.ssd_scan/exp",
     "block.mamba2_mixer"),
    ("jit(fn)/pd_role.forward/pd.mul/dot_general", None), (None, None)])
def test_scope_of_an_op_name(op_name, scope):
    assert rooflines.scope_of(op_name) == scope
    if op_name:    # and the scope hides neither the role nor the op's type
        assert program_trace.provenance_of(op_name)[1] == \
            op_name.split("/pd.")[1].split("/")[0]


def test_load_is_the_mean_over_steps_and_layers(evidence):
    assert LOAD.compute(evidence) == pytest.approx(1.3)


def test_rows_are_those_of_the_traced_steps(evidence):
    """Not the window's mean: the last trace_steps - steps_in_flight
    publications, over both layers."""
    assert rooflines.traced_rows_routed(evidence) == pytest.approx(1536.0)


def test_expert_products_against_the_roofline(evidence):
    flops, bytes_ = FAMILY.expert_product_cost(CONFIG, 1536.0)
    assert flops == pytest.approx(6 * 2 * 1536 * 2688 * 1856)
    assert bytes_ == pytest.approx(
        6 * 2 * (1536 * 2688 + 1536 * 1856 + 8 * 2688 * 1856))
    # bound by the held experts' weights: bytes over 819 GB/s, 4 layers,
    # over the 10 ms a step the two kernels took
    least = 4 * max(flops / 197e12, bytes_ / 819e9)
    assert bytes_ / 819e9 > flops / 197e12
    assert GMM.compute(evidence) == pytest.approx(100 * least / 0.010)


@pytest.mark.parametrize("config,experts,scans", [
    (CONFIG, 4, 4),
    (run.load_json("configs", "tiny-nemotron-h", DATA), 2, 2),
    ({"hybrid_override_pattern":
      "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}, 23, 23)],
    ids=["the-cell", "tiny", "published"])
def test_layer_counts_are_the_patterns(config, experts, scans):
    assert FAMILY.expert_layers(config) == experts
    assert FAMILY.scan_layers(config) == scans


def test_scan_against_the_roofline(evidence):
    flops, bytes_ = FAMILY.scan_cost(CONFIG, 4096)
    least = 4 * max(flops / 197e12, bytes_ / 819e9)
    assert SCAN.compute(evidence) == pytest.approx(100 * least / 0.016)
    assert 0 < SCAN.compute(evidence) < 100


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__[-24:])
def test_a_parent_program_reports_nothing(reader, evidence, monkeypatch):
    """No such op in the trace and no such counter: None, not an error;
    and None without a trace at all."""
    from paddle_tpu import telemetry
    bare = [step(0.1, {("unattributed", "mul"): 0.1})]
    monkeypatch.setattr(program_trace, "of_evidence",
                        lambda ev: {"device_steps": bare})
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: [
        step(0.1, {("unattributed", "(fusion)"): 0.1})])
    monkeypatch.setattr(telemetry, "recent_events",
                        lambda n=None, kind=None: [])
    evidence["counters"] = {}
    evidence["trace"]["device_ops"] = [["fusion", 0.1]]
    assert reader.compute(evidence) is None
    monkeypatch.setattr(program_trace, "of_evidence", lambda ev: None)
    monkeypatch.setattr(rooflines, "scoped_steps", lambda ev: None)
    evidence["trace"] = None
    assert reader.compute(evidence) is None


def test_required_flops_are_the_issues_arithmetic():
    """2.05 GFLOP a token and step: Mamba 47 %, experts 28 %, attention
    12 %, head 13 % (ISSUE 30, section 4)."""
    per = FAMILY.layer_flops_per_item(CONFIG)
    assert per["M"] == pytest.approx(80.2e6, rel=0.01)
    assert per["E"] == pytest.approx(48.1e6, rel=0.01)
    assert per["*"] == pytest.approx(80.3e6, rel=0.01)
    assert per["head"] == pytest.approx(88.1e6, rel=0.01)
    total = FAMILY.required_flops_per_item(CONFIG)
    assert total == pytest.approx(2.05e9, rel=0.01)
    shares = [3 * 4 * per["M"] / total, 3 * 4 * per["E"] / total,
              3 * per["*"] / total, 3 * per["head"] / total]
    assert shares == pytest.approx([0.47, 0.28, 0.12, 0.13], abs=0.01)


def test_the_configuration_is_the_published_one_cut_as_stated():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published, = [r["config"] for r in rows
                  if r["source_url"] == CONFIG["source"]]
    differs = {k for k, v in published.items()
               if isinstance(v, (int, float)) and CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert published["hybrid_override_pattern"].startswith(
        CONFIG["hybrid_override_pattern"])
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["n_routed_experts_published"],
            CONFIG["vocab_size_published"]) == (
        published["num_hidden_layers"], published["n_routed_experts"],
        published["vocab_size"])
