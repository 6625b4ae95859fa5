"""A value crosses the model axis once (tensor_parallel.gather_once,
ISSUE 52): what a column-parallel product reads is constrained whole
along its features after the cast, and a row-parallel product's output
cotangent where it enters the gradient. On four of the harness's virtual
devices: what the rule counts, what the step computes, what GSPMD then
gathers, and where the rule stays out."""

import re

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import telemetry
from paddle_tpu.ops import sibling_products
from paddle_tpu.parallel import tensor_parallel
from test_sibling_products import (B, D, LAYERS, MODELS, T, _lowered, _merged,
                                   _mesh, _step)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four (virtual) devices")

# products a layer whose operand (column-parallel) and whose cotangent
# (row-parallel) the rule constrains; the attention model's head has 101
# columns, which no axis divides: the planner leaves it whole
PINNED = {
    "attention": {"operand": 4, "cotangent": 2},    # q, k, v, up | out, down
    "gated_ffn": {"operand": 2, "cotangent": 1},    # gate, up | down
}


def _pinned():
    """{side: count} of tp_gather_pinned_total over every program."""
    out = {"operand": 0, "cotangent": 0}
    for series, v in telemetry.read_series("tp_gather_pinned_total").items():
        out[re.search(r"side=(\w+)", series).group(1)] += v
    return out


def _moved(before):
    return {side: n - before[side] for side, n in _pinned().items()}


def _whole_gathers(account, rows):
    """(dtype, channel) of every tp all-gather of the step that writes a
    whole [B * T / fsdp, D] activation: one a collective, however many
    instructions hold a piece of it."""
    whole = re.compile(r"^(\w+)\[(%d,%d|%d,%d,%d)\]$"
                       % (rows, D, rows // T, T, D))
    return {(m.group(1), i.channel if i.channel is not None else i.name)
            for i in account if i.kind == "all-gather" and i.axis == "tp"
            for m in [whole.match(i.moved)] if m}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_rule_counts_the_products_it_constrained(model):
    """On fsdp x tp one trace books 4 operands and 2 cotangents a layer
    (2 and 1 with a gated FFN), the gradient ops' re-trace books nothing,
    and the sibling merge still reads one a layer."""
    build, lay, *_ = MODELS[model]
    before, merged = _pinned(), _merged()
    _step(build, lay, _mesh((2, 2), ("fsdp", "tp")))
    assert _moved(before) == {s: n * LAYERS for s, n in PINNED[model].items()}
    assert _merged() - merged == LAYERS


def test_gspmd_gathers_each_value_once(monkeypatch):
    """The attention model's compiled step gathers a whole activation
    over tp 2 + 2 times a layer (one a value a column-parallel product
    reads, one a cotangent a row-parallel product's gradient reads) and
    once for the head's input gradient, where the step traced with the
    rule emptied gathers one a consumer (11.5 a layer here). (The gated
    FFN's stream GSPMD leaves whole: nothing to gather either way.)"""
    build, lay, *_ = MODELS["attention"]
    _, _, account = _step(build, lay, _mesh((2, 2), ("fsdp", "tp")))
    with_rule = _whole_gathers(account, B * T // 2)
    monkeypatch.setattr(tensor_parallel, "GATHER_ONCE_SIDES", frozenset())
    before = _pinned()
    _, _, account = _step(build, lay, _mesh((2, 2), ("fsdp", "tp")))
    assert _moved(before) == {"operand": 0, "cotangent": 0}
    assert len(with_rule) == 4 * LAYERS + 1
    assert len(_whole_gathers(account, B * T // 2)) > 2 * len(with_rule)


@pytest.mark.parametrize("amp", [None, "O2"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_pinned_step_computes_what_one_device_does(model, amp, monkeypatch):
    """Loss and every parameter's gradient after one step, this rule
    alone (the sibling merge emptied): the planned program against the
    same program on one device, in float32 element by element to the
    planned-step tests' tolerance (tests/test_planner.py), under AMP O2
    with the gradient held as one vector."""
    monkeypatch.setattr(sibling_products, "SIBLING_OPS", frozenset())
    build, lay, *_ = MODELS[model]
    want_loss, want, _ = _step(build, lay, None, amp, fetch_grads=True)
    before = _pinned()
    got_loss, got, _ = _step(build, lay, _mesh((2, 2), ("fsdp", "tp")), amp,
                             fetch_grads=True)
    assert _moved(before) == {s: n * LAYERS for s, n in PINNED[model].items()}
    np.testing.assert_allclose(got_loss, want_loss,
                               rtol=2e-3 if amp else 2e-4)
    assert sorted(got) == sorted(want) and got
    if amp:
        flat = np.concatenate([np.ravel(got[n] - g) for n, g in want.items()])
        ref = np.concatenate([np.ravel(g) for g in want.values()])
        assert np.linalg.norm(flat) < 0.02 * np.linalg.norm(ref)
        return
    for name, g in want.items():
        np.testing.assert_allclose(got[name], g, rtol=2e-3, atol=2e-4,
                                   err_msg=name)


@pytest.mark.parametrize("mesh", ["none", "fsdp_alone", "tp_of_one"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_rule_stays_out_where_no_model_axis_splits_the_weights(
        model, mesh, monkeypatch):
    """No mesh, fsdp alone, a tp axis of size 1: nothing is counted and
    the lowered step is, text for text, the one traced with the rule's
    sides emptied."""
    build, lay, *_ = MODELS[model]
    on = {"none": lambda: None,
          "fsdp_alone": lambda: _mesh((2,), ("fsdp",)),
          "tp_of_one": lambda: _mesh((2, 1), ("fsdp", "tp"))}[mesh]
    before = _pinned()
    with_rule = _lowered(build, lay, on())
    assert _pinned() == before
    monkeypatch.setattr(tensor_parallel, "GATHER_ONCE_SIDES", frozenset())
    assert _lowered(build, lay, on()) == with_rule


@pytest.mark.parametrize("side", ["operand", "cotangent"])
def test_one_side_alone_changes_the_planned_step(side, monkeypatch):
    """The control of the test above: on fsdp x tp either side alone
    gives another text than the rule emptied, and counts only itself."""
    build, lay, *_ = MODELS["attention"]
    mesh = lambda: _mesh((2, 2), ("fsdp", "tp"))      # noqa: E731
    monkeypatch.setattr(tensor_parallel, "GATHER_ONCE_SIDES",
                        frozenset({side}))
    before = _pinned()
    with_side = _lowered(build, lay, mesh())
    moved = _moved(before)
    assert moved.pop(side) == PINNED["attention"][side] * LAYERS
    assert set(moved.values()) == {0}
    monkeypatch.setattr(tensor_parallel, "GATHER_ONCE_SIDES", frozenset())
    assert _lowered(build, lay, mesh()) != with_side


def test_gate_reads_the_weights_spec_and_the_mesh():
    """`gather_once` by hand: the model axis on the weight's output
    dimension gives the operand's side, on its contraction dimension the
    cotangent's, with the rows over the batch axes and no axis on the
    features; a weight with no spec, a spec of another rank, a batch
    its axes do not divide or a mesh without the axis give nothing."""
    from jax.sharding import PartitionSpec

    program = fluid.Program()
    program._mesh = _mesh((2, 2), ("fsdp", "tp"))
    for name, spec in {"col": ("fsdp", "tp"), "row": ("tp", "fsdp"),
                       "zero": ("fsdp", None), "vec": ("tp",),
                       "both": ("tp", ("fsdp", "tp"))}.items():
        tensor_parallel.shard_parameter(program, name, spec)
    sides, whole = tensor_parallel.gather_once(program, "col", 8)
    assert sides == {"operand"}
    assert whole(2).spec == PartitionSpec("fsdp", None)
    assert whole(3).spec == PartitionSpec("fsdp", None, None)
    assert whole(3).mesh == program._mesh
    assert tensor_parallel.gather_once(program, "row", 8)[0] == {"cotangent"}
    assert tensor_parallel.gather_once(program, "both", 8)[0] == \
        {"operand", "cotangent"}
    for name, batch in [("zero", 8), ("vec", 8), ("absent", 8), ("col", 7)]:
        assert tensor_parallel.gather_once(program, name, batch) == \
            (frozenset(), None), name
    program._mesh = _mesh((2,), ("fsdp",))
    assert tensor_parallel.gather_once(program, "col", 8) == \
        (frozenset(), None)
    program._mesh = _mesh((2,), ("tp",))
    sides, whole = tensor_parallel.gather_once(program, "col", 7)
    assert sides == {"operand"} and whole(2).spec == PartitionSpec(None, None)
