"""ops/kernel_choice.py: the one place where a lowering's choice between
its Pallas kernel and the XLA path is booked (PR 45). The table of
reasons against each gate's source (tools/check_registry.py
check_pallas_table), `book` against the table, and the one "a gradient's
re-trace of a forward books nothing" state under every counter of
forward lowerings that asks it."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import telemetry
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import kernel_choice

from test_nemotron_h import SCAN_ORDER, routed, scan_inputs
from test_registry_lint import _load_checker

OPS = ("conv2d", "scaled_dot_product_attention", "block_diffusion_attention",
       "ssd_scan", "kda_scan", "causal_conv1d", "moe_experts", "pair_sum")


def test_the_table_holds_the_seven_ops_with_a_kernel():
    """Seven registered ops (`kda_scan` since PR 56, `causal_conv1d` since
    PR 60), and `pair_sum`:
    moe_experts' second choice, a kernel booked under its own name WITHIN
    that op (PR 47)."""
    assert tuple(kernel_choice.REASONS) == OPS == tuple(kernel_choice.GATES)
    assert kernel_choice.WITHIN == {"pair_sum": "moe_experts"}


def test_the_lint_catches_a_kernel_within_no_registered_op(monkeypatch):
    assert not _problems("pair_sum")
    monkeypatch.setitem(kernel_choice.WITHIN, "pair_sum", "moe_expert")
    assert any("not registered" in m for _, m in _problems("pair_sum"))


# --- the lint ---------------------------------------------------------------

def _problems(op):
    return [(w, m) for w, m in _load_checker().check_pallas_table()
            if f"REASONS['{op}']" in w]


@pytest.mark.parametrize("op", OPS)
def test_declared_reasons_are_what_the_gates_source_returns(op):
    """Both ways, for each op of the table: every `return "<reason>"` of
    the op's gate is declared and every declared reason is returned."""
    assert kernel_choice.REASONS[op]
    assert not _problems(op), _problems(op)


@pytest.mark.parametrize("op", OPS)
def test_the_lint_catches_a_reason_the_table_lacks_or_no_gate_gives(
        op, monkeypatch):
    declared = kernel_choice.REASONS[op]
    lacking = sorted(declared)[0]
    monkeypatch.setitem(kernel_choice.REASONS, op, declared - {lacking})
    assert any(f"'{lacking}'" in m and "not declared" in m
               for _, m in _problems(op)), _problems(op)
    monkeypatch.setitem(kernel_choice.REASONS, op,
                        declared | {"phase_of_moon"})
    assert any("'phase_of_moon'" in m and "never produced" in m
               for _, m in _problems(op)), _problems(op)


def test_the_lint_catches_an_op_without_a_gate(monkeypatch):
    monkeypatch.setitem(kernel_choice.REASONS, "relu", frozenset({"mood"}))
    assert any("GATES" in m for _, m in _problems("relu")), _problems("relu")


def test_the_lint_catches_a_second_creator_of_the_counters(monkeypatch):
    checker = _load_checker()
    sources = checker._package_sources()
    assert len(sources) > 100
    assert "paddle_tpu/ops/kernel_choice.py" in sources
    planted = dict(sources)
    planted["paddle_tpu/ops/new_kernel.py"] = (
        'telemetry.counter(\n    "pallas_kernel_total", "mine",\n'
        '    labels=("op",)).labels(op="new").inc()\n')
    monkeypatch.setattr(checker, "_package_sources", lambda: planted)
    problems = checker.check_pallas_table()
    assert [w for w, m in problems] == ["paddle_tpu/ops/new_kernel.py"]
    assert "pallas_kernel_total" in problems[0][1]


# --- book -------------------------------------------------------------------

def _series():
    return {name: dict(telemetry.read_series(name)) for name in (
        "pallas_kernel_total", "pallas_fallback_total", "quant_kernel_total",
        "quant_fallback_total", "activation_kept_total")}


def _grown(before):
    """{series: {labels: growth}} since `before`, the still ones left out."""
    after = _series()
    return {name: {k: v - before[name].get(k, 0) for k, v in rows.items()
                   if v != before[name].get(k, 0)}
            for name, rows in after.items()
            if rows != before[name]}


def test_book_takes_a_hit_or_a_declared_reason_and_refuses_the_rest():
    before = _series()
    kernel_choice.book("ssd_scan", None)
    kernel_choice.book("ssd_scan", "chunk")
    with kernel_choice.retrace():
        kernel_choice.book("ssd_scan", None)
        kernel_choice.book("ssd_scan", "state")
        with pytest.raises(ValueError, match="rows"):
            kernel_choice.book("ssd_scan", "rows")      # moe_experts' own
    assert not kernel_choice.in_retrace()
    with pytest.raises(ValueError, match="not declared"):
        kernel_choice.book("moe_experts", "chunk")
    with pytest.raises(KeyError):
        kernel_choice.book("relu", None)
    assert _grown(before) == {
        "pallas_kernel_total": {"op=ssd_scan": 1},
        "pallas_fallback_total": {"op=ssd_scan,reason=chunk": 1}}


# --- a gradient's re-trace books nothing ------------------------------------

def _one_op(op_type, inputs, outputs, attrs, wrt, backward, amp=None):
    """Run a program of one `op_type` op over data vars and a summed
    loss, with the gradient ops to `wrt` or without them."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        helper = LayerHelper(op_type)
        vars_ = {}
        for slot, value in inputs.items():
            var = fluid.layers.data(
                name=slot.lower(), shape=list(value.shape),
                dtype=str(value.dtype), append_batch_size=False)
            var.stop_gradient = var.desc.stop_gradient = slot not in wrt
            vars_[slot] = var
        outs = {slot: helper.create_tmp_variable(dtype)
                for slot, dtype in outputs.items()}
        helper.append_op(type=op_type,
                         inputs={s: [v] for s, v in vars_.items()},
                         outputs={s: [v] for s, v in outs.items()},
                         attrs=attrs)
        loss = fluid.layers.reduce_sum(
            fluid.layers.cast(next(iter(outs.values())), "float32"))
        if backward:
            fluid.backward.append_backward(loss)
    if amp:
        fluid.amp.enable(main, level=amp)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        out, = exe.run(main, feed={s.lower(): v for s, v in inputs.items()},
                       fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()
    return {op.type for op in main.global_block().ops}


def _scan():
    return dict(op_type="ssd_scan",
                inputs=scan_inputs(np.random.default_rng(11), 1, 128, 2, 1),
                outputs={"Out": "float32"}, attrs={"chunk_size": 128},
                wrt=SCAN_ORDER)


def _delta_rule(width, value):
    """kda_scan at heads of `width` and `value` channels: a lane block
    each is the kernels', anything else XLA's chunked form."""
    from test_kda_moe import SLOTS, scan_inputs as kda_inputs
    return lambda: dict(
        op_type="kda_scan",
        inputs=kda_inputs(False, bsz=1, seqlen=64, heads=2, width=width,
                          value=value),
        outputs={"Out": "float32"},
        attrs={"chunk_size": 32, "epsilon": 1e-6}, wrt=SLOTS)


def _experts():
    rng = np.random.default_rng(3)
    n, d, f, k, held = 64, 128, 128, 4, 4
    idx, weight = routed(rng, n, k, 16, favour=[5, 6])
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return dict(
        op_type="moe_experts",
        inputs={"X": f32(n, d) * 0.5, "TopkIdx": idx, "TopkWeight": weight,
                "W1": f32(held, d, f) * 0.2, "W2": f32(held, f, d) * 0.2},
        outputs={"Out": "float32", "RowsRouted": "float32",
                 "RowsCombined": "float32", "LoadMaxOverMean": "float32",
                 "Up": "float32"},
        attrs={"num_experts": 16, "experts_held": held, "expert_offset": 4,
               "top_k": k},
        wrt=("X", "TopkWeight", "W1", "W2"))


def _short_conv(t, gated):
    """causal_conv1d over [1, t, 128] under three taps: the plain form,
    or LFM2's (a gate ahead, a gate behind, no activation); whole 128-wide
    blocks of time are the kernels', anything else the statement's."""
    def build():
        rng = np.random.default_rng(8)
        rows = lambda: rng.standard_normal((1, t, 128)).astype(  # noqa: E731
            np.float32)
        inputs = {"X": rows(), "Filter": (0.5 * rng.standard_normal(
            (128, 3))).astype(np.float32)}
        if gated:
            inputs.update(PreGate=rows(), PostGate=rows())
        return dict(op_type="causal_conv1d", inputs=inputs,
                    outputs={"Out": "float32"},
                    attrs={"activation": "identity"} if gated else {},
                    wrt=tuple(inputs))
    return build


def _gelu():
    x = np.random.default_rng(5).standard_normal((2, 8, 128))
    return dict(op_type="gelu", inputs={"X": x.astype(np.float32)},
                outputs={"Out": "float32"}, attrs={}, wrt=("X",))


def _conv_o3():
    rng = np.random.default_rng(6)
    return dict(
        op_type="conv2d",
        inputs={"Input": rng.standard_normal((2, 128, 6, 6)).astype("float32"),
                "Filter": (rng.standard_normal((128, 128, 3, 3)) * 0.1)
                .astype("float32")},
        outputs={"Output": "float32"},
        attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
               "groups": 1},
        wrt=("Input", "Filter"), amp="O3")


def _mul_o3():
    rng = np.random.default_rng(7)
    return dict(op_type="mul",
                inputs={"X": rng.standard_normal((8, 64)).astype("float32"),
                        "Y": rng.standard_normal((64, 32)).astype("float32")},
                outputs={"Out": "float32"},
                attrs={"x_num_col_dims": 1, "y_num_col_dims": 1},
                wrt=("X", "Y"), amp="O3")


# the lowerings that book themselves, and all that one forward lowering
# books: generic gradients (jax.vjp traces the forward lowering again)
# but for conv2d's, moe_experts' and causal_conv1d's, whose explicit
# gradient ops book nothing (they take the forward's choice in silence)
BOOKED = {
    "ssd_scan": (_scan, {"pallas_kernel_total": {"op=ssd_scan": 1}}),
    "kda_scan": (_delta_rule(128, 128),
                 {"pallas_kernel_total": {"op=kda_scan": 1}}),
    "kda_scan_narrow": (_delta_rule(16, 8), {
        "pallas_fallback_total": {"op=kda_scan,reason=width": 1}}),
    # its 64 tokens are no whole tile of the token side's kernel
    "moe_experts": (_experts, {
        "pallas_kernel_total": {"op=moe_experts": 1},
        "pallas_fallback_total": {"op=pair_sum,reason=tokens": 1}}),
    # the gated form (PR 62) is booked as the plain one, hit or reason
    "causal_conv1d": (_short_conv(128, False),
                      {"pallas_kernel_total": {"op=causal_conv1d": 1}}),
    "causal_conv1d_gated": (_short_conv(256, True),
                            {"pallas_kernel_total": {"op=causal_conv1d": 1}}),
    "causal_conv1d_gated_declined": (_short_conv(96, True), {
        "pallas_fallback_total": {"op=causal_conv1d,reason=time": 1}}),
    "gelu_kept": (_gelu, {"activation_kept_total": {"act=gelu": 1}}),
    "conv2d_o3": (_conv_o3, {"pallas_kernel_total": {"op=conv2d": 1},
                             "quant_kernel_total": {"op=conv2d": 1}}),
    "mul_o3": (_mul_o3, {"quant_kernel_total": {"op=mul": 1}}),
}


@pytest.mark.parametrize("case", BOOKED)
def test_a_gradients_retrace_of_the_forward_books_nothing(case):
    """The program with the gradient op books what the program without
    it books: one sample a compile for the forward op, nothing for the
    gradient op, whose lowering traces the forward's again inside
    kernel_choice.retrace()."""
    build, booked = BOOKED[case]
    before = _series()
    ops = _one_op(backward=False, **build())
    assert not [t for t in ops if t.endswith("_grad")]
    assert _grown(before) == booked
    before = _series()
    ops = _one_op(backward=True, **build())
    assert build()["op_type"] + "_grad" in ops
    assert _grown(before) == booked


@pytest.mark.parametrize("chunk,k,v,reason", [
    (64, 128, 128, None), (32, 128, 256, None), (256, 128, 128, None),
    (64, 16, 16, "width"), (64, 128, 64, "width"), (64, 256, 128, "width"),
    (48, 128, 128, "chunk"), (8, 128, 128, "chunk"), (96, 128, 128, "chunk")])
def test_the_delta_rules_gate_reads_widths_and_the_chunk(chunk, k, v, reason):
    """A head one lane block of K and whole blocks of V, and a chunk that
    doubles up from sub-blocks of sixteen rows; heads and tokens of any
    number tile (a step owns a divisor of the heads, fewer the longer
    the chunk and the wider the operands; a tail is padded)."""
    from paddle_tpu.ops import hybrid_ops, pallas_kda
    assert hybrid_ops.kda_scan_ineligible(chunk, k, v) == reason
    assert [pallas_kda.heads_a_step(h) for h in (1, 2, 3, 6, 32)] \
        == [1, 2, 3, 6, 8]
    assert [pallas_kda.heads_a_step(32, *how) for how in (
        (64, 4), (128, 2), (256, 2), (256, 4))] == [4, 4, 2, 1]
