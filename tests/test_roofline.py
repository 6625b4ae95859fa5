"""Roofline attribution (ISSUE 6): analytic op costs, probe/ridge math,
synthetic-xplane report joins and waterfall bucketing. The synthetic
traces hand-encode the XSpace wire format so the tests pin the parser and
the report logic together without a device."""

import numpy as np
import pytest

from paddle_tpu import roofline, xplane


class A:
    """Minimal aval stand-in: anything with .shape/.dtype."""

    def __init__(self, shape, dtype=np.float32):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)


# --- hand-rolled XSpace encoder (mirrors xplane.py's decoder) ---------------

def _varint(n):
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(fno, wt, payload):
    key = _varint((fno << 3) | wt)
    if wt == 2:
        return key + _varint(len(payload)) + payload
    return key + _varint(payload)


def _event(mid, off_ps, dur_ps):
    return (_field(1, 0, mid) + _field(2, 0, off_ps)
            + _field(3, 0, dur_ps))


def _line(name, ts_ns, events):
    buf = _field(2, 2, name.encode()) + _field(3, 0, ts_ns)
    for e in events:
        buf += _field(4, 2, e)
    return buf


def _meta(mid, name):
    inner = _field(1, 0, mid) + _field(2, 2, name.encode())
    return _field(1, 0, mid) + _field(2, 2, inner)


def _plane(name, lines, metas):
    buf = _field(2, 2, name.encode())
    for ln in lines:
        buf += _field(3, 2, ln)
    for m in metas:
        buf += _field(4, 2, m)
    return buf


def _write_xspace(path, planes):
    path.write_bytes(b"".join(_field(1, 2, p) for p in planes))


class TestOpCost:
    def test_matmul_flops_and_bytes(self):
        ins = {"X": [A((64, 128))], "Y": [A((128, 32))]}
        outs = {"Out": [A((64, 32))]}
        flops, bytes_ = roofline.op_cost("matmul", ins, outs)
        assert flops == 2 * 64 * 128 * 32
        assert bytes_ == 4 * (64 * 128 + 128 * 32 + 64 * 32)

    def test_matmul_transpose_x_uses_other_contraction_dim(self):
        ins = {"X": [A((128, 64))], "Y": [A((128, 32))]}
        outs = {"Out": [A((64, 32))]}
        flops, _ = roofline.op_cost("matmul", ins, outs,
                                    {"transpose_X": True})
        assert flops == 2 * 64 * 32 * 128

    def test_mul_respects_x_num_col_dims(self):
        ins = {"X": [A((8, 4, 16))], "Y": [A((64, 10))]}
        outs = {"Out": [A((8, 10))]}
        flops, _ = roofline.op_cost("mul", ins, outs, {"x_num_col_dims": 1})
        assert flops == 2 * 8 * 10 * (4 * 16)

    def test_conv2d_counts_macs_from_filter(self):
        ins = {"Input": [A((2, 3, 16, 16))], "Filter": [A((8, 3, 3, 3))]}
        outs = {"Output": [A((2, 8, 16, 16))]}
        flops, _ = roofline.op_cost("conv2d", ins, outs)
        assert flops == 2 * (2 * 8 * 16 * 16) * 3 * 3 * 3

    def test_grad_op_doubles_forward_work(self):
        ins = {"X": [A((64, 128))], "Y": [A((128, 32))],
               "Out@GRAD": [A((64, 32))]}
        outs = {"X@GRAD": [A((64, 128))], "Y@GRAD": [A((128, 32))]}
        flops, _ = roofline.op_cost("matmul_grad", ins, outs)
        assert flops == roofline._GRAD_FACTOR * 2 * 64 * 128 * 32

    def test_data_movement_is_zero_flops_nonzero_bytes(self):
        ins = {"X": [A((128, 64))]}
        outs = {"Out": [A((64, 128))]}
        flops, bytes_ = roofline.op_cost("reshape2", ins, outs)
        assert flops == 0.0
        assert bytes_ == 4 * 2 * 128 * 64

    def test_reduce_costs_input_elems(self):
        ins = {"X": [A((32, 32))]}
        outs = {"Out": [A(())]}
        flops, _ = roofline.op_cost("reduce_sum", ins, outs)
        assert flops == 32 * 32


class TestProbes:
    def test_env_overrides_and_ridge(self, monkeypatch):
        monkeypatch.setattr(roofline, "_PROBES", {})
        monkeypatch.setenv("PADDLE_TPU_SUSTAINED_TFLOPS", "0.5")
        monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "20")
        p = roofline.ensure_probes()
        assert p["sustained_tflops"] == 0.5
        assert p["hbm_gbps"] == 20.0
        assert p["ridge"] == (0.5e12) / (20e9)   # 25 flops/byte

    def test_probe_false_leaves_values_unmeasured(self, monkeypatch):
        monkeypatch.setattr(roofline, "_PROBES", {})
        monkeypatch.delenv("PADDLE_TPU_SUSTAINED_TFLOPS", raising=False)
        monkeypatch.delenv("PADDLE_TPU_HBM_GBPS", raising=False)
        p = roofline.ensure_probes(probe=False)
        assert p["sustained_tflops"] is None or "sustained_tflops" \
            not in roofline._PROBES
        assert p["ridge"] is None


class TestSyntheticReport:
    """End-to-end collect_report over a hand-encoded device plane: the
    attribution join, the per-row verdicts against the ridge, the
    (unattributed) pool, and the telemetry gauges."""

    # a module as Compiled.as_text() prints it: the matmul a fusion around
    # a dot under pd_at.3/pd.matmul, the relu an elementwise maximum
    HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[256,256], param_1: f32[256,256]) -> f32[256,256] {
  %param_0 = f32[256,256]{1,0} parameter(0)
  %param_1 = f32[256,256]{1,0} parameter(1)
  ROOT %dot.0 = f32[256,256]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (p0: f32[256,256], p1: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,256]{1,0} parameter(0)
  %p1 = f32[256,256]{1,0} parameter(1)
  %fusion.1 = f32[256,256]{1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/pd_at.3/pd_role.forward/pd.matmul/dot_general"}
  %c = f32[256,256]{1,0} constant(0)
  ROOT %maximum.7 = f32[256,256]{1,0} maximum(%fusion.1, %c), metadata={op_name="jit(step)/pd_at.4/pd_role.forward/pd.relu/max"}
}
"""

    def _trace(self, tmp_path):
        # fusion.1 appears on the `XLA Ops` line (40us) AND a derived line
        # (40us again): only the core's own line is read, 40 not 80.
        # unknown.9 is no instruction of the block -> "(unattributed)".
        metas = [_meta(1, "fusion.1"), _meta(2, "maximum.7"),
                 _meta(3, "unknown.9")]
        raw = _line("XLA Ops", 1000, [_event(1, 0, 40_000_000),
                                      _event(2, 40_000_000, 10_000_000),
                                      _event(3, 50_000_000, 10_000_000)])
        derived = _line("Steps", 1000, [_event(1, 0, 40_000_000)])
        _write_xspace(tmp_path / "t.xplane.pb",
                      [_plane("/device:TPU:0", [raw, derived], metas)])

    def _accounts(self):
        n = 256
        cost = {"ops": {
            "matmul": {"flops": 2.0 * n ** 3,
                       "bytes": 3.0 * n * n * 4, "count": 1},
            "relu": {"flops": float(n * n),
                     "bytes": 2.0 * n * n * 4, "count": 1}}}
        cost["total_flops"] = sum(d["flops"] for d in cost["ops"].values())
        cost["total_bytes"] = sum(d["bytes"] for d in cost["ops"].values())
        instrs = xplane.compact(xplane.hlo_instructions(self.HLO))
        return [(instrs, {"program": "p", "cost": lambda: cost,
                          "xla_flops": 2.0 * n ** 3 + n * n})]

    def test_verdicts_and_unattributed_pool(self, tmp_path, monkeypatch):
        monkeypatch.setattr(roofline, "_PROBES", {})
        monkeypatch.setenv("PADDLE_TPU_SUSTAINED_TFLOPS", "0.5")
        monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "20")
        self._trace(tmp_path)
        report = roofline.collect_report(str(tmp_path), steps=2,
                                         accounts=self._accounts())
        assert report is not None and report["mapped"]
        rows = {r["op"]: r for r in report["rows"]}
        assert set(rows) == {"matmul", "relu", roofline.UNATTRIBUTED}
        # a row is an op INSTANCE: its position in the block
        assert rows["matmul"]["at"] == 3 and rows["relu"]["at"] == 4
        # the ops line alone: 40us once, not the raw+derived 80us
        assert rows["matmul"]["ps"] == 40_000_000
        # matmul intensity 2*256^3/(3*256^2*4) ~ 42.7 >= ridge 25
        assert rows["matmul"]["bound"] == "compute"
        # relu intensity 256^2/(3*256^2*4) < 25 (it reads the constant too)
        assert rows["relu"]["bound"] == "memory"
        assert rows[roofline.UNATTRIBUTED]["bound"] == "unattributed"
        assert rows[roofline.UNATTRIBUTED]["flops"] is None
        assert abs(sum(r["frac"] for r in report["rows"]) - 1.0) < 1e-9
        # executed work from the account: the dot inside the fusion
        mm = rows["matmul"]
        assert mm["flops"] == 2.0 * 256 ** 3
        assert mm["required_flops"] == 2.0 * 256 ** 3
        # achieved TF/s: the flops of its one run over its device time
        assert abs(mm["tflops"] - mm["flops"] / (mm["ps"] / 1e12) / 1e12) \
            < 1e-9
        # floor share: max(flops / 0.5 TF/s, bytes / 20 GB/s) over 40us
        floor_s = max(mm["flops"] / 0.5e12, mm["bytes"] / 20e9)
        assert mm["efficiency"] == pytest.approx(floor_s / 40e-6)
        cc = report["cost_crosscheck"]
        assert cc["executed_rel_err"] == pytest.approx(0.0)
        assert report["kernel_counts"] == {"modules": 1, "instructions": 2,
                                        "fusions": 1}

    def test_format_report(self, tmp_path, monkeypatch):
        monkeypatch.setattr(roofline, "_PROBES", {})
        monkeypatch.setenv("PADDLE_TPU_SUSTAINED_TFLOPS", "0.5")
        monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "20")
        self._trace(tmp_path)
        report = roofline.collect_report(str(tmp_path), steps=2,
                                         accounts=self._accounts())
        lines = roofline.format_report(report)
        device_rows = [ln for ln in lines if ln.startswith("[device] ")]
        assert device_rows[0].split()[1] == "matmul"
        assert device_rows[0].split()[-1] == "@3"
        assert any(roofline.UNATTRIBUTED in ln for ln in device_rows)
        assert any(ln.startswith("[roofline]") for ln in lines)
        assert any(ln.startswith("[crosscheck]") and "executed" in ln
                   for ln in lines)
        top = report["rows"][0]
        assert (top["op"], top["at"], top["bound"]) == ("matmul", 3,
                                                        "compute")
        assert top["flops"] == 2.0 * 256 ** 3

    def test_foreign_trace_without_accounts_still_reports(self, tmp_path,
                                                          monkeypatch):
        monkeypatch.setattr(roofline, "_PROBES", {})
        monkeypatch.setenv("PADDLE_TPU_SUSTAINED_TFLOPS", "0.5")
        monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "20")
        self._trace(tmp_path)
        report = roofline.collect_report(str(tmp_path), accounts=[])
        assert report is not None and not report["mapped"]
        assert all(r["bound"] == "unattributed" for r in report["rows"])


class TestWaterfall:
    def test_buckets_and_duty_cycle(self, tmp_path):
        # busiest line: compute 40us, all-reduce 20us, infeed copy 10us,
        # then a 30us hole before a final 0-width marker -> span 100us
        metas = [_meta(1, "fusion.1"), _meta(2, "all-reduce.2"),
                 _meta(3, "copy.3"), _meta(4, "fusion.4")]
        busy = _line("XLA Ops", 1000, [
            _event(1, 0, 40_000_000),
            _event(2, 40_000_000, 20_000_000),
            _event(3, 60_000_000, 10_000_000),
            _event(4, 100_000_000, 0)])
        idle = _line("Steps", 1000, [_event(1, 0, 40_000_000)])
        _write_xspace(tmp_path / "t.xplane.pb",
                      [_plane("/device:TPU:0", [busy, idle], metas)])
        wf = roofline.waterfall(str(tmp_path))
        assert wf is not None
        assert wf["compute_ps"] == 40_000_000
        assert wf["collective_ps"] == 20_000_000
        assert wf["infeed_ps"] == 10_000_000
        assert wf["span_ps"] == 100_000_000
        assert wf["host_gap_ps"] == 30_000_000
        assert abs(wf["device_duty_cycle"] - 0.7) < 1e-9

    def test_host_fallback_ignores_bookkeeping_lines(self, tmp_path):
        # CPU-backend shape: a python line spanning the whole session and
        # an XLA thread line with the real instructions. The waterfall
        # must anchor on the instruction line.
        metas = [_meta(1, "$profiler.py:226 trace"), _meta(2, "dot.3")]
        py = _line("python", 500, [_event(1, 0, 1_000_000_000)])
        xla = _line("tf_XLATfrtCpuClient/1", 500,
                    [_event(2, 0, 50_000_000)])
        _write_xspace(tmp_path / "t.xplane.pb",
                      [_plane("/host:CPU", [py, xla], metas)])
        wf = roofline.waterfall(str(tmp_path))
        assert wf is not None
        assert wf["compute_ps"] == 50_000_000
        assert wf["span_ps"] == 50_000_000
        assert wf["device_duty_cycle"] == 1.0


class TestOpsLineAlone:
    def test_a_step_per_core_and_the_derived_line_unread(self, tmp_path):
        metas = [_meta(1, "fusion.1")]
        raw = _line("XLA Ops", 0, [_event(1, 0, 10)])
        derived = _line("Steps", 0, [_event(1, 0, 7)])
        p0 = _plane("/device:TPU:0", [raw, derived], metas)
        p1 = _plane("/device:TPU:1", [raw], metas)
        _write_xspace(tmp_path / "t.xplane.pb", [p0, p1])
        account = xplane.step_account(str(tmp_path), accounts=[])
        # per core the ops line's 10, never 10 + 7; per-core time adds up
        assert [(s["device"], s["busy_ms"]) for s in account["steps"]] == [
            ("/device:TPU:0", pytest.approx(10e-9)),
            ("/device:TPU:1", pytest.approx(10e-9))]
