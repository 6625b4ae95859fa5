"""Fleet observability (ISSUE 8): collective-kind classification, busbw
factor math, HLO collective parsing (shape -> bytes, pd.coll sites, the
GSPMD `near` fallback), the exposed-vs-overlapped split, the synthetic
xplane -> collective_table join, the goodput ledger arithmetic, and a
real 2-process FleetSnapshot reduce over the coordination service. The
synthetic traces hand-encode the XSpace wire format (same encoder as
test_roofline.py) so the tests pin the parser and the attribution logic
together without a device."""

import json
import os
import socket
import subprocess
import sys

import pytest

from paddle_tpu import fleet, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


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


# --- classification + busbw factors ------------------------------------------

class TestCollectiveKind:
    def test_hlo_spellings(self):
        assert xplane.collective_kind("all-reduce.3") == "all-reduce"
        assert xplane.collective_kind("all-gather-start.1") == "all-gather"
        assert (xplane.collective_kind("reduce-scatter.2")
                == "reduce-scatter")
        assert xplane.collective_kind("all-to-all.7") == "all-to-all"
        assert (xplane.collective_kind("collective-permute-start")
                == "collective-permute")
        assert xplane.collective_kind("send.1") == "send/recv"
        assert xplane.collective_kind("recv-done.4") == "send/recv"

    def test_runtime_and_framework_spellings(self):
        assert xplane.collective_kind("AllReduce") == "all-reduce"
        assert (xplane.collective_kind("cross-replica-sum.1")
                == "all-reduce")
        assert xplane.collective_kind("ppermute") == "collective-permute"

    def test_non_collectives_are_none(self):
        for name in ("fusion.3", "dot.1", "infeed", "copy.2",
                     "dynamic-update-slice.9"):
            assert xplane.collective_kind(name) is None, name

    def test_reduce_scatter_not_shadowed_by_all_reduce(self):
        # match order matters: 'reduce-scatter' must win over the broader
        # reduce-family patterns (tools/check_registry.py lints the table)
        assert (xplane.collective_kind("reduce-scatter-start.1")
                == "reduce-scatter")


class TestBusbwFactor:
    def test_nccl_tests_convention(self):
        assert xplane.busbw_factor("all-reduce", 4) == pytest.approx(1.5)
        assert xplane.busbw_factor("all-gather", 4) == pytest.approx(0.75)
        assert (xplane.busbw_factor("reduce-scatter", 8)
                == pytest.approx(7 / 8))
        assert xplane.busbw_factor("collective-permute", 4) == 1.0
        assert xplane.busbw_factor("send/recv", 2) == 1.0

    def test_degenerate(self):
        assert xplane.busbw_factor("all-reduce", 1) == 0.0
        assert xplane.busbw_factor("not-a-kind", 4) == 0.0


# --- HLO parsing --------------------------------------------------------------

_HLO = """\
HloModule jit_step

ENTRY main {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %dot.3 = f32[8,8]{1,0} dot(%p0, %p0)
  %all-reduce.1 = f32[1024,1024]{1,0} all-reduce(%p0), channel_id=1, \
replica_groups=[1,4]<=[4], to_apply=%add, \
metadata={op_name="jit(step)/jit(main)/pd.mul_grad/pd.coll.dp_grad/add"}
  %all-gather-start.2 = (f32[256]{0}, f32[1024]{0}) \
all-gather-start(%p0), replica_groups=[1,4]<=[4], dimensions={0}, \
metadata={op_name="jit(step)/pd.mul/pd.coll.tp_gather/g"}
  %all-gather-done.2 = f32[1024]{0} all-gather-done(%all-gather-start.2), \
metadata={op_name="jit(step)/pd.mul/pd.coll.tp_gather/g"}
  %all-reduce.9 = f32[64]{0} all-reduce(%p0), replica_groups=[1,4]<=[4], \
to_apply=%add, metadata={op_name="jit(step)/pd.mean/reduce_sum"}
}
"""


class TestHloCollectives:
    def test_sites_bytes_and_done_halves(self):
        out = {i.name: i for i in xplane.hlo_instructions(_HLO) if i.kind}
        assert set(out) == {"all-reduce.1", "all-gather-start.2",
                            "all-gather-done.2", "all-reduce.9"}
        ar = out["all-reduce.1"]
        assert ar.kind == "all-reduce"
        assert ar.site == "dp_grad"
        assert ar.payload == 1024 * 1024 * 4
        # async start carries an (input, output) tuple aliasing ONE
        # transfer: the payload is the output, not the sum
        ag = out["all-gather-start.2"]
        assert ag.kind == "all-gather"
        assert ag.site == "tp_gather"
        assert ag.payload == 1024 * 4
        # the -done half joins time but contributes 0 bytes (no double
        # counting of the pair's payload)
        assert out["all-gather-done.2"].payload == 0
        # GSPMD-inserted collective: no pd.coll scope, but the inherited
        # op_name names the responsible layer
        g = out["all-reduce.9"]
        assert g.site is None
        assert g.op == "mean"

    def test_participants(self):
        # each instruction's OWN group size, not the module's first
        sizes = {i.name: i.group_size
                 for i in xplane.hlo_instructions(_HLO)
                 if i.kind and not i.opcode.endswith("-done")}
        assert len(sizes) == 3 and set(sizes.values()) == {4}
        (two,) = [i for i in xplane.hlo_instructions(
            "ENTRY main {\n  %p = f32[8]{0} parameter(0)\n"
            "  %all-reduce.1 = f32[8]{0} all-reduce(%p), "
            "replica_groups={{0,1},{2,3}}, to_apply=%add\n}\n") if i.kind]
        assert two.group_size == 2
        (none,) = [i for i in xplane.hlo_instructions(
            "ENTRY main {\n  %p = f32[8]{0} parameter(0)\n"
            "  %all-reduce.1 = f32[8]{0} all-reduce(%p), to_apply=%add\n"
            "}\n") if i.kind]
        assert none.group_size is None


# --- exposed-vs-overlapped split ---------------------------------------------

class TestExposedInLine:
    def test_partial_overlap(self):
        # all-reduce 50..150; compute covers [0,100] and [120,140]
        # -> 70 covered, 30 exposed
        events = [("fusion.1", 0, 100), ("all-reduce.5", 50, 100),
                  ("copy.2", 120, 20)]
        assert xplane.exposed_in_line(events) == {"all-reduce.5": 30}

    def test_fully_hidden_and_fully_exposed(self):
        events = [("fusion.1", 0, 200), ("all-reduce.5", 50, 100),
                  ("ppermute.2", 300, 40)]
        out = xplane.exposed_in_line(events)
        assert out["all-reduce.5"] == 0
        assert out["ppermute.2"] == 40

    def test_zero_duration_events_ignored(self):
        assert xplane.exposed_in_line([("all-reduce.1", 0, 0)]) == {}


# --- synthetic trace -> collective_table join --------------------------------

@pytest.fixture
def pinned_ici(monkeypatch):
    """Pin the link roofline to 100 GB/s and keep the probe cache clean on
    both sides, so pct_link is deterministic and probe-free."""
    from paddle_tpu import roofline
    monkeypatch.setenv("PADDLE_TPU_ICI_GBPS", "100")
    roofline._PROBES.pop("ici_gbps", None)
    yield 100.0
    roofline._PROBES.pop("ici_gbps", None)


def _write_trace(tmp_path):
    # device plane, two lines: the `XLA Ops` line (all-reduce.1 4us, of
    # which 1us hides under fusion.1) and a derived line repeating the
    # same event shorter — only the core's own line is read
    metas = [_meta(1, "fusion.1"), _meta(2, "all-reduce.1")]
    raw = _line("XLA Ops", 0, [
        _event(1, 0, 2_000_000),            # fusion.1: 0..2us
        _event(2, 1_000_000, 4_000_000),    # all-reduce.1: 1..5us
    ])
    derived = _line("steps", 0, [_event(2, 0, 3_000_000)])
    _write_xspace(tmp_path / "t.xplane.pb",
                  [_plane("/device:TPU:0", [raw, derived], metas)])


class TestCollectiveEventsDir:
    def test_ops_line_alone_and_exposed(self, tmp_path):
        _write_trace(tmp_path)
        (step,) = xplane.device_steps(str(tmp_path))
        assert [e[0] for e in step["events"]] == ["fusion.1", "all-reduce.1"]
        account = xplane.step_account(str(tmp_path), accounts=[])
        (rec,) = [r for r in account["steps"][0]["rows"] if r["kind"]]
        assert rec["kind"] == "all-reduce"
        assert rec["ms"] == pytest.approx(0.004)          # once, not 4+3
        assert rec["exposed_ms"] == pytest.approx(0.003)  # 1us under fusion.1


class TestCollectiveTable:
    def test_join_busbw_and_roofline_pct(self, tmp_path, pinned_ici):
        _write_trace(tmp_path)
        table = fleet.collective_table(
            str(tmp_path), accounts=[xplane.hlo_instructions(_HLO)],
            probe=False)
        assert table["ici_gbps"] == pinned_ici
        assert table["participants"] == 4
        assert len(table["rows"]) == 1
        r = table["rows"][0]
        assert r["kind"] == "all-reduce"
        assert r["site"] == "dp_grad"
        assert r["count"] == 1
        assert r["group_size"] == 4 and r["axis"] is None   # no mesh given
        assert r["bytes"] == 1024 * 1024 * 4         # payload x its one run
        assert r["time_ms"] == pytest.approx(0.004)
        assert r["exposed_ms"] == pytest.approx(0.003)
        assert r["overlap_frac"] == pytest.approx(0.25)
        algbw = r["bytes"] / 4e-6 / 1e9
        assert r["algbw_gbps"] == pytest.approx(algbw)
        assert r["busbw_gbps"] == pytest.approx(algbw * 1.5)   # 2(n-1)/n
        assert r["pct_link"] == pytest.approx(algbw * 1.5 / pinned_ici)

    def test_unjoined_event_pools_under_gspmd(self, tmp_path, pinned_ici):
        _write_trace(tmp_path)
        table = fleet.collective_table(str(tmp_path), accounts=[],
                                       probe=False)
        (r,) = table["rows"]
        assert r["site"] == "(gspmd)"
        assert r["bytes"] == 0
        assert r["algbw_gbps"] == 0.0   # time joined, payload unknown


# --- goodput ledger -----------------------------------------------------------

class TestGoodput:
    def test_bucket_arithmetic(self):
        events = [
            {"kind": "run", "mono": 100.0, "seconds": 10.0,
             "compile_s": 4.0, "execute_s": 5.0},
            {"kind": "run_window", "mono": 106.0, "seconds": 5.0,
             "execute_s": 5.0},
            {"kind": "checkpoint", "op": "save", "seconds": 1.0},
            # io.py's save event nests inside the multihost one above —
            # the ledger must prefer the multihost marker, not add both
            {"kind": "checkpoint_save", "seconds": 0.4},
            # ...but with no multihost load marker, io's load counts
            {"kind": "checkpoint_load", "seconds": 0.3},
        ]
        gp = fleet.goodput_report(events, input_stall_s=0.5,
                                  collective_wait_s=2.0)
        # span: first run start (100-10=90) .. last run end (106)
        assert gp["span_s"] == pytest.approx(16.0)
        assert gp["runs"] == 2
        b = gp["buckets"]
        assert b["productive"] == pytest.approx(8.0)   # 10 exec - 2 wait
        assert b["compile"] == pytest.approx(4.0)
        assert b["checkpoint_save"] == pytest.approx(1.0)
        assert b["restore"] == pytest.approx(0.3)
        assert b["input_stall"] == pytest.approx(0.5)
        assert b["collective_wait"] == pytest.approx(2.0)
        assert b["idle"] == pytest.approx(16.0 - 15.8)
        assert gp["goodput_fraction"] == pytest.approx(0.5)

    def test_collective_wait_clamped_to_execute(self):
        events = [{"kind": "run", "mono": 10.0, "seconds": 10.0,
                   "execute_s": 3.0}]
        gp = fleet.goodput_report(events, input_stall_s=0.0,
                                  collective_wait_s=99.0)
        assert gp["buckets"]["collective_wait"] == pytest.approx(3.0)
        assert gp["buckets"]["productive"] == 0.0
        assert gp["goodput_fraction"] == 0.0

    def test_no_runs_is_none(self):
        assert fleet.goodput_report([{"kind": "checkpoint",
                                      "op": "save", "seconds": 1.0}]) is None

    def test_publishes_gauges(self):
        from paddle_tpu import telemetry
        events = [{"kind": "run", "mono": 50.0, "seconds": 4.0,
                   "execute_s": 2.0}]
        gp = fleet.goodput_report(events, input_stall_s=0.0,
                                  collective_wait_s=0.0)
        assert (telemetry.read_gauge("goodput_fraction")
                == pytest.approx(gp["goodput_fraction"]))
        assert (telemetry.read_gauge("goodput_seconds", bucket="productive")
                == pytest.approx(2.0))

    def test_formatting(self):
        assert fleet.format_goodput(None) == \
            ["[goodput] no run events recorded"]
        gp = fleet.goodput_report(
            [{"kind": "run", "mono": 10.0, "seconds": 4.0,
              "execute_s": 2.0}],
            input_stall_s=0.0, collective_wait_s=0.0)
        lines = fleet.format_goodput(gp)
        assert "50.0% productive" in lines[0]
        assert any("productive" in ln for ln in lines[1:])


# --- fleet snapshot -----------------------------------------------------------

class TestFleetSnapshot:
    def test_local_snapshot_shape(self):
        snap = fleet.local_snapshot()
        assert set(snap) >= {"host", "steps", "step_time_s",
                             "infeed_wait_s", "collective_wait_s",
                             "hbm_bytes_in_use", "hbm_peak_bytes"}
        # read-only peeks: a host that never stepped contributes numbers
        # (or None for never-set gauges), never raises
        json.dumps(snap)   # must stay JSON-serializable for the allgather

    def test_single_process_reduce(self):
        from paddle_tpu import telemetry
        local = {"host": 3, "step_time_s": 0.25, "infeed_wait_s": 0.0,
                 "collective_wait_s": 0.0}
        snap = fleet.fleet_snapshot(local)
        assert snap["n_hosts"] == 1
        assert snap["step_skew"] == 1.0
        assert snap["median_step_s"] == pytest.approx(0.25)
        assert snap["straggler"] == {"host": 3, "cause": "compute",
                                     "alerts_total": 0.0}
        assert telemetry.read_gauge("fleet_step_skew") == 1.0
        assert "straggler host 3 (compute)" in fleet.format_fleet(snap)

    def test_two_process_reduce(self):
        """Real 2-process FleetSnapshot allgather + skew reduce over the
        coordination service (harness: test_telemetry's reduce test)."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        env.pop("PADDLE_TRAINER_ID", None)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(HERE), env.get("PYTHONPATH", "")])
        procs = [subprocess.Popen(
            [sys.executable, os.path.join(HERE, "_fleet_worker.py"),
             coordinator, "2", str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True) for pid in (0, 1)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=180)
                outs.append((p.returncode, out, err))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for rc, out, err in outs:
            assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\n" \
                            f"stderr:{err}"
            assert "RESULT" in out, out
        results = [json.loads(out.split("RESULT", 1)[1])
                   for _, out, _ in outs]
        # both sides agree: host 1 is the straggler, blamed on infeed,
        # skew = 0.2 / median(0.1, 0.2)
        for r in results:
            assert r["skew"] == pytest.approx(0.2 / 0.15)
            assert r["straggler"] == {"host": 1, "cause": "infeed",
                                      "alerts_total": 0.0}
