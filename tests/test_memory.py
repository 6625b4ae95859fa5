"""Memory observability (ISSUE 4): static HBM analysis parity, the HLO
peak-liveness walk, live tracker classification, donation audit,
checkpoint-size telemetry,
per-shard parameter bytes under GSPMD, and OOMError forensics through the
flight-recorder crash report."""

import json
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import cli, inspector, memory, parallel, telemetry
from paddle_tpu import executor as executor_mod
from paddle_tpu.errors import OOMError


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    memory.reset()
    yield
    inspector.disable_flight_recorder()
    telemetry.reset()
    memory.reset()


def _smoke(name="fit_a_line"):
    spec = memory.build_smoke(name)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(spec["startup"])
    return exe, spec


# ---------------------------------------------------------------------------
# Pure helpers
# ---------------------------------------------------------------------------

class TestHelpers:
    def test_shape_bytes(self):
        assert memory.shape_bytes("f32[128,13]{1,0}") == 128 * 13 * 4
        assert memory.shape_bytes("bf16[8]") == 16
        assert memory.shape_bytes("(f32[8,16], s8[4])") == 8 * 16 * 4 + 4
        assert memory.shape_bytes("pred[]") == 1
        assert memory.shape_bytes("token[]") == 0

    def test_nbytes_of_never_reads_data(self):
        import jax
        aval = jax.ShapeDtypeStruct((1 << 20, 13), np.float32)
        assert memory.nbytes_of(aval) == (1 << 20) * 13 * 4
        assert memory.nbytes_of(np.zeros((2, 3), np.float64)) == 48
        assert memory.nbytes_of(None) == 0

    def test_is_oom(self):
        assert memory.is_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
        assert memory.is_oom(RuntimeError("ran Out of memory on chip"))
        assert not memory.is_oom(ValueError("shapes do not match"))

    def test_hlo_peak_liveness_synthetic(self):
        hlo = """\
HloModule test, is_scheduled=true

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0), metadata={op_name="jit(f)/pd.feed/x"}
  %a = f32[4]{0} add(f32[4]{0} %p0, f32[4]{0} %p0), metadata={op_name="jit(f)/pd.elementwise_add/add"}
  %b = f32[4]{0} multiply(f32[4]{0} %a, f32[4]{0} %p0), metadata={op_name="jit(f)/pd.mul/mul"}
  ROOT %c = f32[4]{0} add(f32[4]{0} %b, f32[4]{0} %a)
}
"""
        peak = memory.hlo_peak_liveness(hlo)
        # all four 16-byte buffers overlap at the ROOT: param pinned to the
        # end, a/b both used at pos 3, plus the ROOT output itself
        assert peak["n_instructions"] == 4
        assert peak["peak_bytes"] == 64
        assert peak["live_at_peak"] == 4
        by_instr = {r["instruction"]: r for r in peak["top"]}
        assert by_instr["a"]["op"] == "elementwise_add"
        assert by_instr["c"]["op"] == "add"  # no metadata -> opcode

    def test_headroom_model_exact_linear(self):
        model = memory.HeadroomModel.fit([(4, 1400), (16, 2600),
                                          (64, 7400)])
        assert model.predict(32) == 1000 + 100 * 32
        assert model.max_batch(11_000) == 100
        assert model.max_batch(500) == 0
        flat = memory.HeadroomModel(1000, 0.0)
        assert flat.max_batch(1 << 30) is None
        with pytest.raises(ValueError):
            memory.HeadroomModel.fit([(8, 100), (8, 100)])


# ---------------------------------------------------------------------------
# Static analysis
# ---------------------------------------------------------------------------

class TestStaticAnalysis:
    def test_parity_with_param_bytes(self):
        scope = executor_mod.global_scope()
        exe, spec = _smoke()
        rec = exe.static_memory_analysis(
            spec["main"], feed=spec["feed_fn"](8),
            fetch_list=[spec["loss"]])
        param_bytes = sum(
            memory.nbytes_of(scope.find_var(p.name))
            for p in spec["main"].global_block().all_parameters())
        assert param_bytes > 0
        # the arguments of the compiled step include every parameter
        assert rec.argument_bytes >= param_bytes
        assert rec.total_bytes >= rec.argument_bytes - rec.alias_bytes
        assert rec.donated_bytes >= param_bytes
        # liveness walk found a peak and attributed it to IR ops
        assert rec.peak and rec.peak["peak_bytes"] > 0
        assert rec.peak["top"]
        assert rec is memory.latest_record(rec.program)

    def test_aval_feeds_never_materialize(self):
        # a ~52 GiB feed: static analysis must accept the aval without
        # allocating anything close to that on the host
        exe, spec = _smoke()
        rec = exe.static_memory_analysis(
            spec["main"], feed=spec["feed_fn"](1_000_000_000),
            fetch_list=[spec["loss"]])
        assert rec.argument_bytes > 52 * (1 << 30)

    def test_executor_on_compile_publishes(self, tmp_path):
        inspector.enable_flight_recorder(str(tmp_path / "crash.json"))
        exe, spec = _smoke()
        exe.run(spec["main"], feed=spec["data_fn"](4),
                fetch_list=[spec["loss"]])
        label = telemetry.program_label(spec["main"])
        assert memory.latest_record(label) is not None
        total = telemetry.read_gauge("memory_total_bytes", program=label)
        assert total and total > 0
        events = [e for e in telemetry.recent_events(100)
                  if e.get("kind") == "memory_analysis"]
        assert any(e.get("program") == label for e in events)
        # second signature does NOT re-run the analysis
        n_before = len(events)
        exe.run(spec["main"], feed=spec["data_fn"](6),
                fetch_list=[spec["loss"]])
        n_after = len([e for e in telemetry.recent_events(100)
                       if e.get("kind") == "memory_analysis"])
        assert n_after == n_before
        # flight-recorder step records carry the hbm sample
        rec = inspector._RECORDER.records[-1]
        assert rec.get("hbm_bytes_in_use") is not None


# ---------------------------------------------------------------------------
# Live tracker
# ---------------------------------------------------------------------------

class TestTracker:
    def test_classification(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(input=x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            # Momentum so the state carries optimizer accumulators
            fluid.optimizer.Momentum(
                learning_rate=0.1, momentum=0.9).minimize(
                    loss, startup_program=startup)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"x": np.zeros((4, 8), np.float32),
                            "y": np.zeros((4, 1), np.float32)},
                fetch_list=[loss])
        last = memory.tracker().last
        assert last["source"] in ("device", "live_arrays")
        assert last["bytes_in_use"] > 0
        cls = last["classes"]
        assert cls["params"] >= (8 * 1 + 1) * 4      # w + b
        assert cls["opt_state"] > 0                  # velocity + lr
        assert cls["feeds"] == 4 * 8 * 4 + 4 * 1 * 4
        assert cls["activations"] >= 0
        assert telemetry.read_series("hbm_bytes_in_use")
        assert telemetry.read_gauge(
            "hbm_class_bytes", device=last["device"],
            kind="params") == cls["params"]

    def test_peak_is_monotone(self):
        t = memory.MemoryTracker()
        t.sample()
        first = t.peak_bytes
        t.sample()
        assert t.peak_bytes >= first


# ---------------------------------------------------------------------------
# Donation audit
# ---------------------------------------------------------------------------

class TestDonationAudit:
    def test_warns_once_and_counts(self):
        rec = memory.ProgramMemory(program="p_test")
        rec.donated_bytes = 1000
        rec.alias_bytes = 0
        rec.donation_lost_bytes = 1000
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            memory._audit_donation(rec)
            memory._audit_donation(rec)
        audits = [x for x in w if "not aliased by XLA" in str(x.message)]
        assert len(audits) == 1                       # once per process
        ctr = telemetry.read_series("donation_fallback_total")
        assert ctr.get("program=p_test") == 2.0       # counted per compile

    def test_fully_aliased_is_silent(self):
        rec = memory.ProgramMemory(program="p_ok")
        rec.donated_bytes = 1000
        rec.alias_bytes = 1000
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            memory._audit_donation(rec)
        assert not [x for x in w if "not aliased" in str(x.message)]
        assert not telemetry.read_series("donation_fallback_total")


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestOOM:
    def test_forced_oom_raises_structured_error(self, tmp_path):
        crash = tmp_path / "crash.json"
        inspector.enable_flight_recorder(str(crash))
        exe, spec = _smoke()
        exe.run(spec["main"], feed=spec["data_fn"](4),
                fetch_list=[spec["loss"]])

        def boom(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                               "allocating 12345678 bytes.")

        for blk in exe._cache.values():
            blk.fn = boom
        with pytest.raises(OOMError) as ei:
            exe.run(spec["main"], feed=spec["data_fn"](4),
                    fetch_list=[spec["loss"]])
        err = ei.value
        # retry loops matching the raw XLA status text must still fire
        assert "RESOURCE_EXHAUSTED" in str(err)
        assert err.breakdown                        # non-empty breakdown
        assert err.breakdown["feeds"] > 0
        assert err.breakdown["params"] > 0
        assert err.suggestions
        assert err.analysis and err.analysis["total_bytes"] > 0
        assert isinstance(err, MemoryError) and isinstance(err, RuntimeError)

        report = inspector.read_crash_report(str(crash))
        assert report["error"]["type"] == "OOMError"
        assert report["error"]["breakdown"]["feeds"] > 0
        assert report["memory"]["programs"]
        text = inspector.format_crash_report(report)
        assert "memory breakdown" in text
        assert "OOMError" in text

    def test_non_oom_errors_pass_through(self):
        exe, spec = _smoke()
        exe.run(spec["main"], feed=spec["data_fn"](4),
                fetch_list=[spec["loss"]])

        def boom(*a, **k):
            raise ValueError("not a memory problem")

        for blk in exe._cache.values():
            blk.fn = boom
        with pytest.raises(ValueError):
            exe.run(spec["main"], feed=spec["data_fn"](4),
                    fetch_list=[spec["loss"]])


# ---------------------------------------------------------------------------
# Satellites: checkpoint bytes, per-shard bytes, bench summary, CLI
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_checkpoint_bytes_telemetry(self, tmp_path):
        exe, spec = _smoke()
        fluid.io.save_persistables(exe, str(tmp_path / "ckpt"),
                                   main_program=spec["main"])
        saved = telemetry.read_gauge("checkpoint_bytes", op="save")
        assert saved and saved > 0
        fluid.io.load_persistables(exe, str(tmp_path / "ckpt"),
                                   main_program=spec["main"])
        loaded = telemetry.read_gauge("checkpoint_bytes", op="load")
        assert loaded == saved
        kinds = {e.get("kind") for e in telemetry.recent_events(50)}
        assert {"checkpoint_save", "checkpoint_load"} <= kinds

    def test_per_shard_param_bytes(self):
        import jax
        from jax.sharding import Mesh
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            pred = fluid.layers.fc(input=x, size=8)
            fluid.layers.mean(pred)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = next(p.name for p in main.global_block().all_parameters()
                 if "w" in p.name)
        main._mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        main._param_shardings = {w: ("dp", None)}
        out = parallel.per_shard_param_bytes(main)
        assert out["devices"] == 4
        assert out["replicated_bytes"] == 8 * 4          # bias
        assert out["sharded_bytes_per_device"] == 16 * 8 * 4 // 4
        assert out["per_device_bytes"] == \
            out["replicated_bytes"] + out["sharded_bytes_per_device"]
        assert out["params"][w]["factor"] == 4

    def test_memory_report(self):
        exe, spec = _smoke()
        exe.run(spec["main"], feed=spec["data_fn"](4),
                fetch_list=[spec["loss"]])
        rep = memory.memory_report()
        assert rep["programs"] and rep["tracker"]
        assert rep["peak_bytes"] > 0

    def test_memory_cli_static_and_live(self, capsys):
        rc = cli.main(["memory", "--smoke", "fit_a_line", "--batch", "16",
                       "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        entry = out["programs"][0]
        assert entry["batch"] == 16
        assert entry["static"]["total_bytes"] > 0
        assert entry["live"]["bytes_in_use"] > 0
        assert out["report"]["programs"]

    def test_read_series(self):
        telemetry.counter("rs_test", "x", labels=("k",)).labels(k="a").inc(2)
        telemetry.counter("rs_test", "x", labels=("k",)).labels(k="b").inc()
        assert telemetry.read_series("rs_test") == {"k=a": 2.0, "k=b": 1.0}
        assert telemetry.read_series("nope") == {}
