"""Profiler: host event table, per-op eager events, chrome-trace export
(reference: profiler.py:76, platform/profiler.h, tools/timeline.py:31,
test_profiler.py)."""

import json

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import profiler


class TestProfiler:
    def _run_once(self, use_jit):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(input=x, size=3)
            out = fluid.layers.reduce_sum(y)
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                exe.run(fluid.default_main_program(),
                        feed={"x": np.zeros((2, 4), np.float32)},
                        fetch_list=[out], use_jit=use_jit)

    def test_jit_run_records_block_event(self, capsys, tmp_path):
        profiler.reset_profiler()
        with profiler.profiler("All", sorted_key="total"):
            self._run_once(use_jit=True)
        captured = capsys.readouterr().out
        assert "executor_run(jit)" in captured

        trace = str(tmp_path / "trace.json")
        profiler.export_chrome_trace(trace)
        data = json.load(open(trace))
        names = {e["name"] for e in data["traceEvents"]}
        assert "executor_run(jit)" in names
        assert all(e["ph"] == "X" and e["dur"] >= 0
                   for e in data["traceEvents"])

    def test_eager_run_records_per_op_events(self, capsys):
        profiler.reset_profiler()
        with profiler.profiler("All"):
            self._run_once(use_jit=False)
        captured = capsys.readouterr().out
        assert "mul" in captured and "reduce_sum" in captured

    def test_jit_device_table_attributes_hot_op(self, capsys, tmp_path):
        """Per-op device-time attribution in JIT mode:
        the xplane trace joined with the compiled HLO's pd.<op> scopes
        must rank the known-hot op — a 768x768 matmul dwarfing the other
        ops — first, like the reference's ParseEvents table
        (platform/profiler.h:137-166)."""
        n = 768
        profiler.reset_profiler()
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[n, n], dtype="float32",
                                  append_batch_size=False)
            y = fluid.layers.matmul(x, x)
            out = fluid.layers.reduce_sum(fluid.layers.sigmoid(y))
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                xs = np.random.RandomState(0).randn(n, n) \
                    .astype(np.float32) * 0.01
                exe.run(fluid.default_main_program(), feed={"x": xs},
                        fetch_list=[out])       # warm: compile outside
                with profiler.profiler("All", sorted_key="total",
                                       trace_dir=str(tmp_path / "tr")):
                    for _ in range(5):
                        exe.run(fluid.default_main_program(),
                                feed={"x": xs}, fetch_list=[out])
        captured = capsys.readouterr().out
        device_rows = [ln for ln in captured.splitlines()
                       if ln.startswith("[device]")]
        assert device_rows, captured
        assert device_rows[0].split()[1] == "matmul", device_rows


class TestXplaneRoundTrip:
    """Real jax.profiler.trace -> xplane parser round-trip on the CPU
    backend (ISSUE 6): CPU jax writes only host planes, so these pin the
    host-plane fallback, the timeline/offset parsing, and the analytic
    FLOPs vs XLA cost_analysis cross-check."""

    def _trace(self, tmp_path):
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda a: (a @ a).sum())
        x = jnp.ones((128, 128), jnp.float32)
        f(x).block_until_ready()            # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(3):
                f(x).block_until_ready()

    def test_host_plane_fallback_keeps_only_instructions(self, tmp_path):
        from paddle_tpu import xplane
        self._trace(tmp_path)
        steps = xplane.device_steps(str(tmp_path))
        assert steps and all(s["host"] for s in steps), \
            "trace produced no step"
        agg = {e[0] for s in steps for e in s["events"]}
        # the fallback must admit only instruction-like names: the python
        # line's '$profiler.py:226 trace' event spans the whole session
        # and would otherwise dwarf every real instruction
        assert all(xplane.instr_like(name) for name in agg), agg
        assert any(name.startswith("dot") for name in agg), agg

    def test_timeline_parses_offsets_and_timestamps(self, tmp_path):
        from paddle_tpu import xplane
        self._trace(tmp_path)
        records = xplane.timeline_dir(str(tmp_path))
        lines = [r for r in records if r["events"]]
        assert lines
        assert any(r["timestamp_ns"] > 0 for r in lines)
        # offsets place events within their line: the three timed calls
        # must yield distinct, increasing offsets for the repeated dot
        dots = sorted(off for r in lines for (name, off, dur) in r["events"]
                      if name.startswith("dot") and dur > 0)
        assert len(dots) >= 2 and dots[0] < dots[-1], dots

    def test_matmul_flops_crosscheck_within_10pct(self, tmp_path,
                                                  monkeypatch):
        from paddle_tpu import roofline
        monkeypatch.setenv("PADDLE_TPU_SUSTAINED_TFLOPS", "0.5")
        monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "20")
        monkeypatch.setattr(roofline, "_PROBES", {})
        n = 256
        profiler.reset_profiler()
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[n, n], dtype="float32",
                                  append_batch_size=False)
            out = fluid.layers.reduce_sum(fluid.layers.matmul(x, x))
            exe = fluid.Executor(fluid.CPUPlace())
            with executor_mod.scope_guard(executor_mod.Scope()):
                exe.run(fluid.default_startup_program())
                xs = np.random.RandomState(0).randn(n, n) \
                    .astype(np.float32) * 0.01
                main = fluid.default_main_program()

                def step():
                    exe.run(main, feed={"x": xs}, fetch_list=[out])

                step()                      # warm: compile outside
                report = roofline.capture(step, steps=4)
        assert report is not None
        rows = {r["op"]: r for r in report["rows"]}
        assert "matmul" in rows, rows
        assert rows["matmul"]["flops"] == 2.0 * n ** 3
        cc = report.get("cost_crosscheck")
        assert cc, report["notes"]
        assert cc["rel_err"] <= 0.10, cc
        # fractions sum to the true device total, unattributed included
        assert abs(sum(r["frac"] for r in report["rows"]) - 1.0) < 1e-6
