"""Whole-program static verifier (ISSUE 12): every analyzer pass
against hand-built broken programs, zero error-severity diagnostics
over each shipped example, the PADDLE_TPU_VERIFY executor hook, the
`python -m paddle_tpu analyze` CLI, and the desc attr JSON round-trip
(tuples must survive with type intact — the analyzer clones descs and
op lowerings compare attrs with `== (0, 1)`)."""

import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.analysis import analyze_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.RandomState(7)


def _by_code(report, code):
    return [d for d in report.diagnostics if d.code == code]


def _fit_a_line():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13])
        y = fluid.layers.data(name="y", shape=[1])
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(
            loss, startup_program=startup)
    return main, startup, loss


# ---------------------------------------------------------------------------
# shapes pass
# ---------------------------------------------------------------------------

class TestShapesPass:
    def test_clean_program_has_no_errors(self):
        main, _, loss = _fit_a_line()
        report = analyze_program(main, feeds=["x", "y"],
                                 fetches=[loss.name])
        assert report.ok, report.format(show_info=True)

    def test_rank_mismatch_cites_op_and_site(self):
        main, _, loss = _fit_a_line()
        # corrupt the feed declaration after build: rank 2 -> rank 1
        main.global_block().desc.var("x").shape = [-1]
        report = analyze_program(main, feeds=["x", "y"],
                                 fetches=[loss.name])
        errs = _by_code(report, "rank-mismatch")
        assert errs, report.format(show_info=True)
        d = errs[0]
        assert d.op_index is not None and d.op_type == "mul"
        # creation_site points back at this test file's fc() call
        assert d.site and "test_analysis.py" in d.site

    def test_unregistered_op_is_an_error(self):
        from paddle_tpu.framework.desc import OpDesc

        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            fluid.layers.data(name="x", shape=[4])
            b = main.global_block()
            b.create_var(name="o", shape=[-1, 4], dtype="float32")
            # append_op refuses unregistered types, so plant it in the
            # desc directly and rebuild the Operator wrappers
            b.desc.ops.append(OpDesc(
                type="definitely_not_an_op",
                inputs={"X": ["x"]}, outputs={"Out": ["o"]}))
            b._sync_ops()
        report = analyze_program(main, feeds=["x"], fetches=["o"])
        assert _by_code(report, "unregistered-op"), \
            report.format(show_info=True)


# ---------------------------------------------------------------------------
# dataflow pass
# ---------------------------------------------------------------------------

class TestDataflowPass:
    def test_use_before_def(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            fluid.layers.data(name="x", shape=[4])
            b = main.global_block()
            b.create_var(name="t", shape=[-1, 4], dtype="float32")
            b.create_var(name="o", shape=[-1, 4], dtype="float32")
            # consumer appended before its producer
            b.append_op(type="scale", inputs={"X": ["t"]},
                        outputs={"Out": ["o"]}, attrs={"scale": 2.0})
            b.append_op(type="scale", inputs={"X": ["x"]},
                        outputs={"Out": ["t"]}, attrs={"scale": 1.0})
        report = analyze_program(main, feeds=["x"], fetches=["o"])
        errs = _by_code(report, "use-before-def")
        assert errs and errs[0].op_index == 0 and errs[0].var == "t"
        assert "reorder" in (errs[0].hint or "")

    def test_dead_op(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[4])
            kept = fluid.layers.scale(x, scale=2.0)
            fluid.layers.scale(x, scale=3.0)  # never fetched
        report = analyze_program(main, feeds=["x"], fetches=[kept.name])
        dead = _by_code(report, "dead-op")
        assert dead and "prune" in (dead[0].hint or "")
        assert dead[0].op_index is not None

    def test_donated_and_fetched(self):
        main, _, loss = _fit_a_line()
        params = [n for n, v in
                  main.global_block().desc.vars.items()
                  if v.persistable and n.endswith(".w_0")]
        assert params, "expected an fc weight param"
        report = analyze_program(main, feeds=["x", "y"],
                                 fetches=[loss.name, params[0]])
        hits = _by_code(report, "donated-fetch")
        assert hits and hits[0].var == params[0]

    def test_param_grad_pairing_breaks_on_desc_edit(self):
        main, _, loss = _fit_a_line()
        pairs = getattr(main, "_grad_param_pairs", [])
        dense = [g for _, g in pairs if g.endswith(".w_0@GRAD")]
        assert dense, pairs
        main.global_block().desc.var(dense[0]).shape = [3, 3, 3]
        report = analyze_program(main, feeds=["x", "y"],
                                 fetches=[loss.name])
        assert _by_code(report, "param-grad-shape"), \
            report.format(show_info=True)


# ---------------------------------------------------------------------------
# preflight pass
# ---------------------------------------------------------------------------

class TestPreflightPass:
    def test_sharding_indivisible(self):
        main = fluid.Program()
        b = main.global_block()
        b.create_var(name="w", shape=[10, 6], dtype="float32",
                     persistable=True)
        main._param_shardings = {"w": (None, "mp")}
        main._mesh = SimpleNamespace(shape={"mp": 4}, axis_names=("mp",))
        report = analyze_program(main, feeds=[], fetches=[])
        errs = _by_code(report, "sharding-indivisible")
        assert errs and errs[0].var == "w"
        assert "pad the dim to 8" in (errs[0].hint or "")

    def test_sharding_unknown_axis(self):
        main = fluid.Program()
        main.global_block().create_var(
            name="w", shape=[8, 8], dtype="float32", persistable=True)
        main._param_shardings = {"w": ("tp", None)}
        main._mesh = SimpleNamespace(shape={"mp": 4}, axis_names=("mp",))
        report = analyze_program(main, feeds=[], fetches=[])
        assert _by_code(report, "sharding-unknown-axis")

    def test_conv_channel_miss_is_a_quant_matter_only(self):
        """A 64-channel bf16 conv is XLA's convolution like every other
        (PR 25): no route is missed and preflight advises nothing. Under
        O3 the same conv does miss one, the int8 kernel's 128-lane
        tiling, and the quant pass says what that kernel needs."""
        def conv_report(**tags):
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                img = fluid.layers.data(name="img", shape=[64, 16, 16])
                out = fluid.layers.conv2d(input=img, num_filters=128,
                                          filter_size=3, padding=1)
            main._amp_dtype = "bfloat16"
            for k, v in tags.items():
                setattr(main, k, v)
            return analyze_program(main, feeds=["img"],
                                   fetches=[out.name])

        report = conv_report()
        assert not _by_code(report, "pallas-conv-fallback")
        assert not _by_code(report, "quant-fallback")
        assert not report.errors and not report.warnings, \
            report.format(show_info=True)

        report = conv_report(_amp_level="O3", _quant_mode="int8")
        warns = _by_code(report, "quant-fallback")
        assert warns and not _by_code(report, "pallas-conv-fallback")
        assert not report.errors  # a fast-path miss is advisory, not fatal
        d = warns[0]
        assert "reason: kernel" in d.message and d.op_index is not None
        assert "multiples of 128" in (d.hint or "")

    def test_quant_preflight_flags_shallow_matmul(self):
        """ISSUE 20 satellite: planted defect — a K=24 fc under O3
        fails the shape gate (K < 32), and the preflight quant pass
        says so before compile by dry-running quant.gate_for_op on the
        desc avals; the K=64 layer downstream passes and stays quiet."""
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[24], dtype="float32")
            h = fluid.layers.fc(input=x, size=64, act="relu")
            out = fluid.layers.fc(input=h, size=64)
        main._amp_dtype = "bfloat16"
        main._amp_level = "O3"
        main._quant_mode = "int8"
        report = analyze_program(main, feeds=["x"], fetches=[out.name])
        warns = _by_code(report, "quant-fallback")
        assert len(warns) == 1, report.format(show_info=True)
        assert not report.errors  # advisory, not fatal
        d = warns[0]
        assert "reason: shape" in d.message and d.op_index is not None
        assert "K=24" in (d.hint or "")

    def test_quant_preflight_silent_below_o3(self):
        """The same shallow matmul without _quant_mode emits nothing:
        an O1/O2 program falling back everywhere is configuration, not
        a diagnosis."""
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[24], dtype="float32")
            out = fluid.layers.fc(input=x, size=64)
        main._amp_dtype = "bfloat16"
        report = analyze_program(main, feeds=["x"], fetches=[out.name])
        assert not _by_code(report, "quant-fallback")

    def test_emb_cache_thrash_warning(self):
        """ISSUE 14 satellite: a cache_rows request below the static
        per-step touched-row bound (batch x slots ids can all be
        distinct) warns BEFORE any step runs — at runtime that config
        evicts rows staged the same step, and a fused window can fail
        outright on the union-must-fit check."""
        def prog(cache_rows):
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                with fluid.unique_name.guard():
                    ids = fluid.layers.data(name="ids", shape=[26],
                                            dtype="int64")
                    fluid.layers.embedding(
                        ids, size=[1000, 8], is_sparse=True,
                        param_attr=fluid.ParamAttr(name="emb_w"),
                        cache_rows=cache_rows)
            return main

        # bound = _PROBE_BATCH(8) x 26 slots = 208 > 64 -> warn
        report = analyze_program(prog(64), feeds=["ids"], fetches=[])
        warns = _by_code(report, "emb-cache-thrash")
        assert warns and warns[0].var == "emb_w"
        assert not report.errors       # advisory: sizing, not soundness
        assert "208" in warns[0].message
        assert "cache_rows" in (warns[0].hint or "")
        # a bound-covering cache_rows is silent
        report = analyze_program(prog(256), feeds=["ids"], fetches=[])
        assert not _by_code(report, "emb-cache-thrash")


# ---------------------------------------------------------------------------
# shipped examples: the acceptance bar is zero error-severity findings
# ---------------------------------------------------------------------------

def _load_example(name):
    path = os.path.join(REPO, "examples", "fluid", f"train_{name}.py")
    spec = importlib.util.spec_from_file_location(f"_ex_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", [
    "fit_a_line", "criteo_dlrm", "transformer_long_context"])
def test_examples_analyze_clean(name):
    built = _load_example(name).build_programs()
    report = analyze_program(built["main"], feeds=built["feeds"],
                             fetches=built["fetches"])
    assert not report.errors, report.format(show_info=True)
    startup_report = analyze_program(built["startup"], feeds=[],
                                     fetches=[])
    assert not startup_report.errors, \
        startup_report.format(show_info=True)


# ---------------------------------------------------------------------------
# PADDLE_TPU_VERIFY executor hook
# ---------------------------------------------------------------------------

class TestVerifyMode:
    def test_clean_program_still_runs(self, monkeypatch):
        monkeypatch.setattr(executor_mod, "_VERIFY", True)
        main, startup, loss = _fit_a_line()
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup)
            out, = exe.run(main,
                           feed={"x": RNG.rand(4, 13).astype("float32"),
                                 "y": RNG.rand(4, 1).astype("float32")},
                           fetch_list=[loss])
        assert np.isfinite(float(np.ravel(out)[0]))

    def test_broken_program_raises_before_compile(self, monkeypatch):
        from paddle_tpu import errors

        monkeypatch.setattr(executor_mod, "_VERIFY", True)
        main, startup, loss = _fit_a_line()
        main.global_block().desc.var("x").shape = [-1]
        main._version += 1  # desc edited behind the cache's back
        exe = fluid.Executor(fluid.CPUPlace())
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup)
            with pytest.raises(errors.ProgramVerifyError) as ei:
                exe.run(main,
                        feed={"x": RNG.rand(4).astype("float32"),
                              "y": RNG.rand(4, 1).astype("float32")},
                        fetch_list=[loss])
        assert ei.value.diagnostics
        assert "rank-mismatch" in str(ei.value)

    def test_off_by_default(self):
        assert executor_mod._VERIFY is False


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_analyze_cli_json():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "analyze",
         "--example", "fit_a_line", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    payload = json.loads(r.stdout)
    reports = payload if isinstance(payload, list) else [payload]
    assert reports and all(p["counts"]["error"] == 0 for p in reports), \
        r.stdout


# ---------------------------------------------------------------------------
# desc attr JSON round-trip (tuples keep their type)
# ---------------------------------------------------------------------------

class TestAttrRoundTrip:
    def test_every_attr_type(self):
        from paddle_tpu.framework.desc import (BlockRef, BlocksRef,
                                               OpDesc)

        attrs = {
            "b": True, "i": 7, "f": 0.5, "s": "NCHW", "none": None,
            "li": [1, 2, 3], "lf": [0.1, 0.2], "ls": ["a", "b"],
            "t": (0, 1),
            "lt": [(1, 2), (3, 4)],
            "nested": ((1, [2, 3]), "x"),
            "blk": BlockRef(1), "blks": BlocksRef([1, 2]),
        }
        op = OpDesc(type="anything", inputs={"X": ["a"]},
                    outputs={"Out": ["b"]}, attrs=dict(attrs))
        back = OpDesc.from_dict(json.loads(json.dumps(op.to_dict())))
        assert back.attrs == attrs
        # equality alone can't prove it in older pythons; pin the types
        assert isinstance(back.attrs["t"], tuple)
        assert isinstance(back.attrs["li"], list)
        assert all(isinstance(x, tuple) for x in back.attrs["lt"])
        assert isinstance(back.attrs["nested"], tuple)
        assert isinstance(back.attrs["nested"][0][1], list)

    def test_program_level_roundtrip(self):
        main, _, loss = _fit_a_line()
        from paddle_tpu.framework.desc import ProgramDesc

        s = main.desc.to_json()
        back = ProgramDesc.from_json(s)
        assert back.to_json() == s
