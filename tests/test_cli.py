"""CLI (`python -m paddle_tpu`) parity with `paddle train` (reference:
TrainerMain.cpp:32-64, submit_local.sh.in)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = '''
import numpy as np
import paddle_tpu as fluid

def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return {"main_program": main, "startup_program": startup,
            "feed_order": ["x", "y"], "loss": loss, "fetch": [pred]}

_rng = np.random.RandomState(0)
_w = _rng.randn(4, 1).astype(np.float32)

def train_reader():
    rng = np.random.RandomState(1)
    for _ in range(192):
        x = rng.randn(4).astype(np.float32)
        yield x, (x @ _w).astype(np.float32)
'''


def run_cli(args, cwd):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "paddle_tpu"] + args,
                          capture_output=True, text=True, cwd=cwd, env=env,
                          timeout=300)


class TestCLI:
    def test_version(self, tmp_path):
        r = run_cli(["version"], str(tmp_path))
        assert r.returncode == 0 and "paddle_tpu" in r.stdout

    def test_train_save_infer_roundtrip(self, tmp_path):
        cfg = tmp_path / "conf.py"
        cfg.write_text(CONFIG)
        save_dir = tmp_path / "model"
        ckpt_dir = tmp_path / "ckpt"
        r = run_cli(["train", f"--config={cfg}", "--epochs=3",
                     "--batch-size=32", f"--save-dir={save_dir}",
                     f"--checkpoint-dir={ckpt_dir}"], str(tmp_path))
        assert r.returncode == 0, r.stderr[-1500:]
        assert "epoch 2" in r.stdout and "saved inference model" in r.stdout
        # training should actually have learned the linear map
        losses = [float(l.split("loss=")[1].split(" ")[0].rstrip(")"))
                  for l in r.stdout.splitlines() if "loss=" in l]
        assert losses[-1] < 0.05, r.stdout

        # resume path: epoch counter continues from checkpoint
        r2 = run_cli(["train", f"--config={cfg}", "--epochs=4",
                      f"--checkpoint-dir={ckpt_dir}", "--resume"],
                     str(tmp_path))
        assert r2.returncode == 0, r2.stderr[-1500:]
        assert "resumed from checkpoint epoch 2" in r2.stdout
        assert "epoch 3" in r2.stdout and "epoch 0" not in r2.stdout

        # infer on the saved model
        xs = np.random.RandomState(3).randn(5, 4).astype(np.float32)
        np.savez(tmp_path / "batch.npz", x=xs)
        r3 = run_cli(["infer", f"--model-dir={save_dir}",
                      f"--input={tmp_path / 'batch.npz'}"], str(tmp_path))
        assert r3.returncode == 0, r3.stderr[-1500:]
        assert "shape=[5, 1]" in r3.stdout

    def test_time_job(self, tmp_path):
        cfg = tmp_path / "conf.py"
        cfg.write_text(CONFIG)
        r = run_cli(["time", f"--config={cfg}", "--steps=5"], str(tmp_path))
        assert r.returncode == 0, r.stderr[-1500:]
        assert "steps/s" in r.stdout


class TestPerfCLI:
    def test_perf_smoke(self, tmp_path):
        # env probe overrides keep the run hermetic and fast (no
        # sustained-matmul / bandwidth measurement in CI)
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_SUSTAINED_TFLOPS="0.5",
                   PADDLE_TPU_HBM_GBPS="20")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "perf", "--smoke",
             "--steps=2", "--batch=8"],
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
            timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr[-1500:]
        out = r.stdout
        # every instruction of the smoke's step is named by its account:
        # XLA's own count of the step stands beside the executed one
        assert "[crosscheck]" in out and "executed" in out
        assert "[waterfall]" in out and "[roofline]" in out
        assert "[mfu]" in out
        rows = [ln.split() for ln in out.splitlines()
                if ln.startswith("[device] ")]
        data_rows = [t for t in rows
                     if len(t) >= 8 and t[3].endswith("%")]
        assert data_rows, out
        # every row: op, ms, frac, GFLOPs, MB, TF/s, AI, bound verdict,
        # floor share, and the op instance (@position in the block)
        assert all(t[8] in ("compute", "memory", "unattributed")
                   for t in data_rows), data_rows
        assert any(t[-1].startswith("@") for t in data_rows), data_rows
        # fractions (incl. the unattributed pool) sum to the device total
        total = sum(float(t[3].rstrip("%")) for t in data_rows)
        assert abs(total - 100.0) < 1.0, out
        # at least one attributed row carries real numbers end to end
        attributed = [t for t in data_rows
                      if t[8] in ("compute", "memory")]
        assert attributed, out
        assert all(t[4] != "-" and t[6] != "-" for t in attributed), out

    def test_perf_smoke_json(self, tmp_path):
        import json as json_mod
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_SUSTAINED_TFLOPS="0.5",
                   PADDLE_TPU_HBM_GBPS="20")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "perf", "--smoke",
             "--steps=2", "--batch=8", "--json"],
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
            timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr[-1500:]
        report = json_mod.loads(r.stdout)
        assert report["rows"] and report["mapped"]
        for row in report["rows"]:
            assert {"op", "at", "ps", "frac", "flops", "bytes", "tflops",
                    "bound", "efficiency"} <= set(row)
        assert report["ridge_intensity"] == 25.0
        assert report.get("device_duty_cycle") is not None


def test_serve_smoke_prints_a_line_a_phase_and_a_summary(tmp_path):
    """`serve --smoke`: one JSON line for the normal phase, one for the
    overload phase at twice the clients, then the engine/batcher
    summary."""
    import json
    r = run_cli(["serve", "--smoke", "--clients", "2", "--requests", "3"],
                str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr[-1500:]
    normal, overload, summary = [json.loads(ln)
                                 for ln in r.stdout.splitlines()
                                 if ln.startswith("{")]
    for phase, line, clients in (("normal", normal, 2),
                                 ("overload", overload, 4)):
        assert (line["phase"], line["clients"]) == (phase, clients)
        assert {"p50_ms", "p99_ms", "qps", "shed_fraction", "bucket_hits",
                "goodput_fraction"} <= set(line), line
        assert 0.0 < line["p50_ms"] <= line["p99_ms"]
        assert line["requests"] == 3 * clients and line["errors"] == 0
    assert summary["model"] == "smoke"
    assert summary["batcher"]["submitted"] == 18
    assert sum(summary["engine"]["bucket_runs"].values()) >= 1


class TestCheckgrad:
    def test_checkgrad_passes(self, tmp_path):
        cfg = tmp_path / "conf.py"
        cfg.write_text(CONFIG)
        r = run_cli(["checkgrad", "--config", str(cfg), "--samples", "3"],
                    str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "checkgrad PASSED" in r.stdout, r.stdout

    def test_checkgrad_catches_wrong_grad(self, tmp_path):
        # a config whose loss path hides a stop_gradient: analytic grad is
        # legitimately zero for w2 but numeric is not -> checkgrad FAILs
        bad = CONFIG.replace(
            'pred = fluid.layers.fc(input=x, size=1)',
            'h = fluid.layers.fc(input=x, size=4)\n'
            '        h.stop_gradient = True\n'
            '        pred = fluid.layers.fc(input=h, size=1)')
        cfg = tmp_path / "bad.py"
        cfg.write_text(bad)
        r = run_cli(["checkgrad", "--config", str(cfg), "--samples", "3"],
                    str(tmp_path))
        # either the program refuses (no grads for the frozen slice) or
        # the check flags the mismatch — silence is the only failure
        assert r.returncode != 0, r.stdout + r.stderr


class TestFpTrap:
    def test_trap_fp_raises_on_nan(self, tmp_path):
        script = tmp_path / "nan.py"
        script.write_text(
            "import numpy as np\n"
            "import paddle_tpu as fluid\n"
            "x = fluid.layers.data(name='x', shape=[2], dtype='float32')\n"
            "y = fluid.layers.log(x)   # log(-1) -> NaN\n"
            "exe = fluid.Executor(fluid.CPUPlace())\n"
            "exe.run(fluid.default_startup_program())\n"
            "out, = exe.run(feed={'x': np.array([[-1.0, 1.0]],"
            " np.float32)}, fetch_list=[y])\n"
            "print('got', out)\n")
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_TRAP_FP="1")
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode != 0, r.stdout      # trapped, not silent NaN
        assert "nan" in (r.stdout + r.stderr).lower()
