"""benchmarks/step_account.py and the five readers of the step's account
by instruction (PR 35) on a hand-written two-chip trace joined to a
hand-written compiled module, whose numbers can be checked by hand
(data/step_account.pbtxt and data/step_account.hlo.txt say what is in
them), and on evidence that has none."""

import json
import os

import pytest

from benchmarks import run, step_account
from paddle_tpu import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "fixture.cell"
PEAK, HBM = 197e12, 819e9      # paddle_tpu/chip.py, "TPU v5 lite"

PRODUCT_FLOPS = 2 * 8 * 128 * 256 * 512 + 8 * 128 * 512   # + the bias add
# x, the cast weights and the output; the bias was prefetched into VMEM
# (`S(1)` in its layout) by copy-start.1 and is not read from HBM here
PRODUCT_BYTES = (8 * 128 * 256 + 256 * 512 + 8 * 128 * 512) * 2
UPDATE_BYTES = 3 * 256 * 512 * 4
READERS = {
    # floor of fusion.1 over its 1 ms, on either chip
    "xla_product_roofline_pct.train":
        100 * max(PRODUCT_FLOPS / PEAK, PRODUCT_BYTES / HBM) / 1e-3,
    # convert.1 0.1 + copy-done.1 0.1 + fusion.3 1.0 of 4.9 and of 4.4 ms
    "copy_time_pct.train": 100 * (1.2 / 4.9 + 1.2 / 4.4) / 2,
    # fusion.2: three 512 KB arrays over its 0.5 ms
    "membound_roofline_pct.train": 100 * (UPDATE_BYTES / HBM) / 0.5e-3,
    # all-reduce.1: 1.0 ms on chip 0, 0.5 on chip 1
    "tp_collective_ms.train": 0.75,
    # all-gather-start.1 0.1 + all-gather-done.1 0.4, on both
    "fsdp_collective_ms.train": 0.5,
}


def _module():
    with open(os.path.join(DATA, "step_account.hlo.txt")) as f:
        return f.read()


def _write(root, accounts=True):
    """The text trace written out as the profiler would leave it under
    <root>/<cell>/, with the account an earlier reader in the traced
    process saved beside it."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "step_account.pbtxt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    cell = root / CELL
    out = cell / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    if accounts:
        instrs = xplane.compact(xplane.hlo_instructions(
            _module(), mesh={"fsdp": 2, "tp": 2}))
        xplane._save_accounts(str(cell), [instrs], "TPU v5 lite")
    return str(root)


@pytest.fixture()
def no_accounts_in_process(monkeypatch):
    monkeypatch.setattr(xplane, "_ACCOUNTS", type(xplane._ACCOUNTS)())
    step_account._account.cache_clear()
    yield
    step_account._account.cache_clear()


@pytest.fixture()
def traced(tmp_path, monkeypatch, no_accounts_in_process):
    """Evidence of a two-chip run whose traced step is the fixture."""
    monkeypatch.setattr(run, "TRACE_DIR", _write(tmp_path))
    return {"cell": {"name": CELL, "trace_steps": 1}, "trace": None}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_the_fixture(traced, metric):
    reader = run.load_module("layer_metrics", metric)
    assert reader.SOURCE == "device_trace"
    assert reader.MOVES == "train_items_per_s"
    assert reader.compute(traced) == pytest.approx(READERS[metric])


def test_no_share_over_100_and_axes_sum_to_the_exposed_time(traced):
    for metric in ("xla_product_roofline_pct.train", "copy_time_pct.train",
                   "membound_roofline_pct.train"):
        assert 0 < run.load_module("layer_metrics", metric).compute(
            traced) <= 100
    by_axis = step_account.collective_ms_by_axis(traced)
    assert by_axis == {"tp": pytest.approx(0.75),
                       "fsdp": pytest.approx(0.5),
                       "fsdp+tp": pytest.approx(0.2)}
    # what exposed_collective_ms.train reads off the same trace: every
    # instruction named after a collective, a chip and traced step
    exposed = run.load_module("layer_metrics", "exposed_collective_ms.train")
    names = {}
    for step in xplane.device_steps(os.path.join(run.TRACE_DIR, CELL)):
        for name, _, dur, _ in step["events"]:
            names[name] = names.get(name, 0.0) + dur / 1e12 / 2
    ev = dict(traced, trace={"busy_s": 1.0,
                             "device_ops": [[n, s] for n, s in names.items()]})
    assert exposed.compute(ev) == pytest.approx(sum(by_axis.values()))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_without_an_account_reports_nothing(tmp_path, monkeypatch,
                                                   no_accounts_in_process,
                                                   metric):
    """A parent program keeps no account: the trace's events are joined to
    nothing and no reader invents a number; nor without a trace."""
    reader = run.load_module("layer_metrics", metric)
    monkeypatch.setattr(run, "TRACE_DIR", _write(tmp_path, accounts=False))
    assert reader.compute({"cell": {"name": CELL, "trace_steps": 1},
                           "trace": None}) is None
    assert reader.compute({"cell": {"name": "never.traced",
                                    "trace_steps": 1}, "trace": None}) is None
    # a program from before this reader existed
    monkeypatch.delattr(xplane, "step_account")
    step_account._account.cache_clear()
    assert reader.compute({"cell": {"name": CELL, "trace_steps": 1},
                           "trace": None}) is None


def test_account_of_the_process_wins_and_is_saved_beside_the_trace(
        tmp_path, monkeypatch, no_accounts_in_process):
    root = _write(tmp_path, accounts=False)
    cell_dir = os.path.join(root, CELL)
    xplane.remember_account(
        "jit_fn", xplane.compact(xplane.hlo_instructions(
            _module(), mesh={"fsdp": 2, "tp": 2})), program="p1")
    account = xplane.step_account(cell_dir)
    assert account["joined"] == pytest.approx(1.0)
    with open(os.path.join(cell_dir, xplane.ACCOUNT_FILE)) as f:
        saved = json.load(f)
    assert "operands" not in saved["fields"] and len(saved["accounts"]) == 1
    # later, from the directory alone
    xplane.forget_accounts()
    again = xplane.step_account(cell_dir)
    assert [r["name"] for r in again["steps"][0]["rows"]] == \
        [r["name"] for r in account["steps"][0]["rows"]]
    assert again["steps"][0]["rows"][0]["flops"] == \
        account["steps"][0]["rows"][0]["flops"]


def test_tables_print_from_the_directory_alone(tmp_path, capsys,
                                               no_accounts_in_process):
    root = _write(tmp_path)
    assert step_account.main([os.path.join(root, CELL), "5"]) == 0
    out = capsys.readouterr().out
    assert "2 chips, 1 traced steps a chip" in out
    assert "joined to an account: 100.00% of busy time" in out
    # the product, by instruction, with its op instance and operand shapes
    (product,) = [ln for ln in out.splitlines()
                  if ln.startswith("fusion.1 [convolution]")]
    assert "for/block:mul@3" in product
    assert "bf16[8,128,256] * bf16[256,512,1]" in product
    # the collectives by axis, and the split of the exposed time
    assert any(ln.split()[:3] == ["tp", "all-reduce", "collective"]
               for ln in out.splitlines())
    assert "= tp 0.750 + fsdp 0.500 + fsdp+tp 0.200" in out
    assert step_account.main([str(tmp_path / "nothing")]) == 1
    assert step_account.main([]) == 2
