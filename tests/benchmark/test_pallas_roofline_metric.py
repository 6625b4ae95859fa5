"""`pallas_roofline_pct.train` (PR 66) on the hand-written two-chip
trace of test_step_account.py, whose one Mosaic call is given a
declaration here; nothing on a parent's account; and the guard that a
declaration moves no accepted reader: `is_membound`, `is_product`,
`is_copy`, `floor_share_pct` and `time_pct` give the same values on the
account with declarations as on the same account without."""

import os

import pytest

from benchmarks import run, step_account
from paddle_tpu import xplane

from .test_step_account import (CELL, DATA, HBM, PEAK, READERS,  # noqa: F401
                                no_accounts_in_process)

METRIC = "pallas_roofline_pct.train"
# what flash_fwd.1 declares: 0.2 ms of products, 0.01 ms of bytes
FLOPS, TRANSCENDENTALS, BYTES = int(0.2e-3 * PEAK), 4096, int(0.01e-3 * HBM)
DECLARATION = (
    ', backend_config={"custom_call_config":{"body":"' + "QUJD" * 512 + '",'
    '"cost_estimate":{"flops":"%d","transcendentals":"%d","bytes_accessed":'
    '"%d","remote_bytes_transferred":"0"},"needs_layout_passes":true}}'
    % (FLOPS, TRANSCENDENTALS, BYTES))


def _module(declared):
    with open(os.path.join(DATA, "step_account.hlo.txt")) as f:
        lines = f.read().split("\n")
    (at,) = [i for i, ln in enumerate(lines) if "%flash_fwd.1 = " in ln]
    if declared:
        lines[at] += DECLARATION
    return "\n".join(lines)


def _write(root, declared):
    """test_step_account._write with the account of `_module(declared)`."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "step_account.pbtxt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    cell = root / CELL
    out = cell / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    instrs = xplane.compact(xplane.hlo_instructions(
        _module(declared), mesh={"fsdp": 2, "tp": 2}))
    xplane._save_accounts(str(cell), [instrs], "TPU v5 lite")
    return str(root)


def _evidence(tmp_path, monkeypatch, declared):
    root = tmp_path / ("declared" if declared else "parent")
    root.mkdir()
    monkeypatch.setattr(run, "TRACE_DIR", _write(root, declared))
    step_account._account.cache_clear()
    return {"cell": {"name": CELL, "trace_steps": 1}, "trace": None}


def test_reader_on_the_fixture(tmp_path, monkeypatch, no_accounts_in_process):
    reader = run.load_module("layer_metrics", METRIC)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "kernels", "%", "train_items_per_s", "device_trace")
    ev = _evidence(tmp_path, monkeypatch, True)
    # flash_fwd.1 takes 0.5 ms on either chip; its floor is its products'
    assert reader.compute(ev) == pytest.approx(100 * 0.2 / 0.5, rel=1e-6)
    (row,) = [r for r in step_account.steps_of(step_account.of_evidence(ev))
              [0]["rows"] if r["name"] == "flash_fwd.1"]
    assert row["kernel_bound"] == "flops" and row["declared_by"] == "kernel"
    assert row["declared_transcendentals"] == TRANSCENDENTALS


def test_reader_reports_nothing_on_a_parents_account(tmp_path, monkeypatch,
                                                     no_accounts_in_process):
    reader = run.load_module("layer_metrics", METRIC)
    # a program whose calls declare nothing: the account is there, the
    # accepted readers read it, this one finds nothing to read
    ev = _evidence(tmp_path, monkeypatch, False)
    assert step_account.of_evidence(ev) is not None
    assert reader.compute(ev) is None
    # no trace, and a program from before the account existed
    assert reader.compute({"cell": {"name": "never.traced",
                                    "trace_steps": 1}, "trace": None}) is None
    monkeypatch.delattr(xplane, "step_account")
    step_account._account.cache_clear()
    assert reader.compute(ev) is None


def test_reader_skips_rows_an_older_account_left_without_the_fields(
        tmp_path, monkeypatch, no_accounts_in_process):
    """The benchmark's files are laid over the parent's checkout too: its
    rows carry no `declared_by` key at all."""
    reader = run.load_module("layer_metrics", METRIC)
    ev = _evidence(tmp_path, monkeypatch, True)
    account = step_account.of_evidence(ev)
    for step in account["steps"]:
        for row in step["rows"]:
            for key in [k for k in row if k.startswith(("declared_",
                                                        "kernel_"))]:
                del row[key]
    monkeypatch.setattr(step_account, "of_evidence", lambda ev: account)
    assert reader.compute(ev) is None


def test_a_declaration_moves_no_accepted_reader(tmp_path, monkeypatch,
                                                no_accounts_in_process):
    """The guard of PR 66: `flops` and `floor_ms` stay None on a Mosaic
    row whatever it declares, so every selector and both folds of
    benchmarks/step_account.py read an account with declarations as they
    read the same account without."""
    selectors = (step_account.is_membound, step_account.is_product,
                 step_account.is_copy)
    seen = {}
    for declared in (False, True):
        ev = _evidence(tmp_path, monkeypatch, declared)
        account = step_account.of_evidence(ev)
        rows = [r for step in account["steps"] for r in step["rows"]]
        (flash,) = {r["name"] for r in rows if r["heavy"] == "flash_fwd"}
        for r in rows:
            if r["name"] == flash:
                assert r["flops"] is None and r["floor_ms"] is None
                assert r["bound"] is None
                assert (r["declared_by"] == "kernel") == declared
        seen[declared] = dict(
            picked=[[(r["name"], select(r)) for r in rows]
                    for select in selectors],
            floors=[step_account.floor_share_pct(ev, select)
                    for select in selectors],
            times=[step_account.time_pct(ev, select)
                   for select in selectors + (lambda r: r["joined"],)],
            readers={name: run.load_module("layer_metrics", name).compute(ev)
                     for name in READERS},
            floor_ms=[(r["name"], r["floor_ms"], r["bound"], r["flops"],
                       r["bytes"], r["ms"]) for r in rows])
    assert seen[True] == seen[False]
    assert seen[True]["readers"] == {
        name: pytest.approx(value) for name, value in READERS.items()}
    assert any(picked for _, picked in seen[True]["picked"][0])
