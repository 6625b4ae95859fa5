"""program_trace.py on a small hand-written trace whose numbers can be
checked by hand (data/program_trace.pbtxt says what is in it), and the
readers of the program's own spans, counters and device-time split on
that trace and on evidence that has none."""

import os

import pytest

from benchmarks import program_trace, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3
CELL = "fixture.cell"
NEW_READERS = {
    "step_launch_ms.train": 2.0,
    "step_host_overhead_ms.train": 2.0,
    "input_build_ms.train": 2.0,
    "backward_device_ms.train": 3.0,
    "optimizer_device_ms.train": 3.0,
    "unattributed_device_ms.train": 1.0,
}
COUNTER_READERS = {
    "setup_trace_s.train": 1.5 + 0.25 + 4.0,
    "setup_compile_s.train": 2.0,
    "setup_analysis_s.train": 3.0,
}


def _text():
    with open(os.path.join(DATA, "program_trace.pbtxt")) as f:
        return "".join(ln for ln in f if not ln.startswith("#"))


def _write(root, text):
    """The text trace written out as the profiler would leave it, under
    <root>/<cell>/ as run.py's TRACE_DIR holds a cell's trace."""
    from jax.profiler import ProfileData

    out = root / CELL / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(root)


@pytest.fixture(scope="module")
def trace_root(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("program_trace"), _text())


@pytest.fixture()
def traced(trace_root, monkeypatch):
    """Evidence of a run whose traced steps are the fixture."""
    monkeypatch.setattr(run, "TRACE_DIR", trace_root)
    return {"cell": {"name": CELL}, "trace": None}


def test_roles_sum_to_busy_time(trace_root):
    got = program_trace.reduce_dir(os.path.join(trace_root, CELL))
    assert got["source"] == "metadata_stat"
    (step,) = got["device_steps"]
    assert step["window_s"] == pytest.approx(10 * MS)
    assert step["busy_s"] == pytest.approx(9 * MS)
    assert step["by_role"] == {
        "forward": pytest.approx(2 * MS), "backward": pytest.approx(3 * MS),
        "optimize": pytest.approx(3 * MS),
        "unattributed": pytest.approx(1 * MS)}
    assert sum(step["by_role"].values()) == pytest.approx(step["busy_s"])
    # the outermost pd.<type> names the program op; what has no scope
    # goes by its HLO kind in brackets
    assert step["by_op"] == {
        ("forward", "mul"): pytest.approx(2 * MS),
        ("backward", "fused_chain"): pytest.approx(3 * MS),
        ("optimize", "fused_adam"): pytest.approx(3 * MS),
        ("unattributed", "(copy)"): pytest.approx(1 * MS)}


def test_host_step_anatomy(trace_root):
    got = program_trace.reduce_dir(os.path.join(trace_root, CELL))
    (step,) = got["host_steps"]
    assert step["step"] == 7
    assert step["seconds"] == pytest.approx(4 * MS)
    assert step["phases"] == {
        "prepare": pytest.approx(0.5 * MS), "launch": pytest.approx(2 * MS),
        "bookkeep": pytest.approx(1 * MS),
        "writeback": pytest.approx(0.5 * MS)}
    assert step["self_s"] == pytest.approx(0.0, abs=1e-9)   # they tile it
    assert got["host_spans"]["input_build"] == [pytest.approx(2 * MS)]
    assert got["host_spans"]["input_wait"] == [pytest.approx(0.3 * MS)]
    assert "dispatch" not in got["host_spans"]     # bench.* is not ours
    assert got["host_self"]["step"] == pytest.approx(0.0, abs=1e-9)
    assert got["host_self"]["launch"] == pytest.approx(2 * MS)


def test_idle_gap_goes_to_the_innermost_span(trace_root):
    got = program_trace.reduce_dir(os.path.join(trace_root, CELL))
    # 5 .. 6 ms lies in pd.step and in its pd.launch: the inner one; the
    # feeder thread's input_build is not on the dispatching thread
    assert got["idle_gaps"] == {"launch": pytest.approx(1 * MS)}


def test_gap_outside_every_span_is_none():
    spans = [("step", "python", 0.0, 1.0, None)]
    assert program_trace.label_gaps([(2.0, 2.5), (3.0, 3.00001)], spans) == {
        "none": pytest.approx(0.5), "between ops": pytest.approx(1e-5)}


def test_gap_across_phases_is_split_between_them():
    spans = [("step", "python", 0.0, 1.0, 5),
             ("prepare", "python", 0.0, 0.5, 5),
             ("launch", "python", 0.5, 0.9, 5)]
    assert program_trace.label_gaps([(0.4, 1.2)], spans) == {
        "prepare": pytest.approx(0.1), "launch": pytest.approx(0.4),
        "step": pytest.approx(0.1), "none": pytest.approx(0.2)}


def test_two_threads_of_one_name_are_two_threads(tmp_path):
    """A v5e trace calls every Python thread's line `python3`: the
    feeder's input_build must not nest under the main thread's spans."""
    text = _text().replace('name: "pd-feeder-batch"', 'name: "python"')
    got = program_trace.reduce_dir(
        os.path.join(_write(tmp_path, text), CELL))
    assert got["host_self"]["input_build"] == pytest.approx(2 * MS)
    assert got["idle_gaps"] == {"launch": pytest.approx(1 * MS)}


@pytest.mark.parametrize("op_name, expected", [
    ("jit(fn)/pd_role.forward/pd.mul/dot_general:", ("forward", "mul")),
    ("jit(fn)/pd_role.backward/pd.fused_chain/pd_role.backward/"
     "pd.elementwise_add_grad/reduce_sum:", ("backward", "fused_chain")),
    ("jit(fn)/pd_role.optimize/pd.fused_adam/slice:",
     ("optimize", "fused_adam")),
    ("jit(step)/pd_role.backward/pd.mul_grad/pd.coll.dp_grad/add",
     ("backward", "mul_grad")),
    ("jit(step)/pd.coll.tp_gather/g", ("unattributed", "coll.tp_gather")),
    ("jit(fn)/pd.conv2d/conv2d_stats/pallas_call",
     ("unattributed", "conv2d")),          # a parent: scope, no role
    ("jit(fn)/convert_element_type", ("unattributed", None)),
    ("", ("unattributed", None)),
    (None, ("unattributed", None)),
])
def test_provenance_of(op_name, expected):
    assert program_trace.provenance_of(op_name) == expected


def test_op_name_in_the_instruction_text_is_second_choice(tmp_path):
    """No stat anywhere, `metadata={op_name=...}` in the text: read."""
    text = _text().replace(
        ' stats { metadata_id: 1 str_value: "jit(fn)/pd_role.forward/'
        'pd.mul/dot_general:" }', "").replace(
        "kind=kOutput", 'kind=kOutput, metadata={op_name=\\"jit(fn)/'
        'pd_role.forward/pd.mul/dot_general\\"}')
    for other in ("backward", "optimize"):
        start = text.index(' stats { metadata_id: 1 str_value: "jit(fn)/'
                           "pd_role.%s" % other)
        text = text[:start] + text[text.index(" }", start) + 2:]
    got = program_trace.reduce_dir(
        os.path.join(_write(tmp_path, text), CELL))
    assert got["source"] == "hlo_text"
    (step,) = got["device_steps"]
    assert step["by_role"]["forward"] == pytest.approx(2 * MS)
    assert step["by_role"]["unattributed"] == pytest.approx(7 * MS)


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_reader_on_the_fixture(traced, name):
    reader = run.load_module("layer_metrics", name)
    assert reader.compute(traced) == pytest.approx(NEW_READERS[name])


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_reader_sums_programs(name):
    from paddle_tpu import executor, telemetry

    telemetry.reset()
    executor._book_build("p0", {"trace": 1.5, "lower": 0.25, "compile": 2.0,
                                "analysis": 3.0, "execute": 9.0})
    executor._book_build("p1", {"trace": 4.0})
    reader = run.load_module("layer_metrics", name)
    assert reader.MOVES == "setup_s"
    assert reader.compute({"cell": {"name": CELL}}) == pytest.approx(
        COUNTER_READERS[name])
    telemetry.reset()


@pytest.mark.parametrize("name",
                         sorted(NEW_READERS) + sorted(COUNTER_READERS))
def test_reader_without_evidence_returns_none(tmp_path, monkeypatch, name):
    """No trace under TRACE_DIR/<cell>, no build counter booked (a parent
    program, an untraced run): nothing to read, no error."""
    from paddle_tpu import telemetry

    telemetry.reset()
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    reader = run.load_module("layer_metrics", name)
    assert reader.compute({"cell": {"name": CELL}, "trace": None}) is None


def test_role_readers_without_roles(tmp_path, monkeypatch):
    """A parent program's trace has pd.<type> scopes and no role: the
    named roles read None, and everything is unattributed."""
    text = _text().replace("pd_role.forward/", "").replace(
        "pd_role.backward/", "").replace("pd_role.optimize/", "")
    monkeypatch.setattr(run, "TRACE_DIR", _write(tmp_path, text))
    ev = {"cell": {"name": CELL}, "trace": None}
    assert program_trace.median_role_ms(ev, "backward") is None
    assert program_trace.median_role_ms(ev, "optimize") is None
    assert program_trace.median_role_ms(ev, "unattributed") == \
        pytest.approx(9.0)


def test_command_prints_the_tables(trace_root, capsys):
    assert program_trace.main([os.path.join(trace_root, CELL)]) == 0
    out = capsys.readouterr().out
    assert "provenance of device operations: metadata_stat" in out
    assert "backward / fused_chain" in out
    assert "optimize" in out and "launch" in out
    assert program_trace.main([os.path.join(trace_root, CELL),
                               "--dump"]) == 0
    assert "metadata op_name: jit(fn)/pd_role.forward/pd.mul" in \
        capsys.readouterr().out
    assert program_trace.main([os.path.join(trace_root, "nothing")]) == 1
