"""trace_reduce.py on a small hand-written trace whose numbers can be
checked by hand (data/small_trace.pbtxt says what is in it), and the
peaks table's refusal of a device nobody wrote down."""

import os

import pytest

from benchmarks import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    """The text trace written out as the profiler would leave it."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    root = tmp_path_factory.mktemp("trace")
    out = root / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(root)


def test_busy_window_ops_and_gaps(trace_dir):
    got = trace_reduce.reduce_dir(trace_dir)
    assert got["window_s"] == pytest.approx(11 * MS)
    # 0..6 and 9..10 and 10.01..11: the overlap counts once
    assert got["busy_s"] == pytest.approx(7.99 * MS)
    ops = dict(got["device_ops"])
    # conv2d.1 is charged 3 of its 4 ms (fusion.7 ran through the 4th),
    # conv2d.2 1 ms; the fusions 3 and 0.99 ms: together the busy time
    assert ops["conv2d"] == pytest.approx(4 * MS)
    assert ops["fusion"] == pytest.approx(3.99 * MS)
    assert sum(ops.values()) == pytest.approx(got["busy_s"])
    assert got["device_ops"][0][0] == "conv2d"          # longest first
    assert got["kernel_s"] == pytest.approx(4 * MS)     # the Mosaic calls
    gaps = dict(got["idle_gaps"])
    assert gaps == {"feed": pytest.approx(3 * MS),
                    "between ops": pytest.approx(0.01 * MS)}
    assert got["busy_s"] + sum(gaps.values()) == pytest.approx(
        got["window_s"])


def test_gap_no_span_covers_is_labelled_none():
    got = trace_reduce.reduce_events(
        {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 2.0, 3.0)]},
        host_spans=[("fetch", 5.0, 6.0)])
    assert got["idle_gaps"] == [["none", pytest.approx(1.0)]]
    assert got["busy_s"] == pytest.approx(2.0)
    assert got["kernel_s"] == 0


def test_two_chips_are_averaged():
    got = trace_reduce.reduce_events(
        {"/device:TPU:0": [("a", 0.0, 4.0)],
         "/device:TPU:1": [("a", 0.0, 1.0), ("a", 3.0, 4.0)]},
        host_spans=[], kernels=["a"])
    assert got["busy_s"] == pytest.approx(3.0)
    assert got["window_s"] == pytest.approx(4.0)
    assert got["kernel_s"] == pytest.approx(3.0)


def test_a_trace_without_device_operations_reduces_to_none(tmp_path):
    assert trace_reduce.reduce_dir(str(tmp_path)) is None


def test_labels():
    text = ('%conv2d_grad_filter.51 = f32[1,1,1024,2048]{3,2,1,0} '
            'custom-call(bf16[2]{0} %x), custom_call_target='
            '"tpu_custom_call"')
    assert trace_reduce.op_label(text) == "conv2d_grad_filter"
    assert trace_reduce.is_mosaic(text)
    assert trace_reduce.op_label("%copy-done.3 = f32[2]{0} copy-done("
                                 "%copy-start.3)") == "copy-done"
    assert not trace_reduce.is_mosaic("%fusion.1 = f32[2]{0} fusion(%x)")


def test_peaks_table_refuses_an_unknown_device():
    assert trace_reduce.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(trace_reduce.UnknownDeviceError):
        trace_reduce.peak_flops("TPU v99")
