"""What a run gathers besides the clock: the benchmark's own host spans
(also written into the profiler's trace), the deltas of the program's
counters over the window, and the device's peak memory. The layer
metrics read the one `evidence` dict assembled from these."""

import collections
import contextlib
import time

from benchmarks.trace_reduce import HOST_SPAN_PREFIX


class Spans:
    """Host spans by name: seconds of each occurrence. Each span is also a
    jax.profiler.TraceAnnotation `bench.<name>`, so a traced run has the
    host's doings on the device trace's clock."""

    def __init__(self):
        self.seconds = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds[name].append(time.perf_counter() - t0)


def counters_now():
    """The program's counters and histograms (sum, count) as of now."""
    from paddle_tpu import telemetry
    snap = telemetry.snapshot()
    out = {name: dict(series) for name, series in snap["counters"].items()}
    for name, series in snap["histograms"].items():
        out[name] = {k: {"sum": h["sum"], "count": h["count"]}
                     for k, h in series.items()}
    return out


def counters_delta(before, after):
    """{family: {labels: delta}} (histograms: {sum, count} deltas) of
    every series in `after`; a series absent from `before` started at 0."""
    delta = {}
    for name, series in after.items():
        old = before.get(name, {})
        delta[name] = {}
        for labels, value in series.items():
            if isinstance(value, dict):
                was = old.get(labels, {"sum": 0.0, "count": 0})
                delta[name][labels] = {k: value[k] - was[k] for k in value}
            else:
                delta[name][labels] = value - old.get(labels, 0)
    return delta


def family_total(delta, name, field=None):
    """Sum over the label sets of one family of a counters_delta();
    `field` picks sum or count of a histogram. None if never booked."""
    series = delta.get(name)
    if not series:
        return None
    return sum(v[field] if field else v for v in series.values())


def memory_bytes_now(devices):
    """Bytes held right now on the fullest of `devices`: its buffers
    (`bytes_in_use`) and the scratch its loaded programs reserve
    (`bytes_reserved`; a TPU keeps a step's temporaries there and not
    among the buffers: ResNet-50 bs256 reads 1.1 GB in use and 13.6 GB
    reserved while its step is loaded, chip run PR 23). A traffic kind
    samples this while its work is in flight and reports the highest
    sample as `memory_peak_bytes`; the allocator's own two high-water
    marks cannot be added, because the reference check before the window
    raises the one and the step the other. 0 where the backend reports
    nothing (the CPU)."""
    held = []
    for device in devices:
        stats = device.memory_stats() or {}
        held.append(stats.get("bytes_in_use", 0)
                    + stats.get("bytes_reserved", 0))
    return int(max(held))
