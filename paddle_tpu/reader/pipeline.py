"""Device-side input pipeline: double-buffered host->HBM prefetch.

TPU-native replacement for the reference's decorated-reader chain
(reference: framework/reader.h:28-68 ReaderBase/DecoratedReader,
operators/reader/create_double_buffer_reader_op.cc — a background thread
that stages the next batch on the device while the current one computes;
operators/reader/create_batch_reader_op.cc, create_shuffle_reader_op.cc).

The reference implements each decorator as a C++ reader op chained inside
the program; here the chain is a host-side pipeline object the executor
pulls from. The part that matters for TPU throughput — overlapping the
host->HBM copy of batch N+1 with the compute of batch N — is kept: a
producer thread converts each batch and `jax.device_put`s it into HBM
ahead of consumption, bounded by a small queue (capacity 2 = classic
double buffering)."""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

__all__ = ["DoubleBufferedFeeder"]

_STOP = object()


class DoubleBufferedFeeder:
    """Wrap a batch reader into an iterator of device-resident feed dicts.

    reader: callable returning an iterable of batches (paddle reader
    convention). to_feed: batch -> {name: ndarray/LoDTensor} (e.g.
    DataFeeder.feed, or identity for dict readers). device: target
    jax.Device for the prefetch copies. capacity: queue depth (2 =
    double buffering, the reference's default). window_prefetch: how many
    STACKED next_window windows to build ahead (1 = the classic
    synchronous stack: only the per-batch producer overlaps; >1 moves
    the stack + device_put of up to that many windows onto a background
    thread, so window N+1's host work fully overlaps window N's
    compute)."""

    def __init__(self, reader: Callable[[], Iterable], to_feed=None,
                 device=None, capacity: int = 2, window_prefetch: int = 1):
        self.reader = reader
        self.to_feed = to_feed or (lambda b: b)
        self.device = device
        self.capacity = capacity
        self.window_prefetch = max(1, int(window_prefetch))
        self._thread: Optional[threading.Thread] = None
        self._queue: Optional[queue.Queue] = None
        self._stop = threading.Event()
        # persistent consumer generator for next_window: windows pull from
        # ONE pass rather than restarting the reader per window
        self._consumer = None
        # window-builder thread state (window_prefetch > 1)
        self._wthread: Optional[threading.Thread] = None
        self._wqueue: Optional[queue.Queue] = None
        self._wstop = threading.Event()
        self._wkey = None
        # consumer-side trace context the builder thread adopts
        # (tracing.capture_context handle; None when no span is live)
        self._wctx = None

    def _produce(self):
        from .. import tracing
        try:
            for batch in self.reader():
                if self._stop.is_set():
                    return
                # `input_build`: what one batch costs this thread, the
                # host->device copy included; the wait for a free slot in
                # the queue is not part of it
                with tracing.span("input_build"):
                    feed = self.to_feed(batch)
                    if self.device is not None:
                        feed = {
                            k: (jax.device_put(v, self.device)
                                if isinstance(v, (np.ndarray, np.generic))
                                else v)
                            for k, v in feed.items()}
                self._queue.put(feed)
        except BaseException as e:          # surface in the consumer
            self._queue.put(e)
            return
        self._queue.put(_STOP)

    def __iter__(self):
        import time

        from .. import telemetry, tracing
        stall = telemetry.histogram(
            "input_stall_seconds",
            "consumer wait on the prefetch queue (0 when the producer "
            "keeps ahead — the pipeline's headroom signal)")
        batches = telemetry.counter(
            "input_batches_total", "batches delivered by prefetch feeders")
        self.reset()
        while True:
            with tracing.span("input_wait"):
                t0 = time.perf_counter()
                item = self._queue.get()
                stall.observe(time.perf_counter() - t0)
            if item is _STOP:
                self._thread.join()
                self._thread = None
                return
            if isinstance(item, BaseException):
                self._thread.join()
                self._thread = None
                raise item
            batches.inc()
            yield item

    def next_window(self, k: int, device=None, sparse_slots=None
                    ) -> Dict[str, Any]:
        """Pull the next k batches and stack each feed name into ONE
        [k, ...] array, `jax.device_put` to `device` — the input half of the
        fused multi-step loop (Executor.run_steps). The producer thread
        keeps staging batch k+1, k+2, ... into the bounded queue while the
        device computes the PREVIOUS window, so the host-side stack +
        host->HBM copy of window N+1 overlaps window N's compute.

        For window mode construct the feeder with device=None and pass the
        target device here: one stacked transfer beats k small ones, and
        per-batch device_put in the producer would force the stack back
        through the host. Raises StopIteration at end of pass; a short
        remainder (< k batches, XLA would need a fresh window shape) is
        dropped and counted in input_window_dropped_batches_total.

        With window_prefetch > 1 the stack + device_put happens on a
        background window-builder thread holding up to window_prefetch
        ready windows in a bounded queue — this call just dequeues.

        sparse_slots=[names]: the emb_cache prefetch hook. The return
        becomes `(window, {name: unique-id union over the window})` for
        each listed feed name present, the named slots stay host-side
        numpy (the cache remaps them to slot indices before they ever
        reach the device), and the dedup runs on the builder thread
        under window_prefetch > 1. Batch accounting (dedup, dropped
        remainder) is identical either way — test-pinned."""
        from .. import telemetry
        sparse = tuple(sparse_slots) if sparse_slots else None
        if self.window_prefetch > 1:
            return self._next_window_prefetched(k, device, sparse)
        if self._consumer is None:
            self._consumer = iter(self)
        feeds: List[Dict[str, Any]] = []
        try:
            while len(feeds) < k:
                feeds.append(next(self._consumer))
        except StopIteration:
            self._consumer = None
            self._count_dropped(len(feeds))
            raise StopIteration from None
        from .. import tracing
        with tracing.span("input_window_build", batches=k):
            window = self._stack_window(feeds, device, sparse)
        telemetry.counter(
            "input_windows_total",
            "stacked k-step windows delivered by prefetch feeders").inc()
        return window

    @staticmethod
    def _stack_window(feeds: List[Dict[str, Any]], device,
                      sparse_slots=None):
        names = set(feeds[0])
        if any(set(f) != names for f in feeds[1:]):
            raise ValueError("window batches must share the same feed names")
        window = {n: np.stack([np.asarray(f[n]) for f in feeds])
                  for n in sorted(names)}
        uniq = None
        if sparse_slots is not None:
            uniq = {n: np.unique(window[n]) for n in sparse_slots
                    if n in window}
        if device is not None:
            skip = set(uniq or ())
            window = {n: (v if n in skip else jax.device_put(v, device))
                      for n, v in window.items()}
        return (window, uniq) if sparse_slots is not None else window

    @staticmethod
    def _count_dropped(n: int):
        if n:
            from .. import telemetry
            telemetry.counter(
                "input_window_dropped_batches_total",
                "end-of-pass remainder batches shorter than the "
                "window").inc(n)

    def _produce_windows(self, k: int, device, wq, wstop,
                         sparse_slots=None):
        """Window-builder thread body: pull k batches at a time from the
        batch pipeline, stack + device_put (+ sparse-slot dedup), enqueue
        the ready window. `wq`/`wstop` are locals (not self attributes)
        so a builder abandoned by a (k, device) change can neither
        pollute its replacement's queue nor block forever on its own."""
        def _put(item):
            while not wstop.is_set():
                try:
                    wq.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        from .. import tracing
        try:
            it = iter(self)
            while not wstop.is_set():
                feeds: List[Dict[str, Any]] = []
                try:
                    while len(feeds) < k:
                        feeds.append(next(it))
                except StopIteration:
                    # the drop count rides the stop marker so the CONSUMER
                    # books it at the pull that raises StopIteration —
                    # counting here would race the caller's reads of the
                    # dropped-batches counter (the builder runs ahead)
                    _put((_STOP, len(feeds)))
                    return
                # adopt the consumer thread's captured trace context so
                # the build span is a child of the owning step trace, not
                # an orphan root minted on this thread
                with tracing.adopt(self._wctx):
                    with tracing.span("input_window_build", batches=k):
                        window = self._stack_window(feeds, device,
                                                    sparse_slots)
                if not _put(window):
                    return
        except BaseException as e:        # surface in the consumer
            _put(e)

    def _next_window_prefetched(self, k: int, device, sparse_slots=None):
        from .. import telemetry
        from .. import tracing
        # refreshed every pull: the builder parents its next build span
        # under whatever step trace is live on the consumer right now
        self._wctx = tracing.capture_context()
        key = (k, device, sparse_slots)
        if self._wthread is None or self._wkey != key:
            self._stop_windows()
            self._wkey = key
            self._wstop = threading.Event()
            self._wqueue = queue.Queue(maxsize=self.window_prefetch)
            self._wthread = threading.Thread(
                target=self._produce_windows,
                args=(k, device, self._wqueue, self._wstop, sparse_slots),
                daemon=True, name="pd-feeder-window")
            self._wthread.start()
        item = self._wqueue.get()
        if type(item) is tuple and len(item) == 2 and item[0] is _STOP:
            self._count_dropped(item[1])
            self._wthread.join()
            self._wthread = None
            self._wkey = None
            raise StopIteration
        if isinstance(item, BaseException):
            self._wthread.join()
            self._wthread = None
            self._wkey = None
            raise item
        telemetry.counter(
            "input_windows_total",
            "stacked k-step windows delivered by prefetch feeders").inc()
        return item

    def _stop_windows(self):
        # the builder itself resets the nested batch pipeline through
        # iter(self) -> reset() -> stop(); never self-join from there
        if self._wthread is None or \
                self._wthread is threading.current_thread():
            return
        self._wstop.set()
        try:                      # unblock a builder stuck on batch get()
            self._queue.put_nowait(_STOP)
        except (queue.Full, AttributeError):
            pass
        try:                      # unblock a builder stuck on window put()
            while True:
                self._wqueue.get_nowait()
        except queue.Empty:
            pass
        self._wthread.join(timeout=5)
        self._wthread = None
        self._wkey = None

    def reset(self):
        # NOTE: does not touch _consumer — __iter__'s generator body calls
        # reset() on its first next(), which runs AFTER next_window stored
        # the generator; next_window clears it itself at end of pass
        self.stop()
        self._stop.clear()
        self._queue = queue.Queue(maxsize=self.capacity)
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="pd-feeder-batch")
        self._thread.start()

    def stop(self):
        self._stop_windows()
        if self._thread is not None:
            self._stop.set()
            try:                      # unblock a producer stuck on put()
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5)
            self._thread = None
