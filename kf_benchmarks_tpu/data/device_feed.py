"""Double-buffered host->device input feed.

The analog of the reference's per-device StagingArea / MultiDeviceIterator
prefetch chain (ref: scripts/tf_cnn_benchmarks/benchmark_cnn.py:2572-2600
CPU staging, :2993-3006 gpu_compute_stage H2D boundary;
preprocessing.py:368-399 MultiDeviceIterator): a background thread pulls
host batches from the preprocessor iterator and ``jax.device_put``s them
onto the global batch sharding ahead of the step loop, so the H2D copy
overlaps the previous step's compute.

Chunk mode (--steps_per_dispatch=K): ``chunk=K`` makes the worker stage K
host batches at a time -- stacked on a new leading axis host-side and
transferred as ONE (K, batch, ...) array onto the chunk sharding -- so a
K-step scanned dispatch finds its whole input staged and never waits on
H2D mid-scan. The queue then counts chunks (``prefetch`` stays in
batches and is rounded up to whole chunks), keeping roughly the same
number of batches in flight as the unchunked feed. A stream that ends
mid-chunk yields a final partial stack (leading axis < K); the consumer
runs those through the single-step program.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from kf_benchmarks_tpu import metrics as metrics_lib
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.parallel import mesh as mesh_lib


class DeviceFeeder:
  """Prefetching device-transfer iterator (depth-``prefetch`` pipeline).

  Instrumented: every ``__next__`` records the consumer's blocked-wait
  time and the queue depth it found, so ``stats()`` can answer the
  question the reference never measured about its StagingArea chain --
  does the prefetch actually OVERLAP host work with device compute?
  ``feed_stall_fraction`` (consumer wait / wall time across the consume
  window) ~0 means the feed hides behind the step; ~1 means the loop is
  input-bound and ``--input_prefetch_depth`` (or more host threads) is
  the lever. Rides the benchmark stats and the bench JSON line.
  """

  def __init__(self, host_iterator: Iterator, sharding,
               prefetch: int = 2, chunk: int = 1):
    self._host_iterator = host_iterator
    self._sharding = sharding
    self._chunk = max(1, chunk)
    self.prefetch_batches = max(1, prefetch)
    depth = -(-self.prefetch_batches // self._chunk)  # batches -> chunks
    self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    self._stop = threading.Event()
    self._error: Optional[BaseException] = None
    # Consumer-side instrumentation (all under the consumer thread; no
    # locking needed -- __next__ is single-consumer by contract).
    self._wait_s = 0.0
    self._fetches = 0
    self._depth_sum = 0
    self._depth_max = 0
    self._window_start: Optional[float] = None
    self._window_end: Optional[float] = None
    self._thread = threading.Thread(target=self._worker, daemon=True,
                                    name="device-feeder")
    self._thread.start()

  def _pull(self, it):
    """Next host item: one batch, or a chunk of up to ``chunk`` batches
    stacked on a new leading axis. None at stream end."""
    if self._chunk == 1:
      try:
        return next(it)
      except StopIteration:
        return None
    batches = []
    while len(batches) < self._chunk and not self._stop.is_set():
      try:
        batches.append(next(it))
      except StopIteration:
        break
    if not batches:
      return None
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)

  def _worker(self) -> None:
    try:
      it = iter(self._host_iterator)
      # Check the stop flag BEFORE pulling: pulling is where the host
      # preprocessing work happens, so a stopped feeder must not decode
      # another full global batch just to discard it.
      while not self._stop.is_set():
        # Run-trace feed lane (tracing.py active session; no-op sink
        # otherwise): "fetch" is the host preprocessing pull, "h2d" the
        # device_put -- the producer half of the overlap question
        # stats() answers from the consumer side.
        trace = tracing.active()
        with trace.span("feed", "fetch", chunk=self._chunk):
          batch = self._pull(it)
        if batch is None:
          break
        with trace.span("feed", "h2d"):
          device_batch = mesh_lib.put_batch(batch, self._sharding)
        while not self._stop.is_set():
          try:
            self._queue.put(device_batch, timeout=0.5)
            break
          except queue.Full:
            continue
      if not self._stop.is_set():
        self._queue.put(None)
    except BaseException as e:  # surfaced on the consumer side
      self._error = e

  def __iter__(self):
    return self

  def __next__(self):
    t0 = time.monotonic()
    trace = tracing.active()
    if self._window_start is None:
      self._window_start = t0
    depth = self._queue.qsize()
    # Consumer-wait lane (tracing.py): the blocking part of every
    # fetch is a live span, so a profiler capture shows what the loop
    # was waiting for while the device sat idle. That is EVERY wait the
    # consumer sat through, the terminal ones too (the end-of-stream
    # sentinel's drain, a wait that ends in the worker's error): the
    # host was blocked there like anywhere else, so the span's totals
    # count one more than the delivered fetches on a finite stream,
    # while the feed_wait SAMPLE below keeps to delivered batches. Poll
    # with a timeout so a worker error is surfaced even when the queue
    # is full at error time and the sentinel could not be enqueued.
    with trace.span("feed", "wait", queue_depth=depth * self._chunk):
      while True:
        try:
          item = self._queue.get(timeout=0.5)
          break
        except queue.Empty:
          if self._error is not None:
            raise self._error
          if not self._thread.is_alive():
            raise StopIteration
    if item is None:
      # End-of-stream sentinel: not a delivered batch -- counting its
      # (terminal-drain) wait would read a healthy finite stream as
      # input-bound.
      if self._error is not None:
        raise self._error
      raise StopIteration
    now = time.monotonic()
    waited = now - t0
    self._wait_s += waited
    self._window_end = now
    self._fetches += 1
    # Percentile sample (tracing.py): every delivered fetch feeds the
    # feed_wait p50/p90/p99.
    trace.add_sample("feed_wait", waited)
    # Live metric lanes (metrics.py active registry; no-op sink when no
    # endpoint/registry session is active): the /metrics scrape shows
    # queue depth and the feed-wait distribution WHILE the run feeds,
    # not just the run-end stats() aggregate.
    registry = metrics_lib.active()
    registry.inc("fetches")
    registry.set("queue_depth", depth * self._chunk)
    registry.observe("feed_wait_s", waited)
    # Queue depth in BATCH units (the queue itself holds chunks when
    # chunk > 1), so the number reads against prefetch_batches.
    self._depth_sum += depth * self._chunk
    self._depth_max = max(self._depth_max, depth * self._chunk)
    return item

  def stats(self) -> dict:
    """Consumer-side feed stats: total blocked wait, the wall window
    spanning the fetches, the stall fraction (wait / window -- the
    fraction of loop wall the feed failed to hide), and queue depth at
    fetch time (mean/max; depth ~prefetch means the worker keeps up).
    The first fetch's wait covers pipeline warm-fill and is counted --
    report stats over a run long enough to amortize it."""
    window = ((self._window_end - self._window_start)
              if self._fetches and self._window_end is not None else 0.0)
    return {
        "fetches": self._fetches,
        "consumer_wait_s": self._wait_s,
        "window_s": window,
        "feed_stall_fraction": (self._wait_s / window if window > 0
                                else None),
        "queue_depth_mean": (self._depth_sum / self._fetches
                             if self._fetches else None),
        "queue_depth_max": self._depth_max,
        "prefetch_batches": self.prefetch_batches,
    }

  def stop(self) -> None:
    self._stop.set()
    # Drain so the worker unblocks, then join it and close the host
    # iterator so generator cleanup (e.g. the preprocessor's thread pool
    # shutdown in its finally block) runs deterministically rather than
    # at GC time.
    while self._thread.is_alive():
      try:
        while True:
          self._queue.get_nowait()
      except queue.Empty:
        pass
      self._thread.join(timeout=0.1)
    close = getattr(self._host_iterator, "close", None)
    if close is not None:
      close()
