"""Lag-N pipelined metrics fetching with real per-step wall times.

Round-1 measured the timed loop two ways and both were wrong in one
direction or the other: blocking on the current step's metrics every
iteration costs a full host<->device round-trip per step and leaves the
device queue empty while the host reads, while fetching one flat window average made the
printed uncertainty/jitter constants (always 0.0). This module gives both
honest per-step statistics and full dispatch pipelining:

* Each dispatched step's metrics enter a lag-``N`` ring; an async
  device-to-host copy is started immediately so the transfer runs as soon
  as the step completes on device.
* ``N`` iterations later the value is read (by then the copy has landed, so
  the read does not stall the dispatch queue), and the wall-clock interval
  between consecutive reads is recorded. At steady state the loop is
  rate-limited by step completion, so these arrival intervals ARE the real
  per-step device times -- the pipelined analog of the reference's
  per-sess.run timing (ref: benchmark_cnn.py:786-884 benchmark_one_step,
  :887-902 get_perf_timing).
* Host-side pauses that are not step work (checkpoint saves, mid-train
  eval) are excluded from the next interval via ``note_aux_time`` -- the
  analog of the reference keeping checkpoint time out of its step timer.

Chunked dispatches (--steps_per_dispatch=K): one ``push`` carries K
steps' stacked metrics (``count=K``). The ring and the lag count
DISPATCHES, the resolution unstacks the K per-step metric trees host-side
so every printed value is still the exact value for its step. Timing is
HONEST at chunk granularity only: the host observes one arrival per
chunk, so each of the K steps is attributed interval/K and the printed
uncertainty/jitter measure chunk-to-chunk variation, not within-chunk
variation (within a chunk there is no host-visible boundary to time).
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from kf_benchmarks_tpu import tracing


class CompletedStep:
  """A resolved step: its 1-based index, host metrics, and wall interval.

  ``chunk_len``/``chunk_interval`` carry the dispatch this step arrived
  in: (1, interval) for single-step dispatches; for a K-step chunk every
  member reports the chunk's size and full wall interval (its own
  ``interval`` is the amortized 1/K share). ``chunk_end`` is True on the
  dispatch's final step, so per-dispatch consumers (chunk timing rows)
  count each dispatch once.
  """

  __slots__ = ("index", "metrics", "interval", "chunk_len",
               "chunk_interval", "chunk_end")

  def __init__(self, index: int, metrics: Dict[str, Any], interval: float,
               chunk_len: int = 1, chunk_interval: Optional[float] = None,
               chunk_end: bool = True):
    self.index = index
    self.metrics = metrics
    self.interval = interval
    self.chunk_len = chunk_len
    self.chunk_interval = (interval if chunk_interval is None
                           else chunk_interval)
    self.chunk_end = chunk_end


def _start_async_copy(metrics) -> None:
  for leaf in jax.tree.leaves(metrics):
    copy = getattr(leaf, "copy_to_host_async", None)
    if copy is not None:
      copy()


class MetricsPipeline:
  """Keeps ``lag`` dispatches in flight; resolves older ones without
  stalling.

  Usage:
    pipe = MetricsPipeline(lag=2)
    for i in range(num_batches):
      state, metrics = step(...)
      for done in pipe.push(i + 1, metrics):
        handle(done)            # done.interval is a real per-step time
    for done in pipe.flush():
      handle(done)

  A chunked dispatch covering steps ``index-count+1 .. index`` pushes its
  stacked metrics once with ``count=K``; resolution yields K
  CompletedSteps in step order.
  """

  def __init__(self, lag: int = 2):
    self.lag = max(0, lag)
    self._ring: "collections.deque[Tuple[int, Any, int]]" = \
        collections.deque()
    self._last_time: Optional[float] = None
    self._aux_time = 0.0

  def reset_clock(self) -> None:
    """Restart interval timing (after a drain, reshape, or loop start)."""
    self._last_time = time.time()
    self._aux_time = 0.0

  def note_aux_time(self, seconds: float) -> None:
    """Exclude ``seconds`` of non-step host work from the next interval."""
    self._aux_time += max(0.0, seconds)

  def _resolve(self, index: int, metrics, count: int) -> \
      List[CompletedStep]:
    # The one place the timed loop blocks on the device: a live span
    # (run trace + profiler), so a reader can take it out of the host's
    # own time per step.
    with tracing.active().span("fetch", "metrics", step=index):
      host = jax.device_get(metrics)
    now = time.time()
    if self._last_time is None:
      self._last_time = now
      interval = 0.0
    else:
      interval = max(1e-9, now - self._last_time - self._aux_time)
    self._last_time = now
    self._aux_time = 0.0
    if count <= 1:
      return [CompletedStep(index, host, interval)]
    # Unstack the chunk host-side: leaf j of step j is row j of each
    # stacked (K,)-leading leaf; unstacked leaves (a metric that is not
    # per-step) pass through unchanged. Each step gets the amortized
    # interval share (see module docstring on chunk-window timing).
    per = interval / count

    def pick(j):
      def slice_leaf(x):
        arr = np.asarray(x)
        if arr.ndim and arr.shape[0] == count:
          return arr[j]
        return x
      return jax.tree.map(slice_leaf, host)

    return [CompletedStep(index - count + 1 + j, pick(j), per,
                          chunk_len=count, chunk_interval=interval,
                          chunk_end=(j == count - 1))
            for j in range(count)]

  def push(self, index: int, metrics,
           count: int = 1) -> List[CompletedStep]:
    """Add a just-dispatched step (or K-step chunk ending at ``index``);
    return any steps whose dispatch left the ring."""
    _start_async_copy(metrics)
    self._ring.append((index, metrics, count))
    done = []
    while len(self._ring) > self.lag:
      done.extend(self._resolve(*self._ring.popleft()))
    return done

  def flush(self) -> List[CompletedStep]:
    """Resolve everything in flight (end of loop or forced sync point)."""
    done = []
    while self._ring:
      done.extend(self._resolve(*self._ring.popleft()))
    return done

  def __len__(self) -> int:
    return len(self._ring)
