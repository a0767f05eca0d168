"""Runtime training-health telemetry: in-step device stats, flight
recorder, stall watchdog.

TPU-native-only subsystem with no reference analog: the reference's
observability is post-hoc -- a Chrome trace of one step, tfprof top-ops
and tiered summaries (SURVEY 5.1/9) -- and nothing there watches a
RUNNING job. The failure modes that matter (a stalled dispatcher, a
preemption, fp16 loss-scale collapse, non-finite gradients) all strike
mid-run, so this layer follows the
MLPerf structured-run-logging norm (Mattson et al., "MLPerf Training
Benchmark"): every step leaves an auditable record, and anomalies dump
a post-mortem window instead of a dead terminal.

Three cooperating pieces:

* In-step health stats: ``health_partials``/``health_finalize`` build
  the compact f32 vector (global grad norm, update/param norm ratio,
  non-finite leaf count, loss scale + skip flag) that train_step.py
  computes INSIDE the compiled step -- each replica reduces a 1/n
  slice of every tree and the pre-scaled partial sums ride the
  existing loss pmean, so the health-on program carries NO extra
  collective AND no replicated full-tree passes (the roofline-free
  claim holds on param-bound models too) -- gated by
  ``--health_stats`` (``resolve_health_stats``; default auto = on for
  replica-synchronous training with a telemetry sink --
  ``--train_dir``/``--benchmark_log_dir``).
* Flight recorder: a bounded ring of per-step JSON records continuously
  rewritten to ``train_dir/flight_recorder.jsonl`` (the file always
  holds the newest window), with the full window + a diagnosis line
  appended to ``flight_recorder.dump.jsonl`` on anomaly (non-finite
  grads/loss, grad-norm spike beyond a configurable sigma, loss-scale
  halving streak), on SIGTERM/SIGINT, and at run end.
* Stall watchdog: a daemon thread fed heartbeats at dispatch
  boundaries. Before the first completed dispatch it is PATIENT
  (a first compile has no upper bound the watchdog could know;
  log-only). Mid-run, silence beyond ``factor`` x the trailing mean
  chunk wall emits a diagnostic (last flight-recorder rows + the
  platform env) and NEVER kills the process -- it diagnoses, the
  operator decides; liveness signals come from the host observing
  completed work (the pipelined metric fetch).
"""

from __future__ import annotations

import collections
import json
import math
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from kf_benchmarks_tpu import metrics as metrics_lib
from kf_benchmarks_tpu import tracing as tracing_lib
from kf_benchmarks_tpu.utils import log as log_util


# Order of the in-step health vector (health_finalize builds it from
# the pmean'd health_partials inside the step). Single-sourced in the
# metric registry (metrics.py), where every health/<key> scalar the
# recorder emits is registered.
HEALTH_KEYS = metrics_lib.HEALTH_KEYS


# -- in-step stats (compiled side) -------------------------------------------

def _sharded_sumsq(leaf, index, num):
  """This replica's partial square-sum of ``leaf``: row ``index`` of the
  flattened leaf reshaped (num, size//num), plus the < num-element tail
  on replica 0. Each replica touches ~1/num of the leaf, so the health
  pass costs one tree read TOTAL across the mesh instead of one per
  replica -- without this the stats were measured at ~2x step time on
  param-bound models (the reductions replicated n-fold)."""
  flat = leaf.reshape(-1).astype(jnp.float32)
  k = flat.size // num
  part = jnp.float32(0.0)
  if k:
    rows = flat[:num * k].reshape(num, k)
    row = lax.dynamic_index_in_dim(rows, index, axis=0, keepdims=False)
    part = jnp.sum(jnp.square(row))
  tail = flat[num * k:]
  if tail.size:
    part = part + jnp.where(index == 0, jnp.sum(jnp.square(tail)),
                            jnp.float32(0.0))
  return part


def health_partials(grads, params, updates, axis_name):
  """This replica's sharded partial sums for the in-step health stats,
  as one f32 vector ``[grad_sq(leaf 0..L-1), update_sq, param_sq]``
  pre-scaled by the replica count so the caller's single loss pmean
  (a MEAN) yields global SUMS; ``health_finalize`` turns the pmean'd
  totals into the HEALTH_KEYS vector.

  All inputs are replica-identical for the replica-synchronous
  strategies ``resolve_health_stats`` admits: ``grads`` is the APPLIED
  gradient tree (under relaxed consistency the deferred bank, matching
  the existing grad_norm metric convention), ``updates`` the optimizer
  update tree bracketing ``params``. Grad partials stay per-leaf so
  the non-finite LEAF count survives the reduction.
  """
  index = lax.axis_index(axis_name)
  num = lax.axis_size(axis_name)

  def _tree_sumsq(tree):
    leaves = jax.tree.leaves(tree)
    if not leaves:
      return jnp.float32(0.0)
    return sum(_sharded_sumsq(l, index, num) for l in leaves)

  grad_sq = [_sharded_sumsq(g, index, num)
             for g in jax.tree.leaves(grads)] or [jnp.float32(0.0)]
  vec = jnp.stack(grad_sq + [_tree_sumsq(updates), _tree_sumsq(params)])
  return vec * jnp.float32(num)


def health_finalize(totals, loss_scale, skipped, update_suppressed):
  """The HEALTH_KEYS vector from the pmean'd ``health_partials``
  (global per-leaf grad square-sums + update/param square-sums).

  A leaf counts as non-finite when its global square-sum is (any
  nan/inf element poisons the sum; a finite-value overflow of the f32
  sum also lands here, which is an anomaly worth flagging anyway).
  ``update_ratio`` is the per-step relative weight motion an operator
  eyeballs for LR sanity (~1e-3 healthy); ``update_suppressed`` zeroes
  it on steps whose apply was skipped by the loss-scale machine (the
  optimizer's would-be update tree is non-finite there).
  """
  grad_sq = totals[:-2]
  upd_sq, param_sq = totals[-2], totals[-1]
  grad_norm = jnp.sqrt(jnp.sum(grad_sq))
  nonfinite = jnp.sum(1.0 - jnp.isfinite(grad_sq).astype(jnp.float32))
  ratio = jnp.where(
      jnp.asarray(update_suppressed, jnp.float32) > 0, jnp.float32(0.0),
      jnp.sqrt(upd_sq) / jnp.maximum(jnp.sqrt(param_sq), 1e-12))
  return jnp.stack([grad_norm, ratio, nonfinite,
                    jnp.asarray(loss_scale, jnp.float32),
                    jnp.asarray(skipped, jnp.float32)])


def health_scalars(metrics) -> Dict[str, float]:
  """Expand a metrics dict's packed health vector into named scalars.

  The ONE schema shared by the flight-recorder records and the
  SummaryWriter scalar stream: both carry ``health/<key>`` entries, so
  a recorder row and a summary event line up field-for-field.
  """
  vec = metrics.get("health") if isinstance(metrics, dict) else None
  if vec is None:
    return {}
  arr = np.asarray(vec, np.float32).ravel()
  if arr.size != len(HEALTH_KEYS):
    return {}
  # Key construction goes through the registry's health_key helper --
  # the metric-key-literal lint bans assembling the health/ namespace
  # anywhere outside metrics.py.
  return {metrics_lib.health_key(k): float(v)
          for k, v in zip(HEALTH_KEYS, arr)}


# variable_update modes whose gradient reduction leaves every replica
# holding the SAME applied gradient tree -- the precondition for the
# in-step stats being global values rather than replica-local ones.
_SYNC_REPLICATED_UPDATES = (
    "replicated", "distributed_replicated", "parameter_server",
    "collective_all_reduce", "distributed_all_reduce", "horovod")


def resolve_health_stats(params, strategy=None):
  """Resolve ``--health_stats`` (None = auto) -> (enabled, note).

  Auto turns the stats ON for training runs that (a) reduce gradients
  replica-synchronously (``strategy.cross_replica``; replicated family
  / kungfu sync_sgd) and (b) have a telemetry SINK to record into
  (``--train_dir`` for the flight-recorder files, or
  ``--benchmark_log_dir`` for the health metric row). Gossip/async
  modes auto-off with a one-line note (the per-replica gradient trees
  diverge, so a "global" norm would silently be replica-local);
  sink-less runs auto-off quietly -- nothing durable would be recorded,
  and the in-step readout is not free (it rides the step's tail, after
  the optimizer apply). Explicit ``--health_stats`` always engages
  (the window stays in memory and anomalies still dump to the log);
  explicit True with an incompatible mode is rejected up front in
  validation.validate_cross_flags.
  """
  v = getattr(params, "health_stats", None)
  if v is False:
    return False, None
  if getattr(params, "eval", False) or getattr(params, "forward_only",
                                               False):
    # Training-only: there is no gradient tree to measure.
    return False, None
  if (getattr(params, "shard_optimizer_state", False) or
      (strategy is not None and getattr(strategy, "sharded_state",
                                        False))):
    # Sharded-state steps apply the optimizer on 1/n flat shards
    # (train_step.py + ops/sharded.py): the full update tree the stats
    # read never materializes. Explicit --health_stats is rejected up
    # front (validation.py); auto resolves off with a note when a sink
    # asked for telemetry, quietly otherwise.
    if getattr(params, "train_dir", None) or getattr(
        params, "benchmark_log_dir", None):
      return False, (
          "health_stats: --shard_optimizer_state applies the optimizer "
          "on per-device state shards; the full-tree in-step stats are "
          "disabled (elastic/fault-injected runs with a train_dir keep "
          "their flight-recorder/watchdog session regardless)")
    return False, None
  if strategy is not None:
    cross = bool(getattr(strategy, "cross_replica", False))
  else:
    cross = (
        (params.variable_update in _SYNC_REPLICATED_UPDATES and
         bool(getattr(params, "cross_replica_sync", True))) or
        (params.variable_update == "kungfu" and
         getattr(params, "kungfu_option", None) == "sync_sgd"))
  if not cross:
    return False, (
        "health_stats: --variable_update=%s keeps per-replica gradient "
        "trees (no replica-synchronous reduction); in-step health stats "
        "disabled -- pass --health_stats with a replicated-family mode "
        "to enable them" % params.variable_update)
  if v is None and not (getattr(params, "train_dir", None) or
                        getattr(params, "benchmark_log_dir", None)):
    return False, None
  return True, None


def flight_recorder_path(train_dir: Optional[str], rank: int = 0
                         ) -> Optional[str]:
  """Per-rank continuous-window path: rank 0 owns the canonical
  ``flight_recorder.jsonl``; other ranks write rank-suffixed files the
  rank-0 exit aggregation merges (``aggregate_rank_windows``)."""
  if not train_dir:
    return None
  name = ("flight_recorder.jsonl" if rank == 0
          else f"flight_recorder.rank{rank}.jsonl")
  return os.path.join(train_dir, name)


def aggregate_rank_windows(train_dir: str) -> List[dict]:
  """Merge every rank's continuous window under ``train_dir`` into one
  step-ordered record list (rank breaks ties), for the rank-0 exit
  aggregation in multi-process runs."""
  records = []
  try:
    names = sorted(os.listdir(train_dir))
  except OSError:
    return records
  for name in names:
    if not (name.startswith("flight_recorder") and
            name.endswith(".jsonl") and ".dump." not in name and
            name != "flight_recorder.all.jsonl"):
      continue
    try:
      with open(os.path.join(train_dir, name)) as f:
        for line in f:
          line = line.strip()
          if line:
            records.append(json.loads(line))
    except (OSError, ValueError):
      continue
  records.sort(key=lambda r: (r.get("step", 0), r.get("rank", 0)))
  return records


# -- flight recorder (host side) ---------------------------------------------

class FlightRecorder:
  """Bounded ring of per-step records with anomaly-triggered dumps.

  ``record()`` is called once per completed step with that step's
  scraped metrics; the newest ``window`` records are continuously
  rewritten to ``path`` (atomic replace, so a reader never sees a torn
  window), and anomalies append the full window + a diagnosis record to
  ``<dir>/flight_recorder.dump.jsonl`` -- append-mode, so a clean-exit
  dump never clobbers the mid-run post-mortem that mattered.
  """

  # Consecutive loss-scale halvings that count as a collapse streak
  # (each halving is one overflow-skipped step of the auto-loss-scale
  # machine; three in a row is divergence, not noise).
  HALVING_STREAK = 3

  def __init__(self, path: Optional[str] = None, window: int = 64,
               sigma: float = 6.0, rank: int = 0, log_fn=None,
               min_history: int = 8, run_id: Optional[str] = None):
    self.path = path
    # Shared with the run trace (tracing.py resolve_run_id): one run id
    # across recorder rows and trace events, so a post-mortem window
    # can be laid over the span timeline it belongs to.
    self.run_id = run_id
    self.dump_path = (os.path.join(os.path.dirname(path),
                                   "flight_recorder.dump.jsonl")
                      if path else None)
    if path:
      # The continuous window must hit disk from step 1 -- its whole
      # point is surviving a mid-run death. Checkpointing creates
      # train_dir only at the first save, so without this every
      # in-run _write_window dies on FileNotFoundError (a swallowed
      # OSError) and only the post-checkpoint exit dump ever lands.
      try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
      except OSError:
        pass  # unwritable sink: record() keeps the in-memory window
    self.window = max(1, int(window))
    self.sigma = float(sigma)
    self.rank = int(rank)
    self._log = log_fn or log_util.log_fn
    self._min_history = max(2, int(min_history))
    self._records: "collections.deque[dict]" = collections.deque(
        maxlen=self.window)
    self._prev_scale: Optional[float] = None
    self._halvings = 0
    self._skip_streak = 0
    self._in_anomaly = False
    self._old_handlers: Dict[int, Any] = {}
    # Summary counters (bench.py's health JSON fields).
    self._max_grad_norm: Optional[float] = None
    self._nonfinite_steps = 0
    self._anomaly_dumps = 0
    self._last_scale: Optional[float] = None

  # -- recording ------------------------------------------------------------

  def _stamp(self, rec: Dict[str, Any]) -> Dict[str, Any]:
    """Wall + MONOTONIC timestamps (and the shared run id) on every
    row: the wall clock anchors the row in operator time, the
    monotonic one lays it over the run-trace timeline (tracing.py uses
    the same clock for spans), immune to wall-clock steps mid-run."""
    rec["t_wall"] = round(time.time(), 3)
    rec["t_mono"] = round(time.monotonic(), 6)
    if self.run_id:
      rec["run_id"] = self.run_id
    return rec

  def record(self, step: int, loss: Optional[float] = None, lr=None,
             health=None, wall_ms: Optional[float] = None,
             chunk_len: int = 1, rtt_ms: Optional[float] = None,
             span_id: Optional[int] = None) -> dict:
    """Append one per-step record; detect anomalies against the
    TRAILING window (the current record is judged, not self-judged);
    rewrite the continuous window file. ``span_id`` cross-links the
    enclosing run-trace span (the dispatch this step resolved in), so
    a post-mortem dump can be laid over the exported timeline."""
    rec: Dict[str, Any] = self._stamp({"step": int(step),
                                       "rank": self.rank})
    if span_id:
      rec["span_id"] = int(span_id)
    if loss is not None:
      rec["loss"] = float(loss)
    if lr is not None:
      rec["lr"] = float(lr)
    if wall_ms is not None:
      rec["wall_ms"] = round(float(wall_ms), 3)
    if chunk_len != 1:
      rec["chunk_len"] = int(chunk_len)
    if rtt_ms is not None:
      rec["rtt_ms"] = round(float(rtt_ms), 3)
    rec.update(health_scalars({"health": health}))

    reasons = self._detect_anomalies(rec)
    self._records.append(rec)
    self._update_summary(rec)
    self._write_window()
    if reasons:
      if not self._in_anomaly:
        # Edge-triggered: one dump per anomaly episode, not per step of
        # a divergence that lasts the rest of the run.
        self._anomaly_dumps += 1
        self.dump("; ".join(reasons))
      self._in_anomaly = True
    else:
      self._in_anomaly = False
    return rec

  def _detect_anomalies(self, rec: dict) -> List[str]:
    reasons = []
    step = rec["step"]
    loss = rec.get("loss")
    nonfinite = rec.get("health/nonfinite_leaves", 0.0)
    gn = rec.get("health/grad_norm")
    if (nonfinite and nonfinite > 0) or (
        loss is not None and not math.isfinite(loss)) or (
        gn is not None and not math.isfinite(gn)):
      reasons.append(
          f"non-finite training signal at step {step} "
          f"(nonfinite_leaves={nonfinite:.0f}, loss={loss})")
    if gn is not None and math.isfinite(gn):
      trail = [r["health/grad_norm"] for r in self._records
               if math.isfinite(r.get("health/grad_norm", float("nan")))]
      if len(trail) >= self._min_history:
        mean = sum(trail) / len(trail)
        std = math.sqrt(sum((t - mean) ** 2 for t in trail) / len(trail))
        if std > 0 and gn > mean + self.sigma * std:
          reasons.append(
              f"grad-norm spike at step {step}: {gn:.3e} > trailing "
              f"mean {mean:.3e} + {self.sigma:g} sigma ({std:.3e})")
    scale = rec.get("health/loss_scale")
    skipped = rec.get("health/skipped", 0.0)
    if scale is not None:
      if self._prev_scale is not None and scale < self._prev_scale:
        self._halvings += 1
      elif self._prev_scale is not None and scale >= self._prev_scale:
        self._halvings = 0
      self._prev_scale = scale
      # The scale floors at 1.0 (train_step.py), so sustained overflow
      # stops halving but keeps skipping: count both signals.
      self._skip_streak = self._skip_streak + 1 if skipped else 0
      if max(self._halvings, self._skip_streak) == self.HALVING_STREAK:
        reasons.append(
            f"loss-scale collapse at step {step}: "
            f"{self.HALVING_STREAK} consecutive "
            f"{'halvings' if self._halvings >= self.HALVING_STREAK else 'skipped updates'}"
            f" (scale now {scale:g})")
    return reasons

  def _update_summary(self, rec: dict) -> None:
    gn = rec.get("health/grad_norm")
    if gn is not None and math.isfinite(gn):
      self._max_grad_norm = (gn if self._max_grad_norm is None
                             else max(self._max_grad_norm, gn))
    loss = rec.get("loss")
    if (rec.get("health/nonfinite_leaves", 0.0) > 0 or
        (loss is not None and not math.isfinite(loss))):
      self._nonfinite_steps += 1
    if rec.get("health/loss_scale") is not None:
      self._last_scale = rec["health/loss_scale"]

  def _write_window(self) -> None:
    if not self.path:
      return
    tmp = self.path + ".tmp"
    try:
      with open(tmp, "w") as f:
        for r in self._records:
          f.write(json.dumps(r) + "\n")
      os.replace(tmp, self.path)
    except OSError:
      pass  # a failed telemetry write must never take down the run

  def tail(self, n: int = 3) -> List[dict]:
    return list(self._records)[-n:]

  def note_event(self, event: Dict[str, Any]) -> dict:
    """Append a non-step event record (elastic resize, injected fault)
    to the ring + continuous window -- the post-mortem that follows a
    preemption must show WHAT the run was doing, not just its losses.
    Events bypass anomaly detection (they are operator actions, not
    training signals)."""
    rec = self._stamp({"rank": self.rank})
    rec.update(event)
    self._records.append(rec)
    self._write_window()
    return rec

  # -- dumps ----------------------------------------------------------------

  def dump(self, reason: str) -> None:
    """Append the full window + a diagnosis record to the dump file and
    emit one diagnosis line through log_fn (one whole line: telemetry
    must never interleave inside a step line, tests/test_benchmark.py)."""
    diagnosis = {
        "flight_recorder_dump": reason,
        "rank": self.rank,
        "records": len(self._records),
        "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    where = "window retained in memory (no --train_dir)"
    if self.dump_path:
      try:
        with open(self.dump_path, "a") as f:
          f.write(json.dumps(diagnosis) + "\n")
          for r in self._records:
            f.write(json.dumps(r) + "\n")
        where = f"{len(self._records)}-record window dumped to " \
                f"{self.dump_path}"
      except OSError as e:
        where = f"dump write failed ({e})"
    self._log(f"flight recorder: {reason} -- {where}")

  # -- signal handlers ------------------------------------------------------

  def install_signal_handlers(self) -> None:
    """Dump the window on SIGTERM/SIGINT, then chain to the previous
    handler (so ctrl-C still interrupts and a SIGTERM still terminates
    -- the recorder adds a post-mortem, it never swallows the signal)."""
    for signum in (signal.SIGTERM, signal.SIGINT):
      try:
        self._old_handlers[signum] = signal.signal(
            signum, self._handle_signal)
      except ValueError:
        # Not the main thread (e.g. a test harness worker): signals
        # cannot be installed there; recorder still works sans handlers.
        pass

  def _handle_signal(self, signum, frame) -> None:
    self.dump(f"signal {signal.Signals(signum).name}")
    old = self._old_handlers.get(signum)
    signal.signal(signum, old if old is not None else signal.SIG_DFL)
    signal.raise_signal(signum)

  def close(self) -> None:
    """Restore any installed signal handlers (tests run in-process;
    a leaked handler would outlive its recorder)."""
    for signum, old in self._old_handlers.items():
      try:
        if signal.getsignal(signum) == self._handle_signal:
          signal.signal(signum, old)
      except ValueError:
        pass
    self._old_handlers.clear()

  def summary(self) -> Dict[str, Any]:
    return {
        "records": len(self._records),
        "max_grad_norm": self._max_grad_norm,
        "nonfinite_steps": self._nonfinite_steps,
        "loss_scale_final": self._last_scale,
        "anomaly_dumps": self._anomaly_dumps,
    }


# -- stall watchdog ----------------------------------------------------------

class StallWatchdog:
  """Daemon thread that watches dispatch-boundary heartbeats.

  Two regimes, split on whether ANY dispatch has completed:

  * First compile (no heartbeat yet): PATIENT. A first compile has no
    upper bound the watchdog could know (49 s cold for resnet50 on the
    v5e, PERF.md; unmeasured for the LM family); the watchdog logs a
    reassurance line every ``patience_s`` and does nothing else.
  * Mid-run: silence longer than ``factor`` x the trailing mean chunk
    wall (floored at ``min_stall_s``) emits ONE diagnostic per stall
    episode -- the spans the main thread is inside (the run trace's
    ``open_spans``), the last flight-recorder rows plus the platform
    env -- and counts it. It NEVER kills, signals, or interrupts the process:
    it diagnoses, the operator decides.

  Heartbeats come from the host observing real completed work (the
  pipelined metric fetch, utils/pipeline.py).
  """

  TRAILING_WINDOW = 16

  def __init__(self, factor: float = 10.0, poll_s: float = 1.0,
               patience_s: float = 600.0, min_stall_s: float = 5.0,
               log_fn=None, recorder: Optional[FlightRecorder] = None,
               time_fn=time.monotonic):
    self.factor = float(factor)
    self.poll_s = float(poll_s)
    self.patience_s = float(patience_s)
    self.min_stall_s = float(min_stall_s)
    self._log = log_fn or log_util.log_fn
    self._recorder = recorder
    self._time = time_fn
    self._lock = threading.Lock()
    self._walls: "collections.deque[float]" = collections.deque(
        maxlen=self.TRAILING_WINDOW)
    self._last_beat = self._time()
    self._beats = 0
    self._stalls = 0
    self._stalled = False
    self._last_patient_log: Optional[float] = None
    self._stop_event = threading.Event()
    self._thread: Optional[threading.Thread] = None

  @property
  def enabled(self) -> bool:
    return self.factor > 0

  @property
  def stalls(self) -> int:
    return self._stalls

  def start(self) -> None:
    if not self.enabled or self._thread is not None:
      return
    with self._lock:
      self._last_beat = self._time()
    self._thread = threading.Thread(
        target=self._run, name="kf-stall-watchdog", daemon=True)
    self._thread.start()

  def beat(self, wall_s: Optional[float] = None) -> None:
    """Mark a completed dispatch; ``wall_s`` (the chunk wall interval)
    feeds the trailing-mean stall threshold."""
    with self._lock:
      self._last_beat = self._time()
      self._beats += 1
      self._stalled = False
      if wall_s is not None and wall_s > 0:
        self._walls.append(float(wall_s))

  def stop(self) -> None:
    self._stop_event.set()
    if self._thread is not None:
      self._thread.join(timeout=5.0)
      self._thread = None

  def _run(self) -> None:
    while not self._stop_event.wait(self.poll_s):
      try:
        self._check(self._time())
      except Exception as e:
        # A watchdog crash must never take down the run -- but one
        # failed evaluation (say, an OSError out of the injected
        # log_fn) must not silently retire the thread either, or every
        # later stall goes undetected while summary() reports healthy.
        try:
          self._log(f"stall watchdog: check failed ({e!r}); continuing")
        except Exception:
          pass  # the log sink itself is down; keep polling regardless

  def _check(self, now: float) -> None:
    """One watchdog evaluation at host time ``now`` (separated from the
    thread loop so tests can drive it with a fake clock)."""
    with self._lock:
      idle = now - self._last_beat
      beats = self._beats
      walls = list(self._walls)
      stalled = self._stalled
    if beats == 0:
      # First compile: patient, log-only.
      if idle > self.patience_s and (
          self._last_patient_log is None or
          now - self._last_patient_log > self.patience_s):
        self._last_patient_log = now
        self._log(
            "stall watchdog: no dispatch completed yet after "
            f"{idle / 60.0:.1f} min -- a first compile can "
            "legitimately take this long; staying patient (the "
            "watchdog never kills)")
      return
    trailing = sum(walls) / len(walls) if walls else None
    threshold = max(self.factor * trailing if trailing else 0.0,
                    self.min_stall_s)
    if idle > threshold and not stalled:
      with self._lock:
        self._stalls += 1
        self._stalled = True
      self._emit_diagnostic(idle, trailing)
    elif idle <= threshold and stalled:
      with self._lock:
        self._stalled = False

  def _emit_diagnostic(self, idle: float, trailing: Optional[float]
                       ) -> None:
    trail_txt = (f"{idle / trailing:.1f}x the {trailing:.2f}s trailing "
                 "mean chunk wall" if trailing else "no trailing mean yet")
    self._log(
        f"stall watchdog: no dispatch completed for {idle:.1f}s "
        f"({trail_txt}); diagnosing only -- NOT killing the process")
    # Where the main thread is, in the run trace's own vocabulary (the
    # one a late iteration's ``host stall:`` line uses); read unlocked.
    open_spans = tracing_lib.active().open_spans()
    if open_spans:
      self._log("stall watchdog: stalled inside "
                + " > ".join(open_spans) + f" for {idle:.1f} s")
    # Env only: the watchdog thread never touches the backend.
    self._log("stall watchdog: platform env: JAX_PLATFORMS="
              + os.environ.get("JAX_PLATFORMS", "unset"))
    if self._recorder is not None:
      for rec in self._recorder.tail(3):
        self._log("stall watchdog: last record: " + json.dumps(rec))


# -- session (benchmark.py's single wiring point) ----------------------------

class TelemetrySession:
  """Flight recorder + stall watchdog bundled for one training run."""

  @classmethod
  def create(cls, params, rank: int = 0, log_fn=None,
             num_ranks: int = 1,
             run_id: Optional[str] = None) -> Optional["TelemetrySession"]:
    """None unless the run's resolved --health_stats is on (benchmark
    resolves auto -> bool before building the step) -- OR the run is
    elastic/fault-injected with a train_dir sink: a preemption must
    produce a flight-recorder post-mortem window and a recorded elastic
    event even when the in-step stats are off (e.g.
    --shard_optimizer_state auto-disables them). The recorder and
    watchdog are host-side only, so this changes no compiled program."""
    wants = bool(getattr(params, "health_stats", None)) or (
        bool(getattr(params, "train_dir", None)) and
        (bool(getattr(params, "elastic", False)) or
         bool(getattr(params, "fault_schedule", None))))
    if not wants:
      return None
    return cls(params, rank=rank, log_fn=log_fn, num_ranks=num_ranks,
               run_id=run_id)

  def __init__(self, params, rank: int = 0, log_fn=None,
               num_ranks: int = 1, run_id: Optional[str] = None):
    self.train_dir = getattr(params, "train_dir", None)
    self.rank = int(rank)
    self.num_ranks = max(1, int(num_ranks))
    self.recorder = FlightRecorder(
        path=flight_recorder_path(self.train_dir, self.rank),
        window=int(getattr(params, "flight_recorder_window", None) or 64),
        sigma=float(getattr(params, "health_grad_norm_sigma", None)
                    or 6.0),
        rank=self.rank, log_fn=log_fn, run_id=run_id)
    self.recorder.install_signal_handlers()
    self.watchdog = StallWatchdog(
        factor=float(getattr(params, "stall_watchdog_factor", None)
                     or 0.0),
        log_fn=log_fn, recorder=self.recorder)
    self.watchdog.start()
    self._slo_monitor = None
    self._closed = False

  def attach_slo(self, monitor) -> None:
    """Attach a metrics.SLOMonitor so /healthz carries its burn state
    (and its alert episodes already ride this session's recorder when
    the monitor was built with ``recorder=session.recorder``)."""
    self._slo_monitor = monitor

  def beat(self, wall_s: Optional[float] = None) -> None:
    self.watchdog.beat(wall_s)

  def record(self, **kwargs) -> None:
    self.recorder.record(**kwargs)

  def elastic_event(self, generation: int, old_mesh: str, new_mesh: str,
                    step: int) -> None:
    """One recorder row per resize (benchmark.py logs the matching
    single line): the post-mortem window shows generation, old -> new
    mesh and the resume step instead of an unexplained loss-curve
    seam."""
    self.recorder.note_event({
        "elastic_event": f"{old_mesh}->{new_mesh}",
        "generation": int(generation),
        "step": int(step),
    })

  def fault_event(self, description: str, step: int) -> None:
    self.recorder.note_event({"fault_event": description,
                              "step": int(step)})

  def summary(self) -> Dict[str, Any]:
    s = self.recorder.summary()
    s["watchdog_stalls"] = self.watchdog.stalls
    return s

  def healthz(self) -> Dict[str, Any]:
    """The /healthz payload half this session owns (metrics.py serves
    it): liveness read from watchdog + flight-recorder state. "stalled"
    means the watchdog is currently inside a stall episode -- a scrape
    can see a live job that stopped dispatching, which is exactly the
    signature the watchdog exists to diagnose."""
    stalled = bool(getattr(self.watchdog, "_stalled", False))
    payload = {"status": "stalled" if stalled else "ok"}
    payload.update(self.summary())
    last = self.recorder.tail(1)
    if last:
      payload["last_step"] = last[0].get("step")
    if self._slo_monitor is not None:
      # "up" vs "up but burning error budget": a firing SLO stream
      # upgrades an otherwise-ok status (a stall still wins -- a
      # stuck dispatcher is the more urgent diagnosis).
      slo = self._slo_monitor.state()
      payload["slo"] = slo
      if payload["status"] == "ok" and slo["status"] != "ok":
        payload["status"] = slo["status"]
    return payload

  def close(self, reason: str = "run end") -> None:
    if self._closed:
      return
    self._closed = True
    self.watchdog.stop()
    self.recorder.dump(reason)
    if (self.rank == 0 and self.num_ranks > 1 and self.train_dir):
      # Rank-0 exit aggregation: merge every rank's window (shared
      # train_dir) into one step-ordered view next to the per-rank
      # files (cluster.py process_rank tags the rows).
      merged = aggregate_rank_windows(self.train_dir)
      if merged:
        try:
          path = os.path.join(self.train_dir, "flight_recorder.all.jsonl")
          with open(path, "w") as f:
            for r in merged:
              f.write(json.dumps(r) + "\n")
        except OSError:
          pass
    self.recorder.close()
