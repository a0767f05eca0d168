"""Training-health telemetry (telemetry.py): in-step device stats,
flight recorder, stall watchdog.

Layers, reference-style (SURVEY 7.1):
  * pure-unit: health-stat resolution + validation rules, flight-recorder
    window/anomaly/dump/signal logic, watchdog state machine on a fake
    clock.
  * numerical equivalence: per-step losses and trained params
    bit-identical with --health_stats on vs off, including the
    --steps_per_dispatch and --num_grad_accum compositions (the stats are
    a pure readout packed into the existing loss pmean).
  * compiled-HLO: the health-on step program carries NO extra collective
    (the vector pmean replaces the two scalar loss pmeans).
  * log-scraping e2e: an injected non-finite gradient dumps the flight
    recorder with the offending step's record; a synthetic stalled
    dispatch draws a watchdog diagnostic and the process survives.
"""

import json
import math
import os
import re
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import benchmark, params as params_lib, validation
from kf_benchmarks_tpu import telemetry
from kf_benchmarks_tpu.utils import log as log_util

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: ([\d.]+) \+/- ([\d.]+) \(jitter = ([\d.]+)\)\t"
    r"([\d.naninf]+)")


def _run_and_scrape(**overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=8, num_warmup_batches=1,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=2)
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def _health_vec(grad_norm=1.0, update_ratio=1e-4, nonfinite=0.0,
                loss_scale=1.0, skipped=0.0):
  return np.asarray([grad_norm, update_ratio, nonfinite, loss_scale,
                     skipped], np.float32)


# -- pure-unit: resolution + validation ---------------------------------------

def test_health_scalars_schema():
  vec = _health_vec(grad_norm=2.5, loss_scale=128.0)
  s = telemetry.health_scalars({"health": vec})
  assert s == {"health/grad_norm": 2.5, "health/update_ratio": pytest.approx(1e-4),
               "health/nonfinite_leaves": 0.0, "health/loss_scale": 128.0,
               "health/skipped": 0.0}
  assert telemetry.health_scalars({}) == {}
  assert telemetry.health_scalars({"health": np.zeros(3)}) == {}


def test_resolve_health_stats_auto():
  mk = params_lib.make_params
  # Auto = on only for replica-synchronous training WITH a telemetry
  # sink to record into (train_dir / benchmark_log_dir) -- sink-less
  # runs keep the seed step program, quietly (the in-step readout rides
  # the step's tail after the optimizer apply, so it is not free).
  on, note = telemetry.resolve_health_stats(
      mk(variable_update="replicated", train_dir="/tmp/t"))
  assert on and note is None
  on, note = telemetry.resolve_health_stats(
      mk(variable_update="kungfu", kungfu_option="sync_sgd",
         benchmark_log_dir="/tmp/b"))
  assert on
  assert telemetry.resolve_health_stats(mk()) == (False, None)
  # Explicit --health_stats engages without a sink (in-memory window,
  # anomalies still dump to the log).
  on, note = telemetry.resolve_health_stats(mk(health_stats=True))
  assert on and note is None
  # Per-replica/gossip modes auto-disable with an operator-facing note.
  for kw in (dict(variable_update="independent"),
             dict(variable_update="kungfu", kungfu_option="async_sgd"),
             dict(variable_update="parameter_server",
                  cross_replica_sync=False)):
    on, note = telemetry.resolve_health_stats(mk(train_dir="/tmp/t", **kw))
    assert not on and note and "health_stats" in note
  # Training-only; explicit off wins silently.
  assert telemetry.resolve_health_stats(
      mk(eval=True, train_dir="/tmp/t")) == (False, None)
  assert telemetry.resolve_health_stats(
      mk(forward_only=True, train_dir="/tmp/t")) == (False, None)
  assert telemetry.resolve_health_stats(
      mk(health_stats=False, train_dir="/tmp/t")) == (False, None)


def test_resolve_follows_strategy_object():
  from kf_benchmarks_tpu.parallel import strategies
  p = params_lib.make_params(variable_update="kungfu",
                             kungfu_option="sync_sgd", train_dir="/tmp/t")
  on, _ = telemetry.resolve_health_stats(p, strategies.get_strategy(p))
  assert on
  p = params_lib.make_params(variable_update="kungfu",
                             kungfu_option="sma", train_dir="/tmp/t")
  on, _ = telemetry.resolve_health_stats(p, strategies.get_strategy(p))
  assert not on


def test_validation_rejects_explicit_health_stats_mismatches():
  mk = params_lib.make_params
  for kw, msg in ((dict(eval=True), "training only"),
                  (dict(forward_only=True), "training only"),
                  (dict(variable_update="independent"), "never reduces"),
                  (dict(variable_update="kungfu",
                        kungfu_option="async_sgd"), "gossip"),
                  (dict(variable_update="parameter_server",
                        cross_replica_sync=False), "UNAVERAGED")):
    with pytest.raises(validation.ParamError, match=msg):
      validation.validate_cross_flags(mk(health_stats=True, **kw))
  # The default-on path and the explicit replicated form both validate.
  validation.validate_cross_flags(mk(health_stats=True))
  validation.validate_cross_flags(mk())


# -- pure-unit: flight recorder -----------------------------------------------

def test_flight_recorder_window_file_holds_newest_tail(tmp_path):
  path = str(tmp_path / "flight_recorder.jsonl")
  rec = telemetry.FlightRecorder(path=path, window=16, log_fn=lambda s: None)
  for i in range(100):
    rec.record(step=i + 1, loss=1.0, health=_health_vec())
  rows = [json.loads(l) for l in open(path)]
  assert [r["step"] for r in rows] == list(range(85, 101))
  assert rows[-1]["health/grad_norm"] == 1.0
  assert rows[-1]["rank"] == 0
  # Continuous mode leaves no dump file: nothing anomalous happened.
  assert not os.path.exists(str(tmp_path / "flight_recorder.dump.jsonl"))
  s = rec.summary()
  assert s["records"] == 16 and s["nonfinite_steps"] == 0
  assert s["anomaly_dumps"] == 0


def test_flight_recorder_creates_missing_train_dir(tmp_path):
  """The window must hit disk from step 1 even when train_dir does not
  exist yet -- checkpointing only creates it at the first save, and the
  recorder's job is surviving a death BEFORE that (pre-fix every in-run
  window write died on a swallowed FileNotFoundError and only the
  post-checkpoint exit dump ever landed)."""
  train_dir = tmp_path / "not_yet_created"
  path = str(train_dir / "flight_recorder.jsonl")
  rec = telemetry.FlightRecorder(path=path, window=8,
                                 log_fn=lambda s: None)
  rec.record(step=1, loss=1.0, health=_health_vec())
  rows = [json.loads(l) for l in open(path)]
  assert [r["step"] for r in rows] == [1]


def test_flight_recorder_nonfinite_dump_carries_offending_record(tmp_path):
  logs = []
  rec = telemetry.FlightRecorder(path=str(tmp_path / "fr.jsonl"),
                                 window=8, log_fn=logs.append)
  for i in range(5):
    rec.record(step=i + 1, loss=1.0, health=_health_vec())
  rec.record(step=6, loss=float("nan"), health=_health_vec(nonfinite=3.0))
  dump = str(tmp_path / "flight_recorder.dump.jsonl")
  rows = [json.loads(l) for l in open(dump)]
  assert "non-finite" in rows[0]["flight_recorder_dump"]
  offending = [r for r in rows[1:]
               if r.get("health/nonfinite_leaves", 0) > 0]
  assert offending and offending[0]["step"] == 6
  assert any("flight recorder: non-finite" in l for l in logs)
  # Edge-triggered: a continuing anomaly episode does not re-dump.
  rec.record(step=7, loss=float("nan"), health=_health_vec(nonfinite=3.0))
  assert rec.summary()["anomaly_dumps"] == 1
  assert rec.summary()["nonfinite_steps"] == 2
  # Recovery then a NEW anomaly dumps again.
  rec.record(step=8, loss=1.0, health=_health_vec())
  rec.record(step=9, loss=float("inf"), health=_health_vec(nonfinite=1.0))
  assert rec.summary()["anomaly_dumps"] == 2


def test_flight_recorder_grad_norm_spike(tmp_path):
  logs = []
  rec = telemetry.FlightRecorder(path=str(tmp_path / "fr.jsonl"),
                                 window=32, sigma=6.0, log_fn=logs.append)
  # Trailing history with real variance, then a far outlier.
  for i in range(16):
    rec.record(step=i + 1, loss=1.0,
               health=_health_vec(grad_norm=1.0 + 0.01 * (i % 4)))
  rec.record(step=17, loss=1.0, health=_health_vec(grad_norm=50.0))
  assert any("grad-norm spike" in l for l in logs)
  assert rec.summary()["anomaly_dumps"] == 1
  assert rec.summary()["max_grad_norm"] == 50.0


def test_flight_recorder_loss_scale_collapse_streak():
  logs = []
  rec = telemetry.FlightRecorder(log_fn=logs.append)
  scale = 1024.0
  rec.record(step=1, loss=1.0, health=_health_vec(loss_scale=scale))
  for i in range(2, 5):
    scale /= 2
    rec.record(step=i, loss=1.0,
               health=_health_vec(loss_scale=scale, skipped=1.0))
  assert any("loss-scale collapse" in l for l in logs), logs
  # The streak fired exactly once at the threshold crossing.
  assert sum("loss-scale collapse" in l for l in logs) == 1


def test_flight_recorder_signal_dump_and_restore(tmp_path):
  rec = telemetry.FlightRecorder(path=str(tmp_path / "fr.jsonl"),
                                 window=8, log_fn=lambda s: None)
  rec.record(step=1, loss=1.0, health=_health_vec())
  before = signal.getsignal(signal.SIGINT)
  rec.install_signal_handlers()
  with pytest.raises(KeyboardInterrupt):
    # The handler dumps, restores the previous handler, and re-raises
    # the signal -- it never swallows the interrupt.
    signal.raise_signal(signal.SIGINT)
  rows = [json.loads(l)
          for l in open(str(tmp_path / "flight_recorder.dump.jsonl"))]
  assert rows[0]["flight_recorder_dump"] == "signal SIGINT"
  assert rows[1]["step"] == 1
  rec.close()
  assert signal.getsignal(signal.SIGINT) == before


def test_aggregate_rank_windows(tmp_path):
  for rank in (0, 1, 2):
    path = telemetry.flight_recorder_path(str(tmp_path), rank)
    with open(path, "w") as f:
      for step in (rank + 1, rank + 4):
        f.write(json.dumps({"step": step, "rank": rank}) + "\n")
  # Dump files must never leak into the aggregate.
  with open(str(tmp_path / "flight_recorder.dump.jsonl"), "w") as f:
    f.write(json.dumps({"flight_recorder_dump": "x"}) + "\n")
  merged = telemetry.aggregate_rank_windows(str(tmp_path))
  assert [(r["step"], r["rank"]) for r in merged] == \
      [(1, 0), (2, 1), (3, 2), (4, 0), (5, 1), (6, 2)]


# -- pure-unit: stall watchdog ------------------------------------------------

def test_watchdog_patient_during_first_compile():
  logs = []
  t = [0.0]
  wd = telemetry.StallWatchdog(factor=3.0, patience_s=10.0,
                               min_stall_s=0.0, log_fn=logs.append,
                               time_fn=lambda: t[0])
  # No dispatch has completed: arbitrarily long silence is log-only.
  t[0] = 11.0
  wd._check(t[0])
  assert wd.stalls == 0
  assert any("staying patient" in l for l in logs)
  # The reassurance line is rate-limited to once per patience window.
  t[0] = 12.0
  wd._check(t[0])
  assert sum("staying patient" in l for l in logs) == 1
  t[0] = 25.0
  wd._check(t[0])
  assert sum("staying patient" in l for l in logs) == 2


def test_watchdog_midrun_stall_diagnoses_and_never_kills(tmp_path):
  logs = []
  rec = telemetry.FlightRecorder(log_fn=logs.append)
  rec.record(step=7, loss=1.25, health=_health_vec())
  t = [0.0]
  wd = telemetry.StallWatchdog(factor=3.0, patience_s=600.0,
                               min_stall_s=0.0, log_fn=logs.append,
                               recorder=rec, time_fn=lambda: t[0])
  wd.beat(0.1)  # synthetic completed dispatch: 100 ms chunk wall
  t[0] = 0.2
  wd._check(t[0])
  assert wd.stalls == 0
  t[0] = 1.0  # 1 s of silence >> 3 x 0.1 s: the synthetic stall
  wd._check(t[0])
  assert wd.stalls == 1
  diag = [l for l in logs if "stall watchdog:" in l]
  assert any("NOT killing the process" in l for l in diag)
  assert any("platform env" in l for l in diag)
  assert any('"step": 7' in l for l in diag)  # last recorder rows ride along
  # Latched: the same stall episode is counted once...
  t[0] = 2.0
  wd._check(t[0])
  assert wd.stalls == 1
  # ...and a completed dispatch re-arms detection.
  wd.beat(0.1)
  t[0] = 3.5
  wd._check(t[0])
  assert wd.stalls == 2
  # Process is demonstrably alive and the watchdog exposes no kill path.
  assert not any("SIGKILL" in l or "terminat" in l for l in diag)


def test_watchdog_diagnostic_names_the_open_spans():
  """A hang is reported in the run trace's vocabulary: the spans the
  main thread is inside, outermost first. With no session active the
  diagnostic is what it was."""
  from kf_benchmarks_tpu import tracing

  def stall_once():
    logs, t = [], [0.0]
    wd = telemetry.StallWatchdog(factor=3.0, min_stall_s=0.0,
                                 log_fn=logs.append, time_fn=lambda: t[0])
    wd.beat(0.1)
    t[0] = 12.3
    wd._check(t[0])
    assert wd.stalls == 1
    return logs

  before = stall_once()
  assert len(before) == 2 and not any("stalled inside" in l for l in before)
  tr = tracing.RunTrace()
  tracing.activate(tr)
  try:
    with tr.step("train", 31):
      with tr.span("dispatch", "train_step"):
        logs = stall_once()
    idle = stall_once()        # a session, and the loop inside no span
  finally:
    tracing.deactivate()
  assert ("stall watchdog: stalled inside run/train > dispatch/train_step "
          "for 12.3 s") in logs
  assert [l for l in logs if "stalled inside" not in l] == before
  assert idle == before


def test_watchdog_thread_survives_failing_check():
  """One raising check evaluation (e.g. the log sink erroring inside a
  diagnostic) logs and keeps the poll loop alive -- it must not retire
  the thread, or every later stall goes undetected while summary()
  reports the run healthy."""
  logs = []
  wd = telemetry.StallWatchdog(factor=2.0, poll_s=0.01, log_fn=logs.append)
  calls = []

  def _boom(now):
    calls.append(now)
    if len(calls) == 1:
      raise OSError("sink down")

  wd._check = _boom
  wd.start()
  deadline = time.time() + 5.0
  while len(calls) < 3 and time.time() < deadline:
    time.sleep(0.01)
  wd.stop()
  assert len(calls) >= 3  # the loop outlived the raising evaluation
  assert any("check failed" in l for l in logs)


def test_watchdog_thread_smoke_and_disabled_factor():
  logs = []
  wd = telemetry.StallWatchdog(factor=2.0, poll_s=0.01, patience_s=60.0,
                               min_stall_s=0.05, log_fn=logs.append)
  wd.start()
  wd.beat(0.01)
  time.sleep(0.5)  # silence far beyond max(2 x 10 ms, 50 ms)
  wd.stop()
  assert wd.stalls >= 1
  assert any("NOT killing" in l for l in logs)
  off = telemetry.StallWatchdog(factor=0.0, log_fn=logs.append)
  off.start()
  assert off._thread is None and not off.enabled
  off.stop()


# -- numerical equivalence: stats on vs off -----------------------------------

# The composition variants each compile two more full step programs
# (~20-26 s apiece): slow-tiered so tier-1 keeps its 870 s wall budget
# (CLAUDE.md); [plain] stays tier-1 as the bit-identical regression pin.
@pytest.mark.parametrize("extra", [
    {},
    pytest.param({"steps_per_dispatch": 8}, marks=pytest.mark.slow),
    pytest.param({"num_grad_accum": 2}, marks=pytest.mark.slow),
    pytest.param({"steps_per_dispatch": 8, "num_grad_accum": 2},
                 marks=pytest.mark.slow),
], ids=["plain", "K8", "accum2", "K8+accum2"])
def test_health_stats_bit_identical_to_stats_off(extra):
  """Acceptance: the health vector is a pure readout -- per-step losses
  AND trained params bit-identical with --health_stats on vs off, on
  the 8-device mesh, through the chunked-dispatch and microbatched
  compositions (per-step rows, not per-chunk)."""
  on_logs, on = _run_and_scrape(health_stats=True, num_devices=8, **extra)
  off_logs, off = _run_and_scrape(health_stats=False, num_devices=8,
                                  **extra)
  st_on = [(m.group(1), m.group(5)) for l in on_logs
           if (m := STEP_RE.match(l))]
  st_off = [(m.group(1), m.group(5)) for l in off_logs
            if (m := STEP_RE.match(l))]
  assert len(st_on) == 8 and st_on == st_off, (st_on, st_off)
  for a, b in zip(jax.tree.leaves(on["state"].params),
                  jax.tree.leaves(off["state"].params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  assert on["health"] is not None and off["health"] is None
  assert on["health"]["records"] == 8
  assert on["health"]["max_grad_norm"] > 0
  assert on["health"]["nonfinite_steps"] == 0


# -- compiled-HLO: no extra collectives ---------------------------------------

# Single-sourced with the program-contract auditor (analysis/contracts.py).
from kf_benchmarks_tpu.analysis.contracts import (  # noqa: E402
    ALL_REDUCE_DEF as _ALL_REDUCE_DEF,
    compile_for_audit as _compile_for_audit)


def test_health_stats_add_no_extra_collectives():
  """Acceptance: the health-on step program carries NO additional
  collective -- the stats ride the loss pmean as one f32 vector
  all-reduce (it REPLACES the two scalar loss pmeans, so the count can
  only stay equal or drop)."""
  def lowered(health):
    p = params_lib.make_params(model="trivial", batch_size=4,
                               num_batches=2, device="cpu",
                               num_devices=8, health_stats=health)
    bench = benchmark.BenchmarkCNN(p)
    init_state, train_step, _, _, _ = bench._build()
    rng = jax.random.PRNGKey(0)
    batch = bench._input_iterator(rng, "train")[0]()
    shape = (bench.batch_size_per_device,) + bench._model_image_shape()
    state = init_state(rng, jnp.zeros(shape, jnp.float32))
    return _compile_for_audit(
        train_step.lower(state, *batch)).as_text()

  n_on = len([l for l in lowered(True).splitlines()
              if _ALL_REDUCE_DEF.search(l)])
  n_off = len([l for l in lowered(False).splitlines()
               if _ALL_REDUCE_DEF.search(l)])
  assert n_on <= n_off, (
      f"health stats added collectives: {n_on} all-reduces vs {n_off} "
      "with stats off")


# -- log-scraping e2e ---------------------------------------------------------

def test_injected_nonfinite_grads_dump_flight_recorder(tmp_path):
  """Acceptance: an injected non-finite gradient (divergent LR blows the
  params to inf, so the next backward is non-finite) produces a
  flight-recorder dump whose window contains the offending step's
  record -- and the run still completes every step."""
  logs, stats = _run_and_scrape(train_dir=str(tmp_path),
                                init_learning_rate=1e30, num_batches=6)
  dump = str(tmp_path / "flight_recorder.dump.jsonl")
  assert os.path.exists(dump), logs
  rows = [json.loads(l) for l in open(dump)]
  headers = [r for r in rows if "flight_recorder_dump" in r]
  assert any("non-finite" in h["flight_recorder_dump"] for h in headers)
  offending = [r for r in rows if r.get("health/nonfinite_leaves", 0) > 0]
  assert offending, rows
  assert any("flight recorder: non-finite" in l for l in logs)
  assert stats["num_steps"] == 6  # diagnosed, not killed
  assert stats["health"]["nonfinite_steps"] > 0
  # The continuous window file also exists and carries the same schema.
  window = [json.loads(l)
            for l in open(str(tmp_path / "flight_recorder.jsonl"))]
  assert {"step", "rank", "loss"} <= set(window[-1])


def test_flight_recorder_schema_shared_with_summaries(tmp_path):
  """Recorder rows and SummaryWriter scalar events carry the same
  health/<key> fields (one schema, telemetry.py + observability.py)."""
  logs, stats = _run_and_scrape(train_dir=str(tmp_path),
                                save_summaries_steps=2,
                                summary_verbosity=1)
  events = [json.loads(l) for l in open(str(tmp_path / "events.jsonl"))]
  scalar_keys = set(events[0]["scalars"])
  window = [json.loads(l)
            for l in open(str(tmp_path / "flight_recorder.jsonl"))]
  health_keys = {f"health/{k}" for k in telemetry.HEALTH_KEYS}
  assert health_keys <= scalar_keys
  assert health_keys <= set(window[-1])
  assert stats["health"]["loss_scale_final"] == 1.0
  assert stats["health"]["watchdog_stalls"] == 0


def test_health_auto_disables_for_gossip_with_note():
  logs, stats = _run_and_scrape(num_devices=4, variable_update="kungfu",
                                kungfu_option="async_sgd")
  assert stats["health"] is None
  assert any(l.startswith("health_stats:") for l in logs)
  # No recorder/watchdog lines from a disabled telemetry layer.
  assert not any("flight recorder:" in l for l in logs)


def test_chunked_flight_recorder_rows_are_per_step(tmp_path):
  """--steps_per_dispatch=K: the recorder gets one row per STEP (the
  pipeline unstacks the chunk host-side), each row tagging its chunk."""
  logs, stats = _run_and_scrape(train_dir=str(tmp_path),
                                steps_per_dispatch=4, num_batches=8,
                                num_warmup_batches=0)
  window = [json.loads(l)
            for l in open(str(tmp_path / "flight_recorder.jsonl"))]
  assert [r["step"] for r in window] == list(range(1, 9))
  assert all(r.get("chunk_len") == 4 for r in window)
  assert all("health/grad_norm" in r for r in window)
  # Distinct per-step health values within one chunk (stacked rows, not
  # one per-chunk value copied K times): grad norms differ step-to-step.
  norms = {round(r["health/grad_norm"], 9) for r in window[:4]}
  assert len(norms) > 1, window[:4]
