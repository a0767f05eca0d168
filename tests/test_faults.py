"""Fault-injection harness (kf_benchmarks_tpu/faults.py +
--fault_schedule): every elastic failure mode as a reproducible event.

Layers:
  * pure-unit: schedule grammar + validation wiring, rank filtering,
    one-shot persistence across generations (the marker file that keeps
    a kill from re-firing after the rejoin), checkpoint truncation.
  * in-process e2e: drop_msg suppresses one coordination poll and the
    pending resize SURVIVES to the next poll; heartbeat_delay starves
    the stall watchdog into its diagnose-never-kill path; fault events
    land in the flight-recorder window.
  * subprocess e2e (slow): sigterm@step drives the real chained-handler
    path (flight-recorder post-mortem on disk, process dies by
    SIGTERM); kill@step after corrupt_ckpt@step proves a SIGKILL'd
    run resumes past the torn checkpoint from the previous snapshot.
"""

import json
import os
import re
import signal
import subprocess
import sys

import pytest

from kf_benchmarks_tpu import benchmark, faults, params as params_lib
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.utils import log as log_util

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- pure-unit: grammar + validation ------------------------------------------

def test_parse_schedule_grammar():
  sched = faults.parse_schedule(
      "kill@10:rank=1, sigterm@6, heartbeat_delay@5:secs=2.5,"
      "drop_msg@8,corrupt_ckpt@4")
  assert [(f.kind, f.step, f.rank) for f in sched] == [
      ("kill", 10, 1), ("sigterm", 6, None), ("heartbeat_delay", 5, None),
      ("drop_msg", 8, None), ("corrupt_ckpt", 4, None)]
  assert sched[2].secs == 2.5
  assert faults.parse_schedule("") == []
  assert faults.parse_schedule(None) == []


@pytest.mark.parametrize("bad", [
    "explode@4",          # unknown kind
    "kill@x",             # non-integer step
    "kill@0",             # steps are 1-based
    "kill",               # no step
    "kill@4:rank=one",    # malformed modifier value
    "kill@4:depth=2",     # unknown modifier
])
def test_parse_schedule_rejects_malformed(bad):
  with pytest.raises(faults.FaultScheduleError):
    faults.parse_schedule(bad)


def test_validation_wires_fault_schedule(tmp_path):
  with pytest.raises(validation.ParamError, match="fault_schedule"):
    validation.validate_cross_flags(
        params_lib.make_params(fault_schedule="explode@4"))
  with pytest.raises(validation.ParamError, match="train_dir"):
    validation.validate_cross_flags(
        params_lib.make_params(fault_schedule="corrupt_ckpt@4"))
  with pytest.raises(validation.ParamError, match="training"):
    validation.validate_cross_flags(params_lib.make_params(
        fault_schedule="kill@4", forward_only=True,
        train_dir=str(tmp_path)))
  # kill/sigterm without a train_dir would re-fire every relaunched
  # generation (no one-shot marker) and have nothing to rejoin from.
  with pytest.raises(validation.ParamError, match="one-shot"):
    validation.validate_cross_flags(params_lib.make_params(
        fault_schedule="kill@4:rank=1"))
  # Every fault kind must have its observer wired, or the injection
  # proves nothing: drop_msg needs elastic polling, heartbeat_delay a
  # live watchdog session.
  with pytest.raises(validation.ParamError, match="elastic"):
    validation.validate_cross_flags(params_lib.make_params(
        fault_schedule="drop_msg@2"))
  with pytest.raises(validation.ParamError, match="watchdog"):
    validation.validate_cross_flags(params_lib.make_params(
        fault_schedule="heartbeat_delay@3"))
  with pytest.raises(validation.ParamError, match="watchdog"):
    validation.validate_cross_flags(params_lib.make_params(
        fault_schedule="heartbeat_delay@3", train_dir=str(tmp_path),
        stall_watchdog_factor=0))
  validation.validate_cross_flags(params_lib.make_params(
      fault_schedule="kill@4:rank=1,drop_msg@2", elastic=True,
      train_dir=str(tmp_path)))
  validation.validate_cross_flags(params_lib.make_params(
      fault_schedule="heartbeat_delay@3", train_dir=str(tmp_path)))


# -- pure-unit: injector semantics --------------------------------------------

def test_rank_filter():
  sched = faults.parse_schedule("kill@10:rank=1,drop_msg@4")
  inj0 = faults.FaultInjector(sched, rank=0)
  inj1 = faults.FaultInjector(sched, rank=1)
  assert inj0.due(4) and not inj0.due(10)
  assert inj1.due(4) and inj1.due(10)
  assert [f.kind for f in inj1.peek_due(10)] == ["kill"]


def test_one_shot_persists_across_generations(tmp_path):
  """The marker file written BEFORE a fault fires keeps it from
  re-firing when a restarted generation replays past its step (the
  kill/rejoin loop-breaker)."""
  sched = faults.parse_schedule("drop_msg@3,heartbeat_delay@5:secs=0")
  inj = faults.FaultInjector(sched, rank=0, state_dir=str(tmp_path))
  fired = inj.fire_due(3)
  assert fired.dropped_message and [f.kind for f in fired.fired] == [
      "drop_msg"]
  assert not inj.due(3) and inj.due(5)
  # A fresh injector (the restarted generation) reads the marker.
  inj2 = faults.FaultInjector(sched, rank=0, state_dir=str(tmp_path))
  assert not inj2.due(3) and inj2.due(5)
  assert inj2.fire_due(3).fired == []


def test_corrupt_ckpt_truncates_newest(tmp_path):
  (tmp_path / "model.ckpt-2.msgpack").write_bytes(b"x" * 100)
  (tmp_path / "model.ckpt-4.msgpack").write_bytes(b"y" * 100)
  inj = faults.FaultInjector(faults.parse_schedule("corrupt_ckpt@4"),
                             rank=0)
  inj.fire_due(4, train_dir=str(tmp_path))
  assert (tmp_path / "model.ckpt-4.msgpack").stat().st_size == 50
  assert (tmp_path / "model.ckpt-2.msgpack").stat().st_size == 100


# -- in-process e2e -----------------------------------------------------------

class _OneTarget:
  """A pending-RESIZE controller: the target stays pending until a poll
  actually consumes it (what drop_msg must not lose)."""

  def __init__(self, target):
    self.target = target

  def poll(self):
    t, self.target = self.target, None
    return t


def _run(controller=None, **overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=8, num_warmup_batches=0,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=8, init_learning_rate=0.005)
    defaults.update(overrides)
    bench = benchmark.BenchmarkCNN(params_lib.make_params(**defaults))
    if controller is not None:
      bench.elastic_controller = controller
    stats = bench.run()
  finally:
    log_util.log_fn = orig
  return logs, stats


@pytest.mark.slow
def test_drop_msg_delays_but_never_loses_a_resize():
  """The dropped poll's RESIZE stays pending and lands at the NEXT poll
  window -- a lost coordination message may delay a resize, never drop
  it. The fault fires at a NON-poll boundary (step 3; polls run every
  4): the drop is sticky until it suppresses an actual poll, so the
  injection always tests something."""
  logs, stats = _run(controller=_OneTarget(4), num_batches=12,
                     elastic=True, elastic_check_every_n_steps=4,
                     fault_schedule="drop_msg@3")
  assert any("fault injected: drop_msg at step 3" in l for l in logs)
  assert any("fault drop_msg: coordination poll at step 4 dropped" in l
             for l in logs), logs
  assert [e["step"] for e in stats["reshape_events"]] == [8], logs
  assert any("elastic event: generation 1: mesh 8 -> 4, resume "
             "step 8" in l for l in logs), logs


@pytest.mark.slow
def test_heartbeat_delay_starves_watchdog_which_never_kills(tmp_path):
  """A 6 s injected heartbeat gap (past the 5 s min-stall floor) makes
  the watchdog emit its diagnostic and count a stall; the run finishes
  -- the watchdog NEVER kills (telemetry.py StallWatchdog)."""
  tmp = str(tmp_path / "train")
  logs, stats = _run(train_dir=tmp, stall_watchdog_factor=0.1,
                     fault_schedule="heartbeat_delay@4:secs=6")
  assert any("fault injected: heartbeat_delay 6s at step 4" in l
             for l in logs)
  assert any("stall watchdog: no dispatch completed for" in l
             for l in logs), logs
  assert stats["num_steps"] == 8  # the run survived to completion
  assert stats["health"]["watchdog_stalls"] >= 1
  # The fault landed in the flight-recorder window too.
  with open(os.path.join(tmp, "flight_recorder.jsonl")) as f:
    rows = [json.loads(l) for l in f if l.strip()]
  assert any(r.get("fault_event", "").startswith("heartbeat_delay")
             for r in rows), rows


# -- subprocess e2e (the signals are real) ------------------------------------

def _cli_cmd(train_dir, *extra):
  return [sys.executable, "-m", "kf_benchmarks_tpu.cli",
          "--model=trivial", "--device=cpu", "--num_devices=1",
          "--batch_size=4", "--num_batches=6", "--num_warmup_batches=0",
          "--display_every=1", f"--train_dir={train_dir}", *extra]


def _cli_env():
  env = dict(os.environ)
  env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
  env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
  return env


@pytest.mark.slow
def test_sigterm_fault_produces_postmortem(tmp_path):
  """sigterm@3 rides the real delivery path: the chained telemetry
  handlers dump the flight-recorder window, then the default handler
  terminates the process -- preemption produces a post-mortem instead
  of silence."""
  tmp = str(tmp_path / "train")
  proc = subprocess.run(
      _cli_cmd(tmp, "--fault_schedule=sigterm@3"),
      env=_cli_env(), capture_output=True, text=True)
  assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                              proc.stdout, proc.stderr)
  dump = os.path.join(tmp, "flight_recorder.dump.jsonl")
  assert os.path.exists(dump), os.listdir(tmp)
  with open(dump) as f:
    rows = [json.loads(l) for l in f if l.strip()]
  assert any(r.get("flight_recorder_dump") == "signal SIGTERM"
             for r in rows), rows
  # The window behind the diagnosis row carries the pre-signal steps.
  assert any("loss" in r for r in rows), rows


@pytest.mark.slow
def test_kill_after_corrupt_ckpt_resumes_from_previous_snapshot(tmp_path):
  """corrupt_ckpt@5 + kill@5: the newest snapshot (step 4) is torn and
  the worker is SIGKILL'd before any further save. The relaunched run
  must SKIP the torn file with a logged warning and resume from step 2
  -- a torn write never poisons resume (the satellite-1 contract, end
  to end)."""
  tmp = str(tmp_path / "train")
  cmd = _cli_cmd(tmp, "--save_model_steps=2",
                 "--fault_schedule=corrupt_ckpt@5,kill@5")
  proc = subprocess.run(cmd, env=_cli_env(), capture_output=True,
                        text=True)
  assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                              proc.stdout, proc.stderr)
  # On disk: a valid step-2 snapshot and a truncated step-4 one.
  assert os.path.exists(os.path.join(tmp, "model.ckpt-4.msgpack"))
  # Relaunch the SAME command: the fired-fault markers in train_dir
  # keep step 5's faults from re-firing on the replay.
  proc2 = subprocess.run(cmd, env=_cli_env(), capture_output=True,
                         text=True)
  assert proc2.returncode == 0, (proc2.returncode, proc2.stdout,
                                 proc2.stderr)
  out = proc2.stdout
  assert re.search(r"skipping torn/corrupt checkpoint "
                   r"model\.ckpt-4\.msgpack", out), out
  assert "Restored checkpoint at global step 2" in out, out
  assert "total images/sec" in out, out
