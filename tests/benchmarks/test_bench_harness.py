"""The harness end to end: one CPU rehearsal of a whole run at tiny size
(``trivial`` model, batch 4, 5 + 70 steps) with the device check stubbed
in the test, the pieces of the clock on hand-made lines, and the command
refusing a machine without a TPU."""

import json
import math
import os
import shutil
import subprocess
import sys
import time

import pytest

import bench_testlib
from bench_testlib import REPO, TINY_CELL, TINY_E2E, TINY_LAYER, \
    make_tiny_tree
from benchmarks import checks
from benchmarks import harness
from benchmarks import xplane

STEP_LINE = "%d\timages/sec: 100.0 +/- 0.0 (jitter = 0.0)\t%s"


# -- the rehearsal ------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
  """ONE untraced run of the tiny cell; (result, info lines, program
  lines)."""
  root = make_tiny_tree(str(tmp_path_factory.mktemp("bench")))
  mp = pytest.MonkeyPatch()
  bench_testlib.stub_machine(mp)
  program_lines = []
  mp.setattr(harness, "_stderr", program_lines.append)
  said = []
  try:
    result = harness.run_cell(root, TINY_CELL, seed=7, seconds=7.0,
                              traced=False, t0=time.monotonic(),
                              say=said.append)
  finally:
    mp.undo()
  return result, {s["info"]: s for s in said}, program_lines


def test_rehearsal_last_line_keys(rehearsal):
  result, _, _ = rehearsal
  assert list(result) == ["correct", "attempted", "failed", "metrics",
                          "device"]
  assert result["correct"] is True
  assert result["attempted"] == 70 and result["failed"] == 0
  assert set(result["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
  assert set(result["metrics"]) == set(TINY_E2E)
  for metric in result["metrics"].values():
    assert set(metric) == {"value", "unit"}
    assert isinstance(metric["value"], float) and metric["value"] > 0
  json.loads(harness.dumps(result))


def test_rehearsal_window_is_sized_from_the_step_hint(rehearsal):
  _, info, _ = rehearsal
  # 7 s at a hint of 0.1 s a step: 70 timed steps after 5 of warm-up.
  assert info["cell"]["make_params"]["num_batches"] == 70
  assert info["cell"]["make_params"]["num_warmup_batches"] == 5
  assert info["cell"]["make_params"]["tf_random_seed"] == 7
  assert info["window"]["step_lines"] == 70
  assert info["window"]["global_batch"] == 4


def test_rehearsal_stamps_every_step_line(rehearsal):
  result, info, lines = rehearsal
  timed = [l for l in lines if harness.STEP_RE.match(l)]
  assert len(timed) == 70
  rate = result["metrics"]["samples_per_sec"]["value"]
  assert rate == pytest.approx(69 * 4 / info["window"]["window_s"])
  # The benchmark's clock and the program's own agree on a quiet run.
  assert rate == pytest.approx(info["window"]["program_images_per_sec"],
                               rel=0.2)


def test_rehearsal_loss_is_the_64th_timed_line(rehearsal):
  result, info, lines = rehearsal
  timed = [l for l in lines if harness.STEP_RE.match(l)]
  want = float(timed[63].split("\t")[-1])
  assert timed[63].startswith("64\t")
  assert info["loss"]["step_64"] == want
  assert info["loss"]["step_1"] == float(timed[0].split("\t")[-1])


def test_rehearsal_setup_split_adds_up(rehearsal):
  result, info, _ = rehearsal
  split = info["setup"]
  parts = (split["import_s"] + split["init_s"] + split["first_dispatch_s"]
           + split["warmup_s"])
  assert parts == pytest.approx(split["setup_s"])
  assert result["metrics"]["setup_s"]["value"] == split["setup_s"]
  assert info["compile"]["compiles_in_window"] == 0
  assert info["compile"]["ledger_shapes"] == 1


def test_rehearsal_non_finite_loss_counts_as_failed(tmp_path, stub_machine):
  # A learning rate no float32 survives: the loss is inf or nan within a
  # few steps, the run is not correct and the bad steps are counted.
  root = make_tiny_tree(str(tmp_path),
                        extra_params={"init_learning_rate": 1e30})
  lines = []
  result = harness.run_cell(root, TINY_CELL, seed=1, seconds=1.0,
                            traced=False, t0=time.monotonic(),
                            say=lambda obj: lines.append(harness.dumps(obj)))
  # Every line is still JSON: a nan reaches it as null.
  assert json.loads(lines[-1])["failures"]
  json.loads(harness.dumps(result))
  assert result["attempted"] == 10
  assert 0 < result["failed"] <= 10
  assert result["correct"] is False


def test_rehearsal_traced_run_reads_the_per_layer_metrics(
    tmp_path, stub_machine, monkeypatch):
  # A CPU trace has no device plane, so the reduction is fed the recorded
  # TPU trace; everything before it (profiler opened and closed from the
  # step lines, the file found) is the real path.
  root = make_tiny_tree(str(tmp_path))
  fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "vgg16_4chip.xplane.pb")
  real_find = xplane.find_xplane
  found = []

  def find(trace_dir):
    found.append(real_find(trace_dir))
    return fixture

  monkeypatch.setattr(xplane, "find_xplane", find)
  said = []
  result = harness.run_cell(root, TINY_CELL, seed=1, seconds=7.0,
                            traced=True, t0=time.monotonic(),
                            say=said.append)
  assert found[0] and found[0].endswith(".xplane.pb")
  info = {s["info"]: s for s in said}
  assert info["trace"]["first_line"] == 20
  assert info["trace"]["last_line"] >= 20 + 20 + xplane.SKIP_STEPS
  assert list(result) == ["correct", "attempted", "failed", "breakdown",
                          "metrics", "device"]
  assert set(result["metrics"]) == set(TINY_LAYER)
  assert result["metrics"]["train_loss_step_64"]["value"] == \
      info["loss"]["step_64"]
  assert result["device"]["busy_s"] > 0
  assert result["device"]["window_s"] >= result["device"]["busy_s"]
  for rows in result["breakdown"].values():
    assert 0 < len(rows) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in rows)


# -- the clock, on hand-made lines --------------------------------------------

def _log(losses, on_step=None):
  log = harness.StepLog(lambda line: None, on_step)
  log("Running warm up")
  log("Step\tImg/sec\ttotal_loss")
  for i, loss in enumerate(losses, 1):
    log(STEP_LINE % (i, loss))
  log("total images/sec: 123.45")
  return log


def test_steplog_parses_and_stamps():
  log = _log(["7.661", "7.500", "nan", "inf"])
  assert [s.index for s in log.steps] == [1, 2, 3, 4]
  assert log.steps[1].loss == 7.5 and math.isnan(log.steps[2].loss)
  assert log.t_warmup <= log.t_header <= log.steps[0].t <= log.t_banner
  assert log.banners == 1
  assert [s.t for s in log.steps] == sorted(s.t for s in log.steps)


@pytest.mark.parametrize("losses, timed, failed", [
    (["1.0", "2.0", "3.0"], 3, 0),
    (["1.0", "nan", "3.0"], 3, 1),
    (["1.0", "inf"], 4, 3),          # one non-finite, two lines missing
    ([], 2, 2),
])
def test_failed_steps(losses, timed, failed):
  log = _log(losses)
  assert checks.failed_steps(log.steps, timed) == failed
  assert bool(checks.step_lines(log.steps, timed)) == bool(failed)


def test_trace_window_opens_and_closes_on_step_lines(monkeypatch):
  import jax
  calls = []
  monkeypatch.setattr(jax.profiler, "start_trace",
                      lambda d: calls.append(("start", d)))
  monkeypatch.setattr(jax.profiler, "stop_trace",
                      lambda: calls.append(("stop",)))
  window = harness.TraceWindow("dir", after_steps=5, min_steps=4,
                               min_s=0.0, max_s=60.0)
  _log(["1.0"] * 30, window.on_step)
  assert calls == [("start", "dir"), ("stop",)]
  assert window.first == 5
  assert window.last == 5 + 4 + xplane.SKIP_STEPS + 1
  assert not window.tracing
  # Intervals the profiler distorted: after its start and after its stop;
  # with whole_window, everything in between as well.
  stalled = [i for i in range(1, 31) if window.stalled(i, False)]
  assert stalled == [6, 7, 8, 13, 14, 15]
  inside = [i for i in range(1, 31) if window.stalled(i, True)]
  assert inside == list(range(6, 16))


def test_dumps_writes_non_finite_numbers_as_null():
  assert harness.dumps({"a": float("nan"), "b": [1.5, float("inf")],
                        "c": {"d": -float("inf"), "e": "nan"}}) == \
      '{"a": null, "b": [1.5, null], "c": {"d": null, "e": "nan"}}'


@pytest.mark.parametrize("q, want", [(0, 1.0), (50, 3.0), (90, 4.6),
                                     (100, 5.0)])
def test_percentile(q, want):
  assert harness.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == \
      pytest.approx(want)


# -- the machine --------------------------------------------------------------

class _Dev:
  def __init__(self, platform, kind):
    self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices, chips, message", [
    ([_Dev("cpu", "cpu")], 1, "no accelerator"),
    ([_Dev("tpu", "TPU v9")], 1, "not in benchmarks/peaks.json"),
    ([_Dev("tpu", "TPU v5 lite")], 4, "needs 4 chip"),
])
def test_check_device_refuses(devices, chips, message):
  with pytest.raises(harness.Refused, match=message):
    harness.check_device(devices, chips, {"TPU v5 lite": {}})


def test_check_device_reports_what_jax_reports():
  devices = [_Dev("tpu", "TPU v5 lite")] * 4
  assert harness.check_device(devices, 1, {"TPU v5 lite": {}}) == {
      "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_command_refuses_a_machine_without_a_tpu(capsys):
  # The tier-1 command runs under JAX_PLATFORMS=cpu: the command's own
  # main(), as `python3 benchmarks/run.py` calls it, must refuse.
  from benchmarks import run as run_py
  bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
  code = run_py.main(["--workload", bench["workloads"][0]["name"],
                      "--seed", "1", "--seconds", "1", "--trace", "0"])
  out, err = capsys.readouterr()
  assert code != 0
  assert "REFUSED no accelerator" in err
  assert '"metrics"' not in out and '"correct"' not in out


def test_command_fails_without_the_program(tmp_path):
  # A directory that holds only BENCHMARK.json and the files under `paths`.
  bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
  shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
  for path in bench["paths"]:
    shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                    ignore=shutil.ignore_patterns("__pycache__"))
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  env.pop("PYTHONPATH", None)
  done = subprocess.run(
      [sys.executable] + bench["command"][1:] +
      ["--workload", bench["workloads"][0]["name"], "--seed", "1",
       "--seconds", "1", "--trace", "0"],
      cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
  assert done.returncode != 0
  assert "kf_benchmarks_tpu" in done.stderr
  assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


class _MemDev:
  def __init__(self, stats):
    self._stats = stats

  def memory_stats(self):
    return self._stats


def test_memory_peak_is_live_plus_reserved_on_the_fullest_chip():
  devices = [_MemDev({"peak_bytes_in_use": 100, "peak_bytes_reserved": 900}),
             _MemDev({"peak_bytes_in_use": 300, "peak_bytes_reserved": 50}),
             _MemDev({"peak_bytes_in_use": 400})]
  assert harness.memory_peak_bytes(devices) == 1000
  with pytest.raises(RuntimeError, match="peak_bytes_in_use"):
    harness.memory_peak_bytes([_MemDev(None)])
