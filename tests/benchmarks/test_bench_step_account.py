"""The three readers of the program's step account (PR 37), without a
chip: ``host_stalls_in_window``, ``host_step_max_over_median`` and
``host_self_ms`` on a made-up account, with and without a trace window
(the iterations the harness's own profiler distorted are dropped, a
stall anywhere else is kept), on a program without the account (nothing,
and no raise), through the harness on the CPU, and each file against its
entry."""

import math
import os
import time

import pytest

import bench_testlib
from bench_testlib import REPO, TINY_CELL, make_tiny_tree
from benchmarks import harness
from benchmarks import spec
from benchmarks import step_account
from benchmarks import xplane
from test_bench_spec import metric_rules

METRICS = ["host_stalls_in_window", "host_step_max_over_median",
           "host_self_ms"]
STEP_S = 0.25        # a made-up iteration: a quarter second, 1 ms its own
T0 = 1000.0


def _account(durations, self_s=0.001):
  """Rows back to back from ``T0``, each ``self_s`` its own and the rest
  under the blocking fetch."""
  rows, t = [], T0
  for i, dur in enumerate(durations):
    rows.append({"step": 5 + i, "t0": t, "dur_s": dur,
                 "by_span": {"fetch/metrics": dur - self_s,
                             "self": self_s}})
    t += dur
  return {"iterations": len(rows), "rows": rows, "stalls": [],
          "median_s": None}


def _window(t_start=None, t_stop=None):
  window = harness.TraceWindow("dir", after_steps=5, min_steps=6,
                               min_s=0.0, max_s=60.0)
  window.t_start, window.t_stop = t_start, t_stop
  return window


def _run(stats, window=None):
  return harness.Run(cell={"name": "x", "config_data": {}}, device={},
                     peaks={}, kwargs={}, timed_steps=40, t0=0.0,
                     stats=stats, window=window)


def _read(run):
  return {m: spec.load_metric(REPO, "per_layer", m).read(run)
          for m in METRICS}


def test_an_even_run_reads_no_stall():
  got = _read(_run({"step_account": _account([STEP_S] * 40)}))
  assert got["host_stalls_in_window"] == 0
  assert got["host_step_max_over_median"] == pytest.approx(1.0)
  assert got["host_self_ms"] == pytest.approx(1.0)


def test_a_planted_stall_is_counted_and_sized():
  durations = [0.003, 0.003] + [STEP_S] * 38   # two to fill the pipeline
  durations[30] = 2.5
  durations[12] = 1.4 * STEP_S                  # late, and not a stall
  got = _read(_run({"step_account": _account(durations)}))
  assert got["host_stalls_in_window"] == 1
  assert got["host_step_max_over_median"] == pytest.approx(10.0)


@pytest.mark.parametrize("which", ["start", "stop", "both"])
def test_the_profilers_own_iterations_are_dropped(which):
  """The iteration in which the harness's profiler started (or stopped)
  and the STALL_STEPS after it leave the reading; a stall elsewhere
  stays, inside the traced stretch too."""
  durations = [STEP_S] * 40
  durations[5] = 3.0              # start_trace, inside the line's listener
  durations[7] = 0.6              # the pipeline refilling behind it
  durations[20] = 2.0             # stop_trace
  durations[12] = 1.0             # a stall of the program's own
  account = _account(durations)
  rows = account["rows"]
  inside = lambda i: rows[i]["t0"] + 0.9 * rows[i]["dur_s"]
  window = _window(
      t_start=inside(5) if which in ("start", "both") else None,
      t_stop=inside(20) if which in ("stop", "both") else None)
  run = _run({"step_account": account}, window)
  kept = step_account.rows_left(run)
  drop = lambda i: set(range(i, i + 1 + harness.STALL_STEPS))
  dropped = ((drop(5) if which != "stop" else set()) |
             (drop(20) if which != "start" else set()))
  assert harness.STALL_STEPS == 3
  assert [r["step"] - 5 for r in kept] == [
      i for i in range(40) if i not in dropped]
  got = _read(run)
  left = {"start": [20, 12], "stop": [5, 7, 12], "both": [12]}[which]
  assert got["host_stalls_in_window"] == len(left)
  assert got["host_step_max_over_median"] == pytest.approx(
      max(durations[i] for i in left) / STEP_S)
  assert got["host_self_ms"] == pytest.approx(1.0)


def test_a_stop_after_the_last_iteration_drops_nothing():
  # The profiler closed in the flush after the loop (or by the harness's
  # own finally): no iteration holds the stamp.
  account = _account([STEP_S] * 12)
  run = _run({"step_account": account},
             _window(t_start=T0 - 5.0, t_stop=T0 + 13 * STEP_S))
  assert len(step_account.rows_left(run)) == 12


@pytest.mark.parametrize("stats", [
    None, {}, {"step_account": None},
    {"step_account": {"iterations": 0, "median_s": None, "rows": [],
                      "stalls": []}}],
    ids=["no-stats", "no-account", "none", "no-rows"])
def test_a_program_without_the_account_reads_as_nothing(stats):
  """The parent of the PR that added the account, or a loop that never
  ran: None (the line leaves the metric out), never 0, never a raise."""
  for window in (None, _window(T0, T0 + 1.0)):
    assert _read(_run(stats, window)) == dict.fromkeys(METRICS)


def test_every_iteration_dropped_reads_as_nothing():
  run = _run({"step_account": _account([STEP_S] * 3)},
             _window(t_start=T0 + 0.1))
  assert step_account.rows_left(run) is None
  assert _read(run) == dict.fromkeys(METRICS)


@pytest.mark.parametrize("name", METRICS)
def test_reader_agrees_with_its_entry_and_names_no_cell(name):
  metric_rules(REPO, "per_layer", name)
  entry = spec._entry(spec.load_benchmark(REPO)["per_layer"], name,
                      "per-layer metric")
  # Defined in every run of a program that keeps the account: every cell
  # that reports samples_per_sec reports it, those of later PRs too.
  assert "workloads" not in entry
  assert entry["layer"] == "driver_loop"
  assert entry["moves"] == "samples_per_sec"
  for cell in spec.load_benchmark(REPO)["workloads"]:
    assert name in spec.cell_metrics(REPO, "per_layer", cell["name"])


def test_the_three_entries_are_listed_once():
  names = [m["name"] for m in spec.load_benchmark(REPO)["per_layer"]]
  assert all(names.count(m) == 1 for m in METRICS)


def test_traced_rehearsal_reports_the_three_from_the_programs_account(
    tmp_path, stub_machine, monkeypatch):
  """Through the harness on the CPU (the recorded TPU trace stands in
  for the device planes): the program's real account, the real window's
  stamps on the same clock, three numbers on the line."""
  root = make_tiny_tree(str(tmp_path),
                        per_layer=bench_testlib.TINY_LAYER + METRICS)
  # (the tiny tree holds the metric files; the shared reader is imported
  # from the checkout, as spans.py is)
  fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "vgg16_4chip.xplane.pb")
  monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: fixture)
  seen = {}
  real_rows_left = step_account.rows_left

  def rows_left(run):
    seen["run"] = run
    return real_rows_left(run)

  monkeypatch.setattr(step_account, "rows_left", rows_left)
  result = harness.run_cell(root, TINY_CELL, seed=1, seconds=7.0,
                            traced=True, t0=time.monotonic(),
                            say=lambda obj: None)
  assert result["correct"] is True
  metrics = {k: v["value"] for k, v in result["metrics"].items()}
  assert set(METRICS) <= set(metrics)
  assert all(math.isfinite(metrics[m]) for m in METRICS)
  assert metrics["host_stalls_in_window"] >= 0
  assert metrics["host_step_max_over_median"] >= 1.0
  assert 0 < metrics["host_self_ms"] < 50.0
  run = seen["run"]
  account = run.stats["step_account"]
  assert account["iterations"] == run.timed_steps == len(account["rows"])
  # One clock: both profiler stamps lie inside an iteration of the
  # account, and those iterations (with the STALL_STEPS after) are gone.
  rows = account["rows"]
  holds = lambda t: [i for i, r in enumerate(rows)
                     if r["t0"] <= t <= r["t0"] + r["dur_s"]]
  (start,), stop = holds(run.window.t_start), holds(run.window.t_stop)
  # (on_step counts step LINES, printed two iterations after dispatch)
  assert start == run.window.first + 1
  kept = {r["step"] for r in real_rows_left(run)}
  for i in [start] + stop:
    assert rows[i]["step"] not in kept
    assert rows[min(i + harness.STALL_STEPS, len(rows) - 1)]["step"] \
        not in kept
  assert len(kept) >= len(rows) - 2 * (1 + harness.STALL_STEPS)
