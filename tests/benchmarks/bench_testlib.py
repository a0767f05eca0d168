"""Shared pieces of the benchmark's own tests: the repo root on the
path, and a tiny CPU benchmark tree (``trivial`` model, batch 4) that the
harness's discovery and the rehearsal run against. A module of its own
(not conftest.py) so that test files can import it by a name nothing
else in ``tests/`` has."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

TINY_CELL = "trivial-train-bs4-cpu"
TINY_E2E = ["samples_per_sec", "peak_hbm_gib", "setup_s"]
TINY_LAYER = ["first_dispatch_s", "compiles_in_window", "host_dispatch_ms",
              "step_ms_p90", "device_step_ms", "train_loss_step_64", "mfu",
              "device_idle_share"]


def write_json(path, obj):
  os.makedirs(os.path.dirname(path), exist_ok=True)
  with open(path, "w", encoding="utf-8") as f:
    json.dump(obj, f, indent=2)


def make_tiny_tree(root, extra_params=None):
  """A benchmark tree of one CPU cell under ``root``; the metric readers
  and the peaks table are the repo's own."""
  for sub in ("end_to_end", "layer_metrics"):
    shutil.copytree(os.path.join(REPO, "benchmarks", sub),
                    os.path.join(root, "benchmarks", sub))
  shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"),
              os.path.join(root, "benchmarks", "peaks.json"))
  params = {"model": "trivial", "device": "cpu", "optimizer": "momentum"}
  params.update(extra_params or {})
  write_json(os.path.join(root, "benchmarks/configs/trivial.json"), {
      "source": "tests", "reduced": [], "params": params,
      "sample_unit": "images", "forward_flops_per_sample": 1000})
  write_json(os.path.join(root, "benchmarks/traffic/train-bs4-cpu.json"), {
      "chips": 1, "checks": [],
      "params": {"batch_size": 4, "num_devices": 1,
                 "variable_update": "replicated"}})
  write_json(os.path.join(root, f"benchmarks/workloads/{TINY_CELL}.json"), {
      "config": "trivial", "traffic": "train-bs4-cpu", "chips": 1,
      "step_s_hint": 0.1,
      "trace": {"after_steps": 20, "min_steps": 20, "min_s": 0.2,
                "max_s": 3.0},
      "end_to_end": TINY_E2E, "per_layer": TINY_LAYER})
  write_json(os.path.join(root, "BENCHMARK.json"), {
      "configs": [{"name": "trivial",
                   "file": "benchmarks/configs/trivial.json"}],
      "workloads": [{"name": TINY_CELL, "config": "trivial",
                     "traffic": "train-bs4-cpu", "chips": 1}]})
  return root


def stub_machine(mp):
  """Stub the device check and the memory reading on MonkeyPatch ``mp``.
  Done in the tests: the harness has no option that lets a CPU pass for a
  chip."""
  from benchmarks import harness
  mp.setattr(harness, "check_device",
             lambda devices, chips, peaks: {
                 "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
  mp.setattr(harness, "memory_peak_bytes", lambda devices: 5 * 2 ** 30)
