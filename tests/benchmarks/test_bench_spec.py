"""BENCHMARK.json against the builder's contract, every name against its
file, and the proof that a configuration, a cell and a metric are each
added by new files and new entries alone."""

import glob
import json
import os
import re

import pytest

from bench_testlib import REPO, TINY_CELL, make_tiny_tree, write_json
from benchmarks import checks
from benchmarks import harness
from benchmarks import spec

BENCH = spec.load_benchmark(REPO)
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYER_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
E2E = [m["name"] for m in BENCH["end_to_end"]]
LAYER = [m["name"] for m in BENCH["per_layer"]]


def _files(sub, ext):
  return sorted(os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
      os.path.join(REPO, "benchmarks", sub, "*" + ext)))


# -- the contract's shape -----------------------------------------------------

def test_exactly_the_contract_keys():
  assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
  assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
  assert BENCH["command"] == ["python3", "benchmarks/run.py"]
  assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
  for arg in BENCH["command"]:
    assert not arg.startswith("/") and ".." not in arg
  assert isinstance(BENCH["run_seconds"], int)
  assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_plain_and_unique():
  names = CELLS + CONFIGS + E2E + LAYER
  assert len(names) == len(set(names))
  for name in names:
    assert NAME_RE.match(name), name


def test_files_under_paths_have_plain_names():
  for top in BENCH["paths"]:
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, top)):
      dirnames[:] = [d for d in dirnames if d != "__pycache__"]
      for f in filenames:
        rel = os.path.relpath(os.path.join(dirpath, f), REPO)
        assert PATH_RE.match(rel), rel


def test_cells_chips_and_whys():
  assert 2 <= len(CELLS) <= 24
  pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
  assert len(pairs) == len(set(pairs))
  four = [w for w in BENCH["workloads"] if w["chips"] == 4]
  assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
  assert len(four) <= max(1, len(CELLS) // 4)
  for entry in BENCH["workloads"] + BENCH["configs"]:
    assert 0 < len(entry["why"]) <= 200, entry["name"]


def test_every_config_is_used_and_not_reduced():
  used = {w["config"] for w in BENCH["workloads"]}
  assert used == set(CONFIGS)
  files = [c["file"] for c in BENCH["configs"]]
  assert len(files) == len(set(files))
  for c in BENCH["configs"]:
    assert c["file"].startswith("benchmarks/")
    assert c["reduced"] == []
    assert c["source"].startswith("https://")


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
  assert set(metric) <= {"name", "unit", "better", "bound", "source",
                         "workloads"}
  assert metric["better"] in ("higher", "lower")
  assert metric["source"] in ("host_clock", "device_trace")
  assert 0.01 <= metric["bound"] <= 0.1


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry(metric):
  assert set(metric) <= {"name", "unit", "better", "source", "layer",
                         "moves", "workloads"}
  assert metric["source"] in SOURCES
  assert LAYER_RE.match(metric["layer"]), metric["layer"]
  assert metric["moves"] in E2E
  assert set(metric.get("workloads", CELLS)) <= set(CELLS)
  if metric["name"].endswith("_roofline"):
    assert metric["unit"] == "%"


def test_setup_s_is_an_end_to_end_metric():
  assert "setup_s" in E2E


# -- every name resolves to a file, every file to a name ----------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_config_file(name):
  config = spec.load_config(REPO, name)
  entry = next(c for c in BENCH["configs"] if c["name"] == name)
  assert config["source"] == entry["source"]
  assert config["reduced"] == entry["reduced"]
  assert config["sample_unit"] == "images"
  assert config["forward_flops_per_sample"] > 0
  assert config["params"]["device"] == "tpu"


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
  cell = spec.load_cell(REPO, name)
  assert cell["step_s_hint"] > 0
  assert set(cell["trace"]) == {"after_steps", "min_steps", "min_s",
                                "max_s"}
  assert set(cell["traffic_data"].get("checks", [])) <= set(checks.NAMED)
  assert cell["who"] and cell["why"]
  # What the cell reports is what BENCHMARK.json says exists there.
  for kind, names in (("end_to_end", E2E), ("per_layer", LAYER)):
    by_name = {m["name"]: m for m in BENCH[kind]}
    want = [n for n in names if name in by_name[n].get("workloads", CELLS)]
    assert cell[kind] == want
  assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
  assert len(cell["per_layer"]) >= 1
  kwargs = harness.job_kwargs(cell, seed=3, seconds=BENCH["run_seconds"])
  assert kwargs["num_devices"] == cell["chips"]
  assert kwargs["tf_random_seed"] == 3 and kwargs["display_every"] == 1
  # 100 steps or more, so that step_ms_p90 has ten samples beyond it and
  # loss_at_step_64 exists.
  assert kwargs["num_batches"] >= 100


@pytest.mark.parametrize("kind, name",
                         [("end_to_end", n) for n in E2E] +
                         [("per_layer", n) for n in LAYER])
def test_metric_file_agrees_with_its_entry(kind, name):
  module = spec.load_metric(REPO, kind, name)
  entry = next(m for m in BENCH[kind] if m["name"] == name)
  for field in spec.METRIC_FIELDS[kind]:
    assert getattr(module, field) == entry[field.lower()], field
  assert callable(module.read) and module.__doc__


def test_no_file_without_an_entry():
  assert _files("configs", ".json") == sorted(CONFIGS)
  assert _files("workloads", ".json") == sorted(CELLS)
  assert _files("traffic", ".json") == sorted(
      {w["traffic"] for w in BENCH["workloads"]})
  assert _files("end_to_end", ".py") == sorted(E2E)
  assert _files("layer_metrics", ".py") == sorted(LAYER)


def test_peaks_table():
  peaks = spec.load_peaks(REPO)
  v5e = peaks["TPU v5 lite"]
  assert v5e["bf16_flops_per_s"] == 197e12
  assert v5e["hbm_bytes_per_s"] == 819e9
  assert all(p["source"] for p in peaks.values())


# -- data-driven: add, do not edit --------------------------------------------

def test_config_cell_and_metric_added_as_files(tmp_path):
  root = make_tiny_tree(str(tmp_path))
  bench = spec.load_benchmark(root)
  # A later PR's three additions: files, plus entries in BENCHMARK.json.
  write_json(os.path.join(root, "benchmarks/configs/newnet.json"), {
      "source": "https://example.org/newnet", "reduced": [],
      "params": {"model": "lenet", "device": "cpu"},
      "sample_unit": "tokens", "tokens_per_sample": 128,
      "forward_flops_per_sample": 7})
  write_json(os.path.join(root, "benchmarks/workloads/newnet-cell.json"), {
      "config": "newnet", "traffic": "train-bs4-cpu", "chips": 1,
      "step_s_hint": 0.5, "trace": {}, "end_to_end": ["setup_s"],
      "per_layer": ["new_metric"]})
  with open(os.path.join(root, "benchmarks/layer_metrics/new_metric.py"),
            "w", encoding="utf-8") as f:
    f.write('"""Steps the run timed."""\nLAYER = "driver_loop"\n'
            'UNIT = "count"\nBETTER = "higher"\nSOURCE = "program_counter"\n'
            'MOVES = "samples_per_sec"\n\n\n'
            'def read(run):\n  return run.timed_steps\n')
  bench["configs"].append({"name": "newnet",
                           "file": "benchmarks/configs/newnet.json"})
  bench["workloads"].append({"name": "newnet-cell", "config": "newnet",
                             "traffic": "train-bs4-cpu", "chips": 1})
  write_json(os.path.join(root, "BENCHMARK.json"), bench)

  cell = spec.load_cell(root, "newnet-cell")
  assert cell["config_data"]["params"]["model"] == "lenet"
  kwargs = harness.job_kwargs(cell, seed=1, seconds=10)
  assert kwargs["model"] == "lenet" and kwargs["batch_size"] == 4
  assert kwargs["num_batches"] == 20
  module = spec.load_metric(root, "per_layer", "new_metric")

  class FakeRun:
    timed_steps = 20
  assert module.read(FakeRun) == 20 and module.UNIT == "count"
  # ... and the cell that was there still loads, untouched.
  assert spec.load_cell(root, TINY_CELL)["config"] == "trivial"


@pytest.mark.parametrize("breakage, message", [
    ("unknown", "no workload named"),
    ("chips", "chips is"),
    ("no_metric", "no file"),
    ("harness_key", "which the harness fixes"),
])
def test_disagreements_are_refused(tmp_path, breakage, message):
  root = make_tiny_tree(str(tmp_path))
  traffic = os.path.join(root, "benchmarks/traffic/train-bs4-cpu.json")
  with pytest.raises(spec.SpecError, match=message):
    if breakage == "unknown":
      spec.load_cell(root, "nope")
    elif breakage == "chips":
      data = json.load(open(traffic))
      data["chips"] = 4
      write_json(traffic, data)
      spec.load_cell(root, TINY_CELL)
    elif breakage == "no_metric":
      spec.load_metric(root, "per_layer", "not_there")
    else:
      data = json.load(open(traffic))
      data["params"]["num_batches"] = 5
      write_json(traffic, data)
      harness.job_kwargs(spec.load_cell(root, TINY_CELL), 1, 10)


def test_metric_file_missing_a_field_is_refused(tmp_path):
  root = make_tiny_tree(str(tmp_path))
  path = os.path.join(root, "benchmarks/layer_metrics/half.py")
  with open(path, "w", encoding="utf-8") as f:
    f.write("UNIT = 'ms'\n\n\ndef read(run):\n  return 1\n")
  with pytest.raises(spec.SpecError, match="does not define"):
    spec.load_metric(root, "per_layer", "half")
