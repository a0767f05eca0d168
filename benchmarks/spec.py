"""Discovery: ``BENCHMARK.json`` names things, files define them.

A configuration, a cell and a metric are each ONE file the harness finds
by the name ``BENCHMARK.json`` gives it, so a later PR adds a cell by
adding files and entries and edits nothing that is there:

* configuration ``c``  -> the ``file`` of its ``configs`` entry
* cell ``w``           -> ``benchmarks/workloads/<w>.json``
* traffic mix ``t``    -> ``benchmarks/traffic/<t>.json``
* end-to-end metric    -> ``benchmarks/end_to_end/<name>.py``
* per-layer metric     -> ``benchmarks/layer_metrics/<name>.py``
* peaks                -> ``benchmarks/peaks.json`` (keyed by device_kind)

Every function takes the checkout ``root`` so tests can point the
discovery at a temporary tree.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

METRIC_DIRS = {"end_to_end": "benchmarks/end_to_end",
               "per_layer": "benchmarks/layer_metrics"}
# What a metric file declares beside read(); each must equal the
# metric's BENCHMARK.json entry (tests/benchmarks holds them together).
METRIC_FIELDS = {"end_to_end": ("UNIT", "BETTER", "SOURCE"),
                 "per_layer": ("UNIT", "BETTER", "SOURCE", "LAYER", "MOVES")}


class SpecError(ValueError):
  """A name that resolves to no file, or files that disagree."""


def _load_json(path: str) -> Any:
  try:
    with open(path, encoding="utf-8") as f:
      return json.load(f)
  except OSError as e:
    raise SpecError(f"cannot read {path}: {e}") from e
  except json.JSONDecodeError as e:
    raise SpecError(f"{path} is not JSON: {e}") from e


def load_benchmark(root: str) -> Dict[str, Any]:
  return _load_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: List[Dict[str, Any]], name: str, what: str):
  for e in entries:
    if e["name"] == name:
      return e
  raise SpecError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                  f"{[e['name'] for e in entries]}")


def load_config(root: str, name: str) -> Dict[str, Any]:
  """The configuration's file, as BENCHMARK.json's ``configs`` names it."""
  entry = _entry(load_benchmark(root)["configs"], name, "configuration")
  config = _load_json(os.path.join(root, entry["file"]))
  config["name"] = name
  return config


def load_cell(root: str, name: str) -> Dict[str, Any]:
  """One cell: its BENCHMARK.json entry, its own file, and the files of
  its configuration (``config_data``) and traffic mix (``traffic_data``).
  ``config``, ``traffic`` and ``chips`` are stated in the entry (which the
  driver reads) and in the files (which people read) and must agree."""
  entry = _entry(load_benchmark(root)["workloads"], name, "workload")
  cell = _load_json(os.path.join(root, "benchmarks", "workloads",
                                 name + ".json"))
  traffic = _load_json(os.path.join(root, "benchmarks", "traffic",
                                    entry["traffic"] + ".json"))
  for key, stated in (("config", cell.get("config")),
                      ("traffic", cell.get("traffic")),
                      ("chips", cell.get("chips")),
                      ("chips", traffic.get("chips"))):
    if stated != entry[key]:
      raise SpecError(
          f"cell {name!r}: {key} is {stated!r} in a file and "
          f"{entry[key]!r} in BENCHMARK.json")
  cell["name"] = name
  cell["config_data"] = load_config(root, cell["config"])
  cell["traffic_data"] = traffic
  return cell


def load_metric(root: str, kind: str, name: str):
  """The metric's module: ``read(run)`` plus its declared fields. Loaded
  by file path, so a metric is a file and never an import-table edit."""
  path = os.path.join(root, METRIC_DIRS[kind], name + ".py")
  if not os.path.isfile(path):
    raise SpecError(f"{kind} metric {name!r}: no file {path}")
  spec = importlib.util.spec_from_file_location(
      f"_bench_metric_{kind}_{name}", path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  missing = [f for f in METRIC_FIELDS[kind] + ("read",)
             if not hasattr(module, f)]
  if missing:
    raise SpecError(f"{path} does not define {missing}")
  return module


def load_peaks(root: str) -> Dict[str, Dict[str, Any]]:
  peaks = _load_json(os.path.join(root, "benchmarks", "peaks.json"))
  return {k: v for k, v in peaks.items() if not k.startswith("_")}
