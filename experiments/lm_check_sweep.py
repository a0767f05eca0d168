#!/usr/bin/env python3
"""A language-model cell's reference check over many seeds and planted
faults in ONE process: what sets the check's limits (PERF.md section 6)
at a fraction of the chip time a process a run would take (each process
spends ~15 s reaching the chip and traces the whole step again; here the
persistent compile cache serves every build after the first).

    python3 experiments/lm_check_sweep.py --workload <cell> \
        --seeds 11 12 13 --faults mhc_bf16 sinkhorn_1 [--seconds 2] \
        [--out chiprun_out/sweep.jsonl]

Each run is ``benchmarks/run.py``'s (``harness.run_cell``, untraced) with
a short window; a fault is planted with
``experiments/lm_precision_control.plant`` and taken out again after its
run. One JSON line a run: the seed, the fault, ``correct``, the rate and
every compared number beside its limit. Faults run on the first seed.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "experiments"))


def main():
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seeds", type=int, nargs="+", required=True)
  parser.add_argument("--faults", nargs="*", default=[])
  parser.add_argument("--seconds", type=float, default=2.0)
  parser.add_argument("--out", default="chiprun_out/lm_check_sweep.jsonl")
  parser.add_argument("--root", default=ROOT,
                      help="the benchmark tree's root (a test's tiny tree)")
  args = parser.parse_args()
  import jax
  import lm_precision_control
  from benchmarks import harness
  os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
  runs = [(seed, None) for seed in args.seeds] + [
      (args.seeds[0], fault) for fault in args.faults]
  for seed, fault in runs:
    planted = []

    def setattr_(obj, name, value, raising=False):
      del raising
      planted.append((obj, name, getattr(obj, name, None)))
      setattr(obj, name, value)
    if fault:
      lm_precision_control.plant(fault, setattr_)
    t = time.monotonic()
    try:
      result = harness.run_cell(args.root, args.workload, seed, args.seconds,
                                False, t, lambda obj: None)
      line = {"seed": seed, "fault": fault, "correct": result["correct"],
              "samples_per_sec": result["metrics"].get(
                  "samples_per_sec", {}).get("value"),
              "compared": {k: v["value"] for k, v in
                           result["compared"].items()},
              "wall_s": time.monotonic() - t}
    except Exception as e:  # one run's failure is that run's line
      line = {"seed": seed, "fault": fault, "error": repr(e)[:2000],
              "wall_s": time.monotonic() - t}
    finally:
      for obj, name, value in reversed(planted):
        setattr(obj, name, value)
    with open(args.out, "a", encoding="utf-8") as f:
      f.write(harness.dumps(line) + "\n")
    print(harness.dumps(line), flush=True)
    result = None
    gc.collect()
    jax.clear_caches()
  return 0


if __name__ == "__main__":
  sys.exit(main())
