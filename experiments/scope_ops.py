#!/usr/bin/env python3
"""One model scope of a traced run, by pass and operation: where a
per-layer metric's milliseconds are (PERF.md section 5's table of
``moe_route``, PR 33). Reads the profiler's file a ``--trace 1`` run of
the benchmark leaves under ``.bench_trace/<cell>/`` with the benchmark's
own window and leaf split (``benchmarks/lm_scopes.py``), so the sum is
the metric's reading; runs on the CPU, in seconds.

    python3 experiments/scope_ops.py <file.xplane.pb> [--scope moe_route]
        [--exclude moe_experts] [--top 40]

A leaf operation is filed by its ``op_name``: under
``rematted_computation`` it is the forward that ``nn.remat`` repeats,
elsewhere under ``transpose(jvp(forward))`` the backward, else the first
forward. One line an (operation, result type): ms a step and calls a
step in each pass.
"""

import argparse
import collections
import dataclasses
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PASSES = ("forward", "repeat", "backward")


def which_pass(op_name: str) -> str:
  if "rematted_computation" in op_name:
    return "repeat"
  return "backward" if "/transpose(jvp(forward))/" in op_name else "forward"


def main(argv=None) -> int:
  from benchmarks import lm_scopes, spans, xplane
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("path")
  parser.add_argument("--scope", default="moe_route")
  parser.add_argument("--exclude", action="append", default=None)
  parser.add_argument("--top", type=int, default=40)
  args = parser.parse_args(argv)
  exclude = ["moe_experts"] if args.exclude is None else args.exclude
  trace = spans.load(args.path)
  names = spans.op_names(args.path)
  for dev in trace.devices:
    window = spans._window(dev, xplane.SKIP_STEPS)
    if window is None:
      continue
    lo, hi, steps = window
    op_of = {}
    for plane, table in names.items():
      m = xplane.DEVICE_PLANE_RE.match(plane)
      if m and int(m.group(1)) == dev.device:
        op_of.update((xplane.parse_op(event)[0], op_name)
                     for event, op_name in table.items())
    inside = [dataclasses.replace(e, start=max(e.start, lo),
                                  end=min(e.end, hi))
              for e in dev.ops if min(e.end, hi) > max(e.start, lo)]
    leaves, _ = xplane.split_leaves(inside)
    seconds = collections.defaultdict(float)
    calls = collections.defaultdict(int)
    for e in leaves:
      op_name = op_of.get(e.name, "")
      scopes = lm_scopes.components(op_name)
      if args.scope not in scopes or any(x in scopes for x in exclude):
        continue
      tail = op_name.split(args.scope)[-1].lstrip(")/").rstrip(":")
      key = (re.sub(r"\.\d+", "", e.name), tail[-64:], which_pass(op_name))
      seconds[key] += e.end - e.start
      calls[key] += 1
    ms = lambda s: 1e3 * s / steps
    by_pass = {p: sum(s for k, s in seconds.items() if k[2] == p)
               for p in PASSES}
    print(f"device {dev.device}, {steps} steps; {args.scope} less "
          f"{exclude}: " + ", ".join(
              f"{p} {ms(s):.2f}" for p, s in by_pass.items())
          + f"; sum {ms(sum(by_pass.values())):.2f} ms a step")
    rows = sorted({k[:2] for k in seconds}, key=lambda k: -sum(
        seconds[k + (p,)] for p in PASSES))
    print("  " + "  ".join(f"{p:>15s}" for p in PASSES)
          + "   (ms a step x calls a step)")
    for row in rows[:args.top]:
      cells = [f"{ms(seconds[row + (p,)]):8.3f} x{calls[row + (p,)] / steps:5.1f}"
               if calls[row + (p,)] else " " * 15 for p in PASSES]
      print("  " + "  ".join(cells) + f"   {row[0]:40s} {row[1]}")
  return 0


if __name__ == "__main__":
  sys.exit(main())
