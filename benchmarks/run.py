#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, on the machine it is started
on, in this one process (a chip belongs to the first process that
touches JAX). Program logs go to stderr; stdout carries a few JSON info
lines and, last, the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Exits nonzero and prints no result line when JAX finds no
TPU, a TPU that ``benchmarks/peaks.json`` does not know, or fewer chips
than the cell asks for. See ``benchmarks/README.md``.
"""

import time

T0 = time.monotonic()  # process start, as near as Python lets us stamp it

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root: str = ROOT, t0: float = T0) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seed", type=int, required=True)
  parser.add_argument("--seconds", type=float, required=True)
  parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
  args = parser.parse_args(argv)

  if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
  from benchmarks import harness

  def say(obj) -> None:
    print(harness.dumps(obj), flush=True)

  try:
    result = harness.run_cell(root, args.workload, args.seed, args.seconds,
                              bool(args.trace), t0, say)
  except harness.Refused as e:
    print(f"benchmarks/run.py: REFUSED {e}", file=sys.stderr, flush=True)
    return 1
  say(result)
  return 0


if __name__ == "__main__":
  sys.exit(main())
