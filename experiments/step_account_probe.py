#!/usr/bin/env python3
"""The step account on the chip: what it costs, whether it agrees with
the trace, whether it names a planted stall, and the hunt for the real
one (PR 37; PERF.md section 6 holds what this printed).

No reference-file counterpart: the reference has no host-side account of
its loop (ref: benchmark_cnn.py:786-884 times a step as a whole).

Three commands, all through ``chiprun`` from the root of a checkout:

  runs   one benchmark run per (root, seed), each in a process of its own
         (a chip belongs to the first process that touches JAX; this
         parent stays off it). ``--roots . _chip/parent`` alternates the
         sides seed by seed (change, parent / parent, change / ...), so
         a drift of the machine falls on both. Each child is
         ``benchmarks/run.py``'s own path (``harness.run_cell``) in the
         root it is given, plus what the result line leaves out: the
         program's ``stats["step_account"]`` without its rows, every
         ``host stall:`` line, the garbage collector's passes by phase
         and, in a traced run, the account's own ``host_busy_ms`` over
         the traced stretch beside the one read from the trace's host
         plane. ``--skip-named-checks`` leaves out the checks the cell
         names (the language-model cells' reference check runs ~2 min
         AFTER the window and moves nothing inside it); ``--sleep-at N
         --sleep-s S`` wraps the harness's tee on the step line so that
         it sleeps at timed line N: the planted stall. Nothing in the
         program is switched: the wrapper is this script's.
  spans  nanoseconds per live span with no profiler open: 10**6 spans in
         made-up iterations of four, under the profiler's real
         annotation factories, for each root.
  child  one run, as ``runs`` starts it.

Every line of output is JSON; ``--out`` appends the same lines to a file
under ``chiprun_out/``.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.abspath(__file__)
STALL_PREFIX = "host stall: "
HOST_WAITS = ("fetch/metrics", "feed/wait")


def _say(obj, out=None):
  line = json.dumps(obj)
  print(line, flush=True)
  if out:
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "a", encoding="utf-8") as f:
      f.write(line + "\n")


# -- one run, in the root it is given -----------------------------------------

def _mean(values):
  return sum(values) / len(values) if values else None


def _account_summary(run, metric_file):
  """What the result line leaves out of ``stats["step_account"]``; an
  empty dict from a program that has none (the parent)."""
  from benchmarks import harness
  account = (run.stats or {}).get("step_account")
  if not account:
    return {}
  rows = account["rows"]
  out = {
      "iterations": account["iterations"],
      "median_ms": (None if account["median_s"] is None
                    else 1e3 * account["median_s"]),
      "stalls": [{k: v for k, v in s.items() if k != "t0"}
                 for s in account["stalls"]],
      "adds_up_to_us": 1e6 * max(
          (abs(sum(r["by_span"].values()) - r["dur_s"]) for r in rows),
          default=0.0),
      "dur_ms": [round(1e3 * r["dur_s"], 2) for r in rows],
      # Late, and under the program's own threshold: no line names these.
      "late": [{"iteration": i + 1, "by_span_ms": {
          k: round(1e3 * v, 2) for k, v in r["by_span"].items()}}
               for i, r in enumerate(rows)
               if account["median_s"] and
               1.1 * account["median_s"] < r["dur_s"]],
      "mean_ms_by_span": {
          name: 1e3 * sum(r["by_span"].get(name, 0.0) for r in rows) /
          len(rows)
          for name in sorted({k for r in rows for k in r["by_span"]})},
  }
  window = run.window
  if window is not None and window.t_start and window.t_stop:
    # The traced stretch as the trace's own reader cuts it, near enough:
    # after the iterations the profiler's start distorted, before the one
    # that stopped it.
    inside = [r for r in rows if r["t0"] > window.t_start and
              r["t0"] + r["dur_s"] < window.t_stop][harness.STALL_STEPS:]
    busy = [r["dur_s"] - sum(r["by_span"].get(k, 0.0) for k in HOST_WAITS)
            for r in inside]
    from benchmarks import spans
    out["traced_stretch"] = {
        "iterations": len(inside),
        "account_host_busy_ms": (None if not busy else 1e3 * _mean(busy)),
        "trace_host_busy_ms": spans.from_trace(run, metric_file,
                                               "host_busy_ms"),
        "trace_train_steps": spans.from_trace(run, metric_file,
                                              "train_steps"),
        "trace_host_spans_ms": spans.from_trace(run, metric_file,
                                                "host_spans_ms"),
    }
  return out


def child(args) -> int:
  root = os.path.abspath(args.root)
  os.chdir(root)
  sys.path.insert(0, root)
  from benchmarks import harness

  stall_lines = []
  seen = {}
  real_call = harness.StepLog.__call__

  def tee(self, msg):
    line = str(msg)
    if line.startswith(STALL_PREFIX):
      stall_lines.append(line)
    m = harness.STEP_RE.match(line)
    if m and args.sleep_s and int(m.group(1)) == args.sleep_at:
      time.sleep(args.sleep_s)      # the planted stall: a slow listener
    real_call(self, msg)

  harness.StepLog.__call__ = tee
  real_judge = harness._judge

  def judge(run, used, judges):
    seen["run"] = run
    return real_judge(run, used, judges)

  harness._judge = judge
  if args.skip_named_checks:
    real_checks = harness.load_checks
    harness.load_checks = lambda r, cell: real_checks(
        r, dict(cell, config_data=dict(cell["config_data"], checks=[]),
                traffic_data=dict(cell["traffic_data"], checks=[])))

  said = []
  try:
    result = harness.run_cell(root, args.workload, args.seed, args.seconds,
                              bool(args.trace), T0, said.append)
  except harness.Refused as e:
    print(f"step_account_probe: REFUSED {e}", file=sys.stderr, flush=True)
    return 1
  run = seen["run"]
  info = {s["info"]: s for s in said if "info" in s}
  totals = (run.stats or {}).get("span_totals") or {}
  # (spans.from_trace finds the trace from a metric file's place)
  metric_file = os.path.join(root, "benchmarks", "layer_metrics", "x.py")
  _say({
      "root": args.root, "workload": args.workload, "seed": args.seed,
      "trace": args.trace, "correct": result["correct"],
      "named_checks": not args.skip_named_checks,
      "planted": ({"line": args.sleep_at, "sleep_s": args.sleep_s}
                  if args.sleep_s else None),
      "metrics": {k: v["value"] for k, v in result["metrics"].items()},
      "samples_per_sec": info["window"]["samples_per_sec"],
      "step_ms_median": 1e3 * (info["window"]["step_s_median"] or 0.0),
      "setup_s": info["setup"]["setup_s"],
      "memory_peak_bytes": result["device"]["memory_peak_bytes"],
      "busy_s": result["device"].get("busy_s"),
      "window_s": result["device"].get("window_s"),
      "host_stall_lines": stall_lines,
      "gc_collections": {phase: block["counters"].get("gc_collections")
                         for phase, block in totals.items()},
      "host_gc": {phase: block["spans"].get("host/gc")
                  for phase, block in totals.items()},
      "timed_loop_spans": (totals.get("timed_loop") or {}).get("spans"),
      "account": _account_summary(run, metric_file),
  }, args.out)
  return 0 if result["correct"] else 3


# -- many runs, sides alternating ---------------------------------------------

def runs(args) -> int:
  worst = 0
  if args.out:
    args.out = os.path.abspath(args.out)   # a child works in its own root
  for i, seed in enumerate(args.seeds):
    roots = list(args.roots)
    if i % 2:
      roots.reverse()
    for root in roots:
      cmd = [sys.executable, HERE, "child", "--root", root,
             "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", str(args.trace),
             "--sleep-at", str(args.sleep_at), "--sleep-s",
             str(args.sleep_s)]
      if args.skip_named_checks:
        cmd.append("--skip-named-checks")
      if args.out:
        cmd += ["--out", args.out]
      t = time.monotonic()
      done = subprocess.run(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
      lines = done.stdout.strip().splitlines()
      print(lines[-1] if lines else json.dumps(
          {"root": root, "seed": seed, "no_result": done.returncode}),
            flush=True)
      if args.out:
        tag = "%s_%s_%d_t%d" % (os.path.basename(os.path.abspath(root)),
                                args.workload.split("-")[0], seed,
                                args.trace)
        with open(os.path.join(os.path.dirname(args.out), tag + ".err"),
                  "w", encoding="utf-8") as f:
          f.write(done.stderr)
      if done.returncode:
        worst = max(worst, done.returncode)
        print(done.stderr[-3000:], file=sys.stderr, flush=True)
      print(json.dumps({"took_s": round(time.monotonic() - t, 1),
                        "rc": done.returncode}), flush=True)
  return worst


# -- the cost of a span -------------------------------------------------------

SPAN_LOOP = """
import sys, time
sys.path.insert(0, %(root)r)
import jax
from kf_benchmarks_tpu import tracing
tr = tracing.RunTrace(annotation=jax.profiler.TraceAnnotation,
                      step_annotation=jax.profiler.StepTraceAnnotation)
tracing.activate(tr)
tr.begin_phase(tracing.PHASE_TIMED)
n = %(n)d
t = time.perf_counter()
for i in range(n // 4):
  with tr.step("train", i):
    with tr.span("dispatch", "train_step", step=i, first_call=False):
      pass
    with tr.span("fetch", "metrics", step=i):
      pass
    with tr.span("handle", "step", step=i):
      pass
dt = time.perf_counter() - t
tracing.deactivate()
print(1e9 * dt / n)
"""


def spans(args) -> int:
  for root in args.roots:
    readings = []
    for _ in range(args.repeats):
      done = subprocess.run(
          [sys.executable, "-c",
           SPAN_LOOP % {"root": os.path.abspath(root), "n": args.n}],
          stdout=subprocess.PIPE, text=True, check=True,
          env=dict(os.environ, JAX_PLATFORMS="cpu"))
      readings.append(float(done.stdout.strip().splitlines()[-1]))
    _say({"root": root, "spans": args.n, "ns_per_live_span": readings},
         args.out)
  return 0


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  sub = parser.add_subparsers(dest="command", required=True)
  for name in ("runs", "child"):
    p = sub.add_parser(name)
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--skip-named-checks", action="store_true")
    p.add_argument("--sleep-at", type=int, default=0)
    p.add_argument("--sleep-s", type=float, default=0.0)
    p.add_argument("--out")
    if name == "runs":
      p.add_argument("--roots", nargs="+", default=["."])
      p.add_argument("--seeds", type=int, nargs="+", required=True)
    else:
      p.add_argument("--root", default=".")
      p.add_argument("--seed", type=int, required=True)
  p = sub.add_parser("spans")
  p.add_argument("--roots", nargs="+", default=["."])
  p.add_argument("--n", type=int, default=10 ** 6)
  p.add_argument("--repeats", type=int, default=3)
  p.add_argument("--out")
  args = parser.parse_args(argv)
  return {"runs": runs, "child": child, "spans": spans}[args.command](args)


if __name__ == "__main__":
  sys.exit(main())
