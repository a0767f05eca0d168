"""Tracing, profiling, program dumps, summaries, and the benchmark logger.

TPU-native re-design of the reference's observability stack (SURVEY 5.1,
5.5):

  --trace_file    Chrome-trace of one step (ref: benchmark_cnn.py:270-275,
                  :806-817 RunMetadata/timeline) -> jax.profiler trace of
                  one designated step; output readable by Perfetto /
                  TensorBoard.
  --trace_events_file  whole-run HOST-side span timeline (tracing.py;
                  feed/dispatch/compile/checkpoint/elastic spans,
                  Chrome trace-event export, compile ledger, latency
                  percentiles). Its live spans also enter whatever
                  profiler capture is open as kf/<lane>/<name>
                  annotations, so maybe_trace_step's capture below
                  holds the host's spans beside the device planes.
  --tfprof_file   tfprof top-op profile (ref :276-289, :1208-1228) ->
                  compiled-HLO cost analysis (flops / bytes accessed /
                  estimated seconds) plus memory analysis of the jitted
                  step.
  --graph_file    GraphDef text dump (ref :2142-2148) -> StableHLO text of
                  the lowered step program; the partitioned-graph analog
                  (ref :293-296) is covered because the SPMD partitioner
                  output is part of the compiled HLO.
  --benchmark_log_dir  model-garden BenchmarkFileLogger JSON emission
                  (ref :1594-1608, :847-854, :1694-1724): benchmark_run.log
                  with run metadata + metric.log with one JSON line per
                  metric.
  --summary_verbosity / --save_summaries_steps  TF-summary tiers 0-3
                  (ref :586-593, :2811-2846) -> JSONL scalar/histogram
                  event stream under train_dir (no TensorBoard dependency;
                  the format is trivially convertible).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import time
from typing import Any, Dict, NamedTuple, Optional

import jax
import numpy as np

from kf_benchmarks_tpu import metrics as metrics_lib


# -- one-step trace (ref: benchmark_cnn.py:270-275) -------------------------

def trace_dir_of(trace_file: Optional[str]) -> str:
  """The profiler output directory for a --trace_file value. The ONE
  derivation shared by the capture side (maybe_trace_step) and the
  readback side (measured per-op table): if they ever diverged, the
  run-pinning exclude snapshot would silently read the wrong directory."""
  return os.path.dirname(trace_file or "") or "."


@contextlib.contextmanager
def maybe_trace_step(trace_file: Optional[str], step: int,
                     trace_at_step: int = 0):
  """Trace exactly one designated step into the trace dir.

  The reference captures a FULL_TRACE of a single step (step -2 there);
  we trace the first timed step by default. jax.profiler writes a
  directory; ``trace_file``'s directory component is used, mirroring the
  reference's file-path flag shape.
  """
  if trace_file and step == trace_at_step:
    trace_dir = trace_dir_of(trace_file)
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
      yield True
    return
  yield False


# -- compiled-program dumps (ref: tfprof + graph_file) ----------------------

def dump_program_text(lowered, path: str) -> None:
  """StableHLO text of a lowered program (the GraphDef-dump analog,
  ref: benchmark_cnn.py:2142-2148). Takes the result of ``jit.lower(...)``
  so one lowering can feed multiple dumps."""
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  with open(path, "w") as f:
    f.write(lowered.as_text())


def dump_partitioned_text(compiled, path: str) -> None:
  """Post-SPMD-partitioning program text of a compiled step (the
  per-device partitioned GraphDef analog, ref: benchmark_cnn.py:293-296,
  :869-883). Takes an already-compiled object so callers compile once."""
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  with open(path, "w") as f:
    f.write(compiled.as_text())


def dump_cost_analysis(lowered, path: str, compiled=None,
                       device_kind: Optional[str] = None
                       ) -> Dict[str, Any]:
  """Compiled-HLO cost + memory analysis (the tfprof analog,
  ref: benchmark_cnn.py:276-289, :1208-1228 top-20 by accelerator time).

  Takes the result of ``jit.lower(...)`` (and optionally its
  already-compiled object, so callers needing several compiled dumps pay
  one compilation); writes a JSON report and returns it. Keys depend on
  the backend; flops and bytes-accessed are present on CPU and TPU. A
  surface the backend does not offer lands as a ``*_error`` entry
  naming ``device_kind`` -- never as a silently absent field (the
  caller logs it).
  """
  compiled = compiled if compiled is not None else lowered.compile()
  report: Dict[str, Any] = {"device_kind": device_kind}
  try:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
      cost = cost[0] if cost else {}
    report["cost_analysis"] = {
        k: float(v) for k, v in dict(cost or {}).items()
        if np.isscalar(v) and np.isfinite(float(v))}
  except Exception as e:  # backend-dependent surface
    report["cost_analysis_error"] = f"{device_kind}: {e!r}"
  try:
    mem = compiled.memory_analysis()
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
      if hasattr(mem, attr):
        report.setdefault("memory_analysis", {})[attr] = int(
            getattr(mem, attr))
  except Exception as e:  # backend-dependent surface
    report["memory_analysis_error"] = f"{device_kind}: {e!r}"
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  with open(path, "w") as f:
    json.dump(report, f, indent=2, sort_keys=True)
  return report


# -- per-op profile table (ref: benchmark_cnn.py:1208-1228 tfprof) ----------

class DevicePeaks(NamedTuple):
  """Published peaks of one chip: bf16 FLOP/s and HBM bytes/s."""
  flops: float
  bytes_per_s: float
  source: str


# The ONE peaks table, keyed by the device_kind jax reports. A device
# that is not here gets no roofline or MFU line (no_peaks_line): a
# default would rate it against another chip's roofline.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}


def no_peaks_line(device_kind: str) -> str:
  return ("roofline table / MFU line: not printed -- device_kind "
          f"{device_kind!r} has no entry in observability.DEVICE_PEAKS "
          "(peaks are per chip; a default would rate this device "
          "against another chip's roofline)")


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shapes_bytes(text: str) -> int:
  total = 0
  for dtype, dims in _SHAPE_RE.findall(text):
    if dtype not in _DTYPE_BYTES:
      continue
    elems = 1
    for d in dims.split(","):
      if d:
        elems *= int(d)
    total += elems * _DTYPE_BYTES[dtype]
  return total


def _shape_dims(text: str):
  m = _SHAPE_RE.search(text)
  if not m:
    return []
  return [int(d) for d in m.group(2).split(",") if d]


def _split_operands(operand_text: str):
  """Split a top-level-comma operand list (shapes contain commas too)."""
  parts, depth, cur = [], 0, []
  for ch in operand_text:
    if ch in "([{":
      depth += 1
    elif ch in ")]}":
      depth -= 1
    if ch == "," and depth == 0:
      parts.append("".join(cur))
      cur = []
    else:
      cur.append(ch)
  if cur:
    parts.append("".join(cur))
  return parts


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*?)\s([a-z][a-z0-9\-]*)\(")


def _instr_flops(opcode: str, result_type: str, operands, attrs: str) -> float:
  """MXU-op flop estimate from shapes (convolution / dot); everything
  else is treated as bandwidth-bound (0 flops)."""
  out_elems = 1
  for d in _shape_dims(result_type):
    out_elems *= d
  if opcode == "convolution" and len(operands) >= 2:
    # flops = 2 * out_elems * prod(kernel_spatial) * Cin_per_group, with
    # the kernel's spatial and input-feature dims located via dim_labels
    # (rhs labels: digits = spatial, 'i' = input features). HLO kernel
    # shapes already carry Cin/feature_group_count on the 'i' dim, so no
    # further group division (a depthwise conv's 'i' dim is 1).
    rhs_dims = _shape_dims(operands[1])
    m = re.search(r"dim_labels=[^_]+_([\w]+)->", attrs)
    if not m or not rhs_dims:
      return 0.0
    rhs_labels = m.group(1)
    if len(rhs_labels) != len(rhs_dims):
      return 0.0
    kernel_elems_per_out = 1
    for label, dim in zip(rhs_labels, rhs_dims):
      if label.isdigit() or label == "i":
        kernel_elems_per_out *= dim
    return 2.0 * out_elems * kernel_elems_per_out
  if opcode == "dot" and operands:
    lhs_dims = _shape_dims(operands[0])
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
    if not m or not lhs_dims:
      return 0.0
    contracted = 1
    for idx in m.group(1).split(","):
      if idx and int(idx) < len(lhs_dims):
        contracted *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contracted
  return 0.0


def per_op_costs(hlo_text: str, peaks: DevicePeaks):
  """Per-instruction cost rows from an optimized-HLO text dump, timed
  against ``peaks``' roofline.

  Walks every computation EXCEPT fusion bodies (a fusion instruction
  already accounts for its body's memory traffic; convs/dots stay
  top-level on TPU), estimating flops for MXU ops and bytes for all, and
  a roofline time estimate. Occurrence counts are static (a while-loop
  body is counted once, not trip-count-weighted)."""
  # Pass 1: name -> result type. Optimized HLO prints operands as bare
  # %names (no inline types), so operand shapes resolve through this
  # symbol table.
  types = {}
  for line in hlo_text.splitlines():
    m = _INSTR_RE.match(line)
    if m:
      types[m.group(1)] = m.group(2)

  def _resolve(operand: str) -> str:
    if _SHAPE_RE.search(operand):  # unoptimized dumps inline the type
      return operand
    nm = re.search(r"%[\w.\-]+", operand)
    return types.get(nm.group(0), "") if nm else ""

  rows = []
  in_fusion_body = False
  for line in hlo_text.splitlines():
    stripped = line.strip()
    if stripped.endswith("{") and stripped.startswith("%fused_"):
      in_fusion_body = True
      continue
    if stripped == "}" or stripped.startswith("} "):
      in_fusion_body = False
      continue
    if in_fusion_body:
      continue
    m = _INSTR_RE.match(line)
    if not m:
      continue
    name, result_type, opcode = m.groups()
    if opcode in ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast", "after-all"):
      continue
    # Balanced-paren scan for the operand list (attrs may contain parens).
    start = m.end()
    depth, i = 1, start
    while i < len(line) and depth:
      if line[i] == "(":
        depth += 1
      elif line[i] == ")":
        depth -= 1
      i += 1
    operand_text, attrs = line[start:i - 1], line[i:]
    operands = [_resolve(op) for op in _split_operands(operand_text)]
    flops = _instr_flops(opcode, result_type, operands, attrs)
    nbytes = _shapes_bytes(result_type) + sum(
        _shapes_bytes(op) for op in operands)
    est_s = max(flops / peaks.flops, nbytes / peaks.bytes_per_s)
    rows.append({"name": name, "opcode": opcode, "flops": flops,
                 "bytes": nbytes, "est_time_s": est_s})
  return rows


# Collective opcodes (the communication side of the comm/compute
# overlap accounting; -start/-done async forms match by prefix).
_COLLECTIVE_OPCODES = ("all-reduce", "reduce-scatter", "all-gather",
                       "collective-permute", "all-to-all")


def collective_overlap_stats(hlo_text: str, peaks: DevicePeaks):
  """Static comm/compute overlap accounting from an optimized-HLO dump.

  A collective that lives INSIDE a loop body (a computation referenced
  by a while instruction's ``body=``) was issued in the loop -- e.g.
  FSDP's per-scanned-block gathers under --shard_params -- and the
  scheduler can interleave it with the remaining loop iterations'
  compute; a top-level collective serializes after the compute feeding
  it. Returns {num_collectives, comm_s, comm_in_loop_s,
  overlap_fraction} with times from the same bandwidth roofline as the
  per-op table (the RANKING convention; absolute seconds are
  chip-relative).
  """
  body_names = set(re.findall(r"body=%?([\w\.\-]+)", hlo_text))
  comp = None
  num = 0
  comm_s = 0.0
  in_loop_s = 0.0
  for line in hlo_text.splitlines():
    s = line.strip()
    if s.endswith("{") and "(" in s:
      toks = s.split()
      if toks:
        name = toks[1] if toks[0] == "ENTRY" and len(toks) > 1 else toks[0]
        comp = name.lstrip("%")
      continue
    m = _INSTR_RE.match(line)
    if not m:
      continue
    opcode = m.group(3)
    base = opcode[:-6] if opcode.endswith("-start") else opcode
    if base not in _COLLECTIVE_OPCODES:
      continue
    num += 1
    est = _shapes_bytes(m.group(2)) / peaks.bytes_per_s
    comm_s += est
    if comp in body_names:
      in_loop_s += est
  return {
      "num_collectives": num,
      "comm_s": comm_s,
      "comm_in_loop_s": in_loop_s,
      "overlap_fraction": in_loop_s / comm_s if comm_s else 0.0,
  }


def overlap_fraction_line(hlo_text: str, peaks: DevicePeaks) -> str:
  """One roofline-table line for the comm/compute overlap axis: how
  much of the program's collective time is issued inside loop bodies
  (schedulable against remaining compute -- where --shard_params'
  per-block gathers sit) vs trailing the compute."""
  stats = collective_overlap_stats(hlo_text, peaks)
  if not stats["num_collectives"]:
    return ("comm/compute overlap: no collectives in program "
            "(single replica or unreduced mode)")
  return (f"comm/compute overlap: {stats['num_collectives']} "
          f"collectives, ~{stats['comm_s'] * 1e6:.1f} us est comm; "
          f"{100.0 * stats['overlap_fraction']:.1f}% issued inside "
          "loop bodies (in-backward, overlappable with compute), "
          f"{(stats['comm_s'] - stats['comm_in_loop_s']) * 1e6:.1f} us "
          "serialized after it")


PER_OP_TABLE_HEADER = ("rank  est_time_us  %total        flops"
                       "        bytes  op")

def dispatch_overhead_line(dispatch_overhead_s: float,
                           dispatch_wall_s: float,
                           steps_per_dispatch: int = 1) -> str:
  """One line for the HOST axis from the run's OWN measurements: the
  mean host time per timed dispatch call (stats["dispatch_overhead_s"])
  against the mean wall of one dispatch -- the share
  --steps_per_dispatch amortizes K-fold."""
  k = max(1, int(steps_per_dispatch))
  frac = dispatch_overhead_s / max(dispatch_wall_s, 1e-12)
  return (f"dispatch overhead: {dispatch_overhead_s * 1e3:.3f} ms host "
          f"time/dispatch measured over {k} step(s)/dispatch "
          f"({dispatch_wall_s * 1e3:.3f} ms wall/dispatch) -> "
          f"{100.0 * frac:.1f}% of dispatch wall")


def mfu_line(total_flops: float, step_time_s: float, peaks: DevicePeaks,
             source: str = "roofline-estimated") -> str:
  """Model-FLOP-utilization line: achieved FLOP/s over the chip's bf16
  peak (``peaks``, from DEVICE_PEAKS). With the static roofline
  estimate as the denominator this is the utilization CEILING the
  program shape admits; with a measured step time it is the achieved
  MFU."""
  if step_time_s <= 0:
    return "MFU: n/a (no step time)"
  achieved = total_flops / step_time_s
  return (f"MFU: {100.0 * achieved / peaks.flops:.1f}% "
          f"({achieved / 1e12:.2f} TFLOP/s {source} over "
          f"{peaks.flops / 1e12:.0f} TFLOP/s bf16 peak; "
          f"{total_flops:.3e} flops/step)")


def hbm_breakdown_line(mem) -> str:
  """One peak-HBM line from a compiled program's memory_analysis():
  the operator-facing footprint summary the chunked-head/remat/grad-
  accum levers move (argument = live state + staged inputs, temp =
  activations/residuals/collective buffers -- the part those levers
  shrink)."""
  mib = 1024.0 * 1024.0
  args = getattr(mem, "argument_size_in_bytes", 0)
  out = getattr(mem, "output_size_in_bytes", 0)
  temp = getattr(mem, "temp_size_in_bytes", 0)
  return (f"peak HBM (compiled): {(args + temp) / mib:.1f} MiB "
          f"(arguments {args / mib:.1f} + temps {temp / mib:.1f}; "
          f"outputs {out / mib:.1f} aliased over arguments where "
          "donated)")


def per_op_table(hlo_text: str, peaks: DevicePeaks,
                 top_n: int = 20) -> str:
  """The tfprof top-op table analog (ref: benchmark_cnn.py:1208-1228
  prints the top-20 ops by accelerator time): top-``top_n`` HLO
  instructions by device time estimated against ``peaks``' roofline,
  closed by the roofline MFU line (the utilization ceiling this program
  shape admits) and the comm/compute overlap line."""
  rows = per_op_costs(hlo_text, peaks)
  rows.sort(key=lambda r: r["est_time_s"], reverse=True)
  total = sum(r["est_time_s"] for r in rows) or 1.0
  total_flops = sum(r["flops"] for r in rows)
  lines = [f"Top {top_n} ops by estimated accelerator time "
           "(static roofline on the compiled HLO)",
           PER_OP_TABLE_HEADER]
  for rank, r in enumerate(rows[:top_n], 1):
    lines.append(
        f"{rank:4d}  {r['est_time_s'] * 1e6:11.1f}  "
        f"{100.0 * r['est_time_s'] / total:5.1f}%  {r['flops']:11.3e}  "
        f"{r['bytes']:11.3e}  {r['name']} {r['opcode']}")
  lines.append(mfu_line(total_flops, total, peaks))
  lines.append(overlap_fraction_line(hlo_text, peaks))
  return "\n".join(lines)


def dump_per_op_profile(compiled, path: str, peaks: DevicePeaks,
                        top_n: int = 20) -> str:
  """Write the per-op table next to the tfprof cost JSON and return it."""
  table = per_op_table(compiled.as_text(), peaks, top_n=top_n)
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  with open(path, "w") as f:
    f.write(table + "\n")
  return table


def packing_feed_line(feed_stats: Dict[str, Any],
                      packing_stats: Optional[Dict[str, Any]] = None
                      ) -> str:
  """One operator-facing input-pipeline line (printed next to the
  timing rows; the device-side roofline table has no host-edge row):
  the DeviceFeeder's measured feed-stall fraction -- the share of the
  consume window the step loop spent BLOCKED on the feed, ~0 when the
  prefetch overlaps host work with device compute -- plus, for
  --packed_sequences runs, the packer's measured efficiency (real
  tokens / slots, the useful-tokens/s multiplier packing buys over the
  one-document-per-row padded baseline)."""
  parts = []
  if packing_stats and packing_stats.get("packing_efficiency") is not None:
    parts.append(
        "packing efficiency %.1f%% (%d real tokens / %d slots, %d docs)"
        % (100.0 * packing_stats["packing_efficiency"],
           packing_stats["real_tokens"], packing_stats["token_slots"],
           packing_stats["documents"]))
  stall = feed_stats.get("feed_stall_fraction")
  depth_mean = feed_stats.get("queue_depth_mean")
  parts.append(
      "feed stall %s of wall (%.1f ms wait / %d fetches, queue depth "
      "%.1f mean / %d max, prefetch %d)"
      % ("%.1f%%" % (100.0 * stall) if stall is not None else "n/a",
         1e3 * feed_stats.get("consumer_wait_s", 0.0),
         feed_stats.get("fetches", 0),
         depth_mean if depth_mean is not None else 0.0,
         feed_stats.get("queue_depth_max", 0),
         feed_stats.get("prefetch_batches", 0)))
  return "input pipeline: " + "; ".join(parts)


def chunk_timing_rows(steps_per_dispatch: int, chunk_intervals,
                      global_batch: int, max_rows: int = 8):
  """Per-chunk timing rows for the chunked dispatch mode
  (--steps_per_dispatch): the dispatch-granularity wall intervals the
  amortized per-step stats derive from, printed so an operator can see
  chunk-to-chunk variation directly. Shows the last ``max_rows`` chunks
  plus a summary line over all of them."""
  k = max(1, int(steps_per_dispatch))
  times = list(chunk_intervals)
  if not times:
    return []
  mean = sum(times) / len(times)
  lines = [
      "dispatch chunks (K=%d): %d dispatches, mean %.1f ms/chunk "
      "(%.2f ms/step, %.1f img/s), min %.1f ms, max %.1f ms" % (
          k, len(times), mean * 1e3, mean / k * 1e3,
          k * global_batch / max(mean, 1e-9),
          min(times) * 1e3, max(times) * 1e3),
      "chunk  wall_ms  img/s",
  ]
  first = max(0, len(times) - max_rows)
  if first:
    lines.append(f"  ... ({first} earlier chunks elided)")
  for idx in range(first, len(times)):
    t = times[idx]
    lines.append("%5d  %7.1f  %.1f" % (
        idx + 1, t * 1e3, k * global_batch / max(t, 1e-9)))
  return lines


# -- MEASURED per-op profile from the captured trace ------------------------
# The reference's tfprof read MEASURED accelerator time out of RunMetadata
# (ref: benchmark_cnn.py:1208-1228); the static roofline table above ranks by
# estimate only. Here the jax.profiler trace captured under --trace_file is
# parsed back into measured per-op device time: every complete ("X") trace
# event whose args carry an ``hlo_op`` key is an XLA op execution on the
# backend (CPU thunks and TPU device ops both emit them), so durations sum
# to real measured time -- trip-count-weighted through loops, unlike the
# static table's counted-once while bodies.

def list_profile_runs(trace_dir: str):
  """Timestamped profiler run dirs under trace_dir, oldest first.
  Callers snapshot this BEFORE capturing a trace so the measured table
  can be pinned to the run this invocation actually wrote (a stale dump
  from an earlier run at the same path must never masquerade as this
  run's profile)."""
  import glob
  return sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*")))


def load_trace_op_events(trace_dir: str, exclude=()):
  """Op-execution events from the newest profiler dump under trace_dir,
  skipping any run dir listed in ``exclude`` (pre-existing runs).

  jax.profiler.trace writes plugins/profile/<ts>/<host>.trace.json.gz in
  Chrome trace-event format. Returns the raw event dicts (ph == "X" with
  args.hlo_op), or [] when no (new) dump or no op events exist.
  """
  import glob
  import gzip
  stale = set(exclude)
  runs = [r for r in list_profile_runs(trace_dir) if r not in stale]
  if not runs:
    return []
  events = []
  for path in glob.glob(os.path.join(runs[-1], "*.trace.json.gz")):
    try:
      with gzip.open(path, "rt") as f:
        data = json.load(f)
    except (OSError, ValueError):
      continue
    for e in data.get("traceEvents", []):
      if (e.get("ph") == "X" and
          isinstance(e.get("args"), dict) and "hlo_op" in e["args"]):
        events.append(e)
  return events


def measured_op_costs(events):
  """Aggregate op events -> per-op rows with measured device time.

  Keyed by (hlo_module, hlo_op): two modules in one traced span (e.g. a
  train step plus a metrics program) can both own a "fusion.1", and
  merging those would corrupt both rows. Rows carry total microseconds
  across the whole trace, occurrence count, and per-execution average. A
  scanned/while-looped op appears once per trip, so totals reflect what
  the device actually spent.
  """
  agg: Dict[Any, Dict[str, Any]] = {}
  for e in events:
    name = e["args"]["hlo_op"]
    module = e["args"].get("hlo_module", "")
    row = agg.setdefault((module, name),
                         {"name": name, "total_us": 0.0, "count": 0,
                          "module": module})
    row["total_us"] += float(e.get("dur", 0.0))
    row["count"] += 1
  rows = list(agg.values())
  for r in rows:
    r["avg_us"] = r["total_us"] / max(r["count"], 1)
  return rows


MEASURED_OP_TABLE_HEADER = ("rank     total_us  %total  count       avg_us"
                            "  op")


def measured_per_op_table(trace_dir: str, top_n: int = 20,
                          exclude=()) -> Optional[str]:
  """The MEASURED half of the tfprof analog: top-``top_n`` XLA ops by
  accelerator time summed from the captured profiler trace (ref:
  benchmark_cnn.py:1208-1228 ranked by measured accelerator time).
  Returns None when the trace contains no op events (nothing to rank).
  ``exclude`` lists pre-existing profiler run dirs to ignore."""
  rows = measured_op_costs(load_trace_op_events(trace_dir, exclude=exclude))
  if not rows:
    return None
  rows.sort(key=lambda r: r["total_us"], reverse=True)
  total = sum(r["total_us"] for r in rows) or 1.0
  # Disambiguate op names only when several modules landed in the span.
  multi_module = len({r["module"] for r in rows}) > 1
  lines = [f"Top {top_n} ops by MEASURED accelerator time "
           "(jax.profiler trace of the designated step)",
           MEASURED_OP_TABLE_HEADER]
  for rank, r in enumerate(rows[:top_n], 1):
    name = (f"{r['name']} [{r['module']}]" if multi_module else r["name"])
    lines.append(
        f"{rank:4d}  {r['total_us']:11.1f}  {100.0 * r['total_us'] / total:5.1f}%"
        f"  {r['count']:5d}  {r['avg_us']:11.2f}  {name}")
  return "\n".join(lines)


def dump_measured_op_profile(trace_dir: str, path: str, top_n: int = 20,
                             exclude=()) -> Optional[str]:
  """Write the measured per-op table (next to the static .ops.txt) and
  return it; None when the trace yielded no op events -- in which case
  any table a PREVIOUS run left at ``path`` is removed too (a stale
  table must not sit next to this run's fresh .ops.txt)."""
  table = measured_per_op_table(trace_dir, top_n=top_n, exclude=exclude)
  if table is None:
    try:
      os.unlink(path)
    except FileNotFoundError:
      pass
    return None
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  with open(path, "w") as f:
    f.write(table + "\n")
  return table


# -- benchmark logger (ref: benchmark_cnn.py:1594-1608) ---------------------

class BenchmarkLogger:
  """model-garden BenchmarkFileLogger-compatible JSON emission.

  benchmark_run.log: one JSON object of run metadata
  (ref _log_benchmark_run :1694-1724). metric.log: one JSON line per
  metric {name, value, unit, global_step, timestamp, extras}
  (ref :847-854, :1915-1922).
  """

  def __init__(self, log_dir: str):
    self.log_dir = log_dir
    os.makedirs(log_dir, exist_ok=True)
    self._metric_path = os.path.join(log_dir, "metric.log")

  def log_run_info(self, params, model_name: str, dataset_name: str,
                   num_devices: int, batch_size: int) -> None:
    info = {
        "model_name": model_name,
        "dataset": {"name": dataset_name},
        # (ref: --benchmark_test_id threading into the model-garden
        # logger's run info, benchmark_cnn.py:344-348)
        **({"test_id": params.benchmark_test_id}
           if getattr(params, "benchmark_test_id", None) else {}),
        "machine_config": {"num_devices": num_devices,
                           "platform": jax.devices()[0].platform},
        "batch_size": batch_size,
        "run_date": time.strftime("%Y-%m-%dT%H:%M:%S.000Z", time.gmtime()),
        "run_parameters": [
            {"name": k, "value": str(v)}
            for k, v in sorted(params._asdict().items())
            if v is not None],
    }
    with open(os.path.join(self.log_dir, "benchmark_run.log"), "w") as f:
      json.dump(info, f, indent=2)

  def log_metric(self, name: str, value, unit: Optional[str] = None,
                 global_step: Optional[int] = None,
                 extras: Optional[dict] = None) -> None:
    value = float(value)
    if not np.isfinite(value):
      # A diverged run must leave a trace, not a silent gap: emit a
      # sentinel record (null value, flagged) that stays valid JSON.
      extras = dict(extras or {})
      extras["non_finite"] = repr(value)
      value = None
    record = {
        "name": name,
        "value": value,
        "unit": unit,
        "global_step": global_step,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        # Canonical model-garden shape: a list of {name, value} objects.
        "extras": [{"name": k, "value": str(v)}
                   for k, v in sorted((extras or {}).items())],
    }
    with open(self._metric_path, "a") as f:
      f.write(json.dumps(record) + "\n")
    # Mirror REGISTERED names into the active metric registry
    # (metrics.py; no-op sink without a session), so a metric that
    # reaches the reference-schema benchmark log also reaches the live
    # /metrics scrape -- one emission, two sinks. Summary names that
    # live under the health/ namespace map through health_key;
    # reference-only names (current/average_examples_per_sec) have no
    # registry analog and stay file-only.
    if value is not None:
      if name in metrics_lib.SCHEMA:
        metrics_lib.active().set(name, value)
      elif metrics_lib.health_key(name) in metrics_lib.SCHEMA:
        metrics_lib.active().set(metrics_lib.health_key(name), value)


# -- summary writer (ref: benchmark_cnn.py:586-593, 2811-2846) --------------

class SummaryWriter:
  """Tiered JSONL event stream under train_dir.

  Tier 1: scalars (loss, lr, images/sec). Tier 2: + parameter/gradient
  histograms. Tier 3: + per-variable detail (every leaf, not a capped
  subset). The reference's tiers are summaries-none / scalars /
  grad-histograms / all-histograms+images (ref :586-593).
  """

  MAX_TIER2_LEAVES = 16

  def __init__(self, train_dir: str, verbosity: int):
    self.verbosity = verbosity
    self.path = os.path.join(train_dir, "events.jsonl")
    os.makedirs(train_dir, exist_ok=True)

  def _write(self, record: dict) -> None:
    with open(self.path, "a") as f:
      f.write(json.dumps(record) + "\n")

  def write_scalars(self, step: int, scalars: Dict[str, Any]) -> None:
    if self.verbosity < 1:
      return
    clean = {}
    for k, v in scalars.items():
      v = float(v)
      if np.isfinite(v):
        clean[k] = v
    self._write({"step": step, "scalars": clean})

  def write_histograms(self, step: int, tree, prefix: str,
                       stacked_prefixes=()) -> None:
    """``stacked_prefixes`` names top-level tree keys whose leaves are
    scan-stacked over layers (nn.scan rebuilt transformer_lm's blocks
    with a leading depth axis): those unstack into per-layer-indexed
    keys (``params/blocks/layer3/...``) so the histogram stream reads
    per layer instead of blending every depth into one histogram."""
    if self.verbosity < 2:
      return
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    # Tier-2 bound on EMITTED histograms (unstacked per-layer entries
    # each count): truncating the leaf list instead would let one
    # scan-stacked leaf fan out into num_layers records past the cap.
    cap = self.MAX_TIER2_LEAVES if self.verbosity < 3 else None

    def _hist(arr):
      counts, edges = np.histogram(arr, bins=20)
      return {"counts": counts.tolist(),
              "min": float(edges[0]), "max": float(edges[-1]),
              "mean": float(arr.mean()), "std": float(arr.std())}

    hists = {}
    for path, leaf in leaves:
      if cap is not None and len(hists) >= cap:
        break
      # Conventional slash names ("params/conv1/kernel"), not the
      # bracketed keystr/str rendering ("['conv1']['kernel']").
      parts = [str(getattr(p, "key", getattr(p, "name",
                                             getattr(p, "idx", p))))
               for p in path]
      arr = np.asarray(leaf, np.float32)
      if arr.size == 0:
        continue
      if parts and parts[0] in stacked_prefixes and arr.ndim >= 2:
        for i in range(arr.shape[0]):
          if cap is not None and len(hists) >= cap:
            break
          hists["/".join([prefix, parts[0], f"layer{i}"] + parts[1:])] \
              = _hist(arr[i].ravel())
        continue
      hists["/".join([prefix] + parts)] = _hist(arr.ravel())
    self._write({"step": step, "histograms": hists})
