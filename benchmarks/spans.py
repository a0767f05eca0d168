"""What the program says about itself, read beside the device trace.

Two sources, both written by the program under test (``tracing.py``):

* ``run.stats["span_totals"]`` -- per phase (``setup``, ``warmup``,
  ``timed_loop``) the total of every span name and the compile-cache
  counters. The set-up metrics come from here, in any run.
* The traced run's ``.xplane.pb`` under ``<root>/.bench_trace/<cell>/``.
  Its host plane holds the program's live spans as ``kf/<lane>/<name>``
  events and one ``train`` event per timed iteration, on the clock of
  the device planes; each ``XLA Ops`` event's metadata holds the
  ``op_name`` the program gave the operation (statistic ``tf_op``, seen
  on "TPU v5 lite", jax 0.9.0), which names the phase of the step it
  belongs to (``jax.named_scope`` in ``train_step.make_step_fns``).

Every leaf operation of the steady window goes to exactly one of six
parts, so the parts add up to the chip's busy time:

  exchange   a collective by opcode (``xplane.collective_kind``), or
             ``op_name`` under the scope ``exchange``
  backward   under ``transpose(jvp(forward))`` (recomputation included),
             however many ``forward`` components lie inside it: where
             the model is differentiated under the step's own scope, as
             an unrolled rematerialised stack is, the backward pass's
             operations are named ``transpose(jvp(forward))/.../
             jvp(forward)/...``, inner component untransposed
  forward    under ``forward`` components none of which is transposed
  optimizer  under ``optimizer_apply``
  metrics    under ``metrics``
  unscoped   none of these: the honesty check on the scopes

Where scopes nest the innermost decides the part (a reduction hook
named ``exchange`` inside the model is exchange), and where it is
``forward`` any transposed ``forward`` around it makes it backward. A
FUSED operation carries one
``op_name``, that of the instruction XLA made its root, and its whole
time goes to that phase: a weight-gradient fusion that also applies the
momentum update counts as backward.

``jax.profiler.ProfileData`` gives events their own statistics but not
their metadata's, so ``tf_op`` is read from the file's bytes with a
reader of the few protobuf fields involved (``op_names``). A trace from
a program without the spans or the scopes (the parent of the PR that
added them) reads as nothing: every function here returns None for what
is not there, and raises only on a trace that contradicts itself or the
program that made it. The second can happen: ``op_name`` is metadata,
the persistent compile cache leaves metadata out of its key, so a
program whose TEXT an earlier checkout already compiled is handed that
checkout's executable, with that checkout's scopes. The program says
which scopes it names (``stats["step_scopes"]``) and ``check_scopes``
holds the trace to them.

``python3 -m benchmarks.spans <trace-dir-or-xplane.pb>`` prints the whole
reading of a trace as JSON (CPU only, seconds).
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import os
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks import harness
from benchmarks import xplane

PARTS = ("forward", "backward", "optimizer", "exchange", "metrics",
         "unscoped")
HOST_PLANE = "/host:CPU"
STEP_EVENT = "train"
SPAN_PREFIX = "kf/"
FETCH_SPAN = "kf/fetch/metrics"
FEED_WAIT_SPAN = "kf/feed/wait"
DISPATCH_PREFIX = "kf/dispatch/"
OP_NAME_STAT = "tf_op"
FLOW_PRODUCER, FLOW_CONSUMER = "_p", "_c"
# How well the profiler lays the device's clock over the host's, with
# room to spare. Where the pipeline is empty when the window opens (the
# four-chip cell), the first execution follows its dispatch within a
# millisecond or so, and four traces of that cell read +1.19, +0.12,
# -0.04 and +0.24 ms for that distance: the alignment wanders by about
# a millisecond from capture to capture. A tenth of the shortest step.
CLOCK_SLACK_S = 5e-3
# A scope as one component of an op_name, possibly inside the names of
# the transformations it went through: ``transpose(jvp(forward))``.
SCOPE_PART = {"forward": "forward", "exchange": "exchange",
              "metrics": "metrics", "optimizer_apply": "optimizer"}
SCOPE_RE = re.compile(
    r"^((?:[a-z_]+\()*)(%s)\)*$" % "|".join(SCOPE_PART))
# Set-up phases of stats["span_totals"] (tracing.PHASE_*).
PHASE_SETUP, PHASE_WARMUP = "setup", "warmup"
STATE_INIT_SPANS = ("setup/", "checkpoint/restore")
TRACE_SPANS = ("compile/jaxpr_trace", "compile/jaxpr_to_mlir")
COMPILE_SPANS = ("compile/backend_compile",)


# -- stats["span_totals"] -----------------------------------------------------

def _phase(run, phase: str) -> Optional[Dict[str, Any]]:
  totals = (run.stats or {}).get("span_totals") or {}
  return totals.get(phase)


def span_seconds(run, phase: str, prefixes: Sequence[str]
                 ) -> Optional[float]:
  """Seconds in ``phase`` under span names that start with one of
  ``prefixes``; None where the program reports no totals."""
  block = _phase(run, phase)
  if block is None:
    return None
  return sum(row["total_s"] for name, row in block["spans"].items()
             if name.startswith(tuple(prefixes)))


def counter(run, key: str, phases: Sequence[str] = (PHASE_SETUP,
                                                    PHASE_WARMUP)
            ) -> Optional[float]:
  """A compile-cache counter summed over ``phases`` (by default all of
  set-up, which is where a run should do its compiling)."""
  blocks = [_phase(run, p) for p in phases]
  if all(b is None for b in blocks):
    return None
  return sum(b["counters"].get(key, 0) for b in blocks if b is not None)


# -- op_name from the file's bytes --------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
  value = shift = 0
  while True:
    b = buf[i]
    i += 1
    value |= (b & 0x7F) << shift
    if not b & 0x80:
      return value, i
    shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
  """``(field number, value)`` of one protobuf message: an int for a
  varint, a memoryview for a length-delimited or fixed-width field."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    wire = key & 7
    if wire == 0:
      value, i = _varint(buf, i)
    elif wire == 2:
      size, i = _varint(buf, i)
      value, i = buf[i:i + size], i + size
    elif wire in (1, 5):
      size = 8 if wire == 1 else 4
      value, i = buf[i:i + size], i + size
    else:
      raise ValueError(f"unexpected protobuf wire type {wire}")
    yield key >> 3, value


def _map_entry(buf) -> Tuple[int, Any]:
  key, value = 0, b""
  for field, v in _fields(buf):
    if field == 1:
      key = v
    elif field == 2:
      value = v
  return key, value


def op_names(path: str) -> Dict[str, Dict[str, str]]:
  """``{device plane: {event name: op_name}}`` of an ``.xplane.pb``.

  The fields read (tsl ``xplane.proto``): XSpace.planes = 1; XPlane.name
  = 2, .event_metadata = 4, .stat_metadata = 5 (both maps from id);
  XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
  XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of a
  stat metadata whose name is the string). Lines and events are skipped
  unread: ``xplane.load``'s reader has those."""
  with open(path, "rb") as f:
    space = memoryview(f.read())
  out: Dict[str, Dict[str, str]] = {}
  for field, plane in _fields(space):
    if field != 1:
      continue
    name, events, stat_names = "", [], {}
    for pf, value in _fields(plane):
      if pf == 2:
        name = bytes(value).decode("utf-8", "replace")
      elif pf == 4:
        events.append(_map_entry(value)[1])
      elif pf == 5:
        key, meta = _map_entry(value)
        stat_names[key] = next(
            (bytes(v).decode("utf-8", "replace")
             for f2, v in _fields(meta) if f2 == 2), "")
    if not xplane.DEVICE_PLANE_RE.match(name):
      continue
    wanted = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
    names: Dict[str, str] = {}
    for meta in events:
      event_name, op_name = "", None
      for ef, value in _fields(meta):
        if ef == 2:
          event_name = bytes(value).decode("utf-8", "replace")
        elif ef == 5:
          stat = dict(_fields(value))
          if stat.get(1) in wanted:
            if 5 in stat:
              op_name = bytes(stat[5]).decode("utf-8", "replace")
            elif 7 in stat:
              op_name = stat_names.get(stat[7], "")
      if op_name is not None:
        names[event_name] = op_name
    out[name] = names
  return out


def scopes_of(op_name: str) -> List[Tuple[str, bool]]:
  """``(scope, transposed)`` of every component of an ``op_name`` that
  is one of the step program's scopes, outermost first."""
  found = []
  for component in op_name.split("/"):
    m = SCOPE_RE.match(component)
    if m:
      found.append((m.group(2), "transpose(" in m.group(1)))
  return found


def part_of(op_name: str, collective: bool) -> str:
  """The one of PARTS an operation belongs to (module docstring)."""
  if collective:
    return "exchange"
  scopes = scopes_of(op_name)
  if not scopes:
    return "unscoped"
  scope, _ = scopes[-1]
  if scope == "forward" and any(
      transposed for s, transposed in scopes if s == "forward"):
    return "backward"
  return SCOPE_PART[scope]


def check_scopes(found: Sequence[str],
                 declared: Optional[Sequence[str]]) -> None:
  """Hold a device trace's scopes to the ones the program says it names
  (module docstring: a warm compile cache can hand a program another
  checkout's metadata). Raises where the trace carries no ``forward``
  scope at all (the executable of a program without scopes), carries a
  scope the program does not name (renamed or removed since), or the
  program names one this reader has no part for. A scope that kept its
  name and moved is NOT caught: a change to the scopes alone has to be
  measured from a cold cache (CLAUDE.md)."""
  if declared is None:
    return
  unknown = sorted(set(declared) - set(SCOPE_PART))
  if unknown:
    raise RuntimeError(
        f"the step program names scopes {unknown} that benchmarks/spans.py "
        "books under no part")
  stale = sorted(set(found) - set(declared))
  if "forward" not in found or stale:
    raise RuntimeError(
        f"the trace's op_names carry the scopes {sorted(found)}, the "
        f"program that ran names {sorted(declared)}: the executable came "
        "out of a persistent compile cache that another version of the "
        "program filled (the cache's key leaves op_name out). Measure "
        "from a cold cache")


# -- the trace ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OpEvent(xplane.Event):
  part: str = "unscoped"


@dataclasses.dataclass(frozen=True)
class HostEvent:
  name: str
  start: float
  end: float
  line: str


@dataclasses.dataclass
class DeviceOps:
  device: int
  ops: List[OpEvent]
  modules: List[Tuple[float, float, str, Optional[int]]]  # + flow id


@dataclasses.dataclass
class Trace:
  devices: List[DeviceOps]
  host: List[HostEvent]         # ``train`` and ``kf/`` events
  launches: Dict[int, float]    # flow id -> start of the host's enqueue
  scopes: frozenset             # the step program's scopes seen in op_names


def load(path: str) -> Trace:
  """The device operations with their parts, the module executions, the
  host plane's program events and the runtime's enqueues. The profiler
  links an enqueue on the host to the execution it caused by a flow id:
  statistic ``_p`` on the producer, ``_c`` on the consumer."""
  from jax.profiler import ProfileData
  names = op_names(path)
  devices: List[DeviceOps] = []
  host: List[HostEvent] = []
  launches: Dict[int, float] = {}
  scopes = set()
  for plane in ProfileData.from_file(path).planes:
    m = xplane.DEVICE_PLANE_RE.match(plane.name)
    if m:
      op_name_of = names.get(plane.name, {})
      parts: Dict[str, Tuple[str, str, str]] = {}
      ops: List[OpEvent] = []
      modules = []
      for line in plane.lines:
        if line.name == xplane.OPS_LINE:
          for e in line.events:
            if e.name not in parts:
              label, opcode = xplane.parse_op(e.name)
              collective = xplane.collective_kind(
                  xplane.Event(label, 0, 0, opcode)) is not None
              op_name = op_name_of.get(e.name, "")
              parts[e.name] = (label, opcode, part_of(op_name, collective))
              scopes.update(scope for scope, _ in scopes_of(op_name))
            label, opcode, part = parts[e.name]
            ops.append(OpEvent(label, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               opcode, part))
        elif line.name == xplane.MODULES_LINE:
          for e in line.events:
            modules.append((e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name,
                            dict(e.stats).get(FLOW_CONSUMER)))
      devices.append(DeviceOps(int(m.group(1)), ops, modules))
    elif plane.name == HOST_PLANE:
      for line in plane.lines:
        for e in line.events:
          start = e.start_ns * 1e-9
          if e.name == STEP_EVENT or e.name.startswith(SPAN_PREFIX):
            host.append(HostEvent(
                e.name, start, (e.start_ns + e.duration_ns) * 1e-9,
                line.name))
            continue
          stats = dict(e.stats)
          if "run_id" in stats and FLOW_PRODUCER in stats:
            launches[stats[FLOW_PRODUCER]] = start
  devices.sort(key=lambda d: d.device)
  host.sort(key=lambda e: e.start)
  return Trace(devices, host, launches, frozenset(scopes))


def _window(dev: DeviceOps, skip_steps: int
            ) -> Optional[Tuple[float, float, int]]:
  """``(lo, hi, whole steps)`` exactly as ``xplane.reduce_device`` cuts
  it: from the start of the step after the skipped ones to the start of
  the last step seen."""
  name = xplane.step_module(
      [xplane.Event(n, s, e) for s, e, n, _ in dev.modules])
  starts = sorted(s for s, _, n, _ in dev.modules if n == name)[skip_steps:]
  if len(starts) < 2:
    return None
  return starts[0], starts[-1], len(starts) - 1


def _mean(values: Sequence[float]) -> float:
  return sum(values) / len(values)


def reduce(trace: Trace, skip_steps: int = xplane.SKIP_STEPS
           ) -> Dict[str, Any]:
  """Everything this module reads from a trace, as one dict. Keys whose
  source the trace lacks are None."""
  out: Dict[str, Any] = {
      "devices": len(trace.devices), "scopes": sorted(trace.scopes),
      "parts_ms": None, "busy_ms": None, "host_busy_ms": None,
      "train_steps": 0, "idle_s": None, "idle_attributed_share": None,
      "launches_checked": 0, "host_spans_ms": None}
  windows = {d.device: _window(d, skip_steps) for d in trace.devices}
  per_device_parts: List[Dict[str, float]] = []
  idle_total = attributed_total = 0.0
  ours = [e for e in trace.host if e.name.startswith(SPAN_PREFIX)]
  attributing = [(e.start, e.end) for e in ours if e.name != FETCH_SPAN]
  for dev in trace.devices:
    window = windows[dev.device]
    if window is None:
      continue
    lo, hi, steps = window
    inside = [dataclasses.replace(e, start=max(e.start, lo),
                                  end=min(e.end, hi))
              for e in dev.ops if min(e.end, hi) > max(e.start, lo)]
    leaves, _ = xplane.split_leaves(inside)
    seconds = dict.fromkeys(PARTS, 0.0)
    for e in leaves:
      seconds[e.part] += e.end - e.start
    per_device_parts.append({p: 1e3 * s / steps
                             for p, s in seconds.items()})
    idle = xplane.subtract([(lo, hi)], [(e.start, e.end) for e in leaves])
    idle_total += xplane.total(idle)
    attributed_total += (xplane.total(idle) -
                         xplane.total(xplane.subtract(idle, attributing)))
  if per_device_parts:
    parts = {p: _mean([d[p] for d in per_device_parts]) for p in PARTS}
    out["busy_ms"] = sum(parts.values())
    out["idle_s"] = idle_total
    if "forward" in trace.scopes:
      out["parts_ms"] = parts
    if ours and idle_total > 0:
      out["idle_attributed_share"] = 100.0 * attributed_total / idle_total
  out["launches_checked"] = check_shared_clock(trace)
  known = [w for w in windows.values() if w is not None]
  if ours:
    # The steady window every chip shares; the whole trace where no
    # device plane gives one (a CPU run).
    lo = max((w[0] for w in known), default=float("-inf"))
    hi = min((w[1] for w in known), default=float("inf"))
    out.update(_host_busy(trace.host, lo, hi))
  return out


def _host_busy(host: Sequence[HostEvent], lo: float, hi: float
               ) -> Dict[str, Any]:
  """Per ``train`` event wholly inside ``(lo, hi)``: its length less the
  blocking metric fetch and the wait for input inside it -- the time the
  host itself needs per iteration. Mean, in ms; and the mean per step of
  every ``kf/`` span name inside those events."""
  steps = [e for e in host if e.name == STEP_EVENT
           and e.start >= lo and e.end <= hi]
  if not steps:
    return {}
  busy: List[float] = []
  by_name: Dict[str, float] = {}
  for step in steps:
    waited = 0.0
    for e in host:
      if (e.line != step.line or e.name == STEP_EVENT or
          e.start < step.start or e.end > step.end):
        continue
      by_name[e.name] = by_name.get(e.name, 0.0) + (e.end - e.start)
      if e.name in (FETCH_SPAN, FEED_WAIT_SPAN):
        waited += e.end - e.start
    busy.append((step.end - step.start) - waited)
  return {"host_busy_ms": 1e3 * _mean(busy), "train_steps": len(steps),
          "host_spans_ms": {name: 1e3 * s / len(steps)
                            for name, s in sorted(by_name.items())}}


def check_shared_clock(trace: Trace) -> int:
  """Host spans and device planes are on one clock only if every
  dispatch begins before the device runs what it launched. The runtime's
  enqueue of a program (a host event with a ``run_id``) and its
  execution on the device share a flow id; the ``kf/dispatch`` span that
  launched it is the last one begun before that enqueue. Returns how
  many executions were checked (0 where the trace has no such events or
  no dispatch spans); raises if one ran more than ``CLOCK_SLACK_S``
  before its dispatch began: two clocks, not one clock read twice."""
  dispatches = sorted(e.start for e in trace.host
                      if e.name.startswith(DISPATCH_PREFIX))
  if not dispatches or not trace.launches:
    return 0
  checked = 0
  for dev in trace.devices:
    for start, _, name, flow in dev.modules:
      enqueue = trace.launches.get(flow)
      if enqueue is None:
        continue  # enqueued before the profiler opened
      i = bisect.bisect_right(dispatches, enqueue)
      if i == 0:
        continue
      checked += 1
      if start < dispatches[i - 1] - CLOCK_SLACK_S:
        raise RuntimeError(
            f"host and device clocks disagree: {name} starts on device "
            f"{dev.device} at {start:.6f} s, more than {CLOCK_SLACK_S} s "
            f"before the kf/dispatch span that launched it "
            f"({dispatches[i - 1]:.6f} s; enqueued at {enqueue:.6f} s)")
  return checked


# -- the run ------------------------------------------------------------------

_cache: Dict[Tuple[str, float], Dict[str, Any]] = {}


def root_of(metric_file: str) -> str:
  """The checkout a metric file was loaded from
  (``<root>/benchmarks/layer_metrics/<name>.py``): the harness hands
  readers no root, and writes the trace under the one it was given."""
  return os.path.dirname(os.path.dirname(os.path.dirname(
      os.path.abspath(metric_file))))


def trace_reading(run, metric_file: str) -> Optional[Dict[str, Any]]:
  """``reduce`` of the traced run's file, once per file; None in an
  untraced run or where no file was written. Raises where the device
  operations' scopes are not the program's own (``check_scopes``)."""
  if run.reduction is None:
    return None
  path = xplane.find_xplane(os.path.join(
      root_of(metric_file), harness.TRACE_DIR, run.cell["name"]))
  if path is None:
    return None
  key = (path, os.path.getmtime(path))
  if key not in _cache:
    _cache.clear()
    reading = reduce(load(path))
    declared = (run.stats or {}).get("step_scopes")
    if reading["devices"]:
      check_scopes(reading["scopes"], declared)
    if declared is None:
      # A program that names no scopes (the parent of the PR that added
      # them): whatever a cached executable's metadata says, no phase
      # is this program's.
      reading["parts_ms"] = None
    _cache[key] = reading
  return _cache[key]


def part_ms(run, metric_file: str, part: str) -> Optional[float]:
  reading = trace_reading(run, metric_file)
  if reading is None or reading["parts_ms"] is None:
    return None
  return reading["parts_ms"][part]


def from_trace(run, metric_file: str, key: str) -> Optional[float]:
  reading = trace_reading(run, metric_file)
  return None if reading is None else reading[key]


def main(argv: Sequence[str]) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("trace",
                      help="a profiler directory or .xplane.pb to read")
  path = parser.parse_args(argv).trace
  if os.path.isdir(path):
    path = xplane.find_xplane(path)
  print(json.dumps(reduce(load(path)), indent=1))
  return 0


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
