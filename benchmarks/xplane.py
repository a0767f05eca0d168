"""Reduction of a profiler trace (``*.xplane.pb``) to per-layer numbers.

The yardstick for everything read from the device: busy and idle time,
step boundaries, the top operations, collective time and its exposed
part. Kept with the benchmark so that every PR computes the same number
the same way; checked on a recorded trace in ``tests/benchmarks``.

What the TPU writes (seen on "TPU v5 lite", jax 0.9.0): one plane per
chip named ``/device:TPU:<n>``; in it the line ``XLA Modules`` carries
one event per execution of a jitted program and the line ``XLA Ops`` one
event per HLO operation, named by the instruction's whole text
(``%fusion.3 = bf16[64,56,56,64]{...} fusion(...)``) and nested where an
operation has a body (``while``, ``call``). One core runs one operation
at a time, so the leaf events of ``XLA Ops`` never overlap and their
union is the time the chip was busy. (``Steps`` repeats the module
boundaries and ``Async XLA Ops`` the spans of ``-start``/``-done``
pairs; neither is read.)

Interval arithmetic works on plain ``(start, end)`` tuples in seconds so
that it can be tested without a trace.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import math
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_RE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO opcodes that move data between chips. An asynchronous collective
# shows as a ``-start`` and a ``-done`` event; the transfer runs between
# them, so its span is start's begin to done's end.
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?(?:[.\-_].*)?$")
# ``%name = result-type opcode(operands), attributes``
HLO_TEXT_RE = re.compile(r"^%?(?P<name>\S+) = (?P<rest>.*)$", re.DOTALL)
LAYOUT_RE = re.compile(r"\{[^}]*\}")
COMMENT_RE = re.compile(r"/\*.*?\*/")
SHAPE_RE = re.compile(r"\b[a-z][a-z0-9]*\[[0-9,]*\]")
# Steps dropped at the head of the traced window: start_trace() stalls the
# host, the lag-2 pipeline runs dry, and the first steps after it refill
# it -- not the steady state.
SKIP_STEPS = 2
BREAKDOWN_ROWS = 10


@dataclasses.dataclass(frozen=True)
class Event:
  """``name`` is a short label (``op_label``); ``opcode`` the HLO opcode
  where the trace gave the instruction's text, else empty."""
  name: str
  start: float
  end: float
  opcode: str = ""


@dataclasses.dataclass
class DeviceTimeline:
  device: int
  ops: List[Event]
  modules: List[Event]


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
  """Merged, sorted, non-overlapping cover of ``intervals``."""
  merged: List[Interval] = []
  for start, end in sorted(i for i in intervals if i[1] > i[0]):
    if merged and start <= merged[-1][1]:
      if end > merged[-1][1]:
        merged[-1] = (merged[-1][0], end)
    else:
      merged.append((start, end))
  return merged


def total(merged: Iterable[Interval]) -> float:
  return sum(end - start for start, end in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
  """``union(a)`` minus ``union(b)``."""
  out: List[Interval] = []
  b = union(b)
  j = 0
  for start, end in union(a):
    cur = start
    while j < len(b) and b[j][1] <= cur:
      j += 1
    k = j
    while k < len(b) and b[k][0] < end:
      if b[k][0] > cur:
        out.append((cur, b[k][0]))
      cur = max(cur, b[k][1])
      k += 1
    if cur < end:
      out.append((cur, end))
  return out


# -- events -------------------------------------------------------------------

def split_leaves(ops: Sequence[Event]) -> Tuple[List[Event], Dict[str, float]]:
  """``(leaf events, self seconds by name)`` of a nested event line.

  An event that contains later events is a container (``while``,
  ``call``): its self time is its duration minus its children's, and it
  is not a leaf."""
  ordered = sorted(ops, key=lambda e: (e.start, -e.end))
  self_s: Dict[str, float] = collections.defaultdict(float)
  leaves: List[Event] = []
  stack: List[List] = []  # [event, seconds covered by children]

  def close(upto: float) -> None:
    while stack and stack[-1][0].end <= upto:
      event, covered = stack.pop()
      self_s[event.name] += max(0.0, (event.end - event.start) - covered)
      if covered == 0.0:
        leaves.append(event)
      if stack:
        stack[-1][1] += event.end - event.start

  for event in ordered:
    close(event.start)
    stack.append([event, 0.0])
  close(float("inf"))
  leaves.sort(key=lambda e: e.start)
  return leaves, dict(self_s)


def parse_op(text: str) -> Tuple[str, str]:
  """``(label, opcode)`` of an ``XLA Ops`` event. The label is short and
  stable: the instruction's name and the largest shape of its result,
  without the layout -- ``fusion.328 bf16[64,224,224,64]``. The opcode
  matters because a name need not say it: the gradient all-reduce that
  ``lax.psum`` makes is called ``psum_invariant.205``. A name that is not
  instruction text is kept as it is, with no opcode."""
  m = HLO_TEXT_RE.match(text)
  if not m:
    return text.lstrip("%"), ""
  rest = COMMENT_RE.sub("", LAYOUT_RE.sub("", m.group("rest")))
  if rest.startswith("("):  # a tuple result, possibly nested
    depth = 0
    for i, c in enumerate(rest):
      depth += (c == "(") - (c == ")")
      if depth == 0:
        break
    result, tail = rest[:i + 1], rest[i + 1:]
  else:
    result, _, tail = rest.partition(" ")
  opcode = tail.strip().split("(", 1)[0].strip()
  shapes = SHAPE_RE.findall(result)

  def elements(shape: str) -> int:
    dims = shape[shape.index("[") + 1:-1]
    return math.prod(int(d) for d in dims.split(",") if d)

  label = " ".join([m.group("name")] + ([max(shapes, key=elements)]
                                        if shapes else []))
  return label, opcode


def collective_kind(event: Event) -> Optional[Tuple[str, str]]:
  """``(opcode, "" | "-start" | "-done")`` for a collective's event: by
  its opcode, or without one by the first word of its name."""
  m = COLLECTIVE_RE.match(event.opcode or event.name.split(" ", 1)[0])
  return (m.group(1), m.group(2) or "") if m else None


def collective_spans(leaves: Sequence[Event]) -> List[Interval]:
  """One interval per collective: the event itself when synchronous,
  ``-start``'s begin to the matching ``-done``'s end when not (matched
  first-in first-out per opcode, which is the order one core issues
  them in)."""
  spans: List[Interval] = []
  pending: Dict[str, collections.deque] = collections.defaultdict(
      collections.deque)
  for event in leaves:
    kind = collective_kind(event)
    if kind is None:
      continue
    opcode, phase = kind
    if phase == "-start":
      pending[opcode].append(event.start)
    elif phase == "-done":
      begin = pending[opcode].popleft() if pending[opcode] else event.start
      spans.append((begin, event.end))
    else:
      spans.append((event.start, event.end))
  return spans


def step_module(modules: Sequence[Event]) -> Optional[str]:
  """The program that is the step: the module with the most device time."""
  by_name: Dict[str, float] = collections.defaultdict(float)
  for m in modules:
    by_name[m.name] += m.end - m.start
  return max(by_name, key=by_name.get) if by_name else None


# -- one device ---------------------------------------------------------------

@dataclasses.dataclass
class DeviceReduction:
  device: int
  window: Interval
  steps: int
  busy_s: float
  step_intervals_s: List[float]
  exchange_s: Optional[float]
  exchange_exposed_s: Optional[float]
  op_self_s: Dict[str, float]
  gaps: List[Tuple[str, float]]


def reduce_device(timeline: DeviceTimeline, skip_steps: int = SKIP_STEPS
                  ) -> Optional[DeviceReduction]:
  """The steady window of one chip: from the start of the step after the
  skipped ones to the start of the last step seen (whole steps only, so
  the cut edges of the trace count as neither busy nor idle). Without at
  least two step boundaries the window is the extent of the operations."""
  if not timeline.ops:
    return None
  name = step_module(timeline.modules)
  starts = sorted(m.start for m in timeline.modules if m.name == name)
  starts = starts[skip_steps:]
  if len(starts) >= 2:
    window = (starts[0], starts[-1])
    steps = len(starts) - 1
    step_intervals = [b - a for a, b in zip(starts, starts[1:])]
  else:
    window = (min(e.start for e in timeline.ops),
              max(e.end for e in timeline.ops))
    steps, step_intervals = 0, []
  lo, hi = window
  inside = [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in timeline.ops if min(e.end, hi) > max(e.start, lo)]
  leaves, op_self = split_leaves(inside)
  busy = union((e.start, e.end) for e in leaves)

  def gap_name(begin: float, end: float, before: str, after: str) -> str:
    i = bisect.bisect_left(starts, begin)
    crosses = steps and i < len(starts) and starts[i] <= end
    return f"{'between steps' if crosses else 'in step'}: {before} -> {after}"

  gaps: List[Tuple[str, float]] = []
  prev_name, cursor = "window start", lo
  for event in leaves:
    if event.start > cursor:
      gaps.append((gap_name(cursor, event.start, prev_name, event.name),
                   event.start - cursor))
    if event.end > cursor:
      prev_name, cursor = event.name, event.end
  if hi > cursor:
    gaps.append((gap_name(cursor, hi, prev_name, "window end"), hi - cursor))

  spans = collective_spans(leaves)
  exchange = exposed = None
  if spans:
    other = [(e.start, e.end) for e in leaves
             if collective_kind(e) is None]
    exchange = total(union(spans))
    exposed = total(subtract(spans, other))
  return DeviceReduction(
      device=timeline.device, window=window, steps=steps,
      busy_s=total(busy), step_intervals_s=step_intervals,
      exchange_s=exchange, exchange_exposed_s=exposed,
      op_self_s=op_self, gaps=gaps)


# -- all devices --------------------------------------------------------------

@dataclasses.dataclass
class TraceReduction:
  """What the per-layer readers and the last line take from a trace.
  Seconds are means over the chips unless a field says otherwise."""
  devices: int
  steps: int
  window_s: float
  busy_s: float
  idle_share_worst: float
  device_step_ms: Optional[float]
  exchange_ms: Optional[float]
  exchange_exposed_ms: Optional[float]
  device_ops: List[List]
  idle_gaps: List[List]


def _top(named_seconds: Dict[str, float], rows: int = BREAKDOWN_ROWS
         ) -> List[List]:
  ranked = sorted(named_seconds.items(), key=lambda kv: -kv[1])
  return [[name, seconds] for name, seconds in ranked[:rows]
          if seconds > 0]


def reduce(timelines: Sequence[DeviceTimeline],
           skip_steps: int = SKIP_STEPS) -> Optional[TraceReduction]:
  per_device = [r for r in (reduce_device(t, skip_steps) for t in timelines)
                if r is not None]
  if not per_device:
    return None
  n = len(per_device)
  mean = lambda xs: sum(xs) / len(xs)
  window_s = mean([d.window[1] - d.window[0] for d in per_device])
  steps = min(d.steps for d in per_device)
  intervals = [s for d in per_device for s in d.step_intervals_s]
  with_exchange = [d for d in per_device if d.exchange_s is not None]
  exchange_ms = exposed_ms = None
  if with_exchange and steps:
    exchange_ms = 1e3 * mean(
        [d.exchange_s / d.steps for d in with_exchange])
    exposed_ms = 1e3 * mean(
        [d.exchange_exposed_s / d.steps for d in with_exchange])
  ops: Dict[str, float] = collections.defaultdict(float)
  gaps: Dict[str, float] = collections.defaultdict(float)
  for d in per_device:
    for name, seconds in d.op_self_s.items():
      ops[name] += seconds / n
    for name, seconds in d.gaps:
      gaps[name] += seconds / n
  return TraceReduction(
      devices=n, steps=steps, window_s=window_s,
      busy_s=mean([d.busy_s for d in per_device]),
      idle_share_worst=max(
          1.0 - d.busy_s / (d.window[1] - d.window[0]) for d in per_device),
      device_step_ms=(1e3 * statistics.median(intervals)
                      if intervals else None),
      exchange_ms=exchange_ms, exchange_exposed_ms=exposed_ms,
      device_ops=_top(ops), idle_gaps=_top(gaps))


# -- reading the file ---------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
  """The newest ``*.xplane.pb`` the profiler wrote under ``trace_dir``."""
  paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                 "*.xplane.pb"))
  return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> List[DeviceTimeline]:
  """Device timelines of an ``.xplane.pb``, read with JAX alone."""
  from jax.profiler import ProfileData
  timelines = []
  for plane in ProfileData.from_file(path).planes:
    m = DEVICE_PLANE_RE.match(plane.name)
    if not m:
      continue
    lines = {line.name: line for line in plane.lines}

    def events(line_name: str, parse) -> List[Event]:
      out = []
      for e in (lines[line_name].events if line_name in lines else ()):
        label, opcode = parse(e.name)
        out.append(Event(label, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, opcode))
      return out

    timelines.append(DeviceTimeline(
        int(m.group(1)), events(OPS_LINE, functools.lru_cache(None)(parse_op)),
        events(MODULES_LINE, lambda name: (name, ""))))
  timelines.sort(key=lambda t: t.device)
  return timelines
