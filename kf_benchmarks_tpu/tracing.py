"""Run tracing: ONE timeline of what the host did, two sinks, totals
always.

Re-design of the reference's one-step post-hoc tracing (``--trace_file``
captures a FULL_TRACE of step -2 and converts it through
``timeline.Timeline`` into a Chrome trace, ref: benchmark_cnn.py:270-275,
:806-817) into a WHOLE-RUN account of the host, on the device trace's
clock whenever a profiler capture is open.

Every wall-clock boundary the run crosses on the host -- set-up pieces
(build, state init, broadcast, restore), JAX's own trace / lower /
compile episodes, dispatch issue, the blocking metric fetch, the
per-step bookkeeping, DeviceFeeder fetches and consumer waits,
checkpoint saves, mid-training eval, elastic resize seams, fault
injections, the serving engine's requests -- is one span or instant
event emitted through the run's ``RunTrace`` (reached everywhere via
``active()``). From that one emission point the event goes to:

* **The profiler's trace.** A live ``span()`` also enters a profiler
  annotation named ``kf/<subsystem>/<name>`` with the span's arguments,
  and ``step()`` a step annotation. Whenever ANY ``jax.profiler``
  capture is open (the benchmark harness's window, ``--trace_file``, an
  operator's ``start_server`` session) the span lands in the
  ``.xplane.pb`` host plane beside the device planes, on the
  profiler's own clock: one file, one clock, host and device. With no
  capture open a closed annotation costs under a microsecond. The
  annotation factories (``jax.profiler.TraceAnnotation`` /
  ``StepTraceAnnotation``) are INJECTED where the session is created
  (benchmark.py); this module stays pure stdlib. Retrospective records
  (``add_span``: durations measured elsewhere, such as the ``device``
  lane's arrival-interval estimate and JAX's monitoring events) cannot
  enter the profiler and stay in the sinks below.
* **The span file** (``--trace_events_file``): the same spans as Chrome
  trace-event JSON (loads in Perfetto / chrome://tracing), ``pid`` =
  process rank, ``tid`` = subsystem; spans are retained only with the
  flag. Measured with ``time.monotonic`` and anchored to the wall
  clock once at session start so that ranks merge onto one axis.
* **Totals, always.** O(1) per span name -- ``n``, ``total_s``,
  ``max_s``, ``self_s`` -- kept separately for set-up, warm-up and the
  timed loop (``begin_phase``), with the compile-cache counters and the
  garbage collector's passes beside them; ``stats["span_totals"]``
  carries them in every run, file or no file.
* **The step account, always.** Live spans nest: each knows its parent
  (the innermost span open on ITS thread when it began) and, at its
  close, its self time (its duration less what its direct children
  covered). Every ``step()`` iteration of the timed loop leaves one row
  ``{step, t0, dur_s, by_span}`` whose ``by_span`` holds the self time
  of every span closed inside it, and the iteration's own under
  ``self``: exclusive seconds that add up to ``dur_s``.
  ``stats["step_account"]`` carries the newest rows and the STALLS
  (iterations over ``STALL_FACTOR`` x the median) with the span each
  lay under, and the run prints one ``host stall:`` line for each: the
  program itself says which boundary a slow iteration stalled on.

Compilation is read from JAX itself (``jax.monitoring``; listeners
registered by benchmark.py): each ``/jax/core/compile/*`` time span
becomes a ``compile``-lane span carrying ``fun_name``, and the
``/jax/compilation_cache/*`` events move the counters ``cache_hits``,
``cache_misses``, ``cache_requests`` and ``backend_compiles``. JAX reports a nested trace (an inner ``jit``
traced while the outer one is) as an event of its own, so the compile
lane's totals count OUTERMOST intervals only: they add up to the wall
time spent, not to a multiple of it.

Hard contract (enforced by the program-contract auditor's twin-trace
rule, analysis/audit.rule_trace_twin): tracing is HOST-ONLY.  The
trace-on step program is structurally identical to the trace-off one,
and per-step losses are bit-identical (tests/test_tracing.py pins it
through ``--steps_per_dispatch`` / ``--num_grad_accum`` /
``--shard_optimizer_state``). The timed loop never blocks on the device
for tracing's sake: dispatch spans bracket the async jit call alone.

On top of the same spans:

* **Compile ledger** -- per-program-shape first-dispatch wall times keyed
  on the auditor's contract fingerprint keys
  (analysis/baseline.config_fingerprint_key), with ``cache_hit`` from the
  real cache events of that dispatch, persisted/merged to
  ``train_dir/compile_ledger.json`` and printed as a table at run end.
* **Streaming latency percentiles** -- p50/p90/p99 of chunk wall, feed
  wait and checkpoint save, printed at run end and carried in the
  benchmark stats + bench.py JSON.

Span/event EMISSION is single-sourced here -- the lint rule
``trace-event-emission`` (analysis/lint.py) bans Chrome trace-event
construction, percentile helpers and profiler-annotation construction
outside this module's ``RunTrace``.  The flight recorder (telemetry.py)
shares this session's run id and cross-links rows to span ids, so a
post-mortem dump lays over the timeline.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# Subsystem lanes (Chrome tid; one timeline row per subsystem under
# each rank's pid). Order fixes the tid numbering so merged multi-rank
# timelines line up row-for-row; new lanes go at the end so the old
# ones keep their rows. "serving" is the request engine's lane
# (serving/engine.py: enqueue/shed instants, prefill/decode-step spans,
# whole-request spans); "setup" the pieces before warm-up; "fetch" the
# blocking metric read; "handle" the per-step host bookkeeping; "host"
# what the interpreter does to the loop behind its back (the cyclic
# garbage collector, ``host/gc``).
SUBSYSTEMS = ("run", "compile", "dispatch", "device", "feed",
              "checkpoint", "eval", "elastic", "faults", "serving",
              "setup", "fetch", "handle", "host")

# Phases the always-on totals are kept for (begin_phase): everything up
# to the first dispatch, the warm-up (whose first dispatch traces and
# compiles the step), and the timed loop.
PHASE_SETUP = "setup"
PHASE_WARMUP = "warmup"
PHASE_TIMED = "timed_loop"

# jax.monitoring events the session listens to (the listeners are
# registered by benchmark.py; this module never imports jax). The time
# spans become compile-lane spans under the short names on the right.
COMPILE_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
CACHE_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}
COUNTER_KEYS = ("cache_hits", "cache_misses", "cache_requests",
                "backend_compiles", "gc_collections")

# The step account (RunTrace.step_account): the newest rows kept, how far
# over the median an iteration has to run to be called a stall, how many
# stalls get a line, and the key of an iteration's own self time.
ACCOUNT_ROWS = 4096
STALL_FACTOR = 1.5
MAX_STALL_LINES = 8
SELF_KEY = "self"

# Canonical latency-sample keys (the percentile lines / stats fields).
# The serving/* entries come from the request engine: TTFT per request,
# decode-step wall per emitted token, and the accepted speculative
# prefix length per slot per verify round (serving/engine.py).
SAMPLE_KEYS = ("chunk_wall", "feed_wait", "checkpoint_save",
               "serving/ttft", "serving/token_latency",
               "serving/accept_len")

# Reported quantiles. Every ``<key>_p<q>`` stats/bench-JSON field is
# SAMPLE_KEYS x QUANTILES; the metric registry (metrics.py) registers
# each rendered key literally and its schema audit cross-checks the
# registration against these two tuples, so the set cannot drift.
QUANTILES = (50, 90, 99)

# The persisted per-train_dir compile ledger (write_ledger /
# read_ledger below).
LEDGER_FILENAME = "compile_ledger.json"


def resolve_run_id(wall_fn=time.time) -> str:
  """One run id shared by the trace and the flight recorder.

  Under kfrun every worker inherits KF_RUN_ID from the launcher, so all
  ranks of one job share a single id (the merge invariant); standalone
  processes mint a wall-clock/pid-derived one."""
  env = os.environ.get("KF_RUN_ID")
  if env:
    return env
  return f"run-{int(wall_fn() * 1000.0):x}-{os.getpid():x}"


def percentile(values, q: float) -> Optional[float]:
  """Linear-interpolated percentile (numpy's default convention) in
  pure deterministic python; None on an empty sample set."""
  vs = sorted(float(v) for v in values)
  if not vs:
    return None
  if len(vs) == 1:
    return vs[0]
  pos = (len(vs) - 1) * (q / 100.0)
  lo = int(pos)
  hi = min(lo + 1, len(vs) - 1)
  frac = pos - lo
  return vs[lo] * (1.0 - frac) + vs[hi] * frac


def _event_sort_key(e):
  """Metadata rows first, then epoch order -- the ONE event ordering
  every export and merge path shares (a forked copy of this key or of
  the payload shape below is exactly the schema drift the
  trace-event-emission lint rule exists to prevent, so both are
  single-sourced here even within this module)."""
  return (e.get("ph") != "M", e.get("ts", 0.0))


def _payload(events, run_id: str, **extra_meta) -> Dict[str, Any]:
  """The ONE Chrome trace-event JSON payload shape."""
  meta: Dict[str, Any] = {"run_id": run_id,
                          "format": "kf_benchmarks_tpu run trace"}
  meta.update(extra_meta)
  return {"traceEvents": events, "displayTimeUnit": "ms",
          "metadata": meta}


def rank_path(path: str, rank: int) -> str:
  """Per-rank span-file path: rank 0 owns the canonical ``path`` (and
  the merged timeline); other ranks write rank-suffixed siblings the
  rank-0 exit merge collects -- the flight_recorder_path convention."""
  if rank == 0:
    return path
  base, ext = os.path.splitext(path)
  return f"{base}.rank{rank}{ext or '.json'}"


def validate_chrome_trace(obj) -> List[str]:
  """Structural check of a Chrome trace-event JSON object; returns
  problem strings (empty = valid). The schema contract the export tests
  pin (the Trace Event Format: ph/ts/dur/pid/tid/name fields)."""
  problems = []
  if not isinstance(obj, dict):
    return ["top level is not an object"]
  events = obj.get("traceEvents")
  if not isinstance(events, list):
    return ["traceEvents missing or not a list"]
  for i, e in enumerate(events):
    if not isinstance(e, dict):
      problems.append(f"event {i} is not an object")
      continue
    ph = e.get("ph")
    if ph not in ("M", "X", "i"):
      problems.append(f"event {i}: unknown ph {ph!r}")
      continue
    if not isinstance(e.get("name"), str) or not e["name"]:
      problems.append(f"event {i}: missing name")
    if not isinstance(e.get("pid"), int) or not isinstance(
        e.get("tid"), int):
      problems.append(f"event {i}: pid/tid must be ints")
    if ph in ("X", "i"):
      ts = e.get("ts")
      if not isinstance(ts, (int, float)) or ts < 0:
        problems.append(f"event {i}: bad ts {ts!r}")
    if ph == "X":
      dur = e.get("dur")
      if not isinstance(dur, (int, float)) or dur < 0:
        problems.append(f"event {i}: bad dur {dur!r}")
  return problems


class _Open:
  """A live span, as a context manager: begun at ``__enter__`` (which
  yields its mutable arguments), closed at ``__exit__``."""

  __slots__ = ("trace", "subsystem", "name", "factory", "label", "args",
               "account", "step", "sid", "parent", "children_s",
               "annotation", "t0")

  def __init__(self, trace, subsystem: str, name: str, factory, label: str,
               args: Dict[str, Any],
               account: Optional[Dict[str, float]] = None):
    self.trace = trace
    self.subsystem, self.name = subsystem, name
    self.factory, self.label, self.args = factory, label, args
    self.account, self.step = account, account is not None
    self.sid, self.parent, self.children_s = 0, None, 0.0
    self.annotation = None

  def __enter__(self) -> Dict[str, Any]:
    self.trace._open(self)
    return self.args

  def __exit__(self, *exc) -> bool:
    self.trace._close(self)
    return False


class RunTrace:
  """One process's span timeline + totals + latency samples + compile
  ledger.

  Host-side only and always cheap: with no ``path`` the span list is
  not retained (the totals, the samples and the ledger still are, so
  ``stats["span_totals"]``, the percentile lines and the bench JSON
  fields work without ``--trace_events_file``). All methods are
  thread-safe (the DeviceFeeder worker emits feed spans from its own
  thread). ``time_fn``/``wall_fn`` are injectable so the unit tests
  drive a deterministic clock. ``annotation`` / ``step_annotation`` are
  the profiler's annotation factories (``name, **kwargs`` -> context
  manager), None for no profiler sink.
  """

  MAX_SPANS = 200_000  # bound memory on very long runs; drops counted
  # Per-key latency-sample bound: at the cap the list decimates 2:1 and
  # the key's stride doubles (keep every 2^k-th sample), so a multi-day
  # run's feed_wait stream stays bounded while the percentile estimate
  # keeps its shape; reported n stays the TRUE observation count.
  MAX_SAMPLES = 16_384

  def __init__(self, path: Optional[str] = None, rank: int = 0,
               num_ranks: int = 1, run_id: Optional[str] = None,
               chrome_format: bool = True, time_fn=time.monotonic,
               wall_fn=time.time, log_fn=None, annotation=None,
               step_annotation=None):
    self.path = path
    self.rank = int(rank)
    self.num_ranks = max(1, int(num_ranks))
    self.chrome_format = bool(chrome_format)
    self.run_id = run_id or resolve_run_id(wall_fn=wall_fn)
    self._time = time_fn
    self._wall = wall_fn
    self._log = log_fn or (lambda s: None)
    # Re-entrant: the garbage collector's hook (on_gc) closes a span on
    # whichever thread a pass ran, possibly inside a locked region here.
    self._lock = threading.RLock()
    # Wall anchor: spans are monotonic-clocked; export maps them onto
    # the epoch axis via this one (wall, mono) pair so ranks merge onto
    # a comparable timeline.
    self._anchor_mono = self._time()
    self._anchor_wall = self._wall()
    self._keep_spans = path is not None
    self._spans: List[Dict[str, Any]] = []
    self._dropped = 0
    self._ids = itertools.count(1)
    # Open live spans, innermost last, per thread; ``_stacks`` lets
    # another thread (the stall watchdog) read the owner's, unlocked.
    self._local = threading.local()
    self._stacks: Dict[int, List["_Open"]] = {}
    self._owner = threading.get_ident()
    # The step account: the newest iterations of the timed loop, how
    # many there were, and the step number of the first.
    self._account: "collections.deque[Dict[str, Any]]" = \
        collections.deque(maxlen=ACCOUNT_ROWS)
    self._iterations = 0
    self._first_step: Optional[int] = None
    self._tids: Dict[str, int] = {s: i for i, s in enumerate(SUBSYSTEMS)}
    self._samples: Dict[str, List[float]] = {}
    self._sample_counts: Dict[str, int] = {}
    self._sample_strides: Dict[str, int] = {}
    self._ledger: List[Dict[str, Any]] = []
    self._annotation = annotation
    self._step_annotation = step_annotation
    # Always-on totals: {phase: {"<sub>/<name>": [n, total_s, max_s,
    # self_s]}} and the counters, overall (for compile_mark) and per
    # phase.
    self._phase = PHASE_SETUP
    self._totals: Dict[str, Dict[str, List[float]]] = {PHASE_SETUP: {}}
    self._counters: Dict[str, float] = dict.fromkeys(COUNTER_KEYS, 0)
    self._phase_counters: Dict[str, Dict[str, float]] = {
        PHASE_SETUP: dict.fromkeys(COUNTER_KEYS, 0)}
    # Outermost compile intervals still standing: (t0, t1, totals row).
    self._compile_cover: List[Any] = []
    # What the program states of itself at trace time (set_static).
    self._statics: Dict[str, Any] = {}

  # -- clock ------------------------------------------------------------------

  def now(self) -> float:
    """This session's monotonic clock (the injectable one -- callers
    attributing spans retrospectively must read time here, not
    time.monotonic, or fake-clock tests skew)."""
    return self._time()

  def _tid(self, subsystem: str) -> int:
    if subsystem not in self._tids:
      self._tids[subsystem] = len(self._tids)
    return self._tids[subsystem]

  # -- span emission (the ONE place trace records are built) ------------------

  def add_span(self, subsystem: str, name: str, t0: float, dur_s: float,
               args: Optional[Dict[str, Any]] = None) -> int:
    """Record a completed span retrospectively (``t0`` from ``now()``);
    returns its id, or 0 when the span was NOT retained (no export
    path, or the MAX_SPANS cap dropped it) -- so a cross-link consumer
    (the flight recorder's span_id) never references a span absent
    from the exported timeline. The retrospective form exists for
    durations measured elsewhere -- the pipeline's chunk arrival
    intervals, JAX's monitoring events -- where wrapping a ``with``
    block around the measured region is not possible. It takes no part
    in the nesting of live spans: nobody's child, its self time its
    whole duration."""
    dur_s = max(0.0, float(dur_s))
    with self._lock:
      total = self._total_row(f"{subsystem}/{name}", dur_s)
      total[1] += dur_s
      total[3] += dur_s
      return self._emit("X", subsystem, name, float(t0), dur_s,
                        dict(args or {}))

  def _total_row(self, key: str, dur_s: float) -> List[float]:
    """The current phase's totals row of a span name, with ``n`` and
    ``max_s`` already counted; the caller adds what the span is worth
    to ``total_s`` and ``self_s`` (all of it, but for the compile lane's
    nesting and a live span's children). Called under the lock."""
    row = self._totals[self._phase].setdefault(key, [0, 0.0, 0.0, 0.0])
    row[0] += 1
    if dur_s > row[2]:
      row[2] = dur_s
    return row

  def instant(self, subsystem: str, name: str, **args) -> int:
    """A zero-duration marker event (fault injections, profiler-capture
    markers); returns its id, or 0 when not retained."""
    return self._emit("i", subsystem, name, self._time(), 0.0,
                      dict(args))

  def _emit(self, ph: str, subsystem: str, name: str, t0: float,
            dur_s: float, args: Dict[str, Any], sid: Optional[int] = None,
            parent: int = 0) -> int:
    with self._lock:
      if not self._keep_spans:
        return 0
      if len(self._spans) >= self.MAX_SPANS:
        self._dropped += 1
        return 0
      if sid is None:
        sid = next(self._ids)
      span = {"id": sid, "ph": ph, "sub": subsystem,
              "tid": self._tid(subsystem), "name": name,
              "t0": t0, "dur": dur_s, "args": args}
      if parent:
        span["parent"] = parent
      self._spans.append(span)
    return sid

  def span(self, subsystem: str, name: str, **args):
    """Context manager form; yields the (mutable) args dict so callers
    can attach results discovered inside the span (e.g. the elastic
    generation number). Also enters the profiler annotation
    ``kf/<subsystem>/<name>`` with the arguments given at entry."""
    return _Open(self, subsystem, name, self._annotation,
                 f"kf/{subsystem}/{name}", args)

  def step(self, name: str, step_num: int):
    """One iteration of a loop: a ``run``-lane span that enters the
    profiler's STEP annotation ``name`` (which profile viewers group
    device and host activity by) instead of a ``kf/`` one, and whose
    close leaves one row of the step account."""
    return _Open(self, "run", name, self._step_annotation, name,
                 {"step_num": int(step_num)}, account={})

  def _stack(self) -> List["_Open"]:
    try:
      return self._local.stack
    except AttributeError:
      stack = self._local.stack = []
      self._stacks[threading.get_ident()] = stack
      return stack

  def _open(self, frame: "_Open") -> None:
    """Begin a live span on this thread: a child of the innermost one
    open here. A ``step()`` starts a new account of exclusive seconds
    by span name; any other span books into the one its parent books
    into."""
    stack = self._stack()
    if self._keep_spans:
      frame.sid = next(self._ids)
    if stack:
      frame.parent = stack[-1]
      if not frame.step:
        frame.account = frame.parent.account
    if frame.factory is not None:
      frame.annotation = frame.factory(frame.label, **frame.args)
    stack.append(frame)
    frame.t0 = self._time()
    if frame.annotation is not None:
      frame.annotation.__enter__()

  def _close(self, frame: "_Open") -> None:
    if frame.annotation is not None:
      frame.annotation.__exit__(None, None, None)
    dur_s = self._time() - frame.t0
    stack = self._stack()
    if stack and stack[-1] is frame:
      stack.pop()
    elif frame in stack:  # closed out of order (a generator left open)
      stack.remove(frame)
    self_s = dur_s - frame.children_s
    key = f"{frame.subsystem}/{frame.name}"
    parent, account, row = frame.parent, frame.account, None
    if parent is not None:
      parent.children_s += dur_s
    if account is not None:
      booked = SELF_KEY if frame.step else key
      account[booked] = account.get(booked, 0.0) + self_s
    if frame.step:  # (iterations do not nest: one inside another would
      # keep its seconds from the outer one's account)
      row = {"step": frame.args["step_num"], "t0": frame.t0,
             "dur_s": dur_s, "by_span": account}
    with self._lock:
      total = self._total_row(key, dur_s)
      total[1] += dur_s
      total[3] += self_s
      if row is not None and self._phase == PHASE_TIMED:
        if self._first_step is None:
          self._first_step = row["step"]
        self._iterations += 1
        self._account.append(row)
      self._emit("X", frame.subsystem, frame.name, frame.t0, dur_s,
                 frame.args, frame.sid,
                 parent.sid if parent is not None else 0)

  def open_spans(self) -> List[str]:
    """The spans open right now on the thread that opened this session
    (the main path), outermost first. For another thread to read (the
    stall watchdog): takes no lock, so it never holds the loop up."""
    return [f"{f.subsystem}/{f.name}"
            for f in list(self._stacks.get(self._owner, ()))]

  def on_gc(self, phase: str, info: Dict[str, Any]) -> None:
    """The ``gc.callbacks`` hook (installed by ``activate``): one
    ``host/gc`` span per pass of the cyclic collector, on whichever
    thread it ran, a child of whatever span that thread was inside."""
    if phase == "start":
      frame = self._local.gc = _Open(
          self, "host", "gc", self._annotation, "kf/host/gc",
          {"generation": info.get("generation")})
      self._open(frame)
      return
    frame = getattr(self._local, "gc", None)
    if frame is None:
      return  # the pass began before the hook was installed
    self._local.gc = None
    frame.args["collected"] = info.get("collected")
    self._close(frame)
    with self._lock:
      self._count("gc_collections", 1)

  # -- always-on totals -------------------------------------------------------

  def begin_phase(self, phase: str) -> None:
    """Spans and counters from here on are totalled under ``phase``
    (PHASE_SETUP from session start, PHASE_WARMUP from the first
    dispatch, PHASE_TIMED from the first timed one)."""
    with self._lock:
      self._phase = phase
      self._totals.setdefault(phase, {})
      self._phase_counters.setdefault(phase,
                                      dict.fromkeys(COUNTER_KEYS, 0))

  def span_totals(self) -> Dict[str, Any]:
    """``{phase: {"spans": {"<subsystem>/<name>": {n, total_s, max_s,
    self_s}}, "counters": {...}}}`` -- the ``stats["span_totals"]``
    field. ``self_s`` is ``total_s`` less what the spans' direct
    children covered."""
    with self._lock:
      return {
          phase: {
              "spans": {key: {"n": int(n), "total_s": total, "max_s": mx,
                              "self_s": own}
                        for key, (n, total, mx, own) in sorted(rows.items())},
              "counters": dict(self._phase_counters[phase]),
          } for phase, rows in self._totals.items()}

  # -- the step account -------------------------------------------------------

  def step_account(self) -> Dict[str, Any]:
    """``{"iterations", "median_s", "rows", "stalls"}`` of the timed
    loop -- the ``stats["step_account"]`` field. ``rows`` are the newest
    ``ACCOUNT_ROWS`` iterations, oldest first: ``{step, t0, dur_s,
    by_span}`` with ``t0`` on this session's clock and ``by_span`` the
    iteration's exclusive seconds by span name (its own under ``self``),
    which add up to ``dur_s``. A STALL is a row longer than
    ``STALL_FACTOR`` x the rows' median; each adds ``timed_step`` (1 for
    the loop's first step), ``excess_s`` over the median, ``under``, the
    key of ``by_span`` that holds the most seconds, and ``then_s``, the
    lengths of the two iterations after it: where the stall lay under
    the wait for the device and those two took no time, the results
    were ready and the HOST was held in the wait; where they took a
    step each, the device was late. Longest first."""
    with self._lock:
      rows = list(self._account)
      iterations, first = self._iterations, self._first_step
    median = percentile([r["dur_s"] for r in rows], 50)
    stalls = []
    for i, r in enumerate(rows):
      if r["dur_s"] > STALL_FACTOR * median:
        stalls.append(dict(
            r, timed_step=r["step"] - first + 1,
            excess_s=r["dur_s"] - median,
            under=max(r["by_span"], key=r["by_span"].get),
            then_s=[n["dur_s"] for n in rows[i + 1:i + 3]]))
    stalls.sort(key=lambda r: -r["dur_s"])
    return {"iterations": iterations, "median_s": median, "rows": rows,
            "stalls": stalls}

  def stall_lines(self, account: Optional[Dict[str, Any]] = None
                  ) -> List[str]:
    """Run-end report of the stalled iterations, one WHOLE line each
    (the scrape-guard contract), at most ``MAX_STALL_LINES``, longest
    first; none where no iteration stalled. ``host stall: timed step N
    took D ms (median M): <span> <ms>, ...; then <ms>, <ms>``: the
    iteration's exclusive milliseconds by span, most first, and the
    lengths of the two iterations after it. ``account`` is a
    ``step_account()`` the caller already holds."""
    if account is None:
      account = self.step_account()
    lines = []
    for r in account["stalls"][:MAX_STALL_LINES]:
      parts = sorted(r["by_span"].items(), key=lambda kv: -kv[1])
      lines.append(
          "host stall: timed step %d took %.1f ms (median %.1f): %s; "
          "then %s" % (
              r["timed_step"], 1e3 * r["dur_s"],
              1e3 * account["median_s"],
              ", ".join("%s %.1f" % (k, 1e3 * v) for k, v in parts),
              ", ".join("%.1f" % (1e3 * v) for v in r["then_s"]) or "-"))
    return lines

  # -- jax.monitoring listeners (registered by benchmark.py) ------------------

  def on_time_span(self, event: str, start_time: float, end_time: float,
                   **kwargs) -> None:
    """``jax.monitoring`` time-span listener: a ``/jax/core/compile/*``
    span (wall-clock seconds) becomes a compile-lane span carrying
    ``fun_name``. Events arrive when they END, inner before outer, so
    an interval that contains standing ones takes their place in the
    totals (module docstring: outermost intervals only)."""
    name = COMPILE_SPAN_EVENTS.get(event)
    if name is None:
      return
    dur = max(0.0, float(end_time) - float(start_time))
    t0 = self._anchor_mono + (float(start_time) - self._anchor_wall)
    with self._lock:
      row = self._total_row(f"compile/{name}", dur)
      cover = self._compile_cover
      while cover and cover[-1][0] >= start_time:
        inner_t0, inner_t1, inner_row = cover.pop()
        inner_row[1] -= inner_t1 - inner_t0
        inner_row[3] -= inner_t1 - inner_t0
      cover.append((start_time, end_time, row))
      row[1] += dur
      row[3] += dur
      if name == "backend_compile":
        self._count("backend_compiles", 1)
    self._emit("X", "compile", name, t0, dur,
               {"fun_name": str(kwargs.get("fun_name", ""))})

  def on_event(self, event: str, **kwargs) -> None:
    """``jax.monitoring`` event listener: the compilation cache's hit /
    miss / request events."""
    key = CACHE_COUNT_EVENTS.get(event)
    if key is not None:
      with self._lock:
        self._count(key, 1)

  def _count(self, key: str, by) -> None:
    self._counters[key] += by
    self._phase_counters[self._phase][key] += by

  # -- static counters --------------------------------------------------------

  def set_static(self, key: str, value: Dict[str, Any]) -> None:
    """A counter the program computes from shapes while it is traced
    (``factor_exchange``: layers, bytes kept off the all-reduce, bytes
    gathered). SET, not added to: a step traced twice states the same
    thing twice."""
    with self._lock:
      self._statics[key] = dict(value)

  def static(self, key: str) -> Optional[Dict[str, Any]]:
    with self._lock:
      value = self._statics.get(key)
      return dict(value) if value is not None else None

  def compile_mark(self):
    """The counters now; hand it to ``note_compile(since=...)`` after a
    dispatch to learn what that dispatch compiled or loaded."""
    c = self._counters
    return (c["cache_hits"], c["cache_requests"], c["backend_compiles"])

  def compiles_since(self, mark) -> int:
    """Backend compilations (loads from the cache included) since
    ``mark``."""
    return int(self._counters["backend_compiles"] - mark[2])

  # -- latency samples --------------------------------------------------------

  def add_sample(self, key: str, seconds: float) -> None:
    with self._lock:
      self._sample_counts[key] = self._sample_counts.get(key, 0) + 1
      stride = self._sample_strides.setdefault(key, 1)
      if (self._sample_counts[key] - 1) % stride:
        return  # decimated-out observation (still counted above)
      vs = self._samples.setdefault(key, [])
      vs.append(float(seconds))
      if len(vs) >= self.MAX_SAMPLES:
        # Deterministic 2:1 decimation + stride doubling: memory stays
        # bounded on arbitrarily long runs, the retained subsample
        # keeps the distribution's shape for the percentile estimate.
        self._samples[key] = vs[::2]
        self._sample_strides[key] = stride * 2

  def percentiles(self) -> Dict[str, Dict[str, float]]:
    """{key: {p50, p90, p99, n}} over every sampled latency key; n is
    the TRUE observation count (the retained subsample may be a
    strided decimation on very long runs, see add_sample)."""
    with self._lock:
      samples = {k: list(v) for k, v in self._samples.items()}
      counts = dict(self._sample_counts)
    out = {}
    for key in sorted(samples):
      vs = samples[key]
      out[key] = {"p50": percentile(vs, 50), "p90": percentile(vs, 90),
                  "p99": percentile(vs, 99),
                  "n": counts.get(key, len(vs))}
    return out

  def percentile_fields(self) -> Dict[str, Optional[float]]:
    """Flat ``<key>_p<q>`` seconds fields for the benchmark stats dict
    (bench.py forwards the chunk_wall/feed_wait subset into its JSON
    line)."""
    out: Dict[str, Optional[float]] = {}
    for key, row in self.percentiles().items():
      for q in QUANTILES:
        out[f"{key}_p{q}"] = row[f"p{q}"]
    return out

  def latency_lines(self) -> List[str]:
    """Run-end percentile report, one WHOLE line per sampled key (the
    scrape-guard contract: never interleaves inside step lines)."""
    lines = []
    for key, row in self.percentiles().items():
      lines.append(
          "latency percentiles: %s p50=%.3fms p90=%.3fms p99=%.3fms "
          "(n=%d)" % (key, 1e3 * row["p50"], 1e3 * row["p90"],
                      1e3 * row["p99"], row["n"]))
    return lines

  # -- compile ledger ---------------------------------------------------------

  def note_compile(self, key: str, program: str, wall_s: float,
                   since=None, **meta) -> None:
    """Record one compile episode. ``key`` is the program-shape
    fingerprint (analysis/baseline.config_fingerprint_key); ``wall_s``
    the host-observed wall of the dispatch that compiled (it blocks on
    trace+compile -- the benchmark.py compile_s convention). With
    ``since`` (a ``compile_mark`` taken before the dispatch) the row
    also says ``cache_hit``: every compile request of the episode was
    answered from the persistent cache, by the cache's own events."""
    entry = {"key": key, "program": program,
             "wall_s": round(float(wall_s), 6)}
    entry.update(meta)
    if since is not None:
      hits = self._counters["cache_hits"] - since[0]
      requests = self._counters["cache_requests"] - since[1]
      entry["cache_hit"] = bool(hits > 0 and hits == requests)
    with self._lock:
      self._ledger.append(entry)
    self.add_span("compile", program, self._time() - float(wall_s),
                  float(wall_s), {"fingerprint": key, **meta})

  def compile_ledger(self) -> Dict[str, Any]:
    """This run's ledger summary: distinct program shapes + total
    compile seconds (the bench.py JSON fields)."""
    with self._lock:
      entries = list(self._ledger)
    return {
        "shapes": len({e["key"] for e in entries}),
        "total_compile_s": round(sum(e["wall_s"] for e in entries), 6),
        "entries": entries,
    }

  def ledger_lines(self) -> List[str]:
    """The run-end compile-ledger table, every row a whole
    self-identifying line (scrape-guard contract)."""
    ledger = self.compile_ledger()
    if not ledger["entries"]:
      return []
    lines = ["compile ledger: %d program shape(s), total compile %.2f s"
             % (ledger["shapes"], ledger["total_compile_s"])]
    lines.append("compile ledger: fingerprint        wall_s  program")
    for e in ledger["entries"]:
      extra = "".join(
          f"  {k}={e[k]}" for k in sorted(e)
          if k not in ("key", "program", "wall_s"))
      lines.append("compile ledger: %-16s %8.3f  %s%s" % (
          e["key"][:16], e["wall_s"], e["program"], extra))
    return lines

  def write_ledger(self, train_dir: str) -> Optional[str]:
    """Persist/merge the ledger to ``train_dir/compile_ledger.json``.

    Merged by fingerprint key across runs (compiles count up; best/last
    walls kept), so the file accumulates the per-shape compile history
    the persistent compile cache (ROADMAP item 5) will key on. Returns
    the path, or None when nothing compiled / the write failed."""
    ledger = self.compile_ledger()
    if not ledger["entries"]:
      return None
    path = os.path.join(train_dir, LEDGER_FILENAME)
    entries: Dict[str, Any] = {}
    try:
      with open(path, encoding="utf-8") as f:
        prior = json.load(f)
      if isinstance(prior, dict) and isinstance(prior.get("entries"),
                                                dict):
        entries = prior["entries"]
    except (OSError, ValueError):
      entries = {}
    for e in ledger["entries"]:
      row = entries.setdefault(e["key"], {
          "program": e["program"], "compiles": 0,
          "min_wall_s": e["wall_s"]})
      row["compiles"] = int(row.get("compiles", 0)) + 1
      row["last_wall_s"] = e["wall_s"]
      row["min_wall_s"] = min(float(row.get("min_wall_s", e["wall_s"])),
                              e["wall_s"])
      for k, v in e.items():
        if k not in ("key", "wall_s"):
          row.setdefault(k, v)
      if "cache_hit" in e:
        # Last value wins: the shape's FIRST run legitimately misses
        # and every later run should read as the hit it was.
        row["cache_hit"] = e["cache_hit"]
    payload = {"run_id": self.run_id, "entries": entries}
    try:
      os.makedirs(train_dir, exist_ok=True)
      tmp = path + ".tmp"
      with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
      os.replace(tmp, path)
    except OSError as e:
      self._log(f"compile ledger write failed (non-fatal): {e}")
      return None
    return path

  # -- export -----------------------------------------------------------------

  def _epoch_us(self, t_mono: float) -> float:
    return (self._anchor_wall + (t_mono - self._anchor_mono)) * 1e6

  def chrome_events(self) -> List[Dict[str, Any]]:
    """This rank's spans as Chrome trace events (metadata + X/i)."""
    with self._lock:
      spans = list(self._spans)
      tids = dict(self._tids)
    events: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": self.rank, "tid": 0,
        "args": {"name": f"rank {self.rank}"},
    }]
    used = {s["tid"] for s in spans}
    for sub, tid in sorted(tids.items(), key=lambda kv: kv[1]):
      if tid in used:
        events.append({"ph": "M", "name": "thread_name",
                       "pid": self.rank, "tid": tid,
                       "args": {"name": sub}})
    for s in spans:
      e = {"ph": s["ph"], "name": s["name"], "cat": s["sub"],
           "pid": self.rank, "tid": s["tid"],
           "ts": round(self._epoch_us(s["t0"]), 3),
           "args": {"span_id": s["id"], **s["args"]}}
      if s.get("parent"):
        e["args"]["parent_id"] = s["parent"]
      if s["ph"] == "X":
        e["dur"] = round(s["dur"] * 1e6, 3)
      else:
        e["s"] = "t"  # instant scope: thread
      events.append(e)
    return events

  def _prior_events(self, path: str) -> List[Dict[str, Any]]:
    """THIS rank's events from an earlier generation's file at
    ``path``: a kfrun checkpoint-restart re-execs the same command with
    the same KF_RUN_ID, and the relaunched generation must EXTEND the
    job's timeline, not truncate it. Foreign run ids (a fresh job
    reusing the path) and unreadable files carry nothing over -- those
    overwrite. Filtered to this rank's pid (rank 0's canonical file may
    be a prior MERGE holding every rank; sibling ranks re-contribute
    their own history through their own rank files) and to non-metadata
    events (metadata regenerates)."""
    try:
      with open(path, encoding="utf-8") as f:
        data = json.load(f)
    except (OSError, ValueError):
      return []
    if not isinstance(data, dict) or \
        data.get("metadata", {}).get("run_id") != self.run_id:
      return []
    return [e for e in data.get("traceEvents", [])
            if isinstance(e, dict) and e.get("ph") != "M"
            and e.get("pid") == self.rank]

  def export(self, merge_wait_s: float = 10.0) -> Optional[str]:
    """Write this rank's span file; rank 0 additionally merges every
    rank's file into one coherent timeline at ``path``.

    Rank files: rank 0 owns ``path`` itself, rank r writes
    ``rank_path(path, r)``. The rank-0 merge waits (bounded, host-side
    file polling -- no process is ever signaled) for sibling files
    because ranks reach run end at slightly different wall times; files
    still missing at the deadline are skipped with a logged note, and
    the per-rank files remain on disk either way. A same-run-id file
    already at the rank path (an earlier restart generation) is
    extended, not truncated."""
    if not self.path:
      return None
    my_path = rank_path(self.path, self.rank)
    my_events: List[Dict[str, Any]] = []
    try:
      os.makedirs(os.path.dirname(my_path) or ".", exist_ok=True)
      # Atomic tmp + os.replace (the write_ledger pattern): the rank-0
      # merge polls for sibling FILES, so a non-atomic write would be
      # seen (and dropped as unreadable) the instant open() creates it.
      tmp = my_path + ".tmp"
      if self.chrome_format:
        my_events = self._prior_events(my_path) + self.chrome_events()
        my_events.sort(key=_event_sort_key)
        with open(tmp, "w", encoding="utf-8") as f:
          json.dump(_payload(my_events, self.run_id,
                             dropped_spans=self._dropped), f)
      else:
        # --use_chrome_trace_format=false: the raw span records, one
        # JSON line each (the flight-recorder-style schema), for
        # consumers that want the unconverted timeline. Same-run-id
        # files extend (restart generations); others are overwritten.
        prior_lines: List[str] = []
        try:
          with open(my_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
          if lines and json.loads(lines[0]).get("run_id") == self.run_id:
            prior_lines = lines
        except (OSError, ValueError):
          pass
        with open(tmp, "w", encoding="utf-8") as f:
          if prior_lines:
            f.write("\n".join(prior_lines) + "\n")
          else:
            f.write(json.dumps({"run_id": self.run_id,
                                "rank": self.rank,
                                "anchor_wall": self._anchor_wall,
                                "anchor_mono": self._anchor_mono})
                    + "\n")
          with self._lock:
            for s in self._spans:
              f.write(json.dumps(s) + "\n")
      os.replace(tmp, my_path)
    except OSError as e:
      self._log(f"trace export failed (non-fatal): {e}")
      return None
    if self.rank != 0 or self.num_ranks <= 1 or not self.chrome_format:
      return my_path
    return self._merge_ranks(my_events, merge_wait_s) or my_path

  def _merge_ranks(self, my_events: List[Dict[str, Any]],
                   wait_s: float) -> Optional[str]:
    """Rank-0 exit merge: one timeline with pid=rank per process.
    ``my_events`` is rank 0's just-exported event list (including any
    prior-generation carry-over)."""
    expected = [rank_path(self.path, r) for r in range(1, self.num_ranks)]
    deadline = time.monotonic() + max(0.0, wait_s)
    while (any(not os.path.exists(p) for p in expected) and
           time.monotonic() < deadline):
      time.sleep(0.1)
    events = list(my_events)
    missing = []
    for p in expected:
      try:
        with open(p, encoding="utf-8") as f:
          data = json.load(f)
        if data.get("metadata", {}).get("run_id") != self.run_id:
          # A stale sibling from a previous job at the same path must
          # not fold foreign epoch-anchored events into THIS run's
          # timeline (same foreign-run-id rule as _prior_events).
          missing.append(p + " (foreign run id)")
          continue
        events.extend(e for e in data.get("traceEvents", [])
                      if isinstance(e, dict))
      except (OSError, ValueError):
        missing.append(p)
    if missing:
      self._log("trace merge: %d rank file(s) missing/unreadable/"
                "foreign at exit (%s); merged what arrived" % (
                    len(missing), ", ".join(missing)))
    events.sort(key=_event_sort_key)
    try:
      with open(self.path, "w", encoding="utf-8") as f:
        json.dump(_payload(events, self.run_id,
                           dropped_spans=self._dropped), f)
    except OSError as e:
      self._log(f"trace merge write failed (non-fatal): {e}")
      return None
    return self.path


# -- compile-ledger query API -------------------------------------------------
# Read side of the persisted ledger (write_ledger above): the autotuner's
# warm pass (analysis/autotune.py) cross-references it to decide which
# program shapes to precompile. Pure stdlib, like everything here.

def read_ledger(train_dir: str) -> Dict[str, Any]:
  """The persisted compile ledger at ``train_dir/compile_ledger.json``
  ({"entries": {}} when absent/unreadable/foreign-shaped -- a missing
  ledger must read as empty history, never raise)."""
  path = os.path.join(train_dir, LEDGER_FILENAME)
  try:
    with open(path, encoding="utf-8") as f:
      data = json.load(f)
  except (OSError, ValueError):
    return {"entries": {}}
  if not isinstance(data, dict) or not isinstance(data.get("entries"),
                                                  dict):
    return {"entries": {}}
  return data


def ledger_keys(ledger: Dict[str, Any]) -> set:
  """The program-shape fingerprint keys a ledger has seen."""
  return set((ledger or {}).get("entries") or {})


def ledger_programs(ledger: Dict[str, Any]) -> set:
  """The program labels (train_step / train_chunk / eval_step ...) a
  ledger predicts a job of this train_dir will compile."""
  out = set()
  for row in ((ledger or {}).get("entries") or {}).values():
    if isinstance(row, dict) and row.get("program"):
      out.add(str(row["program"]))
  return out


def merge_rank_files(path: str, num_ranks: int,
                     run_id: str = "") -> Optional[str]:
  """Standalone merge of already-written per-rank Chrome files (for
  post-hoc tooling/tests when rank 0's exit merge raced a slow rank)."""
  events: List[Dict[str, Any]] = []
  found = 0
  for r in range(num_ranks):
    p = rank_path(path, r)
    try:
      with open(p, encoding="utf-8") as f:
        data = json.load(f)
    except (OSError, ValueError):
      continue
    found += 1
    events.extend(e for e in data.get("traceEvents", [])
                  if isinstance(e, dict))
    run_id = run_id or data.get("metadata", {}).get("run_id", "")
  if not found:
    return None
  events.sort(key=_event_sort_key)
  with open(path, "w", encoding="utf-8") as f:
    json.dump(_payload(events, run_id, merged_ranks=found), f)
  return path


# -- active-session registry --------------------------------------------------
# Deep call sites (DeviceFeeder's worker thread, checkpoint saves, fault
# firing) emit through the active session instead of threading a handle
# through every signature; with no session active they hit the no-op
# sink below, which keeps the untraced hot path allocation-free.

class _NullTrace:
  """No-op sink with the RunTrace emission AND reporting surface (so
  code paths that never installed a session -- direct _train_loop test
  callers -- report empty rather than crash)."""

  rank = 0
  run_id = ""
  path = None

  def now(self) -> float:
    return 0.0

  def add_span(self, *a, **k) -> int:
    return 0

  def instant(self, *a, **k) -> int:
    return 0

  @contextlib.contextmanager
  def span(self, *a, **k):
    yield {}

  step = span

  def begin_phase(self, phase: str) -> None:
    pass

  def set_static(self, key: str, value) -> None:
    pass

  def static(self, key: str):
    return None

  def span_totals(self) -> Dict[str, Any]:
    return {}

  def step_account(self) -> Dict[str, Any]:
    return {"iterations": 0, "median_s": None, "rows": [], "stalls": []}

  def stall_lines(self, account=None) -> List[str]:
    return []

  def open_spans(self) -> List[str]:
    return []

  def compile_mark(self):
    return (0, 0, 0)

  def compiles_since(self, mark) -> int:
    return 0

  def add_sample(self, *a, **k) -> None:
    pass

  def note_compile(self, *a, **k) -> None:
    pass

  def percentiles(self) -> Dict[str, Any]:
    return {}

  def percentile_fields(self) -> Dict[str, Any]:
    return {}

  def latency_lines(self) -> List[str]:
    return []

  def compile_ledger(self) -> Dict[str, Any]:
    return {"shapes": 0, "total_compile_s": 0.0, "entries": []}

  def ledger_lines(self) -> List[str]:
    return []

  def write_ledger(self, train_dir: str) -> None:
    return None

  def export(self, *a, **k) -> None:
    return None


NULL_TRACE = _NullTrace()
_active: Any = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
  """The one ``gc.callbacks`` entry: hands each pass of the cyclic
  collector to the active session (RunTrace.on_gc)."""
  trace = _active
  if trace is not None:
    trace.on_gc(phase, info)


def activate(trace: RunTrace) -> RunTrace:
  global _active
  _active = trace
  if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
  return trace


def deactivate() -> None:
  global _active
  _active = None
  if _on_gc in gc.callbacks:
    gc.callbacks.remove(_on_gc)


def active():
  """The process's active RunTrace, or the no-op sink."""
  return _active if _active is not None else NULL_TRACE


@contextlib.contextmanager
def session(trace: Optional[RunTrace] = None):
  """``trace`` (a fresh RunTrace by default) is the active one inside the
  block; what was active before is put back after it. For code that
  traces a step outside a run and wants what the step states of itself
  (``set_static``): the contract auditor, tests."""
  global _active
  outer, _active = _active, trace if trace is not None else RunTrace()
  try:
    yield _active
  finally:
    _active = outer
