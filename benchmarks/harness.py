"""One run of one cell: load, warm up, measure, check, report.

The program under test is reached only through the three calls
``cli.main`` makes (``params.make_params`` -> ``benchmark.setup`` ->
``BenchmarkCNN(params).run()``), in this one process, which owns the
cell's chips. Everything that times or judges the run is here or in a
file this module finds by name (``spec.py``); from the program come only
its step lines, its ``stats`` dict and the trained state.

Clock: the program runs with ``--display_every=1`` and a tee on
``utils.log.log_fn`` stamps ``time.monotonic()`` at the arrival of each
timed step line. A line is printed after that step's value fetch, which
on this machine is a real device sync (PERF.md, PR 21), so the stamps
are step completions. The program has no time-based stop, so the window
is filled with ``ceil(seconds / step_s_hint)`` steps, ``step_s_hint``
being the cell's step time as measured when the cell was defined.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from benchmarks import checks
from benchmarks import spec
from benchmarks import xplane

WARMUP_STEPS = 5
TRACE_DIR = ".bench_trace"  # under the checkout, fixed, git-ignored
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Step lines after a profiler start or stop whose arrival interval the
# host stall (and the lag-2 pipeline refilling) distorts.
STALL_STEPS = 3

# The reference's step line and banner, as utils/log.py prints them.
STEP_RE = re.compile(
    r"^(\d+)\timages/sec: ([\d.]+) \+/- ([\d.]+) \(jitter = ([\d.]+)\)\t"
    r"(\S+)")
TOTAL_RE = re.compile(r"^total images/sec: ([\d.]+)$")
WARMUP_LINE = "Running warm up"
HEADER_PREFIX = "Step\tImg/sec\t"


class Refused(RuntimeError):
  """The machine cannot run this cell: no result line is printed."""


@dataclasses.dataclass(frozen=True)
class Step:
  index: int
  t: float
  loss: float


class StepLog:
  """The tee on the program's ``log_fn``: stamps the lines the clock
  needs and passes every line on to ``echo``."""

  def __init__(self, echo: Callable[[str], None],
               on_step: Optional[Callable[[int], None]] = None):
    self.echo = echo
    self.on_step = on_step
    self.steps: List[Step] = []
    self.t_warmup: Optional[float] = None   # "Running warm up"
    self.t_header: Optional[float] = None   # first timed dispatch follows
    self.t_banner: Optional[float] = None   # timed loop over
    self.banners = 0

  def __call__(self, msg) -> None:
    t = time.monotonic()
    line = str(msg)
    m = STEP_RE.match(line)
    if m:
      try:
        loss = float(m.group(5))
      except ValueError:
        loss = float("nan")
      self.steps.append(Step(int(m.group(1)), t, loss))
    elif line == WARMUP_LINE:
      self.t_warmup = t
    elif line.startswith(HEADER_PREFIX):
      self.t_header = t
    elif TOTAL_RE.match(line):
      self.t_banner = t
      self.banners += 1
    self.echo(line)
    if m and self.on_step is not None:
      self.on_step(len(self.steps))


class TraceWindow:
  """Opens and closes the profiler on a steady stretch of the timed
  loop, driven by the step lines: after ``after_steps`` lines, for at
  least ``min_steps`` further lines and ``min_s`` seconds, at most
  ``max_s`` seconds."""

  def __init__(self, trace_dir: str, after_steps: int, min_steps: int,
               min_s: float, max_s: float):
    self.trace_dir = trace_dir
    self.after_steps = after_steps
    self.min_steps = min_steps
    self.min_s = min_s
    self.max_s = max_s
    self.first: Optional[int] = None  # step line count at start_trace
    self.last: Optional[int] = None   # step line count at stop_trace
    self.t_start = self.t_stop = None

  @property
  def tracing(self) -> bool:
    return self.first is not None and self.last is None

  def on_step(self, n: int) -> None:
    import jax
    if self.first is None:
      if n == self.after_steps:
        jax.profiler.start_trace(self.trace_dir)
        self.first, self.t_start = n, time.monotonic()
    elif self.last is None:
      elapsed = time.monotonic() - self.t_start
      enough = (n - self.first >= self.min_steps + xplane.SKIP_STEPS + 1
                and elapsed >= self.min_s)
      if enough or elapsed >= self.max_s:
        self.stop(n)

  def stop(self, n: int) -> None:
    import jax
    self.t_stop = time.monotonic()
    jax.profiler.stop_trace()
    self.last = n

  def stalled(self, i: int, whole_window: bool) -> bool:
    """Is the arrival interval ending at step line ``i`` distorted by the
    profiler: by its start or stop stalling the host, or (with
    ``whole_window``) by lying inside the traced stretch at all."""
    if self.first is None:
      return False
    last = self.last if self.last is not None else 10 ** 9
    if whole_window:
      return self.first < i <= last + STALL_STEPS
    return (self.first < i <= self.first + STALL_STEPS or
            last < i <= last + STALL_STEPS)


@dataclasses.dataclass
class Run:
  """What the metric readers see. Times are ``time.monotonic()`` seconds;
  ``t0`` is the process start (the first statement of ``run.py``),
  ``t_imported`` the end of all imports and ``t_backend`` the return of
  the ``jax.devices()`` that follows them."""
  cell: Dict[str, Any]
  device: Dict[str, Any]
  peaks: Dict[str, Any]
  kwargs: Dict[str, Any]
  timed_steps: int
  t0: float
  t_imported: float = 0.0
  t_backend: float = 0.0
  global_batch: int = 0
  log: Optional[StepLog] = None
  window: Optional[TraceWindow] = None
  stats: Optional[Dict[str, Any]] = None
  compiles_in_window: Optional[int] = None
  memory_peak_bytes: Optional[int] = None
  reduction: Optional[xplane.TraceReduction] = None

  @property
  def config(self) -> Dict[str, Any]:
    return self.cell["config_data"]

  @property
  def training(self) -> bool:
    return not self.kwargs.get("forward_only")

  @property
  def samples_per_step(self) -> float:
    """Samples one step completes over all the cell's chips, in the
    cell's ``sample_unit``: the global batch, times ``tokens_per_sample``
    where the unit is tokens."""
    per = self.cell.get("tokens_per_sample",
                        self.config.get("tokens_per_sample", 1))
    return self.global_batch * per

  def intervals(self, steady: bool = False, outside_trace: bool = False
                ) -> List[float]:
    """Arrival intervals between consecutive timed step lines.
    ``steady`` drops those a profiler start or stop distorted;
    ``outside_trace`` drops the whole traced stretch as well."""
    steps = self.log.steps
    out = []
    for a, b in zip(steps, steps[1:]):
      if b.index != a.index + 1:
        continue
      if (steady or outside_trace) and self.window is not None and \
          self.window.stalled(b.index, outside_trace):
        continue
      out.append(b.t - a.t)
    return out

  def samples_per_sec(self) -> Optional[float]:
    """``(n-1) * samples_per_step / (t_n - t_1)`` on the benchmark's own
    stamps: the end-to-end rate of an untraced run."""
    intervals = self.intervals()
    if not intervals:
      return None
    return len(intervals) * self.samples_per_step / sum(intervals)

  def steady_samples_per_sec(self) -> Optional[float]:
    """The rate of a traced run outside its profiled stretch:
    ``samples_per_step`` over the MEDIAN arrival interval there. The
    median, because the profiler also stalls the host seconds after it
    has stopped (it writes its files in the background), which a mean
    would count."""
    intervals = self.intervals(outside_trace=True)
    if not intervals:
      return None
    return self.samples_per_step / percentile(intervals, 50)

  @property
  def backend_init_s(self) -> float:
    """The one ``jax.devices()`` call: the machine handing the chip to
    this process. 5 to 11 s here and the widest swing of a run, set by
    the machine and by nothing in the repo, so ``setup_s`` leaves it out."""
    return self.t_backend - self.t_imported

  @property
  def setup_s(self) -> Optional[float]:
    """Process start to the dispatch of the first timed step, less
    ``backend_init_s``."""
    if self.log.t_header is None:
      return None
    return self.log.t_header - self.t0 - self.backend_init_s

  def setup_split(self) -> Dict[str, Optional[float]]:
    """``setup_s`` and its parts (which add up to it): imports of JAX and
    the program; ``setup()``, build and state initialisation; the first
    dispatch; the rest of warm-up. ``backend_init_s`` is beside them."""
    log, first = self.log, (self.stats or {}).get("compile_s")
    warm = None
    if None not in (log.t_warmup, log.t_header, first):
      warm = log.t_header - log.t_warmup - first
    return {
        "import_s": self.t_imported - self.t0,
        "init_s": (None if log.t_warmup is None
                   else log.t_warmup - self.t_backend),
        "first_dispatch_s": first,
        "warmup_s": warm,
        "setup_s": self.setup_s,
        "backend_init_s": self.backend_init_s,
    }

  def loss_at(self, timed_step: int) -> Optional[float]:
    for s in self.log.steps:
      if s.index == timed_step:
        return s.loss
    return None


def percentile(values: List[float], q: float) -> float:
  """Linear-interpolated percentile, q in [0, 100]."""
  import numpy
  return float(numpy.percentile(values, q))


# -- the machine --------------------------------------------------------------

def check_device(devices, chips: int, peaks: Dict[str, Any]
                 ) -> Dict[str, Any]:
  """The device as JAX reports it, or Refused: a cell runs only on TPUs
  whose kind is in the peaks table, with at least its ``chips``."""
  device = {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
  if device["platform"] != "tpu":
    raise Refused(f"no accelerator: JAX found platform="
                  f"{device['platform']} ({device['kind']}, "
                  f"{device['count']} device(s)); not measured")
  if device["kind"] not in peaks:
    raise Refused(f"device_kind {device['kind']!r} is not in "
                  f"benchmarks/peaks.json ({sorted(peaks)}); not measured")
  if device["count"] < chips:
    raise Refused(f"the cell needs {chips} chip(s), JAX sees "
                  f"{device['count']}; not measured")
  return device


def memory_peak_bytes(devices) -> int:
  """Peak bytes held on the fullest of ``devices``: live buffers plus
  what the runtime reserved for programs. On this TPU runtime a loaded
  program's temporaries (activations, scratch) are counted under
  ``peak_bytes_reserved`` and never under ``peak_bytes_in_use`` (my chip
  run, PR 22: 3.22 GB of temporaries moved ``reserved`` and left
  ``in_use`` at the 1 GiB argument), and stay reserved while the program
  is loaded, so the footprint is the sum."""
  peaks = []
  for d in devices:
    stats = d.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
      raise RuntimeError(f"{d} reports no peak_bytes_in_use")
    peaks.append(int(stats["peak_bytes_in_use"]) +
                 int(stats.get("peak_bytes_reserved", 0)))
  return max(peaks)


# -- the job ------------------------------------------------------------------

def job_kwargs(cell: Dict[str, Any], seed: int, seconds: float
               ) -> Dict[str, Any]:
  """The one generator of a training job: the configuration's flags,
  then the traffic mix's, passed to ``make_params`` unchanged, plus what
  the harness itself fixes (seed, window length, warm-up, a line per
  step)."""
  kwargs = dict(cell["config_data"].get("params", {}))
  kwargs.update(cell["traffic_data"].get("params", {}))
  for key in ("num_batches", "num_warmup_batches", "display_every",
              "tf_random_seed"):
    if key in kwargs:
      raise spec.SpecError(f"cell {cell['name']!r} sets {key}, which the "
                           "harness fixes")
  kwargs.update(
      num_batches=max(2, math.ceil(seconds / float(cell["step_s_hint"]))),
      num_warmup_batches=WARMUP_STEPS, display_every=1,
      tf_random_seed=seed)
  return kwargs


def _stderr(line: str) -> None:
  print(line, file=sys.stderr, flush=True)


def run_cell(root: str, name: str, seed: int, seconds: float, traced: bool,
             t0: float, say: Callable[[Dict[str, Any]], None]
             ) -> Dict[str, Any]:
  """Run cell ``name`` once and return the last line's object. ``say``
  prints an earlier line. Raises Refused (or whatever the program
  raises) when there is no result to print."""
  cell = spec.load_cell(root, name)
  peaks_table = spec.load_peaks(root)
  kind = "per_layer" if traced else "end_to_end"
  readers = {m: spec.load_metric(root, kind, m) for m in cell[kind]}
  kwargs = job_kwargs(cell, seed, seconds)

  # Every import first, then the one call that attaches the chip, so that
  # its time can be told apart from the program's (Run.backend_init_s).
  import jax
  from kf_benchmarks_tpu import benchmark  # noqa: F401
  t_imported = time.monotonic()
  devices = jax.devices()
  t_backend = time.monotonic()
  device = check_device(devices, cell["chips"], peaks_table)
  used = devices[:int(kwargs.get("num_devices", 1))]

  run = Run(cell=cell, device=device, peaks=peaks_table[device["kind"]],
            kwargs=kwargs, timed_steps=kwargs["num_batches"], t0=t0,
            t_imported=t_imported, t_backend=t_backend)
  say({"info": "cell", "workload": name, "seed": seed, "seconds": seconds,
       "trace": int(traced), "device": device, "make_params": kwargs})

  trace_dir = os.path.join(root, TRACE_DIR, name)
  if traced:
    shutil.rmtree(trace_dir, ignore_errors=True)
    t = cell["trace"]
    run.window = TraceWindow(trace_dir, t["after_steps"], t["min_steps"],
                             t["min_s"], t["max_s"])
  run.log = StepLog(_stderr, run.window.on_step if traced else None)
  compile_stamps = _drive(run)
  log = run.log
  t_first = log.steps[0].t if log.steps else float("inf")
  t_end = log.t_banner if log.t_banner is not None else float("inf")
  run.compiles_in_window = sum(t_first <= t <= t_end for t in compile_stamps)
  run.memory_peak_bytes = memory_peak_bytes(used)
  failures = _judge(run, used)

  intervals = run.intervals()
  ledger = run.stats.get("compile_ledger") or {}
  say({"info": "setup", **run.setup_split()})
  say({"info": "window", "timed_steps": run.timed_steps,
       "step_lines": len(log.steps),
       "window_s": sum(intervals), "step_s_hint": cell["step_s_hint"],
       "step_s_median": (percentile(intervals, 50) if intervals else None),
       "global_batch": run.global_batch,
       "samples_per_sec": run.samples_per_sec(),
       "program_images_per_sec": run.stats.get("images_per_sec"),
       "program_dispatch_overhead_s": run.stats.get("dispatch_overhead_s")})
  say({"info": "loss", "step_1": run.loss_at(1), "step_64": run.loss_at(64),
       "last": log.steps[-1].loss if log.steps else None})
  say({"info": "compile", "compiles_in_window": run.compiles_in_window,
       "backend_compile_events": len(compile_stamps),
       "ledger_shapes": ledger.get("shapes"),
       "ledger_total_compile_s": ledger.get("total_compile_s")})
  say({"info": "checks", "failures": failures})

  result: Dict[str, Any] = {
      "correct": not failures,
      "attempted": run.timed_steps,
      "failed": checks.failed_steps(log.steps, run.timed_steps),
  }
  out_device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
  if traced:
    r = run.reduction = _reduce_trace(run, trace_dir)
    say({"info": "trace", "first_line": run.window.first,
         "last_line": run.window.last,
         "host_window_s": run.window.t_stop - run.window.t_start,
         "devices": r.devices, "steps": r.steps, "window_s": r.window_s,
         "busy_s": r.busy_s,
         "steady_samples_per_sec": run.steady_samples_per_sec()})
    out_device.update(busy_s=r.busy_s, window_s=r.window_s)
    result["breakdown"] = {"device_ops": r.device_ops,
                           "idle_gaps": r.idle_gaps}
  metrics = {}
  for metric, module in readers.items():
    value = module.read(run)
    if value is not None:
      metrics[metric] = {"value": float(value), "unit": module.UNIT}
  result["metrics"] = metrics
  result["device"] = out_device
  return result


def _drive(run: Run) -> List[float]:
  """The three calls ``cli.main`` makes, under the step-line tee and a
  listener that stamps every backend compilation (which also fires for a
  load from the persistent cache). Fills ``run.stats`` and
  ``run.global_batch``; returns the compilation stamps."""
  import jax
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util

  compile_stamps: List[float] = []

  def on_duration(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
      compile_stamps.append(time.monotonic())

  jax.monitoring.register_event_duration_secs_listener(on_duration)
  orig_log = log_util.log_fn
  log_util.log_fn = run.log
  try:
    params = benchmark.setup(params_lib.make_params(**run.kwargs))
    bench = benchmark.BenchmarkCNN(params)
    run.global_batch = bench.batch_size * max(bench.num_workers, 1)
    run.stats = bench.run()
  finally:
    log_util.log_fn = orig_log
    if run.window is not None and run.window.tracing:
      run.window.stop(len(run.log.steps))
    jax.monitoring.unregister_event_duration_listener(on_duration)
  return compile_stamps


def _judge(run: Run, used) -> List[str]:
  """Every way this run is not ``correct``; empty when it is."""
  log = run.log
  failures = checks.step_lines(log.steps, run.timed_steps)
  failures += checks.run_stats(
      run.stats, log.banners, run.timed_steps,
      WARMUP_STEPS + run.timed_steps if run.training else None)
  if run.compiles_in_window:
    failures.append(f"{run.compiles_in_window} compilation(s) inside the "
                    "measured window")
  named = ["params_finite"] + list(run.cell["traffic_data"].get("checks", []))
  for check in named:
    failures += checks.NAMED[check](run.stats["state"], used)
  return failures


def _reduce_trace(run: Run, trace_dir: str) -> xplane.TraceReduction:
  """The traced stretch, reduced; a traced run in which no operation ran
  on a device plane has nothing to report and is an error."""
  path = xplane.find_xplane(trace_dir)
  if run.window.first is None or path is None:
    raise RuntimeError(
        f"the traced run wrote no trace (profiler opened at step line "
        f"{run.window.first}, {len(run.log.steps)} lines seen, file {path})")
  reduction = xplane.reduce(xplane.load(path))
  if reduction is None or not reduction.busy_s > 0:
    raise RuntimeError(f"no operation ran on a device plane of {path}")
  return reduction


def _finite(obj):
  """``obj`` with every non-finite float as None: a nan loss must reach
  the lines as JSON, not stop them."""
  if isinstance(obj, float):
    return obj if math.isfinite(obj) else None
  if isinstance(obj, dict):
    return {k: _finite(v) for k, v in obj.items()}
  if isinstance(obj, (list, tuple)):
    return [_finite(v) for v in obj]
  return obj


def dumps(obj: Dict[str, Any]) -> str:
  return json.dumps(_finite(obj), allow_nan=False)
