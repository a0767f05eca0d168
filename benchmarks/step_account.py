"""The program's own account of its timed loop, read without a trace.

``run.stats["step_account"]`` (``tracing.RunTrace.step_account``) holds
one row per timed iteration of the driver loop: ``{step, t0, dur_s,
by_span}``, ``t0`` on ``time.monotonic`` (this harness's clock too) and
``by_span`` the iteration's host seconds by span name, exclusive, the
iteration's own under ``self``: they add up to ``dur_s``. The program
keeps it in every run, so the three metrics that read it are defined
wherever the program has it; a program without it (the parent of the PR
that added it) reads as nothing.

In a traced run the harness's own profiler stalls the host where it
starts and where it stops (``TraceWindow.on_step``, called from inside
the step line's listener), which is the benchmark's doing and not the
program's. ``rows_left`` drops the iterations that hold
``run.window.t_start`` or ``t_stop`` and the ``harness.STALL_STEPS``
iterations after each, as ``Run.intervals(steady=True)`` drops the
arrival intervals there. A stall anywhere else stays.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks import harness

STALL_FACTOR = 1.5   # over the median of the iterations left: a stall
SELF_KEY = "self"


def rows_left(run) -> Optional[List[Dict[str, Any]]]:
  """The account's rows without those the harness's profiler distorted;
  None where the program reports no account or no row of it is left."""
  rows = ((run.stats or {}).get("step_account") or {}).get("rows")
  if not rows:
    return None
  dropped = set()
  window = run.window
  for t in (window.t_start, window.t_stop) if window is not None else ():
    if t is None:
      continue
    for i, row in enumerate(rows):
      if row["t0"] <= t <= row["t0"] + row["dur_s"]:
        dropped.update(range(i, i + 1 + harness.STALL_STEPS))
        break
  return [row for i, row in enumerate(rows) if i not in dropped] or None


def _durations(run) -> Optional[List[float]]:
  rows = rows_left(run)
  return None if rows is None else [row["dur_s"] for row in rows]


def stalls(run) -> Optional[int]:
  """Iterations left that ran longer than ``STALL_FACTOR`` x their
  median."""
  durations = _durations(run)
  if durations is None:
    return None
  limit = STALL_FACTOR * harness.percentile(durations, 50)
  return sum(d > limit for d in durations)


def max_over_median(run) -> Optional[float]:
  """The longest iteration left over their median."""
  durations = _durations(run)
  if durations is None:
    return None
  return max(durations) / harness.percentile(durations, 50)


def self_ms(run) -> Optional[float]:
  """Mean over the iterations left of the host time no span names."""
  rows = rows_left(run)
  if rows is None:
    return None
  return 1e3 * sum(row["by_span"].get(SELF_KEY, 0.0)
                   for row in rows) / len(rows)
