"""90th percentile of the arrival intervals between timed step lines, on
the benchmark's own stamps, without the few lines a profiler start or
stop stalled. Wants about 100 intervals so that ten lie beyond it; with
fewer than 30 it reports nothing."""

LAYER = "driver_loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "samples_per_sec"

MIN_INTERVALS = 30


def read(run):
  from benchmarks import harness
  intervals = run.intervals(steady=True)
  if len(intervals) < MIN_INTERVALS:
    return None
  return 1e3 * harness.percentile(intervals, 90)
