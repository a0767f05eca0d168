"""Share of the steady window's device idle time, over all chips, during
which the host was inside one of the program's ``kf/`` spans other than
the blocking metric fetch: idle time the program can name
(benchmarks/spans.py)."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.from_trace(run, __file__, "idle_attributed_share")
