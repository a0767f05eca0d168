"""Host time per timed iteration that is the host's own: the ``train``
step span less the blocking metric fetch (``kf/fetch/metrics``) and the
wait for input (``kf/feed/wait``) inside it, mean over the traced steady
window. The host binds where this nears ``device_step_ms``."""

LAYER = "driver_loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.from_trace(run, __file__, "host_busy_ms")
