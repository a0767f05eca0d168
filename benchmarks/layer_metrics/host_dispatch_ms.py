"""Mean host time per asynchronous dispatch call of the timed loop
(the program's ``dispatch_overhead_s``): what ``--steps_per_dispatch``
would amortise. It binds only where it nears ``device_step_ms``."""

LAYER = "driver_loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "samples_per_sec"


def read(run):
  value = run.stats.get("dispatch_overhead_s")
  return None if value is None else 1e3 * value
