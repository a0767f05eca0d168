"""The longest timed iteration of the driver loop over the median one, by
the program's own account of its loop (``stats["step_account"]``,
benchmarks/step_account.py), without the iterations this harness's
profiler start and stop distorted. 1.00-1.05 where nothing stalls (an
iteration waits for a step of the device, and steps are even); the size
of the worst stall where one does. The first iterations, which fill the
lag-2 pipeline and wait for nothing, are short and leave it alone. None
where the program keeps no such account."""

LAYER = "driver_loop"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import step_account
  return step_account.max_over_median(run)
