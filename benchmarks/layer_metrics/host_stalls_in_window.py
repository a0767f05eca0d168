"""Timed iterations of the driver loop that ran longer than 1.5 x the
median iteration, by the program's own account of its loop
(``stats["step_account"]``, benchmarks/step_account.py), without the
iterations this harness's profiler start and stop distorted. Expected 0:
the run whose rate reads far off its cell's others is the run where this
is not, and its ``host stall:`` line on stderr names the span the late
iteration lay under. None where the program keeps no such account."""

LAYER = "driver_loop"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import step_account
  return step_account.stalls(run)
