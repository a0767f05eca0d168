"""Samples completed per second over all the cell's chips.

``(n-1) * samples_per_step / (t_n - t_1)`` on the benchmark's own stamps
of the timed step lines. ``samples_per_step`` is the global batch (times
the cell's ``tokens_per_sample`` where its ``sample_unit`` is tokens), so
every training cell reuses this metric as data.
"""

UNIT = "samples/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
  return run.samples_per_sec()
