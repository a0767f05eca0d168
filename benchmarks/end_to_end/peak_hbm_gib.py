"""Peak device memory held on the fullest of the cell's chips, GiB:
``memory_stats()`` read by the benchmark after the run, live buffers
(``peak_bytes_in_use``) plus the programs' reserved temporaries
(``peak_bytes_reserved``); see ``harness.memory_peak_bytes``.
"""

UNIT = "GiB"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
  return run.memory_peak_bytes / 2.0 ** 30
