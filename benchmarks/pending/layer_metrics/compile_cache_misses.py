"""Executables compiled and written to the persistent compilation cache
before the first timed step (``/jax/compilation_cache/cache_misses``):
0 in a warm run, every program of the run in the first run of a fresh
checkout."""

LAYER = "setup"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
  from benchmarks import spans
  return spans.counter(run, "cache_misses")
