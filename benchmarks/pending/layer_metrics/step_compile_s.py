"""Seconds in XLA's backend compilation of the step during warm-up, or
in reading the executable back from the persistent cache
(``jax.monitoring``'s ``backend_compile`` time spans, which cover
both)."""

LAYER = "setup"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
  from benchmarks import spans
  return spans.span_seconds(run, spans.PHASE_WARMUP, spans.COMPILE_SPANS)
