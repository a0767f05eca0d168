"""Seconds JAX spent tracing the step to a jaxpr and lowering it to
StableHLO during warm-up, cache or no cache (``jax.monitoring``'s
``jaxpr_trace`` and ``jaxpr_to_mlir_module`` time spans, outermost
intervals only, as ``tracing.RunTrace`` totals them)."""

LAYER = "setup"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
  from benchmarks import spans
  return spans.span_seconds(run, spans.PHASE_WARMUP, spans.TRACE_SPANS)
