"""Wall time of the first dispatch of the step program: JAX tracing,
lowering and XLA compilation, or a load from the persistent cache."""

LAYER = "setup"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
  return run.stats.get("compile_s")
