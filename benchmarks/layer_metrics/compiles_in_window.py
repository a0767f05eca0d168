"""Backend compilations (``jax.monitoring``'s backend-compile event, which
also fires for a load from the persistent cache) between the first timed
step line and the banner. Must be 0; ``correct`` is false otherwise."""

LAYER = "setup"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  return run.compiles_in_window
