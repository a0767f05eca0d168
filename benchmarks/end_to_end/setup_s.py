"""Process start to the dispatch of the first timed step: imports of JAX
and the program, ``setup()``, build, state initialisation, the first
dispatch (trace + compile, or trace + cache load) and warm-up -- less the
one ``jax.devices()`` call in which the machine hands the chip to the
process (``backend_init_s`` on the ``setup`` info line), which swings by
seconds from run to run and which nothing in the repo can move.
"""

UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
  return run.setup_s
