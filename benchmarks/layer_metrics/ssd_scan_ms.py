"""Device time per step under the program's scope ``ssd_scan`` inside
``mamba_mixer``: the softplus of the step, the chunked state-space scan
(the products inside a chunk, the chunks' own states, the states carried
between them) and the ``D x`` term, in every Mamba-2 layer; forward, the
forward that the mixer's backward pass forms again, and backward
together (benchmarks/lm_scopes.py). None where the trace has no such
scope."""

LAYER = "state_space"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "ssd_scan")
