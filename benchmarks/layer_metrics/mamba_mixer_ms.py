"""Device time per step under the program's scope ``mamba_mixer``:
everything of a Mamba-2 mixer (``in_proj``, the causal depthwise
convolution, the state-space scan, the gated norm, ``out_proj``), in
every such layer; forward, the inside of the mixer that its backward
pass forms again, and backward together (benchmarks/lm_scopes.py). None
where the trace has no such scope (a program without the layer)."""

LAYER = "state_space"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "mamba_mixer")
