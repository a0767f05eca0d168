"""Device time per step under the program's scope ``attention_core``,
inside ``mla_attention``: the flash kernels of latent attention's core
and XLA's operations around them (layouts, the partial-dq sum), in every
layer and the MTP block; forward, the forward that remat repeats and
backward together (benchmarks/lm_scopes.py)."""

LAYER = "attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "attention_core")
