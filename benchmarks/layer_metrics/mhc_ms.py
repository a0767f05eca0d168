"""Device time per step under the program's scope ``mhc``: everything of
the residual streams' hyper-connections (models/mla_moe_lm.py, family
``xing4_0``): each sublayer's coefficients (the streams' norm statistic,
the product with phi, the sigmoids, Sinkhorn-Knopp), the pre-mix that
forms the sublayer's input and the residual mix that forms the streams
after it, in every layer and the MTP block; forward, the forward that
remat repeats and backward together (benchmarks/lm_scopes.py). None
where the trace has no such scope (a program with one residual
stream)."""

LAYER = "residual_streams"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "mhc")
