"""Device time per step under the program's scope ``gqa_attention``:
grouped-query attention's four projections, head norms, RoPE (window
layers), gate and the cores inside it (``attention_core_window``,
``attention_core_full``), in every layer; forward, the forward that
remat repeats and backward together (benchmarks/lm_scopes.py)."""

LAYER = "attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "gqa_attention")
