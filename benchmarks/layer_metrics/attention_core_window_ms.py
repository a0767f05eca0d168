"""Device time per step under the program's scope
``attention_core_window``, inside ``gqa_attention``: the window layers'
cores, all together: the banded flash kernels, which skip the score
tiles outside the band, and XLA's operations around them (layouts, the
partial-dq sum); forward, the forward that remat repeats and backward
together (benchmarks/lm_scopes.py)."""

LAYER = "attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_scopes
  return lm_scopes.scope_ms(run, __file__, "attention_core_window")
