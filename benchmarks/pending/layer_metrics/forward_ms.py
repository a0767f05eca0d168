"""Device time per step in the forward pass: leaf operations whose
``op_name`` lies under the scope ``forward`` and not under its
transpose, mean over the chips (benchmarks/spans.py)."""

LAYER = "step_program"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.part_ms(run, __file__, "forward")
