"""Device time per step in the backward pass: leaf operations whose
``op_name`` lies under ``transpose(jvp(forward))``, recomputation and
the optimizer updates XLA fused into weight gradients included, mean
over the chips (benchmarks/spans.py)."""

LAYER = "step_program"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.part_ms(run, __file__, "backward")
