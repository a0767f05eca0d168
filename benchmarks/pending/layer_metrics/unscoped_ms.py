"""Device busy time per step in none of forward, backward, optimizer,
exchange (scope or collective opcode) and metrics: operations that carry
no scope of the program's, such as the compiler's own copies. The
honesty check on the scopes (benchmarks/spans.py)."""

LAYER = "step_program"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.part_ms(run, __file__, "unscoped")
