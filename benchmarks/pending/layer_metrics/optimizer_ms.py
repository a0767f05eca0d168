"""Device time per step in operations whose ``op_name`` lies under the
scope ``optimizer_apply``, mean over the chips. A fused operation goes
whole to the one ``op_name`` it carries (benchmarks/spans.py), so this
is the optimizer's time only where the update runs as operations of its
own: after the exchange, on more than one chip. On ONE chip XLA fuses
the momentum update into the weight-gradient fusions, which count as
backward, and what is left here is the un-fused remainder (microseconds)
-- hence the four-chip cell alone lists the metric, until a reader can
split a fusion by instruction."""

LAYER = "step_program"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import spans
  return spans.part_ms(run, __file__, "optimizer")
