"""Median start-to-start interval of the step program on the device
plane (``XLA Modules`` line), over every chip and traced step."""

LAYER = "step_program"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  return run.reduction.device_step_ms if run.reduction else None
