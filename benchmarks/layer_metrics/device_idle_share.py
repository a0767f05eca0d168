"""1 - (union of operation intervals) / window on the device plane, over
whole traced steps, for the chip where it is largest."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  return 100.0 * run.reduction.idle_share_worst if run.reduction else None
