"""The part of ``exchange_ms`` during which no other operation runs on
that chip: what overlap could still hide."""

LAYER = "exchange"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  return run.reduction.exchange_exposed_ms if run.reduction else None
