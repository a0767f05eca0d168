"""Device time per step inside collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; a ``-start``
/ ``-done`` pair counts from start's begin to done's end), mean over the
chips. Nothing on a trace without collectives."""

LAYER = "exchange"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  return run.reduction.exchange_ms if run.reduction else None
