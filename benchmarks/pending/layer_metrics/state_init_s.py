"""Seconds in the program's own set-up spans before warm-up: model build,
``make_step_fns``, opening the input and placing the first batch,
``init_state`` (its compilation included), the replica-0 broadcast and a
checkpoint restore (``setup/*`` and ``checkpoint/restore`` of
``stats["span_totals"]["setup"]``, benchmarks/spans.py)."""

LAYER = "setup"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
  from benchmarks import spans
  return spans.span_seconds(run, spans.PHASE_SETUP, spans.STATE_INIT_SPANS)
