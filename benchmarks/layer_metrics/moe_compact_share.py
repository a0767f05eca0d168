"""The share of the expert layer's work that took one round: of the
mixture layers of the timed steps (layers x steps, the MTP block's
included), the share whose (token, expert) pairs for the experts held
fit one round of the routed path's sorted buffer (the program's
counter ``stats["moe"]["compact_share"]``, models/mla_moe_lm.py; the
round's rows are ``stats["moe"]["buffer_rows"]``, twice the share of the
pairs the held experts get under perfect balance:
parallel/expert.compact_rows). 1 is every layer of every step. A layer
whose pairs do not fit runs further rounds on the chip: routing stays
dropless, and the step pays that layer's routed path once more per
round, so a share that falls is time, never tokens. None where the
program has no such counter (a program before the rounds, whose buffer
held all tokens x k rows)."""

LAYER = "moe"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  return ((run.stats or {}).get("moe") or {}).get("compact_share")
