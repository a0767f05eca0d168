"""How far the residual mixes are from doubly stochastic: the largest
|row or column sum - 1| of any sublayer's H_res over every token of
every timed step (the program's counter
``stats["mhc"]["sinkhorn_residual"]``, models/mla_moe_lm.py). The
manifold constraint is what keeps the streams' gain bounded through the
depth; 20 Sinkhorn-Knopp iterations leave a few 1e-6 to 1e-5 at the
family's start, one iteration leaves 0.1 and more. A change that trims
the iterations or the coefficients' precision reads here first. None
where the program has no such counter (one residual stream)."""

LAYER = "residual_streams"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  return ((run.stats or {}).get("mhc") or {}).get("sinkhorn_residual")
