"""The share of a step's hyper-connected sublayers whose mixing takes the
Pallas kernels (``mhc_pre_fwd`` / ``mhc_pre_bwd`` / ``mhc_post_fwd`` /
``mhc_post_bwd``) rather than ``jax.numpy``: the program's counter
``stats["mhc"]["kernel_share"]`` (ops/mhc.mhc_stats, chosen by
``mhc.mhc_plan`` from the shapes, the streams' dtype and the backend;
the sublayers share one shape, so 1 or 0). 1 in the xing cell on a TPU.
A share that falls to 0 is a mixing that left the kernels for the XLA
fusions, which wrote float32 copies of the four streams between them
(PERF.md section 6): a change of shape or type that the plan
refuses reads here as the cause, where ``mhc_ms`` reads only the time.
None where the program has no such counter (one residual stream, or a
program before the kernels)."""

LAYER = "residual_streams"
UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  return ((run.stats or {}).get("mhc") or {}).get("kernel_share")
