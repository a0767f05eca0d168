"""The hyper-connections' share of their roofline: the least time the
chip could take for the work the residual streams' mixing of a training
step NEEDS (benchmarks/mhc_flops.mhc_necessary: per sublayer and token
the n streams read and written once, the sublayer's input written and
its output read once, forward; backward the streams' gradient read and
written, the streams read once more, the input's and the output's
gradients and the output once each; the coefficients' products x3)
over the device time under the scope ``mhc`` (benchmarks/lm_scopes.py).
The numerator is counted from the configuration ALONE, so the share
reads the same whatever implements the mixing (XLA's fusions now, a
Pallas kernel later), and what a form runs beyond the necessary (float32
copies of the streams, a forward formed again under remat) lowers it.
None where the trace has no such scope."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  from benchmarks import mhc_flops
  ms = lm_scopes.scope_ms(run, __file__, "mhc")
  if not ms:
    return None
  flops, bytes_ = mhc_flops.mhc_necessary(
      run.config, run.cell["tokens_per_sample"] * run.global_batch)
  return lm_flops.roofline_share(flops, bytes_, ms * 1e-3, run.peaks)
