"""The state-space scan's share of its roofline: the least time the
chip could take for the work the scans of a training step NEED
(benchmarks/nemotron_h_flops.ssd_scan_necessary: the chunked algorithm's
products at the published chunk, the causal half inside a chunk, x3 for
forward and backward; x, B, C, dt in and y out and their gradients once)
over the device time under the scope ``ssd_scan``
(benchmarks/lm_scopes.py). The numerator is counted from the
configuration ALONE, so the share reads the same whatever implements
the scan, and what a form runs beyond the necessary (the masked half of
a chunk's square, float32 passes, a forward formed twice) lowers it.
None where the trace has no such scope."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  from benchmarks import nemotron_h_flops
  ms = lm_scopes.scope_ms(run, __file__, "ssd_scan")
  if not ms:
    return None
  flops, bytes_ = nemotron_h_flops.ssd_scan_necessary(
      run.config, run.cell["tokens_per_sample"] * run.global_batch)
  return lm_flops.roofline_share(flops, bytes_, ms * 1e-3, run.peaks)
