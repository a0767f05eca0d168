"""The window layers' attention cores' share of their roofline: the
least time the chip could take for the operations and bytes the flash
kernels EXECUTE under ``attention_core_window`` in a step
(benchmarks/afmoe_flops.attention_core_executed: the pairs INSIDE the band only,
two products a forward launch and five a fused backward launch, by the
launches the trace shows under the scope) over the device time under
that scope (benchmarks/lm_scopes.py). None where the trace has no such
scope or no kernel under it."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import afmoe_flops
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  scope = "attention_core_window"
  ms = lm_scopes.scope_ms(run, __file__, scope)
  launches = lm_scopes.kernel_launches(run, __file__, scope)
  if not ms or not any(lm_flops.splash_launches(launches)):
    return None
  flops, bytes_ = afmoe_flops.attention_core_executed(
      run.config, run.cell["tokens_per_sample"], run.global_batch,
      afmoe_flops.WINDOW, launches)
  return lm_flops.roofline_share(flops, bytes_, ms * 1e-3, run.peaks)
