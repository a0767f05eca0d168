"""The attention core's share of its roofline: the least time the chip
could take for the operations and bytes the flash kernels EXECUTE under
``attention_core`` in a step (benchmarks/lm_flops.attention_core_executed:
the causal half only, two products a forward launch and five a fused
backward launch, by the launches the trace shows under the scope, so a
forward that remat repeats counts where it runs and nowhere else) over
the device time under that scope (benchmarks/lm_scopes.py). None where
the trace has no such scope or no kernel under it."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  ms = lm_scopes.scope_ms(run, __file__, "attention_core")
  launches = lm_scopes.kernel_launches(run, __file__, "attention_core")
  if not ms or not any(lm_flops.splash_launches(launches)):
    return None
  flops, bytes_ = lm_flops.attention_core_executed(
      run.config, run.cell["tokens_per_sample"], run.global_batch, launches)
  return lm_flops.roofline_share(flops, bytes_, ms * 1e-3, run.peaks)
