"""The attention core's share of its roofline where v has a head size of
its own (latent attention at q/k nope + rope and v ``v_head_dim``, 192
and 128 in the xing cell): the least time the chip could take for the
operations and bytes the flash kernels EXECUTE under ``attention_core``
in a step (benchmarks/mhc_flops.attention_core_executed: the causal half
only, the scores and dk, dq at q/k's size, the weighted values, dp and
dv at v's, by the launches the trace shows under the scope) over the
device time under that scope (benchmarks/lm_scopes.py). The twin of
``attention_core_roofline``, whose count takes one head size for q, k
and v. None where the trace has no such scope or no kernel under it."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  from benchmarks import mhc_flops
  ms = lm_scopes.scope_ms(run, __file__, "attention_core")
  launches = lm_scopes.kernel_launches(run, __file__, "attention_core")
  if not ms or not any(lm_flops.splash_launches(launches)):
    return None
  flops, bytes_ = mhc_flops.attention_core_executed(
      run.config, run.cell["tokens_per_sample"], run.global_batch, launches)
  return lm_flops.roofline_share(flops, bytes_, ms * 1e-3, run.peaks)
