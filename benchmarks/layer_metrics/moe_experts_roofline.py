"""The grouped products' share of their roofline: the least time the
chip could take for the operations and bytes the program EXECUTES under
``moe_experts`` in a step (benchmarks/lm_flops.moe_experts_executed, one
function for both decoder families: at the pairs the program's counter
``pairs_routed_here`` reports, and as many passes over them as the
``gmm`` and ``tgmm`` launches the trace shows under the scope make:
forward, every forward that is run again, backward) over the device
time under that scope (benchmarks/lm_scopes.py). None where the trace
has no such scope or no such kernels, the program no such counter, or
the launches are not the pattern the count of passes stands on (a
kernel fused or renamed: ``lm_flops.moe_experts_passes``)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  ms = lm_scopes.scope_ms(run, __file__, "moe_experts")
  launches = lm_scopes.kernel_launches(run, __file__, "moe_experts")
  moe = (run.stats or {}).get("moe") or {}
  pairs = moe.get("pairs_routed_here")
  if not ms or pairs is None or not launches.get("tgmm"):
    return None
  executed = lm_flops.moe_experts_executed(
      run.config, pairs, launches.get("gmm", 0.0), launches["tgmm"],
      one_round=moe.get("compact_share") == 1.0)
  if executed is None:
    return None
  return lm_flops.roofline_share(*executed, ms * 1e-3, run.peaks)
