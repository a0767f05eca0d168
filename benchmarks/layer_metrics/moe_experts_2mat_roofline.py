"""The grouped products' share of their roofline where an expert is TWO
matrices (``down(relu(up(x))^2)``): as ``moe_experts_roofline``, whose
numerator counts three products a pair, with two
(benchmarks/nemotron_h_flops.moe_experts_executed: 2 x 2 x pairs x
hidden x width a pass at the pairs the program's counter
``pairs_routed_here`` reports, as many passes as the ``gmm`` and ``tgmm``
launches the trace shows under ``moe_experts`` make) over the device
time under that scope (benchmarks/lm_scopes.py). None where the trace
has no such scope or no such kernels, the program no such counter, its
experts three matrices (``stats["moe"]["expert_matrices"]``), or the
launches are not the pattern the count of passes stands on."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import lm_flops
  from benchmarks import lm_scopes
  from benchmarks import nemotron_h_flops
  ms = lm_scopes.scope_ms(run, __file__, "moe_experts")
  launches = lm_scopes.kernel_launches(run, __file__, "moe_experts")
  moe = (run.stats or {}).get("moe") or {}
  pairs = moe.get("pairs_routed_here")
  if (not ms or pairs is None or not launches.get("tgmm") or
      moe.get("expert_matrices") != 2):
    return None
  executed = nemotron_h_flops.moe_experts_executed(
      run.config, pairs, launches.get("gmm", 0.0), launches["tgmm"],
      one_round=moe.get("compact_share") == 1.0)
  if executed is None:
    return None
  return lm_flops.roofline_share(*executed, ms * 1e-3, run.peaks)
