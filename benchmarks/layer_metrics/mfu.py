"""Model FLOP/s utilisation: the configuration's
``forward_flops_per_sample`` (per unit of its ``sample_unit``) times 3
for a training step (forward, and twice that for the backward pass;
recomputation not counted) or 1 forward-only, times samples per second,
over chips times the chip's bf16 peak. The rate is the steady one of the
traced run (``Run.steady_samples_per_sec``: outside the profiled stretch,
from the median step interval)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "samples_per_sec"


def read(run):
  rate = run.steady_samples_per_sec()
  flops = run.config.get("forward_flops_per_sample")
  if rate is None or flops is None:
    return None
  per_sample = flops * (3 if run.training else 1)
  chips = int(run.kwargs.get("num_devices", 1))
  return 100.0 * per_sample * rate / (chips * run.peaks["bf16_flops_per_s"])
