"""The four-chip vgg16 cell's step, compiled for a TPU that is described
and not attached (jax.experimental.topologies, v5e:2x2): what the chip's
own compiler makes of the factor data plane (parallel/kungfu.py), at the
benchmark's real shapes and at no chip time.

Nothing runs, so this says nothing about results or times. It holds two
things that only the TPU compiler decides and that PR 25 found by this
very compile (PERF.md section 6):

* the collectives: ONE combined all-reduce that does not carry a dense
  kernel (the parent had three, ``f32[25088,4096]`` alone), and the six
  all-gathers of the factors;
* the device memory of the step program: at most the parent's. The first
  version of the mechanism had the same live bytes at the peak and 63 MB
  MORE in XLA's assignment (the scheduler threaded the small all-gathers
  through every backward fusion), which would have broken the benchmark's
  1% bound on ``peak_hbm_gib``; one optimization barrier in the layer's
  backward put it 143 MB UNDER the parent.

The TPU's library loads in the worker that runs this file and nowhere
else: the topology is described inside a fixture, never at import, and
this is the only test file that does it.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kf_benchmarks_tpu import benchmark
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu.analysis import contracts
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS

# XLA's memory analysis of the PARENT's step (commit 9f5d874, the same
# compile): temporaries, and the peak with arguments and outputs.
PARENT_TEMP_BYTES = 4_257_519_104
PARENT_PEAK_BYTES = 5_395_633_664


@pytest.fixture(scope="module")
def topo():
  import os
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  from jax.experimental import topologies
  try:
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_vgg16_four_chip_step_on_the_tpu_compiler(topo):
  p = params_lib.make_params(
      device="cpu", num_devices=4, num_batches=3, model="vgg16",
      batch_size=64, use_fp16=True, optimizer="momentum",
      variable_update="kungfu", kungfu_option="sync_sgd")
  bench = benchmark.BenchmarkCNN(p)
  # The cell as the chip runs it: bfloat16 compute (--device=cpu would
  # take float16) over a mesh of the four described chips.
  bench.compute_dtype = jnp.bfloat16
  bench.mesh = Mesh(np.asarray(topo.devices), (REPLICA_AXIS,))
  compiled = contracts.lower_step_program(bench)[1].compile()

  text = compiled.as_text()
  all_reduces = [ln for ln in text.splitlines() if " all-reduce(" in ln]
  assert len(all_reduces) == 1, len(all_reduces)
  for shape in ("25088,4096", "4096,4096]", "4096,1001"):
    assert shape not in all_reduces[0].split("all-reduce(")[0], shape
  assert "f32[3,3,512,512]" in all_reduces[0]  # the convolutions are
  # The six gathers: x and dy of fc6, fc7, fc8 over the four chips.
  gathered = set(re.findall(r"= (bf16\[256,\d+\])\S* all-gather\(", text))
  assert gathered == {"bf16[256,25088]", "bf16[256,4096]",
                      "bf16[256,1001]"}, gathered

  memory = compiled.memory_analysis()
  assert memory.temp_size_in_bytes <= PARENT_TEMP_BYTES, (
      memory.temp_size_in_bytes, PARENT_TEMP_BYTES)
  assert memory.peak_memory_in_bytes <= PARENT_PEAK_BYTES, (
      memory.peak_memory_in_bytes, PARENT_PEAK_BYTES)
