#!/usr/bin/env python
"""Dispatch-amortization A/B: --steps_per_dispatch=K vs K=1.

Measures wall-clock throughput of the SAME training config at several
chunk sizes, with utils.sync.drain() at every window boundary. Two arms:

  * the harness arm runs the full BenchmarkCNN loop (what an operator
    gets from the CLI flag);
  * the program arm times raw train_step vs train_chunk dispatches,
    isolating the dispatch amortization from input/metrics plumbing.

Runs on whatever device JAX reports (8 devices wanted). The PERF.md
round-6 rows are CPU-mesh rows (dispatch overhead exists there too --
Python, jit-call machinery, 8-way virtual-device collectives); chip
rows: not measured.

Usage: python experiments/dispatch_amortization_probe.py [model] [batch]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
  os.environ["XLA_FLAGS"] = (
      xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# The device is whatever JAX reports here (JAX_PLATFORMS=cpu gives the
# 8-virtual-device mesh; a TPU host gives its chips) -- asked, printed,
# and used for every mesh below.
DEVICE = jax.devices()[0]
PLATFORM = DEVICE.platform
print(f"probe device: platform={PLATFORM} device_kind={DEVICE.device_kind} "
      f"count={len(jax.devices())}", file=sys.stderr, flush=True)

from kf_benchmarks_tpu import benchmark, params as params_lib  # noqa: E402
from kf_benchmarks_tpu.utils import sync  # noqa: E402


def build(model, batch, k, steps):
  p = params_lib.make_params(
      model=model, batch_size=batch, device=PLATFORM, num_devices=8,
      num_batches=steps, num_warmup_batches=0, steps_per_dispatch=k)
  b = benchmark.BenchmarkCNN(p)
  init_state, train_step, _, broadcast_init, train_chunk = b._build()
  rng = jax.random.PRNGKey(0)
  batch_arrays = b._input_iterator(rng, "train", chunk=k)[0]()
  shape = (b.batch_size_per_device,) + b._model_image_shape()
  state = init_state(rng, jnp.zeros(shape, jnp.float32))
  state = state.replace(params=broadcast_init(state.params))
  fn = train_chunk if k > 1 else train_step
  return b, state, fn, batch_arrays


def timed_window(state, fn, batch, n_dispatches):
  state, metrics = fn(state, *batch)  # compile + warm
  sync.drain(metrics)
  t0 = time.time()
  for _ in range(n_dispatches):
    state, metrics = fn(state, *batch)
  sync.drain(metrics)
  return time.time() - t0


def main():
  # trivial = the CPU mesh's dispatch-bound exemplar (PERF.md round 6);
  # pass lenet/resnet50 etc. to probe compute-heavier steps.
  model = sys.argv[1] if len(sys.argv) > 1 else "trivial"
  batch = int(sys.argv[2]) if len(sys.argv) > 2 else 4
  steps = 64
  rows = []
  for k in (1, 2, 4, 8, 16):
    b, state, fn, arrays = build(model, batch, k, steps)
    t = timed_window(state, fn, arrays, steps // k)
    ips = steps * b.batch_size / t
    rows.append({"steps_per_dispatch": k, "wall_s": round(t, 3),
                 "images_per_sec": round(ips, 1),
                 "ms_per_step": round(t / steps * 1e3, 2)})
    print(json.dumps({"model": model, "global_batch": b.batch_size,
                      **rows[-1]}))
  base = rows[0]["images_per_sec"]
  print(json.dumps({"model": model, "speedup_at_k8":
                    round(rows[3]["images_per_sec"] / base, 2),
                    "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
  main()
