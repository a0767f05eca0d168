#!/usr/bin/env python3
"""The state-space scan alone on the chip (PR 40; PERF.md section 6
holds what this printed): milliseconds a call of ``ops/ssd.ssd_scan`` at
the nemotron cell's shapes (one sequence of 8,192 positions, 64 heads of
64 over 8 groups, state 128, chunks of 128; bfloat16 operands, float32
steps), forward alone and forward + backward, for

  pallas  the two kernels ``ssd.scan_plan`` chooses on a TPU,
  xla     the same function's einsums under autodiff, which the CPU
          suites and every shape the kernels do not tile run,

and how far the kernels' output and gradients lie from the einsums' on
the same inputs (the largest difference over the largest magnitude, a
tensor). The calls of one timing are dispatched one behind the other
and waited for once, so the host's dispatch (0.2-0.5 ms) hides behind
the device's work.

No reference-file counterpart: the reference zoo has no sequence model.

    chiprun --chips 1 -- python3 experiments/ssd_scan_probe.py

One JSON line a variant; through ``chiprun`` from the root of a
checkout, on one chip (about a minute).
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("x", "dt", "a", "B", "C")


def main(argv=None) -> int:
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.ops import ssd
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--shape", nargs=7, type=int,
                      default=[1, 8192, 64, 64, 8, 128, 128],
                      metavar=("BATCH", "POSITIONS", "HEADS", "HEAD_DIM",
                               "GROUPS", "STATE", "CHUNK"))
  parser.add_argument("--iters", type=int, default=20)
  args = parser.parse_args(argv)
  batch, t, heads, p, groups, n, chunk = args.shape
  device = jax.devices()[0]
  k = jax.random.split(jax.random.PRNGKey(0), 6)
  bf16 = jnp.bfloat16
  inputs = (jax.random.normal(k[0], (batch, t, heads, p)).astype(bf16),
            jax.nn.softplus(jax.random.normal(k[1], (batch, t, heads)) - 4),
            -jnp.arange(1, heads + 1, dtype=jnp.float32),
            jax.random.normal(k[3], (batch, t, groups, n)).astype(bf16),
            jax.random.normal(k[4], (batch, t, groups, n)).astype(bf16))
  w = jax.random.normal(k[5], (batch, t, heads, p))
  plan = ssd.scan_plan(t, heads, groups, chunk, p, n)

  def timed(fn):
    jax.block_until_ready(fn(*inputs))
    t0 = time.perf_counter()
    out = [fn(*inputs) for _ in range(args.iters)]
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / args.iters

  results = {}
  variants = {"xla": dataclasses.replace(plan, implementation="xla")}
  if plan.implementation == "pallas":
    variants = {"pallas": plan, **variants}
  for label, this in variants.items():
    scan = (functools.partial(ssd._xla_scan, plan=this,
                              scan_dtype=jnp.float32) if label == "xla"
            else functools.partial(ssd._pallas_scan, plan=this))
    forward = jax.jit(scan)
    both = jax.jit(jax.grad(lambda *v: jnp.sum(scan(*v) * w),
                            argnums=range(5)))
    results[label] = (forward(*inputs), both(*inputs))
    row = {"variant": label, "shape": args.shape,
           "forward_ms": timed(forward), "forward_backward_ms": timed(both),
           "device": device.device_kind}
    if label == "xla" and "pallas" in results:
      f32 = lambda v: jnp.asarray(v, jnp.float32)
      err = lambda got, want: float(jnp.max(jnp.abs(f32(got) - f32(want))) /
                                    jnp.max(jnp.abs(f32(want))))
      y, grads = results["pallas"]
      row["pallas_minus_this_over_max"] = dict(
          y=err(y, results["xla"][0]),
          **{name: err(g, g0) for name, g, g0 in zip(NAMES, grads,
                                                     results["xla"][1])})
    print(json.dumps(row), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
