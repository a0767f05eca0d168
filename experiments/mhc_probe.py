#!/usr/bin/env python3
"""One sublayer's hyper-connection alone on the chip (PERF.md section 6
holds what this printed): milliseconds a call at the xing
cell's shapes (one sequence of 4,096 tokens, 4 streams of 3,584,
bfloat16 streams, float32 coefficients, 20 Sinkhorn-Knopp iterations),
for

  pallas  the kernels ``mhc.mhc_plan`` chooses on a TPU (``--blocks``:
          the tokens of a grid step to try; the plan's first),
  xla     the same functions in ``jax.numpy`` under autodiff, which the
          CPU suites and every shape the kernels do not take run,

each as ``mhc_pre`` alone, ``mhc_post`` alone, and both around a
stand-in sublayer (y = h) forward and backward, with the bytes each
must move and the rate that makes; and how far the kernels' outputs and
gradients lie from ``jax.numpy``'s on the same inputs (the largest
difference over the largest magnitude, a tensor). The calls of one
timing are dispatched one behind the other and waited for once.

No reference-file counterpart: the reference zoo has no multi-stream
residual.

    python3 experiments/mhc_probe.py [--blocks 128 256]

One JSON line a variant; from the root of a checkout, on a machine
with one chip (about a minute).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("streams", "phi", "bias", "alpha")


def main(argv=None) -> int:
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.ops import mhc
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--shape", nargs=3, type=int, default=[4096, 4, 3584],
                      metavar=("TOKENS", "STREAMS", "WIDTH"))
  parser.add_argument("--blocks", nargs="*", type=int, default=[])
  parser.add_argument("--iters", type=int, default=20)
  args = parser.parse_args(argv)
  t, n, d = args.shape
  cols = 2 * n + n * n
  device = jax.devices()[0]
  k = jax.random.split(jax.random.PRNGKey(0), 5)
  inputs = (jax.random.normal(k[0], (1, t, n, d)).astype(jnp.bfloat16),
            0.02 * jax.random.normal(k[1], (n * d, cols)),
            jax.random.normal(k[2], (cols,)),
            jnp.ones((3,), jnp.float32))
  w = jax.random.normal(k[3], (1, t, n, d)).astype(jnp.bfloat16)
  mixing = mhc.Mixing(20, 1e-6, (-30.0, 30.0), 1e-6)
  plan = mhc.mhc_plan(t, n, d, jnp.bfloat16)
  row_bytes = t * d * 2

  def timed(fn, *a):
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    out = [fn(*a) for _ in range(args.iters)]
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / args.iters

  def forms(label, block):
    if label == "xla":
      def pre(*v):
        h_pre, h_post, h_res = mhc.coefficients(*v, mixing, mhc.H_POST_SCALE)
        return mhc.Premixed(mhc.premix(v[0], h_pre, jnp.bfloat16), h_pre,
                            h_post, h_res, v[0], mhc.MhcPlan("xla", 0))
      return pre, mhc.residual_mix
    return ((lambda *v: mhc._pallas_pre(*v, mixing, mhc.H_POST_SCALE,
                                        jnp.bfloat16, block)),
            (lambda x, r, p, y: mhc._pallas_post(x, r, p, y, block)))

  variants = [("xla", 0)]
  if plan.implementation == "pallas":
    variants = [("pallas", b) for b in (args.blocks or [plan.token_block])
                ] + variants
  results = {}
  for label, block in variants:
    pre, post = forms(label, block)
    pre_j = jax.jit(pre)
    h, _, h_post, h_res, *_ = pre_j(*inputs)
    post_j = jax.jit(post)

    def sublayer(*v, pre=pre, post=post):
      h, h_pre, h_post, h_res, streams, _ = pre(*v)
      out = post(streams, h_res, h_post, h)
      return (jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)) +
              jnp.sum(h_pre))
    both = jax.jit(jax.value_and_grad(sublayer, argnums=range(4)))
    results[label, block] = (pre_j(*inputs), both(*inputs))
    pre_ms = timed(pre_j, *inputs)
    post_ms = timed(post_j, inputs[0], h_res, h_post, h)
    both_ms = timed(both, *inputs)
    row = {"variant": label, "token_block": block, "shape": args.shape,
           "pre_ms": pre_ms, "pre_GBps": 5 * row_bytes / pre_ms / 1e6,
           "post_ms": post_ms, "post_GBps": 9 * row_bytes / post_ms / 1e6,
           "forward_backward_ms": both_ms,
           # pre 5 + post 9 forward, post 14 + pre 9 backward.
           "forward_backward_GBps": 37 * row_bytes / both_ms / 1e6,
           "device": device.device_kind}
    if label == "xla" and len(results) > 1:
      f32 = lambda v: jnp.asarray(v, jnp.float32)
      err = lambda got, want: float(jnp.max(jnp.abs(f32(got) - f32(want))) /
                                    jnp.max(jnp.abs(f32(want))))
      want_out, (want_loss, want_grads) = results["xla", 0]
      for (other, b), (out, (loss, grads)) in results.items():
        if other == "xla":
          continue
        row[f"pallas_{b}_minus_this_over_max"] = dict(
            **{name: err(g, g0) for name, g, g0 in zip(
                ("h", "h_pre", "h_post", "h_res"), out, want_out)},
            loss=err(loss, want_loss),
            **{f"d_{name}": err(g, g0) for name, g, g0 in zip(
                NAMES, grads, want_grads)})
    print(json.dumps(row), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
