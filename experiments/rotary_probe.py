#!/usr/bin/env python3
"""The rotary stage alone on the chip (PR 38; PERF.md section 6 holds
what this printed): milliseconds a call, forward and backward, at the
two language-model cells' call shapes, for

  stage        ``ops/rotary.rotary_stage`` as the models call it (the
               kernel on a TPU),
  jnp          the same function's plain ``jnp`` form (the rotation as a
               permutation product), which the CPU suites run,
  composition  what the attention modules ran until PR 38: ``RMSNorm``,
               the factor, a ``rope`` that slices and joins halves, the
               cast, differentiated by autodiff,

and how far the kernel's output and gradients lie from the ``jnp``
form's on the same inputs. ``--block-rows`` times the kernel at other
blocks than ``rotary_plan``'s. A timed program is ``--chain`` calls, each
fed the one before it, so that the host's dispatch (0.2-0.5 ms, more
than a pass takes) is paid once a chain; the backward is the pullback to
the input alone, chained through its cotangent (a kernel whose output
nothing reads is dropped, so the stage's is its backward kernel and no
forward). The chains compile for
minutes (13 chip-minutes for all shapes and two further blocks at the
default chain): ask for the shapes you want.

No reference-file counterpart: the reference zoo has no attention.

    chiprun --chips 1 -- python3 experiments/rotary_probe.py

One JSON line a (shape, variant); through ``chiprun`` from the root of a
checkout, on one chip.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

THETA, EPS = 10000.0, 1e-5
# name: (B, T, H, D), rot_dims, normed, factor
SHAPES = {
    "trinity_window_q": ((1, 8192, 32, 128), 128, True, 128 ** -0.5),
    "trinity_window_k": ((1, 8192, 4, 128), 128, True, 1.0),
    "trinity_full_q": ((1, 8192, 32, 128), 0, True, 128 ** -0.5),
    "glm_q": ((2, 4096, 20, 256), 64, False, 1.0),
    "glm_k_rot": ((2, 4096, 1, 64), 64, False, 1.0),
}


def main(argv=None) -> int:
  import dataclasses
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.models import mla_moe_lm as lm
  from kf_benchmarks_tpu.ops import rotary
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
  parser.add_argument("--block-rows", nargs="*", type=int, default=[])
  parser.add_argument("--iters", type=int, default=10)
  parser.add_argument("--chain", type=int, default=6)
  args = parser.parse_args(argv)
  device = jax.devices()[0]

  def rope(x):
    r = x.shape[-1]
    inv_freq = 1.0 / (THETA ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin

  def timed(fn, x, scale):
    """ms a call of ``fn`` (x, scale) -> an array like x."""
    def chain(x, scale):
      for _ in range(args.chain):
        x = fn(x, scale)
      return x
    chain = jax.jit(chain)
    jax.block_until_ready(chain(x, scale))
    t0 = time.perf_counter()
    for _ in range(args.iters):
      out = chain(x, scale)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / (args.iters * args.chain)

  for name in args.shapes:
    shape, rot, normed, factor = SHAPES[name]
    _, t, heads, head_dim = shape
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, shape, jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, shape, jnp.float32).astype(jnp.bfloat16)
    scale = (1.0 + 0.2 * jax.random.normal(ks, (head_dim,))) if normed \
        else None
    plan = rotary.rotary_plan(t, heads, head_dim, rot, normed, x.dtype)

    def stage(plan):
      tabs = rotary.tables(t, rot, THETA, plan.table_width)
      return lambda x, scale: rotary._stage(x, scale, tabs, rot, EPS,
                                            float(factor), plan)

    def composition(x, scale):
      n = x.astype(jnp.float32)
      if normed:
        n = lm.RMSNorm(EPS).apply({"params": {"scale": scale}}, x)
      n = n * factor if factor != 1.0 else n
      if rot:
        lead = head_dim - rot
        n = jnp.concatenate([n[..., :lead], rope(n[..., lead:])], -1)
      return n.astype(x.dtype)
    variants = {"stage": stage(plan), "composition": composition}
    if plan.implementation == "pallas":
      variants["jnp"] = stage(rotary.rotary_plan(t, heads, head_dim, rot,
                                                 normed, x.dtype,
                                                 on_tpu=False))
      for rows in args.block_rows:
        if t % rows == 0:
          variants[f"stage_{rows}_rows"] = stage(
              dataclasses.replace(plan, block_rows=rows))
    results = {}
    for label, fn in variants.items():
      pull = lambda x, scale, fn=fn: jax.vjp(fn, x, scale)[1](w)
      y, grads = jax.jit(fn)(x, scale), jax.jit(pull)(x, scale)
      results[label] = (y, grads)
      row = {"shape": name, "variant": label,
             "forward_ms": timed(fn, x, scale),
             "backward_ms": timed(
                 lambda g, scale, fn=fn: jax.vjp(fn, x, scale)[1](g)[0], w,
                 scale),
             "device": device.device_kind,
             "plan": dataclasses.asdict(plan) if label == "stage" else None}
      if label != "stage":
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        err = lambda a, b: float(jnp.max(jnp.abs(f32(a) - f32(b))) /
                                 jnp.max(jnp.abs(f32(b))))
        y0, g0 = results["stage"]
        row["stage_minus_this_over_max"] = {
            "y": err(y0, y), "dx": err(g0[0], grads[0]),
            **({"dscale": err(g0[1], grads[1])} if normed else {})}
      print(json.dumps(row), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
