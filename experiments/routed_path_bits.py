#!/usr/bin/env python3
"""The routed path alone on the chip, bit for bit: sha256 of
``held_experts_ffn``'s y and of its five gradients (x, the pairs'
weights, the three expert weights) at fixed inputs, for the tree given.
Run it on two trees in ONE chip call and compare the lines: a change
that claims "the same arithmetic" (PERF.md section 6, PR 33) hashes
equal; the whole step's check may still move in its last digits, because
XLA fuses the path's neighbours otherwise.

    python3 experiments/routed_path_bits.py [--root DIR]
        [--shape tokens,k,width,expert_width,held,experts]

The default shape is the trinity-mini cell's (8192,8,2048,1024,16,128);
the glm cell's is 8192,4,2048,1536,8,64. The path runs as the tree's
model runs it: at ``expert.row_tile``'s tile where the tree has one (the
tile-aligned layout), packed before. One process a tree (a chip
belongs to the first process that touches JAX); ~80 s each.
"""

import argparse
import hashlib
import json
import os
import sys


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--root", default=os.path.dirname(
      os.path.dirname(os.path.abspath(__file__))))
  parser.add_argument("--shape", default="8192,8,2048,1024,16,128")
  args = parser.parse_args(argv)
  sys.path.insert(0, args.root)
  import jax
  import jax.numpy as jnp
  import numpy as np
  from kf_benchmarks_tpu.parallel import expert
  tokens, k, d, f, g, e = (int(a) for a in args.shape.split(","))
  rows = expert.compact_rows(tokens * k, g, e)
  layout = ({"tile": expert.row_tile(tokens * k, g, e)}
            if hasattr(expert, "row_tile") else {})
  keys = jax.random.split(jax.random.PRNGKey(7), 6)
  x = jax.random.normal(keys[0], (tokens, d), jnp.bfloat16)
  router = 0.02 * jax.random.normal(keys[1], (d, e), jnp.float32)
  w = [0.02 * jax.random.normal(key, shape, jnp.float32) for key, shape in
       zip(keys[2:5], [(g, d, f), (g, d, f), (g, f, d)])]
  cotangent = jax.random.normal(keys[5], (tokens, d), jnp.bfloat16)
  weights, idx, _ = expert.route_topk(x, router, jnp.zeros((e,)), k, 1.0)

  @jax.jit
  def value_and_gradients(x, weights, *w):
    y, pull = jax.vjp(lambda x, weights, *w: expert.held_experts_ffn(
        x, weights, idx, *w, 0, impl="gmm", rows=rows, **layout)[0], x,
        weights, *w)
    return (y,) + pull(cotangent)
  outs = jax.block_until_ready(value_and_gradients(x, weights, *w))
  names = ("y", "d_x", "d_pair_w", "d_w_gate", "d_w_up", "d_w_down")
  print(json.dumps({
      "root": args.root, "shape": args.shape, "rows": rows, **layout,
      "device": jax.devices()[0].device_kind,
      **{name: hashlib.sha256(np.asarray(
          out.astype(jnp.float32)).tobytes()).hexdigest()[:16]
         for name, out in zip(names, outs)}}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
