#!/usr/bin/env python3
"""One mixture layer's routed path alone, at the glm-4.7-flash cell's
sizes, on the chip: what each form of "work on the rows in use" costs
(PERF.md section 6, PR 28). Forward, the forward remat repeats and
backward of ``route_topk`` + ``held_experts_ffn`` over 8,192 tokens x 4
choices, 8 of 64 experts of 2048 x 1536 held, bfloat16, under
``jax.checkpoint`` as the model's block is.

    python3 experiments/moe_rounds_probe.py [--reps 20]

Forms (``rows`` = sorted rows of one round; 8,192 by ``compact_rows``):

* ``all_pairs``: one round of all 32,768 rows, the buffer the layer had
  before PR 28;
* ``rounds``: the program's form, rounds of 8,192 rows in ONE while loop
  that starts from zeros, rows summed into tokens by k gathers through
  the clipped inverse;
* ``cond_autodiff``: ``lax.cond(pairs <= rows, compact, all pairs)``
  differentiated by JAX (the residuals of both branches cross it);
* ``cond_custom_vjp``: the same conditional inside a ``custom_vjp`` that
  saves the inputs and runs the chosen tier again in the backward's own
  conditional;
* ``rounds_two``: the program's form on a step whose pairs need two
  rounds (every token's first choice forced onto a held expert).

Prints one JSON line a form: milliseconds a call (median of ``--reps``),
the compiler's temporaries, the pairs held here.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument("--reps", type=int, default=20)
  parser.add_argument("--tokens", type=int, default=8192)
  parser.add_argument("--impl", default="gmm")
  args = parser.parse_args()
  import jax
  import jax.numpy as jnp
  from jax import lax
  from kf_benchmarks_tpu.parallel import expert

  n, k, d, f, g, e = args.tokens, 4, 2048, 1536, 8, 64
  rows = expert.compact_rows(n * k, g, e)
  keys = jax.random.split(jax.random.PRNGKey(0), 5)
  x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
  router = 0.02 * jax.random.normal(keys[1], (d, e))
  w = [0.02 * jax.random.normal(key, shape) for key, shape in zip(
      keys[2:], [(g, d, f), (g, d, f), (g, f, d)])]

  def route(x, router, bias):
    return expert.route_topk(x, router, bias, k, 1.8)[:2]

  def program(rows, x, router, bias, *w):
    weights, idx = route(x, router, bias)
    return expert.held_experts_ffn(x, weights, idx, *w, first_expert=0,
                                   impl=args.impl, rows=rows)[0]

  def tier(size):
    return lambda plan, x, pair_w, *w: expert.experts_round(
        0, x, pair_w, *w, plan, size, args.impl)[0]
  compact, whole = tier(rows), tier(n * k)

  def operands_of(x, router, bias, *w):
    """(pairs fit one round, the operands of both tiers)."""
    weights, idx = route(x, router, bias)
    plan, held = expert.sort_pairs(idx, 0, g)
    pair_w = jnp.where(held, weights, 0).astype(jnp.float32)
    return plan.ends[-1] <= rows, (
        plan, x, pair_w) + tuple(m.astype(x.dtype) for m in w)

  def cond_autodiff(x, router, bias, *w):
    fits, operands = operands_of(x, router, bias, *w)
    return lax.cond(fits, compact, whole, *operands).astype(x.dtype)

  @jax.custom_vjp
  def both(fits, *operands):
    return lax.cond(fits, compact, whole, *operands)

  def both_fwd(fits, *operands):
    return both(fits, *operands), (fits, operands)

  def both_bwd(res, grad):
    fits, (plan, *rest) = res
    pull = lambda tier: lambda *a: jax.vjp(
        functools.partial(tier, plan), *a)[1](grad)
    return (None, None) + tuple(lax.cond(fits, pull(compact), pull(whole),
                                         *rest))
  both.defvjp(both_fwd, both_bwd)

  def cond_custom_vjp(x, router, bias, *w):
    fits, operands = operands_of(x, router, bias, *w)
    return both(fits, *operands).astype(x.dtype)

  balanced = jnp.zeros((e,))
  forced = balanced.at[0].set(9.0)
  forms = [
      ("all_pairs", functools.partial(program, n * k), balanced),
      ("rounds", functools.partial(program, rows), balanced),
      ("cond_autodiff", cond_autodiff, balanced),
      ("cond_custom_vjp", cond_custom_vjp, balanced),
      ("rounds_two", functools.partial(program, rows), forced),
  ]
  device = jax.devices()[0]
  for name, fn, bias in forms:
    loss = lambda x, router, bias, *w: jnp.sum(jnp.sin(jax.checkpoint(fn)(
        x, router, bias, *w).astype(jnp.float32)))
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5)))
    operands = (x, router, bias) + tuple(w)
    memory = step.lower(*operands).compile().memory_analysis()
    jax.block_until_ready(step(*operands))
    times = []
    for _ in range(args.reps):
      t0 = time.perf_counter()
      jax.block_until_ready(step(*operands))
      times.append(1e3 * (time.perf_counter() - t0))
    _, idx = route(x, router, bias)
    print(json.dumps({
        "form": name, "ms": statistics.median(times), "min_ms": min(times),
        "temp_bytes": memory.temp_size_in_bytes,
        "pairs_here": int(jnp.sum(idx < g)), "rows": rows,
        "platform": device.platform, "device_kind": device.device_kind}),
          flush=True)


if __name__ == "__main__":
  main()
