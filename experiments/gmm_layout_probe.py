#!/usr/bin/env python3
"""One mixture layer's grouped products alone, at the four language-model
cells' shapes, on the chip: what the layout of the sorted rows and the
row tile cost (PERF.md section 6). The products of
``expert_hidden`` and ``w_down`` over one round of ``compact_rows`` rows,
forward and forward + backward, bfloat16, with the groups

* ``packed``: end to end, as the sort leaves them (each expert's rows
  start where the previous expert's ended, so megablox visits a row tile
  once for every group that touches it);
* ``aligned``: every held expert's rows from a multiple of the row tile
  (``sort_pairs(..., tile)``), padding rows between them;

each at row tiles of 128, 256 and 512 (``--forms packed:512
aligned:256 ...`` for fewer). The choices are drawn from each of ``--seeds`` with
the experts' popularity lognormal, exp(s x z), s set so that the
largest load over the mean matches the cell's ``moe_load_max_over_mean``
(1.35 / 4.34 / 2.62 / 3.90): the held experts' loads
spread as the cells' do (``--balanced``: uniform choices, loads within a
few percent of each other, which hand a tile of 512 one expert's 384
rows exactly and so flatter it).

    python3 experiments/gmm_layout_probe.py [--reps 20] [--cells xing ...]
        [--seeds 0 1] [--forms packed:512 aligned:256 aligned:512]

Prints one JSON line a (cell, layout, tile): milliseconds a call (median
of ``--reps``) forward and forward + backward, the rows the kernel's
row tiles cover over the pairs held here (``rows_in_tiles``), the tile
``row_tile`` chooses for the cell.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell: (tokens, k, experts, held, hidden, expert width, gated, s of the
# experts' lognormal popularity for the cell's load max over mean)
CELLS = {
    "xing": (4096, 4, 64, 8, 3584, 1024, True, 0.12),
    "trinity-mini": (8192, 8, 128, 16, 2048, 1024, True, 0.73),
    "glm": (8192, 4, 64, 8, 2048, 1536, True, 0.46),
    "nemotron": (8192, 6, 128, 8, 2688, 1856, False, 0.65),
}
FORMS = [f"{layout}:{tile}" for layout in ("packed", "aligned")
         for tile in (128, 256, 512)]


def timed(fn, operands, reps):
  import jax
  jax.block_until_ready(fn(*operands))
  times = []
  for _ in range(reps):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*operands))
    times.append(1e3 * (time.perf_counter() - t0))
  return statistics.median(times)


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument("--reps", type=int, default=20)
  parser.add_argument("--seeds", type=int, nargs="+", default=[0])
  parser.add_argument("--cells", nargs="+", default=list(CELLS))
  parser.add_argument("--forms", nargs="+", default=FORMS)
  parser.add_argument("--balanced", action="store_true")
  parser.add_argument("--forward", action="store_true",
                      help="time the forward alone too")
  parser.add_argument("--impl", default="gmm")
  args = parser.parse_args()
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.parallel import expert

  device = jax.devices()[0]
  for cell, seed in [(c, s) for c in args.cells for s in args.seeds]:
    n, k, e, g, d, f, gated, spread = CELLS[cell]
    rows = expert.compact_rows(n * k, g, e)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    popular = 0.0 if args.balanced else spread * jax.random.normal(
        keys[5], (e,))
    idx = jax.lax.top_k(popular + jax.random.gumbel(keys[0], (n, e)), k)[1]
    load = jnp.sum(jax.nn.one_hot(idx.reshape(-1), e), 0)
    w = [(0.02 * jax.random.normal(key, shape)).astype(jnp.bfloat16)
         for key, shape in zip(keys[1:4], [(g, d, f), (g, d, f), (g, f, d)])]
    if not gated:
      w[0] = None
    activation = "silu" if gated else "relu2"
    for layout, tile in (form.split(":") for form in args.forms):
      tile = int(tile)
      plan, held = expert.sort_pairs(idx, 0, g,
                                     tile if layout == "aligned" else 1)
      ends = jnp.clip(plan.ends, 0, rows)
      sizes = jnp.diff(ends, prepend=0)
      live = plan.key[:rows] < g
      xs = jnp.where(live[:, None], jax.random.normal(
          keys[4], (rows, d), jnp.bfloat16), 0)

      def products(xs, *w):
        product = lambda r, m: expert.grouped_matmul(r, m, sizes, live,
                                                     args.impl, tile)
        return product(expert.expert_hidden(
            xs, w[0], w[1], product, activation), w[2])
      loss = lambda xs, *w: jnp.sum(products(xs, *w).astype(jnp.float32))
      wanted = (0, 2, 3) if w[0] is None else (0, 1, 2, 3)
      forward = jax.jit(products)
      both = jax.jit(jax.value_and_grad(loss, argnums=wanted))
      tm = expert.gmm_tiling(tile, d, f)[0]
      pairs = int(jnp.sum(held))
      print(json.dumps({
          "cell": cell, "seed": seed, "layout": layout, "tile": tile,
          "forward_ms": (timed(forward, [xs] + w, args.reps)
                         if args.forward else None),
          "forward_backward_ms": timed(both, [xs] + w, args.reps),
          "pairs_here": pairs, "rows": rows,
          "load_max_over_mean": float(load.max() / load.mean()),
          "held_loads": [int(v) for v in load[:g]],
          "held_end": int(plan.ends[-1]),
          "rows_per_pair": int(expert.rows_in_tiles(ends, tm)) / pairs,
          "row_tile_rule": expert.row_tile(n * k, g, e),
          "platform": device.platform, "device_kind": device.device_kind}),
            flush=True)


if __name__ == "__main__":
  main()
