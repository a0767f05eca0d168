"""Long-context attention on the real chip: blockwise (flash-style)
vs full attention across sequence lengths.

The claim under test (parallel/sequence.py): the online-softmax
blockwise schedule keeps peak memory O(L * block) so context lengths
that are impossible for full attention's (L, L) score tensor train on
one chip -- the single-device leg of the framework's long-context
design (ring_attention is the multi-chip leg; its schedule is this one
plus ppermute).

Method: one process; differential timing -- scan K attention calls
inside one jit, fetch a scalar, and difference two K values so the
per-dispatch host cost cancels; nothing else runs on the host during
the window.

    python experiments/long_context_probe.py [--dtype bf16]

Prints a markdown table (ms/step and tokens/s per L, both arms) for
PERF.md.

``--grad`` times forward AND backward (the gradient for q, k and v),
and ``--heads`` / ``--head_dim`` set the shape: the attention core of
the glm-4.7-flash cell alone, one layer of it, is

    python experiments/long_context_probe.py --impls flash --grad \
        --lengths 4096 --batch 2 --heads 20 --head_dim 256

``--kv_heads`` (fewer key heads than query heads), ``--window`` (a
causal band) and ``--tiles Q KV HELD`` (the forward's tile and the keys
the backward holds, in place of ``flash_plan``'s own choice) shape the
``flash`` arm alone: a window layer of the trinity-mini cell is

    python experiments/long_context_probe.py --impls flash --grad \
        --lengths 8192 --heads 32 --kv_heads 4 --head_dim 128 --window 2048
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from kf_benchmarks_tpu.parallel import sequence

H, D = 8, 128
BLOCK = 512  # default; --block overrides


def make_rep(impl, l, dtype, block=BLOCK, batch=1, q_block=None,
             heads=H, head_dim=D, grad=False, kv_heads=None, window=None,
             tiles=None):
  ks = jax.random.split(jax.random.PRNGKey(0), 3)
  q, k, v = (jax.random.normal(kk, (batch, l, n, head_dim), dtype)
             for kk, n in zip(ks, (heads, kv_heads or heads,
                                   kv_heads or heads)))
  if impl != "flash" and (kv_heads or window or tiles):
    raise ValueError("--kv_heads, --window and --tiles shape the flash arm")

  if impl == "full":
    attn = lambda q, k, v: sequence.full_attention(q, k, v, causal=True)
  elif impl == "tiled":
    # Two-level q x kv tiling: block-sized accumulators + causal skip
    # of strictly-future K/V blocks (the round-5 MFU work).
    attn = lambda q, k, v: sequence.blockwise_attention(
        q, k, v, block_size=block, causal=True,
        q_block_size=block if q_block is None else q_block)
  elif impl == "flash":
    # The hand-tiled Pallas kernel (TPU-only) -- measures what XLA's
    # scan lowering leaves on the table, if anything. --block sets the
    # kernel's q/k tiles so the A/B against tiled/blockwise compares
    # matched tilings (one shared BlockSizes builder in sequence.py).
    plan = None
    if tiles:
      block_q, block_kv, held = tiles
      plan = sequence.FlashPlan(1, min(block, l), block_q, block_kv, held,
                                l // held)
    attn = lambda q, k, v: sequence.pallas_flash_attention(
        q, k, v, causal=True, block=block, window=window, plan=plan)
  else:
    attn = lambda q, k, v: sequence.blockwise_attention(
        q, k, v, block_size=block, causal=True)

  def gradients(q, k, v):
    # All three gradients feed the next query, so none is dead code.
    dq, dk, dv = jax.grad(lambda *a: jnp.sum(
        attn(*a).astype(jnp.float32)) * 1e-3, (0, 1, 2))(q, k, v)
    # (A scalar of dk and dv: they have the key heads' shape.)
    return q + dq + (jnp.mean((dk + dv).astype(jnp.float32)) * 1e-6).astype(
        q.dtype)

  @functools.partial(jax.jit, static_argnums=(3,))
  def rep(q, k, v, reps):
    def body(c, _):
      out = gradients(c, k, v) if grad else attn(c, k, v)
      # Feed the output back as the next query so the scan chains on
      # the device (nothing constant-folds away).
      return out, None
    y, _ = jax.lax.scan(body, q, None, length=reps)
    return jnp.sum(y.astype(jnp.float32))

  return rep, (q, k, v)


def _reps_for(l):
  """(small, big, iters): one attention call at L=32k runs ~10 s of MXU
  work, so the chained-rep counts shrink as L grows to keep each arm's
  wall time bounded while the differential still cancels the RTT."""
  if l >= 16384:
    return 1, 3, 2
  return 2, 10, 4


def sync_time(f, args, reps, iters):
  float(f(*args, reps))
  ts = []
  for _ in range(iters):
    t0 = time.time()
    float(f(*args, reps))
    ts.append(time.time() - t0)
  return min(ts)


def measure(impl, l, dtype, block=BLOCK, batch=1, q_block=None, **shape):
  reps_small, reps_big, iters = _reps_for(l)
  rep, args = make_rep(impl, l, dtype, block, batch, q_block, **shape)
  t_small = sync_time(rep, args, reps_small, iters)
  t_big = sync_time(rep, args, reps_big, iters)
  return (t_big - t_small) / (reps_big - reps_small)


def causal_tflops(l, batch, heads=H, head_dim=D, grad=False, **_):
  """Useful (unmasked) causal attention FLOPs: 2 matmuls x B H L^2/2 D
  MACs x 2 flops/MAC; with the backward, the 7 the mathematics needs
  (scores, weighted values; the probabilities' gradient, dq, dk, dv, and
  the scores once more, since no schedule keeps them)."""
  products = 7 if grad else 2
  return (products * 2 * batch * heads * (l * l / 2) * head_dim / 1e12)


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
  ap.add_argument("--lengths", type=int, nargs="+",
                  default=[2048, 4096, 8192, 16384, 32768, 65536])
  ap.add_argument("--block", type=int, default=BLOCK)
  ap.add_argument("--q_block", type=int, default=None)
  ap.add_argument("--batch", type=int, nargs="+", default=[1])
  ap.add_argument("--impls", nargs="+",
                  choices=["full", "blockwise", "tiled", "flash"],
                  default=["full", "blockwise", "tiled"])
  ap.add_argument("--heads", type=int, default=H)
  ap.add_argument("--head_dim", type=int, default=D)
  ap.add_argument("--grad", action="store_true",
                  help="time forward and backward, not forward alone")
  ap.add_argument("--kv_heads", type=int, default=None)
  ap.add_argument("--window", type=int, default=None)
  ap.add_argument("--tiles", type=int, nargs=3, default=None,
                  metavar=("Q", "KV", "HELD"))
  args = ap.parse_args()
  shape = dict(heads=args.heads, head_dim=args.head_dim, grad=args.grad,
               kv_heads=args.kv_heads, window=args.window, tiles=args.tiles)
  dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32

  print(f"devices: {jax.devices()}")
  rows = []
  for batch in args.batch:
    for l in args.lengths:
      row = {"L": l, "B": batch}
      for impl in args.impls:
        try:
          dt = measure(impl, l, dtype, args.block, batch, args.q_block,
                       **shape)
          row[impl] = dt
          print(f"B={batch} L={l} {impl}: {dt*1e3:.2f} ms "
                f"({batch*l/dt:,.0f} tok/s, "
                f"{causal_tflops(l, batch, **shape)/dt:.1f} TFLOP/s eff)",
                flush=True)
        except Exception as e:  # noqa: BLE001 -- OOM is an expected arm
          row[impl] = None
          print(f"B={batch} L={l} {impl}: FAILED ({type(e).__name__}: "
                f"{str(e)[:120]})", flush=True)
      rows.append(row)

  print(f"\nH={args.heads} D={args.head_dim} block={args.block} q_block="
        f"{args.q_block or args.block} dtype={args.dtype}, causal, "
        f"{'forward + backward' if args.grad else 'forward'}")
  hdr = " | ".join(f"{i} ms | {i} TFLOP/s" for i in args.impls)
  print(f"| B | L | {hdr} |")
  print("|---" * (2 + 2 * len(args.impls)) + "|")
  for r in rows:
    cells = []
    for impl in args.impls:
      if r.get(impl) is None:
        cells += ["OOM", "-"]
      else:
        cells += [f"{r[impl]*1e3:.2f}",
                  f"{causal_tflops(r['L'], r['B'], **shape)/r[impl]:.1f}"]
    print(f"| {r['B']} | {r['L']} | " + " | ".join(cells) + " |")


if __name__ == "__main__":
  main()
