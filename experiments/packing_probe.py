"""Packed vs padded variable-length LM input: the CPU A/B behind
PERF.md round 13 (--packed_sequences).

The claim under test: at a fixed (B, T) step program, useful-tokens/s
scales with packing efficiency -- the padded one-document-per-row feed
wastes (1 - mean_len/T) of every step on masked slots, and first-fit
packing recovers it. Both arms run the SAME segment-aware program
(masks, weighted loss, token-weighted metrics) over the SAME seeded
document distribution on the 8-virtual-device CPU mesh; only the
packer's row-filling policy differs, so the useful-tokens/s ratio
isolates exactly what packing buys. The DeviceFeeder's consumer stats
ride along: feed_stall_fraction ~0 proves the host-side packing work
overlapped the step (the prefetch-overlap half of the round-13 claim).

Run from the repo root (~2 min):

    python experiments/packing_probe.py [--steps 24] [--batch 2]
        [--seq_len 512] [--impl tiled]

Prints a markdown table + one JSON line per arm. Timing uses
utils.sync.drain() at window boundaries: whole timed window over N
steps, warmup excluded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Append (not setdefault): pre-existing XLA_FLAGS must not silently
# drop the 8-device forcing (same recipe as the sibling probes).
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""):
  os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                             " --xla_force_host_platform_device_count=8"
                             ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from kf_benchmarks_tpu import params as params_lib  # noqa: E402
from kf_benchmarks_tpu import train_step as train_step_lib  # noqa: E402
from kf_benchmarks_tpu.data import device_feed  # noqa: E402
from kf_benchmarks_tpu.data import packing  # noqa: E402
from kf_benchmarks_tpu.models import transformer_lm as lm  # noqa: E402
from kf_benchmarks_tpu.parallel import mesh as mesh_lib  # noqa: E402
from kf_benchmarks_tpu.parallel import strategies  # noqa: E402
from kf_benchmarks_tpu.utils import sync  # noqa: E402

VOCAB = 1024


class _ProbeLM(lm.TransformerLMModel):
  """The packed transformer_lm contract at probe scale (full-size
  compiles take minutes on the CPU mesh; the packing win is a property
  of the INPUT form, not the model width)."""

  def __init__(self, seq_len: int, batch: int, params=None):
    super().__init__(params=params)
    self.seq = seq_len
    self.set_batch_size(batch)

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32):
    del nclass, data_format
    impl = os.environ.get("KF_TRANSFORMER_LM_ATTN", "tiled")
    return lm._TransformerLMModule(
        vocab=VOCAB, d_model=128, n_layers=2, n_heads=4, d_ff=256,
        attn_block=128, attn_q_block=128, max_len=self.seq,
        attn_impl=impl, dtype=dtype, param_dtype=param_dtype)

  def get_input_shapes(self, subset):
    n = self.get_batch_size()
    return [[n, 3, self.seq], [n, self.seq]]


def run_arm(name: str, one_per_row: bool, steps: int, batch: int,
            seq_len: int, warmup: int = 3, seed: int = 13):
  import optax
  p = params_lib.make_params(
      device="cpu", num_devices=8, batch_size=batch,
      model="transformer_lm", packed_sequences=True, weight_decay=0.0)
  model = _ProbeLM(seq_len, batch, params=p)
  module = model.make_module(0, True)
  mesh = mesh_lib.build_mesh(8, "cpu")
  fns = train_step_lib.make_step_fns(
      model, module, module, strategies.get_strategy(p),
      optax.sgd(0.05), lambda s: jnp.float32(0.05), p, mesh)
  init_state, train_step = fns[0], fns[1]
  global_batch = 8 * batch
  stream = packing.PackedBatchStream(seq_len, global_batch, VOCAB,
                                     seed=seed, one_per_row=one_per_row)
  feeder = device_feed.DeviceFeeder(stream,
                                    mesh_lib.batch_sharding(mesh),
                                    prefetch=3)
  state = init_state(jax.random.PRNGKey(0),
                     jnp.zeros((batch, 3, seq_len), jnp.int32))
  it = iter(feeder)
  try:
    fractions = []
    for i in range(warmup + steps):
      images, labels = next(it)
      state, metrics = train_step(state, images, labels)
      if i == warmup - 1:
        sync.drain(metrics)
        t0 = time.monotonic()
      if i >= warmup:
        # Async handles only: a per-step float() readback here would
        # serialize the loop on each step's completion and hand the
        # feeder a free step of idle wall every iteration -- the
        # stall fraction would read ~0 by harness construction. Values
        # are fetched AFTER the timed window instead.
        fractions.append(metrics["real_token_fraction"])
    sync.drain(metrics)
    wall = time.monotonic() - t0
    feed = feeder.stats()
    # Real label positions per step (the loss denominator), read back
    # outside the timed window.
    useful = sum(float(f) for f in fractions) * global_batch * seq_len
  finally:
    feeder.stop()
  pack = stream.stats()
  return {
      "arm": name,
      "steps": steps,
      "wall_s": round(wall, 3),
      "steps_per_s": round(steps / wall, 3),
      "slot_tokens_per_s": round(steps * global_batch * seq_len / wall, 1),
      "useful_tokens_per_s": round(useful / wall, 1),
      "packing_efficiency": round(pack["packing_efficiency"], 4),
      "feed_stall_fraction": (round(feed["feed_stall_fraction"], 4)
                              if feed["feed_stall_fraction"] is not None
                              else None),
      "queue_depth_mean": round(feed["queue_depth_mean"], 2),
  }


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--steps", type=int, default=24)
  ap.add_argument("--batch", type=int, default=2)
  ap.add_argument("--seq_len", type=int, default=512)
  ap.add_argument("--impl", default="tiled", choices=("tiled", "flash"))
  args = ap.parse_args()
  os.environ["KF_TRANSFORMER_LM_ATTN"] = args.impl

  padded = run_arm("padded_one_doc_per_row", True, args.steps,
                   args.batch, args.seq_len)
  packed = run_arm("packed_first_fit", False, args.steps, args.batch,
                   args.seq_len)

  eff_ratio = (packed["packing_efficiency"] /
               padded["packing_efficiency"])
  gain = (packed["useful_tokens_per_s"] /
          max(padded["useful_tokens_per_s"], 1e-9))
  print("\n| arm | packing eff | useful tok/s | slot tok/s | "
        "steps/s | feed stall |")
  print("|---|---|---|---|---|---|")
  for r in (padded, packed):
    print("| %s | %.1f%% | %.0f | %.0f | %.2f | %.2f%% |" % (
        r["arm"], 100 * r["packing_efficiency"],
        r["useful_tokens_per_s"], r["slot_tokens_per_s"],
        r["steps_per_s"], 100 * (r["feed_stall_fraction"] or 0.0)))
  print("\nuseful-tokens/s gain: %.3fx; packing-efficiency ratio: "
        "%.3fx; gain/ratio = %.3f (claim: within 10%% of 1.0)"
        % (gain, eff_ratio, gain / eff_ratio))
  for r in (padded, packed):
    print(json.dumps(r))
  print(json.dumps({"metric": "packing_useful_tokens_gain",
                    "value": round(gain, 3),
                    "efficiency_ratio": round(eff_ratio, 3),
                    "impl": args.impl, "seq_len": args.seq_len,
                    "global_batch": 8 * args.batch}))


if __name__ == "__main__":
  main()
