#!/usr/bin/env python
"""FSDP gather-in-loop vs replicated params: the n=8 CPU A/B.

Measures the SAME small scanned-transformer training config with
--shard_optimizer_state alone (params replicated between steps, the
round-11 steady state) and with --shard_params (full FSDP: params live
as 1/n shard stacks and each scan iteration re-assembles ONE block
inside the loop body, ops/sharded.py gather_params), with
utils.sync.drain() at every window boundary and differential K-step
timing.

Reported per arm: steady-state per-device param bytes (the FSDP memory
claim), step wall, and -- for the FSDP arm -- the gather-overlap
fraction from observability.collective_overlap_stats: the share of the
program's collective bytes issued INSIDE loop bodies, i.e. the
per-block gathers/scatters the scheduler can overlap with the
neighbouring blocks' compute (the one-slot-ahead position the
custom_vjp hook earns).

CPU-mesh caveat, on record: on 8
virtual CPU devices collectives are memcpy-speed and XLA:CPU does not
run compute and collectives concurrently, so the wall A/B bounds the
OVERHEAD of the gather machinery rather than demonstrating wall-clock
overlap; the overlap win itself needs the chip's asynchronous ICI
collectives. Chip rows of PERF.md round 15: not measured. The
compiled-HLO structure the
win rides on -- one packed gather per block inside the while body, no
full-tree re-assembly -- is pinned by tests/test_fsdp.py and the
fsdp_* golden contracts.

Usage: python experiments/fsdp_gather_probe.py [steps]
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
import numpy as np  # noqa: E402
import flax.linen as nn  # noqa: E402
import optax  # noqa: E402

# The device is whatever JAX reports here (JAX_PLATFORMS=cpu gives the
# 8-virtual-device mesh; a TPU host gives its chips) -- asked, printed,
# and used for every mesh below.
DEVICE = jax.devices()[0]
PLATFORM = DEVICE.platform
print(f"probe device: platform={PLATFORM} device_kind={DEVICE.device_kind} "
      f"count={len(jax.devices())}", file=sys.stderr, flush=True)

from kf_benchmarks_tpu import benchmark  # noqa: E402
from kf_benchmarks_tpu import params as params_lib  # noqa: E402
from kf_benchmarks_tpu import train_step as train_step_lib  # noqa: E402
from kf_benchmarks_tpu.ops import sharded as sharded_lib  # noqa: E402
from kf_benchmarks_tpu.parallel import mesh as mesh_lib  # noqa: E402
from kf_benchmarks_tpu.parallel import strategies  # noqa: E402
from kf_benchmarks_tpu.utils import sync  # noqa: E402
from kf_benchmarks_tpu import observability  # noqa: E402

VOCAB, D_MODEL, N_LAYERS, D_FF = 256, 64, 6, 256
BATCH, SEQ = 4, 32


class _Block(nn.Module):
  @nn.compact
  def __call__(self, carry, _):
    x, seg = carry
    h = nn.LayerNorm(name="ln")(x)
    h = nn.gelu(nn.Dense(D_FF, name="up")(h))
    x = x + nn.Dense(D_MODEL, name="down")(h)
    return (x, seg), None


class _ScannedLM(nn.Module):
  fsdp_block_hook: object = None

  @nn.compact
  def __call__(self, tokens):
    x = nn.Embed(VOCAB, D_MODEL, name="embed")(tokens.astype(jnp.int32))
    block_cls = _Block
    if self.fsdp_block_hook is not None:
      block_cls = nn.map_variables(
          _Block, "params", trans_in_fn=self.fsdp_block_hook, init=True)
    blocks = nn.scan(nn.remat(block_cls, prevent_cse=False),
                     variable_axes={"params": 0},
                     split_rngs={"params": True},
                     length=N_LAYERS)(name="blocks")
    (x, _), _ = blocks((x, None), None)
    return nn.Dense(VOCAB, name="head")(x), None


class _ProbeModel:
  """Minimal model surface for make_step_fns (the probe's unit)."""

  def __init__(self, fsdp: bool):
    self.fsdp_gathered_prefixes = ("blocks",) if fsdp else ()
    hook = None
    if fsdp:
      vs = jax.eval_shape(
          lambda: _ScannedLM().init(
              {"params": jax.random.PRNGKey(0),
               "dropout": jax.random.PRNGKey(0)},
              jnp.zeros((BATCH, SEQ), jnp.int32)))
      block_template = jax.tree.map(
          lambda s: jax.ShapeDtypeStruct(tuple(s.shape)[1:], s.dtype),
          vs["params"]["blocks"])
      hook = sharded_lib.fsdp_block_gatherer(
          block_template, mesh_lib.BATCH_AXIS, mesh_lib.MODEL_AXIS)
    self.module = _ScannedLM(fsdp_block_hook=hook)

  def get_name(self):
    return "fsdp_probe_lm"

  def get_input_shapes(self, subset):
    return [[BATCH, SEQ], [BATCH, SEQ]]

  def get_input_data_types(self, subset):
    return [jnp.int32, jnp.int32]

  def get_fp16_loss_scale(self):
    return 1.0

  def loss_function(self, result, labels):
    logits = result.logits[0]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                             -1)
    return -jnp.mean(ll)

  def accuracy_function(self, result, labels):
    return {}


def build_arm(fsdp: bool):
  mesh = mesh_lib.build_mesh_2d(8, 1, PLATFORM)
  model = _ProbeModel(fsdp)
  kw = dict(model="trivial", device=PLATFORM, num_devices=8,
            shard_optimizer_state=True, optimizer="momentum",
            weight_decay=0.0, init_learning_rate=0.05)
  if fsdp:
    kw["shard_params"] = True
  p = params_lib.make_params(**kw)
  strategy = strategies.get_strategy(p)
  tx = optax.sgd(0.05, momentum=0.9)
  init_state, train_step, _, _, _ = train_step_lib.make_step_fns(
      model, model.module, model.module, strategy, tx,
      lambda step: jnp.float32(0.05), p, mesh, total_train_steps=64)
  state = init_state(jax.random.PRNGKey(0),
                     jnp.zeros((BATCH, SEQ), jnp.int32))
  tokens = jax.random.randint(jax.random.PRNGKey(1), (8 * BATCH, SEQ),
                              0, VOCAB, jnp.int32)
  labels = jnp.roll(tokens, -1, axis=1)
  return state, train_step, (tokens, labels)


def time_arm(state, step, batch, steps):
  state, m = step(state, *batch)  # compile + warm
  sync.drain(m["base_loss"])
  t0 = time.time()
  for _ in range(steps):
    state, m = step(state, *batch)
  sync.drain(m["base_loss"])
  return (time.time() - t0) / steps, state


def main():
  steps = int(sys.argv[1]) if len(sys.argv) > 1 else 32
  out = []
  for fsdp in (False, True):
    state, step, batch = build_arm(fsdp)
    wall, state = time_arm(state, step, batch, steps)
    row = {
        "arm": "shard_params" if fsdp else "shard_optimizer_state_only",
        "step_wall_s": round(wall, 6),
        "param_bytes_per_device": benchmark.opt_state_bytes_per_device(
            state.params),
    }
    hlo = step.lower(state, *batch).compile().as_text()
    stats = observability.collective_overlap_stats(hlo)
    row["collective_overlap"] = {
        "num_collectives": stats["num_collectives"],
        "overlap_fraction": round(stats["overlap_fraction"], 4),
    }
    if fsdp:
      print(observability.overlap_fraction_line(hlo))
    out.append(row)
    print(json.dumps(row), flush=True)
  a, b = out
  print(json.dumps({
      "metric": "fsdp_gather_probe",
      "steps": steps,
      "param_bytes_ratio": round(
          b["param_bytes_per_device"] /
          max(a["param_bytes_per_device"], 1), 4),
      "step_wall_ratio": round(
          b["step_wall_s"] / max(a["step_wall_s"], 1e-9), 4),
      "gather_overlap_fraction":
          b["collective_overlap"]["overlap_fraction"],
  }), flush=True)


if __name__ == "__main__":
  main()
