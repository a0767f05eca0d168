#!/usr/bin/env python
"""Overlapped vs post-hoc gradient reduction: the n=8 step-time A/B.

Measures the SAME training config with --overlap_gradient_reduction off
and on (several bucket sizes), with utils.sync.drain() at every window
boundary. Two arms:

  * the step arm times raw train_step dispatches of an MLP-family
    config where the gradient tree has real layer structure (the
    bucket planner's unit of work);
  * the scanned-LM arm times a small transformer_lm whose per-block
    hooks put the collective INSIDE the backward scan body
    (models/transformer_lm.py nn.map_variables hook).

CPU-mesh caveat, on record: on 8 virtual CPU devices the collectives
are memcpy-speed and the XLA CPU scheduler does not run compute and
collectives concurrently, so the A/B bounds the OVERHEAD of the hook
machinery (packing, custom_vjp, per-bucket issue) rather than
demonstrating wall-clock overlap; the overlap win itself needs the
chip's asynchronous ICI collectives. The chip rows of PERF.md round 8:
not measured. The compiled-HLO
structure the win rides on -- one collective per bucket inside the
backward loop body -- is asserted by tests/test_overlap_reduction.py
and reported here via observability.collective_overlap_stats.

Usage: python experiments/overlap_reduction_probe.py [steps]
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
import optax  # noqa: E402
import flax.linen as nn  # noqa: E402

# The device is whatever JAX reports here (JAX_PLATFORMS=cpu gives the
# 8-virtual-device mesh; a TPU host gives its chips) -- asked, printed,
# and used for every mesh below.
DEVICE = jax.devices()[0]
PLATFORM = DEVICE.platform
print(f"probe device: platform={PLATFORM} device_kind={DEVICE.device_kind} "
      f"count={len(jax.devices())}", file=sys.stderr, flush=True)

from kf_benchmarks_tpu import observability  # noqa: E402
from kf_benchmarks_tpu import params as params_lib  # noqa: E402
from kf_benchmarks_tpu import train_step as train_step_lib  # noqa: E402
from kf_benchmarks_tpu import validation  # noqa: E402
from kf_benchmarks_tpu.models import transformer_lm  # noqa: E402
from kf_benchmarks_tpu.models.model import Model  # noqa: E402
from kf_benchmarks_tpu.ops import fused_loss  # noqa: E402
from kf_benchmarks_tpu.parallel import strategies  # noqa: E402
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS, build_mesh  # noqa: E402
from kf_benchmarks_tpu.utils import sync  # noqa: E402

N = 8


class _ProbeMLP(nn.Module):
  """8 x 1024-wide layers: ~9.5 MB of f32 gradients across real layer
  groups, so the default 4 MB bound yields several buckets."""

  width: int = 1024
  depth: int = 8

  @nn.compact
  def __call__(self, x):
    for i in range(self.depth):
      x = nn.tanh(nn.Dense(self.width, name=f"layer{i}")(x))
    return nn.Dense(16, name="head")(x), None


class _ProbeModel(Model):

  def __init__(self, params=None):
    super().__init__("probe_mlp", 16, 0.05, params=params)

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32):
    return _ProbeMLP()

  def loss_function(self, result, labels):
    logits, _ = result.logits
    one_hot = jax.nn.one_hot(labels, logits.shape[-1])
    return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * one_hot, -1))

  def accuracy_function(self, result, labels):
    return {"top_1_accuracy": jnp.float32(0)}


def build_step(overlap, bucket_mb=None):
  kw = dict(device=PLATFORM, num_devices=N, optimizer="momentum",
            overlap_gradient_reduction=overlap)
  if bucket_mb is not None:
    kw["reduce_bucket_mb"] = bucket_mb
  p = params_lib.make_params(**kw)
  validation.validate_cross_flags(p)
  model = _ProbeModel(params=p)
  module = model.make_module(16, True)
  mesh = build_mesh(N, PLATFORM)
  fns = train_step_lib.make_step_fns(
      model, module, module, strategies.get_strategy(p),
      optax.sgd(0.05, momentum=0.9), lambda s: jnp.float32(0.05), p, mesh)
  init_state, train_step = fns[0], fns[1]
  rng = jax.random.PRNGKey(0)
  x = jax.random.normal(rng, (N * 4, 1024), jnp.float32)
  y = jax.random.randint(rng, (N * 4,), 0, 16)
  state = jax.jit(init_state)(rng, x[:1])
  return state, train_step, (x, y)


def time_arm(state, step, batch, steps):
  state, metrics = step(state, *batch)  # compile + warm
  sync.drain(metrics)
  start = time.monotonic()
  for _ in range(steps):
    state, metrics = step(state, *batch)
  sync.drain(metrics)
  return (time.monotonic() - start) / steps


def lm_arm(hooked, steps):
  """Small scanned transformer_lm through raw shard_map grads (the
  per-block in-backward hook vs trailing post-hoc pmean)."""
  from jax.sharding import Mesh, PartitionSpec as P
  mesh = Mesh(np.array(jax.devices()[:N]), (REPLICA_AXIS,))
  cfg = dict(vocab=512, d_model=128, n_layers=6, n_heads=8, d_ff=512,
             attn_block=64, max_len=256, scan_layers=True)
  module = transformer_lm._TransformerLMModule(
      grad_reduce_axis=REPLICA_AXIS if hooked else None, **cfg)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (N * 2, 256), 0, 512)
  labels = jnp.roll(tokens, -1, axis=1)
  params = module.init({"params": jax.random.PRNGKey(1)},
                       tokens[:1])["params"]

  def body(p, toks, lbls):
    def loss(q):
      out, _ = module.apply({"params": q}, toks)
      return fused_loss.fused_softmax_xent(out.hidden, out.kernel, lbls,
                                           chunk_size=64)

    g = jax.grad(loss)(p)
    if not hooked:
      g = jax.tree.map(lambda t: jax.lax.pmean(t, REPLICA_AXIS), g)
    return g

  fn = jax.jit(jax.shard_map(
      body, mesh=mesh,
      in_specs=(P(), P(REPLICA_AXIS), P(REPLICA_AXIS)),
      out_specs=P(), check_vma=False))
  g = fn(params, tokens, labels)  # compile + warm
  sync.drain(jax.tree.leaves(g)[0])
  start = time.monotonic()
  for _ in range(steps):
    g = fn(params, tokens, labels)
  sync.drain(jax.tree.leaves(g)[0])
  per_step = (time.monotonic() - start) / steps
  hlo = fn.lower(params, tokens, labels).compile().as_text()
  return per_step, observability.collective_overlap_stats(hlo)


def main():
  steps = int(sys.argv[1]) if len(sys.argv) > 1 else 30
  print(f"# Overlap-reduction probe: n={N} virtual CPU mesh, "
        f"{steps} timed steps/arm")
  rows = []

  print("\n## MLP step arm (9.5 MB grads, builder-layer buckets)")
  print("| arm | bucket MB | step ms |")
  print("|---|---|---|")
  for label, overlap, mb in (("post-hoc", False, None),
                             ("overlap", True, 1),
                             ("overlap", True, 4),
                             ("overlap", True, 64)):
    state, step, batch = build_step(overlap, mb)
    ms = time_arm(state, step, batch, steps) * 1e3
    rows.append({"arm": label, "family": "mlp", "bucket_mb": mb,
                 "step_ms": round(ms, 3)})
    print(f"| {label} | {mb if mb else '-'} | {ms:.3f} |")

  print("\n## scanned transformer_lm arm (per-block in-backward hook)")
  print("| arm | step ms | collectives | % in backward loop |")
  print("|---|---|---|---|")
  for label, hooked in (("post-hoc", False), ("overlap", True)):
    ms, stats = lm_arm(hooked, steps)
    ms *= 1e3
    rows.append({"arm": label, "family": "transformer_lm",
                 "step_ms": round(ms, 3),
                 "collectives": stats["num_collectives"],
                 "overlap_fraction": round(stats["overlap_fraction"], 3)})
    print(f"| {label} | {ms:.3f} | {stats['num_collectives']} | "
          f"{100 * stats['overlap_fraction']:.1f}% |")

  print()
  print(json.dumps({"metric": "overlap_reduction_probe", "n": N,
                    "steps": steps, "rows": rows}))


if __name__ == "__main__":
  main()
