#!/usr/bin/env python
"""Chip smoke: the trainer's main path, once, on the accelerator.

Drives resnet50 (full width, bs 256/device, bf16, synthetic ImageNet,
random weights from the seed) through the same three calls ``cli.main``
makes -- ``make_params`` -> ``benchmark.setup`` ->
``BenchmarkCNN(params).run()`` -- for a few warm-up and timed steps, and
checks what came out: every printed step line has a finite loss, the
``total images/sec`` banner is there, the step counter and the trained
parameters are finite and as expected. With more than one chip visible
it then runs, in the SAME process, the same model over all of them
(``--variable_update=kungfu --kungfu_option=sync_sgd``) and checks that
every state leaf has one addressable shard on each device and that the
replicas agree bit-for-bit after the all-reduced steps.

One process, no children: a chip belongs to the process that first
touches JAX. Exits nonzero, printing no result line, when JAX finds no
TPU. Any img/s printed here is a smoke reading (a few cold steps), not a
measurement. The last stdout line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

WARMUP_STEPS = 5
TIMED_STEPS = 20
MULTI_TIMED_STEPS = 10

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: ([\d.]+) \+/- ([\d.]+) \(jitter = ([\d.]+)\)\t"
    r"(\S+)$")
TOTAL_RE = re.compile(r"^total images/sec: ([\d.]+)$")


def say(msg: str) -> None:
  print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str):
  raise SystemExit(f"chip_smoke: FAIL {msg}")


def run_leg(name: str, timed_steps: int, **overrides):
  """One trainer run through the CLI's three calls; returns its stats
  after checking the step lines, the banner and the stats."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util

  kwargs = dict(model="resnet50", batch_size=256, use_fp16=True,
                device="tpu", num_devices=1,
                variable_update="replicated",
                num_warmup_batches=WARMUP_STEPS, num_batches=timed_steps,
                display_every=1, tf_random_seed=1234)
  kwargs.update(overrides)
  lines = []
  orig_log = log_util.log_fn

  def tee(msg):
    lines.append(str(msg))
    orig_log(msg)

  say(f"[{name}] " + " ".join(f"--{k}={v}" for k, v in kwargs.items()))
  log_util.log_fn = tee
  t0 = time.monotonic()
  try:
    params = params_lib.make_params(**kwargs)
    params = benchmark.setup(params)
    stats = benchmark.BenchmarkCNN(params).run()
  finally:
    log_util.log_fn = orig_log
  wall = time.monotonic() - t0

  steps = [m for l in lines if (m := STEP_RE.match(l))]
  if [int(m.group(1)) for m in steps] != list(range(1, timed_steps + 1)):
    fail(f"[{name}] expected step lines 1..{timed_steps}, got "
         f"{[m.group(1) for m in steps]}")
  losses = [float(m.group(5)) for m in steps]
  if not all(math.isfinite(v) for v in losses):
    fail(f"[{name}] non-finite loss on a printed step: {losses}")
  if sum(bool(TOTAL_RE.match(l)) for l in lines) != 1:
    fail(f"[{name}] the 'total images/sec' banner is missing")
  if stats["num_steps"] != timed_steps:
    fail(f"[{name}] ran {stats['num_steps']} timed steps, "
         f"wanted {timed_steps}")
  want_step = kwargs["num_warmup_batches"] + timed_steps
  if int(stats["state"].step.ravel()[0]) != want_step:
    fail(f"[{name}] state.step = {stats['state'].step}, "
         f"wanted {want_step}")
  if not stats["images_per_sec"] > 0:
    fail(f"[{name}] images_per_sec = {stats['images_per_sec']}")
  ledger = stats["compile_ledger"] or {}
  say(f"[{name}] compile_s={stats['compile_s']:.2f} (first dispatch; "
      f"ledger: {ledger.get('shapes')} shape(s), "
      f"{ledger.get('total_compile_s')} s) "
      f"step_s={stats['average_wall_time']:.4f} "
      f"dispatch_overhead_s={stats['dispatch_overhead_s']:.6f} "
      f"leg_wall_s={wall:.1f}")
  say(f"[{name}] loss first={losses[0]:.3f} last={losses[-1]:.3f}; "
      f"smoke reading {stats['images_per_sec']:.1f} images/sec over "
      f"{timed_steps} steps (not a measurement)")
  return stats


def check_params_finite(name: str, state) -> None:
  import jax
  import jax.numpy as jnp
  leaves = jax.tree.leaves(state.params)
  ok = jax.jit(lambda ls: jnp.all(jnp.stack(
      [jnp.all(jnp.isfinite(x)) for x in ls])))(leaves)
  if not bool(ok):
    fail(f"[{name}] trained parameters are not all finite")
  say(f"[{name}] {len(leaves)} parameter leaves finite after training")


def check_one_shard_per_device(name: str, state, devices) -> None:
  """Every state leaf lives on all N devices, one addressable shard
  each (not N shards on the first device)."""
  import jax
  want = sorted(d.id for d in devices)
  leaves = jax.tree.leaves(state)
  for leaf in leaves:
    got = sorted(s.device.id for s in leaf.addressable_shards)
    if got != want:
      fail(f"[{name}] a state leaf of shape {leaf.shape} has shards on "
           f"devices {got}, wanted one on each of {want}")
  say(f"[{name}] {len(leaves)} state leaves: one addressable shard on "
      f"each of {len(want)} devices")


def check_replicas_identical(name: str, state) -> None:
  """sync_sgd all-reduces the gradients, so every replica row of every
  parameter must be bit-identical after the steps."""
  import jax
  import jax.numpy as jnp
  spread = jax.jit(lambda ls: jnp.max(jnp.stack(
      [jnp.max(jnp.abs(x - x[:1]).astype(jnp.float32)) for x in ls])))(
          jax.tree.leaves(state.params))
  if float(spread) != 0.0:
    fail(f"[{name}] replicas disagree after sync_sgd: max |row - row0| "
         f"= {float(spread)}")
  say(f"[{name}] all replicas hold identical parameters")


def main() -> int:
  import jax
  # The one backend init of this process; nothing of the package is
  # imported before the device is known.
  devices = jax.devices()
  device = {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
  say(f"platform={device['platform']} device_kind={device['kind']} "
      f"count={device['count']} jax={jax.__version__}")
  if device["platform"] != "tpu":
    print(f"chip_smoke: FAIL no accelerator: JAX found platform="
          f"{device['platform']} ({device['kind']}, {device['count']} "
          "device(s)); not measured", file=sys.stderr, flush=True)
    return 1

  stats = run_leg("one-chip", TIMED_STEPS)
  check_params_finite("one-chip", stats["state"])

  if device["count"] > 1:
    stats = run_leg("multi-chip", MULTI_TIMED_STEPS,
                    num_devices=device["count"],
                    variable_update="kungfu", kungfu_option="sync_sgd")
    check_params_finite("multi-chip", stats["state"])
    check_one_shard_per_device("multi-chip", stats["state"], devices)
    check_replicas_identical("multi-chip", stats["state"])
  else:
    say("multi-chip leg did not run: 1 device visible")

  print(json.dumps({"ok": True, "device": device}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
