"""The comparison that decides ``correct``.

A copy of what ``chip_smoke.py`` checks (proven on one and four chips in
PR 21), kept here so that a later change to the smoke cannot loosen the
benchmark. Every check returns a list of failure strings; an empty list
passes. Cells name the extra checks they need (``"checks"`` in the
cell's file) and the harness finds them in ``NAMED``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence


def step_lines(steps: Sequence, timed_steps: int) -> List[str]:
  """Step lines 1..n are all there, in order, each with a finite loss."""
  out = []
  got = [s.index for s in steps]
  if got != list(range(1, timed_steps + 1)):
    out.append(f"expected step lines 1..{timed_steps}, got {len(got)} "
               f"lines ending at {got[-1] if got else None}")
  bad = [s.index for s in steps if not math.isfinite(s.loss)]
  if bad:
    out.append(f"non-finite loss on step line(s) {bad[:8]}")
  return out


def failed_steps(steps: Sequence, timed_steps: int) -> int:
  """Timed steps with a missing line or a non-finite loss."""
  good = {s.index for s in steps if math.isfinite(s.loss)}
  return sum(1 for i in range(1, timed_steps + 1) if i not in good)


def run_stats(stats: Dict[str, Any], banners: int, timed_steps: int,
              want_step) -> List[str]:
  """``want_step`` is the step counter a training run must end on; None
  for a forward-only run, which never advances it."""
  out = []
  if banners != 1:
    out.append(f"the 'total images/sec' banner appeared {banners} times")
  if stats["num_steps"] != timed_steps:
    out.append(f"ran {stats['num_steps']} timed steps, wanted "
               f"{timed_steps}")
  step = int(stats["state"].step.ravel()[0])
  if want_step is not None and step != want_step:
    out.append(f"state.step = {step}, wanted {want_step}")
  if not stats["images_per_sec"] > 0:
    out.append(f"images_per_sec = {stats['images_per_sec']}")
  return out


def params_finite(state, devices) -> List[str]:
  import jax
  import jax.numpy as jnp
  del devices
  leaves = jax.tree.leaves(state.params)
  ok = jax.jit(lambda ls: jnp.all(jnp.stack(
      [jnp.all(jnp.isfinite(x)) for x in ls])))(leaves)
  return [] if bool(ok) else ["trained parameters are not all finite"]


def one_shard_per_device(state, devices) -> List[str]:
  """Every state leaf lives on all the cell's devices, one addressable
  shard each (not N shards on the first device)."""
  import jax
  want = sorted(d.id for d in devices)
  for leaf in jax.tree.leaves(state):
    got = sorted(s.device.id for s in leaf.addressable_shards)
    if got != want:
      return [f"a state leaf of shape {leaf.shape} has shards on devices "
              f"{got}, wanted one on each of {want}"]
  return []


def replicas_identical(state, devices) -> List[str]:
  """After all-reduced steps every replica row of every parameter is
  bit-identical."""
  import jax
  import jax.numpy as jnp
  del devices
  spread = jax.jit(lambda ls: jnp.max(jnp.stack(
      [jnp.max(jnp.abs(x - x[:1]).astype(jnp.float32)) for x in ls])))(
          jax.tree.leaves(state.params))
  if float(spread) != 0.0:
    return [f"replicas disagree: max |row - row0| = {float(spread)}"]
  return []


NAMED: Dict[str, Callable[[Any, Sequence], List[str]]] = {
    "params_finite": params_finite,
    "one_shard_per_device": one_shard_per_device,
    "replicas_identical": replicas_identical,
}
