"""Value-fetch device sync.

Measured on the TPU v5e (PR 21, PERF.md): a K-step scanned dispatch
ended by ``jax.block_until_ready`` and one ended by :func:`drain` agree
to within the fetch itself (~0.5 ms), so either is a valid timing
boundary; ``drain`` additionally proves the value is readable.
"""

import jax


def drain(tree) -> None:
  """Value-fetch sync: block until every device holding ``tree`` is done."""
  leaves = [l for l in jax.tree.leaves(tree) if hasattr(l, "dtype")]
  if not leaves:
    return
  # The smallest leaf per distinct device set, every addressable shard
  # of it: per-device execution is in-order, so one fetched shard per
  # device drains that device's queue; assembling a replicated leaf
  # would read one device only.
  by_devices = {}
  for leaf in leaves:
    shards = getattr(leaf, "addressable_shards", None)
    devices = (frozenset(s.device.id for s in shards) if shards
               else frozenset())
    best = by_devices.get(devices)
    if best is None or leaf.size < best.size:
      by_devices[devices] = leaf
  for leaf in by_devices.values():
    shards = getattr(leaf, "addressable_shards", None)
    if shards:
      jax.device_get([s.data for s in shards])
    else:
      jax.device_get(leaf)
