"""ZeRO-style sharded optimizer state: scatter / shard / gather helpers.

The TPU reformulation of the reference's central variable placement
(parameter_server / distributed_replicated variable placement,
ref: variable_mgr.py:201-243, :704-831; SURVEY 5.8): instead of a host
process owning the "server copy" of the variables and optimizer slots,
each device owns a flat 1/n shard of them (Rajbhandari et al., ZeRO),
and the collectives the graph-mode PS expressed as send/recv become
compiler-scheduled reduce-scatter / all-gather on the named 2-D
``('batch', 'model')`` mesh (parallel/mesh.py build_mesh_2d) -- the
GSPMD pattern (Xu et al. 2021).

Layout contract (everything here depends on it):

* A leaf of ``size`` elements pads with zeros to ``n * k`` where
  ``k = ceil(size / n)`` and ``n`` is the TOTAL device count; flat
  block ``i`` belongs to the device with flat shard index
  ``i = axis_index('batch') * M + axis_index('model')`` -- row-major
  over the mesh, the order a tiled ``all_gather(('batch', 'model'))``
  concatenates in.
* The gradient mean reduce-scatters over the ``'batch'`` axis ONLY
  (model-axis peers hold the same batch shard and the same fold_in rng,
  so their local gradients are identical by construction): the
  summation meets the same ``B`` distinct contributions in the same
  group order as the replicated path's all-reduce, which is what makes
  the scattered mean BIT-IDENTICAL to the ``pmean`` it replaces
  (pinned in tests/test_sharded_optimizer.py). The model-axis split of
  the batch-block is then a free local slice.
* Optimizer updates on the zero-padded tail are harmless: gradients
  there are exactly zero (pad-in, sum-of-zeros out), every stock
  optimizer maps (g=0, state=0) to update 0, and the tail is dropped at
  gather time regardless.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kf_benchmarks_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS


def shard_len(size: int, num_shards: int) -> int:
  """Per-device flat shard length: ceil(size / num_shards)."""
  return -(-size // num_shards)


def _pad_flat(x, num_shards: int):
  k = shard_len(x.size, num_shards)
  flat = jnp.ravel(x)
  return jnp.pad(flat, (0, num_shards * k - x.size)), k


def stacked_shards(tree, num_shards: int):
  """Full tree -> host-global stacked shard tree: each leaf flattened,
  zero-padded and reshaped ``(n, k)`` so row ``i`` is device ``i``'s
  shard. Global memory stays ~|leaf| (one padded copy, no n-fold
  stacking); sharding row 0 over the mesh axes puts exactly one row on
  each device. This is the layout ``TrainState.opt_state`` carries
  under --shard_optimizer_state (train_step.py)."""
  def f(x):
    flat, k = _pad_flat(x, num_shards)
    return flat.reshape(num_shards, k)
  return jax.tree.map(f, tree)


def scatter_mean(grads, batch_axis: str = BATCH_AXIS,
                 model_axis: str = MODEL_AXIS):
  """Local full-gradient tree -> this device's flat mean-shard.

  Reduce-scatter of the batch-axis mean (wire: ``(B-1)/B * |grads|``
  per device instead of the all-reduce's ``2(n-1)/n``), then the free
  model-axis sub-slice. Runs inside the shard_mapped step body."""
  nb = lax.axis_size(batch_axis)
  nm = lax.axis_size(model_axis)
  n = nb * nm
  mi = lax.axis_index(model_axis)

  def f(x):
    flat, k = _pad_flat(x, n)
    # Each batch group's scatter meets B distinct contributions in
    # group order -- the same association as the replicated pmean.
    block = lax.psum_scatter(flat, batch_axis, tiled=True) / nb
    return lax.dynamic_slice(block, (mi * k,), (k,))
  return jax.tree.map(f, grads)


def local_shards(tree, batch_axis: str = BATCH_AXIS,
                 model_axis: str = MODEL_AXIS):
  """Full (replica-identical) tree -> this device's flat shard by local
  slice -- no collective: every device already holds the whole value."""
  nb = lax.axis_size(batch_axis)
  nm = lax.axis_size(model_axis)
  n = nb * nm
  idx = lax.axis_index(batch_axis) * nm + lax.axis_index(model_axis)

  def f(x):
    flat, k = _pad_flat(x, n)
    return lax.dynamic_slice(flat, (idx * k,), (k,))
  return jax.tree.map(f, tree)


def combined_all_gather(x, batch_axis: str = BATCH_AXIS,
                        model_axis: str = MODEL_AXIS, axis: int = 0,
                        nested: bool = False):
  """Tiled all-gather over the combined ``(batch, model)`` axes.

  ``nested=False`` is the manual-path form: ONE collective over the
  axes tuple (every existing golden contract pins this inventory).
  ``nested=True`` decomposes it into model-then-batch single-axis
  tiled gathers -- element-identical (inner gather tiles the model
  peers, outer gather tiles the batch groups, reproducing the
  row-major ``b * M + m`` concatenation order exactly) but required on
  the --partitioner=gspmd path: jax (0.9.0) has no vmap batching rule
  for a tuple-axis all_gather, and the gspmd twin traces the step body
  under double ``jax.vmap`` (train_step.py)."""
  if not nested:
    return lax.all_gather(x, (batch_axis, model_axis), axis=axis,
                          tiled=True)
  inner = lax.all_gather(x, model_axis, axis=axis, tiled=True)
  return lax.all_gather(inner, batch_axis, axis=axis, tiled=True)


def gather_tree(shards, template, batch_axis: str = BATCH_AXIS,
                model_axis: str = MODEL_AXIS, nested: bool = False):
  """Flat shard tree -> full tree: tiled all-gather over the combined
  ``(batch, model)`` axes (row-major concatenation matches the
  scatter/slice block order), drop the pad, restore leaf shapes.
  ``nested`` selects the vmap-safe decomposed gather (see
  :func:`combined_all_gather`) for the gspmd twin."""
  def f(s, t):
    full = combined_all_gather(s, batch_axis, model_axis, nested=nested)
    return full[:t.size].reshape(t.shape).astype(t.dtype)
  return jax.tree.map(f, shards, template)


# -- FSDP parameter layout (--shard_params) ----------------------------------
#
# The round-11 layout above, applied to the PARAMETER tree itself
# (Rajbhandari et al. ZeRO-3 / the SNIPPETS.md [3] "shard W along the
# model axis" pattern): params live as shards between steps, the step
# re-assembles them per bucket / per scanned block INSIDE the
# forward/backward (ops/overlap.py gather_params), and the optimizer
# applies on the shard -- no full tree ever materializes, and the
# round-11 trailing all-gather disappears from the steady state.
#
# Two leaf families, so the scanned transformer can gather ONE block at
# a time:
#
# * non-scanned leaf (*s):        (n, k),    k = ceil(prod(s) / n)
# * scanned-prefix leaf (L, *s):  (n, L, k), k = ceil(prod(s) / n)
#   -- the (n, k) stacking applied PER LAYER, transposed so the shard
#   row leads uniformly: the whole TrainState keeps one leading
#   stacked-device dim (P over the combined mesh axes), and the
#   nn.scan/lax.scan bodies slice layer l's local shard as row l of the
#   squeezed (L, k) view.


def top_level_key(path) -> str:
  """Top-level pytree key of a jax key path (builder-layer / scanned-
  stack granularity; the same convention as ops/overlap.py bucketing)."""
  if not path:
    return ""
  p = path[0]
  return str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))


def _leaf_map(tree, scanned_prefixes, f_plain, f_scanned):
  def f(path, leaf):
    if top_level_key(path) in scanned_prefixes:
      return f_scanned(leaf)
    return f_plain(leaf)
  return jax.tree_util.tree_map_with_path(f, tree)


def fsdp_stacked_shards(tree, num_shards: int, scanned_prefixes=()):
  """Full param tree -> host-global FSDP shard stacks (see module
  notes): sharding the leading dim over the combined mesh axes puts
  exactly this device's flat shard (rows of every layer, for scanned
  leaves) on each device."""
  def plain(x):
    flat, k = _pad_flat(x, num_shards)
    return flat.reshape(num_shards, k)

  def scanned(x):
    if x.ndim < 1:
      raise ValueError(
          "scanned-prefix FSDP leaves need a leading layer axis; got a "
          f"scalar leaf of shape {tuple(x.shape)}")
    n_layers = x.shape[0]
    size = int(x.size) // n_layers
    k = shard_len(size, num_shards)
    flat = x.reshape(n_layers, size)
    flat = jnp.pad(flat, ((0, 0), (0, num_shards * k - size)))
    # (L, n, k) -> (n, L, k): shard row leads, like every other leaf.
    return jnp.moveaxis(flat.reshape(n_layers, num_shards, k), 1, 0)

  return _leaf_map(tree, scanned_prefixes, plain, scanned)


def fsdp_gather_full(local, template, scanned_prefixes=(),
                     batch_axis: str = BATCH_AXIS,
                     model_axis: str = MODEL_AXIS, nested: bool = False):
  """Local FSDP shard tree (leaves (k,) / (L, k), i.e. the squeezed
  per-device rows) -> the FULL tree, inside the shard_mapped body.

  The whole-tree re-assembly: the eval step and the --num_grad_accum
  path use it (the accumulated-gradient path keeps the full tree
  resident for the microbatch scan, exactly like the round-11 steady
  state -- the in-compute per-bucket gathers disengage there the same
  way the overlap hooks do). ``nested`` selects the vmap-safe
  decomposed gather (:func:`combined_all_gather`) for the gspmd twin."""
  def plain(s, t):
    full = combined_all_gather(s, batch_axis, model_axis, nested=nested)
    return full[:t.size].reshape(t.shape).astype(t.dtype)

  def scanned(s, t):
    size = int(np.prod(t.shape[1:], dtype=np.int64)) if t.ndim > 1 else 1
    full = combined_all_gather(s, batch_axis, model_axis, axis=1,
                               nested=nested)  # (L, n*k)
    return full[:, :size].reshape(t.shape).astype(t.dtype)

  by_path = dict(jax.tree_util.tree_flatten_with_path(template)[0])

  def f(path, s):
    t = by_path[tuple(path)]
    if top_level_key(path) in scanned_prefixes:
      return scanned(s, t)
    return plain(s, t)
  return jax.tree_util.tree_map_with_path(f, local)


def fsdp_scatter_mean(grads, scanned_prefixes=(),
                      batch_axis: str = BATCH_AXIS,
                      model_axis: str = MODEL_AXIS):
  """Full local gradient tree -> this device's FSDP-layout mean shards
  (the post-hoc scatter of the accumulated-gradient path).

  Per element this is EXACTLY :func:`scatter_mean` -- the batch-axis
  psum_scatter meets the same B contributions in the same group order,
  then the free model sub-slice -- only the shard ADDRESSING differs
  (per-layer rows for scanned leaves), so the elementwise optimizer
  sees bit-identical values in either layout."""
  nb = lax.axis_size(batch_axis)
  nm = lax.axis_size(model_axis)
  n = nb * nm
  mi = lax.axis_index(model_axis)

  def plain(x):
    flat, k = _pad_flat(x, n)
    block = lax.psum_scatter(flat, batch_axis, tiled=True) / nb
    return lax.dynamic_slice(block, (mi * k,), (k,))

  def scanned(x):
    n_layers = x.shape[0]
    size = int(x.size) // n_layers
    k = shard_len(size, n)
    flat = jnp.pad(x.reshape(n_layers, size),
                   ((0, 0), (0, n * k - size)))
    block = lax.psum_scatter(flat, batch_axis, scatter_dimension=1,
                             tiled=True) / nb  # (L, nm * k)
    return lax.dynamic_slice(block, (0, mi * k), (n_layers, k))

  return _leaf_map(grads, scanned_prefixes, plain, scanned)


def fsdp_param_bytes(template) -> int:
  """Full-tree parameter bytes of a (possibly abstract) template --
  the denominator of the residency contract (analysis/audit.py
  rule_fsdp_residency)."""
  total = 0
  for leaf in jax.tree.leaves(template):
    total += int(np.prod(leaf.shape, dtype=np.int64)) * jnp.dtype(
        leaf.dtype).itemsize
  return total
