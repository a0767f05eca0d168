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

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kf_benchmarks_tpu.ops import allreduce
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
# forward/backward (:func:`gather_params` below), and the optimizer
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
  stack granularity; the FSDP layout and the gather buckets classify
  prefixes by it)."""
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
  state -- the in-compute per-bucket gathers disengage there, one
  scatter of the ACCUMULATED tree being a pinned invariant). ``nested``
  selects the vmap-safe decomposed gather (:func:`combined_all_gather`)
  for the gspmd twin."""
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
  return sum(_template_nbytes(leaf) for leaf in jax.tree.leaves(template))


# -- FSDP per-bucket parameter gather (--shard_params) -----------------------
#
# A custom_vjp whose FORWARD re-assembles a bucket of parameter shards
# with ONE packed tiled all-gather and whose BACKWARD reduce-scatters
# the bucket's cotangent back onto the shard layout. Placed per
# builder-layer bucket at the top of the loss (train_step.py) and per
# scanned block inside the nn.scan/lax.scan body
# (models/transformer_lm.py, parallel/transformer.py), where block
# l+1's gather is issued while block l's compute is still in flight
# (observability.collective_overlap_stats measures the in-loop
# fraction; experiments/fsdp_gather_probe.py reports it).

# Default gather-bucket bound (--reduce_bucket_mb): 4 MB keeps several
# buckets in flight on the smaller zoo members while staying far above
# the per-collective latency floor.
DEFAULT_BUCKET_MB = 4


class FsdpGatherSpec(NamedTuple):
  """Static (hashable) half of a gather bucket: full leaf shapes in
  bucket order plus the mesh axes. The shard half is the runtime
  argument. ``nested`` selects the vmap-safe decomposed forward gather
  (:func:`combined_all_gather`) for the --partitioner=gspmd twin;
  the default single tuple-axis collective is the manual-path form the
  goldens pin."""
  batch_axis: str
  model_axis: str
  shapes: Tuple[Tuple[int, ...], ...]
  dtypes: Tuple[str, ...]
  nested: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def gather_params(spec: FsdpGatherSpec, shards):
  """Tuple of flat local (k_i,) param shards -> tuple of FULL leaves.

  Forward: :func:`packed_gather_rows` over the combined (batch, model)
  axes. Backward: the bucket's full-leaf cotangents pack into one
  (n, K) matrix and reduce-scatter as ONE collective (batch mean +
  model sub-slice), returning shard-layout cotangents bit-identical
  per element to the post-hoc :func:`scatter_mean`."""
  return _gather_fwd_impl(spec, shards)


# Shared packing primitives: BOTH FSDP gather hooks (this module's
# mesh-2-D gather_params and the composed trainer's
# parallel/transformer._fsdp_block_hook) build on these, so the row
# addressing and pad handling cannot drift between the two legs.

def packed_gather_rows(axes, shapes, dtypes, shards, nested=False):
  """Tuple of flat local (k_i,) shards -> tuple of FULL leaves via ONE
  tiled all-gather over ``axes``: concat the shards, gather, split the
  (n, K) row matrix back per leaf (row-major device order over the
  axes tuple matches the flat shard index). ``nested`` decomposes the
  tuple-axis gather into per-axis gathers (innermost first -- same
  row-major order) for the gspmd twin, whose double-vmap trace has no
  tuple-axis all_gather batching rule (jax 0.9.0)."""
  n = math.prod(lax.axis_size(a) for a in axes)
  ks = tuple(int(s.shape[0]) for s in shards)
  vec = jnp.concatenate(list(shards)) if len(shards) > 1 else shards[0]
  if nested:
    full = vec
    for a in reversed(axes):
      full = lax.all_gather(full, a, tiled=True)
    mat = full.reshape(n, sum(ks))
  else:
    mat = lax.all_gather(vec, axes, tiled=True).reshape(n, sum(ks))
  outs, off = [], 0
  for k, shape, dtype in zip(ks, shapes, dtypes):
    size = int(math.prod(shape)) if shape else 1
    leaf = mat[:, off:off + k].reshape(n * k)[:size].reshape(shape)
    outs.append(leaf.astype(dtype))
    off += k
  return tuple(outs)


def pack_cotangent_rows(cots, shapes, n, common_dtype):
  """Full-leaf cotangents -> (the packed (n, K) row matrix, per-leaf
  shard lengths): each leaf flattens, zero-pads to n * k and lands as
  a k-wide column block, so row i of the matrix is device i's packed
  shard cotangent."""
  cols, ks = [], []
  for cot, shape in zip(cots, shapes):
    size = int(math.prod(shape)) if shape else 1
    k = -(-size // n)
    flat = jnp.ravel(cot).astype(common_dtype)
    cols.append(jnp.pad(flat, (0, n * k - size)).reshape(n, k))
    ks.append(k)
  mat = jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
  return mat, ks


def split_shard_row(row, ks, dtypes):
  """One packed (K,) shard row -> the per-leaf flat (k_i,) shards."""
  outs, off = [], 0
  for k, dtype in zip(ks, dtypes):
    outs.append(row[off:off + k].astype(dtype))
    off += k
  return tuple(outs)


def _gather_fwd_impl(spec, shards):
  return packed_gather_rows((spec.batch_axis, spec.model_axis),
                            spec.shapes, spec.dtypes, shards,
                            nested=spec.nested)


def _gather_params_fwd(spec, shards):
  # No residuals: the shard dtypes equal the full-leaf dtypes (the
  # storage is re-stacked from the full init), so spec carries all the
  # backward needs.
  return _gather_fwd_impl(spec, shards), None


def _gather_params_bwd(spec, _, cotangents):
  nb = lax.axis_size(spec.batch_axis)
  n = nb * lax.axis_size(spec.model_axis)
  mi = lax.axis_index(spec.model_axis)
  # The packed wire rides the bucket's own dtype (f32 for f32 params,
  # bf16 under --fp16_vars) -- same wire class as the post-hoc
  # scatter's per-leaf collectives.
  common = jnp.result_type(*spec.dtypes)
  mat, ks = pack_cotangent_rows(cotangents, spec.shapes, n, common)
  # ONE packed reduce-scatter: batch-group rows sum elementwise in the
  # same order as the per-leaf scatter, so packing changes no values.
  rows = lax.psum_scatter(mat, spec.batch_axis, scatter_dimension=0,
                          tiled=True) / nb  # (nm, K)
  row = lax.dynamic_slice_in_dim(rows, mi, 1, axis=0)[0]
  return (split_shard_row(row, ks, spec.dtypes),)


gather_params.defvjp(_gather_params_fwd, _gather_params_bwd)


def _template_nbytes(leaf) -> int:
  shape = tuple(leaf.shape)
  return (int(math.prod(shape)) if shape else 1) * jnp.dtype(
      leaf.dtype).itemsize


def fsdp_plan_buckets(template, bucket_bytes: int,
                      exclude_prefixes: Tuple[str, ...] = ()):
  """Gather buckets over the FULL-shape template: leaves group by
  top-level key (builder-layer granularity) in flatten order, and
  adjacent groups merge into buckets of at most ``bucket_bytes``
  (allreduce.plan_size_buckets; leaf sizes read from the template --
  the shards are uniformly flat). Leaves under ``exclude_prefixes``
  (the module-gathered scanned stacks) are left out. Returns (buckets,
  excluded) as leaf index lists in template flatten order."""
  flat = jax.tree_util.tree_flatten_with_path(template)[0]
  groups, excluded = [], []
  for idx, (path, leaf) in enumerate(flat):
    key = top_level_key(path)
    if key in exclude_prefixes:
      excluded.append(idx)
      continue
    if groups and groups[-1][0] == key:
      groups[-1][1].append(idx)
      groups[-1][2] += _template_nbytes(leaf)
    else:
      groups.append([key, [idx], _template_nbytes(leaf)])
  merged = allreduce.plan_size_buckets([g[2] for g in groups],
                                       bucket_bytes)
  buckets = [[i for g in span for i in groups[g][1]] for span in merged]
  return buckets, excluded


def fsdp_wrap_shards(shard_tree, template, bucket_bytes: int,
                     batch_axis, model_axis,
                     exclude_prefixes: Tuple[str, ...] = (),
                     nested: bool = False):
  """Shard-layout param tree -> the tree the loss consumes: every
  non-excluded leaf replaced by its gathered FULL value (one
  :func:`gather_params` per builder-layer bucket), excluded
  (module-gathered scanned-stack) leaves passed through as shards for
  the per-block hooks inside the scan body.

  The returned tree is what jax.grad differentiates: gradients arrive
  already reduce-scattered onto the shard layout, one collective per
  bucket, issued where that bucket's cotangent completes."""
  leaves, treedef = jax.tree_util.tree_flatten(shard_tree)
  t_leaves = jax.tree_util.tree_flatten(template)[0]
  buckets, _ = fsdp_plan_buckets(template, bucket_bytes,
                                 exclude_prefixes=exclude_prefixes)
  out = list(leaves)
  for bucket in buckets:
    spec = FsdpGatherSpec(
        batch_axis=batch_axis, model_axis=model_axis,
        shapes=tuple(tuple(t_leaves[i].shape) for i in bucket),
        dtypes=tuple(jnp.dtype(t_leaves[i].dtype).name for i in bucket),
        nested=nested)
    full = gather_params(spec, tuple(leaves[i] for i in bucket))
    for i, leaf in zip(bucket, full):
      out[i] = leaf
  return jax.tree_util.tree_unflatten(treedef, out)


def fsdp_block_gatherer(block_template, batch_axis, model_axis,
                        nested: bool = False):
  """Per-scanned-block gather hook (``nn.map_variables(...,
  trans_in_fn=hook, init=True)`` under nn.scan, or applied to the
  sliced xs at the top of a lax.scan body): stored per-block flat
  shards -> the block's full param tree via ONE packed gather, whose
  backward reduce-scatters the block's cotangent INSIDE the backward
  scan iteration.

  Init never gathers: flax routes the EMPTY pre-creation store through
  trans_in_fn (passed through below), the module creates params at FULL
  shapes (no collective can run under plain jit init), and the step's
  init_state re-stacks the whole tree into the shard layout
  (:func:`fsdp_stacked_shards`)."""
  t_leaves, t_def = jax.tree_util.tree_flatten(block_template)
  spec = FsdpGatherSpec(
      batch_axis=batch_axis, model_axis=model_axis,
      shapes=tuple(tuple(t.shape) for t in t_leaves),
      dtypes=tuple(jnp.dtype(t.dtype).name for t in t_leaves),
      nested=nested)

  def hook(stored):
    leaves, treedef = jax.tree_util.tree_flatten(stored)
    if not leaves:
      # Init, first trace: the EMPTY pre-creation store.
      return stored
    if tuple(tuple(l.shape) for l in leaves) == spec.shapes:
      # Init, re-trace: flax's scan re-runs the body with the params
      # it just created -- still FULL shapes, statically distinguishable
      # from the apply path's flat (k,) shards.
      return stored
    if len(leaves) != len(t_leaves):
      raise ValueError(
          f"FSDP block gather: stored block has {len(leaves)} leaves, "
          f"template has {len(t_leaves)} -- the module structure "
          "drifted from the template built at construction time")
    full = gather_params(spec, tuple(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(full))

  return hook
