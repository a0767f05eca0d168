"""Overlapped gradient reduction: bucketed in-backward all-reduce.

The reference's batched-collective layer exists to PIPELINE gradient
reduction against compute: chunked collectives "let XLA overlap"
transfers and ``--gradient_repacking`` re-shapes reduction granularity
away from tensor boundaries for exactly that reason (ref:
batch_allreduce.py:391-481 _TensorPacker; our port notes the intent at
ops/allreduce.py repack_reduce). The rebuild's post-hoc reduction --
one pass over the whole gradient tree AFTER the backward finishes
(train_step.py) -- preserves the tuning surface but serializes
communication strictly after compute.

This module restores the pipelining, TPU-natively
(``--overlap_gradient_reduction``):

* **Bucket scheduler**: gradient leaves are grouped at builder-layer
  granularity (top-level param-tree key) and merged into size-bounded
  buckets (``--reduce_bucket_mb``; allreduce.plan_size_buckets). Each
  bucket reduces as ONE packed collective (allreduce.pack_tensors /
  unpack_tensors -- the same pack metadata the post-hoc paths use), so
  the compiled program carries one collective per bucket instead of a
  single trailing fused reduction.

* **In-backward hooks**: each bucket's parameters pass through an
  identity-with-custom_vjp wrapper inside the loss function. The
  forward is the identity; the BACKWARD reduces the bucket's cotangent
  the moment it is complete -- at the point in the autodiff graph where
  that layer's backward finishes -- so layer L's gradients start
  reducing while layer L-1's backward is still running, and XLA's
  scheduler is free to interleave the collectives with the remaining
  backward compute. Applied per scanned block (models/transformer_lm.py
  nn.scan via nn.map_variables; parallel/transformer.py lax.scan body)
  the collective lands INSIDE the backward scan's while body -- one
  reduction per layer per backward iteration (tests pin this at the
  compiled-HLO level).

Numerics: pmean is elementwise across replicas, so packing, bucket
boundaries, and reduction placement never change values -- overlapped
gradients are BIT-IDENTICAL to the post-hoc path at the f32 wire dtype
(tests/test_overlap_reduction.py pins it on the 8-device mesh). With a
16-bit wire format (compact_gradient_transfer) the usual rounding
applies, as on the post-hoc paths.

Composition (validation.py enforces the exclusions):

* ``--num_grad_accum=M``: reduction stays POST-HOC on the accumulated
  tree -- one collective per step is a pinned invariant
  (tests/test_grad_accum.py HLO assertion); the hooks disengage.
* ``--steps_per_dispatch=K``: the hooks live inside the scanned step
  body; composes freely.
* auto loss scale: the finite-check runs on the reduced tree exactly
  as on the post-hoc path (the hooks reduce BEFORE the unscale, and
  pmean is linear in the scale).
* excluded: spec/repacking/small-grad/hierarchical reducers (each owns
  reduction granularity, ref: batch_allreduce.py:300-317 selects one
  algorithm), async-PS (consumes unaveraged per-replica gradients),
  gossip/independent modes (no reduction), and
  --track_grad_noise_scale (the estimator needs the pre-reduction
  per-replica gradients, which in-backward reduction never
  materializes).
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from kf_benchmarks_tpu.ops import allreduce
from kf_benchmarks_tpu.parallel import sequence as sequence_lib

# Default bucket bound. The reference's --gradient_repacking=8 on a
# ~100 MB ResNet-50 gradient vector works out to ~12 MB chunks; 4 MB
# keeps several buckets in flight on the smaller zoo members too while
# staying far above the per-collective latency floor.
DEFAULT_BUCKET_MB = 4


class OverlapSpec(NamedTuple):
  """Resolved --overlap_gradient_reduction configuration."""
  bucket_bytes: int
  compact_dtype: Optional[Any]  # 16-bit wire format, or None


def build(params) -> Optional[OverlapSpec]:
  """Flag-resolved overlap spec, or None when the mode is off.

  Callers decide engagement per composition rule (train_step.py
  disengages the hooks under --num_grad_accum; validation.py has
  already rejected the excluded reducer/strategy combinations)."""
  if not getattr(params, "overlap_gradient_reduction", False):
    return None
  mb = getattr(params, "reduce_bucket_mb", None) or DEFAULT_BUCKET_MB
  return OverlapSpec(
      bucket_bytes=int(mb) * 1024 * 1024,
      compact_dtype=allreduce.compact_wire_dtype(params))


# -- the identity-with-custom_vjp hook --------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def reduce_identity(reduce_fn, tree):
  """Identity on the forward; ``reduce_fn`` on the backward cotangent.

  The reduction runs at the exact point in the autodiff graph where
  ``tree``'s cotangent is complete, which for layer-local parameters is
  the moment that layer's backward finishes."""
  del reduce_fn
  return tree


def _reduce_identity_fwd(reduce_fn, tree):
  del reduce_fn
  return tree, None


def _reduce_identity_bwd(reduce_fn, _, cotangent):
  # The reduced cotangent is replicated over the reduction axis, but a
  # bwd rule must return the primal input's type, which varies over it
  # (per-replica stacked params): pcast each leaf back up to its
  # cotangent's varying axes -- a type-level cast, no data movement.
  return (jax.tree.map(
      lambda ct, x: sequence_lib.vary_like(ct, (x,))[0],
      cotangent, reduce_fn(cotangent)),)


reduce_identity.defvjp(_reduce_identity_fwd, _reduce_identity_bwd)


# -- bucket reduction (one packed collective per bucket) --------------------

def packed_pmean(leaves: Sequence[jax.Array], axis_name,
                 compact_dtype=None):
  """Replica-mean of a leaf list as ONE collective: pack into a flat
  vector (allreduce.pack_tensors -- the post-hoc paths' pack metadata),
  optionally compact to the 16-bit wire format, pmean, unpack.

  pmean is elementwise, so at the f32 wire dtype this is bit-identical
  to per-leaf pmean regardless of packing."""
  leaves = list(leaves)
  if not leaves:
    return leaves
  vec, meta = allreduce.pack_tensors(leaves)
  orig = vec.dtype
  if compact_dtype is not None and vec.dtype != compact_dtype:
    vec = vec.astype(compact_dtype)
  vec = lax.pmean(vec, axis_name).astype(orig)
  return allreduce.unpack_tensors(vec, meta)


def _bucket_reduce_fn(axis_name, compact_dtype):
  def reduce_fn(cotangent):
    leaves, treedef = jax.tree_util.tree_flatten(cotangent)
    return jax.tree_util.tree_unflatten(
        treedef, packed_pmean(leaves, axis_name, compact_dtype))
  return reduce_fn


# -- bucket planning (builder-layer granularity, size-bounded) --------------

def _leaf_nbytes(leaf) -> int:
  return int(leaf.size) * jnp.dtype(leaf.dtype).itemsize


def _top_key(path) -> str:
  """Builder-layer granularity: the top-level param-tree key (flax
  modules name one submodule per builder layer: 'conv0', 'cell_1',
  'blocks', ...). Single-sourced in ops/sharded.py -- the FSDP layout
  and this bucketing must classify prefixes identically."""
  from kf_benchmarks_tpu.ops import sharded as sharded_lib
  return sharded_lib.top_level_key(path)


def plan_buckets(tree, bucket_bytes: int,
                 exclude_prefixes: Tuple[str, ...] = ()):
  """Group ``tree``'s leaves into size-bounded reduction buckets.

  Leaves group by top-level key (layer granularity), keeping
  tree-flatten order so adjacent layers share buckets; groups merge
  into buckets of at most ``bucket_bytes`` via
  allreduce.plan_size_buckets (a single oversized layer keeps its own
  bucket -- hook units cannot split below the leaf the cotangent
  arrives on). Leaves under ``exclude_prefixes`` (top-level keys whose
  gradients a module already reduces in-backward, e.g. the scanned
  'blocks' stack) are left out.

  Returns (buckets, excluded): lists of leaf-index lists / the excluded
  leaf indices.
  """
  flat = jax.tree_util.tree_flatten_with_path(tree)[0]
  groups = []  # (key, [leaf indices], nbytes) in flatten order
  excluded = []
  for idx, (path, leaf) in enumerate(flat):
    key = _top_key(path)
    if key in exclude_prefixes:
      excluded.append(idx)
      continue
    if groups and groups[-1][0] == key:
      groups[-1][1].append(idx)
      groups[-1][2] += _leaf_nbytes(leaf)
    else:
      groups.append([key, [idx], _leaf_nbytes(leaf)])
  merged = allreduce.plan_size_buckets([g[2] for g in groups],
                                       bucket_bytes)
  buckets = [[i for g in span for i in groups[g][1]] for span in merged]
  return buckets, excluded


def wrap_tree(tree, axis_name, bucket_bytes: int, compact_dtype=None,
              exclude_prefixes: Tuple[str, ...] = ()):
  """Pass each bucket of ``tree`` through :func:`reduce_identity`.

  Apply to the parameter tree at the top of the loss function (every
  parameter use must flow through the wrapped copy); the gradient
  returned by jax.grad is then already replica-reduced, one collective
  per bucket, each issued in-backward."""
  leaves, treedef = jax.tree_util.tree_flatten(tree)
  buckets, _ = plan_buckets(tree, bucket_bytes,
                            exclude_prefixes=exclude_prefixes)
  reduce_fn = _bucket_reduce_fn(axis_name, compact_dtype)
  out = list(leaves)
  for bucket in buckets:
    wrapped = reduce_identity(reduce_fn, tuple(leaves[i] for i in bucket))
    for i, leaf in zip(bucket, wrapped):
      out[i] = leaf
  return jax.tree_util.tree_unflatten(treedef, out)


# -- FSDP per-bucket parameter gather (--shard_params) -----------------------
#
# The gather-side twin of reduce_identity: a custom_vjp whose FORWARD
# re-assembles a bucket of parameter shards with ONE packed tiled
# all-gather and whose BACKWARD reduce-scatters the bucket's cotangent
# (batch-axis mean + free model sub-slice -- elementwise identical to
# ops/sharded.scatter_mean, see there for the bit-identity argument)
# back onto the shard layout. Placed per builder-layer bucket at the
# top of the loss (train_step.py) and per scanned block inside the
# nn.scan/lax.scan body (models/transformer_lm.py,
# parallel/transformer.py), the gather lands INSIDE the loop body with
# exactly one collective per bucket -- the same one-slot-ahead position
# the in-backward reduction hooks earn for the gradient collectives:
# block l+1's gather is issued while block l's compute is still in
# flight, and XLA's async collectives overlap the two
# (observability.collective_overlap_stats measures the in-loop
# fraction; experiments/fsdp_gather_probe.py reports it).


class FsdpGatherSpec(NamedTuple):
  """Static (hashable) half of a gather bucket: full leaf shapes in
  bucket order plus the mesh axes. The shard half is the runtime
  argument. ``nested`` selects the vmap-safe decomposed forward gather
  (ops/sharded.combined_all_gather) for the --partitioner=gspmd twin;
  the default single tuple-axis collective is the manual-path form the
  goldens pin."""
  batch_axis: str
  model_axis: str
  shapes: Tuple[Tuple[int, ...], ...]
  dtypes: Tuple[str, ...]
  nested: bool = False


def _fsdp_mesh(spec):
  nb = lax.axis_size(spec.batch_axis)
  nm = lax.axis_size(spec.model_axis)
  return nb, nm, nb * nm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def gather_params(spec: FsdpGatherSpec, shards):
  """Tuple of flat local (k_i,) param shards -> tuple of FULL leaves.

  Forward: concat the bucket's shards, ONE tiled all-gather over the
  combined (batch, model) axes, split rows back per leaf (row-major
  device order matches the flat shard index, ops/sharded.py). Backward:
  the bucket's full-leaf cotangents pack into one (n, K) matrix and
  reduce-scatter as ONE collective (batch mean + model sub-slice),
  returning shard-layout cotangents bit-identical per element to the
  post-hoc ops/sharded.scatter_mean."""
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
  nb, nm, n = _fsdp_mesh(spec)
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
  """Gather buckets over the FULL-shape template: builder-layer
  granularity merged under ``bucket_bytes``, exactly the
  :func:`plan_buckets` scheduler (leaf sizes read from the template --
  the shards are uniformly flat). Returns (buckets, excluded) as leaf
  index lists in template flatten order."""
  flat = jax.tree_util.tree_flatten_with_path(template)[0]
  groups, excluded = [], []
  for idx, (path, leaf) in enumerate(flat):
    key = _top_key(path)
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
  bucket, each issued at the point in the backward where that bucket's
  cotangent completes."""
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

  Init never gathers: at init time flax routes the EMPTY pre-creation
  store through trans_in_fn (passed through below), the module creates
  params at FULL shapes (no collective can run under plain jit init),
  and the identity trans_out stores them full; the step's init_state
  then re-stacks the whole tree into the shard layout host-side
  (ops/sharded.fsdp_stacked_shards)."""
  t_leaves, t_def = jax.tree_util.tree_flatten(block_template)
  spec = FsdpGatherSpec(
      batch_axis=batch_axis, model_axis=model_axis,
      shapes=tuple(tuple(t.shape) for t in t_leaves),
      dtypes=tuple(jnp.dtype(t.dtype).name for t in t_leaves),
      nested=nested)

  def hook(stored):
    leaves, treedef = jax.tree_util.tree_flatten(stored)
    if not leaves:
      # Init, first trace: the EMPTY pre-creation store routes through
      # trans_in_fn; pass it through so the module creates its
      # full-shape params.
      return stored
    if tuple(tuple(l.shape) for l in leaves) == spec.shapes:
      # Init, re-trace: flax's scan re-runs the body with the params
      # it just created -- still FULL shapes (init runs under plain
      # jit, before init_state re-stacks to shards; no mesh axis is
      # bound there). Statically distinguishable from the apply path,
      # whose stored leaves are flat (k,) shards.
      return stored
    if len(leaves) != len(t_leaves):
      raise ValueError(
          f"FSDP block gather: stored block has {len(leaves)} leaves, "
          f"template has {len(t_leaves)} -- the module structure "
          "drifted from the template built at construction time")
    full = gather_params(spec, tuple(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(full))

  return hook


def scan_block_hook(axis_name, compact_dtype=None):
  """Per-scanned-block hook: wrap one layer's parameter slice as a
  single bucket.

  Use as ``nn.map_variables(Block, "params", trans_in_fn=hook,
  init=True)`` under nn.scan (models/transformer_lm.py) or applied to
  the carry-free xs slice at the top of a lax.scan body
  (parallel/transformer.py). Each backward scan iteration then issues
  that layer's reduction INSIDE the loop body, interleaved with the
  next iteration's backward compute."""
  reduce_fn = _bucket_reduce_fn(axis_name, compact_dtype)

  def hook(block_params):
    return reduce_identity(reduce_fn, block_params)

  return hook
