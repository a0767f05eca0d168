"""All-reduce spec parsing, gradient packing, and the reduction planner.

TPU-native re-design of the reference's collective layer (ref:
scripts/tf_cnn_benchmarks/allreduce.py:32-104 spec BNF, :420-588
small-tensor packing; batch_allreduce.py:32-153 batched algorithms;
allreduce_legacy.py:320-368 ring/hierarchical builders).

The spec grammar is preserved as a tuning surface:

    spec        := alg_spec (":" limit ":" alg_spec)*
    alg_spec    := alg ("#" shards)?
    alg         := "psum" | "rsag" | "hier" | reference aliases
    limit       := <int>[kKmM]?      (byte threshold; tensors smaller than
                                      the limit use the preceding alg)

e.g. ``psum:32k:rsag#2`` -- tensors under 32KiB all-reduce directly
(latency-bound: one fused psum), larger ones go through a sharded
reduce-scatter + all-gather (bandwidth-optimal on an ICI ring, the analog
of the reference's ``xring``).

``hier`` is UNVALIDATED AT SCALE (only ever measured on single-chip /
virtual meshes; VERDICT weak #4): the default remains ``psum``, and
selecting hier on a single-process mesh logs a warning at build time
(_warn_hier_selected).

Reference algorithm names map onto TPU implementations so reference specs
keep working: nccl->psum, xring->rsag, pscpu/psgpu->psum,
collective->psum, nccl/xring & friends->hier.

On TPU, XLA already lowers ``psum`` to topology-aware ICI rings; the
decompositions here exist to (a) preserve the spec-driven tuning surface,
(b) let the planner pack small gradients into one fused collective
(bandwidth + latency win the reference gets from pack_small_tensors), and
(c) shard large reductions the way the reference's ``#shards`` did.
"""

from __future__ import annotations

import re
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class AllReduceSpecTuple(NamedTuple):
  """(ref: allreduce.py:32-56)"""
  alg: str
  shards: int
  limit: Optional[int]  # byte threshold; None = no upper bound


_TPU_ALGS = ("psum", "rsag", "hier")
_ALIASES = {
    "nccl": "psum",
    "collective": "psum",
    "pscpu": "psum",
    "psgpu": "psum",
    "xring": "rsag",
    "nccl/xring": "hier",
    "nccl/rechd": "hier",
    "nccl/pscpu": "hier",
    "pscpu/pscpu": "hier",
}


def _parse_limit(limit_str: str) -> int:
  m = re.fullmatch(r"(\d+)([kKmM]?)", limit_str)
  if not m:
    raise ValueError(f"Invalid all-reduce spec limit {limit_str!r}")
  val = int(m.group(1))
  suffix = m.group(2).lower()
  if suffix == "k":
    val *= 1024
  elif suffix == "m":
    val *= 1024 * 1024
  return val


def _parse_alg(alg_str: str) -> AllReduceSpecTuple:
  if "#" in alg_str:
    alg, _, shards_str = alg_str.partition("#")
    try:
      shards = int(shards_str)
    except ValueError:
      raise ValueError(f"Invalid all-reduce spec shards {alg_str!r}")
  else:
    alg, shards = alg_str, 1
  alg = _ALIASES.get(alg, alg)
  if alg not in _TPU_ALGS:
    raise ValueError(
        f"Invalid all-reduce algorithm {alg_str!r}; TPU algs are "
        f"{_TPU_ALGS} (reference aliases {sorted(_ALIASES)} accepted)")
  return AllReduceSpecTuple(alg=alg, shards=shards, limit=None)


def parse_all_reduce_spec(spec: str) -> List[AllReduceSpecTuple]:
  """Parse the spec BNF into range-limited tuples (ref: allreduce.py:58-104).

  Returns tuples ordered small-to-large; each tuple's ``limit`` is the
  exclusive upper byte bound it handles (None for the last)."""
  parts = spec.split(":")
  if len(parts) % 2 == 0:
    raise ValueError(f"Spec must alternate alg:limit:alg...: {spec!r}")
  tuples = []
  for i, part in enumerate(parts):
    if i % 2 == 0:
      tuples.append(_parse_alg(part))
    else:
      limit = _parse_limit(part)
      prev = tuples[-1]
      if prev.limit is not None:
        raise ValueError(f"Duplicate limit in spec {spec!r}")
      tuples[-1] = prev._replace(limit=limit)
      if len(tuples) >= 2 and tuples[-2].limit is not None and \
          limit <= tuples[-2].limit:
        raise ValueError(f"Limits must be increasing in spec {spec!r}")
  if tuples[-1].limit is not None:
    raise ValueError(f"Last algorithm in spec must be unbounded: {spec!r}")
  return tuples


# -- packing ----------------------------------------------------------------

def plan_size_buckets(sizes: Sequence[int], bucket_bytes: int):
  """Greedy size-bounded bucketing of an ordered size list.

  The scheduler behind --reduce_bucket_mb (ops/sharded.py
  fsdp_plan_buckets): consecutive items merge into a bucket until adding
  the next would exceed ``bucket_bytes``; an item alone larger than the
  bound keeps its own bucket (a unit cannot split below the granularity
  the caller hands in). Order is preserved -- FSDP's gathers rely on
  buckets covering ADJACENT layers so each bucket's cotangent completes
  in one contiguous stretch of the backward. Returns a list of index
  lists covering ``range(len(sizes))`` exactly.
  """
  buckets = []
  cur, cur_bytes = [], 0
  for i, size in enumerate(sizes):
    if cur and cur_bytes + size > bucket_bytes:
      buckets.append(cur)
      cur, cur_bytes = [], 0
    cur.append(i)
    cur_bytes += size
  if cur:
    buckets.append(cur)
  return buckets


# One precision note per process: compact_wire_dtype is consulted by
# every builder that can consume the wire format, and repeating the
# identical note per consumer would read as several distinct
# engagements.
_compact_f32_noted = False


def compact_wire_dtype(params):
  """The 16-bit wire format the packed reduction paths ride, or None.

  compact_gradient_transfer historically engaged only under --use_fp16
  (ref: batch_allreduce.py:96-103 compacts fp16 gradients); on TPU the
  bf16 wire format is equally valid for f32 training -- the all-reduce
  moves half the bytes while master params and the optimizer apply stay
  f32 -- so --compact_gradient_transfer_f32 opts f32 runs in explicitly
  (validation.py requires a packed path that actually consumes the
  format; the default per-leaf pmean has no wire repacking to compact).
  The opt-in logs a precision note once: gradients ride the wire at
  bf16 (8 mantissa bits), a rounding the f32 post-hoc path does not
  have.
  """
  if not params.compact_gradient_transfer:
    return None
  if params.use_fp16:
    return jnp.bfloat16
  if getattr(params, "compact_gradient_transfer_f32", False):
    global _compact_f32_noted
    if not _compact_f32_noted:
      _compact_f32_noted = True
      from kf_benchmarks_tpu.utils import log as log_util
      log_util.log_fn(
          "compact_gradient_transfer_f32: f32 gradients ride the "
          "all-reduce wire at bfloat16 (8 mantissa bits) -- halves "
          "reduction bytes; NOT bit-identical to the f32 wire path")
    return jnp.bfloat16
  return None


class PackMeta(NamedTuple):
  shapes: tuple
  dtypes: tuple
  sizes: tuple
  pad: int


def pack_tensors(leaves: Sequence[jax.Array], multiple_of: int = 1):
  """Flatten+concat a tensor list into one fp32-width-preserving vector
  (ref: pack_small_tensors / pack_range, allreduce.py:420-510).

  Padding to ``multiple_of`` makes the vector evenly shardable for
  reduce-scatter. Returns (vector, PackMeta)."""
  shapes = tuple(l.shape for l in leaves)
  dtypes = tuple(l.dtype for l in leaves)
  sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
  flat = [jnp.ravel(l) for l in leaves]
  common = jnp.result_type(*dtypes) if leaves else jnp.float32
  vec = jnp.concatenate([f.astype(common) for f in flat]) if flat else \
      jnp.zeros((0,), common)
  pad = (-vec.shape[0]) % multiple_of
  if pad:
    vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
  return vec, PackMeta(shapes, dtypes, sizes, pad)


def unpack_tensors(vec: jax.Array, meta: PackMeta) -> List[jax.Array]:
  """Inverse of pack_tensors (ref: unpack_small_tensors,
  allreduce.py:560-588)."""
  if meta.pad:
    vec = vec[:-meta.pad] if meta.pad else vec
  out = []
  offset = 0
  for shape, dtype, size in zip(meta.shapes, meta.dtypes, meta.sizes):
    out.append(vec[offset:offset + size].reshape(shape).astype(dtype))
    offset += size
  return out


# -- algorithms -------------------------------------------------------------

def _pmean_direct(vec, axis_name):
  return lax.pmean(vec, axis_name)


def _rsag(vec, axis_name, shards=1):
  """Reduce-scatter + all-gather: the bandwidth-optimal ring decomposition
  (the analog of the reference's ring builders, allreduce_legacy.py:338-360).

  ``shards`` subdivides the vector into independently-reduced chunks --
  the reference's ``alg#shards`` ring subdivision (ref: allreduce.py:32-56
  spec, subdiv offsets :185-219): chunked collectives let XLA overlap the
  chunks' scatter/gather phases."""
  n = lax.axis_size(axis_name)
  shards = max(1, int(shards))
  size = vec.shape[0]
  pad = (-size) % (n * shards)
  if pad:
    vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])

  def one(v):
    scattered = lax.psum_scatter(v, axis_name, scatter_dimension=0,
                                 tiled=True)
    return lax.all_gather(scattered, axis_name, axis=0, tiled=True)

  if shards > 1:
    vec = jnp.concatenate([one(part) for part in jnp.split(vec, shards)])
  else:
    vec = one(vec)
  if pad:
    vec = vec[:size]
  return vec / n


def topology_groups(devices, num_groups: Optional[int] = None):
  """Axis-position -> group id for hierarchical reduction, derived from
  real machine topology the way the reference's HierarchicalCopy encodes
  it (ref: batch_allreduce.py:173-267 topology tables).

  ``devices`` is the mesh axis's device order. Multi-process: groups are
  the process (host) boundaries, so the intra-group ring rides ICI and
  only the cross-group ring crosses DCN. Single-process (no topology to
  read): contiguous split into ``num_groups`` (default 2, the reference's
  two-group HierarchicalCopy shape)."""
  procs = [getattr(d, "process_index", 0) for d in devices]
  uniq = sorted(set(procs))
  if len(uniq) > 1:
    gid = {p: i for i, p in enumerate(uniq)}
    return [gid[p] for p in procs]
  n = len(devices)
  k = max(2, int(num_groups or 2))
  if n % k != 0:
    return [0] * n  # degenerate; _hier falls back to pmean
  return [i // (n // k) for i in range(n)]


def _ring_sum(vec, axis_name, cycles, rounds):
  """Sum values around disjoint position cycles: ``rounds`` applications
  of the cycles' successor permutation, accumulating each arrival."""
  perm = []
  for cycle in cycles:
    for j, pos in enumerate(cycle):
      perm.append((pos, cycle[(j + 1) % len(cycle)]))
  acc, cur = vec, vec
  for _ in range(rounds):
    cur = lax.ppermute(cur, axis_name, perm)
    acc = acc + cur
  return acc


def _hier(vec, axis_name, num_groups=2, groups=None):
  """Two-level hierarchical reduction: a ring all-reduce within each
  group (intra-host ICI), then a ring across same-offset members of each
  group -- (g-1) + (num_groups-1) exchange rounds instead of a flat
  ring's n-1 (the analog of the reference's two-group reduce ->
  cross-group reduce -> broadcast HierarchicalCopy,
  batch_allreduce.py:173-267, and 'nccl/rechd',
  allreduce_legacy.py:344-348).

  ``groups`` maps axis position -> group id (from :func:`topology_groups`,
  i.e. process/host boundaries); absent, groups are ``num_groups``
  contiguous blocks. Falls back to a direct pmean when groups are not
  equal-sized (the reference requires symmetric topology too)."""
  n = lax.axis_size(axis_name)
  if groups is not None and len(groups) != n:
    # Stale topology capture (e.g. a reducer built for a different mesh
    # surviving an elastic resize): permuting with wrong-length groups
    # would drop or zero replicas, so reduce flat instead.
    groups = None
  if groups is None:
    num_groups = max(2, int(num_groups))
    if n <= 1 or n % num_groups != 0:
      return lax.pmean(vec, axis_name)
    groups = [i // (n // num_groups) for i in range(n)]
  members = {}
  for pos, g in enumerate(groups):
    members.setdefault(g, []).append(pos)
  sizes = {len(m) for m in members.values()}
  if n <= 1 or len(members) < 2 or len(sizes) != 1:
    return lax.pmean(vec, axis_name)
  gsize = sizes.pop()
  ordered = [members[g] for g in sorted(members)]
  # Intra-group rings (one cycle per group), then cross-group rings (one
  # cycle per member offset, linking the j-th member of every group).
  vec = _ring_sum(vec, axis_name, ordered, gsize - 1)
  cross = [[grp[j] for grp in ordered] for j in range(gsize)]
  vec = _ring_sum(vec, axis_name, cross, len(ordered) - 1)
  return vec / n


# -- planner ----------------------------------------------------------------

def _reduce_packed(vec, spec: AllReduceSpecTuple, axis_name,
                   compact_dtype=None):
  """Reduce one packed vector per its spec, optionally compacted to a
  16-bit wire format (ref: compact_gradient_transfer,
  batch_allreduce.py:96-103 fp16 compaction)."""
  orig_dtype = vec.dtype
  if compact_dtype is not None and vec.dtype != compact_dtype:
    vec = vec.astype(compact_dtype)
  if spec.alg == "psum":
    vec = _pmean_direct(vec, axis_name)
  elif spec.alg == "rsag":
    vec = _rsag(vec, axis_name, spec.shards)
  elif spec.alg == "hier":
    vec = _hier(vec, axis_name, max(spec.shards, 2))
  else:
    raise ValueError(f"Unknown alg {spec.alg!r}")
  return vec.astype(orig_dtype)


class CollectivePlanner:
  """Spec-driven gradient reduction with small-tensor packing.

  The analog of sum_gradients_all_reduce + AllReduceSpec batching
  (ref: allreduce.py:344-417, batch_allreduce.py:270-297): gradients are
  bucketed by byte size per the spec ranges, each bucket packed into one
  flat vector, and reduced with the bucket's algorithm.

  ``agg_max_bytes``/``agg_max_group`` apply the small-gradient packing
  limits within each bucket: only tensors under ``agg_max_bytes`` join
  group packs, capped at ``agg_max_group`` tensors each; larger tensors
  share the bucket-wide pack as before (ref: agg_small_grads_max_bytes/
  _group threading into sum_gradients_all_reduce, allreduce.py:344-417,
  extract_ranges :420-460). ``compact_dtype`` compacts the packed wire
  format to 16 bits (ref: compact_gradient_transfer).
  """

  def __init__(self, spec_tuples: Sequence[AllReduceSpecTuple],
               num_replicas_hint: int = 8, agg_max_bytes: int = 0,
               agg_max_group: Optional[int] = None, compact_dtype=None):
    self.spec_tuples = list(spec_tuples)
    self.num_replicas_hint = num_replicas_hint
    self.agg_max_bytes = agg_max_bytes
    self.agg_max_group = agg_max_group
    self.compact_dtype = compact_dtype

  def _bucket_of(self, nbytes: int) -> int:
    for i, t in enumerate(self.spec_tuples):
      if t.limit is None or nbytes < t.limit:
        return i
    return len(self.spec_tuples) - 1

  def reduce(self, grads, axis_name):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    n = self.num_replicas_hint
    buckets = {}
    for idx, leaf in enumerate(leaves):
      b = self._bucket_of(leaf.size * leaf.dtype.itemsize)
      buckets.setdefault(b, []).append(idx)
    reduced = [None] * len(leaves)
    for b, idxs in sorted(buckets.items()):
      spec = self.spec_tuples[b]
      if self.agg_max_bytes > 0:
        small = [i for i in idxs
                 if leaves[i].size * leaves[i].dtype.itemsize <
                 self.agg_max_bytes]
        rest = [i for i in idxs if i not in small]
        group = max(1, self.agg_max_group or len(small) or 1)
        chunks = [small[s:s + group] for s in range(0, len(small), group)]
        if rest:
          chunks.append(rest)
      else:
        chunks = [idxs]
      for chunk in chunks:
        vec, meta = pack_tensors([leaves[i] for i in chunk], multiple_of=n)
        vec = _reduce_packed(vec, spec, axis_name, self.compact_dtype)
        for i, t in zip(chunk, unpack_tensors(vec, meta)):
          reduced[i] = t
    return jax.tree_util.tree_unflatten(treedef, reduced)


def pack_small_reduce(grads, axis_name, max_bytes: int, max_group: int,
                      num_replicas: int, compact_dtype=None):
  """Default-path (no spec) small-gradient aggregation: pack tensors
  smaller than ``max_bytes`` into groups of at most ``max_group`` and
  all-reduce each pack as one tensor; larger tensors reduce individually
  (ref: agg_small_grads_max_bytes/_group, allreduce.py:420-588
  pack_small_tensors/unpack_small_tensors)."""
  spec = AllReduceSpecTuple(alg="psum", shards=1, limit=None)
  leaves, treedef = jax.tree_util.tree_flatten(grads)
  reduced = [None] * len(leaves)
  small = [i for i, l in enumerate(leaves)
           if l.size * l.dtype.itemsize < max_bytes]
  for i, leaf in enumerate(leaves):
    if i not in small:
      reduced[i] = _reduce_packed(
          jnp.ravel(leaf), spec, axis_name, compact_dtype).reshape(leaf.shape)
  group = max(1, max_group)
  for start in range(0, len(small), group):
    chunk = small[start:start + group]
    vec, meta = pack_tensors([leaves[i] for i in chunk],
                             multiple_of=num_replicas)
    vec = _reduce_packed(vec, spec, axis_name, compact_dtype)
    for i, t in zip(chunk, unpack_tensors(vec, meta)):
      reduced[i] = t
  return jax.tree_util.tree_unflatten(treedef, reduced)


def repack_reduce(grads, axis_name, num_chunks: int, num_replicas: int,
                  compact_dtype=None):
  """Default-path gradient repacking: concatenate ALL gradients into one
  vector, re-split it into ``num_chunks`` even chunks, and reduce each --
  the reference's --gradient_repacking, which re-shapes the reduction
  granularity away from tensor boundaries so chunks pipeline
  (ref: batch_allreduce.py:391-481 _TensorPacker)."""
  spec = AllReduceSpecTuple(alg="psum", shards=1, limit=None)
  leaves, treedef = jax.tree_util.tree_flatten(grads)
  vec, meta = pack_tensors(leaves, multiple_of=num_replicas)
  num_chunks = max(1, int(num_chunks))
  chunk = -(-vec.shape[0] // num_chunks)
  pad = chunk * num_chunks - vec.shape[0]
  size = vec.shape[0]
  if pad:
    vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
  parts = [_reduce_packed(part, spec, axis_name, compact_dtype)
           for part in jnp.split(vec, num_chunks)]
  vec = jnp.concatenate(parts)[:size]
  return jax.tree_util.tree_unflatten(treedef,
                                      unpack_tensors(vec, meta))


def hier_reduce(grads, axis_name, num_groups: int = 2, compact_dtype=None,
                groups=None):
  """Default-path two-level reduction (ref: --hierarchical_copy,
  batch_allreduce.py:173-267 HierarchicalCopy): on TPU, a grouped psum
  within device groups (process/host boundaries via ``groups``, else
  contiguous) then across them."""
  def one(x):
    orig = x.dtype
    if compact_dtype is not None and x.dtype != compact_dtype:
      x = x.astype(compact_dtype)
    return _hier(x, axis_name, num_groups, groups=groups).astype(orig)
  return jax.tree.map(one, grads)


def _warn_hier_selected(source: str) -> None:
  """One-line operator warning at hier selection time.

  The 'hier' algorithm is UNVALIDATED AT SCALE: its two-level ring
  decomposition has only ever been measured on the single-chip /
  virtual-mesh configurations this repo can reach (PERF.md; VERDICT
  weak #4) -- the default remains psum, which XLA lowers to
  topology-aware ICI rings itself. On a single-process mesh the
  process/host boundary hier exists to exploit does not exist, so the
  decomposition can only add latency over the fused psum."""
  from kf_benchmarks_tpu.utils import log as log_util
  if jax.process_count() > 1:
    return
  log_util.log_fn(
      f"Warning: 'hier' all-reduce selected ({source}) on a "
      "single-process mesh: the two-level decomposition is unvalidated "
      "at scale and has no host boundary to exploit here -- the psum "
      "default is the measured-fast path (PERF.md)")


def build_reducer(params):
  """Flag-selected gradient reducer for the replicated-family strategies,
  or None for the direct-pmean default (ref selection:
  batch_allreduce.py:300-317 algorithm_from_params -- spec > repacking >
  small-grad aggregation > hierarchical copy > plain copy).

  Returns fn(grads, axis_name) or None. compact_gradient_transfer rides
  every packed path when reduced precision is on (the fp16-compaction
  analog; bf16 wire format on TPU) or under the explicit f32 opt-in
  (--compact_gradient_transfer_f32; compact_wire_dtype)."""
  compact = compact_wire_dtype(params)
  if params.all_reduce_spec:
    return build_planner(params).reduce
  if params.gradient_repacking:
    return lambda g, ax: repack_reduce(
        g, ax, params.gradient_repacking, params.num_devices, compact)
  if params.agg_small_grads_max_bytes > 0:
    return lambda g, ax: pack_small_reduce(
        g, ax, params.agg_small_grads_max_bytes,
        params.agg_small_grads_max_group, params.num_devices, compact)
  if params.hierarchical_copy:
    # Groups come from real topology (process/host boundaries) on a
    # multi-process mesh, so the intra-group ring rides ICI; num_groups
    # defaults to the process count there and to the reference's 2-group
    # shape single-process (ref: batch_allreduce.py:173-267).
    _warn_hier_selected("--hierarchical_copy")
    from kf_benchmarks_tpu.parallel import mesh as mesh_lib
    devices = mesh_lib.get_devices(params.device, params.num_devices)
    groups = topology_groups(devices, num_groups=jax.process_count()
                             if jax.process_count() > 1 else 2)
    return lambda g, ax: hier_reduce(g, ax, compact_dtype=compact,
                                     groups=groups)
  return None


def build_planner(params) -> Optional[CollectivePlanner]:
  """Construct the planner from --all_reduce_spec (ref selection:
  batch_allreduce.py:300-317 algorithm_from_params), honoring the
  agg_small_grads group cap and 16-bit wire compaction."""
  if not params.all_reduce_spec:
    return None
  tuples = parse_all_reduce_spec(params.all_reduce_spec)
  if any(t.alg == "hier" for t in tuples):
    _warn_hier_selected(f"--all_reduce_spec={params.all_reduce_spec}")
  compact = compact_wire_dtype(params)
  return CollectivePlanner(tuples, num_replicas_hint=params.num_devices,
                           agg_max_bytes=params.agg_small_grads_max_bytes,
                           agg_max_group=params.agg_small_grads_max_group,
                           compact_dtype=compact)
