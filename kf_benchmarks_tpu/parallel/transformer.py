"""Composed parallelism: a decoder-only LM trained over dp x sp x tp
(x ep via Switch-MoE blocks), or dp x pp x sp x tp with the layer
stack sharded over the GPipe stage axis (make_pipelined_train_step).

Beyond-reference capability, and the composition proof for the
parallel/ primitives: one shard_map training step over a
('replica', 'seq', 'tensor') mesh where

* the batch axis rides data parallelism ('replica'),
* the sequence axis rides ring attention ('seq',
  parallel/sequence.py) so context length scales with ring size,
* heads + MLP features ride Megatron sharding ('tensor',
  parallel/tensor.py) with one psum per attention/MLP block.

Gradients for axis-replicated parameters are pmean-ed over the data
and sequence axes (tensor-sharded leaves keep their shard gradients),
so the whole step is a single jit -- XLA overlaps the ring permutes,
the block matmuls, and the gradient reduction. Numerical equivalence
of loss AND the trained parameters against a single-device dense
implementation is pinned by tests/test_transformer_parallel.py.

The reference has nothing in this family (its parallelism is batch-only,
SURVEY 2.3/5.7); this module is the long-context/distributed design the
TPU rebuild treats as first-class.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kf_benchmarks_tpu.parallel import expert as ep_lib
from kf_benchmarks_tpu.parallel import pipeline as pp_lib
from kf_benchmarks_tpu.parallel import sequence as seq_lib
from kf_benchmarks_tpu.parallel import tensor as tp_lib
from kf_benchmarks_tpu.parallel.mesh import BATCH_AXIS, REPLICA_AXIS

SEQ_AXIS = seq_lib.SEQ_AXIS
TENSOR_AXIS = tp_lib.TENSOR_AXIS


def _data_axis(mesh: Mesh) -> str:
  """The data-parallel axis name of a composed-trainer mesh: 'batch' on
  the shared named-mesh family (compose_on_model_axis -- the same axis
  system as parallel/mesh.py build_mesh_2d), 'replica' on the legacy
  3-D/4-D grids. Axis NAMES carry no numerics: the two families produce
  bit-identical programs (tests/test_transformer_parallel.py)."""
  return BATCH_AXIS if BATCH_AXIS in mesh.axis_names else REPLICA_AXIS


def init_params(key, *, vocab: int, d_model: int, n_layers: int,
                n_heads: int, head_dim: int, d_ff: int, max_len: int,
                moe_every: int = 0, n_experts: int = 0) -> Dict[str, Any]:
  """Global (unsharded) parameter pytree; sharding comes from the
  in_specs of make_train_step, so the same tree drives both the
  parallel step and the single-device reference.

  moe_every > 0 replaces every moe_every-th block's dense MLP with a
  Switch-MoE layer of n_experts experts (expert parallelism rides the
  REPLICA axis -- experts are sharded where the tokens already are).
  """
  if moe_every and n_experts < 1:
    raise ValueError(
        f"moe_every={moe_every} needs n_experts >= 1, got {n_experts} "
        f"(a zero-expert gate would only fail later inside switch_moe)")
  scale = 0.02
  ks = iter(jax.random.split(key, 4 + 8 * n_layers))
  params = {
      "embed": jax.random.normal(next(ks), (vocab, d_model)) * scale,
      "pos": jax.random.normal(next(ks), (max_len, d_model)) * scale,
      "ln_f": jnp.ones((d_model,)),
      "blocks": [],
  }
  for i in range(n_layers):
    block = {
        "ln1": jnp.ones((d_model,)),
        "wqkv": jax.random.normal(
            next(ks), (d_model, 3, n_heads, head_dim)) * scale,
        "wo": jax.random.normal(
            next(ks), (n_heads, head_dim, d_model)) * scale,
        "ln2": jnp.ones((d_model,)),
    }
    if moe_every and (i + 1) % moe_every == 0:
      block["gate_w"] = jax.random.normal(
          next(ks), (d_model, n_experts)) * scale
      block["ew1"] = jax.random.normal(
          next(ks), (n_experts, d_model, d_ff)) * scale
      block["eb1"] = jnp.zeros((n_experts, d_ff))
      block["ew2"] = jax.random.normal(
          next(ks), (n_experts, d_ff, d_model)) * scale
      block["eb2"] = jnp.zeros((n_experts, d_model))
    else:
      block["w1"] = jax.random.normal(next(ks), (d_model, d_ff)) * scale
      block["b1"] = jnp.zeros((d_ff,))
      block["w2"] = jax.random.normal(next(ks), (d_ff, d_model)) * scale
      block["b2"] = jnp.zeros((d_model,))
    params["blocks"].append(block)
  return params


def param_specs(params, data_axis: str = REPLICA_AXIS) -> Dict[str, Any]:
  """PartitionSpecs: tensor-sharded leaves on TENSOR_AXIS (heads for
  attention, features for the dense MLP); MoE expert stacks sharded on
  the DATA axis (the expert axis -- experts live where the tokens are;
  'batch' on compose_on_model_axis meshes); everything else
  replicated."""
  dense = {
      "w1": P(None, TENSOR_AXIS), "b1": P(TENSOR_AXIS),
      "w2": P(TENSOR_AXIS, None), "b2": P(),
  }
  moe = {
      "gate_w": P(),
      "ew1": P(data_axis), "eb1": P(data_axis),
      "ew2": P(data_axis), "eb2": P(data_axis),
  }
  blocks = []
  for bp in params["blocks"]:
    spec = {
        "ln1": P(), "ln2": P(),
        "wqkv": P(None, None, TENSOR_AXIS),
        "wo": P(TENSOR_AXIS),
    }
    spec.update(moe if "gate_w" in bp else dense)
    blocks.append(spec)
  return {"embed": P(), "pos": P(), "ln_f": P(), "blocks": blocks}


def stack_blocks(params):
  """Per-layer block list -> ONE stacked block pytree (leading layer
  axis on every leaf), the layout the scan-over-layers path consumes.

  Requires a homogeneous (dense) stack: MoE blocks are heterogeneous
  under moe_every and their capacity queues are per data shard -- the
  same restriction to_pipelined() enforces for the stage axis.
  """
  blocks = params["blocks"]
  if any("gate_w" in b for b in blocks):
    raise ValueError(
        "scan-over-layers requires a homogeneous (dense) layer stack; "
        "MoE blocks are heterogeneous -- use the unscanned "
        "make_train_step for dp x sp x tp x ep")
  out = {k: v for k, v in params.items() if k != "blocks"}
  out["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
  return out


def unstack_blocks(params):
  """Inverse of stack_blocks (so trained scanned state compares
  leaf-for-leaf against the per-layer oracle's)."""
  stacked = params["blocks"]
  n_layers = jax.tree.leaves(stacked)[0].shape[0]
  blocks = [jax.tree.map(lambda x: x[i], stacked)
            for i in range(n_layers)]
  out = {k: v for k, v in params.items() if k != "blocks"}
  out["blocks"] = blocks
  return out


def fsdp_stack_blocks(stacked_params, n_shards: int):
  """stack_blocks() tree -> FSDP storage: every 'blocks' leaf (L, *s)
  becomes its per-layer flat shard stack (L, n, k), k = ceil(prod(s)/n)
  -- the ops/sharded.py (n, k) layout applied per layer, sharded over
  the combined (data, seq) axes by :func:`fsdp_param_specs` so each
  device holds one (L, 1, k) slice. The scan body re-assembles ONE
  layer per iteration (--shard_params's composed-trainer leg)."""
  out = {k: v for k, v in stacked_params.items() if k != "blocks"}

  def f(x):
    n_layers = x.shape[0]
    size = int(x.size) // n_layers
    k = -(-size // n_shards)
    flat = jnp.pad(x.reshape(n_layers, size),
                   ((0, 0), (0, n_shards * k - size)))
    return flat.reshape(n_layers, n_shards, k)

  out["blocks"] = jax.tree.map(f, stacked_params["blocks"])
  return out


def fsdp_unstack_blocks(fsdp_params, block_template):
  """Inverse of :func:`fsdp_stack_blocks` (host-side; tests compare the
  trained FSDP state against the dense oracle's): (L, n, k) stacks
  flatten back per layer, pad drops, full shapes restore from
  ``block_template`` (the stacked blocks tree of the ORIGINAL
  layout)."""
  out = {k: v for k, v in fsdp_params.items() if k != "blocks"}

  def f(x, t):
    n_layers = x.shape[0]
    size = int(math.prod(t.shape[1:]))
    return jnp.asarray(x).reshape(n_layers, -1)[:, :size].reshape(
        tuple(t.shape)).astype(t.dtype)

  out["blocks"] = jax.tree.map(f, fsdp_params["blocks"], block_template)
  return out


def _fsdp_block_hook(block_template, axes):
  """Per-iteration FSDP gather for the scanned composed trainer: sliced
  per-layer flat shards (k,) -> the block's full param tree via one
  packed tiled all-gather over ``axes`` (the combined (data, seq)
  data-parallel axes); the custom_vjp backward reduce-scatters the
  block's cotangent as one packed psum_scatter in the same loop
  position -- the SUM the pre-summed gradient convention of
  make_train_step expects (the /n_data divide happens outside, as for
  every other leaf). Built on ops/sharded.py's shared packing
  primitives (packed_gather_rows / pack_cotangent_rows /
  split_shard_row) so the row addressing cannot drift from the
  benchmark leg's gather_params; only the reduction differs: SUM over
  the combined axes (one shard row per device) instead of
  gather_params' batch-mean + model sub-slice."""
  from kf_benchmarks_tpu.ops import sharded as sharded_lib
  t_leaves = jax.tree_util.tree_flatten(block_template)[0]
  shapes = tuple(tuple(t.shape) for t in t_leaves)
  dtypes = tuple(jnp.dtype(t.dtype).name for t in t_leaves)

  @functools.partial(jax.custom_vjp, nondiff_argnums=())
  def gather(shards):
    return sharded_lib.packed_gather_rows(axes, shapes, dtypes, shards)

  def fwd(shards):
    return gather(shards), None

  def bwd(_, cots):
    n = math.prod(lax.axis_size(a) for a in axes)
    mat, ks = sharded_lib.pack_cotangent_rows(cots, shapes, n,
                                              jnp.float32)
    # SUM over the data-parallel peers (matching the pre-summed
    # gradients of the replicated leaves): the tiled scatter over the
    # full n-device group hands each device exactly its own (1, K)
    # shard row -- the transpose of the gather's concatenation order.
    row = lax.psum_scatter(mat, axes, scatter_dimension=0,
                           tiled=True)[0]
    return (sharded_lib.split_shard_row(row, ks, dtypes),)

  gather.defvjp(fwd, bwd)

  def hook(lp):
    leaves, treedef = jax.tree_util.tree_flatten(lp)
    return jax.tree_util.tree_unflatten(treedef, list(gather(tuple(leaves))))

  return hook


def stacked_param_specs():
  """Specs for the stacked tree: a leading (replicated) layer axis on
  every block leaf; the tensor axis stays on the same dims as
  param_specs, shifted by one."""
  blocks = {
      "ln1": P(None), "ln2": P(None),
      "wqkv": P(None, None, None, TENSOR_AXIS),
      "wo": P(None, TENSOR_AXIS),
      "w1": P(None, None, TENSOR_AXIS), "b1": P(None, TENSOR_AXIS),
      "w2": P(None, TENSOR_AXIS, None), "b2": P(None),
  }
  return {"embed": P(), "pos": P(), "ln_f": P(), "blocks": blocks}


def fsdp_param_specs(data_axis: str):
  """Specs for an :func:`fsdp_stack_blocks` tree: every (L, n, k)
  blocks leaf shards its shard-row dim over the combined (data, seq)
  data-parallel axes (one row per device); non-block leaves keep the
  stacked layout's replication."""
  blocks_spec = P(None, (data_axis, SEQ_AXIS))
  return {"embed": P(), "pos": P(), "ln_f": P(),
          "blocks": {"ln1": blocks_spec, "ln2": blocks_spec,
                     "wqkv": blocks_spec, "wo": blocks_spec,
                     "w1": blocks_spec, "b1": blocks_spec,
                     "w2": blocks_spec, "b2": blocks_spec}}


def _rmsnorm(x, scale, eps=1e-6):
  x = x.astype(jnp.float32)
  return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
          ) * scale


def _embed_positions(params, tokens, *, seq_axis, sp_layout):
  """Token + positional embedding of the LOCAL (B, T_local) shard;
  positions follow the shard's GLOBAL offsets (stripe pair offsets
  under the zigzag layout)."""
  b, t = tokens.shape
  global_t = t * lax.axis_size(seq_axis)
  max_len = params["pos"].shape[0]
  if global_t > max_len:
    # Without this, dynamic_slice would CLAMP later shards' offsets and
    # silently reuse the last pos rows (the single-device oracle fails
    # loudly on the same config).
    raise ValueError(
        f"global sequence length {global_t} exceeds the positional "
        f"table max_len={max_len}")
  x = params["embed"][tokens]
  if sp_layout == "zigzag":
    stripe = t // 2
    zidx = 2 * lax.axis_size(seq_axis) - 1 - lax.axis_index(seq_axis)
    ar = jnp.arange(stripe)
    pos_idx = jnp.concatenate(
        [lax.axis_index(seq_axis) * stripe + ar, zidx * stripe + ar])
    return x + jnp.take(params["pos"], pos_idx, axis=0)
  pos0 = lax.axis_index(seq_axis) * t
  return x + lax.dynamic_slice_in_dim(params["pos"], pos0, t, axis=0)


def _attention_residual(lp, x, *, seq_axis, tensor_axis, sp_layout,
                        attn_inner_block=None):
  """ln -> qkv -> (ring|zigzag) attention -> output proj residual.

  Returns (x_new, h) where h is the post-attention rmsnorm the MLP/MoE
  half of the block consumes -- shared by the flat and the pipelined
  forward paths. ``attn_inner_block`` is the ring schedules' K/V
  sub-block tiling knob (sequence.py): long-context memory control for
  the composed trainer.
  """
  b, t, _ = x.shape
  d_model = lp["wqkv"].shape[0]
  heads_local, head_dim = lp["wqkv"].shape[2], lp["wqkv"].shape[3]
  h = _rmsnorm(x, lp["ln1"])
  qkv = tp_lib.column_parallel_dense(
      h, lp["wqkv"].reshape(d_model, 3 * heads_local * head_dim))
  qkv = qkv.reshape(b, t, 3, heads_local, head_dim)
  if sp_layout == "zigzag":
    att = seq_lib.ring_attention_zigzag(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], axis_name=seq_axis,
        inner_block=attn_inner_block)
  else:
    att = seq_lib.ring_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
        axis_name=seq_axis, causal=True,
        inner_block=attn_inner_block)
  x = x + tp_lib.row_parallel_dense(
      att.reshape(b, t, heads_local * head_dim),
      lp["wo"].reshape(heads_local * head_dim, d_model),
      axis_name=tensor_axis)
  return x, _rmsnorm(x, lp["ln2"])


def forward_local(params, tokens, *, seq_axis=SEQ_AXIS,
                  tensor_axis=TENSOR_AXIS, expert_axis=REPLICA_AXIS,
                  moe_capacity=None, sp_layout: str = "contiguous",
                  attn_inner_block=None, remat_policy=None,
                  fsdp_gather_hook=None):
  """Per-shard forward: tokens (B_local, T_local) -> (logits, moe_aux).

  Runs inside a shard_map body; params are the LOCAL shards
  (tensor-sharded leaves already sliced). MoE blocks (marked by a
  'gate_w' leaf) dispatch over ``expert_axis`` -- the data axis, where
  tokens are already sharded -- with per-shard capacity queues;
  moe_capacity=None means capacity = local token count (no drops).

  sp_layout='zigzag' expects the sequence axis sharded in
  sequence.zigzag_order (stripe pair (idx, 2n-1-idx) per device) and
  runs the load-balanced causal ring; positions follow the stripes.

  A ``params['blocks']`` that is a stack_blocks() pytree (leading layer
  axis) instead of a per-layer list runs the layer stack as ONE
  ``lax.scan`` body under ``jax.checkpoint`` -- compiled-program size
  and saved-residual footprint O(1) in depth instead of O(L).
  ``remat_policy`` is the explicit jax.checkpoint policy for that path
  (None = save nothing, recompute the whole block;
  e.g. jax.checkpoint_policies.dots_with_no_batch_dims_saveable keeps
  the matmul outputs and recomputes only the cheap elementwise work).
  """
  b, t = tokens.shape
  x = _embed_positions(params, tokens, seq_axis=seq_axis,
                       sp_layout=sp_layout)
  moe_aux = jnp.zeros((), jnp.float32)
  if not isinstance(params["blocks"], (list, tuple)):
    # Scanned stack (homogeneous by stack_blocks construction).
    def one_block(xm, lp):
      if fsdp_gather_hook is not None:
        # --shard_params's composed-trainer leg: lp arrives as flat
        # per-layer shards; ONE packed all-gather re-assembles this
        # block INSIDE the scan body (under the jax.checkpoint below,
        # so the backward re-gathers during recompute) and the hook's
        # backward reduce-scatters the block's cotangent in the same
        # position (_fsdp_block_hook).
        lp = fsdp_gather_hook(lp)
      xm, h = _attention_residual(lp, xm, seq_axis=seq_axis,
                                  tensor_axis=tensor_axis,
                                  sp_layout=sp_layout,
                                  attn_inner_block=attn_inner_block)
      xm = xm + tp_lib.parallel_mlp(h, lp["w1"], lp["b1"], lp["w2"],
                                    lp["b2"], axis_name=tensor_axis)
      return xm, None

    body = jax.checkpoint(one_block, policy=remat_policy,
                          prevent_cse=False)
    x, _ = lax.scan(body, x, params["blocks"])
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("btd,vd->btv", x,
                        params["embed"].astype(jnp.float32))
    return logits, moe_aux
  for lp in params["blocks"]:
    d_model = lp["wqkv"].shape[0]
    x, h = _attention_residual(lp, x, seq_axis=seq_axis,
                               tensor_axis=tensor_axis,
                               sp_layout=sp_layout,
                               attn_inner_block=attn_inner_block)
    if "gate_w" in lp:
      cap = (b * t) if moe_capacity is None else moe_capacity
      y, aux = ep_lib.switch_moe(
          h.reshape(b * t, d_model), lp["gate_w"], lp["ew1"],
          lp["eb1"], lp["ew2"], lp["eb2"], capacity=cap,
          axis_name=expert_axis)
      x = x + y.reshape(b, t, d_model)
      moe_aux = moe_aux + aux
    else:
      x = x + tp_lib.parallel_mlp(h, lp["w1"], lp["b1"], lp["w2"],
                                  lp["b2"], axis_name=tensor_axis)
  x = _rmsnorm(x, params["ln_f"])
  logits = jnp.einsum("btd,vd->btv", x,
                      params["embed"].astype(jnp.float32))
  return logits, moe_aux


def _reference_moe(h, lp, groups, capacity, layout="contiguous"):
  """Dense (single-device) Switch-MoE with the SAME per-shard queue
  semantics as the SPMD dispatch: tokens grouped as (replica, seq)
  shards in row-major order, capacity per expert PER GROUP. jnp
  throughout, so the oracle is differentiable.

  layout='zigzag' mirrors sp_layout='zigzag': seq shard s holds the
  stripe pair (s, 2*ns-1-s), in that in-shard order, so the capacity
  queues fill exactly as on the SPMD devices.
  """
  if layout not in ("contiguous", "zigzag"):
    raise ValueError(f"unknown moe layout {layout!r}")
  b, t, d = h.shape
  nr, ns = groups
  bl, tl = b // nr, t // ns
  e_global = lp["gate_w"].shape[1]
  out = jnp.zeros((b, t, d), h.dtype)
  aux = jnp.zeros((), jnp.float32)
  for r in range(nr):
    for s in range(ns):
      if layout == "zigzag":
        # Shard s of the SAME permutation the SPMD data path applies.
        cols = seq_lib.zigzag_order(t, ns).reshape(ns, tl)[s]
      else:
        cols = jnp.arange(s * tl, (s + 1) * tl)
      hg = h[r * bl:(r + 1) * bl, cols].reshape(
          bl * tl, d).astype(jnp.float32)
      probs = jax.nn.softmax(hg @ lp["gate_w"].astype(jnp.float32), -1)
      idx = jnp.argmax(probs, -1)
      assign = jax.nn.one_hot(idx, e_global, dtype=jnp.float32)
      pos = jnp.cumsum(assign, axis=0) - 1.0
      keep = assign * (pos < capacity)
      gate = jnp.max(probs, -1)
      hh = jax.nn.gelu(jnp.einsum("td,edf->tef", hg, lp["ew1"])
                       + lp["eb1"])
      y = jnp.einsum("tef,efd->ted", hh, lp["ew2"]) + lp["eb2"]
      picked = jnp.einsum("te,ted->td", keep, y) * gate[:, None]
      out = out.at[r * bl:(r + 1) * bl, cols].set(
          picked.reshape(bl, tl, d).astype(h.dtype))
      aux = aux + e_global * jnp.sum(
          jnp.mean(assign, 0) * jnp.mean(probs, 0))
  return out, aux / (nr * ns)


def forward_reference(params, tokens, moe_groups=(1, 1),
                      moe_capacity=None, moe_layout="contiguous"):
  """Single-device dense forward from the same GLOBAL params -- the
  equivalence oracle (and the degenerate 1-device program).

  moe_groups = (n_replica, n_seq) of the mesh being mirrored: MoE
  capacity queues are per data shard in the SPMD run, so the oracle
  reproduces that grouping (irrelevant when capacity is never hit).
  """
  b, t = tokens.shape
  x = params["embed"][tokens] + params["pos"][:t]
  moe_aux = jnp.zeros((), jnp.float32)
  for lp in params["blocks"]:
    d_model = lp["wqkv"].shape[0]
    heads, head_dim = lp["wqkv"].shape[2], lp["wqkv"].shape[3]
    h = _rmsnorm(x, lp["ln1"])
    qkv = (h @ lp["wqkv"].reshape(d_model, 3 * heads * head_dim)
           ).reshape(b, t, 3, heads, head_dim)
    att = seq_lib.full_attention(qkv[:, :, 0], qkv[:, :, 1],
                                 qkv[:, :, 2], causal=True)
    x = x + att.reshape(b, t, heads * head_dim) @ lp["wo"].reshape(
        heads * head_dim, d_model)
    h = _rmsnorm(x, lp["ln2"])
    if "gate_w" in lp:
      nr, ns = moe_groups
      cap = ((b // nr) * (t // ns) if moe_capacity is None
             else moe_capacity)
      y, aux = _reference_moe(h, lp, moe_groups, cap,
                              layout=moe_layout)
      x = x + y
      moe_aux = moe_aux + aux
    else:
      x = x + jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
  x = _rmsnorm(x, params["ln_f"])
  logits = jnp.einsum("btd,vd->btv", x,
                      params["embed"].astype(jnp.float32))
  return logits, moe_aux


def _loss_from_logits(logits, labels):
  logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
  ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
  return -jnp.mean(ll)


def reference_loss(params, tokens, labels, moe_groups=(1, 1),
                   moe_capacity=None, moe_aux_weight=0.01,
                   moe_layout="contiguous"):
  logits, aux = forward_reference(params, tokens,
                                  moe_groups=moe_groups,
                                  moe_capacity=moe_capacity,
                                  moe_layout=moe_layout)
  return _loss_from_logits(logits, labels) + moe_aux_weight * aux


def _grid_mesh(sizes, axis_names, devices=None) -> Mesh:
  import numpy as np
  devices = devices if devices is not None else jax.devices()
  need = math.prod(sizes)
  if len(devices) < need:
    raise ValueError(f"need {need} devices, have {len(devices)}")
  return Mesh(np.array(devices[:need]).reshape(sizes), axis_names)


def build_mesh(n_replica: int, n_seq: int, n_tensor: int,
               devices=None) -> Mesh:
  return _grid_mesh((n_replica, n_seq, n_tensor),
                    (REPLICA_AXIS, SEQ_AXIS, TENSOR_AXIS), devices)


def compose_on_model_axis(n_batch: int, n_seq: int, n_tensor: int,
                          devices=None) -> Mesh:
  """The composed trainer on the SHARED axis system of the named 2-D
  mesh (parallel/mesh.py build_mesh_2d): the 'model' axis of a
  ``n_batch x (n_seq * n_tensor)`` 2-D mesh refined into its seq x
  tensor factors -- ``('batch', 'seq', 'tensor')``, same device order
  (row-major), same data axis name the core train step uses. One axis
  system for every parallelism family: collectives over
  ``('seq', 'tensor')`` are collectives over the 2-D family's 'model'
  axis, and the data-parallel legs (batch sharding, gradient pmeans)
  ride 'batch' exactly as train_step.py's sharded branch does --
  instead of the bespoke 'replica'-named wiring of :func:`build_mesh`.
  make_train_step detects the family from the axis names; programs are
  bit-identical across the two namings
  (tests/test_transformer_parallel.py)."""
  return _grid_mesh((n_batch, n_seq, n_tensor),
                    (BATCH_AXIS, SEQ_AXIS, TENSOR_AXIS), devices)


def make_train_step(mesh: Mesh, params_template, learning_rate: float,
                    moe_capacity=None, moe_aux_weight: float = 0.01,
                    sp_layout: str = "contiguous",
                    attn_inner_block=None, scan_layers: bool = False,
                    remat_policy=None, fsdp_blocks: bool = False):
  """Jitted SGD train step over GLOBAL (params, tokens, labels):
  tokens/labels (batch, seq) in NORMAL order, sharded (data, seq) --
  the data axis is 'batch' on compose_on_model_axis meshes, 'replica'
  on legacy build_mesh grids; params per param_specs. MoE blocks (if any in the template) add
  expert parallelism over the replica axis and fold the Switch aux
  loss in at ``moe_aux_weight``. sp_layout='zigzag' permutes the data
  into sequence.zigzag_order at the jit boundary and runs the
  load-balanced causal ring (input pipelines that store sequences
  pre-permuted should shard_map forward_local directly). Returns
  (new_params, loss) -- the token-mean loss is permutation-invariant,
  so the layout never leaks to the caller.

  scan_layers=True expects a stack_blocks() params tree and runs the
  layer stack as one scanned+rematerialized body (forward_local);
  ``remat_policy`` is its explicit jax.checkpoint policy. Losses and
  trained parameters stay numerically equivalent to the unscanned
  step (tests/test_transformer_parallel.py pins it)."""
  if sp_layout not in ("contiguous", "zigzag"):
    raise ValueError(f"unknown sp_layout {sp_layout!r}")
  data_axis = _data_axis(mesh)
  fsdp_hook = None
  if fsdp_blocks:
    # --shard_params's composed-trainer leg: the scanned layer stack
    # stores as fsdp_stack_blocks() per-layer shards over the combined
    # (data, seq) axes; each scan iteration gathers ONE block inside
    # the body and its cotangent reduce-scatters there too
    # (_fsdp_block_hook). Tensor sharding is a DIFFERENT decomposition
    # of the same leaves (each device holds a head/feature slice, not
    # a flat range), so composing both on one leaf is out of scope --
    # FSDP owns the whole block here.
    if not scan_layers:
      raise ValueError(
          "fsdp_blocks=True requires scan_layers=True: the per-block "
          "gather lives in the scanned body (an unscanned stack would "
          "re-assemble every layer at once -- full residency, nothing "
          "sharded)")
    if int(mesh.shape[TENSOR_AXIS]) != 1:
      raise ValueError(
          "fsdp_blocks=True requires a 1-wide tensor axis: tensor "
          "sharding slices block leaves by head/feature while FSDP "
          "slices them by flat range -- one leaf cannot carry both "
          f"decompositions (got tensor axis {mesh.shape[TENSOR_AXIS]})")
    block_template = params_template["blocks"]
    if isinstance(block_template, (list, tuple)):
      raise ValueError(
          "fsdp_blocks=True takes the ORIGINAL stack_blocks() tree as "
          "params_template (full per-layer shapes drive the gather "
          "spec); convert the live params with fsdp_stack_blocks")
    per_layer_template = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(tuple(t.shape)[1:], t.dtype),
        block_template)
    fsdp_hook = _fsdp_block_hook(per_layer_template,
                                 (data_axis, SEQ_AXIS))
    specs = fsdp_param_specs(data_axis)
  elif scan_layers:
    if isinstance(params_template["blocks"], (list, tuple)):
      raise ValueError(
          "scan_layers=True takes a stack_blocks() params tree "
          "(leading layer axis), not the per-layer block list")
    specs = stacked_param_specs()
  else:
    specs = param_specs(params_template, data_axis=data_axis)
  data_spec = P(data_axis, SEQ_AXIS)
  n_data = mesh.shape[data_axis] * mesh.shape[SEQ_AXIS]
  n_seq = mesh.shape[SEQ_AXIS]

  def body(params, tokens, labels):
    def local_loss(p):
      if fsdp_hook is not None:
        # Local storage view: (L, 1, k) shard rows -> the (L, k) per-
        # layer flat shards the scan slices (the squeeze sits inside
        # the loss so the gradient lands back on the storage layout).
        p = dict(p)
        p["blocks"] = jax.tree.map(lambda x: x[:, 0], p["blocks"])
      logits, moe_aux = forward_local(
          p, tokens, moe_capacity=moe_capacity, sp_layout=sp_layout,
          attn_inner_block=attn_inner_block,
          remat_policy=remat_policy,
          expert_axis=data_axis,
          fsdp_gather_hook=fsdp_hook)
      return (_loss_from_logits(logits, labels)
              + moe_aux_weight * moe_aux)

    loss, grads = jax.value_and_grad(local_loss)(params)
    # Token mean over the whole global batch: every shard holds the
    # same token count, so the pmean of shard means is the global mean.
    loss = lax.pmean(loss, (data_axis, SEQ_AXIS))
    # shard_map's vma-aware autodiff has already psum-ed each grad over
    # every axis its parameter is unvarying on (the transpose of the
    # implicit broadcast), so each leaf holds the SUM of the per-data-
    # shard contributions -- measured 4.0x on a (2,2,*) mesh. Turning
    # the global token-sum objective into the token mean is a plain
    # divide; no further collectives are needed (tensor-sharded leaves
    # keep their shard-local slice gradients).
    grads = jax.tree.map(lambda g: g / n_data, grads)
    new_params = jax.tree.map(lambda p, g: p - learning_rate * g,
                              params, grads)
    return new_params, loss

  sharded = jax.shard_map(
      body, mesh=mesh,
      in_specs=(specs, data_spec, data_spec),
      out_specs=(specs, P()))
  if sp_layout == "contiguous":
    return jax.jit(sharded, donate_argnums=(0,))

  def call(params, tokens, labels):
    order = seq_lib.zigzag_order(tokens.shape[1], n_seq)
    return sharded(params, jnp.take(tokens, order, axis=1),
                   jnp.take(labels, order, axis=1))

  return jax.jit(call, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# The pipeline (stage) axis composed in: dp x pp x sp x tp in one jit.
#
# Scope: pipeline stages require a HOMOGENEOUS layer stack (every block
# the same pytree structure, so stages stack into leaves with a leading
# (n_stages, layers_per_stage) axis). MoE blocks are heterogeneous
# under moe_every and their capacity queues are defined per data shard,
# not per microbatch -- composing ep with pp would change the queue
# semantics silently -- so to_pipelined() rejects MoE trees; MoE
# composition is served by make_train_step (dp x sp x tp x ep).
# ---------------------------------------------------------------------------

STAGE_AXIS = pp_lib.STAGE_AXIS


def to_pipelined(params, n_stages: int):
  """Standard param tree -> pipelined tree: the per-layer block list
  becomes one stacked pytree with leading (n_stages, layers_per_stage)
  axes (sharded on STAGE_AXIS by pipelined_param_specs)."""
  blocks = params["blocks"]
  if any("gate_w" in b for b in blocks):
    raise ValueError(
        "pipeline composition requires a homogeneous (dense) layer "
        "stack; MoE blocks change per-shard capacity semantics under "
        "microbatching -- use make_train_step for dp x sp x tp x ep")
  if len(blocks) % n_stages != 0:
    raise ValueError(f"{len(blocks)} layers not divisible by "
                     f"{n_stages} stages")
  lps = len(blocks) // n_stages
  stacked = jax.tree.map(
      lambda *xs: jnp.stack(xs).reshape(
          (n_stages, lps) + xs[0].shape), *blocks)
  out = {k: v for k, v in params.items() if k != "blocks"}
  out["blocks"] = stacked
  return out


def from_pipelined(pparams):
  """Inverse of to_pipelined: stacked stage tree -> per-layer list (so
  the trained state compares leaf-for-leaf against the oracle's)."""
  stacked = pparams["blocks"]
  n_stages, lps = jax.tree.leaves(stacked)[0].shape[:2]
  flat = jax.tree.map(
      lambda x: x.reshape((n_stages * lps,) + x.shape[2:]), stacked)
  blocks = [jax.tree.map(lambda x: x[i], flat)
            for i in range(n_stages * lps)]
  out = {k: v for k, v in pparams.items() if k != "blocks"}
  out["blocks"] = blocks
  return out


def pipelined_param_specs():
  """Specs for the pipelined tree: stage axis leads every block leaf;
  the tensor axis stays on the same dims as param_specs, shifted by
  the two stacking axes."""
  blocks = {
      "ln1": P(STAGE_AXIS), "ln2": P(STAGE_AXIS),
      "wqkv": P(STAGE_AXIS, None, None, None, TENSOR_AXIS),
      "wo": P(STAGE_AXIS, None, TENSOR_AXIS),
      "w1": P(STAGE_AXIS, None, None, TENSOR_AXIS),
      "b1": P(STAGE_AXIS, None, TENSOR_AXIS),
      "w2": P(STAGE_AXIS, None, TENSOR_AXIS, None),
      "b2": P(STAGE_AXIS),
  }
  return {"embed": P(), "pos": P(), "ln_f": P(), "blocks": blocks}


def forward_local_pipelined(params, tokens, *, num_microbatches: int,
                            seq_axis=SEQ_AXIS, tensor_axis=TENSOR_AXIS,
                            stage_axis=STAGE_AXIS,
                            sp_layout: str = "contiguous",
                            attn_inner_block=None):
  """Per-shard forward with the layer stack sharded over the stage
  axis: embed/positions everywhere (stage-replicated), the GPipe scan
  (parallel/pipeline.py) carries activations stage-to-stage via
  ppermute, ring attention and Megatron psums run INSIDE each stage
  tick on the seq/tensor axes, and the retired microbatches are
  broadcast back so the loss/unembed is stage-replicated again."""
  x = _embed_positions(params, tokens, seq_axis=seq_axis,
                       sp_layout=sp_layout)
  n_local = jax.tree.leaves(params["blocks"])[0].shape[0]
  if n_local != 1:
    # Same hazard make_pipeline guards: a stage count that merely
    # DIVIDES the axis size shards legally but p[0] would silently
    # drop every local stage after the first.
    raise ValueError(
        f"blocks leading axis must equal the '{stage_axis}' mesh axis "
        f"size (one stage per device); got a local slice of {n_local} "
        f"stages")
  local = jax.tree.map(lambda p: p[0], params["blocks"])
  lps = local["ln1"].shape[0]

  def stage_fn(p, xm):
    for i in range(lps):
      lp = jax.tree.map(lambda a: a[i], p)
      xm, h = _attention_residual(lp, xm, seq_axis=seq_axis,
                                  tensor_axis=tensor_axis,
                                  sp_layout=sp_layout,
                                  attn_inner_block=attn_inner_block)
      xm = xm + tp_lib.parallel_mlp(h, lp["w1"], lp["b1"], lp["w2"],
                                    lp["b2"], axis_name=tensor_axis)
    return xm

  x = pp_lib.spmd_pipeline(stage_fn, local, x, num_microbatches,
                           axis_name=stage_axis)
  x = _rmsnorm(x, params["ln_f"])
  return jnp.einsum("btd,vd->btv", x,
                    params["embed"].astype(jnp.float32))


def build_mesh_pp(n_replica: int, n_stage: int, n_seq: int,
                  n_tensor: int, devices=None) -> Mesh:
  return _grid_mesh(
      (n_replica, n_stage, n_seq, n_tensor),
      (REPLICA_AXIS, STAGE_AXIS, SEQ_AXIS, TENSOR_AXIS), devices)


def make_pipelined_train_step(mesh: Mesh, pparams_template,
                              learning_rate: float,
                              num_microbatches: int,
                              sp_layout: str = "contiguous",
                              attn_inner_block=None):
  """Jitted SGD step over the 4-D (replica, stage, seq, tensor) mesh.

  pparams_template is a to_pipelined() tree; tokens/labels are GLOBAL
  (batch, seq) in normal order, sharded (replica, seq) and replicated
  over stage/tensor. GPipe with full-batch SGD is mathematically the
  sequential step, so loss AND trained params match the single-device
  oracle (tests/test_transformer_parallel.py); num_microbatches must
  divide the LOCAL batch (global batch / n_replica).
  """
  if sp_layout not in ("contiguous", "zigzag"):
    raise ValueError(f"unknown sp_layout {sp_layout!r}")
  del pparams_template  # shape-independent: specs are structural
  specs = pipelined_param_specs()
  data_spec = P(REPLICA_AXIS, SEQ_AXIS)
  n_data = mesh.shape[REPLICA_AXIS] * mesh.shape[SEQ_AXIS]
  n_seq = mesh.shape[SEQ_AXIS]

  def body(params, tokens, labels):
    def local_loss(p):
      logits = forward_local_pipelined(
          p, tokens, num_microbatches=num_microbatches,
          sp_layout=sp_layout, attn_inner_block=attn_inner_block)
      return _loss_from_logits(logits, labels)

    loss, grads = jax.value_and_grad(local_loss)(params)
    loss = lax.pmean(loss, (REPLICA_AXIS, SEQ_AXIS))
    # Same pre-summed-gradient accounting as make_train_step: data-axis
    # sums -> global token mean by a divide. Stage-sharded block leaves
    # vary on the stage axis, so their gradients stay stage-local, just
    # as tensor-sharded leaves stay shard-local.
    grads = jax.tree.map(lambda g: g / n_data, grads)
    new_params = jax.tree.map(lambda p, g: p - learning_rate * g,
                              params, grads)
    return new_params, loss

  sharded = jax.shard_map(
      body, mesh=mesh,
      in_specs=(specs, data_spec, data_spec),
      out_specs=(specs, P()))
  if sp_layout == "contiguous":
    return jax.jit(sharded, donate_argnums=(0,))

  def call(params, tokens, labels):
    order = seq_lib.zigzag_order(tokens.shape[1], n_seq)
    return sharded(params, jnp.take(tokens, order, axis=1),
                   jnp.take(labels, order, axis=1))

  return jax.jit(call, donate_argnums=(0,))
