"""Jitted train/eval step construction.

This is the TPU-native heart of the framework, replacing the reference's
graph build + per-tower loop + sess.run (ref: benchmark_cnn.py:2619-2731
_build_model, :2958-3209 add_forward_pass_and_gradients, :786-884
benchmark_one_step). Design:

* One SPMD program over a jax.sharding.Mesh: the 1-D 'replica' mesh for
  the replicated/gossip families, or the named 2-D ('batch', 'model')
  mesh (parallel/mesh.py build_mesh_2d) behind --mesh_shape /
  --shard_optimizer_state, where the batch shards over 'batch' and the
  ZeRO state shards span both axes (ops/sharded.py).
* Per-replica state convention: every TrainState leaf carries a leading
  replica dimension sharded P('replica') -- the exact analog of the
  reference's per-GPU variable copies (v0..vN scopes,
  variable_mgr.py:175-177, :277-368). Replicated strategies keep the
  copies bit-identical via collectives; independent/gossip strategies let
  them diverge, which pmap-style stacked state expresses naturally.
* Strategy hooks (parallel/strategies.py) run inside the shard_mapped
  body: gradient psum for replicated/sync-SGD, ppermute weight gossip for
  pair-averaging, weight pmean for SMA.
* Loss scaling: the reference's auto-loss-scale state machine
  (variable_mgr_util.py:51-139) is carried in TrainState and stepped with
  jnp.where -- halve-on-nonfinite + skip update, double every N clean
  steps.
* bf16: activations/compute in bfloat16 when --use_fp16 on TPU; params
  stay fp32 master copies (the fp16 custom-getter analog).
"""

from __future__ import annotations

import dataclasses
import enum
import inspect
import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
import flax
import optax

from kf_benchmarks_tpu import elastic as elastic_lib
from kf_benchmarks_tpu import telemetry as telemetry_lib
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.ops import sharded as sharded_lib
from kf_benchmarks_tpu.parallel import kungfu
from kf_benchmarks_tpu.parallel import mesh as mesh_lib
from kf_benchmarks_tpu.parallel.mesh import (BATCH_AXIS, MODEL_AXIS,
                                             REPLICA_AXIS)

# The phases the step program names (``jax.named_scope`` in
# make_step_fns). Every device operation's ``op_name`` carries the scope
# it was traced under, and the benchmark's trace reader books the step's
# time by them (benchmarks/spans.py); run stats carry this list as
# ``step_scopes`` so that the reader can hold a trace to it. The names
# are METADATA: they are not in the persistent compile cache's key, so a
# change to the scopes alone is served the old executable, old names
# and all, from a warm cache (CLAUDE.md, observability notes). A model
# may name further scopes INSIDE ``forward`` (mla_moe_lm:
# ``mla_attention``, ``attention_core``, ``moe_route``, ``moe_experts``,
# ``shared_expert``, ``mtp``, ``lm_head``; read by
# benchmarks/lm_scopes.py). They are other path components than these
# four: the reader's SCOPE_RE matches these four names only, so its six
# parts and ``check_scopes`` read as before.
STEP_SCOPES = ("forward", "exchange", "metrics", "optimizer_apply")


@flax.struct.dataclass
class TrainState:
  step: Any
  params: Any
  opt_state: Any
  batch_stats: Any
  loss_scale: Any
  loss_scale_normal_steps: Any
  rng: Any
  # Transient double-buffers for the staleness modes (SURVEY 7.4): the
  # XLA analog of the reference's StagingAreas. Holds 'deferred_grads'
  # under --variable_consistency=relaxed (ref: batch_allreduce.py:353-388
  # one-step-stale gradients) and/or 'staged_params' under --staged_vars
  # (ref: variable_mgr.py:246-274 staged variable reads). Not part of
  # checkpoints: a restart warms up with zeros/fresh copies exactly like
  # the reference's StagingArea warmup ops.
  buffers: Any = flax.struct.field(default_factory=dict)


def _is_batch_norm_param(path) -> bool:
  """L2 filtering: the reference excludes batch-norm variables from weight
  decay (ref: models/model.py filter_l2_loss_vars; benchmark_cnn.py:3078-3099)."""
  return any("bn" in str(k).lower() or "batchnorm" in str(k).lower()
             for k in path)


def l2_loss(params, single_op: bool = False):
  """0.5 * sum of squares over non-BN params (tf.nn.l2_loss semantics,
  ref: benchmark_cnn.py:3078-3099). ``single_op`` concatenates first
  (ref --single_l2_loss_op); numerically identical, kept as a knob."""
  leaves = [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if not _is_batch_norm_param(path)]
  if not leaves:
    return jnp.float32(0.0)
  if single_op:
    flat_vec = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                for l in leaves])
    return 0.5 * jnp.sum(flat_vec * flat_vec)
  return 0.5 * sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                   for l in leaves)


def _l2_loss_mixed(params, shard_prefixes, axis_all, single_op=False):
  """:func:`l2_loss` over a mixed FSDP tree (--shard_params on a
  scanned-stack model): non-prefix leaves are the gathered FULL values
  and keep the exact tf.nn.l2_loss formula; leaves under
  ``shard_prefixes`` are flat local shards of the scanned stacks, so
  their term reduces shard-locally and psums over the whole mesh --
  exact in value (the shards tile the stack exactly once and the zero
  pad contributes nothing) but reassociated, hence not bit-identical
  to the replicated-param L2 (logged once by make_step_fns)."""
  full_leaves, shard_leaves = [], []
  for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
    if _is_batch_norm_param(path):
      continue
    if sharded_lib.top_level_key(path) in shard_prefixes:
      shard_leaves.append(leaf)
    else:
      full_leaves.append(leaf)
  if single_op and full_leaves:
    flat_vec = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                for l in full_leaves])
    base = 0.5 * jnp.sum(flat_vec * flat_vec)
  else:
    base = 0.5 * sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                     for l in full_leaves) if full_leaves \
        else jnp.float32(0.0)
  if shard_leaves:
    local = 0.5 * sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                      for l in shard_leaves)
    base = base + lax.psum(local, axis_all)
  return base


def _sync_schedule_counts(src_state, dst_state, bump: int = 0):
  """Copy every ``count`` leaf of ``src_state`` (+``bump``) into
  ``dst_state``.

  optax keys schedules and bias correction on the optimizer's internal
  update count. When one lockstep round applies the optimizer several
  times (async-PS sequential apply), the framework's time base is still
  the ROUND: without this, an N-replica round would advance count-keyed
  LR schedules N times -- decaying N times too early and diverging from
  the logged lr_fn(step).
  """
  src = {jax.tree_util.keystr(p): leaf for p, leaf in
         jax.tree_util.tree_flatten_with_path(src_state)[0]}

  def fix(path, leaf):
    if path and getattr(path[-1], "name", None) == "count":
      return src[jax.tree_util.keystr(path)] + bump
    return leaf

  flat, treedef = jax.tree_util.tree_flatten_with_path(dst_state)
  return jax.tree_util.tree_unflatten(
      treedef, [fix(p, l) for p, l in flat])


def _reduce_unclaimed(grads, claimed, reduce):
  """``reduce`` over the leaves of ``grads`` whose path (a tuple of key
  names) is not in ``claimed``; those pass through as they are: the
  factor data plane made them the replica mean in the backward pass
  (parallel/kungfu.py FactorExchange)."""
  flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
  done = [tuple(k.key for k in path) in claimed for path, _ in flat]
  if sum(done) != len(claimed):
    raise ValueError(
        f"factor exchange: claimed kernels {sorted(claimed)} are not all "
        "leaves of the gradient tree; a leaf reduced in the backward "
        "pass AND here would still be the mean, but the step would not "
        "be the program its counter describes")
  rest = iter(jax.tree.leaves(reduce(treedef.unflatten(
      [None if d else g for d, (_, g) in zip(done, flat)]))))
  return treedef.unflatten(
      [g if d else next(rest) for d, (_, g) in zip(done, flat)])


class Exchange(enum.Enum):
  """How a step's local gradients become the ones its optimizer applies.
  ONE of these per step program, chosen by :func:`plan_step`."""
  # ``strategy.reduce_gradients`` over the whole tree: a built reducer,
  # the sum, nothing at all (local gradients), or the plain replica
  # mean where the factor plane may not engage.
  STRATEGY = "strategy"
  # The plain replica mean with two data planes (parallel/kungfu.py): a
  # dense kernel larger than its batch leaves the backward pass as the
  # mean already, ``strategy.reduce_gradients`` takes the leaves left.
  FACTORED_MEAN = "factored_mean"
  # ZeRO (ops/sharded.py scatter_mean): reduce-scatter of the batch-axis
  # mean onto this device's flat 1/n shard.
  ZERO_SCATTER = "zero_scatter"
  # FSDP under accumulation: the whole tree gathered before the
  # microbatch scan, the accumulated gradients scattered after it.
  FSDP_SCATTER = "fsdp_scatter"
  # FSDP: the per-bucket / per-block gathers' backward pass has
  # reduce-scattered every cotangent; nothing is left to exchange.
  FSDP_IN_BACKWARD = "fsdp_in_backward"


class Apply(enum.Enum):
  """How the optimizer meets the exchanged gradients."""
  PLAIN = "plain"            # one update of the whole per-replica tree
  SEQUENTIAL = "sequential"  # async PS: every replica's own, in turn
  SHARD = "shard"            # ZeRO / FSDP: on this device's 1/n shard


@dataclasses.dataclass(frozen=True)
class StepPlan:
  """Everything about a step program that is decided before it is
  traced (:func:`plan_step`); the stages below read it and decide
  nothing themselves."""
  # Axis system. 1-D ('replica',) meshes keep the exact legacy program
  # (the golden contracts pin it); the 2-D ('batch', 'model') mesh
  # behind --mesh_shape/--shard_optimizer_state shards the batch over
  # 'batch' only (model-axis peers re-compute the same shard) while the
  # stacked state spans both axes.
  axis_data: str
  axis_all: Any
  num_replicas: int
  data_replicas: int
  exchange: Exchange
  apply: Apply
  # --partitioner=gspmd: the per-axis form of the tuple-axis gathers.
  use_gspmd: bool
  num_grad_accum: int
  steps_per_dispatch: int
  # Loss scale: the value a run starts from, whether the gradients are
  # unscaled, whether the state machine runs and its doubling period.
  init_loss_scale: float
  use_loss_scale: bool
  auto_loss_scale: bool
  inc_every_n: Any
  relaxed: bool
  staged_vars: bool
  health_stats: bool
  noise_scale: bool
  weight_decay: float
  single_l2_loss_op: bool
  training_accuracy: bool
  # Total steps of a module with a training-progress schedule (NASNet
  # drop-path's global-step ramp, ref: nasnet_utils.py:407-439, takes
  # ``progress`` = step / total); 0: the module takes none.
  progress_steps: int
  # --shard_params: the full-shape (abstract) parameter tree, the
  # top-level keys whose stacks the MODULE gathers per scanned block,
  # and the bound of a step-level gather bucket.
  fsdp_template: Any
  fsdp_prefixes: Tuple[str, ...]
  fsdp_bucket_bytes: int

  @property
  def two_d(self) -> bool:
    return self.axis_data == BATCH_AXIS

  @property
  def sharded_state(self) -> bool:
    return self.apply is Apply.SHARD

  @property
  def sharded_params(self) -> bool:
    return self.exchange in (Exchange.FSDP_SCATTER,
                             Exchange.FSDP_IN_BACKWARD)


def plan_step(strategy, params, mesh, model, module=None,
              compute_dtype=jnp.float32, total_train_steps=None) -> StepPlan:
  """The :class:`StepPlan` of the step a run with ``strategy`` and
  ``params`` on ``mesh`` dispatches. Nothing is traced. ``module`` (the
  training module) gives the plan its FSDP template and its progress
  schedule; a caller that asks only which exchange and which apply a
  configuration gets may leave it out."""
  num_replicas = mesh.devices.size
  two_d = BATCH_AXIS in mesh.axis_names
  axis_data = BATCH_AXIS if two_d else REPLICA_AXIS
  axis_all = mesh_lib.state_axes(mesh) if two_d else REPLICA_AXIS
  # --shard_optimizer_state: the strategy is the marker, the mechanics
  # are the stages' and ops/sharded.py's. Requires the 2-D mesh
  # (benchmark.py builds Nx1 when --mesh_shape is unset).
  sharded_state = bool(getattr(strategy, "sharded_state", False))
  if sharded_state and not two_d:
    raise ValueError(
        "--shard_optimizer_state requires the named 2-D ('batch', "
        "'model') mesh (parallel/mesh.py build_mesh_2d); got axes "
        f"{mesh.axis_names}")
  # --shard_params (full FSDP, ZeRO-3): params live as the shard stacks
  # of ops/sharded.fsdp_stacked_shards between steps and are
  # re-assembled per builder-layer bucket (loss top) / per scanned block
  # (the module's own hook, model.fsdp_gathered_prefixes) DURING the
  # forward/backward; the optimizer applies on the shard and no
  # trailing full-tree all-gather remains.
  sharded_params = bool(getattr(params, "shard_params", False))
  if sharded_params and not sharded_state:
    raise ValueError(
        "--shard_params requires --shard_optimizer_state: the FSDP "
        "forward consumes the sharded family's scatter/apply machinery "
        "(ops/sharded.py); validation.py rejects the pair upstream")
  # --partitioner: who places the collectives. 'manual' (default) keeps
  # the shard_map programs the golden contracts pin; 'gspmd' lowers the
  # SAME per-replica body under plain jit with NamedSharding-annotated
  # state/batch and lets XLA's SPMD partitioner place them (the
  # twin-referee rule of analysis/audit.py diffs the two inventories).
  # Sharded families only: the other strategies' collectives ARE their
  # semantics (ppermute gossip, sequential PS apply).
  use_gspmd = (getattr(params, "partitioner", None) or "manual") == "gspmd"
  if use_gspmd and not sharded_state:
    raise ValueError(
        "--partitioner=gspmd covers the sharded training families "
        "(--shard_optimizer_state [+ --shard_params]): the other "
        "strategies' collectives are semantic hand placements, not "
        "partitioning choices (validation.py rejects these upstream)")
  # --num_grad_accum=M: the step scans M microbatches, accumulating
  # gradients in f32 before ONE exchange and ONE optimizer apply (the
  # memory lever: backward residuals are sized to B/M). M=1 keeps the
  # exact monolithic program.
  num_grad_accum = int(getattr(params, "num_grad_accum", None) or 1)
  data_replicas = int(mesh.shape[axis_data])

  # THE choice of the exchange. Under accumulation every kind reduces
  # the ACCUMULATED tree once (a pinned invariant), so FSDP's in-compute
  # gathers give way to one gather up front and one scatter after the
  # scan (bit-identity is preserved; the param-residency win is
  # accum=1's), and the factor plane, which reduces in the backward pass
  # of every microbatch, stays out. It also stays out where anyone reads
  # the per-replica gradients (the noise scale), on one data replica and
  # beside a model axis; which LAYERS take it is the shape rule's
  # (kungfu.factors_beat_product).
  if sharded_params:
    exchange = (Exchange.FSDP_IN_BACKWARD if num_grad_accum == 1
                else Exchange.FSDP_SCATTER)
  elif sharded_state:
    exchange = Exchange.ZERO_SCATTER
  elif (getattr(strategy, "plain_mean", False) and data_replicas > 1
        and not (two_d and int(mesh.shape[MODEL_AXIS]) > 1)
        and num_grad_accum == 1 and not params.track_grad_noise_scale):
    exchange = Exchange.FACTORED_MEAN
  else:
    exchange = Exchange.STRATEGY
  if sharded_state:
    apply = Apply.SHARD
  elif getattr(strategy, "sequential_apply", False):
    apply = Apply.SEQUENTIAL
  else:
    apply = Apply.PLAIN

  fsdp_template, fsdp_prefixes, fsdp_bucket_bytes = None, (), 0
  if sharded_params:
    fsdp_prefixes = tuple(getattr(model, "fsdp_gathered_prefixes", ()) or ())
    mb = (getattr(params, "reduce_bucket_mb", None)
          or sharded_lib.DEFAULT_BUCKET_MB)
    fsdp_bucket_bytes = int(mb) * 1024 * 1024
    if module is not None:
      # Full-shape template (abstract -- nothing executes): the gather
      # specs, the eval/accum whole-tree re-assembly and the checkpoint
      # layout all key on it. Mirrors init_state's module.init exactly.
      in_shapes = model.get_input_shapes("train")
      in_dtypes = model.get_input_data_types("train")
      sample = jnp.zeros(tuple(in_shapes[0]), in_dtypes[0])
      fsdp_template = jax.eval_shape(
          lambda: module.init({"params": jax.random.PRNGKey(0),
                               "dropout": jax.random.PRNGKey(0)},
                              sample))["params"]
  # Loss-scale resolution (ref: benchmark_cnn.py:471-480 "None = model
  # default"): float16 compute defaults to the model's scale (128);
  # bfloat16 needs none unless explicitly requested.
  init_loss_scale = 1.0
  if params.use_fp16 and params.fp16_loss_scale is not None:
    init_loss_scale = float(params.fp16_loss_scale)
  elif params.use_fp16 and compute_dtype == jnp.float16:
    init_loss_scale = float(model.get_fp16_loss_scale())
  auto_loss_scale = bool(params.use_fp16 and
                         params.fp16_enable_auto_loss_scale)
  progress_steps = 0
  if module is not None and "progress" in inspect.signature(
      type(module).__call__).parameters:
    # Total steps is the run's RESOLVED count (params.num_batches is
    # None on default/--num_epochs runs).
    if total_train_steps is None:
      total_train_steps = int(getattr(params, "num_batches", None) or 0)
    progress_steps = int(total_train_steps)
  return StepPlan(
      axis_data=axis_data, axis_all=axis_all, num_replicas=num_replicas,
      data_replicas=data_replicas, exchange=exchange, apply=apply,
      use_gspmd=use_gspmd, num_grad_accum=num_grad_accum,
      steps_per_dispatch=int(
          getattr(params, "steps_per_dispatch", None) or 1),
      init_loss_scale=init_loss_scale,
      use_loss_scale=auto_loss_scale or init_loss_scale != 1.0,
      auto_loss_scale=auto_loss_scale,
      inc_every_n=params.fp16_inc_loss_scale_every_n,
      relaxed=getattr(params, "variable_consistency",
                      "strong") == "relaxed",
      staged_vars=bool(getattr(params, "staged_vars", False)),
      # --health_stats (telemetry.py): the CONCRETE boolean benchmark.py
      # resolved; a direct caller's unresolved None gets the exact legacy
      # program. The stats read the one full update tree, which only the
      # plain apply has (validation.py and resolve_health_stats reject /
      # auto-disable the others; this re-guards direct callers).
      health_stats=(bool(getattr(params, "health_stats", None))
                    and apply is Apply.PLAIN),
      noise_scale=bool(params.track_grad_noise_scale and num_replicas > 1),
      weight_decay=params.weight_decay or 0.0,
      single_l2_loss_op=params.single_l2_loss_op,
      training_accuracy=bool(params.print_training_accuracy),
      progress_steps=progress_steps, fsdp_template=fsdp_template,
      fsdp_prefixes=fsdp_prefixes, fsdp_bucket_bytes=fsdp_bucket_bytes)


def _squeeze(tree):
  return jax.tree.map(lambda x: jnp.squeeze(x, axis=0), tree)


def _expand(tree):
  return jax.tree.map(lambda x: x[None], tree)


# -- the four stages of a train step ----------------------------------------
#
# ``per_replica_train`` (make_step_fns) calls them in this order; each
# reads the plan and owns one decision. They are plain functions: XLA's
# ``op_name``s carry the ``named_scope``s and transforms, not these
# names.


class _Backward(NamedTuple):
  """What :func:`_forward_backward` hands the later stages."""
  # The local gradients: the full per-replica tree, or FSDP's shard tree
  # where the gathers' backward pass has scattered them already.
  grads: Any
  base_loss: Any
  total_loss: Any
  batch_stats: Any
  # The network's result (None under accumulation, whose scalar
  # accuracies come averaged over the microbatches in ``accuracy``).
  net_result: Any
  accuracy: Any
  # --packed_sequences under accumulation: the real-label count; or None.
  token_weight: Any
  noise_stats: Any
  # This trace's record of the kernels on the factor plane, or None.
  factor_plan: Any


def _forward_backward(plan, model, module, state, forward_params,
                      batch_stats, images, labels) -> _Backward:
  """Stage 1: the loss and its gradients over this replica's batch (one
  pass, or --num_grad_accum microbatches in a scan), unscaled."""
  token_weight_fn = getattr(model, "token_weight_fn", None)
  fsdp_in_backward = plan.exchange is Exchange.FSDP_IN_BACKWARD
  if plan.exchange is Exchange.FSDP_SCATTER:
    # FSDP + accumulation: one whole-tree gather up front, full-tree
    # microbatch scan, post-hoc scatter in the exchange.
    with jax.named_scope("exchange"):
      forward_params = sharded_lib.fsdp_gather_full(
          forward_params, plan.fsdp_template, plan.fsdp_prefixes,
          nested=plan.use_gspmd)
  # Data-replica id: on the 2-D mesh, model-axis peers fold the SAME
  # id (same batch shard, same dropout stream), which is what makes
  # their local gradients identical by construction -- the free
  # model-axis sub-slice in ops/sharded.py depends on it.
  replica_id = lax.axis_index(plan.axis_data)
  step_rng = jax.random.fold_in(
      jax.random.fold_in(state.rng, state.step), replica_id)

  apply_kwargs = {}
  if plan.progress_steps > 0:
    apply_kwargs["progress"] = (
        state.step.astype(jnp.float32) / plan.progress_steps)
  factor_plan = (kungfu.FactorExchange(plan.axis_data, plan.data_replicas)
                 if plan.exchange is Exchange.FACTORED_MEAN else None)

  def loss_fn(p, mb_images, mb_labels, bs, dropout_rng):
    if fsdp_in_backward:
      # FSDP per-bucket gather (ops/sharded.py fsdp_wrap_shards): the
      # module-gathered scanned stacks stay shards for the per-block
      # hook inside the nn.scan body, every other leaf of p below is
      # its RE-ASSEMBLED full value, and jax.grad returns shard-layout
      # gradients already reduce-scattered. The backward scatters the
      # SCALED cotangents and the unscale divides by a power-of-two
      # scale afterwards: bit-identical to dividing first, as the
      # post-hoc path does.
      with jax.named_scope("exchange"):
        p = sharded_lib.fsdp_wrap_shards(
            p, plan.fsdp_template, plan.fsdp_bucket_bytes, BATCH_AXIS,
            MODEL_AXIS, exclude_prefixes=plan.fsdp_prefixes,
            nested=plan.use_gspmd)
    # One scope for the model, its loss and the weight decay: under
    # jax.grad XLA's op_name reads ``jvp(forward)`` going forward and
    # ``transpose(jvp(forward))`` coming back, which is how the
    # benchmark's trace reader (benchmarks/spans.py) tells the two
    # passes apart. Metadata only.
    with jax.named_scope("forward"), kungfu.factor_exchange(factor_plan):
      variables = {"params": p}
      if bs:
        variables["batch_stats"] = bs
      (logits, aux_logits), updates = module.apply(
          variables, mb_images, mutable=["batch_stats"],
          rngs={"dropout": dropout_rng}, **apply_kwargs)
      new_bs = updates.get("batch_stats", bs)
      from kf_benchmarks_tpu.models.model import BuildNetworkResult
      result = BuildNetworkResult(logits=(logits, aux_logits))
      base_loss = model.loss_function(result, mb_labels)
      total_loss = base_loss
      if plan.weight_decay:
        if fsdp_in_backward and plan.fsdp_prefixes:
          # The scanned-stack leaves of p are SHARDS here: their L2
          # term is exact in value but reassociated (_l2_loss_mixed;
          # the make_step_fns note logs it).
          total_loss = total_loss + plan.weight_decay * _l2_loss_mixed(
              p, plan.fsdp_prefixes, plan.axis_all,
              single_op=plan.single_l2_loss_op)
        else:
          total_loss = total_loss + plan.weight_decay * l2_loss(
              p, single_op=plan.single_l2_loss_op)
      scaled = total_loss * state.loss_scale
    return scaled, (base_loss, total_loss, new_bs, result)

  accuracy = None
  token_weight = None
  if plan.num_grad_accum > 1:
    # Microbatched accumulation (--num_grad_accum=M): one scan
    # iteration per microbatch, so the compiled program carries ONE
    # microbatch-sized forward+backward regardless of M. Gradients
    # accumulate in f32 and are divided once: the mean over
    # microbatches, the monolithic step's estimator up to float
    # reassociation. Everything downstream sees exactly one gradient
    # tree per step.
    m = plan.num_grad_accum
    if images.shape[0] % m:
      raise ValueError(
          f"--num_grad_accum={m} must divide the per-replica batch "
          f"size {images.shape[0]} (validation.py admits only "
          "configurations where it can)")
    split = lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:])
    mb_images = split(images)
    mb_labels = jax.tree.map(split, labels)
    grad_fn = jax.grad(loss_fn, has_aux=True)
    # Scan carries start as zeros; inside the shard_map body what they
    # accumulate varies over every axis the batch OR the parameters vary
    # over, so the zeros are pcast to match (sequence.py vary_like).
    from kf_benchmarks_tpu.parallel import sequence as sequence_lib
    param_axes = tuple(sorted(set().union(
        *(jax.typeof(p).vma for p in jax.tree.leaves(forward_params)))))

    def _vary(tree):
      leaves, treedef = jax.tree_util.tree_flatten(tree)
      return jax.tree_util.tree_unflatten(
          treedef,
          list(sequence_lib.vary_like(images, tuple(leaves),
                                      extra_axes=param_axes)))

    g0 = _vary(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), forward_params))
    bl0, tl0, w0 = _vary((jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32)))
    bs0 = _vary(batch_stats)

    def mb_body(carry, xs):
      g_acc, bl_acc, tl_acc, w_acc, acc_acc, bs = carry
      imgs, lbls, idx = xs
      # Distinct dropout stream per microbatch (a shared one would
      # correlate masks across the effective batch).
      rng_i = jax.random.fold_in(step_rng, idx)
      g, (bl, tl, bs_next, result) = grad_fn(forward_params, imgs,
                                             lbls, bs, rng_i)
      # --packed_sequences: each microbatch's loss is its own
      # token-MEAN (ops/fused_loss.py); weight the accumulation by
      # the microbatch's real-label count so the accumulated step is
      # the PER-REPLICA token-weighted estimator -- sum over tokens /
      # total tokens -- not a mean-of-means over unevenly packed
      # microbatches. Deliberate scope: the CROSS-replica exchange
      # stays the equal-weight pmean (token counts concentrate tightly
      # at ~97% packing; weighting it would rebuild every pinned
      # reduction path for a second-order correction), while the
      # REPORTED metrics are exactly token-weighted
      # (pmean(loss*w)/pmean(w)). Unpacked runs keep mb_w = 1.
      if token_weight_fn is None:
        # Exact legacy equal-weight accumulation (bit-pinned).
        mb_w = jnp.float32(1.0)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                             g_acc, g)
        wb, wt = bl, tl
      else:
        mb_w = jnp.sum(token_weight_fn(imgs))
        g_acc = jax.tree.map(
            lambda a, x: a + x.astype(jnp.float32) * mb_w, g_acc, g)
        wb, wt = bl * mb_w, tl * mb_w
      if acc_acc is not None:
        mb_acc = model.accuracy_function(result, lbls)
        acc_acc = {k: acc_acc[k] + (v if token_weight_fn is None
                                    else v * mb_w)
                   for k, v in mb_acc.items() if k in acc_acc}
      return (g_acc, bl_acc + wb, tl_acc + wt,
              w_acc + mb_w, acc_acc, bs_next), None

    acc0 = None
    if plan.training_accuracy:
      # Keys from an abstract eval (no FLOPs): scalar metrics only.
      lb0 = jax.tree.map(lambda x: x[0], mb_labels)
      shapes = jax.eval_shape(
          lambda: model.accuracy_function(
              loss_fn(forward_params, mb_images[0], lb0,
                      batch_stats, step_rng)[1][3], lb0))
      acc0 = _vary({k: jnp.zeros((), jnp.float32)
                    for k, v in shapes.items() if not v.shape})
    (g_acc, bl_acc, tl_acc, w_sum, acc_acc, new_bs), _ = lax.scan(
        mb_body, (g0, bl0, tl0, w0, acc0, bs0),
        (mb_images, mb_labels, jnp.arange(m)))
    # Normalizer: microbatch count on the legacy path; the summed
    # real-label count on the packed path (w_sum = sum of mb_w), so
    # gradients and losses come out as the monolithic token-weighted
    # estimator up to float reassociation of the batch split.
    norm = (jnp.float32(m) if token_weight_fn is None
            else jnp.maximum(w_sum, 1.0))
    if token_weight_fn is not None:
      # The scan's summed per-microbatch counts ARE this batch's
      # real-label total (0/1 weights in exact f32 integer range):
      # reused at metrics time so the two normalizers cannot drift.
      token_weight = w_sum
    grads = jax.tree.map(lambda a, p: (a / norm).astype(p.dtype),
                         g_acc, forward_params)
    base_loss = bl_acc / norm
    total_loss = tl_acc / norm
    net_result = None
    if acc_acc is not None:
      accuracy = {k: v / norm for k, v in acc_acc.items()}
  else:
    grads, (base_loss, total_loss, new_bs, net_result) = jax.grad(
        loss_fn, has_aux=True)(forward_params, images, labels,
                               batch_stats, step_rng)
  if plan.use_loss_scale:
    grads = jax.tree.map(lambda g: g / state.loss_scale, grads)
  noise_stats = None
  if plan.noise_scale:
    # Measured on the pre-reduction per-replica grads (the small-batch
    # estimate) vs their replica mean (the large-batch estimate):
    # elastic.noise_scale_stats, KungFu's in-collective monitoring
    # (SURVEY 2.9).
    with jax.named_scope("metrics"):
      noise_stats = elastic_lib.noise_scale_stats(
          grads, plan.axis_data, images.shape[0])
  return _Backward(grads, base_loss, total_loss, new_bs, net_result,
                   accuracy, token_weight, noise_stats, factor_plan)


def _exchange(plan, strategy, grads, factor_plan):
  """Stage 2: the local gradients -> the ones the optimizer applies
  (the per-replica tree, or this device's shards where the state is
  sharded), by the plan's ONE exchange."""
  if plan.exchange is Exchange.FSDP_IN_BACKWARD:
    # jax.grad's output IS the shard tree (ops/sharded.py gather_params
    # bwd -- elementwise identical to the post-hoc scatter below). No
    # full gradient tree ever existed.
    return grads
  # "exchange" names the whole reduction -- the casts, concatenations
  # and scalings around the collectives too, which a reader that goes
  # by opcode alone would miss (benchmarks/spans.py).
  with jax.named_scope("exchange"):
    if plan.exchange is Exchange.FSDP_SCATTER:
      # Post-hoc scatter of the accumulated full tree onto the FSDP
      # layout (per-layer rows for the scanned stacks) -- elementwise
      # the same values as scatter_mean.
      return sharded_lib.fsdp_scatter_mean(grads, plan.fsdp_prefixes)
    if plan.exchange is Exchange.ZERO_SCATTER:
      # Reduce-scatter of the batch-axis mean, BIT-IDENTICAL to the
      # replicated pmean (ops/sharded.py layout contract), then the
      # free model-axis sub-slice. The full gradient tree dies here;
      # only this device's 1/n flat shard flows on.
      return sharded_lib.scatter_mean(grads)
    reduce = lambda g: strategy.reduce_gradients(g, plan.axis_data)
    if factor_plan is None or not factor_plan.claimed:
      return reduce(grads)
    grads = _reduce_unclaimed(grads, factor_plan.claimed, reduce)
    counters = factor_plan.counters()
    tracing.active().state(
        "factor_exchange", counters,
        "factor exchange: %d dense layer(s) form the mean gradient "
        "from all-gathered factors: %.1f MB kept off the "
        "all-reduce, %.1f MB gathered instead" % (
            counters["layers"],
            counters["bytes_off_allreduce"] / 1e6,
            counters["bytes_gathered"] / 1e6))
    return grads


def _bank_deferred(plan, grads, buffers):
  """Between exchange and apply: the auto loss scale's finite check of
  THIS step's gradients and --variable_consistency=relaxed's bank.
  Returns (the gradients to apply, ``fresh_finite`` or None, the new
  buffers)."""
  # The loss-scale state machine keys on THIS step's fresh gradients
  # (they reflect the current scale), even when the applied gradients
  # are the deferred ones (ref: variable_mgr_util.py:51-139). The pmin
  # over BOTH axes is exact on the sharded path (the shards tile the
  # reduced tree once) and on the replicated 2-D path (model-axis peers
  # hold identical gradients).
  fresh_finite = None
  if plan.auto_loss_scale:
    ok = jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]))
    # Globally uniform decision (pmin across replicas) so every carried
    # scalar stays replicated (ref chief-only NaN check + broadcast,
    # variable_mgr.py:186-193).
    fresh_finite = lax.pmin(ok.astype(jnp.int32),
                            plan.axis_all).astype(bool)
  new_buffers = dict(buffers)
  if plan.relaxed:
    # Apply the PREVIOUS step's reduced gradients and bank this step's
    # for the next (the reference's deferred StagingArea gradients,
    # batch_allreduce.py:353-388; SURVEY 7.4). Non-finite fresh
    # gradients are never banked: the old bank stays.
    banked = grads
    if fresh_finite is not None:
      banked = jax.tree.map(
          lambda a, b: jnp.where(fresh_finite, a, b),
          grads, buffers["deferred_grads"])
    new_buffers["deferred_grads"] = banked
    grads = buffers["deferred_grads"]
  return grads, fresh_finite, new_buffers


class _Applied(NamedTuple):
  """What :func:`_apply` leaves: the new state's parts, and the update
  tree the health stats read (None under the sequential apply)."""
  params: Any
  opt_state: Any
  batch_stats: Any
  loss_scale: Any
  normal_steps: Any
  updates: Any


def _apply(plan, strategy, tx, state, model_params, opt_state,
           batch_stats, grads, new_bs, fresh_finite) -> _Applied:
  """Stage 3: the optimizer's application by the plan's ONE kind, the
  strategy's weight transforms around it, and the auto loss scale's
  skip."""
  with jax.named_scope("exchange"):
    model_params_pre = strategy.pre_update(model_params, state.step,
                                           plan.axis_data)
  updates = None
  if plan.apply is Apply.SHARD:
    # The ZeRO apply (the reference's central variable placement
    # rendered SPMD, variable_mgr.py:201-243): run the optimizer on
    # the 1/n shard ONLY (elementwise optimizers; validation.py
    # rejects LARS). --shard_params: the state ALREADY holds this
    # device's shards and the updated shards flow straight back into
    # it. Without it, params are replicated: the shard is a free local
    # slice and the updated params return by all-gather.
    param_shards = (model_params_pre if plan.sharded_params
                    else sharded_lib.local_shards(model_params_pre))
    with jax.named_scope("optimizer_apply"):
      updates, new_opt_state = tx.update(grads, opt_state, param_shards)
      new_shards = optax.apply_updates(param_shards, updates)
    with jax.named_scope("exchange"):
      new_params = (new_shards if plan.sharded_params else
                    sharded_lib.gather_tree(new_shards, model_params_pre,
                                            nested=plan.use_gspmd))
  elif plan.apply is Apply.SEQUENTIAL:
    # Async PS with a stateful optimizer (strategies.py): serialize
    # every replica's unaveraged gradient through the SHARED optimizer
    # state, in replica-index order -- the deterministic SPMD rendering
    # of the PS's one-at-a-time applications (benchmark_cnn.py:520-522).
    with jax.named_scope("exchange"):
      g_all = jax.tree.map(
          lambda g: lax.all_gather(g, plan.axis_data, axis=0), grads)

    def _apply_one(carry, g):
      prms, ost = carry
      upd, ost2 = tx.update(g, ost, prms)
      # Every application within the round sees the ROUND's schedule
      # count (momentum/variance state still advances per
      # application); the round bump happens once, below.
      ost2 = _sync_schedule_counts(ost, ost2)
      return (optax.apply_updates(prms, upd), ost2), None

    # The named_scope rides into HLO op_name metadata; the program-
    # contract auditor (analysis/contracts.py) keys the one-apply-
    # per-step check on it.
    with jax.named_scope("optimizer_apply"):
      (new_params, new_opt_state), _ = lax.scan(
          _apply_one, (model_params_pre, opt_state), g_all)
    new_opt_state = _sync_schedule_counts(opt_state, new_opt_state,
                                          bump=1)
  else:
    with jax.named_scope("optimizer_apply"):
      updates, new_opt_state = tx.update(grads, opt_state,
                                         model_params_pre)
      new_params = optax.apply_updates(model_params_pre, updates)
  with jax.named_scope("exchange"):
    new_params = strategy.post_update(new_params, state.step,
                                      plan.axis_data)
    new_bs = strategy.sync_batch_stats(new_bs, plan.axis_data)

  if not plan.auto_loss_scale:
    return _Applied(new_params, new_opt_state, new_bs, state.loss_scale,
                    state.loss_scale_normal_steps, updates)
  # Auto loss-scale state machine (ref: variable_mgr_util.py:51-139):
  # any non-finite FRESH grad -> skip the update, halve scale; else
  # count a normal step and double the scale every ``inc_every_n``.
  # Under relaxed consistency the APPLIED gradients are the previous
  # bank, which only ever admits finite values (banking gate), so the
  # params/opt_state skip is unnecessary there by induction.
  keep = lambda new, old: jax.tree.map(
      lambda a, b: jnp.where(fresh_finite, a, b), new, old)
  if not plan.relaxed:
    new_params = keep(new_params, model_params)
    new_opt_state = keep(new_opt_state, opt_state)
  # batch_stats come from THIS step's forward in both modes: an
  # overflowing forward must not poison the running statistics.
  new_bs = keep(new_bs, batch_stats)
  normal_steps = jnp.where(fresh_finite,
                           state.loss_scale_normal_steps + 1, 0)
  do_double = jnp.logical_and(fresh_finite,
                              normal_steps >= plan.inc_every_n)
  new_scale = jnp.where(
      fresh_finite,
      jnp.where(do_double, state.loss_scale * 2.0, state.loss_scale),
      jnp.maximum(state.loss_scale / 2.0, 1.0))
  normal_steps = jnp.where(do_double, 0, normal_steps)
  return _Applied(new_params, new_opt_state, new_bs, new_scale,
                  normal_steps, updates)


def _step_metrics(plan, model, lr_fn, state, images, labels, model_params,
                  grads, back: _Backward, applied: _Applied, fresh_finite):
  """Stage 4: the step line's loss / accuracy / health reductions, apart
  from the training arithmetic (benchmarks/spans.py). ``grads`` are the
  APPLIED gradients (under relaxed consistency the one-step-stale
  bank)."""
  token_weight_fn = getattr(model, "token_weight_fn", None)
  axis_data = plan.axis_data
  base_loss, total_loss = back.base_loss, back.total_loss
  with jax.named_scope("metrics"):
    lr = lr_fn(state.step)
    # Token-weighted metric combine (--packed_sequences: the model
    # exposes images -> (B, T) per-token loss weights): per-replica
    # losses are already normalized by this replica's real-label count
    # (ops/fused_loss.py) and replicas pack different document mixes,
    # so the global token-mean is pmean(loss * w) / pmean(w), from the
    # SAME packed vector collective that carries the losses (no more
    # collectives than unpacked: the lm_packed audit rule pins it).
    tok_w = None
    if token_weight_fn is not None:
      tok_w = (back.token_weight if back.token_weight is not None
               else jnp.sum(token_weight_fn(images)))
    wm_safe = None
    if plan.health_stats:
      # In-step health stats (telemetry.py): grad norm, update/param
      # ratio, non-finite leaf count, loss scale + skip flag, read from
      # the step's post-reduction values. Each replica reduces a 1/n
      # SLICE of every tree (telemetry.health_partials) and the
      # pre-scaled partial sums ride the LOSS pmean: one f32 vector
      # all-reduce replaces the two scalar loss pmeans, so the
      # health-on program carries NO extra collective and computes
      # bit-identical loss values (both pinned in
      # tests/test_telemetry.py). ``updates`` exists on every
      # health-admitted path (the plain apply: plan_step).
      skipped = (1.0 - fresh_finite.astype(jnp.float32)
                 if fresh_finite is not None else jnp.float32(0.0))
      # The fresh-grad overflow skip only suppresses the applied
      # update on the non-relaxed path (the relaxed bank admits finite
      # gradients only, so its apply always lands).
      suppressed = jnp.float32(0.0) if plan.relaxed else skipped
      # Under --packed_sequences the two loss slots ride token-weighted
      # (loss * w) and w itself is appended to the SAME vector, so the
      # weighted combine still costs the one loss pmean.
      bl32 = base_loss.astype(jnp.float32)
      tl32 = total_loss.astype(jnp.float32)
      loss_slots = (jnp.stack([bl32, tl32]) if tok_w is None else
                    jnp.stack([bl32 * tok_w, tl32 * tok_w]))
      vec = [loss_slots, telemetry_lib.health_partials(
          grads, model_params, applied.updates, axis_data)]
      if tok_w is not None:
        vec.append(jnp.stack([tok_w]))
      packed = lax.pmean(jnp.concatenate(vec), axis_data)
      health_totals = packed[2:] if tok_w is None else packed[2:-1]
      if tok_w is None:
        bl_m, tl_m = packed[0], packed[1]
      else:
        wm_safe = jnp.maximum(packed[-1], 1e-30)
        bl_m, tl_m = packed[0] / wm_safe, packed[1] / wm_safe
      metrics = {
          "base_loss": bl_m,
          "total_loss": tl_m,
          "learning_rate": lr,
          "health": telemetry_lib.health_finalize(
              health_totals, applied.loss_scale, skipped, suppressed),
      }
    elif tok_w is not None:
      # One 3-vector pmean replaces the two scalar loss pmeans: the
      # packed program's collective count stays <= the unpacked one.
      packed = lax.pmean(
          jnp.stack([base_loss.astype(jnp.float32) * tok_w,
                     total_loss.astype(jnp.float32) * tok_w, tok_w]),
          axis_data)
      wm_safe = jnp.maximum(packed[2], 1e-30)
      metrics = {
          "base_loss": packed[0] / wm_safe,
          "total_loss": packed[1] / wm_safe,
          "learning_rate": lr,
      }
    else:
      # Metric pmeans reduce over the DATA axis only: model-axis peers
      # compute the identical loss from the identical batch shard, so
      # the batch-group mean is already the global value -- and it is
      # bit-identical to the replicated path's B-contribution pmean.
      metrics = {
          "base_loss": lax.pmean(base_loss, axis_data),
          "total_loss": lax.pmean(total_loss, axis_data),
          "learning_rate": lr,
      }
    if tok_w is not None and wm_safe is not None:
      # Label coverage of the packed batch (real label positions /
      # slots): the in-step packing-efficiency signal next to the
      # host-side feed line (observability.packing_feed_line). Post-
      # collective scalar math, no extra communication.
      metrics["real_token_fraction"] = wm_safe / jnp.float32(
          sum(math.prod(l.shape) for l in jax.tree.leaves(labels)) or 1)
    if plan.steps_per_dispatch > 1:
      # Replica-mean global norm of the reduced gradients (under relaxed
      # consistency: of the APPLIED, one-step-stale bank), the per-step
      # health scalar the chunked mode stacks beside loss and lr. K=1
      # omits it: the single-step program stays the exact pinned one.
      if "health" in metrics:
        # The health vector already carries this exact norm (same grads
        # tree, sharded reduction): no second full-tree pass.
        metrics["grad_norm"] = metrics["health"][0]
      elif plan.sharded_state:
        # The flat shards tile the reduced gradient exactly once: the
        # psum of per-shard square-sums over BOTH axes is the global one.
        metrics["grad_norm"] = jnp.sqrt(lax.psum(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)), plan.axis_all))
      else:
        metrics["grad_norm"] = lax.pmean(
            jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))), axis_data)
    if plan.training_accuracy:
      # Under microbatching the per-microbatch scalar accuracies were
      # averaged inside the scan (equal microbatch sizes make that the
      # effective-batch value); monolithic computes them here.
      acc = (back.accuracy if back.accuracy is not None
             else model.accuracy_function(back.net_result, labels))
      # Scalars only: detection accuracy_functions also return per-box
      # arrays (decoded predictions), which are not replicated step
      # metrics. Packed runs weight each replica's (already token-
      # weighted) accuracy by its real-label count, like the losses.
      if tok_w is not None and wm_safe is not None:
        metrics.update({k: lax.pmean(v * tok_w, axis_data) / wm_safe
                        for k, v in acc.items() if jnp.ndim(v) == 0})
      else:
        metrics.update({k: lax.pmean(v, axis_data)
                        for k, v in acc.items() if jnp.ndim(v) == 0})
    # A model's own per-step counters (models/model.py
    # ``step_counters``; mla_moe_lm: the expert layer's loads), read
    # from what this step's forward left in ``batch_stats``: one small
    # vector beside the losses, fetched with them, so no host sync and
    # no collective of its own. None for a model without any.
    counters = model.step_counters(applied.batch_stats)
    if counters is not None:
      metrics["counters"] = counters
  if back.noise_stats is not None:
    metrics["noise_scale_g2"], metrics["noise_scale_s"] = back.noise_stats
  return metrics


def make_step_fns(model, module, eval_module, strategy, tx, lr_fn, params,
                  mesh, compute_dtype=jnp.float32, total_train_steps=None):
  """Build (init_fn, train_step, eval_step, broadcast_init, train_chunk)
  jitted over ``mesh``.

  All operate on per-replica stacked state (leading replica dim).
  ``total_train_steps`` is the RESOLVED run length (callers must pass the
  derived count -- params.num_batches is None on default/--num_epochs
  runs); it drives progress-ramped modules (NASNet drop-path).

  ``train_chunk`` is the device-resident multi-step program
  (--steps_per_dispatch=K > 1, else None): K applications of the SAME
  per-replica train step under one ``lax.scan``, so host dispatch is
  paid once per K steps. Inputs carry a leading staged-steps axis --
  size K for real-data chunks, size 1 for the synthetic resident batch
  (reused every scanned step: no staged-batch HBM footprint and no H2D
  at all). Per-step metrics come back stacked on a leading K axis; the
  carry is the ordinary TrainState, so step numbering, the dropout
  stream, LR schedules and the loss-scale state machine advance exactly
  as in K dispatches of ``train_step``. ``--num_grad_accum=M``
  microbatches INSIDE each step, orthogonal to it: K amortizes dispatch
  cost, M bounds backward-residual HBM. Both default off.
  """
  plan = plan_step(strategy, params, mesh, model, module=module,
                   compute_dtype=compute_dtype,
                   total_train_steps=total_train_steps)
  axis_data = plan.axis_data
  axis_all = plan.axis_all
  token_weight_fn = getattr(model, "token_weight_fn", None)
  if plan.fsdp_prefixes and plan.weight_decay:
    from kf_benchmarks_tpu.utils import log as log_util
    log_util.log_fn(
        "shard_params: weight decay over the scanned parameter "
        f"stack(s) {list(plan.fsdp_prefixes)} reduces shard-"
        "locally + one mesh psum (full blocks exist only one at a "
        "time inside the scan): exact L2 value, reassociated -- "
        "total_loss is not bit-identical to the replicated-param L2 "
        "on this model family (pass --weight_decay=0 for bit-exact "
        "A/Bs)")
  tracing.active().state("factor_exchange", kungfu.NO_FACTOR_EXCHANGE)
  state_specs = TrainState(
      step=P(), params=P(axis_all), opt_state=P(axis_all),
      batch_stats=P(axis_all), loss_scale=P(),
      loss_scale_normal_steps=P(), rng=P(), buffers=P(axis_all))

  # -- init -----------------------------------------------------------------

  def _init(rng, sample_images):
    variables = module.init({"params": rng, "dropout": rng}, sample_images)
    model_params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if plan.sharded_params:
      # Full FSDP: the PARAM storage itself is the shard stack (per-
      # layer rows for the scanned prefixes), and the per-shard
      # optimizer state mirrors it leaf-for-leaf.
      params_store = sharded_lib.fsdp_stacked_shards(
          model_params, plan.num_replicas, plan.fsdp_prefixes)
      return params_store, jax.vmap(tx.init)(params_store), batch_stats
    if plan.sharded_state:
      # Per-shard optimizer state: vmap tx.init over the stacked flat
      # param shards (ops/sharded.py layout), so every opt-state leaf
      # comes out (n, k) with row i = device i's shard.
      opt_state = jax.vmap(tx.init)(
          sharded_lib.stacked_shards(model_params, plan.num_replicas))
    else:
      opt_state = tx.init(model_params)
    return model_params, opt_state, batch_stats

  def init_state(rng, sample_images):
    """Builds the stacked per-replica TrainState (identical init on every
    replica == the reference's post-init broadcast, variable_mgr.py:342-356).
    Under --shard_optimizer_state the opt_state rows are per-device
    SHARDS, not copies (see _init); under --shard_params the params
    rows are shards too."""
    params_store, opt_state, batch_stats = _init(rng, sample_images)
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (plan.num_replicas,) + x.shape), t)
    buffers = {}
    if plan.relaxed:
      # Warmed up with zero gradients, like the reference's StagingArea
      # warmup put (ref: batch_allreduce.py:357-359).
      buffers["deferred_grads"] = stack(
          jax.tree.map(jnp.zeros_like, params_store))
    if plan.staged_vars:
      buffers["staged_params"] = stack(params_store)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params_store if plan.sharded_params else stack(params_store),
        opt_state=opt_state if plan.sharded_state else stack(opt_state),
        batch_stats=stack(batch_stats),
        loss_scale=jnp.asarray(plan.init_loss_scale, jnp.float32),
        loss_scale_normal_steps=jnp.zeros((), jnp.int32),
        rng=rng,
        buffers=buffers)

  # -- train step -----------------------------------------------------------

  def per_replica_train(state, images, labels):
    model_params = _squeeze(state.params)
    opt_state = _squeeze(state.opt_state)
    batch_stats = _squeeze(state.batch_stats)
    buffers = _squeeze(state.buffers)
    # --staged_vars: forward/backward read one-step-stale weights while
    # updates land on the live ones (ref: StagedVariableGetter,
    # variable_mgr_util.py:313-393).
    forward_params = (buffers["staged_params"] if plan.staged_vars
                      else model_params)
    back = _forward_backward(plan, model, module, state, forward_params,
                             batch_stats, images, labels)
    grads = _exchange(plan, strategy, back.grads, back.factor_plan)
    grads, fresh_finite, new_buffers = _bank_deferred(plan, grads, buffers)
    applied = _apply(plan, strategy, tx, state, model_params, opt_state,
                     batch_stats, grads, back.batch_stats, fresh_finite)
    metrics = _step_metrics(plan, model, lr_fn, state, images, labels,
                            model_params, grads, back, applied,
                            fresh_finite)
    if plan.staged_vars:
      # Next step's reads see this step's PRE-update weights: the value
      # that was in the staging area at read time (one-step staleness).
      new_buffers["staged_params"] = model_params
    # Writing the updated state back belongs to the apply: XLA fuses the
    # stacking reshape with the update and names the fusion after it.
    with jax.named_scope("optimizer_apply"):
      stacked_params = _expand(applied.params)
      stacked_opt_state = _expand(applied.opt_state)
    new_state = TrainState(
        step=state.step + 1,
        params=stacked_params,
        opt_state=stacked_opt_state,
        batch_stats=_expand(applied.batch_stats),
        loss_scale=applied.loss_scale,
        loss_scale_normal_steps=applied.normal_steps,
        rng=state.rng,
        buffers=_expand(new_buffers))
    return new_state, metrics

  # Explicit init output shardings: required under multi-process SPMD
  # (every process must agree where the stacked state lives) and a no-op
  # single-process.
  init_shardings = jax.tree.map(
      lambda spec: NamedSharding(mesh, spec), state_specs,
      is_leaf=lambda x: isinstance(x, P))
  init_state_fn = jax.jit(init_state, out_shardings=init_shardings)

  # Models built on library-internal scans (optax ctc_loss, flax RNN)
  # seed carries from unvarying constants, which trips the strict
  # varying-manual-axes checker; they opt out via relax_shard_map_vma.
  # Everyone else keeps it (it catches missing pmeans under P()).
  check_vma = not getattr(model, "relax_shard_map_vma", False)

  # 2-D mesh: the step metrics are reduced over 'batch' only, and the
  # model-axis peers hold bit-identical copies by construction, which
  # the varying-manual-axes types cannot know. So the metrics leave the
  # manual region stacked over 'model' (dim ``dim``) and the jitted
  # wrapper keeps row 0: free on the Nx1 meshes. 1-D meshes keep P().
  def _metric_spec(dim=0):
    return P(*([None] * dim), MODEL_AXIS) if plan.two_d else P()

  def _stack_model(tree, dim=0):
    if not plan.two_d:
      return tree
    return jax.tree.map(lambda x: jnp.expand_dims(x, dim), tree)

  def _pick_model(tree, dim=0):
    if not plan.two_d:
      return tree
    return jax.tree.map(lambda x: jnp.take(x, 0, axis=dim), tree)

  def _sharded_step(per_fn, batch_spec, dim=0):
    """jit(shard_map(per_fn)) for a (state, images, labels) ->
    (state, metrics) body whose metrics carry ``dim`` leading axes."""
    def body(state, images, labels):
      new_state, metrics = per_fn(state, images, labels)
      return new_state, _stack_model(metrics, dim)
    body.__name__ = per_fn.__name__
    sharded = jax.shard_map(
        body, mesh=mesh, in_specs=(state_specs, batch_spec, batch_spec),
        out_specs=(state_specs, _metric_spec(dim)), check_vma=check_vma)

    def step(state, images, labels):
      new_state, metrics = sharded(state, images, labels)
      return new_state, _pick_model(metrics, dim)
    step.__name__ = per_fn.__name__
    return jax.jit(step, donate_argnums=(0,))

  # -- the gspmd twin (--partitioner=gspmd) ---------------------------------
  #
  # Same per-replica body, compiler-placed collectives: the body still
  # speaks bound axis names (every lax.p* of the stages), so instead of
  # shard_map it is traced under two nested jax.vmap's -- outer
  # 'batch', inner 'model' -- each binding axis_name AND
  # spmd_axis_name over the (B, M)-regridded stacked state. The
  # surrounding plain jit carries the SAME NamedShardings the manual
  # path's specs induce, and GSPMD is then free to place the
  # collectives. Batch inputs map on the outer vmap only (model peers
  # see the same shard, like in_specs P(axis_data)); scalars replicate
  # in (in_axes=None) and come back broadcast (the [0, 0] pick below
  # avoids proving replication to vmap). eval_step and broadcast_init
  # stay on the manual shard_map path in both modes.
  def _gspmd_wrap(per_fn, batch_dim):
    grid_b = int(mesh.shape[BATCH_AXIS])
    grid_m = int(mesh.shape[MODEL_AXIS])
    stacked = ("params", "opt_state", "batch_stats", "buffers")
    vmap_axes = TrainState(
        step=None, params=0, opt_state=0, batch_stats=0, loss_scale=None,
        loss_scale_normal_steps=None, rng=None, buffers=0)

    def _map_stacked(state, f):
      return state.replace(**{
          name: jax.tree.map(f, getattr(state, name)) for name in stacked})

    def tile(state, images, labels):
      # The vmap's strip both grid dims; the body speaks the leading-1
      # per-replica stacking convention.
      new_state, metrics = per_fn(
          _map_stacked(state, lambda x: x[None]), images, labels)
      return _map_stacked(new_state,
                          lambda x: jnp.squeeze(x, axis=0)), metrics

    inner = jax.vmap(tile, in_axes=(vmap_axes, None, None),
                     axis_name=MODEL_AXIS, spmd_axis_name=MODEL_AXIS)
    outer = jax.vmap(inner, in_axes=(vmap_axes, batch_dim, batch_dim),
                     axis_name=BATCH_AXIS, spmd_axis_name=BATCH_AXIS)

    def global_fn(state, images, labels):
      gridded = _map_stacked(
          state,
          lambda x: x.reshape((grid_b, grid_m) + x.shape[1:]))
      split = lambda x: x.reshape(
          x.shape[:batch_dim] +
          (grid_b, x.shape[batch_dim] // grid_b) +
          x.shape[batch_dim + 1:])
      new_state, metrics = outer(gridded, split(images),
                                 jax.tree.map(split, labels))
      # Stacked leaves come back (B, M, ...) -> the flat (n, ...)
      # stacking; replicated scalars/metrics come back broadcast over
      # the grid -> any single copy (all bit-identical by SPMD).
      pick = lambda x: x[0, 0]
      out_state = _map_stacked(
          new_state,
          lambda x: x.reshape((grid_b * grid_m,) + x.shape[2:]))
      out_state = out_state.replace(
          step=pick(new_state.step), loss_scale=pick(new_state.loss_scale),
          loss_scale_normal_steps=pick(new_state.loss_scale_normal_steps),
          rng=pick(new_state.rng))
      return out_state, jax.tree.map(pick, metrics)

    data_spec = P(axis_data) if batch_dim == 0 else P(None, axis_data)
    data_sharding = NamedSharding(mesh, data_spec)
    return jax.jit(
        global_fn,
        in_shardings=(init_shardings, data_sharding, data_sharding),
        out_shardings=(init_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,))

  if plan.use_gspmd:
    train_step = _gspmd_wrap(per_replica_train, 0)
  else:
    train_step = _sharded_step(per_replica_train, P(axis_data))

  # -- chunked multi-step dispatch (--steps_per_dispatch) -------------------

  def per_replica_train_chunk(state, images, labels):
    """K train steps in one scanned program (leading axis = staged
    steps). A leading axis of 1 is the synthetic resident batch: the
    scan closes over it and runs K steps with no staged inputs -- the
    in-program analog of the reference's reused synthetic feed
    (ref: benchmark_cnn.py:3008-3011) at K steps per dispatch."""
    if images.shape[0] == 1 and plan.steps_per_dispatch > 1:
      im0 = images[0]
      lb0 = jax.tree.map(lambda x: x[0], labels)
      new_state, metrics = lax.scan(
          lambda st, _: per_replica_train(st, im0, lb0), state, None,
          length=plan.steps_per_dispatch)
      return new_state, metrics
    new_state, metrics = lax.scan(
        lambda st, batch: per_replica_train(st, *batch), state,
        (images, labels))
    return new_state, metrics

  train_chunk = None
  if plan.steps_per_dispatch > 1:
    if plan.use_gspmd:
      train_chunk = _gspmd_wrap(per_replica_train_chunk, 1)
    else:
      # Per-step metrics come back stacked on a leading K axis; the
      # model stacking rides behind it.
      train_chunk = _sharded_step(per_replica_train_chunk,
                                  P(None, axis_data), dim=1)

  # -- forward-only / eval step --------------------------------------------

  def per_replica_eval(state, images, labels):
    model_params = _squeeze(state.params)
    if plan.sharded_params:
      # Mid-training eval re-assembles the full tree (the eval module
      # carries no FSDP hooks): eval is occasional, and the residency
      # contract binds the steady-state training program.
      model_params = sharded_lib.fsdp_gather_full(
          model_params, plan.fsdp_template, plan.fsdp_prefixes)
    batch_stats = _squeeze(state.batch_stats)
    variables = {"params": model_params}
    if batch_stats:
      variables["batch_stats"] = batch_stats
    logits, aux_logits = eval_module.apply(variables, images)
    from kf_benchmarks_tpu.models.model import BuildNetworkResult
    result = BuildNetworkResult(logits=(logits, aux_logits))
    acc = model.accuracy_function(result, labels)
    loss = model.loss_function(result, labels)
    if token_weight_fn is not None:
      # Packed runs (mid-training eval; --eval itself is rejected in
      # validation.py): the train metrics' token-weighted cross-replica
      # combine, since an equal-weight pmean would bias the global value
      # toward lightly-packed replicas.
      tok_w = jnp.sum(token_weight_fn(images))
      wm = jnp.maximum(lax.pmean(tok_w, axis_data), 1e-30)
      metrics = {k: lax.pmean(v * tok_w, axis_data) / wm
                 for k, v in acc.items() if jnp.ndim(v) == 0}
      metrics["base_loss"] = lax.pmean(loss * tok_w, axis_data) / wm
    else:
      metrics = {k: lax.pmean(v, axis_data)
                 for k, v in acc.items() if jnp.ndim(v) == 0}
      # Loss included so the forward-only timed loop can print the
      # standard step line (ref forward-only: benchmark_cnn.py:124-126).
      metrics["base_loss"] = lax.pmean(loss, axis_data)
    metrics["total_loss"] = metrics["base_loss"]
    return metrics

  eval_sharded = jax.shard_map(
      lambda st, im, lb: _stack_model(per_replica_eval(st, im, lb)),
      mesh=mesh,
      in_specs=(state_specs, P(axis_data), P(axis_data)),
      out_specs=_metric_spec(), check_vma=check_vma)

  def eval_step_fn(state, images, labels):
    return _pick_model(eval_sharded(state, images, labels))
  eval_step = jax.jit(eval_step_fn)

  # -- broadcast-init (strategy-dependent; ref: benchmark_cnn.py:2094-2100) --

  def per_replica_broadcast(tree):
    return _expand(strategy.broadcast_init(_squeeze(tree), axis_data))

  broadcast_sharded = jax.shard_map(
      per_replica_broadcast, mesh=mesh,
      in_specs=(P(axis_all),), out_specs=P(axis_all))
  broadcast_init = jax.jit(broadcast_sharded)

  return init_state_fn, train_step, eval_step, broadcast_init, train_chunk
