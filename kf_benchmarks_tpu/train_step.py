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

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
import flax
import optax

from kf_benchmarks_tpu import elastic as elastic_lib
from kf_benchmarks_tpu import telemetry as telemetry_lib
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.ops import overlap as overlap_lib
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
# and all, from a warm cache (CLAUDE.md, observability notes).
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
  leaves = []
  flat = jax.tree_util.tree_flatten_with_path(params)[0]
  for path, leaf in flat:
    if not _is_batch_norm_param(path):
      leaves.append(leaf)
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
  paid once per K steps. Inputs carry a leading
  staged-steps axis -- size K for real-data chunks, size 1 for the
  synthetic resident batch (reused every scanned step, folding batch
  "generation" into the program: no staged-batch HBM footprint and no
  H2D at all). Per-step metrics come back stacked on a leading K axis;
  the carry is the ordinary TrainState, so step numbering, the
  fold_in(rng, step) dropout stream, LR schedules, and the loss-scale
  state machine advance exactly as in K dispatches of ``train_step``.

  ``--num_grad_accum=M`` > 1 microbatches INSIDE each train step (an
  inner lax.scan over M batch slices accumulating f32 gradients before
  one reduction + one optimizer apply), orthogonal to the K-step
  dispatch chunking outside: K amortizes host/dispatch cost, M bounds
  backward-residual HBM. Both default off (the exact monolithic
  program).
  """
  num_replicas = mesh.devices.size
  # Axis system. 1-D ('replica',) meshes keep the exact legacy program
  # (every golden contract is pinned against it); the named 2-D
  # ('batch', 'model') mesh behind --mesh_shape/--shard_optimizer_state
  # shards the batch over 'batch' only (model-axis peers re-compute the
  # same shard) while the stacked state and the metric pmeans span both
  # axes.
  two_d = BATCH_AXIS in mesh.axis_names
  axis_data = BATCH_AXIS if two_d else REPLICA_AXIS
  axis_all = mesh_lib.state_axes(mesh) if two_d else REPLICA_AXIS
  # --shard_optimizer_state: the strategy is the marker; the mechanics
  # (reduce-scatter mean, shard apply, param all-gather) live below +
  # ops/sharded.py. Requires the 2-D mesh (benchmark.py builds Nx1 when
  # --mesh_shape is unset).
  sharded_state = bool(getattr(strategy, "sharded_state", False))
  if sharded_state and not two_d:
    raise ValueError(
        "--shard_optimizer_state requires the named 2-D ('batch', "
        "'model') mesh (parallel/mesh.py build_mesh_2d); got axes "
        f"{mesh.axis_names}")
  # --shard_params (full FSDP, ZeRO-3): params live as the (n, k) /
  # (n, L, k) shard stacks of ops/sharded.fsdp_stacked_shards between
  # steps and are re-assembled per builder-layer bucket (loss top) /
  # per scanned block (inside the nn.scan body -- the module's own
  # gather hook, model.fsdp_gathered_prefixes) DURING the
  # forward/backward; the optimizer applies on the shard and NO
  # trailing full-tree all-gather remains -- peak param residency is
  # one bucket/block, steady-state per-device param HBM is |params|/n.
  sharded_params = bool(getattr(params, "shard_params", False))
  if sharded_params and not sharded_state:
    raise ValueError(
        "--shard_params requires --shard_optimizer_state: the FSDP "
        "forward consumes the sharded family's scatter/apply machinery "
        "(ops/sharded.py); validation.py rejects the pair upstream")
  # --partitioner: who places the collectives. 'manual' (default) keeps
  # the exact legacy shard_map programs every golden contract pins;
  # 'gspmd' lowers the SAME per-replica body under plain jit with
  # NamedSharding-annotated state/batch and lets the XLA SPMD
  # partitioner insert/re-place them (SNIPPETS [2]/[3] idiom; the
  # analysis/audit.py twin-referee rule diffs the two inventories).
  # Sharded families only: the replicated/gossip/PS strategies are
  # hand-placed BY DESIGN (their collectives ARE the semantics --
  # ppermute gossip, sequential PS apply); validation.py rejects the
  # combinations upstream, this re-guards direct callers.
  partitioner = getattr(params, "partitioner", None) or "manual"
  use_gspmd = partitioner == "gspmd"
  if use_gspmd and not sharded_state:
    raise ValueError(
        "--partitioner=gspmd covers the sharded training families "
        "(--shard_optimizer_state [+ --shard_params]): the other "
        "strategies' collectives are semantic hand placements, not "
        "partitioning choices (validation.py rejects these upstream)")
  fsdp_template = None
  fsdp_module_prefixes = ()
  fsdp_bucket_bytes = 0
  if sharded_params:
    fsdp_module_prefixes = tuple(
        getattr(model, "fsdp_gathered_prefixes", ()) or ())
    mb = (getattr(params, "reduce_bucket_mb", None)
          or overlap_lib.DEFAULT_BUCKET_MB)
    fsdp_bucket_bytes = int(mb) * 1024 * 1024
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
    if fsdp_module_prefixes and (params.weight_decay or 0.0):
      from kf_benchmarks_tpu.utils import log as log_util
      log_util.log_fn(
          "shard_params: weight decay over the scanned parameter "
          f"stack(s) {list(fsdp_module_prefixes)} reduces shard-"
          "locally + one mesh psum (full blocks exist only one at a "
          "time inside the scan): exact L2 value, reassociated -- "
          "total_loss is not bit-identical to the replicated-param L2 "
          "on this model family (pass --weight_decay=0 for bit-exact "
          "A/Bs)")
  weight_decay = params.weight_decay or 0.0
  # Loss-scale resolution (ref: benchmark_cnn.py:471-480 "None = model
  # default"): float16 compute defaults to the model's scale (128);
  # bfloat16 needs none unless explicitly requested.
  if params.use_fp16:
    if params.fp16_loss_scale is not None:
      init_loss_scale = float(params.fp16_loss_scale)
    elif compute_dtype == jnp.float16:
      init_loss_scale = float(model.get_fp16_loss_scale())
    else:
      init_loss_scale = 1.0
  else:
    init_loss_scale = 1.0
  auto_loss_scale = bool(params.use_fp16 and
                         params.fp16_enable_auto_loss_scale)
  use_loss_scale = auto_loss_scale or init_loss_scale != 1.0
  inc_every_n = params.fp16_inc_loss_scale_every_n

  state_specs = TrainState(
      step=P(), params=P(axis_all), opt_state=P(axis_all),
      batch_stats=P(axis_all), loss_scale=P(),
      loss_scale_normal_steps=P(), rng=P(), buffers=P(axis_all))
  staged_vars = bool(getattr(params, "staged_vars", False))
  relaxed = getattr(params, "variable_consistency", "strong") == "relaxed"
  steps_per_dispatch = int(
      getattr(params, "steps_per_dispatch", None) or 1)
  # --num_grad_accum=M: the step scans M microbatches (leading batch
  # split) accumulating gradients in f32 before ONE reduction collective
  # and ONE optimizer apply -- the Megatron-style memory lever (Shoeybi
  # et al. 2019): backward residuals are sized to B/M instead of B.
  # M=1 keeps the exact monolithic program (the PERF.md envelope).
  num_grad_accum = int(getattr(params, "num_grad_accum", None) or 1)
  # --overlap_gradient_reduction: bucketed in-backward all-reduce
  # (ops/overlap.py). Under microbatching the hooks disengage --
  # reduction stays post-hoc on the ACCUMULATED tree, preserving the
  # one-collective-per-step invariant (in-backward hooks inside the
  # microbatch scan would reduce M times per step).
  overlap_spec = overlap_lib.build(params)
  overlap_in_step = overlap_spec is not None and num_grad_accum == 1
  if overlap_spec is not None and num_grad_accum > 1:
    from kf_benchmarks_tpu.utils import log as log_util
    log_util.log_fn(
        f"overlap_gradient_reduction: --num_grad_accum="
        f"{num_grad_accum} keeps reduction post-hoc on the accumulated "
        "tree (one collective per step is the pinned invariant); "
        "in-backward hooks disengaged")
  # The factor data plane of the mean gradient (parallel/kungfu.py):
  # dense kernels larger than their batch leave the backward pass as the
  # replica mean already and the exchange below skips them. THE one
  # predicate for it: the step reduces by the plain replica mean over
  # more than one data replica, once, on the whole per-replica tree.
  # Every other mode keeps its program: local gradients (independent,
  # async_sgd, sma, the async parameter server), a built reducer,
  # in-backward hooks, ZeRO / FSDP's reduce-scatter, accumulation (one
  # reduction of the ACCUMULATED tree is a pinned invariant), the noise
  # scale (it reads the per-replica gradients), a model axis. Which
  # LAYERS take it is the shape rule's, in the layer
  # (kungfu.factors_beat_product).
  factor_exchange = (
      bool(getattr(strategy, "plain_mean", False))
      and int(mesh.shape[axis_data]) > 1
      and not (two_d and int(mesh.shape[MODEL_AXIS]) > 1)
      and overlap_spec is None
      and num_grad_accum == 1
      and not params.track_grad_noise_scale)
  tracing.active().set_static("factor_exchange",
                              kungfu.NO_FACTOR_EXCHANGE)
  # --health_stats: in-step device health stats (telemetry.py). The
  # step builder takes the CONCRETE boolean benchmark.py resolved
  # (None/auto never reaches here from the runtime); direct callers
  # passing an unresolved None get the exact legacy program, which is
  # what keeps the collective-count HLO pins in older tests meaningful.
  # (sequential_apply has no single optimizer-update tree to measure;
  # async PS is already health-rejected by validation/resolve -- this
  # keeps direct make_step_fns callers safe too.)
  # (sharded state never reaches here with health on -- validation.py
  # rejects the pair and resolve_health_stats auto-disables -- but the
  # builder re-guards for direct callers: the stats read the full
  # update tree, which the shard apply never materializes.)
  health_stats = (bool(getattr(params, "health_stats", None)) and
                  not getattr(strategy, "sequential_apply", False) and
                  not sharded_state)
  # --packed_sequences (models/transformer_lm.py): the model exposes
  # images -> (B, T) per-token loss weights; the cross-replica metric
  # combine then weights each replica by ITS real-label count (token-
  # weighted, not replica-weighted -- replicas pack different document
  # mixes), with the weighted loss terms PACKED into one vector pmean
  # so the packed program carries no more collectives than the
  # unpacked one (the lm_packed audit rule pins this).
  token_weight_fn = getattr(model, "token_weight_fn", None)
  # Top-level param-tree keys whose gradients the MODULE already
  # reduces in-backward (e.g. transformer_lm's scanned 'blocks' stack
  # hooks per layer inside the nn.scan); the step-level buckets skip
  # them so each gradient is reduced exactly once.
  module_reduced_prefixes = tuple(
      getattr(model, "in_backward_reduced_prefixes", ()) or ()
  ) if overlap_in_step else ()
  # Modules with a training-progress schedule (NASNet drop-path's
  # global-step ramp, ref: nasnet_utils.py:407-439) take ``progress`` =
  # step / total_training_steps; total steps is the run's --num_batches.
  import inspect
  module_takes_progress = (
      "progress" in inspect.signature(type(module).__call__).parameters)
  if total_train_steps is None:
    total_train_steps = int(getattr(params, "num_batches", None) or 0)
  total_train_steps = int(total_train_steps)

  def _squeeze(tree):
    return jax.tree.map(lambda x: jnp.squeeze(x, axis=0), tree)

  def _expand(tree):
    return jax.tree.map(lambda x: x[None], tree)

  # -- init -----------------------------------------------------------------

  def _init(rng, sample_images):
    variables = module.init({"params": rng, "dropout": rng}, sample_images)
    model_params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if sharded_params:
      # Full FSDP: the PARAM storage itself is the shard stack (per-
      # layer rows for the scanned prefixes), and the per-shard
      # optimizer state mirrors it leaf-for-leaf -- tx.init vmapped
      # over the uniform leading shard-row dim.
      params_store = sharded_lib.fsdp_stacked_shards(
          model_params, num_replicas, fsdp_module_prefixes)
      return params_store, jax.vmap(tx.init)(params_store), batch_stats
    if sharded_state:
      # Per-shard optimizer state: vmap tx.init over the stacked flat
      # param shards (ops/sharded.py layout), so every opt-state leaf
      # comes out (n, k) with row i = device i's shard -- global bytes
      # ~|state| instead of the replicated stack's n * |state|.
      opt_state = jax.vmap(tx.init)(
          sharded_lib.stacked_shards(model_params, num_replicas))
    else:
      opt_state = tx.init(model_params)
    return model_params, opt_state, batch_stats

  def init_state(rng, sample_images):
    """Builds the stacked per-replica TrainState (identical init on every
    replica == the reference's post-init broadcast, variable_mgr.py:342-356).
    Under --shard_optimizer_state the opt_state rows are per-device
    SHARDS, not copies (see _init); under --shard_params the params
    rows are shards too (the FSDP steady state -- per-device param HBM
    |params|/n)."""
    params_store, opt_state, batch_stats = _init(rng, sample_images)
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_replicas,) + x.shape), t)
    buffers = {}
    if relaxed:
      # Warmed up with zero gradients, like the reference's StagingArea
      # warmup put (ref: batch_allreduce.py:357-359).
      buffers["deferred_grads"] = stack(
          jax.tree.map(jnp.zeros_like, params_store))
    if staged_vars:
      buffers["staged_params"] = stack(params_store)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params_store if sharded_params else stack(params_store),
        opt_state=opt_state if sharded_state else stack(opt_state),
        batch_stats=stack(batch_stats),
        loss_scale=jnp.asarray(init_loss_scale, jnp.float32),
        loss_scale_normal_steps=jnp.zeros((), jnp.int32),
        rng=rng,
        buffers=buffers)

  # -- train step -----------------------------------------------------------

  # --shard_params engagement mirrors the overlap hooks' rule: under
  # --num_grad_accum the in-compute per-bucket gathers DISENGAGE -- the
  # full tree is re-assembled once before the microbatch scan and the
  # accumulated gradient is scattered post-hoc (so the scatter still
  # meets the accumulated sums in the same order as the round-11 path:
  # bit-identity is preserved; the param-residency win is accum=1's).
  fsdp_in_step = sharded_params and num_grad_accum == 1

  def per_replica_train(state, images, labels):
    model_params = _squeeze(state.params)
    opt_state = _squeeze(state.opt_state)
    batch_stats = _squeeze(state.batch_stats)
    buffers = _squeeze(state.buffers)
    # --staged_vars: forward/backward read one-step-stale weights while
    # updates land on the live ones (ref: StagedVariableGetter,
    # variable_mgr_util.py:313-393).
    forward_params = (buffers["staged_params"] if staged_vars
                      else model_params)
    if sharded_params and not fsdp_in_step:
      # FSDP + accumulation: one whole-tree gather up front (the
      # round-11 steady state, rotated to the step top), full-tree
      # microbatch scan, post-hoc scatter below.
      with jax.named_scope("exchange"):
        forward_params = sharded_lib.fsdp_gather_full(
            model_params, fsdp_template, fsdp_module_prefixes,
            nested=use_gspmd)
    # Data-replica id: on the 2-D mesh, model-axis peers fold the SAME
    # id (same batch shard, same dropout stream), which is what makes
    # their local gradients identical by construction -- the free
    # model-axis sub-slice in ops/sharded.py depends on it.
    replica_id = lax.axis_index(axis_data)
    step_rng = jax.random.fold_in(
        jax.random.fold_in(state.rng, state.step), replica_id)

    apply_kwargs = {}
    if module_takes_progress and total_train_steps > 0:
      apply_kwargs["progress"] = (
          state.step.astype(jnp.float32) / total_train_steps)
    # This trace's record of the kernels on the factor plane (None:
    # none may take it, and the context below does nothing).
    factor_plan = (kungfu.FactorExchange(axis_data, mesh.shape[axis_data])
                   if factor_exchange else None)

    def loss_fn(p, mb_images, mb_labels, bs, dropout_rng):
      if overlap_in_step:
        # Bucketed in-backward reduction (ops/overlap.py): every use of
        # p below flows through the wrapped copy, so jax.grad returns
        # ALREADY replica-reduced gradients, one collective per bucket
        # issued where that bucket's backward completes. The post-hoc
        # strategy reduction is skipped (overlap_in_step below).
        # Ordering vs the loss-scale unscale is exact: the hooks reduce
        # the SCALED cotangents and the unscale divides by a
        # power-of-two scale afterwards (exponent shift; bit-identical
        # to dividing first, as the post-hoc path does).
        with jax.named_scope("exchange"):
          p = overlap_lib.wrap_tree(
              p, axis_data, overlap_spec.bucket_bytes,
              compact_dtype=overlap_spec.compact_dtype,
              exclude_prefixes=module_reduced_prefixes)
      if fsdp_in_step:
        # FSDP per-bucket gather (ops/overlap.py gather_params): every
        # non-module-gathered leaf of p below is the RE-ASSEMBLED full
        # value (one packed all-gather per builder-layer bucket), the
        # module-gathered scanned stacks stay shards for the per-block
        # hook inside the nn.scan body; jax.grad then returns shard-
        # layout gradients already reduce-scattered (batch mean + free
        # model sub-slice), one collective per bucket/block, each
        # issued where that bucket's backward completes. The unscale-
        # after-scatter ordering is exact for the same power-of-two
        # reason as the overlap hooks above.
        with jax.named_scope("exchange"):
          p = overlap_lib.fsdp_wrap_shards(
              p, fsdp_template, fsdp_bucket_bytes, BATCH_AXIS, MODEL_AXIS,
              exclude_prefixes=fsdp_module_prefixes, nested=use_gspmd)
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
        if weight_decay:
          if fsdp_in_step and fsdp_module_prefixes:
            # The scanned-stack leaves of p are SHARDS here (their full
            # values exist only block-at-a-time inside the scan), so
            # their L2 term reduces shard-locally + one scalar psum over
            # the mesh -- exact in value (shards tile the stack once,
            # pad is zero) but reassociated, so total_loss is NOT
            # bit-identical to the replicated-param L2 for scanned
            # models with weight decay (the make_step_fns note logs
            # this; the gathered non-scanned leaves keep the exact
            # legacy term).
            total_loss = total_loss + weight_decay * _l2_loss_mixed(
                p, fsdp_module_prefixes, axis_all,
                single_op=params.single_l2_loss_op)
          else:
            total_loss = total_loss + weight_decay * l2_loss(
                p, single_op=params.single_l2_loss_op)
        scaled = total_loss * state.loss_scale
      return scaled, (base_loss, total_loss, new_bs, result)

    accum_acc_metrics = None
    accum_tok_w = None
    if num_grad_accum > 1:
      # Microbatched accumulation (--num_grad_accum=M): one scan
      # iteration per microbatch, so the compiled program carries ONE
      # microbatch-sized forward+backward regardless of M, and XLA
      # reuses that iteration's activation buffers M times. Gradients
      # accumulate in f32 (the master precision) and are divided once,
      # so the accumulated gradient is the mean over microbatches --
      # the same estimator as the monolithic step up to float
      # reassociation of the batch reduction. Everything downstream
      # (ONE strategy reduction, the loss-scale state machine, the
      # optimizer apply) sees exactly one gradient tree per step.
      m = num_grad_accum
      if images.shape[0] % m:
        raise ValueError(
            f"--num_grad_accum={m} must divide the per-replica batch "
            f"size {images.shape[0]} (validation.py admits only "
            "configurations where it can)")
      split = lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:])
      mb_images = split(images)
      mb_labels = jax.tree.map(split, labels)
      grad_fn = jax.grad(loss_fn, has_aux=True)
      want_acc = bool(params.print_training_accuracy)
      # Scan carries start as zeros; inside the shard_map body the
      # gradients/metrics they accumulate vary over every axis the
      # batch OR the parameters vary over (both axes on the 2-D mesh),
      # so the zeros are pcast to match (sequence.py vary_like).
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
        # the PER-REPLICA monolithic token-weighted estimator -- sum
        # over tokens / total tokens -- not a mean-of-means over
        # unevenly packed microbatches. Deliberate scope: the CROSS-
        # replica gradient exchange stays the equal-weight pmean
        # (replicas' token counts concentrate tightly at ~97% packing,
        # and token-weighting the exchange would rebuild every pinned
        # reduction path -- strategies, overlap hooks, the sharded
        # scatter -- for a second-order correction), so the optimized
        # objective weights replicas equally while the REPORTED metrics
        # are exactly token-weighted (pmean(loss*w)/pmean(w) below).
        # Unpacked runs keep mb_w = 1 (the exact legacy equal-weight
        # program).
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
      if want_acc:
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
        accum_tok_w = w_sum
      grads = jax.tree.map(lambda a, p: (a / norm).astype(p.dtype),
                           g_acc, forward_params)
      base_loss = bl_acc / norm
      total_loss = tl_acc / norm
      net_result = None
      if acc_acc is not None:
        accum_acc_metrics = {k: v / norm for k, v in acc_acc.items()}
    else:
      grads, (base_loss, total_loss, new_bs, net_result) = jax.grad(
          loss_fn, has_aux=True)(forward_params, images, labels,
                                 batch_stats, step_rng)
    if use_loss_scale or auto_loss_scale:
      grads = jax.tree.map(lambda g: g / state.loss_scale, grads)
    noise_stats = None
    if params.track_grad_noise_scale and num_replicas > 1:
      # Measured on the pre-reduction per-replica grads (the small-batch
      # estimate) vs their replica mean (the large-batch estimate); see
      # elastic.noise_scale_stats. This is the in-collective monitoring
      # KungFu's runtime does (SURVEY 2.9 "monitored gradient noise
      # scale").
      with jax.named_scope("metrics"):
        noise_stats = elastic_lib.noise_scale_stats(
            grads, axis_data, images.shape[0])
    grad_shards = None
    if fsdp_in_step:
      # Full FSDP: the in-backward gather hooks already reduce-
      # scattered every bucket/block cotangent onto the shard layout
      # (ops/overlap.py gather_params bwd -- elementwise identical to
      # the post-hoc scatter below); jax.grad's output IS the shard
      # tree. No full gradient tree ever existed.
      grad_shards = grads
    elif sharded_params:
      # FSDP + accumulation: post-hoc scatter of the accumulated full
      # tree onto the FSDP layout (per-layer rows for the scanned
      # stacks) -- elementwise the same values as scatter_mean.
      with jax.named_scope("exchange"):
        grad_shards = sharded_lib.fsdp_scatter_mean(grads,
                                                    fsdp_module_prefixes)
    elif sharded_state:
      # ZeRO gradient pass (ops/sharded.py): reduce-scatter of the
      # batch-axis mean -- each scatter group meets the same B distinct
      # contributions in the same group order as the replicated pmean,
      # so the scattered mean is BIT-IDENTICAL to it -- then the free
      # model-axis sub-slice. The full gradient tree dies here; only
      # this device's 1/n flat shard flows on.
      with jax.named_scope("exchange"):
        grad_shards = sharded_lib.scatter_mean(grads)
    elif not overlap_in_step:
      # "exchange" names the strategy's whole reduction -- the casts,
      # concatenations and scalings around the collectives too, which a
      # reader that goes by opcode alone would miss (benchmarks/spans.py).
      with jax.named_scope("exchange"):
        reduce = lambda g: strategy.reduce_gradients(g, axis_data)
        if factor_plan is not None and factor_plan.claimed:
          grads = _reduce_unclaimed(grads, factor_plan.claimed, reduce)
          counters = factor_plan.counters()
          tracing.active().set_static("factor_exchange", counters)
          from kf_benchmarks_tpu.utils import log as log_util
          log_util.log_fn(
              "factor exchange: %d dense layer(s) form the mean gradient "
              "from all-gathered factors: %.1f MB kept off the "
              "all-reduce, %.1f MB gathered instead" % (
                  counters["layers"],
                  counters["bytes_off_allreduce"] / 1e6,
                  counters["bytes_gathered"] / 1e6))
        else:
          grads = reduce(grads)
    # else: the in-backward hooks already reduced every bucket
    # (module-internal hooks for module_reduced_prefixes, the loss_fn
    # wrap for the rest); everything downstream -- the auto-loss-scale
    # finite check, relaxed-consistency banking, the optimizer apply --
    # sees the reduced tree exactly as on the post-hoc path.

    def _all_finite(tree, axis):
      ok = jnp.all(jnp.stack(
          [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(tree)]))
      # Globally uniform decision (pmin across replicas) so every carried
      # scalar stays replicated (ref chief-only NaN check + broadcast,
      # variable_mgr.py:186-193).
      return lax.pmin(ok.astype(jnp.int32), axis).astype(bool)

    # The loss-scale state machine keys on THIS step's fresh gradients
    # (they reflect the current scale), even when the applied gradients
    # are the deferred ones (ref: variable_mgr_util.py:51-139). On the
    # sharded path the shards tile the full reduced tree, so the pmin
    # over BOTH axes covers every element exactly once; on the
    # replicated 2-D path the model-axis peers hold identical gradients,
    # so the same all-axes pmin is exact and keeps the carried loss-scale
    # scalars typed replicated.
    if auto_loss_scale:
      fresh_finite = _all_finite(
          grad_shards if sharded_state else grads, axis_all)
    else:
      fresh_finite = None
    new_buffers = dict(buffers)
    if relaxed:
      # --variable_consistency=relaxed: apply the PREVIOUS step's reduced
      # gradients and bank this step's for the next -- the double-buffered
      # reformulation of the reference's deferred StagingArea gradients
      # (ref: batch_allreduce.py:353-388; SURVEY 7.4). Non-finite fresh
      # gradients are never banked (the deferred analog of the skipped
      # update): the old bank stays.
      banked = grads
      if fresh_finite is not None:
        banked = jax.tree.map(
            lambda a, b: jnp.where(fresh_finite, a, b),
            grads, buffers["deferred_grads"])
      new_buffers["deferred_grads"] = banked
      grads = buffers["deferred_grads"]

    with jax.named_scope("exchange"):
      model_params_pre = strategy.pre_update(model_params, state.step,
                                             axis_data)
    if sharded_state:
      # The ZeRO apply (the reference's central variable placement
      # rendered SPMD, variable_mgr.py:201-243): run the optimizer on
      # the 1/n shard ONLY (elementwise optimizers; validation.py
      # rejects LARS). Optimizer HBM per device is |state|/n.
      # --shard_params: the state ALREADY holds this device's shards
      # (the FSDP steady state) and the updated shards flow straight
      # back into it -- the round-11 trailing full-tree all-gather is
      # GONE; re-assembly happens inside the next step's compute, one
      # bucket/block at a time. Without it, params are replicated: the
      # shard is a free local slice and the updated params return by
      # all-gather for the next forward.
      param_shards = (model_params_pre if sharded_params
                      else sharded_lib.local_shards(model_params_pre))
      with jax.named_scope("optimizer_apply"):
        updates, new_opt_state = tx.update(grad_shards, opt_state,
                                           param_shards)
        new_shards = optax.apply_updates(param_shards, updates)
      with jax.named_scope("exchange"):
        new_params = (new_shards if sharded_params else
                      sharded_lib.gather_tree(new_shards, model_params_pre,
                                              nested=use_gspmd))
    elif getattr(strategy, "sequential_apply", False):
      # Async PS with a stateful optimizer (strategies.py): serialize
      # every replica's unaveraged gradient through the SHARED optimizer
      # state, in replica-index order -- the deterministic SPMD
      # rendering of the PS's one-at-a-time applications (ref async
      # mode: benchmark_cnn.py:520-522).
      with jax.named_scope("exchange"):
        g_all = jax.tree.map(
            lambda g: lax.all_gather(g, axis_data, axis=0), grads)

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
      new_params = strategy.post_update(new_params, state.step, axis_data)
      new_bs = strategy.sync_batch_stats(new_bs, axis_data)

    if auto_loss_scale:
      # Auto loss-scale state machine (ref: variable_mgr_util.py:51-139):
      # any non-finite FRESH grad -> skip the update, halve scale; else
      # count a normal step and double the scale every ``inc_every_n``.
      # Under relaxed consistency the APPLIED gradients are the previous
      # bank, which only ever admits finite values (banking gate above),
      # so the params/opt_state skip is unnecessary there by induction.
      keep = lambda new, old: jax.tree.map(
          lambda a, b: jnp.where(fresh_finite, a, b), new, old)
      if not relaxed:
        new_params = keep(new_params, model_params)
        new_opt_state = keep(new_opt_state, opt_state)
      # batch_stats come from THIS step's forward in both modes: an
      # overflowing forward must not poison the running statistics.
      new_bs = keep(new_bs, batch_stats)
      normal_steps = jnp.where(fresh_finite,
                               state.loss_scale_normal_steps + 1,
                               0)
      do_double = jnp.logical_and(fresh_finite,
                                  normal_steps >= inc_every_n)
      new_scale = jnp.where(
          fresh_finite,
          jnp.where(do_double, state.loss_scale * 2.0, state.loss_scale),
          jnp.maximum(state.loss_scale / 2.0, 1.0))
      normal_steps = jnp.where(do_double, 0, normal_steps)
    else:
      new_scale = state.loss_scale
      normal_steps = state.loss_scale_normal_steps

    # "metrics": the loss / accuracy / health reductions of the step
    # line, apart from the training arithmetic (benchmarks/spans.py).
    with jax.named_scope("metrics"):
      lr = lr_fn(state.step)
      # Token-weighted metric combine (--packed_sequences): this
      # replica's real-label count; per-replica losses are already
      # normalized by it (ops/fused_loss.py), so the global token-mean is
      # pmean(loss * w) / pmean(w) -- computed from the SAME packed
      # vector collective that carries the losses.
      tok_w = None
      if token_weight_fn is not None:
        tok_w = (accum_tok_w if accum_tok_w is not None
                 else jnp.sum(token_weight_fn(images)))
      wm_safe = None
      if health_stats:
        # In-step health stats (telemetry.py): grad norm, update/param
        # ratio, non-finite leaf count, loss scale + skip flag -- all
        # read from the step's post-reduction values, so they are
        # replica-identical for the replica-synchronous strategies
        # validation admits. Each replica reduces a 1/n SLICE of every
        # tree (telemetry.health_partials) and the pre-scaled partial
        # sums ride the LOSS pmean: one f32 vector all-reduce replaces
        # the two scalar loss pmeans, so the health-on program carries
        # NO extra collective (acceptance-pinned in
        # tests/test_telemetry.py) and no replicated full-tree passes.
        # Elementwise, the vector all-reduce computes bit-identical loss
        # values to the scalar ones (equivalence pinned in the same
        # tests). ``updates`` exists on every health-admitted path:
        # sequential_apply (async PS) is rejected/auto-disabled by
        # validation.py and resolve_health_stats.
        skipped = (1.0 - fresh_finite.astype(jnp.float32)
                   if fresh_finite is not None else jnp.float32(0.0))
        # The fresh-grad overflow skip only suppresses the applied
        # update on the non-relaxed path (the relaxed bank admits finite
        # gradients only, so its apply always lands).
        suppressed = jnp.float32(0.0) if relaxed else skipped
        # Under --packed_sequences the two loss slots ride token-weighted
        # (loss * w) and w itself is appended to the SAME vector, so the
        # weighted combine still costs the one loss pmean.
        bl32 = base_loss.astype(jnp.float32)
        tl32 = total_loss.astype(jnp.float32)
        loss_slots = (jnp.stack([bl32, tl32]) if tok_w is None else
                      jnp.stack([bl32 * tok_w, tl32 * tok_w]))
        vec = [loss_slots, telemetry_lib.health_partials(
            grads, model_params, updates, axis_data)]
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
                health_totals, new_scale, skipped, suppressed),
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
      if steps_per_dispatch > 1:
        # Replica-mean global norm of the reduced gradients (under relaxed
        # consistency: of the APPLIED, one-step-stale bank) -- the
        # per-step training-health scalar the chunked mode stacks
        # alongside loss and lr, replacing what an operator would
        # otherwise probe with per-step fetches. K=1 omits it so the
        # single-step program stays the exact program behind PERF.md's
        # pinned envelope numbers.
        if "health" in metrics:
          # The health vector already carries this exact norm (same grads
          # tree, sharded reduction): reuse it rather than paying a second,
          # full-tree replicated square-sum pass -- the replicated pass is
          # the ~2x-step-time cost _sharded_sumsq exists to avoid.
          metrics["grad_norm"] = metrics["health"][0]
        elif sharded_state:
          # The flat shards tile the reduced gradient exactly once, so
          # the psum of per-shard square-sums over BOTH axes is the global
          # square-sum -- no full-tree pass, same cost argument as the
          # health path's sharded reduction.
          metrics["grad_norm"] = jnp.sqrt(lax.psum(
              sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(grad_shards)), axis_all))
        else:
          metrics["grad_norm"] = lax.pmean(
              jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in jax.tree.leaves(grads))), axis_data)
      if params.print_training_accuracy:
        # Under microbatching the per-microbatch scalar accuracies were
        # averaged inside the scan (equal microbatch sizes make that the
        # effective-batch value); monolithic computes them here.
        acc = (accum_acc_metrics if accum_acc_metrics is not None
               else model.accuracy_function(net_result, labels))
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
    if noise_stats is not None:
      metrics["noise_scale_g2"], metrics["noise_scale_s"] = noise_stats

    if staged_vars:
      # Next step's reads see this step's PRE-update weights: the value
      # that was in the staging area at read time (one-step staleness).
      new_buffers["staged_params"] = model_params
    # Writing the updated state back belongs to the apply: XLA fuses the
    # stacking reshape with the update and names the fusion after it.
    with jax.named_scope("optimizer_apply"):
      stacked_params = _expand(new_params)
      stacked_opt_state = _expand(new_opt_state)
    new_state = TrainState(
        step=state.step + 1,
        params=stacked_params,
        opt_state=stacked_opt_state,
        batch_stats=_expand(new_bs),
        loss_scale=new_scale,
        loss_scale_normal_steps=normal_steps,
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
  # varying-manual-axes checker even though the program is correct. Those
  # models opt out via relax_shard_map_vma; everyone else keeps the
  # checker (it catches missing pmeans under out_specs=P()).
  check_vma = not getattr(model, "relax_shard_map_vma", False)

  # 2-D mesh: the step metrics are reduced over 'batch' only, and the
  # model-axis peers hold bit-identical copies by construction (same
  # batch shard, same parameters) -- which the varying-manual-axes types
  # cannot know. So the metrics leave the manual region stacked over
  # 'model' (the honest out_spec, dim ``dim``) and the jitted wrapper
  # keeps row 0: free on the Nx1 meshes, the partitioner's choice on
  # BxM. 1-D meshes keep out_specs=P().
  def _metric_spec(dim=0):
    return P(*([None] * dim), MODEL_AXIS) if two_d else P()

  def _stack_model(tree, dim=0):
    if not two_d:
      return tree
    return jax.tree.map(lambda x: jnp.expand_dims(x, dim), tree)

  def _pick_model(tree, dim=0):
    if not two_d:
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
  # speaks bound axis names (every lax.p* above), so instead of
  # shard_map it is traced under two nested jax.vmap's -- outer
  # 'batch', inner 'model' -- each binding axis_name AND
  # spmd_axis_name over the (B, M)-regridded stacked state. The
  # spmd_axis_name pins each vmap dimension to its mesh axis, the
  # surrounding plain jit carries the SAME NamedShardings the manual
  # path's specs induce, and GSPMD is then free to choose/re-place the
  # collectives (the twin-referee rule in analysis/audit.py diffs the
  # result against the hand placement). Batch inputs map on the outer
  # vmap only (model peers see the same shard, exactly like in_specs
  # P(axis_data)); scalars replicate in (in_axes=None) and come back
  # broadcast (out_axes=0 everywhere -- the [0, 0] pick below avoids
  # proving replication to vmap). eval_step and broadcast_init stay on
  # the manual shard_map path in both modes: neither is on the
  # steady-state hot path the twin A/B measures.
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

  if use_gspmd:
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
    if images.shape[0] == 1 and steps_per_dispatch > 1:
      im0 = images[0]
      lb0 = jax.tree.map(lambda x: x[0], labels)
      new_state, metrics = lax.scan(
          lambda st, _: per_replica_train(st, im0, lb0), state, None,
          length=steps_per_dispatch)
      return new_state, metrics
    new_state, metrics = lax.scan(
        lambda st, batch: per_replica_train(st, *batch), state,
        (images, labels))
    return new_state, metrics

  train_chunk = None
  if steps_per_dispatch > 1:
    if use_gspmd:
      train_chunk = _gspmd_wrap(per_replica_train_chunk, 1)
    else:
      # Per-step metrics come back stacked on a leading K axis; the
      # model stacking rides behind it.
      train_chunk = _sharded_step(per_replica_train_chunk,
                                  P(None, axis_data), dim=1)

  # -- forward-only / eval step --------------------------------------------

  def per_replica_eval(state, images, labels):
    model_params = _squeeze(state.params)
    if sharded_params:
      # Mid-training eval re-assembles the full tree (the eval module
      # carries no FSDP hooks); eval is occasional, so the transient
      # full-tree residency is acceptable -- the steady-state training
      # program is what the residency contract binds.
      model_params = sharded_lib.fsdp_gather_full(
          model_params, fsdp_template, fsdp_module_prefixes)
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
      # validation.py): same token-weighted cross-replica combine as
      # the train metrics -- each replica's loss/accuracy is already
      # normalized by ITS real-label count, and replicas pack different
      # document mixes, so an equal-weight pmean would bias the global
      # value toward lightly-packed replicas.
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
