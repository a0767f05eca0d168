"""Parallelism strategies: the VariableMgr hierarchy, re-designed SPMD.

The reference's VariableMgr subclasses (ref: variable_mgr.py:28-831)
answer: where do variables live, how are gradients aggregated, what syncs
at init. Under SPMD all replicas run one program, so each strategy
becomes a set of pure hooks called inside the shard_mapped train step:

  reduce_gradients  -- gradient aggregation (psum / spec-driven / none)
  plain_mean        -- whether that aggregation is the plain replica mean
  pre_update        -- weight transform before the optimizer step (SMA)
  post_update       -- weight transform after the step (pair-averaging)
  sync_batch_stats  -- BN running-stat treatment across replicas
  broadcast_init    -- replica-0 state broadcast at start

Mapping from --variable_update (ref selection: benchmark_cnn.py:1481-1524):
  independent            -> no reduction (ref: variable_mgr.py:164-198)
  replicated             -> pmean grads (ref: variable_mgr.py:277-368)
  parameter_server       -> pmean grads; sharded optimizer state is the
                            TPU analog of central variable placement
                            (ref: variable_mgr.py:201-243; SURVEY 5.8)
  distributed_replicated -> pmean within + across processes (one SPMD
                            program spans hosts; ref: variable_mgr.py:704-831)
  distributed_all_reduce / collective_all_reduce
                         -> spec-driven reduction (ref: variable_mgr.py:371-625)
  horovod                -> per-gradient pmean (ref: benchmark_cnn.py:3122-3130)
  kungfu                 -> optimizer-level hooks per --kungfu_option
                            (ref: benchmark_cnn.py:1192-1204)

The synchronous contract ("pmean grads" above, KungFu sync_sgd) is that
every replica applies the MEAN of all replicas' gradients every step.
Where a strategy keeps it by the plain mean (``plain_mean``: no reducer
built), that mean has two data planes (parallel/kungfu.py): the
all-reduce of the per-replica products (``allreduce_mean``), and for a
dense kernel larger than its batch the product of the all-gathered
factors (``factor_mean_dot``), formed in the backward pass. The train
step decides once which leaves go which way (train_step.plan_step)
and hands ``reduce_gradients`` the rest.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax import lax

from kf_benchmarks_tpu.parallel import kungfu
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS


class Strategy:
  """Base: single-replica semantics (no cross-replica traffic)."""

  name = "independent"
  # Whether gradients are averaged across replicas (determines whether the
  # effective batch for LR scaling is the global batch).
  cross_replica = False

  def __init__(self, params=None):
    self.params = params

  @property
  def plain_mean(self) -> bool:
    """True where ``reduce_gradients`` IS ``kungfu.allreduce_mean``, leaf
    by leaf: the one case in which a leaf that is the replica mean
    already (the factor data plane) may be left out of it."""
    return False

  def reduce_gradients(self, grads, axis_name=REPLICA_AXIS):
    return grads

  def pre_update(self, model_params, step, axis_name=REPLICA_AXIS):
    return model_params

  def post_update(self, model_params, step, axis_name=REPLICA_AXIS):
    return model_params

  def sync_batch_stats(self, batch_stats, axis_name=REPLICA_AXIS):
    """Replicated modes keep BN stats identical (pmean); independent modes
    keep tower-local stats like the reference's per-tower BN."""
    return batch_stats

  def broadcast_init(self, tree, axis_name=REPLICA_AXIS):
    """Replica-0 broadcast at session start (ref: benchmark_cnn.py:2094-2100).
    Under SPMD, identical init makes this a no-op for most strategies, but
    independent/kungfu keep it for parity with explicitly diverged state."""
    return tree


class IndependentStrategy(Strategy):
  """(ref: variable_mgr.py:164-198)"""
  name = "independent"


class ReplicatedStrategy(Strategy):
  """All-reduce averaged gradients, replicated weights
  (ref: variable_mgr.py:277-368).

  ``reducer`` is the flag-selected reduction path built by
  ops/allreduce.build_reducer -- all_reduce_spec planner, gradient
  repacking, small-grad aggregation, or hierarchical copy (ref:
  batch_allreduce.py:300-317 algorithm_from_params); None = direct pmean.
  """

  name = "replicated"
  cross_replica = True

  def __init__(self, params=None, reducer=None):
    super().__init__(params)
    self.reducer = reducer

  @property
  def plain_mean(self) -> bool:
    return self.reducer is None

  def reduce_gradients(self, grads, axis_name=REPLICA_AXIS):
    if self.reducer is not None:
      return self.reducer(grads, axis_name)
    return kungfu.allreduce_mean(grads, axis_name)

  def sync_batch_stats(self, batch_stats, axis_name=REPLICA_AXIS):
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), batch_stats)


class ParameterServerStrategy(ReplicatedStrategy):
  """PS analog: synchronous aggregation; on TPU the 'server' is the
  sharded optimizer state, not a host process (SURVEY 5.8 gRPC-PS row)."""
  name = "parameter_server"


class ShardedOptimizerStrategy(ReplicatedStrategy):
  """ZeRO/FSDP sharded optimizer state (--shard_optimizer_state) on the
  named 2-D ('batch', 'model') mesh: the faithful TPU rendering of the
  reference's central variable placement (the PS "server copy" of
  variables + optimizer slots, ref: variable_mgr.py:201-243; across
  hosts :704-831; SURVEY 5.8) -- the server is the 1/n state shard each
  device owns, gradients meet in a reduce-scatter instead of the
  all-reduce, and updated params return by all-gather.

  The hooks here are markers only: the scatter/apply/gather mechanics
  live in train_step.py's sharded branch + ops/sharded.py (the step
  owns gradient packing and the optimizer apply, exactly as it owns
  them for sequential_apply). ``sync_batch_stats`` stays the inherited
  pmean -- BN statistics remain replicated; only optimizer state
  shards."""

  name = "parameter_server(sharded)"
  cross_replica = True
  sharded_state = True
  plain_mean = False  # a reduce-scatter onto the state shards

  def reduce_gradients(self, grads, axis_name=REPLICA_AXIS):
    raise NotImplementedError(
        "sharded-state gradient reduction is the step's reduce-scatter "
        "(train_step.py + ops/sharded.py), not a strategy hook")


class AsyncParameterServerStrategy(ReplicatedStrategy):
  """Async PS (--cross_replica_sync=false, ref: benchmark_cnn.py:520-522).

  In the reference every worker applies its own UNAGGREGATED gradient to
  the one PS-hosted weight + optimizer-state copy; the state stays
  shared, only the averaging disappears. The SPMD reformulation keeps
  exactly those properties, by optimizer class:

  * plain SGD: N sequential unaveraged applications to shared weights
    collapse into ONE update by the gradient SUM -- gradients are
    psum-summed and applied once (exact, and cheapest).
  * stateful optimizers (momentum/rmsprop/adam): the collapse does not
    hold, so ``sequential_apply`` makes the train step all-gather the
    per-replica gradients and apply them ONE AT A TIME through the
    shared optimizer state (a lax.scan over replicas) -- a faithful
    serialization of the PS's nondeterministic interleaving, fixed to
    replica-index order so every replica computes the identical result.

  The reference's timing asynchrony itself (workers at different steps,
  GlobalStepWatcher) has no SPMD analog -- steps run in lockstep; the
  per-step window math is therefore exact (see KungFuStrategy's
  throughput note).

  Cost: ``sequential_apply`` is O(n) optimizer applications per step plus
  an all-gather of n full gradient trees -- a CORRECTNESS mode, not a
  scaling mode. validation.py caps it at
  ASYNC_PS_SEQUENTIAL_MAX_DEVICES; the measured cost curve vs n is in
  PERF.md (async-PS micro-benchmark)."""

  name = "parameter_server(async)"
  # Unaveraged gradients: the effective step scale follows the
  # per-worker batch, as the reference's async mode behaves.
  cross_replica = False
  plain_mean = False  # the sum, or every replica's own in turn

  def __init__(self, params=None, reducer=None):
    super().__init__(params, reducer=reducer)
    self.sequential_apply = bool(
        params is not None and getattr(params, "optimizer", "sgd") != "sgd")

  def reduce_gradients(self, grads, axis_name=REPLICA_AXIS):
    if self.sequential_apply:
      # The train step gathers and serializes these local gradients
      # through the shared optimizer state; summing here would apply
      # every gradient n times.
      return grads
    if self.reducer is not None:
      grads = self.reducer(grads, axis_name)
      n = lax.axis_size(axis_name)
      return jax.tree.map(lambda g: g * n, grads)  # undo the mean
    return jax.tree.map(lambda g: lax.psum(g, axis_name), grads)


class CollectiveAllReduceStrategy(ReplicatedStrategy):
  """Spec-driven reduction (ref: variable_mgr.py:486-625). The all-reduce
  spec planner (ops/allreduce.py) may decompose pmean into
  reduce-scatter + all-gather or hierarchical 2-level reductions."""
  name = "collective_all_reduce"

  def __init__(self, params=None, planner=None, reducer=None):
    if planner is not None and reducer is None:
      reducer = planner.reduce
    super().__init__(params, reducer=reducer)
    self.planner = planner


class KungFuStrategy(Strategy):
  """KungFu optimizer-wrapper semantics (ref: benchmark_cnn.py:1192-1204;
  SURVEY 2.9), dispatched on --kungfu_option:

    sync_sgd  -- SynchronousSGDOptimizer: every replica applies the
                 mean gradient (two data planes: the all-reduce of the
                 products, or the product of all-gathered factors for a
                 dense kernel larger than its batch; parallel/kungfu.py)
    async_sgd -- PairAveragingOptimizer: local grads + pairwise weight
                 gossip (ppermute), reformulated synchronous (SURVEY 7.4)
    sma       -- SynchronousAveragingOptimizer: average weights, then
                 local gradient step

  Throughput semantics under async_sgd/sma: AD-PSGD's asynchrony does
  not exist under SPMD -- every replica executes the same step in
  lockstep, so a "global step" is one synchronized step of all replicas
  and the standard window math applies unchanged. The reference's
  GlobalStepWatcher (ref: benchmark_cnn.py:639-684), which existed to
  measure true global-step rate when replicas advanced independently,
  has nothing to measure here by construction; the asynchrony is
  reformulated into the deterministic gossip schedule, not the timing.
  """

  name = "kungfu"

  def __init__(self, params=None, option: str = "sync_sgd"):
    super().__init__(params)
    if option not in ("sync_sgd", "async_sgd", "sma"):
      raise ValueError(f"Invalid kungfu_option {option!r}")
    self.option = option
    self.cross_replica = option == "sync_sgd"

  @property
  def plain_mean(self) -> bool:
    return self.option == "sync_sgd"

  def reduce_gradients(self, grads, axis_name=REPLICA_AXIS):
    if self.option == "sync_sgd":
      return kungfu.allreduce_mean(grads, axis_name)
    return grads

  def pre_update(self, model_params, step, axis_name=REPLICA_AXIS):
    if self.option == "sma":
      return kungfu.sync_average(model_params, axis_name)
    return model_params

  def post_update(self, model_params, step, axis_name=REPLICA_AXIS):
    if self.option == "async_sgd":
      return kungfu.pair_average(model_params, step, axis_name)
    return model_params

  def sync_batch_stats(self, batch_stats, axis_name=REPLICA_AXIS):
    if self.option == "sync_sgd":
      return jax.tree.map(lambda x: lax.pmean(x, axis_name), batch_stats)
    return batch_stats

  def broadcast_init(self, tree, axis_name=REPLICA_AXIS):
    return kungfu.broadcast(tree, root=0, axis_name=axis_name)


def get_strategy(params) -> Strategy:
  """Strategy selection (ref: benchmark_cnn.py:1481-1524)."""
  vu = params.variable_update
  if getattr(params, "shard_optimizer_state", False):
    # validation.validate_cross_flags restricts this to the synchronous
    # replicated/parameter_server family; the sharded strategy subsumes
    # both (the state shard IS the central placement).
    return ShardedOptimizerStrategy(params)
  if vu == "independent":
    return IndependentStrategy(params)
  if vu == "kungfu":
    return KungFuStrategy(params, option=params.kungfu_option)
  from kf_benchmarks_tpu.ops import allreduce
  reducer = allreduce.build_reducer(params)
  if vu in ("replicated", "distributed_replicated"):
    return ReplicatedStrategy(params, reducer=reducer)
  if vu == "parameter_server":
    if not params.cross_replica_sync:
      return AsyncParameterServerStrategy(params, reducer=reducer)
    return ParameterServerStrategy(params, reducer=reducer)
  if vu in ("collective_all_reduce", "distributed_all_reduce"):
    return CollectiveAllReduceStrategy(
        params, planner=allreduce.build_planner(params), reducer=reducer)
  if vu == "horovod":
    # Horovod's per-gradient allreduce has the same SPMD data plane as
    # replicated (ref: benchmark_cnn.py:3122-3130).
    s = ReplicatedStrategy(params, reducer=reducer)
    s.name = "horovod"
    return s
  raise ValueError(f"Unknown variable_update {vu!r}")
