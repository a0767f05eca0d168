"""TPU-native KungFu API surface.

Re-implements the KungFu capabilities the reference consumes (SURVEY 2.9;
call sites: benchmark_cnn.py:1192-1204 optimizer wrap, :1408-1410 cluster
size, :2044-2048/:2629-2631 rank, :2097-2100 broadcast-at-init,
tf_cnn_benchmarks.py:58-60 exit barrier) on JAX collectives:

  allreduce            -> the replica MEAN of the gradients, on one of
                          two data planes: lax.pmean of the product
                          (allreduce_mean), or for a dense kernel larger
                          than its batch the product of the all-gathered
                          factors (factor_mean_dot; see there)
  pair-averaging gossip-> lax.ppermute of the weights (deterministic
                          synchronous schedule; see PairAveraging below)
  broadcast            -> replica-0 masked psum
  barrier              -> multihost sync_global_devices (DCN) or no-op
  cluster size / rank  -> mesh axis size / axis_index inside SPMD code,
                          jax.process_count/index on the host side

The KungFu runtime itself (Go peer mesh) is replaced by the XLA SPMD
runtime plus the native coordination service in native/ (control plane).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS


# -- host-side cluster introspection (ref: kungfu.python.*) -----------------

def current_cluster_size() -> int:
  """World size without global init (ref call: benchmark_cnn.py:1408-1410).

  In the SPMD design a "worker" of the reference maps to a device, so the
  cluster size is the global device count, not the process count.
  """
  return jax.device_count()


def current_rank() -> int:
  """Host-side rank (ref call: benchmark_cnn.py:2044-2048).

  Rank of this process's first device; chief election
  (``current_rank() == 0``) matches the reference's use.
  """
  return jax.process_index() * max(jax.local_device_count(), 1)


def run_barrier() -> None:
  """Global barrier before exit (ref: tf_cnn_benchmarks.py:58-60).

  Under the kfrun launcher (KFCOORD_HOST/PORT/WORLD set) the barrier
  rides the native coordination service over DCN; under multi-process
  JAX it uses sync_global_devices; single-process it is a no-op.
  """
  host = os.environ.get("KFCOORD_HOST")
  port = os.environ.get("KFCOORD_PORT")
  world = os.environ.get("KFCOORD_WORLD")
  if host and port and world:
    from kf_benchmarks_tpu.parallel import coordination
    with coordination.CoordinatorClient(host=host,
                                        port=int(port)) as client:
      client.join(os.environ.get("KFCOORD_NAME", f"proc-{os.getpid()}"))
      # all-ranks: kfrun exports KFCOORD_* to every child it launches,
      # so each of the WORLD processes takes this path and enters
      # "kf_exit" with the same expected count.
      client.barrier("kf_exit", int(world))
    return
  if jax.process_count() > 1:
    from jax.experimental import multihost_utils
    # all-ranks: process_count() is a global property (identical on
    # every process of a jax.distributed job), so this branch is
    # all-or-nothing -- full attendance at the sync.
    multihost_utils.sync_global_devices("kf_benchmarks_tpu_exit_barrier")


# -- in-SPMD collective ops (used inside shard_map bodies) ------------------

def allreduce_mean(tree, axis_name: str = REPLICA_AXIS):
  """Gradient averaging: the S-SGD data plane (KungFu allreduce -> psum).

  Sync SGD's contract is that every replica applies the MEAN of all
  replicas' gradients every step. This is its first data plane: the
  per-replica products meet in an all-reduce. The second one
  (:func:`factor_mean_dot`) never forms the per-replica product."""
  return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)


# -- the mean gradient's second data plane: exchange the factors -------------
#
# A dense kernel's gradient is a product of two small factors,
# dW = x^T dy, and the replica mean of the per-replica products IS the
# product of the concatenated factors over n:
#   (1/n) sum_c x_c^T dy_c = (1/n) X^T DY,  X = concat_c x_c.
# Where the kernel is larger than the batch, all-gathering x and dy and
# forming the global product on every chip moves a fraction of the bytes
# the all-reduce of the products would (Krizhevsky, "One weird trick for
# parallelizing convolutional neural networks", arXiv:1404.5997, in its
# lightest form: the weights stay replicated, only the weight-gradient
# matmul sees the global batch). Same mean, summed in another order.

# The shape rule's two numbers, written down once.
#
# Wire: engage only when the gathered factors, n*B*(K+N) elements of the
# compute dtype, are at most a quarter of the product's K*N elements of
# the parameter dtype. Per chip an all-reduce moves 2(n-1)/n of its
# bytes and an all-gather (n-1)/n: at least 8x less on the wire.
FACTOR_WIRE_RATIO = 4
# Compute: every chip repeats the other chips' share of the matmul,
# (n-1)*2*B*K*N extra FLOPs, to save the all-reduce of K*N*4 bytes.
# Measured on four TPU v5e chips (PERF.md section 6, PR 25): the
# f32[25088,4096] all-reduce runs at 57 MB/ms and a bf16 matmul achieves
# about 100 TFLOP/s, so extra compute / wire time saved =
# (n-1)*B * 2/1e14 * 5.7e10/4 = (n-1)*B * 2.85e-4: 5.5% at 4 x 64,
# break-even at 3,500 rows from the other chips. The bound is where the
# repeated compute costs at most about half of what the wire saves.
FACTOR_MAX_GLOBAL_BATCH = 2048


def factor_bytes(n: int, batch: int, k: int, n_out: int, compute_dtype,
                 param_dtype):
  """``(product, gathered)``: the bytes of a dense kernel's gradient, which
  the all-reduce would carry, and of its two factors gathered from ``n``
  replicas, which the factor plane carries instead."""
  return (k * n_out * jnp.dtype(param_dtype).itemsize,
          n * batch * (k + n_out) * jnp.dtype(compute_dtype).itemsize)


def factors_beat_product(n: int, batch: int, k: int, n_out: int,
                         compute_dtype, param_dtype) -> bool:
  """The shape rule: does a dense layer with kernel ``[k, n_out]`` and
  per-replica batch ``batch`` on ``n`` replicas exchange its factors
  (True) or its product (False)? Everything it asks is visible at trace
  time; there is no flag."""
  if n <= 1 or n * batch > FACTOR_MAX_GLOBAL_BATCH:
    return False
  product, gathered = factor_bytes(n, batch, k, n_out, compute_dtype,
                                   param_dtype)
  return FACTOR_WIRE_RATIO * gathered <= product


class FactorExchange:
  """One trace of the train step under the factor data plane: the axis
  the factors are gathered over, and the kernels that took it.

  The step opens one around the model's forward pass
  (:func:`factor_exchange`) where it reduces gradients by the plain
  replica mean; ``models/builder.py::affine`` asks it layer by layer
  (:meth:`admits`) and claims the kernel (:meth:`claim`), whose
  gradient then leaves the backward pass as the replica mean already;
  the step takes the claimed leaves out of its all-reduce. Every
  gradient leaf is reduced exactly once."""

  def __init__(self, axis_name, axis_size: int):
    self.axis_name = axis_name
    self.axis_size = int(axis_size)
    # parameter path (tuple of names) -> (bytes kept off the all-reduce,
    # bytes gathered instead)
    self.claimed = {}

  def admits(self, *layer) -> bool:
    """``layer`` = (batch, k, n_out, compute_dtype, param_dtype)."""
    return factors_beat_product(self.axis_size, *layer)

  def claim(self, path, *layer) -> None:
    self.claimed[tuple(path)] = factor_bytes(self.axis_size, *layer)

  def counters(self) -> dict:
    """The static counter of one step: dense layers on the factor
    plane, the gradient bytes they keep off the all-reduce, the bytes
    gathered instead (per chip, as the gathers' results)."""
    return {
        "layers": len(self.claimed),
        "bytes_off_allreduce": sum(p for p, _ in self.claimed.values()),
        "bytes_gathered": sum(g for _, g in self.claimed.values()),
    }


# What stats["factor_exchange"] reads in a run where nothing engages.
NO_FACTOR_EXCHANGE = {"layers": 0, "bytes_off_allreduce": 0,
                      "bytes_gathered": 0}

_FACTOR_EXCHANGE = contextvars.ContextVar("kf_factor_exchange",
                                          default=None)


@contextlib.contextmanager
def factor_exchange(plan: Optional[FactorExchange]):
  """Dense layers traced inside may take the factor plane of ``plan``
  (None: none may, the context is a no-op)."""
  token = _FACTOR_EXCHANGE.set(plan)
  try:
    yield plan
  finally:
    _FACTOR_EXCHANGE.reset(token)


def active_factor_exchange() -> Optional[FactorExchange]:
  return _FACTOR_EXCHANGE.get()


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def factor_mean_dot(x, kernel, axis_name, compute_dtype):
  """``x @ kernel`` in ``compute_dtype`` whose KERNEL cotangent is the
  replica mean over ``axis_name`` already: the backward all-gathers
  ``x`` and the output cotangent and forms ``X^T DY / n`` on every
  replica, bit-identical across them. The input cotangent is the local
  ``dy @ kernel^T`` that autodiff gives.

  Dtype flow: the product accumulates into, and is divided by n in, the
  wider of the compute and the parameter dtype -- never narrower than
  autodiff's (the dot's result in the compute dtype, then cast)."""
  return lax.dot_general(x, kernel.astype(compute_dtype),
                         (((x.ndim - 1,), (0,)), ((), ())))


def _factor_mean_dot_fwd(x, kernel, axis_name, compute_dtype):
  return factor_mean_dot(x, kernel, axis_name, compute_dtype), (x, kernel)


def _factor_mean_dot_bwd(axis_name, compute_dtype, residuals, dy):
  x, kernel = residuals
  dx = lax.dot_general(dy, kernel.astype(compute_dtype),
                       (((1,), (1,)), ((), ())))
  with jax.named_scope("exchange"):
    x_all = lax.all_gather(x, axis_name, axis=0, tiled=True)
    dy_all = lax.all_gather(dy, axis_name, axis=0, tiled=True)
    # The gathers end where this layer's backward ends: the input
    # cotangent does not leave before they are done. Left free, the
    # TPU scheduler starts them here and threads them through every
    # later backward fusion up to the update that consumes them, which
    # costs the step program 206 MB of device memory (compiled for
    # v5e:2x2, PERF.md section 6, PR 25).
    dx, x_all, dy_all = lax.optimization_barrier((dx, x_all, dy_all))
  wide = jnp.promote_types(compute_dtype, kernel.dtype)
  dw = lax.dot_general(x_all, dy_all, (((0,), (0,)), ((), ())),
                       preferred_element_type=wide)
  dw = dw / lax.axis_size(axis_name)
  return dx, dw.astype(kernel.dtype)


factor_mean_dot.defvjp(_factor_mean_dot_fwd, _factor_mean_dot_bwd)


def broadcast(tree, root: int = 0, axis_name: str = REPLICA_AXIS):
  """Replica-``root`` broadcast of a pytree (ref: kungfu broadcast,
  benchmark_cnn.py:2097-2100): zero non-root values, psum.

  Dtype-preserving: the masked psum runs in each leaf's own dtype (ints
  stay ints -- routing int32 through float32 would corrupt values above
  2^24); bools ride an int32 psum."""
  idx = lax.axis_index(axis_name)

  def bcast(x):
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    if masked.dtype == jnp.bool_:
      return lax.psum(masked.astype(jnp.int32), axis_name).astype(jnp.bool_)
    return lax.psum(masked, axis_name)

  return jax.tree.map(bcast, tree)


# Axis size at or below which the gossip schedule is the full 1..n-1
# rotation; above it, the hypercube schedule keeps the program at
# ceil(log2 n) switch branches AND one send per step.
GOSSIP_SWITCH_MAX_N = 8


def _gossip_offsets(n: int):
  """Per-period partner offsets of the gossip schedule at axis size n.

  The single source of truth shared by gossip_shift (step -> offset
  lookup) and pair_average (one switch branch per offset), so the two
  can never drift. 2^k here is always < n (k < (n-1).bit_length()), so
  every offset is a valid non-zero cyclic shift.
  """
  if n <= GOSSIP_SWITCH_MAX_N:
    return list(range(1, n))
  return [1 << k for k in range((n - 1).bit_length())]


def gossip_shift(step, axis_size: int):
  """Deterministic peer offset for pair-averaging at this step.

  AD-PSGD's asynchronous random pairing has no SPMD analog, so the
  schedule is a deterministic synchronous rotation (SURVEY 7.4
  "Pair-averaging gossip on TPU"), sized to the axis:

  * n <= GOSSIP_SWITCH_MAX_N: the offset rotates through 1..n-1, so
    every replica pairs with every other within n-1 steps.
  * n > GOSSIP_SWITCH_MAX_N: HYPERCUBE offsets -- the schedule cycles
    through the ceil(log2 n) == (n-1).bit_length() power-of-two shifts
    2^0..2^(ceil(log2 n)-1) (each < n, so valid at ANY axis size, not
    just powers of two). Every offset is a single cyclic permutation
    (one ppermute, ONE tree-sized send), and because every residue
    0..n-1 is a subset-sum of those powers mod n, all n replicas mix
    within ceil(log2 n) steps -- at non-power-of-two n included
    (pinned by test_strategies.py's n=6 submesh case) -- faster mixing
    than the 1..n-1 rotation needs n-1 steps for, at 1/log2(n) of the
    wire cost the round-2 gated-hop lowering paid (which sent the tree
    on every of its log2 n hops and gated the result; measured 2.1x
    step time at n=32, PERF.md round 4).
  """
  step = jnp.asarray(step)
  if axis_size <= 1:
    return jnp.zeros_like(step)
  offsets = _gossip_offsets(axis_size)
  return jnp.asarray(offsets, jnp.int32)[step % len(offsets)]


def pair_average(tree, step, axis_name: str = REPLICA_AXIS):
  """One gossip round: average weights with the step's partner
  (KungFu PairAveragingOptimizer data plane -> ppermute).

  Each replica i receives from (i - shift) mod n and averages, with
  shift = gossip_shift(step, n). This is the row-stochastic gossip
  matrix W = (I + P)/2 with P a cyclic permutation: doubly stochastic,
  so the network average is preserved exactly -- the property
  AD-PSGD's analysis needs. Every branch of either lowering is a
  single ppermute of the whole tree, so a gossip step costs exactly
  one tree-sized send at ANY n; the schedules differ across the
  threshold (1..n-1 rotation vs hypercube offsets, see gossip_shift)
  but both are doubly stochastic every step and fully mixing over
  their window.
  """
  n = lax.axis_size(axis_name)
  if n == 1:
    return tree
  step = jnp.asarray(step)

  def make_branch(s):
    perm = [(i, (i + s) % n) for i in range(n)]
    return lambda t: jax.tree.map(
        lambda x: lax.ppermute(x, axis_name, perm), t)

  # One switch branch per schedule offset: n-1 branches of the full
  # rotation at small n, ceil(log2 n) hypercube branches at scale
  # (n=256 bakes 8, not 255) -- every branch a single tree-sized send.
  # The round-2 design instead decomposed the full rotation into gated
  # power-of-two hops, which kept the program O(log n) but sent the
  # tree on EVERY hop (measured 2.1x step time at n=32); restricting
  # the schedule itself to the power-of-two offsets removes the extra
  # sends instead of gating them.
  offsets = _gossip_offsets(n)
  shifted = lax.switch(step % len(offsets),
                       [make_branch(s) for s in offsets], tree)
  return jax.tree.map(lambda x, y: 0.5 * (x + y), tree, shifted)


def sync_average(tree, axis_name: str = REPLICA_AXIS):
  """Synchronous model averaging (KungFu SynchronousAveragingOptimizer /
  SMA, EA-SGD style): all-replica mean of the weights."""
  return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)
