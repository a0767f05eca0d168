"""Device mesh construction.

The reference enumerates raw device strings per tower
(ref: benchmark_cnn.py:1419-1426); the TPU-native analog is a named
jax.sharding.Mesh whose axes carry the parallelism semantics. Data
parallelism (the only axis the reference has) is the 'replica' axis;
model axes ('stage', 'tensor') are reserved for the pipeline/tensor
extensions.

Two mesh families serve the training runtime:

* the 1-D ``('replica',)`` mesh -- every replicated/gossip strategy
  (``build_mesh``), and
* the named 2-D ``('batch', 'model')`` mesh (``build_mesh_2d``) behind
  ``--mesh_shape=BxM`` / ``--shard_optimizer_state``: the batch shards
  over ``'batch'``; optimizer state shards 1/(B*M) over BOTH axes via
  the stacked ``(n, k)`` row layout of ops/sharded.py inside the
  shard_mapped step -- the GSPMD named-mesh idiom (Xu et al. 2021)
  applied to the reference's central variable placement
  (ref: variable_mgr.py:201-243). :func:`leaf_spec` /
  :func:`tree_shardings` express the SAME 1/n layout as a
  size-thresholded ``NamedSharding`` rule for jit-native
  (``in_shardings``) consumers at the library boundary -- the form the
  remaining FSDP forward leg needs (ROADMAP item 1); the core step
  does not consume them. The composed LM trainer refines the same
  ``'model'`` axis into its seq x tensor factors
  (parallel/transformer.py compose_on_model_axis), so every
  parallelism family shares one axis system.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPLICA_AXIS = "replica"
BATCH_AXIS = "batch"
MODEL_AXIS = "model"

# Leaves below this element count stay replicated under the GSPMD leaf
# rule (tree_shardings): sharding tiny tensors buys no memory and costs
# a collective each.
SHARD_MIN_ELEMS = 1024


def get_devices(device_kind: str = "tpu", num_devices: Optional[int] = None):
  """Resolve the device list (ref: benchmark_cnn.py:1419-1426).

  ``num_devices`` counts devices PER PROCESS (the reference's
  one-process-per-GPU num_gpus); under multi-process SPMD the mesh spans
  every process's devices, so the resolved list is global."""
  devices = jax.devices()
  if device_kind == "cpu":
    found = sorted({d.platform for d in devices})
    devices = [d for d in devices if d.platform == "cpu"]
    if not devices:
      raise ValueError(
          "--device=cpu but JAX exposes no CPU device (platforms: "
          f"{found}); benchmark.setup() selects the CPU platform "
          "before the backend initializes")
  if num_devices is not None:
    # Take the first num_devices of EACH process's devices (a global
    # prefix could exclude some processes entirely, leaving them with no
    # addressable shard of the mesh).
    by_proc = {}
    for d in devices:
      by_proc.setdefault(d.process_index, []).append(d)
    picked = []
    for pid in sorted(by_proc):
      if len(by_proc[pid]) < num_devices:
        raise ValueError(
            f"Requested {num_devices} devices per process but process "
            f"{pid} has only {len(by_proc[pid])} "
            f"({[str(d) for d in by_proc[pid]]})")
      picked.extend(by_proc[pid][:num_devices])
    devices = picked
  return devices


def build_mesh(num_devices: Optional[int] = None, device_kind: str = "tpu",
               devices: Optional[Sequence] = None) -> Mesh:
  """1-D data-parallel mesh over the replica axis (global under
  multi-process SPMD)."""
  if devices is None:
    devices = get_devices(device_kind, num_devices)
  return Mesh(np.asarray(devices), (REPLICA_AXIS,))


def build_mesh_2d(num_batch: int, num_model: int,
                  device_kind: str = "tpu",
                  devices: Optional[Sequence] = None) -> Mesh:
  """Named 2-D ``(batch, model)`` mesh over ``num_batch * num_model``
  devices: axis ``'batch'`` carries data parallelism (the reference's
  replica axis), ``'model'`` carries the state-sharding/tensor
  dimension. Device order is row-major over (batch, model), so device
  ``(b, m)`` has flat shard index ``b * num_model + m`` -- the order
  ops/sharded.py's scatter/slice/gather blocks follow."""
  if num_batch < 1 or num_model < 1:
    raise ValueError(f"mesh shape {num_batch}x{num_model}: both axes "
                     "must be positive")
  if devices is None:
    devices = get_devices(device_kind, num_batch * num_model)
  if len(devices) != num_batch * num_model:
    raise ValueError(
        f"mesh shape {num_batch}x{num_model} needs "
        f"{num_batch * num_model} devices, have {len(devices)}")
  return Mesh(np.asarray(devices).reshape(num_batch, num_model),
              (BATCH_AXIS, MODEL_AXIS))


def data_axis(mesh: Mesh) -> str:
  """The axis the global batch is sharded over: 'batch' on the 2-D
  mesh, 'replica' on the 1-D family."""
  return BATCH_AXIS if BATCH_AXIS in mesh.axis_names else REPLICA_AXIS


def state_axes(mesh: Mesh):
  """Every mesh axis, as the tuple the stacked per-device state's
  leading dim is sharded over (and metric pmeans reduce over)."""
  return tuple(mesh.axis_names)


def num_data_replicas(mesh: Mesh) -> int:
  """Data-parallel width: the global batch is ``per_device_batch`` times
  this (model-axis peers re-compute the same batch shard)."""
  return int(mesh.shape[data_axis(mesh)])


def leaf_spec(shape, mesh: Mesh, min_elems: int = SHARD_MIN_ELEMS) -> P:
  """Size-thresholded GSPMD leaf rule for params/opt-state trees on the
  2-D mesh (the jit-inserted-collective idiom of GSPMD, Xu et al. 2021;
  the compiler analog of the reference's central variable placement,
  variable_mgr.py:201-243): shard dim 0 over the combined
  ``('batch', 'model')`` axes when the leaf is big enough and dim 0
  divides the mesh, else replicate."""
  n = mesh.devices.size
  ndims = len(shape)
  if (ndims == 0 or math.prod(shape) < min_elems or shape[0] % n):
    return P()
  return P(state_axes(mesh))


def tree_shardings(mesh: Mesh, tree):
  """NamedShardings for a params/opt-state pytree under the
  :func:`leaf_spec` rule -- the ``jax.jit`` ``in_shardings`` form of
  the sharded-state layout (SNIPPETS.md [2]/[3] pattern), for
  jit-native library consumers. The train step itself carries the
  equivalent stacked ``(n, k)`` row layout (ops/sharded.py) inside
  shard_map; see the module docstring."""
  return jax.tree.map(
      lambda x: NamedSharding(mesh, leaf_spec(tuple(x.shape), mesh)), tree)


def put_batch(batch, sharding: NamedSharding):
  """Host batch -> device, sharded over the batch axis. Single-process:
  a plain device_put. Multi-process: each process contributes the shard
  for ITS devices (jax.make_array_from_process_local_data), the
  jax-native form of the reference's per-worker input splits
  (ref: preprocessing shift_ratio sharding + per-device StagingAreas)."""
  if jax.process_count() > 1:
    # all-ranks: process_count() is identical on every process, and
    # every process feeds a batch each step -- all ranks reach this
    # cross-host assembly together.
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)), batch)
  return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P(data_axis(mesh)))


def chunk_batch_sharding(mesh: Mesh) -> NamedSharding:
  """Sharding for a staged multi-step chunk (--steps_per_dispatch):
  leading axis = staged steps (replicated), second axis = the global
  batch sharded over replicas -- the per-step batch_sharding behind a
  chunk dimension."""
  return NamedSharding(mesh, P(None, data_axis(mesh)))
