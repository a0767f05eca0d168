"""Core benchmark runtime: build -> compile -> warmup -> timed loop -> report.

Re-design of the reference's BenchmarkCNN (ref: benchmark_cnn.py:1230-2391).
The TF "graph + sess.run" pair becomes "jitted step fn + host loop"; the
fetches dict becomes the step-output metrics pytree; warmup = compile + N
discarded steps; the images/sec + uncertainty + jitter math and the
per-step line format are kept exactly (SURVEY 7.1).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu import checkpoint
from kf_benchmarks_tpu import cluster as cluster_lib
from kf_benchmarks_tpu import elastic as elastic_lib
from kf_benchmarks_tpu import faults as faults_lib
from kf_benchmarks_tpu import learning_rate
from kf_benchmarks_tpu import metrics as metrics_lib
from kf_benchmarks_tpu import observability
from kf_benchmarks_tpu import optimizers
from kf_benchmarks_tpu import telemetry as telemetry_lib
from kf_benchmarks_tpu import tracing as tracing_lib
from kf_benchmarks_tpu import train_step as train_step_lib
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.data import datasets
from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.parallel import mesh as mesh_lib
from kf_benchmarks_tpu.parallel import strategies
from kf_benchmarks_tpu.parallel import kungfu
from kf_benchmarks_tpu.utils import log as log_util
from kf_benchmarks_tpu.utils import pipeline as pipeline_lib
from kf_benchmarks_tpu.utils import sync

def log_fn(msg):
  """Late-bound so tests/bench can monkey-patch log_util.log_fn."""
  log_util.log_fn(msg)


# The persistent-compile-cache dir this PROCESS last applied in code
# (None = off, or left to JAX_COMPILATION_CACHE_DIR): jax initializes
# the cache object lazily and keeps it for the process lifetime, so
# re-pointing the config alone would silently keep writing to the first
# run's directory -- reset_cache() drops the stale cache object before
# the new dir takes effect.
_active_compile_cache_dir = None

# The fixed default of --device=tpu runs. The directory is part of the
# cache key, so it is never built from train_dir, a temp name, a pid or
# the time: a path that moves never hits.
DEFAULT_TPU_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

# The provenance of the LAST --autotuned_config application setup()
# performed ({path, entry} or None): BenchmarkCNN reuses it (matched by
# table path) instead of re-reading the table from disk, so the
# recorded provenance can never disagree with what was applied (e.g. a
# concurrent table rewrite between setup and construction).
_applied_tuned_provenance = None


def resolve_compile_cache_dir(device: str, flag_dir=None):
  """The ONE persistent-compile-cache rule -> ``(dir or None,
  from_env)``.

  ``JAX_COMPILATION_CACHE_DIR`` set: jax already keeps its cache there
  and the program sets no other directory in code (``from_env`` True).
  Unset: ``--compilation_cache_dir`` if given, else
  ``DEFAULT_TPU_COMPILE_CACHE_DIR`` for --device=tpu runs, else off --
  CPU runs with neither env nor flag write no cache, so the test suite
  does not grow the checkout."""
  env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
  if env_dir:
    return env_dir, True
  if flag_dir:
    return flag_dir, False
  return (DEFAULT_TPU_COMPILE_CACHE_DIR if device == "tpu" else None), False


def configure_compile_cache(device: str, flag_dir=None):
  """Apply :func:`resolve_compile_cache_dir` before the first trace
  and return the resolved directory (None = off). Idempotent. Called
  from setup(), the train driver, the serving bench and ``analysis
  warm``."""
  global _active_compile_cache_dir
  cache_dir, from_env = resolve_compile_cache_dir(device, flag_dir)
  if cache_dir:
    # Serialize EVERY compile, not just those over jax's default
    # 1-second floor: the once-per-shape contract (and the ledger's
    # cache_hit accounting) must not depend on how fast a given
    # backend happens to compile a given program.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  if from_env:
    if flag_dir and flag_dir != cache_dir:
      log_fn(f"XLA compilation cache: JAX_COMPILATION_CACHE_DIR="
             f"{cache_dir} is set; --compilation_cache_dir={flag_dir} "
             "ignored")
    return cache_dir
  if cache_dir != _active_compile_cache_dir:
    from jax.experimental.compilation_cache import compilation_cache as cc
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _active_compile_cache_dir = cache_dir
  return cache_dir


def device_identity() -> Dict[str, Any]:
  """The device as JAX reports it (initialises the backend, in this
  process, on first call): the identity every banner, bench line and
  run record names instead of the --device flag."""
  devices = jax.devices()
  return {"platform": devices[0].platform,
          "kind": devices[0].device_kind,
          "count": len(devices)}


def opt_state_bytes_per_device(opt_state) -> int:
  """Per-device optimizer-state HBM of a stacked opt_state tree: every
  leaf carries a leading stacked-replica (or shard-row) dim, so
  per-device bytes are total bytes / leading dim -- ~|state| on the
  replicated layout, ~|state|/n under --shard_optimizer_state (the
  ZeRO partitioning claim, surfaced in bench.py's JSON line).

  Shape/dtype-based, so it accounts concrete device arrays and the
  auditor's ``jax.eval_shape`` ShapeDtypeStructs identically
  (analysis/contracts.py trace_contract aux)."""
  total = 0
  for leaf in jax.tree.leaves(opt_state):
    shape = tuple(leaf.shape)
    lead = shape[0] if shape else 1
    nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(
        leaf.dtype).itemsize
    total += nbytes // max(int(lead), 1)
  return total


def compute_eval_step_set(params, global_batch_size: int,
                          num_train_examples: int, num_batches: int,
                          start_step: int = 0, start_examples: int = 0):
  """Training steps after which mid-training eval runs, from the
  epoch-based and explicit-list schedules (ref: benchmark_cnn.py:1449-1476;
  the every-n-steps cadence is checked separately in the loop).

  ``start_step``/``start_examples`` re-anchor the epoch->step mapping
  after an elastic reshape changes the global batch size mid-run (epoch
  boundaries are example counts, not step counts)."""
  steps = set()

  def epoch_to_step(e):
    # Ref formula: ceil(e * examples / batch) via int arithmetic,
    # re-anchored at the examples already consumed.
    remaining = int(e * num_train_examples) - start_examples
    return (start_step +
            (remaining + global_batch_size - 1) // global_batch_size)

  if params.eval_during_training_every_n_epochs:
    n = float(params.eval_during_training_every_n_epochs)
    num_epochs = ((start_examples +
                   (num_batches - start_step) * global_batch_size) /
                  max(num_train_examples, 1))
    # The endpoint is included when the run lands exactly on an epoch
    # boundary (the reference's exclusive np.arange silently dropped the
    # end-of-training eval for runs of exactly k*n epochs).
    epochs = [e for e in np.arange(n, num_epochs + 1e-9, n)
              if e * num_train_examples > start_examples]
    steps |= {epoch_to_step(e) for e in epochs}
  if params.eval_during_training_at_specified_steps:
    try:
      steps |= set(
          map(int, params.eval_during_training_at_specified_steps))
    except ValueError:
      raise validation.ParamError(
          "eval_during_training_at_specified_steps value of "
          f"{params.eval_during_training_at_specified_steps} cannot be "
          "converted to a list of integers (ref :1457-1463)")
  if params.eval_during_training_at_specified_epochs:
    try:
      epochs = [float(e)
                for e in params.eval_during_training_at_specified_epochs]
    except ValueError:
      raise validation.ParamError(
          "eval_during_training_at_specified_epochs value of "
          f"{params.eval_during_training_at_specified_epochs} cannot be "
          "converted to a list of floats (ref :1465-1476)")
    steps |= {epoch_to_step(e) for e in epochs
              if e * num_train_examples > start_examples}
  return steps


def feeder_prefetch(params) -> int:
  """Host->device prefetch depth: --input_prefetch_depth when set,
  else the deeper of the dataset prefetch buffer and
  --batch_group_size (the reference's input producers hand the staging
  areas ``batch_group_size`` batches at a time, ref: cnn_util.py:118-198
  ImageProducer, benchmark_cnn.py:134-136)."""
  explicit = getattr(params, "input_prefetch_depth", None)
  if explicit:
    return int(explicit)
  return max(params.datasets_prefetch_buffer_size or 1,
             params.batch_group_size or 1)


def setup(params):
  """Process-level setup (ref: benchmark_cnn.py:3356-3395).

  The reference sets cuDNN/MKL env vars and runs a dummy session; the TPU
  analogs are XLA flag plumbing and an eager device touch to trigger
  runtime init ahead of the timed region.
  """
  if getattr(params, "autotuned_config", None):
    # --autotuned_config: apply the tuned-table entry matching this
    # run's base fingerprint over the flag values, FIRST -- every
    # caller (cli.py, bench.py, kfrun workers) goes through setup, so
    # the params the rest of the process sees (and fingerprints) are
    # the applied ones. One provenance line either way
    # (analysis/autotune.py apply_tuned_config).
    from kf_benchmarks_tpu.analysis import autotune as autotune_lib
    global _applied_tuned_provenance
    params, _applied_tuned_provenance = autotune_lib.apply_tuned_config(
        params, log_fn=log_fn)
  if params.device == "cpu":
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if (params.num_devices > 1 and
        "xla_force_host_platform_device_count" not in xla_flags):
      # Provision virtual host devices for multi-replica CPU runs. Only
      # effective if the CPU backend has not been initialized yet.
      os.environ["XLA_FLAGS"] = (
          xla_flags + " --xla_force_host_platform_device_count="
          f"{params.num_devices}").strip()
    jax.config.update("jax_platforms", "cpu")
  # Platform pre-run hook (ref: platforms_util.initialize, called from
  # setup at benchmark_cnn.py:3356-3395). The cluster manager also goes
  # through the platform dispatch so vendor overrides take effect.
  # --coordinator_address/--num_processes/--process_index map onto the
  # KFCOORD_* env the coordination-service clients read (kfrun sets the
  # env directly; these flags cover hand-launched processes,
  # ref: kungfu-run env propagation, SURVEY 2.9).
  if params.coordinator_address and "KFCOORD_HOST" not in os.environ:
    host, _, port = params.coordinator_address.partition(":")
    os.environ["KFCOORD_HOST"] = host
    os.environ["KFCOORD_PORT"] = port or "0"
    os.environ["KFCOORD_WORLD"] = str(params.num_processes)
    os.environ.setdefault("KFCOORD_RANK_HINT", str(params.process_index))
  from kf_benchmarks_tpu.platforms import util as platforms_util
  platforms_util.initialize(params)
  platforms_util.get_cluster_manager(params)
  # The one backend init of the process (ref dummy session :3383-3393),
  # in-process: a chip belongs to one process at a time, so no child
  # probes it first. --device=tpu without a TPU is an error in single-
  # and multi-process runs alike -- a run never trains on another
  # platform under the TPU's name.
  found = device_identity()
  if params.device == "tpu" and found["platform"] != "tpu":
    raise RuntimeError(
        "--device=tpu but JAX found no TPU: platform="
        f"{found['platform']}, device_kind={found['kind']}, "
        f"{found['count']} device(s). There is no CPU fallback; pass "
        "--device=cpu to run on the CPU on purpose.")
  configure_compile_cache(params.device, params.compilation_cache_dir)
  return params


def _fullest_device_memory(devices):
  """``peak_bytes_in_use``, ``peak_bytes_reserved`` and ``bytes_limit``
  of the device with the highest peak in use; None where the backend
  keeps no memory statistics."""
  tables = [d.memory_stats() or {} for d in devices]
  fullest = max(tables, key=lambda m: m.get("peak_bytes_in_use", 0))
  return {k: int(fullest[k]) for k in (
      "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
          if k in fullest} or None


class BenchmarkCNN:
  """Benchmark driver (ref: benchmark_cnn.py:1230).

  Args mirror the reference: Params plus optional dataset/model injection
  (tests inject fake datasets/models the same way,
  ref: benchmark_cnn.py:1230-1233).
  """

  def __init__(self, params, dataset=None, model=None):
    from kf_benchmarks_tpu import params as params_lib
    params_lib.validate_params(params)
    validation.validate_cross_flags(params)
    # Tuned-config provenance for the stats/run record: reuse what
    # setup() just applied (matched by table path -- no second disk
    # read, and the record cannot disagree with the application); a
    # direct construction without setup falls back to the lookup,
    # done BEFORE the auto-resolutions below mutate params (the table
    # keys on the make_params-level config, analysis/autotune.py).
    # None when --autotuned_config is unset.
    self._tuned_provenance = None
    if getattr(params, "autotuned_config", None):
      prov = _applied_tuned_provenance
      if prov and prov.get("path") == params.autotuned_config:
        self._tuned_provenance = dict(prov)
      else:
        from kf_benchmarks_tpu.analysis import autotune as autotune_lib
        self._tuned_provenance = autotune_lib.tuned_provenance(params)
    if params.adaptive_batch_size and not params.track_grad_noise_scale:
      # The adaptive-batch policy keys on the measured noise scale.
      params = params._replace(track_grad_noise_scale=True)
    self.params = params
    # Optional resize driver (tests inject a ScheduledController; the
    # elastic flag wires the coordination service via KFCOORD_* env).
    self.elastic_controller = None
    # --use_synthetic_gpu_images forces synthetic inputs even when a
    # data_dir is set (ref: the flag gates use_synthetic_gpu_inputs).
    data_dir = None if params.use_synthetic_gpu_images else params.data_dir
    self.dataset = dataset or datasets.create_dataset(
        data_dir, params.data_name)
    self.model = model or model_config.get_model_config(
        params.model, self.dataset.name, params)
    if params.batch_size:
      self.model.set_batch_size(params.batch_size)
    self.batch_size_per_device = self.model.get_batch_size()
    gacc = int(params.num_grad_accum or 1)
    if gacc > 1 and self.batch_size_per_device % gacc:
      # validation.py checked an EXPLICIT --batch_size; a model-default
      # batch resolves here, so the divisibility contract is re-checked
      # against the resolved value.
      raise validation.ParamError(
          f"--num_grad_accum={gacc} must divide the per-device batch "
          f"size {self.batch_size_per_device} (model default for "
          f"{self.model.get_name()}); pass a divisible --batch_size")
    self.num_devices = params.num_devices
    # Multi-process (multi-host) runs multiply further (ref num_workers).
    self.num_workers = jax.process_count()
    # Mesh family: --mesh_shape / --shard_optimizer_state select the
    # named 2-D ('batch', 'model') mesh (sharded alone resolves Nx1);
    # everything else keeps the 1-D replica mesh. The GLOBAL batch
    # follows the DATA-parallel width only: model-axis peers re-compute
    # the same batch shard (train_step.py), so a 4x2 mesh feeds the
    # global batch of 4 replicas, not 8.
    if params.mesh_shape or params.shard_optimizer_state:
      nb, nm = (validation.parse_mesh_shape(params.mesh_shape)
                if params.mesh_shape else (self.num_devices, 1))
      self.mesh = mesh_lib.build_mesh_2d(nb, nm, params.device)
    else:
      self.mesh = mesh_lib.build_mesh(self.num_devices, params.device)
    self.num_data_replicas = mesh_lib.num_data_replicas(self.mesh)
    self.batch_size = self.batch_size_per_device * self.num_data_replicas
    self.strategy = strategies.get_strategy(params)
    # --shard_optimizer_state: checkpoints must save/restore the FULL
    # stacked shard rows, not the v0 slice (checkpoint.py).
    self._sharded_state = bool(getattr(self.strategy, "sharded_state",
                                       False))
    # --shard_params (full FSDP): params join the shard-stack layout --
    # same checkpoint rule, plus the params_layout marker so cross-
    # layout restores fail loudly (checkpoint.py).
    self._sharded_params = bool(getattr(params, "shard_params", False))
    # Training-health telemetry (telemetry.py): resolve the auto
    # default (--health_stats unset) against the strategy's reduction
    # semantics ONCE, so the step builder and the host-side recorder/
    # watchdog see the same concrete decision.
    hs, self._health_note = telemetry_lib.resolve_health_stats(
        params, self.strategy)
    if bool(params.health_stats) != hs or params.health_stats is None:
      params = params._replace(health_stats=hs)
      self.params = params
    self._telemetry = None
    # Run-trace session default: the no-op sink until _benchmark_train
    # installs the real one (tracing.py) -- direct _train_loop callers
    # (tests) trace nothing rather than crash.
    self._trace = tracing_lib.NULL_TRACE
    self._compiled_programs = set()
    # Deterministic fault injection (--fault_schedule, faults.py): the
    # named faults fire at dispatch boundaries; the dispatch planner
    # treats their steps as events so a chunk never crosses one.
    self._faults = faults_lib.FaultInjector.from_params(
        params, rank=cluster_lib.process_rank(), log_fn=log_fn)
    self.num_batches = self._get_num_batches()
    # Device-resident multi-step dispatch (--steps_per_dispatch=K): K
    # train steps per compiled program (train_step.py train_chunk), so
    # the per-dispatch host cost amortizes K-fold. A run shorter than one
    # chunk scans the whole run in a single dispatch. Validation has
    # already rejected K > 1 with --eval/--forward_only.
    spd = max(1, params.steps_per_dispatch or 1)
    if spd > self.num_batches:
      spd = max(1, self.num_batches)
    if spd != (params.steps_per_dispatch or 1):
      params = params._replace(steps_per_dispatch=spd)
      self.params = params
    self.steps_per_dispatch = spd
    self.eval_step_set = compute_eval_step_set(
        params, self.batch_size * max(self.num_workers, 1),
        self.dataset.num_examples_per_epoch("train"), self.num_batches)
    # Default matches the reference: max(10, autotune warmup) with no
    # autotune phase on TPU (ref: benchmark_cnn.py:1257).
    self.num_warmup_batches = (
        params.num_warmup_batches if params.num_warmup_batches is not None
        else 10)
    self.display_every = params.display_every
    dtype = jnp.float32
    if params.use_fp16:
      # bfloat16 on TPU; float16 kept for parity when explicitly requested
      # through fp16_vars on non-TPU backends.
      dtype = jnp.bfloat16 if params.device == "tpu" else jnp.float16
    self.compute_dtype = dtype
    self.param_dtype = dtype if params.fp16_vars else jnp.float32

  def _get_num_batches(self) -> int:
    p = self.params
    if p.num_batches is not None:
      return p.num_batches
    if p.num_epochs is not None:
      per_epoch = self.dataset.num_examples_per_epoch("train")
      global_batch = self.batch_size * max(self.num_workers, 1)
      return int(np.ceil(p.num_epochs * per_epoch / global_batch))
    return 100  # reference default (ref: benchmark_cnn.py:137-139)

  def _num_eval_batches_from_epochs(self):
    """--num_eval_epochs -> batches over the validation set (ref:
    get_num_batches_and_epochs applied to eval params,
    benchmark_cnn.py:1429-1446)."""
    p = self.params
    if p.num_eval_epochs is None:
      return None
    per_epoch = self.dataset.num_examples_per_epoch("validation")
    global_batch = self.batch_size * max(self.num_workers, 1)
    return int(np.ceil(p.num_eval_epochs * per_epoch / global_batch))

  # -- info ----------------------------------------------------------------

  def print_info(self):
    """Run-config banner (ref: benchmark_cnn.py:1633-1692)."""
    p = self.params
    mode = "forward-only" if p.forward_only else (
        "evaluation" if p.eval else "training")
    log_fn("TensorFlow:   n/a (kf_benchmarks_tpu, JAX %s)" % jax.__version__)
    log_fn("Model:       %s" % self.model.get_name())
    log_fn("Dataset:     %s (%s)" % (
        self.dataset.name,
        "synthetic" if self.dataset.use_synthetic_gpu_inputs() else
        self.dataset.data_dir))
    log_fn("Mode:        %s" % mode)
    log_fn("Batch size:  %d global" % (
        self.batch_size * max(self.num_workers, 1)))
    log_fn("             %d per device" % self.batch_size_per_device)
    log_fn("Num batches: %d" % self.num_batches)
    # The platform and kind of the devices the mesh actually holds --
    # never the --device flag.
    dev0 = self.mesh.devices.flat[0]
    log_fn("Num devices: %d (%s, %s)" % (
        self.num_devices, dev0.platform, dev0.device_kind))
    if mesh_lib.BATCH_AXIS in self.mesh.axis_names:
      log_fn("Mesh:        %dx%d (batch x model)%s%s" % (
          self.mesh.shape[mesh_lib.BATCH_AXIS],
          self.mesh.shape[mesh_lib.MODEL_AXIS],
          ", sharded optimizer state" if p.shard_optimizer_state else "",
          ", sharded params (FSDP)" if getattr(p, "shard_params", False)
          else ""))
    log_fn("Data format: %s" % p.data_format)
    log_fn("Precision:   %s (params: %s)" % (
        jnp.dtype(self.compute_dtype).name,
        jnp.dtype(self.param_dtype).name))
    log_fn("Optimizer:   %s" % p.optimizer)
    log_fn("Variables:   %s%s" % (
        p.variable_update,
        f" ({p.kungfu_option})" if p.variable_update == "kungfu" else ""))
    log_fn("==========")

  # -- build ---------------------------------------------------------------

  def _build(self):
    p = self.params
    nclass = self.dataset.num_classes
    with self._trace.span("setup", "build_model"):
      module = self.model.make_module(
          nclass=nclass, phase_train=not (p.eval or p.forward_only),
          data_format=p.data_format, dtype=self.compute_dtype,
          param_dtype=self.param_dtype)
      eval_module = self.model.make_module(
          nclass=nclass, phase_train=False, data_format=p.data_format,
          dtype=self.compute_dtype, param_dtype=self.param_dtype)
      lr_fn = learning_rate.make_learning_rate_fn(
          p, self.model,
          self.batch_size_per_device * (
              # Effective batch = per-device x DATA-parallel width
              # (model-axis peers add no examples); == num_devices on
              # 1-D meshes.
              self.num_data_replicas if self.strategy.cross_replica
              else 1),
          self.dataset.num_examples_per_epoch("train"), self.num_workers)
      tx = optimizers.get_optimizer(p, lr_fn)
      self._lr_fn = lr_fn
    with self._trace.span("setup", "make_step_fns"):
      return train_step_lib.make_step_fns(
          self.model, module, eval_module, self.strategy, tx, lr_fn, p,
          self.mesh, compute_dtype=self.compute_dtype,
          # The RESOLVED step count (--num_batches default /
          # --num_epochs derivation, _get_num_batches) --
          # params.num_batches may be None.
          total_train_steps=self.num_batches)

  def _synthetic_global_batch(self, rng):
    """Device-resident synthetic inputs, sharded over replicas
    (ref: "minor hack to avoid H2D copy", benchmark_cnn.py:3008-3011)."""
    nclass = self.dataset.num_classes
    # Build the global batch with the model's per-device shape scaled up.
    self.model.set_batch_size(self.batch_size_per_device)
    images, labels = self.model.get_synthetic_inputs(rng, nclass)
    # Feed floating inputs at the compute dtype: the first model op casts
    # anyway, and a bf16-resident batch halves the HBM read of the largest
    # input tensor every step.
    if jnp.issubdtype(images.dtype, jnp.floating):
      images = images.astype(self.compute_dtype)
    # Labels may be a pytree (e.g. SSD's (boxes, classes, num_matched)).
    # Tile covers THIS process's DATA replicas (model-axis peers read
    # the same shard); put_batch assembles the global array from
    # per-process shards under multi-process SPMD.
    tile = lambda x: jnp.tile(
        x, (self.num_data_replicas,) + (1,) * (x.ndim - 1))
    batch_sharding = mesh_lib.batch_sharding(self.mesh)
    return mesh_lib.put_batch(
        (tile(images), jax.tree.map(tile, labels)), batch_sharding)

  def _input_iterator(self, rng, subset: str = "train", chunk: int = 1):
    """Per-step input source.

    Synthetic (no data_dir): one device-resident batch reused every step
    (ref: benchmark_cnn.py:3008-3011). Real data: preprocessor host
    pipeline + double-buffered DeviceFeeder (the StagingArea/
    MultiDeviceIterator analog, ref: benchmark_cnn.py:2572-2600).
    Returns (next_fn, stop_fn).

    ``chunk`` > 1 stages --steps_per_dispatch batches per fetch: real
    data arrives as one (chunk, batch, ...) staged array; synthetic
    arrives with a leading axis of 1 (the scanned program reuses the
    resident batch, so no K-wide staging footprint exists at all).

    --packed_sequences: the seeded host-side packer (data/packing.py)
    is a REAL host pipeline even though no data_dir is set -- fresh
    variable-length documents are drawn and bin-packed per batch, so
    the stream runs through the DeviceFeeder like record data and the
    feed instrumentation measures whether packing work hides behind
    the step (feed_stall_fraction).
    """
    from kf_benchmarks_tpu.data import device_feed
    p = self.params
    self._feeder = None
    self._packed_stream = None
    if getattr(p, "packed_sequences", False):
      from kf_benchmarks_tpu.data import packing as packing_lib
      # Seeded from the run's data rng (+ the elastic incarnation fold
      # _open_input applied): same seed -> same document stream.
      seed = int(np.asarray(
          jax.random.randint(rng, (), 0, 2**31 - 1, jnp.int32)))
      stream = packing_lib.PackedBatchStream(
          seq_len=self.model.get_input_shapes(subset)[0][-1],
          batch_size=self.batch_size, vocab=self._packed_vocab(),
          seed=seed)
      self._packed_stream = stream
      return self._make_feeder(stream, chunk)
    if self.dataset.use_synthetic_gpu_inputs():
      batch = self._synthetic_global_batch(rng)
      if chunk > 1:
        chunk_sharding = mesh_lib.chunk_batch_sharding(self.mesh)
        batch = jax.tree.map(
            lambda x: jax.device_put(x[None], chunk_sharding), batch)
      return (lambda: batch), (lambda: None)
    pre = self.dataset.get_input_preprocessor(p.input_preprocessor)
    if isinstance(pre, type):
      shape = self._model_image_shape()
      pre = pre(
          batch_size=self.batch_size,
          output_shape=shape,
          train=(subset == "train") and not (p.eval or p.forward_only),
          distortions=bool(p.distortions),
          resize_method=p.resize_method,
          # The incarnation term reshuffles after each elastic reshape so
          # reopened streams do not replay the dataset's leading examples.
          seed=((p.tf_random_seed or 301) + kungfu.current_rank() +
                7919 * getattr(self, "_input_incarnation", 0)),
          shift_ratio=(kungfu.current_rank() /
                       max(kungfu.current_cluster_size(), 1)),
          # Thread-count precedence: the dataset-private pool flag, then
          # the host intra-op pool size, then the parse-parallelism
          # default (ref :203-208, :248-253, map parallelism).
          num_threads=(p.datasets_num_private_threads or
                       p.num_intra_threads or
                       p.input_preprocessing_parallelism or 8),
          repeat_cached_sample=bool(p.datasets_repeat_cached_sample),
          use_caching=bool(p.datasets_use_caching))
      if hasattr(pre, "max_label_length"):
        # Speech: label padding must match the model's static label slot.
        pre.max_label_length = getattr(self.model, "max_label_length",
                                       pre.max_label_length)
    host_iter = pre.minibatches(self.dataset, subset)
    if self.compute_dtype != jnp.float32:
      host_iter = self._cast_images(host_iter)
    return self._make_feeder(host_iter, chunk)

  def _make_feeder(self, host_iter, chunk: int):
    """The ONE DeviceFeeder recipe (sharding pick, prefetch depth,
    stats bookkeeping) shared by the record-data and packed-stream
    input paths, so a prefetch/sharding policy change cannot apply to
    one and silently diverge the other."""
    from kf_benchmarks_tpu.data import device_feed
    feeder = device_feed.DeviceFeeder(
        host_iter,
        mesh_lib.chunk_batch_sharding(self.mesh) if chunk > 1
        else mesh_lib.batch_sharding(self.mesh),
        prefetch=max(feeder_prefetch(self.params), chunk), chunk=chunk)
    self._feeder = feeder
    it = iter(feeder)
    return (lambda: next(it)), feeder.stop

  def _packed_vocab(self) -> int:
    from kf_benchmarks_tpu.models import transformer_lm as lm
    return lm.VOCAB

  def _cast_images(self, host_iter):
    """Cast float32 host batches to the compute dtype before the H2D copy
    (halves the transfer; the model's first op performs this cast
    otherwise)."""
    np_dtype = np.dtype(self.compute_dtype)
    try:
      for images, labels in host_iter:
        if images.dtype == np.float32:
          images = images.astype(np_dtype)
        yield images, labels
    finally:
      close = getattr(host_iter, "close", None)
      if close is not None:
        close()

  def _model_image_shape(self):
    """(H, W, C) the model consumes, from its input spec."""
    self.model.set_batch_size(self.batch_size_per_device)
    image_shape = self.model.get_input_shapes("train")[0]
    return tuple(image_shape[1:])

  # -- run -----------------------------------------------------------------

  def run(self) -> Dict[str, Any]:
    """(ref: benchmark_cnn.py:1726-1755)"""
    self.print_info()
    if self.params.eval:
      return self._run_eval()
    if self.params.forward_only and self.params.aot_load_path:
      return self._benchmark_aot_serving()
    return self._benchmark_train()

  def _benchmark_aot_serving(self) -> Dict[str, Any]:
    """Serving benchmark on a frozen AOT artifact: deserialize the
    exported forward program (weights baked in as constants) in THIS
    process and time it -- the analog of benchmarking the
    TensorRT-converted graph (ref: _preprocess_graph freeze+convert,
    benchmark_cnn.py:2405-2525, timed by the forward-only loop)."""
    from kf_benchmarks_tpu import aot
    p = self.params
    shape = (self.batch_size_per_device,) + self._model_image_shape()
    # Signature-validated load (aot.py): a batch/shape mismatch fails
    # HERE with the exported signature and the available bucket list,
    # not as an XLA arity error mid-loop; the serving-mode diff
    # (quantize sidecar vs this process's --trt_mode) fails a bf16
    # engine pointed at an INT8 export before deserialization.
    trt_mode = (p.trt_mode or "").upper()
    serving_fn = aot.load_forward(p.aot_load_path,
                                  expect_batch=self.batch_size_per_device,
                                  expect_shape=shape,
                                  expect_quantize="int8" if
                                  trt_mode == "INT8" else None)
    log_fn(f"Loaded frozen forward program from {p.aot_load_path}")
    images = jax.random.uniform(jax.random.PRNGKey(p.tf_random_seed or 0),
                                shape, jnp.float32)
    sync.drain(images)
    log_fn("Running warm up")
    t0 = time.time()
    for _ in range(max(self.num_warmup_batches, 1)):
      out = serving_fn(images)
    # The timed loop must start with an empty device queue.
    sync.drain(out)
    log_fn("Warmup (load + %d steps): %.1f s" %
           (max(self.num_warmup_batches, 1), time.time() - t0))
    log_fn("Step\tImg/sec\t" + p.loss_type_to_report)
    step_times = []
    last_display_len = 0
    pipe = pipeline_lib.MetricsPipeline(lag=2)
    pipe.reset_clock()

    def _handle(done):
      nonlocal last_display_len
      step_times.append(done.interval)
      i1 = done.index
      if i1 % self.display_every == 0 or i1 == self.num_batches:
        window = step_times[last_display_len:]
        # The artifact returns logits only; the loss column reports the
        # mean logit as a liveness value (no labels in serving).
        log_fn(log_util.format_step_line(
            i1, self.batch_size_per_device, window,
            float(done.metrics["mean_logit"])))
        last_display_len = len(step_times)

    loop_start = time.time()
    for i in range(self.num_batches):
      out = serving_fn(images)
      for done in pipe.push(i + 1, {"mean_logit": jnp.mean(out)}):
        _handle(done)
    for done in pipe.flush():
      _handle(done)
    total_time = time.time() - loop_start
    images_per_sec = (self.num_batches * self.batch_size_per_device /
                      max(total_time, 1e-9))
    log_fn("-" * 64)
    log_fn(log_util.format_total_line(images_per_sec))
    log_fn("-" * 64)
    return {
        "num_workers": 1,
        "num_steps": self.num_batches,
        "average_wall_time": total_time / max(self.num_batches, 1),
        "images_per_sec": images_per_sec,
        "aot_load_path": p.aot_load_path,
    }

  def _benchmark_train(self) -> Dict[str, Any]:
    p = self.params
    if self._health_note:
      log_fn(self._health_note)
    # Run-trace session (tracing.py): ONE run id shared with the flight
    # recorder so a post-mortem dump lays over the timeline. Always
    # created -- the span totals, latency percentiles and compile
    # ledger ride the stats/bench JSON even without --trace_events_file
    # (span retention and the file export engage only with the flag).
    # The profiler's annotation factories are handed over HERE and
    # nowhere else (lint rule trace-event-emission): every live span
    # then lands in whatever jax.profiler capture is open. Under kfrun
    # the world size comes from the launcher env (jax.process_count()
    # is 1 per CPU worker there), so rank files and the rank-0 merge
    # cover every worker of the job.
    rank = cluster_lib.process_rank()
    world = (int(os.environ.get("KFCOORD_WORLD") or 0) or
             max(self.num_workers, 1))
    run_id = tracing_lib.resolve_run_id()
    self._trace = tracing_lib.RunTrace(
        path=p.trace_events_file, rank=rank, num_ranks=world,
        run_id=run_id, chrome_format=bool(p.use_chrome_trace_format),
        log_fn=log_fn, annotation=jax.profiler.TraceAnnotation,
        step_annotation=jax.profiler.StepTraceAnnotation)
    tracing_lib.activate(self._trace)
    # Metric-registry session (metrics.py): always created -- the
    # registry is the single render source for run stats and the run
    # record -- with the scrape endpoint bound only when --metrics_port
    # asks for it (per-rank offset under kfrun: rank r serves
    # port + r). Host-side only, like the trace session: the metrics-on
    # step program is structurally identical to the metrics-off golden
    # (analysis/audit.rule_metrics_twin).
    self._registry = metrics_lib.MetricRegistry()
    metrics_lib.activate(self._registry)
    self._registry.set("run_id", run_id)
    self._metrics_server = None
    if p.metrics_port:
      port = metrics_lib.resolve_port(p.metrics_port, rank)
      try:
        self._metrics_server = metrics_lib.MetricsServer(
            self._registry, port, healthz_fn=self._healthz_payload)
        log_fn("metrics endpoint: http://127.0.0.1:%d/metrics"
               % self._metrics_server.port)
      except (OSError, OverflowError) as e:
        # A taken port must not cost the run: train without the scrape
        # surface, loudly. (OverflowError: a per-rank offset can push
        # the resolved port past 65535, which bind() rejects with a
        # non-OSError.)
        log_fn(f"metrics endpoint: bind to port {port} failed ({e}); "
               "serving disabled for this run")
    self._compiled_programs = set()
    # Persistent XLA compilation cache, resolved BEFORE the first trace
    # by the one rule (configure_compile_cache; a no-op re-resolve when
    # setup() already applied it): every sealed chip machine starts
    # cold, so this decides whether the second process of a call
    # recompiles the model.
    cache_dir = configure_compile_cache(p.device, p.compilation_cache_dir)
    self._compile_cache_dir = cache_dir
    if cache_dir:
      log_fn(f"XLA compilation cache: {cache_dir}")
    # Everything from the build on runs under the try: a raise anywhere
    # (compile error, bad data_dir, sink failure) must still deactivate
    # the module-global trace session (a leaked active session would
    # swallow later emitters in this process) and export what was
    # captured.
    # JAX's own account of tracing, lowering, compiling and the
    # persistent cache (tracing.py on_*); removed in the finally below.
    jax.monitoring.register_event_time_span_listener(
        self._trace.on_time_span)
    jax.monitoring.register_event_listener(self._trace.on_event)
    try:
      init_state, train_step, eval_step, broadcast_init, train_chunk = \
          self._build()
      rng = jax.random.PRNGKey(p.tf_random_seed or 0)
      data_rng, init_rng = jax.random.split(rng)
      self._data_rng = data_rng
      with self._trace.span("setup", "open_input"):
        next_batch = self._open_input(data_rng, "train")
      # Flight recorder + stall watchdog for the whole build->train span
      # (the watchdog's patient first-compile regime must cover the init
      # and warmup compiles, not just the timed loop). None when the
      # resolved --health_stats is off. Same launcher-derived world as
      # the trace session: under kfrun jax.process_count() is 1 per CPU
      # worker, and num_ranks=1 would silently disable the rank-0
      # flight-recorder merge at exit.
      self._telemetry = telemetry_lib.TelemetrySession.create(
          p, rank=rank, log_fn=log_fn, num_ranks=world, run_id=run_id)
      return self._train_loop(init_state, train_step, eval_step,
                              broadcast_init, init_rng, next_batch,
                              train_chunk)
    finally:
      if self._telemetry is not None:
        self._telemetry.close()
        self._telemetry = None
      stop_input = getattr(self, "_input_stop", None)
      if stop_input is not None:
        stop_input()
      # Endpoint down, then registry session: a scrape arriving during
      # teardown reads the final published snapshot, never a
      # half-closed server. Deactivate AFTER the input stop (the feeder
      # worker publishes feed lanes until it joins), then export: the
      # per-rank span file + the rank-0 multi-rank merge (tracing.py).
      if self._metrics_server is not None:
        self._metrics_server.close()
        self._metrics_server = None
      metrics_lib.deactivate()
      tracing_lib.deactivate()
      jax.monitoring.unregister_event_time_span_listener(
          self._trace.on_time_span)
      jax.monitoring.unregister_event_listener(self._trace.on_event)
      try:
        self._trace.export()
      except Exception as e:  # an export failure must not eat the run
        log_fn(f"trace export failed (non-fatal): {e!r}")

  def _healthz_payload(self) -> Dict[str, Any]:
    """The /healthz body (metrics.MetricsServer calls this from its
    serving thread): watchdog + flight-recorder state when a telemetry
    session is live, a bare liveness ack otherwise. Reads only."""
    payload: Dict[str, Any] = {"status": "ok",
                               "run_id": self._trace.run_id}
    tele = getattr(self, "_telemetry", None)
    if tele is not None:
      payload.update(tele.healthz())
    return payload

  def _open_input(self, rng, subset: str, bump: bool = True):
    """Open a fresh input stream, closing the previous one (elastic
    reshapes swap streams mid-run). ``bump=False`` reopens at the
    CURRENT incarnation (the checkpoint-resume path, which sets the
    incarnation from the snapshot rather than advancing it)."""
    stop_prev = getattr(self, "_input_stop", None)
    if stop_prev is not None:
      stop_prev()
      if bump:
        self._input_incarnation = getattr(self, "_input_incarnation",
                                          0) + 1
    incarnation = getattr(self, "_input_incarnation", 0)
    if incarnation:
      # Folded only for incarnation >= 1 (a plain run's stream is the
      # seed rng exactly, keeping every pre-elastic pin); keyed on the
      # COUNT rather than the fold history so a run resuming after a
      # reshape can reproduce stream k exactly by presetting
      # _input_incarnation -- the bit-identity A/B of the elastic
      # rescale tests depends on it.
      rng = jax.random.fold_in(rng, incarnation)
    # Training streams stage --steps_per_dispatch batches per fetch
    # (already 1 in eval/forward-only modes, validation.py).
    chunk = self.steps_per_dispatch if subset == "train" else 1
    next_batch, stop = self._input_iterator(rng, subset, chunk=chunk)
    self._input_stop = stop
    return next_batch

  def _reshape_topology(self, state, num_devices: int,
                        batch_per_device: int, init_rng,
                        steps_done: int = 0, examples_done: int = 0):
    """Elastic rescale: rebuild mesh + jitted steps for a new topology and
    carry training state across via the checkpoint snapshot/restore path
    (SURVEY 7.4: XLA programs are topology-fixed, so resize == re-jit +
    state re-shard; the KungFu resize_cluster analog).
    """
    # State-dict form, the same shape restore_state consumes when reading
    # a checkpoint file (namedtuple opt states become plain dicts).
    # Under --shard_optimizer_state the snapshot carries the FULL (n, k)
    # shard stack, which restore_state re-slices onto the new topology
    # (checkpoint.py _reshard -- the cross-mesh rescale).
    from flax import serialization
    sharded = self._sharded_state
    snapshot = serialization.to_state_dict(
        checkpoint.savable_state(state, sharded_opt_state=sharded,
                                 sharded_params=self._sharded_params))
    self.num_devices = num_devices
    params_new = self.params._replace(num_devices=num_devices)
    self.batch_size_per_device = batch_per_device
    self.model.set_batch_size(batch_per_device)
    if mesh_lib.BATCH_AXIS in self.mesh.axis_names:
      # 2-D family: the model-axis width survives the resize (the poll
      # path rejected targets it does not divide); the batch axis takes
      # the rest, so the global batch follows the DATA width only.
      nm = int(self.mesh.shape[mesh_lib.MODEL_AXIS])
      self.mesh = mesh_lib.build_mesh_2d(num_devices // nm, nm,
                                         params_new.device)
      if params_new.mesh_shape:
        params_new = params_new._replace(
            mesh_shape=f"{num_devices // nm}x{nm}")
    else:
      self.mesh = mesh_lib.build_mesh(num_devices, params_new.device)
    self.params = params_new
    self.num_data_replicas = mesh_lib.num_data_replicas(self.mesh)
    self.batch_size = batch_per_device * self.num_data_replicas
    # Rebuild the strategy: its reducer may capture topology-derived
    # constants sized to the OLD axis (hierarchical_copy groups,
    # planner replica hints), which would mis-permute on the new mesh.
    self.strategy = strategies.get_strategy(self.params)
    # Epoch-based eval schedules are example counts; re-anchor their
    # step mapping to the new global batch size.
    self.eval_step_set = compute_eval_step_set(
        self.params, self.batch_size * max(self.num_workers, 1),
        self.dataset.num_examples_per_epoch("train"), self.num_batches,
        start_step=steps_done, start_examples=examples_done)
    init_state, train_step, eval_step, broadcast_init, train_chunk = \
        self._build()
    # The rebuilt programs recompile at the new topology: their first
    # dispatches are fresh compile-ledger episodes (the config
    # fingerprint differs -- num_devices/mesh_shape changed).
    self._compiled_programs = set()
    next_batch = self._open_input(self._data_rng, "train")
    shape = (batch_per_device,) + self._model_image_shape()
    new_state = init_state(init_rng, jnp.zeros(shape, jnp.float32))
    new_state = checkpoint.restore_state(
        new_state, snapshot, sharded_opt_state=sharded,
        sharded_params=self._sharded_params)
    new_state = new_state.replace(
        params=broadcast_init(new_state.params))
    self._verify_resumed_state(new_state)
    return new_state, train_step, eval_step, next_batch, train_chunk

  def _save_checkpoint(self, state, incarnation_bump: int = 0) -> None:
    """The ONE checkpoint-write path: layout flag + the input-stream
    incarnation a resumed run must reopen at. ``incarnation_bump=1`` at
    the resize seam: the snapshot's resume point is the POST-resize
    stream (the rebuild bumps the incarnation right after this save).
    Also the ONE place checkpoint-save wall time enters the run trace
    (span + p50/p90/p99 sample, tracing.py)."""
    trace = tracing_lib.active()
    t0 = trace.now()
    with trace.span("checkpoint", "save",
                    incarnation_bump=incarnation_bump):
      checkpoint.save_checkpoint(
          self.params.train_dir, state, self.params.max_ckpts_to_keep,
          sharded_opt_state=self._sharded_state,
          input_incarnation=getattr(self, "_input_incarnation", 0)
          + incarnation_bump,
          sharded_params=self._sharded_params)
    dur = trace.now() - t0
    trace.add_sample("checkpoint_save", dur)
    metrics_lib.active().observe("checkpoint_save_s", dur)

  def _verify_resumed_state(self, state) -> None:
    """Resume-time contract re-verification (analysis/audit.py): every
    state rebuilt onto a new (or restored) mesh must structurally match
    it BEFORE training continues -- a wrong-topology state would train
    under broadcast semantics and corrupt the run long after the seam.
    The traced-program half of the same contract is the
    ``sharded_rescale`` golden (run_tests.py --audit)."""
    from kf_benchmarks_tpu.analysis import audit as audit_lib
    problems = audit_lib.check_resumed_state(state, self.mesh,
                                             self._sharded_state)
    if problems:
      raise RuntimeError(
          "resume contract violated on the rebuilt mesh: "
          + "; ".join(problems))

  def _train_loop(self, init_state, train_step, eval_step, broadcast_init,
                  init_rng, next_batch, train_chunk=None) -> Dict[str, Any]:
    p = self.params
    tele = getattr(self, "_telemetry", None)
    K = self.steps_per_dispatch
    chunked = K > 1 and train_chunk is not None
    # "synthetic" here means the RESIDENT single-batch feed (reused
    # every step, staged once); a --packed_sequences run has no
    # data_dir but streams fresh host-packed batches through the
    # DeviceFeeder, so it takes the real-data cursor/chunk paths.
    synthetic = (self.dataset.use_synthetic_gpu_inputs() and
                 not getattr(p, "packed_sequences", False))
    trace = self._trace
    # The first batch: for the resident synthetic feed this is the one
    # placement of the batch on the devices.
    with trace.span("setup", "first_batch"):
      images, labels = next_batch()
    # What a check by file needs to put the trained state through this
    # run's own step program once more (benchmarks/named_checks/): the
    # program and, for the resident synthetic feed, THE batch (a
    # streamed feed's first batch is not kept alive for it).
    self.timed_step = train_step
    self.timed_batch = (images, labels) if synthetic else None

    def _step_slice(ims, lbs, j: int = 0):
      """One per-step batch out of a staged chunk (identity when
      unchunked). The synthetic resident chunk has a single slot."""
      if not chunked:
        return ims, lbs
      jj = 0 if synthetic else min(j, ims.shape[0] - 1)
      return ims[jj], jax.tree.map(lambda x: x[jj], lbs)

    single_images, _ = _step_slice(images, labels)
    sample = jax.ShapeDtypeStruct(
        (self.batch_size_per_device,) + tuple(single_images.shape[1:]),
        single_images.dtype)
    replicated = mesh_lib.replicated_sharding(self.mesh)
    log_fn("Generating training model")
    t0 = time.time()
    # init_state is already jitted with explicit state shardings
    # (train_step.make_step_fns).
    with trace.span("setup", "init_state"):
      state = init_state(init_rng, jnp.zeros(sample.shape, sample.dtype))
    # Resume from the newest checkpoint if the train_dir has one; the run
    # then executes num_batches MORE steps from the restored global step
    # (ref: Supervisor auto-restore, benchmark_cnn.py:2122-2157).
    resumed = False
    if p.train_dir:
      with trace.span("checkpoint", "restore") as restore_args:
        try:
          # Parse-once resolve that skips torn/corrupt files with a
          # logged warning (checkpoint.load_latest_checkpoint).
          snapshot, path, ckpt_step = checkpoint.load_latest_checkpoint(
              p.train_dir)
          state = checkpoint.restore_state(
              state, snapshot, sharded_opt_state=self._sharded_state,
              sharded_params=self._sharded_params)
          # Cross-topology resumes (a sharded checkpoint written at a
          # different mesh re-slices in restore_state) re-verify the
          # structural contract exactly like an in-run rescale.
          self._verify_resumed_state(state)
          # Reopen the input stream at the snapshot's incarnation: a
          # rejoin after an elastic reshape must continue the POST-resize
          # stream, not silently reset to stream 0.
          snap_inc = int(snapshot.get("input_incarnation", 0) or 0)
          if snap_inc != getattr(self, "_input_incarnation", 0):
            self._input_incarnation = snap_inc
            next_batch = self._open_input(self._data_rng, "train",
                                          bump=False)
            images, labels = next_batch()
            log_fn(f"Resumed input stream at incarnation {snap_inc}")
          log_fn(f"Restored checkpoint at global step {ckpt_step}")
          restore_args["global_step"] = ckpt_step
          resumed = True
        except checkpoint.CheckpointNotFoundException:
          restore_args["found"] = False
    # Backbone warm-start before training (ref: benchmark_cnn.py:2204-2205
    # load_backbone_model at session start). Skipped on resume: the
    # resumed checkpoint's backbone is further-trained than the
    # warm-start values, which must not overwrite it mid-trajectory.
    if p.backbone_model_path and not resumed:
      state, n_restored = checkpoint.restore_backbone(
          state, p.backbone_model_path)
      if not n_restored:
        raise ValueError(
            f"--backbone_model_path={p.backbone_model_path} matched no "
            "variables of this model (wrong checkpoint?)")
      log_fn(f"Loaded {n_restored} backbone tensors from "
             f"{p.backbone_model_path}")
    if int(p.num_grad_accum or 1) > 1 and jax.tree.leaves(
        state.batch_stats):
      # Microbatched BN is standard Megatron-style semantics, but it is
      # a semantics CHANGE, not a pure memory lever: each microbatch
      # normalizes over batch/M samples and the running-stats EMA
      # advances M times per step. Losses/accuracy are NOT expected to
      # match the M=1 run for batch-norm models -- say so up front
      # rather than letting an operator chase a phantom regression.
      log_fn(f"Note: --num_grad_accum={p.num_grad_accum} with a "
             "batch-norm model: BN statistics are per-microbatch "
             f"(batch/{p.num_grad_accum}) and running stats update "
             f"{p.num_grad_accum}x per step; not numerically "
             "equivalent to the monolithic step (BN-free models are)")
    # Replica-0 broadcast at start (ref: benchmark_cnn.py:2094-2100).
    with trace.span("setup", "broadcast_init"):
      state = state.replace(params=broadcast_init(state.params))
      # Resolve the broadcast so the reported initialization time covers
      # the real device work.
      sync.drain(state.params)
    log_fn("Initialization: %.1f s" % (time.time() - t0))

    def make_run_step(train_step, eval_step):
      if p.forward_only:
        # Forward-only benchmarks inference speed: no gradients, no
        # optimizer, eval-phase module (ref: benchmark_cnn.py:124-126).
        def run_step(state, images, labels):
          return state, eval_step(state, images, labels)
        return run_step
      return train_step

    run_step = make_run_step(train_step, eval_step)

    if p.forward_only and p.aot_save_path:
      # The freeze+TRT analog (ref: _preprocess_graph :2405-2525): export
      # the trained forward pass with weights folded in as constants.
      from kf_benchmarks_tpu import aot
      variables = {"params": jax.tree.map(lambda x: x[0], state.params)}
      bs = jax.tree.map(lambda x: x[0], state.batch_stats)
      if bs:
        variables["batch_stats"] = bs
      trt_mode = (p.trt_mode or "").upper()
      export_dtype = {"FP32": jnp.float32, "FP16": jnp.bfloat16,
                      "INT8": jnp.bfloat16}.get(trt_mode,
                                                self.compute_dtype)
      from kf_benchmarks_tpu.analysis import baseline as baseline_lib
      nbytes = aot.export_forward(
          self.model, variables, self.batch_size_per_device,
          p.aot_save_path, nclass=self.dataset.num_classes,
          dtype=export_dtype, quantize=trt_mode == "INT8",
          # Exporting run's program identity, recorded in the signature
          # sidecar (aot.py): a serving process can tie the artifact
          # back to the config that froze it.
          fingerprint=baseline_lib.config_fingerprint_key(
              p._asdict(), "aot_forward"))
      log_fn(f"Exported frozen forward program to {p.aot_save_path} "
             f"({nbytes} bytes"
             + (f", {trt_mode} serving precision" if trt_mode else "")
             + ")")

    # Observability wiring (SURVEY 5.1/5.5; see observability.py).
    bench_logger = None
    if p.benchmark_log_dir:
      bench_logger = observability.BenchmarkLogger(p.benchmark_log_dir)
      bench_logger.log_run_info(p, self.model.get_name(),
                                self.dataset.name, self.num_devices,
                                self.batch_size)
    summary_writer = None
    if p.train_dir and p.save_summaries_steps and p.summary_verbosity:
      summary_writer = observability.SummaryWriter(p.train_dir,
                                                   p.summary_verbosity)
    if p.graph_file or p.tfprof_file or p.partitioned_graph_file_prefix:
      # One lowering feeds all dumps (tracing a big model twice is
      # minutes of redundant startup work). Forward-only dumps the eval
      # program it actually runs; chunked runs dump the K-step scanned
      # program (the unit of dispatch the timed loop executes).
      dump_fn = eval_step if p.forward_only else (
          train_chunk if chunked else train_step)
      lowered = dump_fn.lower(state, images, labels)
      if p.graph_file:
        observability.dump_program_text(lowered, p.graph_file)
        log_fn(f"Wrote program text to {p.graph_file}")
      # The compiled dumps share ONE compilation.
      compiled = (lowered.compile()
                  if p.tfprof_file or p.partitioned_graph_file_prefix
                  else None)
      if p.tfprof_file:
        device_kind = self.mesh.devices.flat[0].device_kind
        report = observability.dump_cost_analysis(
            lowered, p.tfprof_file, compiled=compiled,
            device_kind=device_kind)
        for key in ("cost_analysis_error", "memory_analysis_error"):
          if key in report:
            log_fn(f"tfprof: {key}: {report[key]}")
        log_fn("Wrote cost analysis to %s (note: the analysis compiles "
               "the step once ahead of the jit cache's own compile)"
               % p.tfprof_file)
        # The operator-facing top-op ranking the reference printed from
        # tfprof (ref: benchmark_cnn.py:1208-1228), against the peaks
        # of the device the mesh actually holds.
        peaks = observability.DEVICE_PEAKS.get(device_kind)
        if peaks is None:
          log_fn(observability.no_peaks_line(device_kind))
        else:
          table = observability.dump_per_op_profile(
              compiled, p.tfprof_file + ".ops.txt", peaks)
          for line in table.splitlines():
            log_fn(line)
        try:
          # The footprint the HBM levers (--num_grad_accum, the
          # chunked fused head, scanned-layer remat) actually move.
          log_fn(observability.hbm_breakdown_line(
              compiled.memory_analysis()))
        except Exception as e:  # backend-dependent surface
          log_fn(f"peak HBM line unavailable on {device_kind}: {e!r}")
      if p.partitioned_graph_file_prefix:
        path = p.partitioned_graph_file_prefix + ".txt"
        observability.dump_partitioned_text(compiled, path)
        log_fn(f"Wrote partitioned program text to {path}")

    # Elastic / adaptive-batch drivers (north-star KungFu capabilities;
    # see elastic.py).
    noise_ema = (elastic_lib.NoiseScaleEMA()
                 if p.track_grad_noise_scale else None)
    if noise_ema is not None and self.num_devices < 2:
      # The estimator contrasts per-replica vs replica-mean gradients;
      # with one replica there is no contrast and no metrics are emitted.
      log_fn("track_grad_noise_scale: needs >= 2 devices, no estimates "
             "will be produced (adaptive_batch_size will hold steady)")
    batch_policy = (elastic_lib.AdaptiveBatchPolicy(
        p.adaptive_batch_min, p.adaptive_batch_max)
        if p.adaptive_batch_size else None)
    controller = self.elastic_controller
    if controller is None and p.elastic:
      controller = elastic_lib.ElasticController.from_env(
          max_devices=len(mesh_lib.get_devices(p.device)))
      if controller is None:
        log_fn("elastic: no coordination service in env (KFCOORD_*); "
               "resize polling disabled")
    reshape_events = []

    # Snapshot pre-existing profiler runs so the measured per-op table is
    # pinned to the trace THIS run captures (a stale dump at the same
    # --trace_file path must never be reported as this run's profile).
    trace_dir = observability.trace_dir_of(p.trace_file)
    pre_trace_runs = (observability.list_profile_runs(trace_dir)
                      if p.trace_file and p.tfprof_file else [])

    # Host-side dispatch accounting for the BENCH trajectory: the FIRST
    # dispatch call blocks on trace+compile (compile_s); later calls
    # measure the per-dispatch host overhead (jit-call machinery +
    # transfer/RTT) that --steps_per_dispatch amortizes. Timed-loop
    # entries only feed dispatch_overhead_s (warmup's are cleared), and
    # the measurement brackets the async fn call alone -- never the
    # trace drain.
    dispatch_stats = {"compile_s": None, "call_times": []}

    def _note_compile(label: str, wall_s: float, mark) -> None:
      """A host call of a jitted program that blocked on trace+compile
      (its first, or a later one that recompiled): ledger the episode
      under the program-shape fingerprint key
      (analysis/baseline.config_fingerprint_key). ``mark`` is the
      session's ``compile_mark`` from before the call: the row's
      ``cache_hit`` comes from the compilation cache's own events
      during it (jax.monitoring, tracing.py)."""
      from kf_benchmarks_tpu.analysis import baseline as baseline_lib
      self._compiled_programs.add(label)
      key = baseline_lib.config_fingerprint_key(self.params._asdict(),
                                                label)
      trace.note_compile(
          key, label, wall_s, since=mark, model=self.model.get_name(),
          num_devices=self.num_devices)

    def _traced(trace_file, idx, trace_at, label, fn, *args):
      """One dispatch under the single-dispatch trace policy: trace it
      when ``idx == trace_at`` (warmup traces its LAST dispatch, ref
      :806-817 traces step -2 for the same reason; with zero warmup the
      timed loop traces its first) and -- dispatch being async -- drain
      inside the profiler context so the trace spans the device
      execution.
      The ONE place this invariant lives; every dispatch site routes
      through it. ``label`` names the dispatched program for the
      dispatch-issue span and the compile ledger: the span brackets the
      ASYNC jit call only (device completion is attributed
      differentially from the pipeline arrival intervals in
      _handle). A call during which JAX compiled is ledgered, be it
      the label's first or a recompile of a label already seen."""
      with observability.maybe_trace_step(trace_file, idx, trace_at):
        first = label not in self._compiled_programs
        mark = trace.compile_mark()
        with trace.span("dispatch", label, step=idx, first_call=first):
          t_call = time.monotonic()
          new_state, out_metrics = fn(*args)
          dt = time.monotonic() - t_call
        if dispatch_stats["compile_s"] is None:
          dispatch_stats["compile_s"] = dt
        dispatch_stats["call_times"].append(dt)
        if first or trace.compiles_since(mark):
          _note_compile(label, dt, mark)
        if trace_file and idx == trace_at:
          sync.drain(out_metrics)
      return new_state, out_metrics

    log_fn("Running warm up")
    trace.begin_phase(tracing_lib.PHASE_WARMUP)
    t0 = time.time()
    cursor = 0  # consumed slices of the current staged real-data chunk
    if chunked:
      # Exactly num_warmup_batches warmup steps, like K=1: q whole
      # chunks first, then r = W mod K single steps consuming slices of
      # the next staged chunk. The warmed-up STATE and (real data) the
      # stream position are therefore identical to the K=1 loop's,
      # which is what keeps the timed per-step losses bit-identical
      # across K. The chunk program compiles here when q >= 1 and the
      # single-step program when r >= 1; a program not exercised by
      # this split compiles at its first use instead (a tail/event
      # dispatch, or -- when W < K -- the first timed chunk).
      q, r = divmod(self.num_warmup_batches, K)
      n_dispatches = q + r
      w = 0
      for _ in range(q):
        state, metrics = _traced(p.trace_file, w, n_dispatches - 1,
                                 "train_chunk", train_chunk, state,
                                 images, labels)
        images, labels = next_batch()
        w += 1
      for _ in range(r):
        state, metrics = _traced(p.trace_file, w, n_dispatches - 1,
                                 "train_step", run_step, state,
                                 *_step_slice(images, labels, cursor))
        if not synthetic:
          cursor += 1
          if cursor >= images.shape[0]:
            images, labels = next_batch()
            cursor = 0
        w += 1
      warm_steps = self.num_warmup_batches
      if n_dispatches and not p.trace_file:
        sync.drain(metrics)
    else:
      for w in range(self.num_warmup_batches):
        state, metrics = _traced(p.trace_file, w,
                                 self.num_warmup_batches - 1,
                                 "train_step", run_step, state, images,
                                 labels)
        images, labels = next_batch()
      warm_steps = self.num_warmup_batches
      if self.num_warmup_batches and not p.trace_file:
        # Empty the device queue before the clock starts: timing must not
        # begin with warmup steps still executing (utils/sync.py). With
        # --trace_file the traced last step already drained in-context.
        sync.drain(metrics)
    log_fn("Warmup (compile + %d steps): %.1f s" %
           (warm_steps, time.time() - t0))
    if tele is not None and self.num_warmup_batches:
      # First heartbeat: compile + warmup completed (the drain above is
      # a real value fetch, utils/sync.py) -- the watchdog leaves its
      # patient first-compile regime here. With --num_warmup_batches=0
      # no dispatch has run yet, so the beat is withheld and the
      # watchdog stays in the patient regime through the first timed
      # dispatch (which IS the first compile then, per the chunked
      # warmup-split comment above).
      tele.beat()
    # Base for globally-meaningful step numbers in metric/summary streams
    # (resumed runs must not restart their step axis at 1).
    start_step = int(state.step)

    header = "Step\tImg/sec\t" + p.loss_type_to_report
    if p.print_training_accuracy:
      header += "\ttop_1_accuracy\ttop_5_accuracy"
    log_fn(header)

    step_train_times = []
    chunk_times = []  # wall interval per K-step dispatch (chunked mode)
    loss = float("nan")
    stopped_early = False
    restart_requested = None
    images_processed = 0
    last_save_time = time.time()
    last_display_len = 0
    # Lag-2 pipelined metric fetch (utils/pipeline.py): blocking on each
    # step's metrics costs a full host<->device round trip per step and
    # leaves the device queue empty while the host reads.
    # Reading each step's metrics two dispatches later keeps the device
    # queue full, every printed number is still the exact value for its
    # step, and the read-arrival intervals are real per-step times for
    # the mean/uncertainty/jitter stats (ref: benchmark_cnn.py:887-902).
    pipe = pipeline_lib.MetricsPipeline(lag=2)

    # The device span of the dispatch currently resolving through
    # _handle: opened at its FIRST completed step (every member carries
    # the full chunk interval), shared by all K rows, closed at
    # chunk_end -- so every flight-recorder row cross-links the span
    # it lies inside. issue_walls pairs each resolving dispatch with
    # ITS OWN host-issue wall: the pipeline resolves dispatches FIFO
    # but lag-2 behind the issues, so call_times[-1] would belong to a
    # LATER dispatch (and make the wall - issue differential lie).
    dispatch_span = {"id": None}
    issue_walls = []
    # The model's own per-step counters (train_step: metrics["counters"]).
    counter_rows = []

    def _handle(done: "pipeline_lib.CompletedStep"):
      """The host's bookkeeping for one resolved step, as one span:
      registry, flight recorder, step line, summaries."""
      with trace.span("handle", "step", step=start_step + done.index):
        _handle_step(done)

    def _handle_step(done: "pipeline_lib.CompletedStep"):
      nonlocal loss, last_display_len
      step_train_times.append(done.interval)
      if done.chunk_len > 1 and done.chunk_end:
        chunk_times.append(done.chunk_interval)
      m = done.metrics
      loss = float(m[p.loss_type_to_report])
      # Live registry lanes (metrics.py): the /metrics scrape shows the
      # run's current step/loss/health WHILE it trains. Registered-key
      # sets only; host dict writes, nothing device-side.
      registry = metrics_lib.active()
      registry.set("step", start_step + done.index)
      registry.set("loss", loss)
      if "learning_rate" in m:
        registry.set("learning_rate", float(m["learning_rate"]))
      for health_name, health_value in \
          telemetry_lib.health_scalars(m).items():
        registry.set(health_name, health_value)
      if dispatch_span["id"] is None:
        # Device completion attributed DIFFERENTIALLY: the pipeline's
        # read-arrival interval is the dispatch's real wall (the lag-2
        # fetch IS the sync signal, utils/pipeline.py); the SAME
        # dispatch's host-issue share rides in the args so device time
        # can be read as wall - issue.
        issue_s = issue_walls.pop(0) if issue_walls else None
        t_now = trace.now()
        dispatch_span["id"] = trace.add_span(
            "device", "chunk" if done.chunk_len > 1 else "step",
            t_now - done.chunk_interval, done.chunk_interval,
            {"steps": done.chunk_len,
             "end_step": start_step + done.index + done.chunk_len - 1
             if not done.chunk_end else start_step + done.index,
             "issue_ms": (round(issue_s * 1e3, 3)
                          if issue_s is not None else None)})
      if tele is not None:
        # One flight-recorder row per STEP (chunked dispatches resolve
        # to per-step metrics host-side, utils/pipeline.py); heartbeat
        # once per completed dispatch with its real wall interval. The
        # pipeline's metric fetch IS the liveness signal.
        tele.record(
            step=start_step + done.index, loss=loss,
            lr=m.get("learning_rate"), health=m.get("health"),
            wall_ms=done.interval * 1e3, chunk_len=done.chunk_len,
            rtt_ms=(dispatch_stats["call_times"][-1] * 1e3
                    if dispatch_stats["call_times"] else None),
            span_id=dispatch_span["id"] or None)
        if done.chunk_end:
          tele.beat(done.chunk_interval)
      if done.chunk_end:
        trace.add_sample("chunk_wall", done.chunk_interval)
        metrics_lib.active().observe("chunk_wall_s", done.chunk_interval)
        dispatch_span["id"] = None
      if "counters" in m:
        counter_rows.append(np.asarray(m["counters"], np.float64))
      if noise_ema is not None and "noise_scale_g2" in m:
        noise_ema.update(float(m["noise_scale_g2"]),
                         float(m["noise_scale_s"]))
      i1 = done.index
      if i1 % self.display_every == 0 or i1 == self.num_batches:
        top1 = float(m["top_1_accuracy"]) if "top_1_accuracy" in m else None
        top5 = float(m["top_5_accuracy"]) if "top_5_accuracy" in m else None
        window = step_train_times[last_display_len:]
        # The line's LISTENER (a pipe, a tee that starts a profiler) is
        # not the program's bookkeeping: a span of its own.
        with trace.span("handle", "log_line"):
          log_fn(log_util.format_step_line(
              i1, self.batch_size * max(self.num_workers, 1), window, loss,
              top1, top5))
        registry.set(
            "step_images_per_sec",
            self.batch_size * max(self.num_workers, 1) /
            max(sum(window) / max(len(window), 1), 1e-9))
        if bench_logger is not None:
          # Per-step metric emission (ref: benchmark_cnn.py:847-854).
          window_avg = sum(window) / max(len(window), 1)
          bench_logger.log_metric(
              "current_examples_per_sec",
              self.batch_size * max(self.num_workers, 1) /
              max(window_avg, 1e-9),
              unit="examples/sec", global_step=start_step + i1)
          bench_logger.log_metric(p.loss_type_to_report, loss,
                                  global_step=start_step + i1)
        last_display_len = len(step_train_times)
      if summary_writer is not None and i1 % p.save_summaries_steps == 0:
        scalars = {k: v for k, v in m.items() if np.ndim(v) == 0}
        # The packed health vector expands into the SAME health/<key>
        # scalars the flight-recorder rows carry (one shared schema,
        # telemetry.py).
        scalars.update(telemetry_lib.health_scalars(m))
        summary_writer.write_scalars(start_step + i1, scalars)
        if summary_writer.verbosity >= 2:  # slice only when it will be used
          # Histograms read the live state (may be up to `lag` steps ahead
          # of i1 -- histogram verbosity is a debugging surface).
          # --shard_params never reaches here: validation.py rejects it
          # with verbosity >= 2 (row 0 would be a 1/n flat shard, not
          # the replica-0 parameter copy the histogram keys claim).
          summary_writer.write_histograms(
              start_step + i1,
              jax.tree.map(lambda x: x[0], state.params), "params",
              stacked_prefixes=tuple(
                  getattr(self.model, "scanned_param_prefixes", ())
                  or ()))

    # Step-keyed schedule predicates. The SAME functions feed both the
    # dispatch-length planner (_event_due) and the post-dispatch due
    # flags below, so the chunk-shortening contract ("a chunk never
    # crosses a scheduled step") cannot drift from the schedule that
    # actually fires. The seconds-based checkpoint cadence is not
    # step-keyed: it is checked at dispatch boundaries only, so under
    # chunking it can land up to K-1 steps late -- it is a wall-clock
    # schedule already.
    def _save_steps_due(s: int) -> bool:
      return bool(p.train_dir and p.save_model_steps and
                  s % p.save_model_steps == 0)

    def _eval_sched_due(s: int) -> bool:
      return bool((p.eval_during_training_every_n_steps and
                   s % p.eval_during_training_every_n_steps == 0) or
                  s in self.eval_step_set)

    def _elastic_sched_due(s: int) -> bool:
      return bool((controller is not None or batch_policy is not None) and
                  s % p.elastic_check_every_n_steps == 0)

    def _fault_due(s: int) -> bool:
      return self._faults is not None and self._faults.due(s)

    def _event_due(s: int) -> bool:
      """A host intervention is scheduled immediately after step ``s``."""
      return (_save_steps_due(s) or _eval_sched_due(s) or
              _elastic_sched_due(s) or _fault_due(s))

    def _dispatch_len(done_steps: int) -> int:
      """Length of the next dispatch: up to K steps, stopping at the run
      end and BEFORE any step-keyed event strictly inside the window, so
      checkpoints/eval/elastic keep exact K=1 step semantics (the chunk
      shortens; the short remainder runs as single steps)."""
      n = min(K, self.num_batches - done_steps)
      for d in range(1, n):
        if _event_due(done_steps + d):
          return d
      return n

    loop_start = time.time()
    trace.begin_phase(tracing_lib.PHASE_TIMED)
    pipe.reset_clock()
    # Warmup dispatches (incl. the compile call) must not skew the
    # timed loop's per-dispatch host-overhead average.
    dispatch_stats["call_times"].clear()
    i = 0  # steps completed (cursor carries over from warmup)
    # Injected drop_msg (faults.py) is STICKY: the fault may fire at a
    # non-poll boundary, and what it must suppress is the NEXT actual
    # coordination poll -- consumed there, not at its own step.
    drop_next_poll = False
    while i < self.num_batches:
      n_dispatch = _dispatch_len(i) if chunked else 1
      if chunked and not synthetic and cursor:
        # Mid-chunk (warmup remainder or an event-shortened dispatch
        # consumed part of the staged chunk): run single steps only up
        # to the chunk boundary, so the NEXT dispatch meets a fully
        # unconsumed chunk. Without this cap an event-free run would
        # execute K singles per iteration, land on the same cursor
        # residue forever, and never dispatch a chunk at all.
        n_dispatch = min(n_dispatch, images.shape[0] - cursor)
      # A full-K dispatch needs a chunk-aligned input: the synthetic
      # resident batch always is; a staged real-data chunk only when
      # fully unconsumed.
      use_chunk = (chunked and n_dispatch == K and
                   (synthetic or (cursor == 0 and images.shape[0] == K)))
      # (trace fallback: with zero warmup dispatches the trace runs on
      # the FIRST timed dispatch, via _traced's trace_at == i == 0)
      timed_trace = p.trace_file if self.num_warmup_batches == 0 else None
      # One ``train`` step per dispatch, numbered by the global step it
      # starts at: issue, next batch, and the fetch + bookkeeping of
      # the dispatch that left the lag-2 ring. Scheduled events below
      # (checkpoint, eval, resize) have spans of their own.
      if use_chunk:
        with trace.step("train", start_step + i):
          state, metrics = _traced(timed_trace, i, 0, "train_chunk",
                                   train_chunk, state, images, labels)
          issue_walls.append(dispatch_stats["call_times"][-1])
          images, labels = next_batch()
          i += K
          images_processed += (K * self.batch_size *
                               max(self.num_workers, 1))
          for done in pipe.push(i, metrics, count=K):
            _handle(done)
      else:
        for _ in range(n_dispatch):
          with trace.step("train", start_step + i):
            state, metrics = _traced(timed_trace, i, 0, "train_step",
                                     run_step, state,
                                     *_step_slice(images, labels, cursor))
            issue_walls.append(dispatch_stats["call_times"][-1])
            if not chunked:
              images, labels = next_batch()
            elif not synthetic:
              cursor += 1
              if cursor >= images.shape[0]:
                images, labels = next_batch()
                cursor = 0
            i += 1
            images_processed += self.batch_size * max(self.num_workers, 1)
            for done in pipe.push(i, metrics):
              _handle(done)
      save_due = _save_steps_due(i) or bool(
          p.train_dir and p.save_model_secs and
          time.time() - last_save_time >= p.save_model_secs)
      eval_due = _eval_sched_due(i)
      elastic_due = _elastic_sched_due(i)
      fault_due = _fault_due(i)
      if save_due or eval_due or elastic_due or fault_due:
        # Sync point: resolve everything in flight so checkpoint/eval/
        # resize wall time stays out of the per-step timing, then exclude
        # it from the next interval via note_aux_time.
        for done in pipe.flush():
          _handle(done)
        aux_start = time.time()
        if fault_due:
          # Faults fire FIRST at the boundary (a preemption does not
          # wait for the checkpoint cadence): kill/sigterm never
          # return; corrupt_ckpt truncates the newest snapshot already
          # ON DISK (i.e. before this boundary's own save lands); the
          # recorder row is written BEFORE firing so a kill still
          # leaves its trace in the continuous window.
          if tele is not None:
            for f in self._faults.peek_due(i):
              tele.fault_event(f.describe(), i)
          fired = self._faults.fire_due(i, train_dir=p.train_dir)
          if fired.dropped_message:
            drop_next_poll = True
        if save_due:
          # Periodic checkpoint by steps (ref: benchmark_cnn.py:2304-2309)
          # or seconds (ref: Supervisor save_model_secs, :2137).
          self._save_checkpoint(state)
          last_save_time = time.time()
        if eval_due:
          # Mid-training eval + early stop (ref: benchmark_cnn.py:2310-2324).
          with trace.span("eval", "mid_train_eval", step=i):
            t_eval = trace.now()
            mark = trace.compile_mark()
            acc = eval_step(state, *_step_slice(images, labels, cursor))
            # The ledger convention brackets the ASYNC first call only
            # (blocks on trace+compile) -- the device_get below adds
            # execution + transfer wall, which belongs to the eval
            # span, not the compile episode.
            eval_issue = trace.now() - t_eval
            if ("eval_step" not in self._compiled_programs or
                trace.compiles_since(mark)):
              _note_compile("eval_step", eval_issue, mark)
            acc = jax.device_get(acc)
          top1 = float(acc["top_1_accuracy"])
          log_fn("Accuracy @ 1 = %.4f Accuracy @ 5 = %.4f [%d examples]" %
                 (top1, float(acc["top_5_accuracy"]), self.batch_size))
          if p.stop_at_top_1_accuracy and top1 >= p.stop_at_top_1_accuracy:
            log_fn(f"Stopping early at top-1 accuracy {top1:.4f} "
                   f">= {p.stop_at_top_1_accuracy}")
            stopped_early = True
            break
        # Elastic resize / adaptive batch (north-star KungFu capabilities;
        # SURVEY 2.9, 5.3). Polled at a fixed cadence to keep the hot loop
        # collective-free.
        if elastic_due and i < self.num_batches:
          new_n = None
          restart_np = None
          under_kfrun = "KFCOORD_WORLD" in os.environ
          if controller is not None and drop_next_poll:
            # Injected drop_msg (faults.py): this poll is the lost
            # message. The poll-side dedup never advanced, so a
            # pending RESIZE must re-surface at the next poll instead
            # of vanishing (pinned in tests/test_faults.py).
            drop_next_poll = False
            log_fn(f"fault drop_msg: coordination poll at step {i} "
                   "dropped; a pending resize stays pending")
          elif controller is not None:
            poll_at = getattr(controller, "poll_at", None)
            new_n = poll_at(i) if poll_at else controller.poll()
            raw = getattr(controller, "last_raw_target", None)
            if new_n is not None and raw and under_kfrun:
              # Under the kfrun launcher the RESIZE target is a GLOBAL
              # device count. If it fits the current process set at
              # PER-PROCESS capacity (locally attached devices -- the
              # controller's max_devices is global), reshape in-mesh;
              # otherwise a live JAX world cannot change its process
              # count, so SCHEDULE the checkpoint-restart leg a couple
              # of poll windows ahead -- workers poll at the same step
              # but different wall times, and an immediate restart
              # would split-brain (SURVEY 5.3/7.4 "checkpointed
              # rescale"; KungFu resize_cluster's config-server-
              # synchronized resize).
              action, value = elastic_lib.plan_resize(
                  raw, procs=max(self.num_workers, 1),
                  capacity=jax.local_device_count(),
                  # The restart can only spawn processes that have
                  # somewhere to live: cap at the provisioned host list
                  # (absent one there is no distributed world to
                  # re-form, so scaling stays in-mesh).
                  max_procs=len(p.worker_hosts or []) or 1)
              if action == "restart":
                if (hasattr(controller, "scheduled_restart") and
                    controller.scheduled_restart() is None):
                  k = max(1, p.elastic_check_every_n_steps)
                  controller.schedule_restart(i + 2 * k, value)
                # The restart owns this resize: the clamped global poll
                # value must not fall through to the per-process
                # in-mesh reshape below.
                new_n = None
              else:
                new_n = value
            # Agreement point: adopt any pending scheduled restart. A
            # schedule whose target equals this incarnation's world is
            # already satisfied (stale key from before the re-exec).
            if under_kfrun and hasattr(controller, "scheduled_restart"):
              sched = controller.scheduled_restart()
              if sched is not None:
                sched_step, sched_np = sched
                if (sched_np != max(self.num_workers, 1) and
                    i >= sched_step):
                  restart_np = sched_np
            if restart_np is None and new_n == self.num_devices:
              new_n = None
          if restart_np is not None:
            if not p.train_dir:
              log_fn("Elastic restart to %d worker(s) requested but "
                     "--train_dir is unset; cannot checkpoint-restart, "
                     "ignoring" % restart_np)
            else:
              for done in pipe.flush():
                _handle(done)
              self._save_checkpoint(state)
              log_fn("Elastic restart at step %d: workers %d -> %d "
                     "(checkpoint + re-exec under the launcher)" % (
                         i, max(self.num_workers, 1), restart_np))
              # SPMD lockstep: every worker reaches this at the same
              # step; the barrier holds exits until the chief's
              # checkpoint write completed (the chief enters after
              # writing).
              try:
                controller.restart_barrier(
                    f"kf_restart_{controller.generation()}",
                    max(self.num_workers, 1))
              except Exception as e:  # noqa: BLE001
                log_fn(f"restart barrier failed ({e}); exiting anyway")
              trace.instant("elastic", "checkpoint_restart", step=i,
                            workers=restart_np)
              restart_requested = restart_np
              break
          new_bs = None
          if batch_policy is not None and noise_ema is not None:
            proposed = batch_policy.propose(
                self.batch_size_per_device, noise_ema.b_simple,
                new_n or self.num_devices)
            if proposed != self.batch_size_per_device:
              new_bs = proposed
          nm_axis = (int(self.mesh.shape[mesh_lib.MODEL_AXIS])
                     if mesh_lib.BATCH_AXIS in self.mesh.axis_names else 1)
          if new_n and new_n % nm_axis:
            # 2-D family: the model axis survives a resize, so the
            # target must be a multiple of its width.
            log_fn(f"Elastic reshape to {new_n} devices rejected: the "
                   f"model-axis width ({nm_axis}) must divide the "
                   "target on the 2-D mesh; keeping current topology")
            new_n = None
          if new_n:
            # A resize must honor the same cross-flag rules as startup
            # (e.g. the async-PS sequential-apply device cap): an
            # in-mesh up-resize is the one path that changes num_devices
            # without re-running startup validation, so check here and
            # hold topology rather than grow into a configuration the
            # CLI would have rejected.
            try:
              check = self.params._replace(num_devices=new_n)
              if check.mesh_shape:
                check = check._replace(
                    mesh_shape=f"{new_n // nm_axis}x{nm_axis}")
              validation.validate_cross_flags(check)
            except validation.ParamError as e:
              log_fn(f"Elastic reshape to {new_n} devices rejected by "
                     f"flag validation ({e}); keeping current topology")
              new_n = None
          if new_n or new_bs:
            event = {"step": i,
                     "num_devices": new_n or self.num_devices,
                     "batch_size_per_device":
                         new_bs or self.batch_size_per_device,
                     "b_simple": noise_ema.b_simple if noise_ema else None}
            log_fn("Elastic reshape at step %d: devices %d -> %d, "
                   "per-device batch %d -> %d" % (
                       i, self.num_devices, event["num_devices"],
                       self.batch_size_per_device,
                       event["batch_size_per_device"]))
            old_mesh = "x".join(
                str(int(s)) for s in self.mesh.devices.shape)
            generation = len(reshape_events) + 1
            if controller is not None and hasattr(controller,
                                                  "generation"):
              try:
                generation = controller.generation()
              except Exception:
                pass
            # One span per generation on the elastic track: the whole
            # seam (seam snapshot + mesh rebuild + re-jit + restore +
            # contract re-verification), so the timeline shows where a
            # resize's wall went.
            with trace.span("elastic", f"resize_gen{generation}",
                            generation=generation,
                            resume_step=i) as seam_args:
              if p.train_dir:
                # Drain happened at the sync point above; snapshot to
                # disk BEFORE the rebuild, so a crash mid-rescale (or a
                # preemption racing it) resumes from this exact seam --
                # and a peer run at the new size can start from the same
                # snapshot (the bit-identity contract of the rescale
                # tests). incarnation_bump=1: the seam's resume point is
                # the POST-resize input stream.
                self._save_checkpoint(state, incarnation_bump=1)
                last_save_time = time.time()
              state, train_step, eval_step, next_batch, train_chunk = \
                  self._reshape_topology(state, event["num_devices"],
                                         event["batch_size_per_device"],
                                         init_rng, steps_done=i,
                                         examples_done=images_processed)
              run_step = make_run_step(train_step, eval_step)
              images, labels = next_batch()
              cursor = 0
              reshape_events.append(event)
              # ONE elastic event line (generation, old -> new mesh,
              # resume step) -- the operator-facing record a preemption
              # story needs instead of silence -- mirrored into the
              # flight-recorder window when a telemetry session exists.
              new_mesh = "x".join(
                  str(int(s)) for s in self.mesh.devices.shape)
              event["mesh"] = f"{old_mesh}->{new_mesh}"
              log_fn("elastic event: generation %d: mesh %s -> %s, "
                     "resume step %d" % (generation, old_mesh, new_mesh,
                                         i))
              seam_args["mesh"] = event["mesh"]
            if tele is not None:
              tele.elastic_event(generation, old_mesh, new_mesh, i)
        pipe.note_aux_time(time.time() - aux_start)
    for done in pipe.flush():
      _handle(done)
    total_time = time.time() - loop_start
    if controller is not None and controller is not self.elastic_controller:
      controller.close()

    num_steps = len(step_train_times)
    average_wall_time = total_time / num_steps if num_steps else 0
    images_per_sec = images_processed / total_time
    log_fn("-" * 64)
    log_fn(log_util.format_total_line(images_per_sec))
    log_fn("-" * 64)
    if chunked and chunk_times:
      # Per-chunk timing rows: the dispatch-granularity wall clock the
      # amortized per-step numbers above are derived from (honest-timing
      # note in utils/pipeline.py).
      for line in observability.chunk_timing_rows(
          K, chunk_times, self.batch_size * max(self.num_workers, 1)):
        log_fn(line)
    if p.tfprof_file and dispatch_stats["call_times"]:
      # The host-axis line no per-op row of the static table carries,
      # from the run's OWN measurement: mean host time per timed
      # dispatch call against the mean wall of one dispatch.
      n_dispatches = len(dispatch_stats["call_times"])
      log_fn(observability.dispatch_overhead_line(
          sum(dispatch_stats["call_times"]) / n_dispatches,
          total_time / n_dispatches, K))
    # Input-pipeline line (next to the timing rows; the roofline table
    # covers the device side, this covers the host edge): packing
    # efficiency of the document packer plus the measured feed-stall
    # fraction proving (or disproving) that the DeviceFeeder prefetch
    # overlapped host work with the step (observability.py).
    feeder = getattr(self, "_feeder", None)
    feed_stats = feeder.stats() if feeder is not None else None
    packing_stats = (self._packed_stream.stats()
                     if getattr(self, "_packed_stream", None) is not None
                     else None)
    if feed_stats is not None and feed_stats["fetches"]:
      log_fn(observability.packing_feed_line(feed_stats, packing_stats))
    if bench_logger is not None:
      # Final throughput metrics (ref: _log_benchmark_run
      # average_examples_per_sec emission).
      bench_logger.log_metric("average_examples_per_sec", images_per_sec,
                              unit="examples/sec",
                              global_step=start_step + num_steps)
      if chunked and chunk_times:
        bench_logger.log_metric(
            "chunk_wall_time_mean",
            sum(chunk_times) / len(chunk_times), unit="seconds",
            global_step=start_step + num_steps,
            extras={"steps_per_dispatch": K,
                    "num_chunks": len(chunk_times)})
    if p.tfprof_file:
      # The measured half of the tfprof analog (ref: benchmark_cnn.py:
      # 1208-1228 ranks ops by MEASURED accelerator time from RunMetadata):
      # parse the step trace captured above back into per-op device time,
      # next to the static roofline .ops.txt. Without --trace_file this
      # run captured nothing: no scan (CWD's plugins/profile is not
      # ours to read), but a stale table a previous traced run left at
      # the profile path is still cleared. Best-effort throughout -- an
      # observability failure must never cost a finished run its final
      # checkpoint below.
      try:
        measured_path = p.tfprof_file + ".measured_ops.txt"
        if p.trace_file:
          table = observability.dump_measured_op_profile(
              trace_dir, measured_path, exclude=pre_trace_runs)
          if table is not None:
            for line in table.splitlines():
              log_fn(line)
        elif os.path.exists(measured_path):
          os.unlink(measured_path)
      except Exception as e:  # pragma: no cover - defensive tail
        log_fn(f"measured per-op profile failed (non-fatal): {e!r}")
    # Run-health summary (telemetry.py): the aggregate the one-line
    # BENCH JSON carries next to throughput (bench.py).
    health_summary = None
    if tele is not None:
      health_summary = tele.summary()
      if bench_logger is not None and \
          health_summary.get("max_grad_norm") is not None:
        bench_logger.log_metric(
            "max_grad_norm", health_summary["max_grad_norm"],
            global_step=start_step + num_steps,
            extras={"nonfinite_steps": health_summary["nonfinite_steps"],
                    "watchdog_stalls": health_summary["watchdog_stalls"]})
    # Final checkpoint (ref: benchmark_cnn.py:2374-2378).
    if p.train_dir:
      self._save_checkpoint(state)
    # Streaming latency percentiles (chunk wall / feed wait / checkpoint
    # save), one ``host stall:`` line per timed iteration that ran far
    # over the median, with the span it lay under (the step account),
    # + the compile ledger table -- AFTER the final save so the
    # printed sample counts match the stats fields below; whole lines
    # only (the scrape guard: nothing interleaves inside step lines).
    # The ledger persists to train_dir/compile_ledger.json keyed on
    # contract fingerprints (tracing.py; ROADMAP items 2 and 5).
    for line in self._trace.latency_lines():
      log_fn(line)
    step_account = self._trace.step_account()
    for line in self._trace.stall_lines(step_account):
      log_fn(line)
    for line in self._trace.ledger_lines():
      log_fn(line)
    if p.train_dir:
      self._trace.write_ledger(p.train_dir)
    if p.sync_on_finish:
      # all-ranks: --sync_on_finish is a launch-wide flag (same command
      # line on every kfrun worker), so every rank takes this branch or
      # none do -- the exit barrier always has full attendance.
      kungfu.run_barrier()
    # (ref stats dict: benchmark_cnn.py:2383-2391)
    stats = {
        # What the program states of itself (RunTrace.state: the step's
        # factor exchange, the model's blocks), each merged with what the
        # model's counters add to it over the timed steps; the keys below
        # are the harness's own.
        **self._stated_blocks(counter_rows),
        "num_workers": max(self.num_workers, 1),
        "num_steps": num_steps,
        "average_wall_time": average_wall_time,
        "images_per_sec": images_per_sec,
        "last_average_loss": loss,
        "stopped_early": stopped_early,
        "steps_per_dispatch": K,
        "num_chunks": len(chunk_times),
        # BENCH-trajectory fields: the first dispatch call's wall time
        # (blocks on trace+compile) and the mean host time per TIMED
        # dispatch call (the jit-call + transfer/RTT cost that
        # --steps_per_dispatch amortizes K-fold).
        "compile_s": dispatch_stats["compile_s"],
        "dispatch_overhead_s": (
            sum(dispatch_stats["call_times"]) /
            len(dispatch_stats["call_times"])
            if dispatch_stats["call_times"] else None),
        # Set when a cross-process resize needs the launcher to re-exec
        # this worker set at a new world size (kfrun restart leg).
        "restart_for_resize": restart_requested,
        "reshape_events": reshape_events,
        "grad_noise_scale": noise_ema.b_simple if noise_ema else None,
        # Training-health aggregate (None when --health_stats resolved
        # off): max grad norm, nonfinite_steps, loss_scale_final,
        # watchdog_stalls, anomaly_dumps (telemetry.py).
        "health": health_summary,
        # Mesh topology + per-device optimizer-state HBM: "8" on the
        # 1-D replica mesh, "BxM" on the named 2-D mesh; the bytes
        # field is what --shard_optimizer_state divides by ~n
        # (bench.py forwards both into its one-line JSON).
        "mesh_shape": "x".join(
            str(int(s)) for s in self.mesh.devices.shape),
        "opt_state_bytes_per_device": opt_state_bytes_per_device(
            state.opt_state),
        # Per-device parameter HBM, same leading-dim accounting:
        # ~|params| on the replicated/stacked layouts, ~|params|/n
        # under --shard_params -- the FSDP memory claim, next to the
        # optimizer one (bench.py forwards it).
        "param_bytes_per_device": opt_state_bytes_per_device(
            state.params),
        # Input-pipeline health: fraction of the consume window the
        # loop spent BLOCKED on the feed (None for the resident
        # synthetic batch, which has no feeder) and the packer's
        # measured efficiency (None unless --packed_sequences).
        "feed_stall_fraction": (feed_stats["feed_stall_fraction"]
                                if feed_stats else None),
        "packing_efficiency": (packing_stats["packing_efficiency"]
                               if packing_stats else None),
        # Run-trace aggregates (tracing.py): flat <key>_p50/p90/p99
        # seconds fields over chunk wall / feed wait / checkpoint save
        # (SLO-telemetry groundwork, ROADMAP item 2) and the per-shape
        # compile ledger (persistent-compile-cache groundwork, item 5).
        # bench.py forwards both into its one-line JSON.
        "latency_percentiles": self._trace.percentile_fields() or None,
        "compile_ledger": self._trace.compile_ledger(),
        # Always-on totals per span name (n, total_s, max_s, self_s) and
        # the compile-cache counters, for set-up and for the timed loop
        # (tracing.py span_totals): what the benchmark's set-up and
        # host-side metrics read, with or without a span file.
        "span_totals": self._trace.span_totals() or None,
        # Every timed iteration's host seconds by span, exclusive, and
        # the iterations that ran far over the median with the span
        # each lay under (tracing.py step_account): the ``host stall:``
        # lines above, for a reader of the stats.
        "step_account": step_account,
        # The scopes the step program names: a trace reader holds the
        # device operations' op_names to them (a warm compile cache can
        # hand over another version's metadata; benchmarks/spans.py).
        "step_scopes": list(train_step_lib.STEP_SCOPES),
        # The allocator's own account of the fullest device of the
        # mesh, read as the timed loop ends: live buffers at their peak,
        # what the runtime reserved for loaded programs at its peak, and
        # the limit. The two peaks may overlap, so their sum can pass
        # the limit (PERF.md section 7). None where the backend keeps no
        # such account (CPU).
        "device_memory": _fullest_device_memory(self.mesh.devices.flat),
        # Tuned-config provenance (--autotuned_config,
        # analysis/autotune.py): table path + the matched entry's base
        # fingerprint (entry None when the table had no row for this
        # config); None when the flag is unset. bench.py forwards it
        # into its one-line JSON and the run-store snapshot, so
        # --check-regression histories stay attributable.
        "tuned_config": self._tuned_provenance,
        "run_id": self._trace.run_id or None,
        "state": state,
    }
    # Final registry publication (the endpoint serves this snapshot
    # until teardown) + the run record: one schema-versioned JSONL line
    # per run in the cross-run store (metrics.py RunStore; rank 0 only
    # -- the ranks share one store and the record describes the job).
    metrics_lib.publish_stats(metrics_lib.active(), stats)
    if p.run_store_dir and cluster_lib.process_rank() == 0:
      try:
        from kf_benchmarks_tpu.analysis import baseline as baseline_lib
        record = metrics_lib.run_record(
            metric="images_per_sec", value=images_per_sec,
            unit="images/sec",
            fingerprint=baseline_lib.config_fingerprint_key(
                p._asdict(), "train"),
            run_id=self._trace.run_id,
            platform=self.mesh.devices.flat[0].platform,
            git_rev=metrics_lib.git_revision(),
            jax_version=jax.__version__,
            snapshot=metrics_lib.flatten_stats(stats))
        store = metrics_lib.RunStore(p.run_store_dir)
        store.append(record)
        log_fn("run record appended: %s" % store.path)
      except (OSError, ValueError) as e:
        log_fn(f"run record append failed (non-fatal): {e}")
    return stats

  def _eval_once(self, state, eval_step, images, labels,
                 next_batch=None) -> Dict[str, Any]:
    """One pass over the eval batches (ref: benchmark_cnn.py:1864-1923)."""
    p = self.params
    num_eval = p.num_eval_batches or self._num_eval_batches_from_epochs() \
        or self.num_batches
    top1_sum = top5_sum = 0.0
    start = time.time()
    # Same lag-2 fetch pipeline as the train loop (utils/pipeline.py).
    pipe = pipeline_lib.MetricsPipeline(lag=2)
    accs = []
    for i in range(num_eval):
      acc = eval_step(state, images, labels)
      for done in pipe.push(i + 1, acc):
        accs.append(done.metrics)
      if next_batch is not None and i + 1 < num_eval:
        try:
          images, labels = next_batch()
        except StopIteration:
          # Real-data validation streams are one-pass (data/preprocessing
          # _record_stream); stopping at exhaustion bounds eval by
          # min(num_eval_batches, one epoch), as the reference does.
          break
    for done in pipe.flush():
      accs.append(done.metrics)
    for acc in accs:
      top1_sum += float(acc["top_1_accuracy"])
      top5_sum += float(acc["top_5_accuracy"])
    elapsed = time.time() - start
    evaluated = max(len(accs), 1)
    top1, top5 = top1_sum / evaluated, top5_sum / evaluated
    log_fn("Accuracy @ 1 = %.4f Accuracy @ 5 = %.4f [%d examples]" %
           (top1, top5, evaluated * self.batch_size))
    eval_ips = evaluated * self.batch_size / max(elapsed, 1e-9)
    if p.eval and p.eval_dir:
      # Eval summary stream (ref: --eval_dir FileWriter,
      # benchmark_cnn.py:585-586, :1770-1772).
      observability.SummaryWriter(p.eval_dir, 1).write_scalars(
          int(state.step), {"eval_top_1_accuracy": top1,
                            "eval_top_5_accuracy": top5,
                            "eval_images_per_sec": eval_ips})
    if p.benchmark_log_dir:
      # Eval-result emission (ref: benchmark_cnn.py:1915-1922). The
      # state's step is the restored checkpoint's global step, so
      # successive poll-loop evals stay distinguishable in metric.log.
      gs = int(state.step)
      logger = observability.BenchmarkLogger(p.benchmark_log_dir)
      logger.log_metric("eval_top_1_accuracy", top1, global_step=gs)
      logger.log_metric("eval_top_5_accuracy", top5, global_step=gs)
      logger.log_metric("eval_images_per_sec", eval_ips,
                        unit="examples/sec", global_step=gs)
    return {"top_1_accuracy": top1, "top_5_accuracy": top5,
            "eval_images_per_sec": eval_ips}

  def _stated_blocks(self, counter_rows) -> Dict[str, Any]:
    blocks = self._trace.statics()
    counted = (self.model.counter_blocks(np.stack(counter_rows))
               if counter_rows else {})
    for key, counts in counted.items():
      blocks[key] = dict(blocks.get(key) or {}, **counts)
    return blocks

  def _run_eval(self) -> Dict[str, Any]:
    """Evaluation driver (ref: benchmark_cnn.py:1757-1794).

    With a train_dir: poll for new checkpoints every eval_interval_secs,
    evaluating each; terminate after a staleness window (10 polls with no
    new checkpoint) -- the reference loops until killed and its own TODO
    admits the missing staleness abort (ref :1774); bounding it is a
    deliberate improvement. Without a train_dir: single-shot eval of a
    fresh-init model on synthetic data.
    """
    p = self.params
    init_state, train_step, eval_step, broadcast_init, _ = self._build()
    rng = jax.random.PRNGKey(p.tf_random_seed or 0)
    data_rng, init_rng = jax.random.split(rng)
    shape = self._model_image_shape()
    state = init_state(
        init_rng, jnp.zeros((self.batch_size_per_device,) + shape,
                            jnp.float32))
    # Detection (and other accumulate-then-postprocess) models own their
    # real-data eval: per-image prediction accumulation + mAP has no
    # scalar top-k loop to share (ref: ssd postprocess, ssd_model.py:481-539).
    custom_eval = getattr(self.model, "evaluate_real_data", None)
    if custom_eval is not None and not self.dataset.use_synthetic_gpu_inputs():
      if p.train_dir:
        try:
          snapshot, _, _ = checkpoint.load_latest_checkpoint(p.train_dir)
          state = checkpoint.restore_state(state, snapshot,
                                           restore_opt_state=False)
        except checkpoint.CheckpointNotFoundException:
          pass
      variables = {"params": jax.tree.map(lambda x: x[0], state.params)}
      bs = jax.tree.map(lambda x: x[0], state.batch_stats)
      if bs:
        variables["batch_stats"] = bs
      return custom_eval(variables, p, self.dataset)
    if not p.train_dir:
      return self._eval_pass(state, eval_step, data_rng)
    return self._eval_poll_loop(state, eval_step, data_rng)

  def _eval_pass(self, state, eval_step, data_rng) -> Dict[str, Any]:
    """One full eval over a FRESH validation stream, so every checkpoint
    is scored on the same data (the reference re-runs its input pipeline
    per eval, ref: benchmark_cnn.py:1829-1862 _initialize_eval_graph)."""
    next_batch, stop_input = self._input_iterator(data_rng, "validation")
    try:
      try:
        images, labels = next_batch()
      except StopIteration:
        log_fn("Validation stream yielded no batches (fewer examples "
               "than the global batch size?)")
        return {"top_1_accuracy": 0.0, "top_5_accuracy": 0.0,
                "eval_images_per_sec": 0.0}
      real_data = not self.dataset.use_synthetic_gpu_inputs()
      return self._eval_once(state, eval_step, images, labels,
                             next_batch if real_data else None)
    finally:
      stop_input()

  def _eval_poll_loop(self, state, eval_step, data_rng):
    p = self.params
    last_evaluated_step = -1
    results = None
    stale_polls = 0
    max_stale_polls = 10
    while True:
      try:
        path, ckpt_step = checkpoint.latest_checkpoint(p.train_dir)
      except checkpoint.CheckpointNotFoundException:
        # Missing checkpoints are tolerated: wait (ref :1784-1785), but a
        # never-appearing checkpoint still counts toward the staleness
        # bound so the poll loop cannot spin forever.
        if not p.eval_interval_secs:
          raise
        stale_polls += 1
        if stale_polls >= max_stale_polls:
          return results
        time.sleep(p.eval_interval_secs)
        continue
      if ckpt_step > last_evaluated_step:
        try:
          # Parse-once + torn-file skip; the resolve above stays cheap
          # (no parse) for the common nothing-new poll.
          snapshot, path, ckpt_step = checkpoint.load_latest_checkpoint(
              p.train_dir)
        except checkpoint.CheckpointNotFoundException:
          snapshot = None
        if snapshot is None or ckpt_step <= last_evaluated_step:
          # The newest checkpoint was pruned between resolution and
          # read, or is torn with nothing newer behind it: treat as
          # not-yet-available and re-poll.
          stale_polls += 1
          if stale_polls >= max_stale_polls:
            return results
          time.sleep(p.eval_interval_secs or 1)
          continue
        # Model variables only: the eval process's optimizer flags need
        # not match the trainer's (the eval graph has no slots to fill).
        state = checkpoint.restore_state(state, snapshot,
                                         restore_opt_state=False)
        log_fn(f"Evaluating checkpoint at global step {ckpt_step}")
        results = self._eval_pass(state, eval_step, data_rng)
        results["global_step"] = ckpt_step
        last_evaluated_step = ckpt_step
        stale_polls = 0
      else:
        stale_polls += 1
      if not p.eval_interval_secs or stale_polls >= max_stale_polls:
        return results
      time.sleep(p.eval_interval_secs)
