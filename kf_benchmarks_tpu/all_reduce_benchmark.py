"""Standalone all-reduce microbenchmark CLI.

TPU-native analog of the reference's all-reduce microbenchmark
(ref: scripts/tf_cnn_benchmarks/all_reduce_benchmark.py:60-180): build
model-shaped random gradient tensors, chain ``iters_per_step`` all-reduce
iterations inside ONE compiled SPMD program (data-dependency chaining
replaces the reference's control-dependency fencing,
all_reduce_benchmark.py:89-151), run timed steps, and report the average
time per all-reduce.

Where the reference times ``sess.run`` of a chained graph, we time calls
of a jitted ``shard_map`` program over the replica mesh; the spec-driven
algorithm selection (psum / reduce-scatter+all-gather / hierarchical)
comes from ops/allreduce.py, sharing the reference's spec grammar.

Run: python -m kf_benchmarks_tpu.all_reduce_benchmark --model=resnet50 \
         --num_batches=10 --all_reduce_spec=psum

``--sweep`` replaces the single-config run with the PERF.md round-5
n x spec x size step-time table from ONE command (previously a hand-run
procedure): every (device count, algorithm, packed-vector size) cell is
timed the same way -- chained iterations inside one compiled program,
drain()-bounded windows -- and the result prints as a markdown table
plus one JSON line.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu import flags
from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.ops import allreduce
from kf_benchmarks_tpu.parallel import mesh as mesh_lib
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS
from kf_benchmarks_tpu.utils import sync
from kf_benchmarks_tpu.utils import log as log_util

if "iters_per_step" not in flags.param_specs:
  flags.DEFINE_integer(
      "iters_per_step", 5,
      "Number of chained all-reduce iterations inside one compiled step "
      "(ref: all_reduce_benchmark.py flag of the same name).")
if "sweep" not in flags.param_specs:
  flags.DEFINE_boolean(
      "sweep", False,
      "Emit the PERF round-5 n x spec x size step-time table (markdown "
      "+ one JSON line) instead of the single-config model-shaped run: "
      "device counts are powers of two up to --num_devices, algorithms "
      "from --sweep_specs, packed-vector sizes from --sweep_sizes.")
  flags.DEFINE_string(
      "sweep_specs", "psum,rsag,hier,reduce_scatter,all_gather",
      "Comma-separated algorithms for --sweep (spec grammar "
      "alg[#shards]; reference aliases accepted). The primitive names "
      "'reduce_scatter' and 'all_gather' time the raw collective "
      "instead of an all-reduce composition -- the sharded optimizer "
      "path's exchange (--shard_optimizer_state meets gradients in a "
      "reduce-scatter and returns params by all-gather), so its "
      "collective mix A/Bs against the all-reduce rows of the same "
      "n x size cell.")
  flags.DEFINE_string(
      "sweep_sizes", "256k,4m",
      "Comma-separated packed-vector byte sizes for --sweep "
      "(spec-grammar limits: <int>[kKmM]).")


def get_var_shapes(model, nclass: int = 1001) -> List[Tuple[int, ...]]:
  """Return the model's trainable-variable shapes (ref:
  all_reduce_benchmark.py:60-66 builds the graph just to read var shapes;
  here we init the flax module and read the param tree)."""
  module = model.make_module(nclass=nclass, phase_train=True,
                             data_format="NHWC")
  size = getattr(model, "image_size", 224)
  images = jnp.zeros((1, size, size, 3), jnp.float32)
  rng = jax.random.PRNGKey(0)
  variables = jax.eval_shape(
      lambda: module.init({"params": rng, "dropout": rng}, images))
  leaves = jax.tree_util.tree_leaves(variables.get("params", variables))
  return [tuple(l.shape) for l in leaves]


def build_all_reduce_step(shapes: Sequence[Tuple[int, ...]], mesh,
                          iters_per_step: int, planner=None):
  """Compile one step: ``iters_per_step`` chained all-reduces of the
  tensor list (ref: build_all_reduce_iterations,
  all_reduce_benchmark.py:89-151). Chaining by data dependency: the
  reduced output of iteration i is the input of iteration i+1, so XLA
  cannot elide or overlap the iterations away."""

  def body(*tensors):
    tensors = list(tensors)
    for i in range(iters_per_step):
      if planner is not None:
        tensors = planner.reduce(tensors, REPLICA_AXIS)
      else:
        tensors = [lax.pmean(t, REPLICA_AXIS) for t in tensors]
      # Perturb between iterations so successive reductions are not
      # fixpoints (pmean of an already-averaged value); mirrors the
      # reference reusing live gradient values per iteration.
      if i + 1 < iters_per_step:
        tensors = [t + jnp.asarray(1e-6, t.dtype) for t in tensors]
    return tuple(tensors)

  specs = tuple(P(REPLICA_AXIS) for _ in shapes)
  fn = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs)
  jitted = jax.jit(lambda tensors: fn(*tensors))
  return jitted


def run_benchmark(params) -> Dict[str, float]:
  """Build + time the all-reduce program; returns timing stats
  (ref: all_reduce_benchmark.py:155-180 run_benchmark)."""
  from kf_benchmarks_tpu.data import datasets
  model = model_config.get_model_config(params.model, params.data_name)
  dataset = datasets.create_dataset(params.data_dir, params.data_name)
  shapes = get_var_shapes(model, nclass=dataset.num_classes)
  devices = mesh_lib.get_devices(params.device, params.num_devices or None)
  mesh = mesh_lib.build_mesh(devices=devices)
  n = mesh.devices.size
  planner = allreduce.build_planner(params)
  iters = getattr(params, "iters_per_step", 5)
  dtype = jnp.bfloat16 if params.use_fp16 else jnp.float32

  step = build_all_reduce_step(shapes, mesh, iters, planner)

  rng = np.random.RandomState(0)
  sharding = NamedSharding(mesh, P(REPLICA_AXIS))
  tensors = [
      jax.device_put(
          rng.normal(size=(n,) + s).astype(dtype), sharding)
      for s in shapes]

  num_bytes = sum(int(np.prod(s)) for s in shapes) * jnp.dtype(dtype).itemsize
  log_util.log_fn(
      f"All-reduce benchmark: {len(shapes)} tensors, "
      f"{num_bytes / 1e6:.2f} MB/replica, {n} replicas, "
      f"{iters} iters/step")

  num_steps = params.num_batches or 10
  warmup = params.num_warmup_batches
  if warmup is None:
    warmup = 2

  # Both regions end with a real value fetch of the smallest output
  # tensor: fetching the model-sized tensors themselves would time the
  # host transfer instead of the all-reduce.
  for _ in range(max(warmup, 1)):  # includes compile
    out = step(tensors)
  sync.drain(out)

  start = time.monotonic()
  for _ in range(num_steps):
    out = step(tensors)
  sync.drain(out)
  elapsed = time.monotonic() - start

  avg_step = elapsed / num_steps
  avg_all_reduce = avg_step / iters
  log_util.log_fn(f"Average time per step: {avg_step:.6f} sec")
  log_util.log_fn(f"Average all-reduce time: {avg_all_reduce:.6f} sec")
  return {
      "average_time_per_step": avg_step,
      "average_all_reduce_time": avg_all_reduce,
      "num_tensors": len(shapes),
      "bytes_per_replica": num_bytes,
  }


def sweep_device_counts(total: int) -> List[int]:
  """Powers of two up to the available device count (the round-5 table's
  n axis; a non-power-of-two total contributes itself as the last row)."""
  ns, n = [], 2
  while n <= total:
    ns.append(n)
    n *= 2
  if not ns or ns[-1] != total:
    ns.append(total)
  return [n for n in ns if n <= total]


def build_vector_step(mesh, spec_tuple, iters_per_step: int):
  """One compiled step: ``iters_per_step`` chained reductions of a
  single packed vector (the gradient-vector shape every packed path
  reduces), chained by data dependency like build_all_reduce_step."""

  def body(vec):
    vec = vec[0]  # (1, elems) local shard -> the flat packed vector
    for i in range(iters_per_step):
      vec = allreduce._reduce_packed(vec, spec_tuple, REPLICA_AXIS)
      if i + 1 < iters_per_step:
        vec = vec + jnp.asarray(1e-6, vec.dtype)
    return vec[None]

  fn = jax.shard_map(body, mesh=mesh, in_specs=P(REPLICA_AXIS),
                     out_specs=P(REPLICA_AXIS))
  return jax.jit(fn)


# The primitive-collective rows of --sweep: the sharded optimizer
# path's exchange (ops/sharded.py scatter_mean / gather_tree) timed in
# isolation, beside the all-reduce compositions of the same cell.
PRIMITIVE_COLLECTIVES = ("reduce_scatter", "all_gather")


def build_primitive_step(mesh, collective: str, iters_per_step: int):
  """One compiled step chaining ``iters_per_step`` raw reduce-scatters
  (or all-gathers) of the packed vector. The collective's output shape
  differs from its input (that is the point of the primitive), so the
  chain dependency is a SCALAR read of the output folded back into the
  next iteration's input -- one elementwise op, the same
  cannot-elide/cannot-overlap role as build_vector_step's perturbation.
  Wire bytes per iteration are (n-1)/n x the nominal cell size for
  both primitives, directly comparable to the all-reduce rows."""
  if collective not in PRIMITIVE_COLLECTIVES:
    raise ValueError(f"unknown primitive collective {collective!r}")

  def body(vec):
    vec = vec[0]  # (1, elems) local shard -> the flat packed vector
    n = lax.axis_size(REPLICA_AXIS)
    for _ in range(iters_per_step):
      if collective == "reduce_scatter":
        # Tiled scatter needs a multiple of n; zero-pad like the real
        # consumers do (ops/sharded.py _pad_flat, allreduce.py _rsag)
        # -- non-power-of-two meshes and odd --sweep_sizes otherwise
        # crash the default sweep.
        pad = (-vec.shape[0]) % n
        out = lax.psum_scatter(
            jnp.pad(vec, (0, pad)) if pad else vec,
            REPLICA_AXIS, tiled=True)
      else:
        # Gather of a 1/n shard re-assembles the full nominal size --
        # the param leg of the sharded exchange.
        out = lax.all_gather(vec[:vec.shape[0] // n], REPLICA_AXIS,
                             tiled=True)
      vec = vec + out.reshape(-1)[0] * jnp.asarray(1e-6, vec.dtype)
    return vec[None]

  fn = jax.shard_map(body, mesh=mesh, in_specs=P(REPLICA_AXIS),
                     out_specs=P(REPLICA_AXIS))
  return jax.jit(fn)


def run_sweep(params) -> List[Dict[str, float]]:
  """The round-5 n x spec x size table from one command (PERF.md
  "All-reduce on a 4 MiB gradient vector" was hand-run per cell).

  Per-all-reduce time is measured DIFFERENTIALLY: each cell times two
  compiled programs chaining k and 2k reductions and differences them,
  so per-dispatch host cost cancels -- it would otherwise swamp every
  microsecond-scale cell. step_ms stays the raw k-iteration dispatch
  wall for context.

  Markdown rows via the logger; ONE JSON line on stdout so a harness
  can scrape the whole table like bench.py's result line."""
  devices = mesh_lib.get_devices(params.device, params.num_devices or None)
  iters = getattr(params, "iters_per_step", 5)
  num_steps = params.num_batches or 10
  warmup = params.num_warmup_batches
  warmup = 2 if warmup is None else max(warmup, 1)
  sizes = [allreduce._parse_limit(s.strip())
           for s in params.sweep_sizes.split(",") if s.strip()]
  spec_names = [s.strip() for s in params.sweep_specs.split(",")
                if s.strip()]
  dtype = jnp.bfloat16 if params.use_fp16 else jnp.float32
  itemsize = jnp.dtype(dtype).itemsize
  rows = []
  log_util.log_fn(f"All-reduce sweep: n x spec x size over "
                  f"{len(devices)} available devices, {iters} "
                  f"iters/step, {num_steps} timed steps")
  log_util.log_fn("| n | spec | size | step ms | per-all-reduce ms |")
  log_util.log_fn("|---|---|---|---|---|")
  rng = np.random.RandomState(0)

  def timed(step, vec):
    for _ in range(warmup):  # includes compile
      out = step(vec)
    sync.drain(out)
    start = time.monotonic()
    for _ in range(num_steps):
      out = step(out)
    sync.drain(out)
    return (time.monotonic() - start) / num_steps

  for n in sweep_device_counts(len(devices)):
    mesh = mesh_lib.build_mesh(devices=devices[:n])
    for spec_name in spec_names:
      if spec_name in PRIMITIVE_COLLECTIVES:
        step_k = build_primitive_step(mesh, spec_name, iters)
        step_2k = build_primitive_step(mesh, spec_name, 2 * iters)
      else:
        tup = allreduce._parse_alg(spec_name)
        if tup.alg == "hier":
          tup = tup._replace(shards=max(tup.shards, 2))
        step_k = build_vector_step(mesh, tup, iters)
        step_2k = build_vector_step(mesh, tup, 2 * iters)
      for size in sizes:
        elems = max(size // itemsize, n)
        sharding = NamedSharding(mesh, P(REPLICA_AXIS))
        vec = jax.device_put(
            rng.normal(size=(n, elems)).astype(dtype), sharding)
        step_s = timed(step_k, vec)
        step2_s = timed(step_2k, vec)
        # Differencing the k- and 2k-iteration programs cancels the
        # per-dispatch host cost; clamp at 0 (pure noise floor
        # on cells faster than the timer jitter).
        per_reduce_s = max(step2_s - step_s, 0.0) / iters
        rows.append({"n": n, "spec": spec_name, "bytes": int(size),
                     "step_ms": round(step_s * 1e3, 3),
                     "all_reduce_ms": round(per_reduce_s * 1e3, 3)})
        log_util.log_fn(
            "| %d | %s | %s | %.3f | %.3f |" % (
                n, spec_name, _fmt_bytes(size), step_s * 1e3,
                per_reduce_s * 1e3))
  print(json.dumps({"metric": "all_reduce_sweep",
                    "iters_per_step": iters, "num_steps": num_steps,
                    "dtype": jnp.dtype(dtype).name, "rows": rows}),
        flush=True)
  return rows


def _fmt_bytes(size: int) -> str:
  if size % (1024 * 1024) == 0:
    return f"{size // (1024 * 1024)}m"
  if size % 1024 == 0:
    return f"{size // 1024}k"
  return str(size)


def main(positional_arguments):
  from absl import app
  from kf_benchmarks_tpu import params as params_lib
  if len(positional_arguments) > 1:
    raise app.UsageError(
        "Received unknown positional arguments: %s" % positional_arguments[1:])
  from kf_benchmarks_tpu import benchmark
  params = params_lib.make_params_from_flags()
  params = benchmark.setup(params)
  if getattr(params, "sweep", False):
    run_sweep(params)
  else:
    run_benchmark(params)


def run_main():
  from absl import app
  from kf_benchmarks_tpu import params as params_lib
  flags.define_flags(aliases=params_lib.ALIASES)
  app.run(main)


if __name__ == "__main__":
  run_main()
