"""--shard_optimizer_state: ZeRO/FSDP sharded optimizer state on the
named 2-D ('batch', 'model') mesh (the TPU analog of the reference's
central variable placement, ref: variable_mgr.py:201-243; SURVEY 5.8).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: 2-D mesh construction + GSPMD spec helpers
    (parallel/mesh.py), the --shard_optimizer_state validation matrix,
    and the scatter/slice/gather layout laws of ops/sharded.py on the
    8-device mesh -- including the bit-identity of the scattered batch
    mean against the pmean it replaces.
  * numerical equivalence: per-step losses of the sharded path are
    BIT-IDENTICAL to the replicated path at f32 -- plain, composed with
    --steps_per_dispatch=8 and --num_grad_accum=2, under momentum and
    adam, and on the 4x2 mesh against a 4-replica run of the same
    global batch.
  * program: the compiled sharded step carries reduce-scatter +
    all-gather and NO full-gradient all-reduce (the train_step program
    is golden-pinned in tests/golden_contracts/sharded_*.json via
    test_program_audit.py; here the --steps_per_dispatch chunk program
    is pinned too, proving the scan carry stays sharded).
  * checkpoint: the sharded layout round-trips through save/resume,
    and a layout mismatch is rejected instead of silently broadcast.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu import benchmark, checkpoint
from kf_benchmarks_tpu import params as params_lib, validation
from kf_benchmarks_tpu.ops import sharded as sharded_lib
from kf_benchmarks_tpu.parallel import mesh as mesh_lib
from kf_benchmarks_tpu.utils import log as log_util

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: [\d.]+ \+/- [\d.]+ \(jitter = [\d.]+\)\t(.*)$")


def _run_and_scrape(**overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=8, num_warmup_batches=0,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=8, optimizer="momentum")
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def _loss_columns(logs):
  """(step, loss-and-metric columns) pairs -- everything on the step
  line EXCEPT the timing columns, which legitimately differ."""
  return [(m.group(1), m.group(2)) for l in logs
          if (m := STEP_RE.match(l))]


def _assert_equivalent(kw_replicated, kw_sharded):
  logs_a, stats_a = _run_and_scrape(**kw_replicated)
  logs_b, stats_b = _run_and_scrape(**kw_sharded)
  cols_a, cols_b = _loss_columns(logs_a), _loss_columns(logs_b)
  assert cols_a, "no step lines scraped from the replicated run"
  assert cols_a == cols_b
  # Full f32 precision, not just the printed columns.
  assert stats_a["last_average_loss"] == stats_b["last_average_loss"]
  return stats_a, stats_b


# -- pure-unit: mesh construction + spec helpers ------------------------------

def test_build_mesh_2d_axes_and_order():
  mesh = mesh_lib.build_mesh_2d(4, 2, "cpu")
  assert mesh.axis_names == (mesh_lib.BATCH_AXIS, mesh_lib.MODEL_AXIS)
  assert mesh.devices.shape == (4, 2)
  assert mesh_lib.data_axis(mesh) == "batch"
  assert mesh_lib.num_data_replicas(mesh) == 4
  assert mesh_lib.state_axes(mesh) == ("batch", "model")
  # Row-major device order: (b, m) has flat shard index b * M + m.
  flat = [d.id for d in mesh.devices.reshape(-1)]
  assert flat == sorted(flat)
  one_d = mesh_lib.build_mesh(8, "cpu")
  assert mesh_lib.data_axis(one_d) == "replica"
  assert mesh_lib.num_data_replicas(one_d) == 8


def test_build_mesh_2d_rejects_bad_shapes():
  with pytest.raises(ValueError, match="must be positive"):
    mesh_lib.build_mesh_2d(0, 2, "cpu")
  with pytest.raises(ValueError, match="needs"):
    mesh_lib.build_mesh_2d(4, 2, "cpu",
                           devices=jax.devices("cpu")[:4])


def test_leaf_spec_size_thresholded_rule():
  mesh = mesh_lib.build_mesh_2d(4, 2, "cpu")
  # Big enough and divisible dim 0: sharded over BOTH axes.
  assert (mesh_lib.leaf_spec((8, 256), mesh)
          == P(("batch", "model")))
  # Under the element threshold: replicated.
  assert mesh_lib.leaf_spec((8, 8), mesh) == P()
  # Dim 0 not divisible by the mesh: replicated.
  assert mesh_lib.leaf_spec((6, 4096), mesh) == P()
  # Scalars: replicated.
  assert mesh_lib.leaf_spec((), mesh) == P()


def test_tree_shardings_applies_leaf_rule():
  mesh = mesh_lib.build_mesh_2d(4, 2, "cpu")
  tree = {"big": jnp.zeros((8, 256)), "small": jnp.zeros((4,))}
  sh = mesh_lib.tree_shardings(mesh, tree)
  assert sh["big"].spec == P(("batch", "model"))
  assert sh["small"].spec == P()


# -- pure-unit: validation matrix ---------------------------------------------

def test_parse_mesh_shape():
  assert validation.parse_mesh_shape("8x1") == (8, 1)
  assert validation.parse_mesh_shape("4X2") == (4, 2)
  for bad in ("8", "0x8", "2x-1", "axb", "2x2x2"):
    with pytest.raises(validation.ParamError, match="mesh_shape"):
      validation.parse_mesh_shape(bad)


def test_mesh_shape_must_cover_num_devices():
  with pytest.raises(validation.ParamError, match="cover exactly"):
    validation.validate_cross_flags(params_lib.make_params(
        mesh_shape="4x2", num_devices=4, shard_optimizer_state=True))


def test_model_axis_requires_sharded_state():
  with pytest.raises(validation.ParamError, match="model axis"):
    validation.validate_cross_flags(params_lib.make_params(
        mesh_shape="4x2", num_devices=8))
  # B x 1 without sharding is legal (a named 1-wide model axis).
  validation.validate_cross_flags(params_lib.make_params(
      mesh_shape="8x1", num_devices=8))


@pytest.mark.parametrize("kw,match", [
    (dict(eval=True), "training only"),
    (dict(forward_only=True), "training only"),
    (dict(variable_update="independent"), "replicated or parameter_server"),
    (dict(variable_update="kungfu"), "replicated or parameter_server"),
    (dict(variable_update="distributed_all_reduce"),
     "replicated or parameter_server"),
    (dict(variable_update="parameter_server", cross_replica_sync=False),
     "async"),
    (dict(optimizer="lars"), "lars"),
    (dict(staged_vars=True, variable_update="parameter_server"),
     "staged_vars"),
    (dict(variable_consistency="relaxed"), "relaxed"),
    (dict(adaptive_batch_size=True), "adaptive_batch_size"),
    (dict(track_grad_noise_scale=True), "noise-scale"),
    (dict(all_reduce_spec="rsag"), "all_reduce_spec"),
    (dict(gradient_repacking=2), "gradient_repacking"),
    (dict(agg_small_grads_max_bytes=1024), "agg_small_grads_max_bytes"),
    (dict(hierarchical_copy=True), "hierarchical_copy"),
    (dict(health_stats=True), "health_stats"),
    (dict(num_processes=2), "single-process"),
])
def test_sharded_state_exclusion_matrix(kw, match):
  with pytest.raises(validation.ParamError, match=match):
    validation.validate_cross_flags(params_lib.make_params(
        shard_optimizer_state=True, **kw))


def test_sharded_state_valid_combinations_pass():
  for kw in [dict(),
             dict(mesh_shape="4x2"),
             dict(steps_per_dispatch=4),
             dict(num_grad_accum=2, batch_size=4),
             dict(optimizer="adam"),
             dict(variable_update="parameter_server"),
             # Round 12: the cross-mesh rescale landed, so elastic
             # composes (tests/test_elastic_rescale.py pins the resume).
             dict(elastic=True),
             dict(use_fp16=True, fp16_enable_auto_loss_scale=True)]:
    validation.validate_cross_flags(params_lib.make_params(
        shard_optimizer_state=True, num_devices=8, **kw))


def test_health_stats_auto_resolves_off_with_note(tmp_path):
  from kf_benchmarks_tpu import telemetry
  from kf_benchmarks_tpu.parallel import strategies
  p = params_lib.make_params(shard_optimizer_state=True,
                             train_dir=str(tmp_path / "t"))
  on, note = telemetry.resolve_health_stats(p, strategies.get_strategy(p))
  assert on is False and "shard_optimizer_state" in note
  # Sink-less: off quietly.
  p2 = params_lib.make_params(shard_optimizer_state=True)
  on2, note2 = telemetry.resolve_health_stats(
      p2, strategies.get_strategy(p2))
  assert on2 is False and note2 is None


# -- pure-unit: ops/sharded layout laws on the 8-device mesh ------------------

def _shard_map_2d(fn, mesh, in_specs, out_specs):
  return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False))


def test_stacked_shards_layout():
  tree = {"w": jnp.arange(10, dtype=jnp.float32),
          "b": jnp.arange(4, dtype=jnp.float32)}
  stacked = sharded_lib.stacked_shards(tree, 4)
  assert stacked["w"].shape == (4, 3)  # ceil(10/4) = 3, zero-padded
  np.testing.assert_array_equal(
      np.asarray(stacked["w"]).reshape(-1)[:10], np.arange(10))
  assert np.all(np.asarray(stacked["w"]).reshape(-1)[10:] == 0)
  assert stacked["b"].shape == (4, 1)


@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4)])
def test_local_slice_gather_roundtrip(shape):
  """local_shards -> gather_tree is the identity for replica-identical
  trees: the row-major block order of the combined all-gather matches
  the flat shard indexing."""
  mesh = mesh_lib.build_mesh_2d(*shape, "cpu")
  tree = {"w": jnp.arange(37, dtype=jnp.float32) * 0.5,
          "s": jnp.float32(3.25)}

  def body(t):
    shards = sharded_lib.local_shards(t)
    return sharded_lib.gather_tree(shards, t)

  out = _shard_map_2d(body, mesh, in_specs=P(), out_specs=P())(tree)
  jax.tree.map(np.testing.assert_array_equal, out, tree)


@pytest.mark.parametrize("shape", [(8, 1), (4, 2)])
def test_scatter_mean_bit_identical_to_pmean(shape):
  """gather(scatter_mean(g)) == pmean(g, batch) BIT-identically: the
  scatter meets the same B distinct contributions in the same group
  order as the all-reduce (model-axis peers hold identical grads by
  construction, so their sub-slice is free)."""
  nb, nm = shape
  mesh = mesh_lib.build_mesh_2d(nb, nm, "cpu")
  # Per-BATCH-group gradients, identical across the model axis -- the
  # invariant train_step.py guarantees by folding the same replica id.
  rng = np.random.RandomState(0)
  per_batch = jnp.asarray(rng.randn(nb, 1237).astype(np.float32))

  def body(g_all):
    g = g_all[lax.axis_index(mesh_lib.BATCH_AXIS)]
    want = lax.pmean(g, mesh_lib.BATCH_AXIS)
    got = sharded_lib.gather_tree(
        sharded_lib.scatter_mean({"g": g}), {"g": g})["g"]
    return want, got

  want, got = _shard_map_2d(body, mesh, in_specs=P(),
                            out_specs=P())(per_batch)
  np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# -- numerical equivalence: sharded == replicated, bit-identical --------------

def test_equivalence_plain():
  stats_rep, stats_sh = _assert_equivalent(
      dict(), dict(shard_optimizer_state=True))
  # The ZeRO memory claim: per-device optimizer state drops ~n-fold.
  assert (stats_sh["opt_state_bytes_per_device"] * 7
          < stats_rep["opt_state_bytes_per_device"])
  assert stats_sh["mesh_shape"] == "8x1"
  assert stats_rep["mesh_shape"] == "8"


def test_equivalence_4x2_model_axis_vs_4_replicas():
  """A real model axis (M=2): same global batch as 4 replicas, same
  losses bit-identically -- model peers recompute the same shard and
  the scattered mean still meets B=4 contributions in group order."""
  _assert_equivalent(
      dict(num_devices=4),
      dict(num_devices=8, shard_optimizer_state=True, mesh_shape="4x2"))


@pytest.mark.slow
def test_equivalence_steps_per_dispatch():
  """The K-step scan carry stays sharded: K=8 chunked dispatch, same
  per-step losses as the replicated chunked run."""
  _assert_equivalent(
      dict(steps_per_dispatch=8),
      dict(steps_per_dispatch=8, shard_optimizer_state=True))


@pytest.mark.slow
def test_equivalence_grad_accum():
  _assert_equivalent(
      dict(num_grad_accum=2),
      dict(num_grad_accum=2, shard_optimizer_state=True))


@pytest.mark.slow
def test_equivalence_adam_and_composed():
  """Stateful elementwise optimizer (adam: count + two moments) and the
  full K x M composition in one: the shard apply is exact for every
  admitted optimizer, not just momentum."""
  _assert_equivalent(
      dict(optimizer="adam", steps_per_dispatch=4, num_grad_accum=2),
      dict(optimizer="adam", steps_per_dispatch=4, num_grad_accum=2,
           shard_optimizer_state=True))


# -- program: the chunk program's carry stays sharded -------------------------

@pytest.mark.slow
def test_chunk_program_reduce_scatters_no_all_reduce():
  """The --steps_per_dispatch program under --shard_optimizer_state:
  reduce-scatter + all-gather INSIDE the scanned step body, and no
  full-gradient all-reduce anywhere (the train_step program is pinned
  by the sharded_* golden contracts; this pins the scan carry)."""
  from kf_benchmarks_tpu.analysis import contracts
  c = contracts.trace_contract(
      dict(model="trivial", batch_size=4, optimizer="momentum",
           shard_optimizer_state=True, steps_per_dispatch=4),
      program="train_chunk")
  kinds = {x.kind for x in c.collectives if not x.scalar}
  assert "reduce-scatter" in kinds and "all-gather" in kinds
  assert not c.gradient_collectives()
  assert any(x.in_loop for x in c.collectives
             if x.kind == "reduce-scatter")


# -- checkpoint: sharded layout round-trip ------------------------------------

def test_checkpoint_sharded_roundtrip_and_resume(tmp_path):
  train_dir = str(tmp_path / "ckpt")
  kw = dict(shard_optimizer_state=True, train_dir=train_dir,
            num_batches=4)
  logs_a, stats_a = _run_and_scrape(**kw)
  snap = checkpoint.load_checkpoint(
      checkpoint.latest_checkpoint(train_dir)[0])
  assert snap.get("opt_state_layout") == "sharded"
  # The saved trace rows are the FULL (n, k) stack, not a v0 slice.
  state = stats_a["state"]
  saved_leaves = jax.tree_util.tree_leaves(snap["opt_state"])
  live_leaves = jax.tree_util.tree_leaves(
      jax.tree.map(np.asarray, state.opt_state))
  assert {np.asarray(l).shape for l in saved_leaves} \
      == {l.shape for l in live_leaves}
  # Resume continues from step 4 with the restored shards.
  logs_b, stats_b = _run_and_scrape(**kw)
  assert any("Restored checkpoint at global step 4" in l for l in logs_b)
  assert int(stats_b["state"].step) == 8


def test_checkpoint_layout_mismatch_rejected():
  snap = {"opt_state_layout": "sharded"}
  with pytest.raises(ValueError, match="layout"):
    checkpoint.restore_state(object(), snap, sharded_opt_state=False)
  with pytest.raises(ValueError, match="layout"):
    checkpoint.restore_state(object(), {"step": 0},
                             sharded_opt_state=True)
