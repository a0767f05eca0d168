"""Composed dp x sp x tp transformer training vs single-device dense.

The 3-D composition proof for the parallel/ primitives: one shard_map
SGD step over a (2, 2, 2) = 8-device ('replica', 'seq', 'tensor')
mesh must reproduce the single-device dense implementation -- loss
value AND trained parameters -- and training must make progress.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.parallel import transformer

# Slow tier, whole file (PR 21 tiering): 139 s of composed-trainer
# oracles for parallel/transformer.py, which only tests and the CPU
# dryrun reach (ROADMAP C1); tier-1's 870 s wall is the constraint.
pytestmark = pytest.mark.slow



CFG = dict(vocab=32, d_model=16, n_layers=2, n_heads=4, head_dim=4,
           d_ff=32, max_len=16)


def _setup(seed=0):
  params = transformer.init_params(jax.random.PRNGKey(seed), **CFG)
  kt = jax.random.PRNGKey(seed + 1)
  tokens = jax.random.randint(kt, (4, 16), 0, CFG["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  return params, tokens, labels


def test_composed_step_matches_single_device():
  params, tokens, labels = _setup()
  mesh = transformer.build_mesh(2, 2, 2)
  step = transformer.make_train_step(mesh, params, learning_rate=0.1)

  # The parallel step donates its params argument; give each branch its
  # own buffers.
  ref_params = jax.tree.map(jnp.copy, params)
  got_params = jax.tree.map(jnp.copy, params)
  for i in range(3):
    want_loss, ref_grads = jax.value_and_grad(
        transformer.reference_loss)(ref_params, tokens, labels)
    ref_params = jax.tree.map(lambda p, g: p - 0.1 * g,
                              ref_params, ref_grads)
    got_params, got_loss = step(got_params, tokens, labels)
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5, atol=1e-6)

  for got, want in zip(jax.tree.leaves(got_params),
                       jax.tree.leaves(ref_params)):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_composed_training_makes_progress():
  params, tokens, labels = _setup(seed=7)
  mesh = transformer.build_mesh(2, 2, 2)
  step = transformer.make_train_step(mesh, params, learning_rate=0.5)
  first = last = None
  for i in range(10):
    params, loss = step(params, tokens, labels)
    first = float(loss) if first is None else first
    last = float(loss)
  assert np.isfinite(last) and last < first, (first, last)


def test_rejects_sequence_longer_than_max_len():
  # Global length > max_len must refuse: dynamic_slice would otherwise
  # clamp later seq shards onto the last pos rows, silently wrong.
  params = transformer.init_params(jax.random.PRNGKey(9), **CFG)
  tokens = jnp.zeros((4, 32), jnp.int32)  # global 32 > max_len 16
  labels = tokens
  mesh = transformer.build_mesh(1, 4, 1)
  step = transformer.make_train_step(mesh, params, learning_rate=0.1)
  with pytest.raises(ValueError, match="exceeds the positional"):
    step(jax.tree.map(jnp.copy, params), tokens, labels)


def _assert_moe_step_matches_oracle(mesh_shape, caps, sp_layout,
                                    batch, seed):
  """One SGD step of the MoE transformer vs the grouped oracle: loss
  AND trained params, for each capacity in ``caps``."""
  params = transformer.init_params(
      jax.random.PRNGKey(seed), moe_every=2, n_experts=8, **CFG)
  tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (batch, 16), 0, CFG["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  mesh = transformer.build_mesh(*mesh_shape)
  moe_groups = (mesh_shape[0], mesh_shape[1])
  moe_layout = "zigzag" if sp_layout == "zigzag" else "contiguous"
  for cap in caps:
    step = transformer.make_train_step(mesh, params, learning_rate=0.1,
                                       moe_capacity=cap,
                                       sp_layout=sp_layout)
    want_loss, ref_grads = jax.value_and_grad(
        transformer.reference_loss)(params, tokens, labels,
                                    moe_groups=moe_groups,
                                    moe_capacity=cap,
                                    moe_layout=moe_layout)
    ref_new = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_grads)
    got_new, got_loss = step(jax.tree.map(jnp.copy, params), tokens,
                             labels)
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5, err_msg=f"cap={cap}")
    for got, want in zip(jax.tree.leaves(got_new),
                         jax.tree.leaves(ref_new)):
      np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                 rtol=1e-4, atol=1e-5,
                                 err_msg=f"cap={cap}")


@pytest.mark.parametrize("mesh_shape,caps", [
    ((4, 1, 1), (None, 2)),   # dp x ep, incl. capacity drops
    ((2, 2, 1), (None,)),     # ep composed with the seq axis
    ((2, 2, 2), (None,)),     # ep composed with seq AND tensor axes
])
def test_moe_blocks_match_single_device(mesh_shape, caps):
  # Experts shard over the replica axis; loss AND a trained step match
  # the grouped single-device oracle (including capacity queues), on
  # every mesh shape the expert axis must compose with.
  _assert_moe_step_matches_oracle(mesh_shape, caps,
                                  sp_layout="contiguous", batch=8,
                                  seed=11)


def test_moe_composes_with_all_axes():
  # Full dp x sp x tp x ep on (2, 2, 2): experts over the replica axis,
  # heads/features over tensor, ring attention over seq. Smoke: the
  # composed step runs and training makes progress.
  params = transformer.init_params(
      jax.random.PRNGKey(13), moe_every=2, n_experts=4, **CFG)
  tokens = jax.random.randint(jax.random.PRNGKey(14), (4, 16), 0,
                              CFG["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  mesh = transformer.build_mesh(2, 2, 2)
  step = transformer.make_train_step(mesh, params, learning_rate=0.5)
  first = last = None
  state = jax.tree.map(jnp.copy, params)
  for _ in range(8):
    state, loss = step(state, tokens, labels)
    first = float(loss) if first is None else first
    last = float(loss)
  assert np.isfinite(last) and last < first, (first, last)


@pytest.mark.parametrize("mesh_shape", [(1, 4, 1), (2, 2, 2)])
def test_zigzag_layout_matches_single_device(mesh_shape):
  # The load-balanced sp layout is a pure relabeling of which device
  # holds which token: loss AND trained params must equal the
  # normal-order single-device reference exactly.
  params, tokens, labels = _setup(seed=21)
  mesh = transformer.build_mesh(*mesh_shape)
  step = transformer.make_train_step(mesh, params, learning_rate=0.1,
                                     sp_layout="zigzag")
  want_loss, ref_grads = jax.value_and_grad(
      transformer.reference_loss)(params, tokens, labels)
  ref_new = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_grads)
  got_new, got_loss = step(jax.tree.map(jnp.copy, params), tokens,
                           labels)
  np.testing.assert_allclose(float(got_loss), float(want_loss),
                             rtol=1e-5, atol=1e-6)
  for got, want in zip(jax.tree.leaves(got_new),
                       jax.tree.leaves(ref_new)):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_zigzag_layout_with_moe_matches_single_device():
  # zigzag sp layout + MoE: the capacity queues fill in the zigzag
  # in-shard token order; the oracle mirrors that grouping exactly
  # (moe_layout='zigzag'), including with a tight capacity.
  _assert_moe_step_matches_oracle((2, 2, 1), (None, 3),
                                  sp_layout="zigzag", batch=4, seed=22)


def _pipelined_setup(mesh_shape, seed=31, n_layers=4, batch=4):
  cfg = dict(CFG, n_layers=n_layers)
  params = transformer.init_params(jax.random.PRNGKey(seed), **cfg)
  kt = jax.random.PRNGKey(seed + 1)
  tokens = jax.random.randint(kt, (batch, 16), 0, cfg["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  mesh = transformer.build_mesh_pp(*mesh_shape)
  pparams = transformer.to_pipelined(params, mesh_shape[1])
  return params, pparams, tokens, labels, mesh


@pytest.mark.parametrize("mesh_shape,n_micro,batch", [
    ((1, 2, 2, 2), 2, 4),   # pp x sp x tp
    ((2, 2, 2, 1), 2, 4),   # dp x pp x sp
    ((2, 4, 1, 1), 4, 8),   # dp x pp, deeper pipeline, more microbatches
])
def test_pipelined_step_matches_single_device(mesh_shape, n_micro,
                                              batch):
  # GPipe with full-batch SGD is mathematically the sequential step:
  # loss AND trained params after 2 steps must match the single-device
  # dense oracle on every 4-D mesh shape the stage axis composes with.
  params, pparams, tokens, labels, mesh = _pipelined_setup(
      mesh_shape, batch=batch)
  step = transformer.make_pipelined_train_step(
      mesh, pparams, learning_rate=0.1, num_microbatches=n_micro)
  ref_params = jax.tree.map(jnp.copy, params)
  got = jax.tree.map(jnp.copy, pparams)
  for _ in range(2):
    want_loss, ref_grads = jax.value_and_grad(
        transformer.reference_loss)(ref_params, tokens, labels)
    ref_params = jax.tree.map(lambda p, g: p - 0.1 * g,
                              ref_params, ref_grads)
    got, got_loss = step(got, tokens, labels)
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5, atol=1e-6)
  got_flat = transformer.from_pipelined(got)
  for g, w in zip(jax.tree.leaves(got_flat),
                  jax.tree.leaves(ref_params)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-5)


def test_pipelined_zigzag_matches_single_device():
  # The full 4-D composition with the load-balanced sp layout: stage
  # scan outside, zigzag causal ring inside each tick.
  params, pparams, tokens, labels, mesh = _pipelined_setup(
      (1, 2, 2, 2), seed=37)
  step = transformer.make_pipelined_train_step(
      mesh, pparams, learning_rate=0.1, num_microbatches=2,
      sp_layout="zigzag")
  want_loss, ref_grads = jax.value_and_grad(
      transformer.reference_loss)(params, tokens, labels)
  ref_new = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_grads)
  got, got_loss = step(jax.tree.map(jnp.copy, pparams), tokens, labels)
  np.testing.assert_allclose(float(got_loss), float(want_loss),
                             rtol=1e-5, atol=1e-6)
  for g, w in zip(jax.tree.leaves(transformer.from_pipelined(got)),
                  jax.tree.leaves(ref_new)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-5)


def test_pipelined_round_trip_and_rejections():
  params = transformer.init_params(jax.random.PRNGKey(41),
                                   **dict(CFG, n_layers=4))
  pparams = transformer.to_pipelined(params, 2)
  back = transformer.from_pipelined(pparams)
  for g, w in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w))
  with pytest.raises(ValueError, match="not divisible"):
    transformer.to_pipelined(params, 3)
  moe = transformer.init_params(jax.random.PRNGKey(42), moe_every=2,
                                n_experts=4, **dict(CFG, n_layers=4))
  with pytest.raises(ValueError, match="homogeneous"):
    transformer.to_pipelined(moe, 2)


def test_pipelined_rejects_stage_mesh_mismatch():
  # A stage count that merely DIVIDES the mesh axis size shards
  # legally, but each device would hold >1 stage and p[0] would
  # silently drop the rest -- must refuse, not train on half the net.
  params, pparams, tokens, labels, mesh = _pipelined_setup((1, 2, 2, 2))
  wrong = transformer.to_pipelined(transformer.from_pipelined(pparams),
                                   4)  # 4 stages onto a 2-stage axis
  step = transformer.make_pipelined_train_step(
      mesh, wrong, learning_rate=0.1, num_microbatches=2)
  with pytest.raises(ValueError, match="one stage per device"):
    step(wrong, tokens, labels)


@pytest.mark.parametrize("sp_layout", ["contiguous", "zigzag"])
def test_attn_inner_block_matches_single_device(sp_layout):
  # The ring schedules' K/V sub-block tiling, reachable from the
  # composed trainer in both sequence layouts (zigzag's divisibility is
  # against the stripe length = local shard / 2): numerics must not
  # move.
  params, tokens, labels = _setup(seed=51)
  mesh = transformer.build_mesh(2, 2, 2)
  step = transformer.make_train_step(mesh, params, learning_rate=0.1,
                                     attn_inner_block=2,
                                     sp_layout=sp_layout)
  want_loss, ref_grads = jax.value_and_grad(
      transformer.reference_loss)(params, tokens, labels)
  ref_new = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_grads)
  got_new, got_loss = step(jax.tree.map(jnp.copy, params), tokens,
                           labels)
  np.testing.assert_allclose(float(got_loss), float(want_loss),
                             rtol=1e-5, atol=1e-6)
  for g, w in zip(jax.tree.leaves(got_new), jax.tree.leaves(ref_new)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-5)


def test_alternate_mesh_shapes():
  # Degenerate axes must work too: pure-sp (1, 8, 1) and pure-tp
  # (1, 1, 4) meshes run the same program.
  params, tokens, labels = _setup(seed=3)
  want = float(transformer.reference_loss(params, tokens, labels))
  for shape in [(1, 8, 1), (1, 1, 4), (4, 1, 2)]:
    mesh = transformer.build_mesh(*shape)
    step = transformer.make_train_step(mesh, params, learning_rate=0.1)
    _, loss = step(jax.tree.map(jnp.copy, params), tokens, labels)
    np.testing.assert_allclose(float(loss), want, rtol=1e-5,
                               atol=1e-6, err_msg=str(shape))


@pytest.mark.parametrize("shape", [(2, 2, 2), (1, 4, 2), (4, 2, 1)])
def test_compose_on_model_axis_matches_legacy_mesh(shape):
  """The shared-axis-system mesh (('batch', 'seq', 'tensor'), the
  'model' axis of parallel/mesh.py's 2-D family refined into its
  seq x tensor factors) runs BIT-identically to the legacy
  ('replica', 'seq', 'tensor') grid: axis names route collectives, not
  numerics. Holds on every jax (both arms share the same semantics),
  unlike the oracle comparisons above."""
  params, tokens, labels = _setup(seed=11)
  mesh_a = transformer.build_mesh(*shape)
  mesh_b = transformer.compose_on_model_axis(*shape)
  assert mesh_b.axis_names == ("batch", "seq", "tensor")
  step_a = transformer.make_train_step(mesh_a, params, learning_rate=0.1)
  step_b = transformer.make_train_step(mesh_b, params, learning_rate=0.1)
  pa, la = step_a(jax.tree.map(jnp.copy, params), tokens, labels)
  pb, lb = step_b(jax.tree.map(jnp.copy, params), tokens, labels)
  assert float(la) == float(lb)
  for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compose_on_model_axis_moe_expert_axis():
  # MoE expert stacks shard over the DATA axis on either naming: the
  # composed trainer's ep leg follows the tokens.
  cfg = dict(CFG, moe_every=2, n_experts=2)
  params = transformer.init_params(jax.random.PRNGKey(5), **cfg)
  tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 0,
                              cfg["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  mesh = transformer.compose_on_model_axis(2, 2, 2)
  specs = transformer.param_specs(params, data_axis="batch")
  assert specs["blocks"][1]["ew1"] == transformer.P("batch")
  step = transformer.make_train_step(mesh, params, learning_rate=0.1)
  _, loss = step(jax.tree.map(jnp.copy, params), tokens, labels)
  assert np.isfinite(float(loss))
