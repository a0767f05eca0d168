"""Scan-over-layers with explicit remat: the depth-independent program.

Covers both implementations of the idea:
  * models/transformer_lm.py: the flax module's nn.scan + nn.remat
    block stack (the CLI-reachable flagship), equivalent to the
    unrolled per-layer loop, and -- with the chunked fused head -- the
    full-size bs8 forward+backward compiling under the analytic HBM
    bound recorded in PERF.md round 7.
  * parallel/transformer.py: stack_blocks + lax.scan + jax.checkpoint
    in forward_local/make_train_step for the composed dp x sp x tp
    trainer, equivalent to the per-layer list path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.models import transformer_lm
from kf_benchmarks_tpu.models.model import BuildNetworkResult
from kf_benchmarks_tpu.parallel import transformer


# -- models/transformer_lm.py: nn.scan + nn.remat -----------------------------

def _small(**kw):
  cfg = dict(vocab=128, d_model=32, n_layers=3, n_heads=4, d_ff=64,
             attn_block=16, max_len=64)
  cfg.update(kw)
  return transformer_lm._TransformerLMModule(**cfg)


def _stack_loop_params(params, n_layers):
  """block_{i} per-layer trees -> the scanned module's stacked 'blocks'
  collection (leading layer axis), so the two layouts can share one
  set of weights."""
  stacked = jax.tree.map(
      lambda *xs: jnp.stack(xs),
      *[params[f"block_{i}"] for i in range(n_layers)])
  out = {k: v for k, v in params.items()
         if not k.startswith("block_")}
  out["blocks"] = stacked
  return out


def test_scanned_module_matches_unrolled_loop():
  """Same weights through both layer paths: losses agree to the float
  fusion bound (the op sequence is identical; only XLA's cross-layer
  fusion freedom differs), and the scanned grad program is finite."""
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 128)
  labels = jnp.roll(tokens, -1, axis=1)
  model = model_config.get_model_config("transformer_lm", "synthetic")

  loop_mod = _small(scan_layers=False)
  v_loop = loop_mod.init({"params": jax.random.PRNGKey(1)}, tokens)
  scan_mod = _small(scan_layers=True)
  p_scan = _stack_loop_params(v_loop["params"], 3)

  def loss_of(mod, p):
    out = mod.apply({"params": p}, tokens)
    return model.loss_function(BuildNetworkResult(logits=out), labels)

  l_loop = jax.jit(lambda p: loss_of(loop_mod, p))(v_loop["params"])
  l_scan = jax.jit(lambda p: loss_of(scan_mod, p))(p_scan)
  np.testing.assert_allclose(float(l_scan), float(l_loop),
                             rtol=1e-6, atol=1e-7)
  g = jax.jit(jax.grad(lambda p: loss_of(scan_mod, p)))(p_scan)
  assert all(np.all(np.isfinite(np.asarray(x)))
             for x in jax.tree.leaves(g))


def test_scanned_params_are_depth_stacked():
  tokens = jnp.zeros((1, 16), jnp.int32)
  mod = _small(n_layers=5, max_len=16)
  shapes = jax.eval_shape(
      lambda: mod.init({"params": jax.random.PRNGKey(0)}, tokens))
  blocks = shapes["params"]["blocks"]
  for leaf in jax.tree.leaves(blocks):
    assert leaf.shape[0] == 5  # one stacked leaf per depth, not 5 copies


def test_full_size_bs8_compiles_under_analytic_hbm_bound():
  """Acceptance: transformer_lm at the FULL CLI config (512-d, 6
  layers, 32k vocab, 2048 ctx) and batch 8 -- the config that OOMed the
  16 GiB chip with the monolithic head (PERF.md round 4) -- lowers and
  compiles forward+backward, and the compiled temp footprint stays
  under ONE full f32 logits tensor (2 GiB): the analytic bound PERF.md
  round 7 derives (L layer-boundary residuals + ~5 live head chunks +
  recompute slack < B*T*V*4). Scan-over-layers keeps this CHEAP to
  pin: the program is depth-independent, so the compile takes seconds,
  not the minutes the unrolled program would."""
  model = model_config.get_model_config("transformer_lm", "synthetic")
  module = model.make_module(nclass=1, phase_train=True)
  assert module.fused_head and module.scan_layers  # the defaults under test
  b, t, v = 8, transformer_lm.SEQ_LEN, transformer_lm.VOCAB
  tokens = jnp.zeros((b, t), jnp.int32)
  labels = jnp.zeros((b, t), jnp.int32)
  shapes = jax.eval_shape(
      lambda: module.init({"params": jax.random.PRNGKey(0)}, tokens))
  params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["params"])

  def loss(p):
    out = module.apply({"params": p}, tokens)
    return model.loss_function(BuildNetworkResult(logits=out), labels)

  compiled = jax.jit(jax.grad(loss)).lower(params).compile()
  mem = compiled.memory_analysis()
  full_logits_bytes = b * t * v * 4  # 2 GiB: the tensor that OOMed
  assert mem.temp_size_in_bytes < full_logits_bytes, (
      f"grad-path temps {mem.temp_size_in_bytes} not under one "
      f"{full_logits_bytes}-byte logits tensor")


# -- parallel/transformer.py: stack_blocks + scanned forward ------------------

def _setup(seed=0, n_layers=2):
  cfg = dict(vocab=32, d_model=16, n_layers=n_layers, n_heads=4,
             head_dim=4, d_ff=32, max_len=16)
  params = transformer.init_params(jax.random.PRNGKey(seed), **cfg)
  kt = jax.random.PRNGKey(seed + 1)
  tokens = jax.random.randint(kt, (4, 16), 0, cfg["vocab"])
  labels = jnp.roll(tokens, -1, axis=1)
  return params, tokens, labels


def test_stack_unstack_roundtrip():
  params, _, _ = _setup(n_layers=3)
  stacked = transformer.stack_blocks(params)
  for leaf in jax.tree.leaves(stacked["blocks"]):
    assert leaf.shape[0] == 3
  back = transformer.unstack_blocks(stacked)
  for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stack_blocks_rejects_moe():
  params = transformer.init_params(
      jax.random.PRNGKey(0), vocab=32, d_model=16, n_layers=2,
      n_heads=4, head_dim=4, d_ff=32, max_len=16, moe_every=2,
      n_experts=2)
  with pytest.raises(ValueError, match="homogeneous"):
    transformer.stack_blocks(params)


def test_make_train_step_scan_layers_rejects_list_tree():
  params, _, _ = _setup()
  mesh = transformer.build_mesh(1, 1, 1)
  with pytest.raises(ValueError, match="stack_blocks"):
    transformer.make_train_step(mesh, params, learning_rate=0.1,
                                scan_layers=True)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_scanned_step_matches_list_step_single_axis():
  """Scanned+rematerialized vs per-layer-list training on a 1-device
  mesh: losses and trained parameters agree to the float fusion bound
  across steps."""
  params, tokens, labels = _setup(n_layers=3)
  mesh = transformer.build_mesh(1, 1, 1)
  step_list = transformer.make_train_step(mesh, params,
                                          learning_rate=0.1)
  stacked = transformer.stack_blocks(params)
  step_scan = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True)
  p_list = jax.tree.map(jnp.copy, params)
  p_scan = jax.tree.map(jnp.copy, stacked)
  for _ in range(3):
    p_list, l_list = step_list(p_list, tokens, labels)
    p_scan, l_scan = step_scan(p_scan, tokens, labels)
    np.testing.assert_allclose(float(l_scan), float(l_list),
                               rtol=1e-5, atol=1e-6)
  back = transformer.unstack_blocks(
      jax.tree.map(np.asarray, p_scan))
  for a, b in zip(jax.tree.leaves(p_list), jax.tree.leaves(back)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_scanned_forward_matches_on_composed_mesh():
  """Forward-only equivalence ON the (2, 2, 2) mesh: the scanned
  stack under ring attention + Megatron sharding reproduces the
  list-path loss."""
  params, tokens, labels = _setup(n_layers=2)
  mesh = transformer.build_mesh(2, 2, 2)
  from jax.sharding import PartitionSpec as P
  data_spec = P(transformer.REPLICA_AXIS, transformer.SEQ_AXIS)

  def fwd_loss(p, toks, lbls):
    logits, _ = transformer.forward_local(p, toks)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, lbls[..., None], -1)
    # The logits are already reduced over the tensor axis; the mean
    # runs over the axes the loss still varies on.
    return jax.lax.pmean(
        -jnp.mean(ll), (transformer.REPLICA_AXIS, transformer.SEQ_AXIS))

  run_list = jax.jit(jax.shard_map(
      fwd_loss, mesh=mesh,
      in_specs=(transformer.param_specs(params), data_spec, data_spec),
      out_specs=P()))
  stacked = transformer.stack_blocks(params)
  run_scan = jax.jit(jax.shard_map(
      fwd_loss, mesh=mesh,
      in_specs=(transformer.stacked_param_specs(), data_spec, data_spec),
      out_specs=P()))
  l_list = run_list(params, tokens, labels)
  l_scan = run_scan(stacked, tokens, labels)
  np.testing.assert_allclose(float(l_scan), float(l_list),
                             rtol=1e-5, atol=1e-6)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_scanned_step_matches_list_step_composed_mesh():
  """The full composed proof on (2, 2, 2): scanned+remat training
  equals list-path training, grads included (vma jax only)."""
  params, tokens, labels = _setup(n_layers=2)
  mesh = transformer.build_mesh(2, 2, 2)
  step_list = transformer.make_train_step(mesh, params,
                                          learning_rate=0.1)
  step_scan = transformer.make_train_step(
      mesh, transformer.stack_blocks(params), learning_rate=0.1,
      scan_layers=True,
      remat_policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
  p_list = jax.tree.map(jnp.copy, params)
  p_scan = transformer.stack_blocks(params)
  for _ in range(2):
    p_list, l_list = step_list(p_list, tokens, labels)
    p_scan, l_scan = step_scan(p_scan, tokens, labels)
    np.testing.assert_allclose(float(l_scan), float(l_list),
                               rtol=1e-5, atol=1e-6)
  back = transformer.unstack_blocks(jax.tree.map(np.asarray, p_scan))
  for a, b in zip(jax.tree.leaves(p_list), jax.tree.leaves(back)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_scanned_program_is_depth_independent():
  """The compiled-program-size half of the tentpole claim: at L=8 the
  scanned lowering is (much) smaller than the unrolled one -- the
  while-loop body appears once."""
  params, tokens, labels = _setup(n_layers=8)
  mesh = transformer.build_mesh(1, 1, 1)
  step_list = transformer.make_train_step(mesh, params,
                                          learning_rate=0.1)
  step_scan = transformer.make_train_step(
      mesh, transformer.stack_blocks(params), learning_rate=0.1,
      scan_layers=True)
  text_list = step_list.lower(params, tokens, labels).as_text()
  text_scan = step_scan.lower(
      transformer.stack_blocks(params), tokens, labels).as_text()
  assert len(text_scan) < len(text_list) / 2, (
      len(text_scan), len(text_list))


# -- parallel/transformer.py: FSDP blocks (--shard_params's composed leg) -----

def test_fsdp_stack_unstack_roundtrip():
  params, _, _ = _setup(n_layers=3)
  stacked = transformer.stack_blocks(params)
  fsdp = transformer.fsdp_stack_blocks(stacked, 8)
  for leaf in jax.tree.leaves(fsdp["blocks"]):
    assert leaf.shape[:2] == (3, 8)
  back = transformer.fsdp_unstack_blocks(fsdp, stacked["blocks"])
  for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fsdp_blocks_rejections():
  params, _, _ = _setup()
  stacked = transformer.stack_blocks(params)
  mesh = transformer.build_mesh(2, 2, 2)
  with pytest.raises(ValueError, match="scan_layers"):
    transformer.make_train_step(mesh, stacked, learning_rate=0.1,
                                fsdp_blocks=True)
  with pytest.raises(ValueError, match="tensor"):
    transformer.make_train_step(mesh, stacked, learning_rate=0.1,
                                scan_layers=True, fsdp_blocks=True)


def test_fsdp_blocks_forward_loss_matches_scanned():
  """Step-0 loss on a (4, 2, 1) dp x sp mesh: the per-block gather
  re-assembles exactly the scanned stack's values, so the first
  forward's loss matches the replicated-blocks arm (the comparison
  reads the loss of the SAME params before any update)."""
  params, tokens, labels = _setup(n_layers=2)
  mesh = transformer.build_mesh(4, 2, 1)
  stacked = transformer.stack_blocks(params)
  step_scan = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True)
  step_fsdp = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True,
                                          fsdp_blocks=True)
  n_data = 4 * 2
  _, l_scan = step_scan(jax.tree.map(jnp.copy, stacked), tokens, labels)
  _, l_fsdp = step_fsdp(transformer.fsdp_stack_blocks(stacked, n_data),
                        tokens, labels)
  np.testing.assert_allclose(float(l_fsdp), float(l_scan),
                             rtol=1e-6, atol=1e-7)


def test_fsdp_blocks_gather_sits_inside_scan_body():
  """The composed-trainer residency pin: the per-block all-gather (and
  its backward reduce-scatter) lowers INSIDE the while body, and no
  gather re-assembles the whole (L, ...) stack at once."""
  from kf_benchmarks_tpu.analysis import contracts
  params, tokens, labels = _setup(n_layers=4)
  mesh = transformer.build_mesh(4, 2, 1)
  stacked = transformer.stack_blocks(params)
  step = transformer.make_train_step(mesh, stacked, learning_rate=0.1,
                                     scan_layers=True, fsdp_blocks=True)
  fsdp = transformer.fsdp_stack_blocks(stacked, 8)
  hlo = step.lower(fsdp, tokens, labels).compile().as_text()
  c = contracts.extract_contract(hlo)
  ags = [x for x in c.collectives
         if x.kind == "all-gather" and not x.scalar]
  assert any(x.in_loop for x in ags), "per-block gather left the scan"
  assert any(x.kind == "reduce-scatter" and x.in_loop
             for x in c.collectives), "block scatter left the scan"
  blocks_bytes = sum(int(np.prod(l.shape)) * 4
                     for l in jax.tree.leaves(stacked["blocks"]))
  for x in ags:
    assert x.elems * 4 < blocks_bytes, "a gather re-assembles the stack"


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_fsdp_blocks_training_matches_scanned_degenerate_mesh():
  """n = 1 training equality (every collective is over a singleton
  group): the whole FSDP pipeline -- shard storage, in-scan gather, custom-vjp
  scatter, shard update -- reduces to the scanned step exactly."""
  params, tokens, labels = _setup(n_layers=2)
  mesh = transformer.build_mesh(1, 1, 1)
  stacked = transformer.stack_blocks(params)
  step_scan = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True)
  step_fsdp = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True,
                                          fsdp_blocks=True)
  p_scan = jax.tree.map(jnp.copy, stacked)
  p_fsdp = transformer.fsdp_stack_blocks(stacked, 1)
  for _ in range(3):
    p_scan, l_scan = step_scan(p_scan, tokens, labels)
    p_fsdp, l_fsdp = step_fsdp(p_fsdp, tokens, labels)
    np.testing.assert_allclose(float(l_fsdp), float(l_scan),
                               rtol=1e-6, atol=1e-7)
  back = transformer.fsdp_unstack_blocks(
      jax.tree.map(np.asarray, p_fsdp), stacked["blocks"])
  for a, b in zip(jax.tree.leaves(p_scan), jax.tree.leaves(back)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_fsdp_blocks_training_matches_scanned_dp_mesh():
  """Trained equality on the real (4, 2, 1) dp x sp mesh: the
  replicated-blocks arm's gradients ride shard_map's implicit
  data-axis psums, the FSDP arm's block gradients are explicit."""
  params, tokens, labels = _setup(n_layers=2)
  mesh = transformer.build_mesh(4, 2, 1)
  stacked = transformer.stack_blocks(params)
  step_scan = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True)
  step_fsdp = transformer.make_train_step(mesh, stacked,
                                          learning_rate=0.1,
                                          scan_layers=True,
                                          fsdp_blocks=True)
  p_scan = jax.tree.map(jnp.copy, stacked)
  p_fsdp = transformer.fsdp_stack_blocks(stacked, 8)
  for _ in range(2):
    p_scan, l_scan = step_scan(p_scan, tokens, labels)
    p_fsdp, l_fsdp = step_fsdp(p_fsdp, tokens, labels)
    np.testing.assert_allclose(float(l_fsdp), float(l_scan),
                               rtol=1e-5, atol=1e-6)
  back = transformer.fsdp_unstack_blocks(
      jax.tree.map(np.asarray, p_fsdp), stacked["blocks"])
  for a, b in zip(jax.tree.leaves(p_scan), jax.tree.leaves(back)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)
