"""Overlapped gradient reduction (--overlap_gradient_reduction).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: flag validation (replicated-family requirement, reducer
    and noise-scale exclusions, --reduce_bucket_mb gating) and the
    bucket planner (size bounds, builder-layer grouping, exclusion
    prefixes).
  * numerical equivalence: overlapped (in-backward, bucketed) gradients
    and trained state are BIT-identical to the post-hoc path at the f32
    wire dtype on the 8-device mesh -- pmean is elementwise, so neither
    packing nor reduction placement may change a single bit -- for the
    step-level bucket hooks, the transformer_lm per-scanned-block hook,
    and composed with --steps_per_dispatch.
  * compiled-HLO structure: the overlapped scanned-transformer backward
    carries one collective per bucket INSIDE the backward scan's while
    body (interleaved with backward compute), where the post-hoc
    program has none; the step-level program carries one collective per
    bucket instead of one per leaf; under --num_grad_accum the hooks
    disengage (reduction stays post-hoc, no in-loop collectives).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import flax.linen as nn

from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import train_step as train_step_lib
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.models import model_config, transformer_lm
from kf_benchmarks_tpu.models.model import Model
from kf_benchmarks_tpu.ops import allreduce, fused_loss, overlap
from kf_benchmarks_tpu.parallel import strategies, transformer
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS, build_mesh

N_REPLICAS = 8


# HLO-scraping conventions are single-sourced in analysis/contracts.py
# (the program-contract auditor and these pins share one parser).
from kf_benchmarks_tpu.analysis.contracts import (  # noqa: E402
    all_reduce_defs as _all_reduce_defs,
    compile_for_audit as _compile,
    in_backward_loop as _in_backward_loop)


# -- pure-unit: validation -----------------------------------------------------

def test_requires_replicated_family():
  for vu in ("independent", "kungfu"):
    with pytest.raises(validation.ParamError, match="replicated-family"):
      validation.validate_cross_flags(params_lib.make_params(
          overlap_gradient_reduction=True, variable_update=vu))


def test_rejected_with_async_parameter_server():
  with pytest.raises(validation.ParamError, match="UNAVERAGED"):
    validation.validate_cross_flags(params_lib.make_params(
        overlap_gradient_reduction=True,
        variable_update="parameter_server", cross_replica_sync=False))


def test_rejected_with_granularity_owning_reducers():
  for kw in (dict(all_reduce_spec="psum"), dict(gradient_repacking=4),
             dict(agg_small_grads_max_bytes=1024),
             dict(hierarchical_copy=True, num_devices=8)):
    with pytest.raises(validation.ParamError, match="reduction granularity"):
      validation.validate_cross_flags(params_lib.make_params(
          overlap_gradient_reduction=True, **kw))


def test_rejected_with_noise_scale_tracking():
  with pytest.raises(validation.ParamError, match="PRE-reduction"):
    validation.validate_cross_flags(params_lib.make_params(
        overlap_gradient_reduction=True, track_grad_noise_scale=True))


def test_reduce_bucket_mb_requires_overlap():
  with pytest.raises(validation.ParamError, match="reduce_bucket_mb"):
    validation.validate_cross_flags(params_lib.make_params(
        reduce_bucket_mb=4))
  validation.validate_cross_flags(params_lib.make_params(
      reduce_bucket_mb=4, overlap_gradient_reduction=True))


def test_composes_with_accum_dispatch_relaxed():
  """The documented compositions must validate."""
  validation.validate_cross_flags(params_lib.make_params(
      overlap_gradient_reduction=True, num_grad_accum=2, batch_size=4))
  validation.validate_cross_flags(params_lib.make_params(
      overlap_gradient_reduction=True, steps_per_dispatch=4))
  validation.validate_cross_flags(params_lib.make_params(
      overlap_gradient_reduction=True, variable_consistency="relaxed"))


# -- pure-unit: the bucket scheduler ------------------------------------------

def test_plan_size_buckets_bounds_and_order():
  # 3+4 > 6 closes the first bucket; the oversized 9 keeps its own.
  assert allreduce.plan_size_buckets([3, 4, 9, 1, 1], 6) == \
      [[0], [1], [2], [3, 4]]
  assert allreduce.plan_size_buckets([1, 1, 1], 100) == [[0, 1, 2]]
  assert allreduce.plan_size_buckets([], 10) == []


def test_plan_buckets_layer_granularity_and_exclusion():
  f32 = jnp.float32
  tree = {"conv0": {"k": jnp.zeros((4,), f32), "b": jnp.zeros((4,), f32)},
          "conv1": {"k": jnp.zeros((4,), f32)},
          "blocks": {"w": jnp.zeros((64,), f32)}}
  # Tiny bound: one bucket per layer group; a layer never splits.
  buckets, excluded = overlap.plan_buckets(tree, bucket_bytes=8)
  flat = jax.tree_util.tree_flatten_with_path(tree)[0]
  keys_per_bucket = [{overlap._top_key(flat[i][0]) for i in b}
                     for b in buckets]
  assert all(len(ks) == 1 for ks in keys_per_bucket)
  assert not excluded
  # Large bound: everything merges into one bucket.
  buckets, _ = overlap.plan_buckets(tree, bucket_bytes=1 << 20)
  assert len(buckets) == 1
  # Exclusion prefix: the module-reduced 'blocks' leaves drop out.
  buckets, excluded = overlap.plan_buckets(
      tree, bucket_bytes=1 << 20, exclude_prefixes=("blocks",))
  covered = {i for b in buckets for i in b}
  for idx in excluded:
    assert overlap._top_key(flat[idx][0]) == "blocks"
  assert covered | set(excluded) == set(range(len(flat)))


def test_packed_pmean_roundtrip_shapes_dtypes():
  """pack -> pmean -> unpack must hand back the original shapes/dtypes
  (exercised outside a mesh via a size-1 axis shard_map)."""
  from jax.sharding import Mesh, PartitionSpec as P
  mesh = Mesh(np.array(jax.devices()[:1]), (REPLICA_AXIS,))
  leaves = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            jnp.ones((4,), jnp.float32)]

  def body(a, b):
    out = overlap.packed_pmean([a, b], REPLICA_AXIS)
    return tuple(out)

  out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                              out_specs=(P(), P())))(*leaves)
  for got, want in zip(out, leaves):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- numerical equivalence: the step-level bucket hooks -----------------------

class _MLPModule(nn.Module):
  """Three named layers so the planner sees builder-layer groups."""

  @nn.compact
  def __call__(self, x):
    x = nn.tanh(nn.Dense(16, name="layer0")(x))
    x = nn.tanh(nn.Dense(16, name="layer1")(x))
    return nn.Dense(4, name="head")(x), None


class _MLPModel(Model):

  def __init__(self, params=None):
    super().__init__("mlp", 4, 0.05, params=params)

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32):
    return _MLPModule()

  def loss_function(self, result, labels):
    logits, _ = result.logits
    one_hot = jax.nn.one_hot(labels, logits.shape[-1])
    return -jnp.mean(jnp.sum(
        jax.nn.log_softmax(logits) * one_hot, axis=-1))

  def accuracy_function(self, result, labels):
    return {"top_1_accuracy": jnp.float32(0),
            "top_5_accuracy": jnp.float32(0)}


def _mlp_step(overlap_on, bucket_mb=None, **overrides):
  kw = dict(model="trivial", device="cpu", num_devices=N_REPLICAS,
            optimizer="momentum", weight_decay=1e-4,
            overlap_gradient_reduction=overlap_on)
  if bucket_mb is not None:
    kw["reduce_bucket_mb"] = bucket_mb
  kw.update(overrides)
  p = params_lib.make_params(**kw)
  validation.validate_cross_flags(p)
  model = _MLPModel(params=p)
  module = model.make_module(4, True)
  mesh = build_mesh(N_REPLICAS, "cpu")
  strategy = strategies.get_strategy(p)
  tx = optax.sgd(0.05, momentum=0.9)
  lr_fn = lambda s: jnp.float32(0.05)
  return train_step_lib.make_step_fns(model, module, module, strategy,
                                      tx, lr_fn, p, mesh), model


def _mlp_batch():
  rng = jax.random.PRNGKey(7)
  x = jax.random.normal(rng, (N_REPLICAS * 2, 8), jnp.float32)
  y = jax.random.randint(rng, (N_REPLICAS * 2,), 0, 4)
  return x, y


def _run_steps(fns, steps=4, chunked=False):
  init_state, train_step, _, _, train_chunk = fns
  x, y = _mlp_batch()
  state = jax.jit(init_state)(jax.random.PRNGKey(0), x[:1])
  if chunked:
    state, metrics = train_chunk(state, x[None], y[None])
  else:
    for _ in range(steps):
      state, metrics = train_step(state, x, y)
  return state, metrics, train_step, (state, x, y)


def _assert_trees_bit_identical(a, b):
  la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
  assert len(la) == len(lb)
  for x, y in zip(la, lb):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_overlapped_training_bit_identical_to_post_hoc():
  """The acceptance bar: same state bits after several momentum steps,
  f32 wire, 8-replica mesh -- in-backward bucketed pmeans vs the
  post-hoc strategy reduction."""
  fns_post, _ = _mlp_step(False)
  fns_over, _ = _mlp_step(True)
  s_post, m_post, _, _ = _run_steps(fns_post)
  s_over, m_over, _, _ = _run_steps(fns_over)
  _assert_trees_bit_identical(s_post.params, s_over.params)
  _assert_trees_bit_identical(s_post.opt_state, s_over.opt_state)
  assert float(m_post["total_loss"]) == float(m_over["total_loss"])


def test_overlapped_bit_identical_under_steps_per_dispatch():
  """--steps_per_dispatch composition: hooks live inside the scanned
  step body; the chunked program must still match post-hoc bitwise."""
  fns_post, _ = _mlp_step(False)
  # Chunk of 1 synthetic resident batch x 4 scanned steps.
  p_over = params_lib.make_params(
      model="trivial", device="cpu", num_devices=N_REPLICAS,
      optimizer="momentum", weight_decay=1e-4, steps_per_dispatch=4,
      overlap_gradient_reduction=True)
  model = _MLPModel(params=p_over)
  module = model.make_module(4, True)
  mesh = build_mesh(N_REPLICAS, "cpu")
  fns_chunk = train_step_lib.make_step_fns(
      model, module, module, strategies.get_strategy(p_over),
      optax.sgd(0.05, momentum=0.9), lambda s: jnp.float32(0.05),
      p_over, mesh)
  s_post, _, _, _ = _run_steps(fns_post, steps=4)
  s_chunk, _, _, _ = _run_steps(fns_chunk, chunked=True)
  _assert_trees_bit_identical(s_post.params, s_chunk.params)


def test_bucket_count_shapes_the_program():
  """One collective per BUCKET, not per leaf: vs the post-hoc per-leaf
  pmean baseline, the overlapped program's all-reduce count drops by
  exactly (leaves - buckets)."""
  fns_post, _ = _mlp_step(False)
  fns_over, model = _mlp_step(True)
  _, _, step_post, args = _run_steps(fns_post, steps=1)
  _, _, step_over, _ = _run_steps(fns_over, steps=1)
  hlo_post = _compile(step_post.lower(*args)).as_text()
  hlo_over = _compile(step_over.lower(*args)).as_text()
  n_post = len(_all_reduce_defs(hlo_post))
  n_over = len(_all_reduce_defs(hlo_over))
  module = model.make_module(4, True)
  params = module.init({"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, 8)))["params"]
  n_leaves = len(jax.tree.leaves(params))
  spec = overlap.build(params_lib.make_params(
      overlap_gradient_reduction=True))
  buckets, _ = overlap.plan_buckets(params, spec.bucket_bytes)
  assert n_leaves > len(buckets)  # the merge actually merged
  assert n_post - n_over == n_leaves - len(buckets)


def test_accum_keeps_reduction_post_hoc():
  """--num_grad_accum=M + overlap: hooks disengage; the program has NO
  collective inside the microbatch scan (one reduction per STEP) and
  matches the overlap-off accum program's collective count."""
  fns_acc, _ = _mlp_step(False, num_grad_accum=2, batch_size=2)
  fns_both, _ = _mlp_step(True, num_grad_accum=2, batch_size=2)
  _, _, step_acc, args = _run_steps(fns_acc, steps=1)
  _, _, step_both, _ = _run_steps(fns_both, steps=1)
  hlo_acc = _compile(step_acc.lower(*args)).as_text()
  hlo_both = _compile(step_both.lower(*args)).as_text()
  assert not _in_backward_loop(_all_reduce_defs(hlo_both))
  assert len(_all_reduce_defs(hlo_both)) == len(_all_reduce_defs(hlo_acc))
  s_acc, _, _, _ = _run_steps(fns_acc)
  s_both, _, _, _ = _run_steps(fns_both)
  _assert_trees_bit_identical(s_acc.params, s_both.params)


# -- transformer_lm: per-scanned-block hooks ----------------------------------

def _small_lm(**kw):
  cfg = dict(vocab=128, d_model=32, n_layers=3, n_heads=4, d_ff=64,
             attn_block=16, max_len=64, scan_layers=True)
  cfg.update(kw)
  return transformer_lm._TransformerLMModule(**cfg)


def _lm_grads(module, params, tokens, labels, post_hoc):
  from jax.sharding import Mesh, PartitionSpec as P
  mesh = Mesh(np.array(jax.devices()[:N_REPLICAS]), (REPLICA_AXIS,))

  def body(p, toks, lbls):
    def loss(q):
      out, _ = module.apply({"params": q}, toks)
      return fused_loss.fused_softmax_xent(out.hidden, out.kernel, lbls,
                                           chunk_size=16)

    g = jax.grad(loss)(p)
    if post_hoc:
      g = jax.tree.map(lambda t: jax.lax.pmean(t, REPLICA_AXIS), g)
    return g

  return jax.jit(jax.shard_map(
      body, mesh=mesh,
      in_specs=(P(), P(REPLICA_AXIS), P(REPLICA_AXIS)),
      out_specs=P(), check_vma=False))


def test_scanned_lm_hook_bit_identical_and_in_loop():
  """The scanned transformer acceptance bar: per-block in-backward
  reduction is bit-identical to post-hoc, and the compiled backward
  carries its block collective INSIDE the scan's while body where the
  post-hoc program has none in-loop."""
  tokens = jax.random.randint(jax.random.PRNGKey(0),
                              (N_REPLICAS, 64), 0, 128)
  labels = jnp.roll(tokens, -1, axis=1)
  hooked = _small_lm(grad_reduce_axis=REPLICA_AXIS)
  plain = _small_lm()
  params = plain.init({"params": jax.random.PRNGKey(1)},
                      tokens[:1])["params"]
  # The hook is the identity on the forward: init trees agree.
  params_h = hooked.init({"params": jax.random.PRNGKey(1)},
                         tokens[:1])["params"]
  _assert_trees_bit_identical(params, params_h)

  fn_hook = _lm_grads(hooked, params, tokens, labels, post_hoc=False)
  fn_post = _lm_grads(plain, params, tokens, labels, post_hoc=True)
  g_hook = fn_hook(params, tokens, labels)
  g_post = fn_post(params, tokens, labels)
  # The hooked module reduces the scanned 'blocks' stack in-backward.
  _assert_trees_bit_identical(g_hook["blocks"], g_post["blocks"])

  hlo_hook = _compile(fn_hook.lower(params, tokens, labels)).as_text()
  hlo_post = _compile(fn_post.lower(params, tokens, labels)).as_text()
  in_loop = _in_backward_loop(_all_reduce_defs(hlo_hook))
  assert len(in_loop) == 1, (
      "expected the per-block packed collective inside the backward "
      f"scan body, found {len(in_loop)}")
  assert not _in_backward_loop(_all_reduce_defs(hlo_post)), (
      "post-hoc program must not reduce inside the scan")


def test_make_module_wires_hooks_from_params():
  p = params_lib.make_params(overlap_gradient_reduction=True)
  model = transformer_lm.TransformerLMModel(params=p)
  module = model.make_module(1, True)
  assert module.grad_reduce_axis == REPLICA_AXIS
  assert model.in_backward_reduced_prefixes == ("blocks",)
  # Eval module: no backward, no hooks.
  eval_module = model.make_module(1, False)
  assert eval_module.grad_reduce_axis is None


def test_make_module_disengages_hooks_under_accum():
  p = params_lib.make_params(overlap_gradient_reduction=True,
                             num_grad_accum=2, batch_size=8)
  model = transformer_lm.TransformerLMModel(params=p)
  module = model.make_module(1, True)
  assert module.grad_reduce_axis is None
  assert model.in_backward_reduced_prefixes == ()


# -- parallel/transformer.py: the composed trainer's scan hook ----------------

def test_composed_overlap_requires_scan_layers():
  params = transformer.init_params(
      jax.random.PRNGKey(0), vocab=64, d_model=16, n_layers=2,
      n_heads=2, head_dim=8, d_ff=32, max_len=32)
  mesh = transformer.build_mesh(1, 1, 1)
  with pytest.raises(ValueError, match="scan_layers"):
    transformer.make_train_step(mesh, params, 0.1,
                                overlap_grad_reduce=True)


def test_composed_overlap_matches_unhooked_on_degenerate_mesh():
  """On a (1,1,1) mesh the data-axis reduction is the identity, so the
  hook must be fully transparent: same loss, same trained params as
  the unhooked scanned step."""
  key = jax.random.PRNGKey(0)
  params = transformer.init_params(
      key, vocab=64, d_model=16, n_layers=2, n_heads=2, head_dim=8,
      d_ff=32, max_len=32)
  stacked = transformer.stack_blocks(params)
  mesh = transformer.build_mesh(1, 1, 1)
  tokens = jax.random.randint(key, (2, 32), 0, 64)
  labels = jnp.roll(tokens, -1, axis=1)
  step_plain = transformer.make_train_step(mesh, stacked, 0.1,
                                           scan_layers=True)
  step_hook = transformer.make_train_step(mesh, stacked, 0.1,
                                          scan_layers=True,
                                          overlap_grad_reduce=True)
  p1, l1 = step_plain(jax.tree.map(jnp.copy, stacked), tokens, labels)
  p2, l2 = step_hook(jax.tree.map(jnp.copy, stacked), tokens, labels)
  assert float(l1) == float(l2)
  _assert_trees_bit_identical(p1, p2)


def test_composed_overlap_reduces_inside_scan_body():
  """Structural HLO check on a real (2,2,1) data mesh: the hooked
  scanned program issues data-axis collectives inside the backward
  scan's while body (compile-only)."""
  key = jax.random.PRNGKey(0)
  params = transformer.init_params(
      key, vocab=64, d_model=16, n_layers=2, n_heads=2, head_dim=8,
      d_ff=32, max_len=32)
  stacked = transformer.stack_blocks(params)
  mesh = transformer.build_mesh(2, 2, 1)
  tokens = jax.random.randint(key, (4, 32), 0, 64)
  labels = jnp.roll(tokens, -1, axis=1)
  step = transformer.make_train_step(mesh, stacked, 0.1,
                                     scan_layers=True,
                                     overlap_grad_reduce=True)
  hlo = _compile(step.lower(stacked, tokens, labels)).as_text()
  assert _in_backward_loop(_all_reduce_defs(hlo)), (
      "expected the per-layer data-axis reduction inside the backward "
      "scan body")


# -- the f32 wire-compaction opt-in (satellite) -------------------------------

def test_compact_wire_dtype_decoupled_from_fp16():
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
  # The note names the precision change and fires ONCE even though
  # every consumer (reducer build, overlap build, module hooks)
  # consults compact_wire_dtype.
  assert len(notes) == 1 and "bfloat16" in notes[0]


def test_compact_f32_requires_compact_flag_and_consumer():
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
  for consumer in (dict(overlap_gradient_reduction=True),
                   dict(gradient_repacking=4),
                   dict(agg_small_grads_max_bytes=1024)):
    validation.validate_cross_flags(params_lib.make_params(
        compact_gradient_transfer_f32=True, **consumer))


def test_overlap_with_f32_compaction_rounds_to_bf16():
  """The opt-in engages on the overlap path: gradients reduced over a
  bf16 wire match the post-hoc f32 gradients to bf16 rounding."""
  fns_f32, _ = _mlp_step(False)
  fns_bf16, _ = _mlp_step(True, compact_gradient_transfer_f32=True)
  s_f32, _, _, _ = _run_steps(fns_f32, steps=1)
  s_bf16, _, _, _ = _run_steps(fns_bf16, steps=1)
  for a, b in zip(jax.tree.leaves(s_f32.params),
                  jax.tree.leaves(s_bf16.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-2, atol=1e-2)


# -- log-scraping e2e: the CLI-reachable path ---------------------------------

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: [\d.]+ \+/- [\d.]+ \(jitter = [\d.]+\)\t(.*)$")


def _run_and_scrape(**overrides):
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu.utils import log as log_util
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=6, num_warmup_batches=1,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=2)
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def test_e2e_step_losses_match_post_hoc():
  """The full benchmark loop under --overlap_gradient_reduction prints
  bit-identical per-step loss columns to the post-hoc run (timing
  columns legitimately differ)."""
  logs_base, _ = _run_and_scrape()
  logs_over, stats = _run_and_scrape(overlap_gradient_reduction=True)
  cols = lambda logs: [(m.group(1), m.group(2)) for l in logs
                       if (m := STEP_RE.match(l))]
  base, over = cols(logs_base), cols(logs_over)
  assert base and base == over
  assert np.isfinite(stats["last_average_loss"])
