"""Gradient accumulation (--num_grad_accum).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: flag validation (divisibility, staged-vars / async-PS /
    adaptive-batch exclusions, train-only).
  * numerical equivalence: per-step losses at effective batch B with
    --num_grad_accum=M match M=1 on the 8-device mesh at the printed
    f32 precision, including composed with --steps_per_dispatch > 1
    and non-multiple warmup tails; trained parameters agree to the f32
    reassociation bound (the microbatch mean regroups the batch
    reduction -- the ONLY numerical difference; a unit test pins that
    bound directly against the monolithic gradient).
  * memory: the microbatched grad program's peak temp shrinks vs the
    monolithic step on an activation-heavy config.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import benchmark, params as params_lib, validation
from kf_benchmarks_tpu.analysis import contracts
from kf_benchmarks_tpu.utils import log as log_util

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: [\d.]+ \+/- [\d.]+ \(jitter = [\d.]+\)\t(.*)$")


def _run_and_scrape(**overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=12, num_warmup_batches=2,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=2)
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def _loss_columns(logs):
  """(step, loss-and-metric columns) pairs -- everything on the step
  line EXCEPT the timing columns, which legitimately differ across M."""
  return [(m.group(1), m.group(2)) for l in logs
          if (m := STEP_RE.match(l))]


# -- pure-unit: validation -----------------------------------------------------

def test_rejected_with_eval_and_forward_only():
  with pytest.raises(validation.ParamError, match="training only"):
    validation.validate_cross_flags(
        params_lib.make_params(num_grad_accum=2, eval=True))
  with pytest.raises(validation.ParamError, match="training only"):
    validation.validate_cross_flags(
        params_lib.make_params(num_grad_accum=2, forward_only=True))
  with pytest.raises(ValueError):
    params_lib.make_params(num_grad_accum=0)  # lower_bound=1


def test_rejected_when_batch_not_divisible():
  with pytest.raises(validation.ParamError, match="divide"):
    validation.validate_cross_flags(
        params_lib.make_params(num_grad_accum=3, batch_size=4))
  # Model-default batch resolves in BenchmarkCNN: trivial defaults to 32.
  with pytest.raises(validation.ParamError, match="divide"):
    benchmark.BenchmarkCNN(params_lib.make_params(
        model="trivial", device="cpu", num_grad_accum=3))


def test_rejected_with_staged_vars_async_ps_adaptive_batch():
  with pytest.raises(validation.ParamError, match="staged_vars"):
    validation.validate_cross_flags(params_lib.make_params(
        num_grad_accum=2, staged_vars=True,
        variable_update="parameter_server"))
  with pytest.raises(validation.ParamError, match="sequential-apply"):
    validation.validate_cross_flags(params_lib.make_params(
        num_grad_accum=2, variable_update="parameter_server",
        cross_replica_sync=False))
  with pytest.raises(validation.ParamError, match="adaptive_batch_size"):
    validation.validate_cross_flags(params_lib.make_params(
        num_grad_accum=2, adaptive_batch_size=True))


def test_valid_combinations_pass():
  for kw in [dict(num_grad_accum=2, batch_size=4),
             dict(num_grad_accum=4, batch_size=8, steps_per_dispatch=4),
             dict(num_grad_accum=2, batch_size=4,
                  variable_consistency="relaxed"),
             dict(num_grad_accum=2, batch_size=4, use_fp16=True,
                  fp16_enable_auto_loss_scale=True)]:
    validation.validate_cross_flags(params_lib.make_params(**kw))


# -- unit: accumulated gradient vs monolithic bound ---------------------------

def test_accumulated_gradient_matches_monolithic_to_reassociation():
  """The accumulated gradient is the mean over microbatches; vs the
  monolithic batch mean the only difference is float reassociation of
  the batch reduction. Pin both that it is CLOSE (the estimator is the
  same) and that the implementation accumulates in f32 (a bf16
  accumulator would blow far past this bound)."""
  b, m, din, dout = 32, 4, 16, 8
  w = jax.random.normal(jax.random.PRNGKey(0), (din, dout), jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(1), (b, din), jnp.float32)
  y = jax.random.randint(jax.random.PRNGKey(2), (b,), 0, dout)

  def loss(w, x, y):
    logp = jax.nn.log_softmax(x @ w, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

  g_mono = jax.grad(loss)(w, x, y)

  def accum(w):
    xs = x.reshape(m, b // m, din)
    ys = y.reshape(m, b // m)

    def body(acc, mb):
      g = jax.grad(loss)(w, *mb)
      return jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32),
                          acc, g), None

    acc, _ = jax.lax.scan(body, jnp.zeros_like(w), (xs, ys))
    return acc / m

  g_acc = accum(w)
  # f32 reassociation bound: a few ulps of the gradient scale.
  np.testing.assert_allclose(np.asarray(g_acc), np.asarray(g_mono),
                             rtol=1e-5, atol=1e-7)


# -- numerical equivalence through the stock benchmark path -------------------

def test_losses_match_monolithic_step():
  """Acceptance: per-step losses at effective batch B with
  --num_grad_accum=4 match M=1 at the printed f32 precision on the
  mesh, and the trained parameters agree to the reassociation bound."""
  logs1, stats1 = _run_and_scrape(num_grad_accum=1)
  logs4, stats4 = _run_and_scrape(num_grad_accum=4)
  st1, st4 = _loss_columns(logs1), _loss_columns(logs4)
  assert len(st1) == 12 and st1 == st4, (st1, st4)
  for a, b in zip(jax.tree.leaves(stats1["state"].params),
                  jax.tree.leaves(stats4["state"].params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)
  assert int(stats1["state"].step) == int(stats4["state"].step)


@pytest.mark.slow  # ~22 s: tiered for the 870 s tier-1 wall budget
def test_composes_with_steps_per_dispatch_and_warmup_tail():
  """Acceptance + satellite: --num_grad_accum=2 under
  --steps_per_dispatch=4 with a warmup that is NOT a multiple of K
  (q=1 chunk + r=2 singles must still total exactly 6 warmup steps)
  and a run length with a K=1-semantics tail (11 % 4 = 3 tail steps).
  Both the microbatching (inside the step) and the dispatch chunking
  (outside it) must keep per-step losses aligned with the M=1, K=1
  loop."""
  kw = dict(num_batches=11, num_warmup_batches=6, display_every=1)
  logs_ref, stats_ref = _run_and_scrape(num_grad_accum=1,
                                        steps_per_dispatch=1, **kw)
  logs_mk, stats_mk = _run_and_scrape(num_grad_accum=2,
                                      steps_per_dispatch=4, **kw)
  st_ref, st_mk = _loss_columns(logs_ref), _loss_columns(logs_mk)
  assert len(st_ref) == 11 and st_ref == st_mk, (st_ref, st_mk)
  assert stats_mk["steps_per_dispatch"] == 4
  # Warmup ran exactly 6 steps in both: the timed loops saw the same
  # trained state, or the loss columns above would have diverged.
  assert int(stats_ref["state"].step) == int(stats_mk["state"].step) == 17


def test_auto_loss_scale_machine_and_accuracy_under_accumulation():
  """The loss-scale state machine keys on the ACCUMULATED gradient
  (one finite-check per step, not per microbatch), and training
  accuracy is the microbatch-averaged effective-batch value."""
  kw = dict(use_fp16=True, fp16_enable_auto_loss_scale=True,
            print_training_accuracy=True, num_batches=8,
            num_warmup_batches=1)
  logs1, stats1 = _run_and_scrape(num_grad_accum=1, **kw)
  logs2, stats2 = _run_and_scrape(num_grad_accum=2, **kw)
  st1, st2 = _loss_columns(logs1), _loss_columns(logs2)
  assert len(st1) == 8 and st1 == st2, (st1, st2)
  assert float(stats1["state"].loss_scale) == \
      float(stats2["state"].loss_scale)


def test_relaxed_consistency_composes():
  """Deferred (one-step-stale) gradients bank the ACCUMULATED tree --
  the staleness contract is per step, not per microbatch."""
  kw = dict(variable_consistency="relaxed", num_batches=8,
            num_warmup_batches=1)
  logs1, _ = _run_and_scrape(num_grad_accum=1, **kw)
  logs2, _ = _run_and_scrape(num_grad_accum=2, **kw)
  st1, st2 = _loss_columns(logs1), _loss_columns(logs2)
  assert len(st1) == 8 and st1 == st2, (st1, st2)


# -- memory: the residual footprint actually shrinks --------------------------

def test_grad_program_peak_temp_shrinks():
  """The point of the flag: per-replica train-step peak temps drop when
  the batch is microbatched (activation residuals are sized B/M). Uses
  the transformer_lm scaled-down module -- an activation-heavy body
  where residuals dominate."""
  from kf_benchmarks_tpu.models import transformer_lm
  from kf_benchmarks_tpu.models.model import BuildNetworkResult
  from kf_benchmarks_tpu.models import model_config
  vocab, t, b = 256, 128, 8
  module = transformer_lm._TransformerLMModule(
      vocab=vocab, d_model=64, n_layers=2, n_heads=4, d_ff=256,
      attn_block=32, max_len=t)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (b, t), 0, vocab)
  labels = jnp.roll(tokens, -1, axis=1)
  variables = module.init({"params": jax.random.PRNGKey(1)}, tokens)
  model = model_config.get_model_config("transformer_lm", "synthetic")

  def mono_loss(p):
    out = module.apply({"params": p}, tokens)
    return model.loss_function(BuildNetworkResult(logits=out), labels)

  def accum_loss(p, m=4):
    xs = tokens.reshape(m, b // m, t)
    ys = labels.reshape(m, b // m, t)

    def body(acc, mb):
      g = jax.grad(lambda pp: model.loss_function(
          BuildNetworkResult(logits=module.apply({"params": pp}, mb[0])),
          mb[1]))(p)
      return jax.tree.map(lambda a, gg: a + gg, acc, g), None

    acc, _ = jax.lax.scan(
        body, jax.tree.map(jnp.zeros_like, p), (xs, ys))
    return acc

  p0 = variables["params"]
  peak_mono = jax.jit(jax.grad(mono_loss)).lower(
      p0).compile().memory_analysis().temp_size_in_bytes
  peak_accum = jax.jit(accum_loss).lower(
      p0).compile().memory_analysis().temp_size_in_bytes
  assert peak_accum < peak_mono, (peak_accum, peak_mono)


@pytest.mark.slow  # ~24 s: tiered for the 870 s tier-1 wall budget
def test_batch_norm_model_runs_and_logs_semantics_note():
  """Batch-norm models microbatch with per-microbatch BN statistics --
  a semantics change vs M=1, not an equivalence (the EMA also advances
  M times per step). The run must work, stay finite, and tell the
  operator up front."""
  logs, stats = _run_and_scrape(model="resnet20", data_name="cifar10",
                                num_grad_accum=2, num_batches=4,
                                num_warmup_batches=1)
  assert np.isfinite(stats["last_average_loss"])
  notes = [l for l in logs if "batch-norm model" in l]
  assert len(notes) == 1 and "not numerically equivalent" in notes[0], logs
  # BN-free models stay note-free (their equivalence IS pinned above).
  logs2, _ = _run_and_scrape(num_grad_accum=2, num_batches=4,
                             num_warmup_batches=1)
  assert not [l for l in logs2 if "batch-norm model" in l]


# -- compiled-HLO: ONE reduction collective per step ---------------------------

def test_accum_emits_one_reduction_collective_per_step():
  """PR 2's commit message claimed gradient accumulation pays ONE
  reduction collective per step; pin it at the compiled-HLO level.
  With the packed default-path reducer (agg_small_grads packs every
  leaf into one vector) the M=4 step carries exactly ONE non-scalar
  all-reduce -- outside the microbatch scan's while body -- and the
  M=1 program is identical in collective count (the scalar all-reduces
  are the loss/lr metric pmeans, not gradient traffic)."""
  import optax
  import flax.linen as nn
  from kf_benchmarks_tpu import train_step as train_step_lib
  from kf_benchmarks_tpu.models.model import Model
  from kf_benchmarks_tpu.parallel import strategies
  from kf_benchmarks_tpu.parallel.mesh import build_mesh

  class _TinyModule(nn.Module):

    @nn.compact
    def __call__(self, x):
      h = nn.tanh(nn.Dense(8, name="l0")(x))
      return nn.Dense(4, name="head")(h), None

  class _TinyModel(Model):

    def __init__(self, params=None):
      super().__init__("tiny", 4, 0.05, params=params)

    def make_module(self, nclass, phase_train, data_format="NHWC",
                    dtype=jnp.float32, param_dtype=jnp.float32):
      return _TinyModule()

    def loss_function(self, result, labels):
      logits, _ = result.logits
      one_hot = jax.nn.one_hot(labels, logits.shape[-1])
      return -jnp.mean(jnp.sum(
          jax.nn.log_softmax(logits) * one_hot, axis=-1))

    def accuracy_function(self, result, labels):
      return {"top_1_accuracy": jnp.float32(0)}

  def lowered_hlo(m):
    p = params_lib.make_params(
        device="cpu", num_devices=8, num_grad_accum=m, batch_size=4,
        # Pack EVERY gradient leaf into one all-reduce (the
        # default-path small-grad aggregation), so "one collective"
        # is literal, not per-leaf.
        agg_small_grads_max_bytes=1 << 30,
        agg_small_grads_max_group=1000)
    validation.validate_cross_flags(p)
    model = _TinyModel(params=p)
    module = model.make_module(4, True)
    mesh = build_mesh(8, "cpu")
    fns = train_step_lib.make_step_fns(
        model, module, module, strategies.get_strategy(p),
        optax.sgd(0.05), lambda s: jnp.float32(0.05), p, mesh)
    init_state, train_step = fns[0], fns[1]
    x = jnp.zeros((8 * 4, 8), jnp.float32)
    y = jnp.zeros((8 * 4,), jnp.int32)
    state = jax.jit(init_state)(jax.random.PRNGKey(0), x[:1])
    return contracts.compile_for_audit(
        train_step.lower(state, x, y)).as_text()

  # Shared HLO conventions (analysis/contracts.py): gradient traffic is
  # the non-scalar all-reduce; f32[] reductions are the metric pmeans.
  from kf_benchmarks_tpu.analysis.contracts import grad_all_reduce_defs \
      as grad_collectives

  hlo_m4 = lowered_hlo(4)
  defs4, grad4 = grad_collectives(hlo_m4)
  assert len(grad4) == 1, (
      f"expected exactly ONE gradient all-reduce per step, got "
      f"{len(grad4)}")
  assert not [ln for ln in defs4 if "while" in ln], (
      "no collective may sit inside the microbatch scan body "
      "(reduction is per STEP, not per microbatch)")
  defs1, grad1 = grad_collectives(lowered_hlo(1))
  assert len(grad1) == 1 and len(defs1) == len(defs4)
