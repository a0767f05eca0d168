"""Chunked fused LM-head loss (ops/fused_loss.py).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: chunk selection, FusedLMHead plumbing through the model
    API.
  * numerical equivalence: f32 loss AND gradients BIT-exact against the
    monolithic head (full logits materialized, same chunk-order
    reduction) -- the oracle the ISSUE pins; bf16 stays finite/close.
  * compiled memory analysis: the grad program's peak temp stays under
    1/4 of one full (B, T, V) f32 logits tensor on the CPU backend
    (same style as test_sequence_parallel.py's flash-attention bound),
    while the monolithic oracle's peak carries the full tensor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.models import transformer_lm
from kf_benchmarks_tpu.models.model import BuildNetworkResult
from kf_benchmarks_tpu.ops import fused_loss


def _case(b=2, t=64, v=96, d=32, seed=0):
  kh, kw, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
  hidden = jax.random.normal(kh, (b, t, d), jnp.float32)
  kernel = jax.random.normal(kw, (d, v), jnp.float32) * 0.1
  labels = jax.random.randint(ky, (b, t), 0, v)
  return hidden, kernel, labels


# -- pure-unit ---------------------------------------------------------------

def test_chunk_of_is_largest_divisor():
  assert fused_loss.chunk_of(2048, 256) == 256
  assert fused_loss.chunk_of(60, 16) == 15  # divisor, not truncation
  assert fused_loss.chunk_of(17, 16) == 1   # prime: worst case, still bounded
  assert fused_loss.chunk_of(8, 256) == 8   # short sequences: one chunk


def test_chunks_per_group_follows_the_shapes():
  # The smallest whole number of chunks with 2,048 rows or more, never
  # more than a quarter of the sequence: the trinity-mini cell (1 x 512
  # rows a chunk, 16 chunks), the glm cell (2 x 512, 8 chunks), others.
  assert fused_loss.WEIGHT_GRAD_ROWS == 2048
  assert fused_loss.chunks_per_group(512, 16) == 4
  assert fused_loss.chunks_per_group(1024, 8) == 2
  assert fused_loss.chunks_per_group(1000, 16) == 3  # 3,000 rows, not 2,000
  assert fused_loss.chunks_per_group(4096, 8) == 1
  assert fused_loss.chunks_per_group(32, 16) == 4    # a quarter, 128 rows
  assert fused_loss.chunks_per_group(32, 7) == 1     # a quarter, rounded down
  assert fused_loss.chunks_per_group(32, 3) == 1     # never less than a chunk
  assert fused_loss.chunks_per_group(32, 16, rows=64) == 2


# (sequence, chunk limit, rows of one weight-gradient product) at batch
# 2: the schedule of chunks and groups each case walks.
SCHEDULES = {
    "group_of_one_chunk": (128, 8, 1),         # 16 groups of 1: the old schedule
    "groups_of_two": (128, 8, 32),             # 8 groups of 2 chunks of 16 rows
    "rows_round_up": (128, 8, 40),             # 40 rows want 3 chunks: 1, 3 x 5
    "a_quarter_of_the_sequence": (128, 8, 2048),  # the default rows: 4 of 4
    "short_group_leads": (104, 8, 48),         # 13 chunks: groups of 1, 3 x 4
    "non_dividing_sequence": (135, 16, 60),    # chunk 15, 9 chunks: 1, 2 x 4
    "one_chunk_is_the_sequence": (8, 16, 2048),
}


@pytest.fixture(params=list(SCHEDULES))
def schedule(request):
  return SCHEDULES[request.param]


def test_non_dividing_sequence_still_matches_oracle():
  hidden, kernel, labels = _case(t=60)  # chunk_of(60, 16) = 15
  got = fused_loss.fused_softmax_xent(hidden, kernel, labels, chunk_size=16)
  want = fused_loss.monolithic_softmax_xent(hidden, kernel, labels,
                                            chunk_size=16)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- numerical equivalence: the bit-exact oracle ------------------------------

def test_loss_and_grads_bit_exact_vs_monolithic_head():
  """Acceptance: f32 loss and gradients (both wrt hidden and kernel)
  bit-exact against the monolithic head. Chunking the head matmul along
  rows and the log-softmax along batch axes is exact; both programs fix
  the same summation order, so nothing is left to float reassociation."""
  hidden, kernel, labels = _case()

  def fused(h, w):
    return fused_loss.fused_softmax_xent(h, w, labels, chunk_size=16)

  def mono(h, w):
    return fused_loss.monolithic_softmax_xent(h, w, labels, chunk_size=16)

  l_f = jax.jit(fused)(hidden, kernel)
  l_m = jax.jit(mono)(hidden, kernel)
  np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_m))
  gh_f, gw_f = jax.jit(jax.grad(fused, (0, 1)))(hidden, kernel)
  gh_m, gw_m = jax.jit(jax.grad(mono, (0, 1)))(hidden, kernel)
  np.testing.assert_array_equal(np.asarray(gh_f), np.asarray(gh_m))
  np.testing.assert_array_equal(np.asarray(gw_f), np.asarray(gw_m))
  # Sanity on the value: untrained-ish logits -> CE near ln(V).
  assert abs(float(l_f) - np.log(96)) < 1.0


def _value_and_grads(fn, *args):
  value, grads = jax.jit(jax.value_and_grad(fn, tuple(range(len(args)))))(
      *args)
  return [np.asarray(x) for x in (value,) + tuple(grads)]


def test_every_schedule_is_bit_exact_vs_monolithic_head(schedule):
  """The pins above over the schedules: whatever the chunks and however
  many of them share one product, loss, gradient by hidden and gradient
  by kernel are those of the monolithic head that multiplies in the same
  groups, bit for bit."""
  t, chunk, rows = schedule
  hidden, kernel, labels = _case(t=t)
  kw = dict(chunk_size=chunk, weight_grad_rows=rows)
  got = _value_and_grads(
      lambda h, w: fused_loss.fused_softmax_xent(h, w, labels, **kw),
      hidden, kernel)
  want = _value_and_grads(
      lambda h, w: fused_loss.monolithic_softmax_xent(h, w, labels, **kw),
      hidden, kernel)
  for g, w, name in zip(got, want, ("loss", "d hidden", "d kernel")):
    np.testing.assert_array_equal(g, w, err_msg=name)


def _same_to_rounding(got, want):
  """Loss to the bit; gradients to a product's rounding: the same sums
  through products of other shapes, whose order of summation is the
  backend's own (the oracle above multiplies in the SAME shapes, so
  there every bit holds)."""
  np.testing.assert_array_equal(got[0], want[0])
  for g, w in zip(got[1:], want[1:]):
    np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-7)


def test_grouping_changes_the_order_of_one_sum_only(schedule):
  """A position's loss depends on its own row of the logits alone, so
  the loss is the old schedule's (one chunk a product) to the bit; the
  gradients are the same sums."""
  t, chunk, rows = schedule
  hidden, kernel, labels = _case(t=t)
  run = lambda r: _value_and_grads(
      lambda h, w: fused_loss.fused_softmax_xent(
          h, w, labels, chunk_size=chunk, weight_grad_rows=r),
      hidden, kernel)
  got, old = run(rows), run(1)
  _same_to_rounding(got, old)


def test_weights_go_through_the_same_grouping(schedule):
  """Packed sequences: the weighted loss is the one-chunk schedule's to
  the bit, a weight of zero leaves its position out of every gradient,
  and the mean is over the real tokens."""
  t, chunk, rows = schedule
  hidden, kernel, labels = _case(t=t)
  weights = (jax.random.uniform(jax.random.PRNGKey(7), labels.shape)
             > 0.3).astype(jnp.float32)
  run = lambda r: _value_and_grads(
      lambda h, w: fused_loss.fused_softmax_xent(
          h, w, labels, chunk_size=chunk, weights=weights,
          weight_grad_rows=r), hidden, kernel)
  got, old = run(rows), run(1)
  _same_to_rounding(got, old)
  assert not got[1][np.asarray(weights) == 0].any()
  logp = jax.nn.log_softmax(hidden @ kernel, -1)
  nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
  np.testing.assert_allclose(
      got[0], float(jnp.sum(nll * weights) / jnp.sum(weights)), rtol=1e-6)


def test_pair_is_two_single_losses_into_one_accumulator(schedule):
  """The MTP pair against two single calls: both losses to the bit; the
  kernel's gradient is the two calls' sum, the heads' products taken
  turn about into the one accumulator."""
  t, chunk, rows = schedule
  main, kernel, labels = _case(t=t)
  mtp = _case(t=t, seed=1)[0]
  kw = dict(chunk_size=chunk, weight_grad_rows=rows)

  def pair(h_main, h_mtp, w):
    l_main, l_mtp = fused_loss.fused_softmax_xent_pair(
        (h_main, h_mtp), w, labels, **kw)
    return l_main + 0.3 * l_mtp

  def singles(h_main, h_mtp, w):
    # The MTP head at position i predicts labels[i + 1]; the last
    # position has no label and no weight.
    last = jnp.broadcast_to(jnp.arange(t) == t - 1, labels.shape)
    l_main = fused_loss.fused_softmax_xent(h_main, w, labels, **kw)
    l_mtp = fused_loss.fused_softmax_xent(
        h_mtp, w, jnp.roll(labels, -1, axis=1),
        weights=jnp.where(last, 0.0, 1.0), **kw)
    return l_main + 0.3 * l_mtp

  got = _value_and_grads(pair, main, mtp, kernel)
  want = _value_and_grads(singles, main, mtp, kernel)
  _same_to_rounding(got, want)


def test_bf16_head_finite_and_close():
  hidden, kernel, labels = _case()
  got = fused_loss.fused_softmax_xent(
      hidden.astype(jnp.bfloat16), kernel, labels, chunk_size=16)
  want = fused_loss.fused_softmax_xent(hidden, kernel, labels,
                                       chunk_size=16)
  assert got.dtype == jnp.float32  # softmax upcasts per chunk
  assert np.isfinite(float(got))
  np.testing.assert_allclose(float(got), float(want), rtol=0.05)


def test_accuracy_matches_dense_head_reduction():
  hidden, kernel, labels = _case()
  acc = fused_loss.fused_top_k_accuracy(hidden, kernel, labels,
                                        chunk_size=16)
  logits = hidden @ kernel
  top1 = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
  top5 = jnp.mean(jnp.any(jax.lax.top_k(logits, 5)[1] == labels[..., None],
                          axis=-1).astype(jnp.float32))
  np.testing.assert_allclose(float(acc["top_1_accuracy"]), float(top1),
                             rtol=1e-6)
  np.testing.assert_allclose(float(acc["top_5_accuracy"]), float(top5),
                             rtol=1e-6)


# -- the mechanism, counted ---------------------------------------------------

def _kernel_shaped_products(jaxpr, shape, times=1):
  """How often a product whose result has ``shape`` (either way round)
  runs in ``jaxpr``: a scan's body counts once for each trip."""
  count = 0
  for eqn in jaxpr.eqns:
    if (eqn.primitive.name == "dot_general" and
        sorted(eqn.outvars[0].aval.shape) == sorted(shape)):
      count += times
    inner = times * (eqn.params["length"] if eqn.primitive.name == "scan"
                     else 1)
    for sub in jax.core.jaxprs_in_params(eqn.params):
      count += _kernel_shaped_products(sub, shape, inner)
  return count


@pytest.mark.parametrize("losses", [1, 2])
@pytest.mark.parametrize("t,rows,passes", [
    (256, 1, 16),     # a chunk a product: the schedule before the grouping
    (256, 64, 8),     # 2 chunks of 2 x 16 rows
    (256, 96, 6),     # 3 chunks: groups of 1 (leading), 3 x 5
    (256, 2048, 4),   # the default: a quarter of this short sequence
    (208, 2048, 5),   # 13 chunks, a quarter is 3: 1 (leading), 3 x 4
])
def test_weight_gradient_products_run_once_a_group(t, rows, passes, losses):
  """In the head's backward pass the products of the kernel's shape run
  once a GROUP for each loss (the loop's trips times the products in its
  body, the leading short group beside it), and ``weight_grad_stats``,
  the run's ``stats["lm_head"]``, says that number. No size here equals
  another's, so the kernel's shape is the weight gradient's alone."""
  b, d, v, chunk = 2, 24, 80, 16
  hidden = jnp.zeros((b, t, d))
  kernel = jnp.zeros((d, v))
  labels = jnp.zeros((b, t), jnp.int32)

  def loss(h_main, h_mtp, w):
    main, mtp = fused_loss.fused_softmax_xent_pair(
        (h_main, h_mtp if losses == 2 else None), w, labels,
        chunk_size=chunk, weight_grad_rows=rows)
    return main if mtp is None else main + 0.3 * mtp

  jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(hidden, hidden, kernel)
  assert _kernel_shaped_products(jaxpr.jaxpr, (d, v)) == passes * losses
  stats = fused_loss.weight_grad_stats(b, t, chunk, v, losses, jnp.bfloat16,
                                       weight_grad_rows=rows)
  per_group = -(-(t // chunk) // passes)
  assert stats == {
      "chunk": chunk, "weight_grad_passes": passes, "losses": losses,
      "rows_per_weight_grad_product": per_group * b * chunk,
      "dlogits_bytes_held": losses * per_group * b * chunk * v * 2}


def test_weight_grad_stats_at_the_cells_shapes():
  # trinity-mini: 1 x 8,192 positions, 25,024 rows held, one loss; glm:
  # 2 x 4,096, 19,360 rows, two losses. Both: products over 2,048 rows a
  # loss, 4 passes a loss over the accumulator (16 and 8 before).
  assert fused_loss.weight_grad_stats(1, 8192, 512, 25024, 1,
                                      jnp.bfloat16) == {
      "chunk": 512, "rows_per_weight_grad_product": 2048,
      "weight_grad_passes": 4, "dlogits_bytes_held": 102498304,
      "losses": 1}
  assert fused_loss.weight_grad_stats(2, 4096, 512, 19360, 2,
                                      jnp.bfloat16) == {
      "chunk": 512, "rows_per_weight_grad_product": 2048,
      "weight_grad_passes": 4, "dlogits_bytes_held": 158597120,
      "losses": 2}


# -- model-API integration ----------------------------------------------------

def test_transformer_lm_fused_and_dense_heads_agree_bitwise():
  """The module's fused-head output (FusedLMHead) and the dense-head
  fallback share parameters; loss through the model API must be
  bit-identical (the hidden states are the same tensors, and the fused
  reduction is bit-exact vs the materialized head)."""
  vocab, t = 128, 64
  mk = lambda **kw: transformer_lm._TransformerLMModule(
      vocab=vocab, d_model=32, n_layers=2, n_heads=4, d_ff=64,
      attn_block=16, max_len=t, **kw)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (2, t), 0, vocab)
  labels = jnp.roll(tokens, -1, axis=1)
  variables = mk().init({"params": jax.random.PRNGKey(1)}, tokens)
  model = model_config.get_model_config("transformer_lm", "synthetic")

  out_f, aux = mk().apply(variables, tokens)
  assert isinstance(out_f, fused_loss.FusedLMHead) and aux is None
  out_d, _ = mk(fused_head=False).apply(variables, tokens)
  assert out_d.shape == (2, t, vocab)

  loss_f = model.loss_function(BuildNetworkResult(logits=(out_f, None)),
                               labels)
  loss_d = model.loss_function(BuildNetworkResult(logits=(out_d, None)),
                               labels)
  np.testing.assert_array_equal(np.asarray(loss_f), np.asarray(loss_d))
  acc_f = model.accuracy_function(BuildNetworkResult(logits=(out_f, None)),
                                  labels)
  acc_d = model.accuracy_function(BuildNetworkResult(logits=(out_d, None)),
                                  labels)
  for k in acc_d:
    np.testing.assert_allclose(float(acc_f[k]), float(acc_d[k]),
                               atol=1e-6)


def test_make_module_env_knobs(monkeypatch):
  model = model_config.get_model_config("transformer_lm", "synthetic")
  monkeypatch.setenv("KF_TRANSFORMER_LM_HEAD", "dense")
  assert model.make_module(10, True).fused_head is False
  monkeypatch.setenv("KF_TRANSFORMER_LM_HEAD", "bogus")
  with pytest.raises(ValueError, match="fused.*dense"):
    model.make_module(10, True)
  monkeypatch.delenv("KF_TRANSFORMER_LM_HEAD")
  monkeypatch.setenv("KF_TRANSFORMER_LM_LAYERS", "loop")
  assert model.make_module(10, True).scan_layers is False
  monkeypatch.setenv("KF_TRANSFORMER_LM_LAYERS", "bogus")
  with pytest.raises(ValueError, match="scan.*loop"):
    model.make_module(10, True)


# -- compiled memory analysis -------------------------------------------------

# 2 x 16,384 positions in chunks of 256: 512 rows a chunk, so the default
# 2,048 rows are groups of four chunks, an eighth of the sequence (the
# trinity-mini cell's schedule at a quarter of its chunk).
MEM = dict(b=2, t=16384, v=1024, d=64, chunk=256)
ROWS = {"a_chunk_a_product": 1,
        "default_rows": fused_loss.WEIGHT_GRAD_ROWS}


def _peak_temp(rows, grad=True, head=fused_loss.fused_softmax_xent):
  b, t, v, d, chunk = (MEM[k] for k in ("b", "t", "v", "d", "chunk"))
  hidden = jax.ShapeDtypeStruct((b, t, d), jnp.float32)
  kernel = jax.ShapeDtypeStruct((d, v), jnp.float32)
  labels = jnp.zeros((b, t), jnp.int32)

  def loss(h, w):
    return head(h, w, labels, chunk_size=chunk, weight_grad_rows=rows)

  fn = jax.grad(loss, (0, 1)) if grad else loss
  return jax.jit(fn).lower(hidden, kernel).compile().memory_analysis(
      ).temp_size_in_bytes


@pytest.mark.parametrize("rows", list(ROWS.values()), ids=list(ROWS))
def test_grad_path_peak_temp_under_quarter_logits(rows):
  """Acceptance: the fused grad program's peak temp < 1/4 of one full
  (B, T, V) f32 logits tensor -- no logits-sized residual survives the
  forward into the backward (jax.checkpoint recomputes per group and
  per chunk). The monolithic oracle's grad program, compiled the same
  way, carries at least the full tensor: the bound is meaningful, not
  slack."""
  full_logits_bytes = MEM["b"] * MEM["t"] * MEM["v"] * 4
  peak = _peak_temp(rows)
  assert peak < full_logits_bytes // 4, (
      f"fused grad peak temp {peak} not under 1/4 of the "
      f"{full_logits_bytes}-byte full logits tensor")
  peak_m = _peak_temp(rows, head=fused_loss.monolithic_softmax_xent)
  assert peak_m >= full_logits_bytes, (
      f"oracle peak {peak_m} unexpectedly below one logits tensor -- "
      "the comparison would be vacuous")


@pytest.mark.parametrize("rows", list(ROWS.values()), ids=list(ROWS))
def test_forward_peak_temp_bounded(rows):
  """Forward-only: peak temp stays an O(rows*V) quantity, not
  O(B*T*V)."""
  full_logits_bytes = MEM["b"] * MEM["t"] * MEM["v"] * 4
  peak = _peak_temp(rows, grad=False)
  assert peak < full_logits_bytes // 4, (peak, full_logits_bytes)


def test_grouping_holds_one_groups_dlogits_and_no_more():
  """What the products over 2,048 rows cost in memory: beside the
  one-chunk schedule's temporaries, the backward pass holds one group's
  dlogits in the hidden states' dtype (rows x V x itemsize; float32
  here, bfloat16 on the chip) -- no logits beside them, no float32 copy
  of a bfloat16 group, nothing that grows with the sequence -- and the
  forward pass holds what it held."""
  # ... beside the group's own hidden rows and their gradient.
  held = fused_loss.WEIGHT_GRAD_ROWS * (MEM["v"] + 2 * MEM["d"]) * 4
  added = _peak_temp(fused_loss.WEIGHT_GRAD_ROWS) - _peak_temp(1)
  assert 0 < added <= held, (added, held)
  assert _peak_temp(fused_loss.WEIGHT_GRAD_ROWS, grad=False) == _peak_temp(
      1, grad=False)
