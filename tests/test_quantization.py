"""Weight-only INT8 PTQ for the frozen serving path (the TRT INT8
analog; ref benchmark_cnn.py:2466-2486, flags :615-620).

Layers: pure-unit (quantize/dequantize round-trip bounds), export-level
(INT8 artifact loads and matches f32 logits; artifact shrinks), and an
end-to-end accuracy-delta check on a trained model -- the reference's
methodology of validating the converted serving graph's predictions.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import quantization


def test_round_trip_error_bounded_per_channel():
  # Symmetric per-channel int8: |w - dq(q(w))| <= scale/2 per channel,
  # scale = max|w_channel| / 127.
  w = jax.random.normal(jax.random.PRNGKey(0), (128, 64)) * \
      jnp.linspace(0.1, 3.0, 64)[None, :]
  q = quantization.quantize_variables({"k": w}, min_elems=1)
  back = quantization.dequantize_variables(q)["k"]
  scale = jnp.max(jnp.abs(w), axis=0) / 127.0
  err = jnp.max(jnp.abs(back - w), axis=0)
  assert np.all(np.asarray(err) <= np.asarray(scale) / 2 + 1e-7)


def test_depthwise_layout_gets_per_in_channel_scales():
  """TF-layout depthwise kernels (h, w, in, multiplier) spread their
  output channels over the last TWO axes: reducing over all leading
  axes would give ONE scale per multiplier slot (multiplier=1: one
  scale for the whole kernel), collapsing every input channel's
  dynamic range. The scale must be per (in, multiplier)."""
  chan_mag = jnp.linspace(0.01, 4.0, 64)  # 400x dynamic range across in
  w = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 64, 1)) * \
      chan_mag[None, None, :, None]
  q = quantization.quantize_variables({"dw": w}, min_elems=1)
  assert q["dw"]["__scale__"].shape == (64, 1)
  back = quantization.dequantize_variables(q)["dw"]
  scale = jnp.max(jnp.abs(w), axis=(0, 1)) / 127.0
  err = jnp.max(jnp.abs(back - w), axis=(0, 1))
  # Per-channel bound: err <= scale/2 for EVERY input channel -- a
  # whole-kernel scale would blow this bound on the small channels by
  # orders of magnitude.
  assert np.all(np.asarray(err) <= np.asarray(scale) / 2 + 1e-7)


def test_flax_depthwise_layout_keeps_per_channel_scales():
  # The flax depthwise layout (h, w, 1, channels) already has its output
  # channels last; the layout heuristic must not touch it.
  w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 1, 512))
  q = quantization.quantize_variables({"dw": w}, min_elems=1)
  assert q["dw"]["__scale__"].shape == (512,)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_int8_accuracy_delta_on_depthwise_model():
  """The accuracy-delta check on a depthwise model (mobilenet_v2): the
  quantized forward's top-1 decisions agree with the float forward --
  the depthwise blocks dominate mobilenet, so a mis-scaled depthwise
  quantizer fails exactly here."""
  from kf_benchmarks_tpu import quantization as q_lib
  from kf_benchmarks_tpu.models import model_config
  model = model_config.get_model_config("mobilenet", "imagenet")
  model.set_batch_size(2)
  module = model.make_module(nclass=100, phase_train=False,
                             data_format="NHWC")
  images = jax.random.uniform(jax.random.PRNGKey(5), (2, 224, 224, 3))
  variables = module.init({"params": jax.random.PRNGKey(6)}, images)
  f_logits, _ = module.apply(variables, images)
  qvars = q_lib.quantize_variables(variables)
  assert q_lib.quantized_fraction(qvars) > 0.5
  q_logits, _ = module.apply(q_lib.dequantize_variables(qvars), images)
  f32, q32 = np.asarray(f_logits), np.asarray(q_logits)
  assert np.mean(np.argmax(f32, -1) == np.argmax(q32, -1)) >= 0.75
  assert np.mean(np.abs(q32 - f32)) < 0.05 * max(np.mean(np.abs(f32)), 1e-3)


def test_small_and_nonfloat_leaves_pass_through():
  tree = {
      "bias": jnp.ones((64,)),              # 1-D: never quantized
      "small": jnp.ones((4, 4)),            # under min_elems
      "ints": jnp.arange(200).reshape(10, 20),
      "kernel": jnp.ones((128, 64)),
  }
  q = quantization.quantize_variables(tree, min_elems=1024)
  assert q["bias"] is tree["bias"]
  assert q["small"] is tree["small"]
  assert q["ints"] is tree["ints"]
  assert quantization._is_qleaf(q["kernel"])
  assert q["kernel"]["__int8__"].dtype == jnp.int8
  frac = quantization.quantized_fraction(q)
  assert 0.9 < frac <= 1.0  # kernel dominates the element count


def test_dequantize_inside_jit():
  w = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
  q = quantization.quantize_variables({"k": w}, min_elems=1)

  @jax.jit
  def apply(x):
    f = quantization.dequantize_variables(q, jnp.float32)
    return x @ f["k"]

  x = jax.random.normal(jax.random.PRNGKey(2), (4, 256))
  got = apply(x)
  want = x @ quantization.dequantize_variables(q)["k"]
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def trained_lenet(tmp_path_factory):
  """A few real training steps on synthetic MNIST-shaped data -> the
  (model, variables) pair the export-level tests freeze."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  p = params_lib.make_params(model="lenet", batch_size=8,
                             num_batches=3, num_warmup_batches=0,
                             device="cpu", num_devices=1,
                             variable_update="replicated")
  p = benchmark.setup(p)
  bench = benchmark.BenchmarkCNN(p)
  stats = bench.run()
  state = stats["state"]
  variables = {"params": jax.tree.map(lambda x: x[0], state.params)}
  bs = jax.tree.map(lambda x: x[0], state.batch_stats)
  if bs:
    variables["batch_stats"] = bs
  return bench.model, variables, bench.dataset.num_classes


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_int8_export_matches_f32_logits_and_shrinks(trained_lenet,
                                                    tmp_path):
  from kf_benchmarks_tpu import aot
  model, variables, nclass = trained_lenet
  f32_path = os.path.join(str(tmp_path), "f32.bin")
  int8_path = os.path.join(str(tmp_path), "int8.bin")
  n_f32 = aot.export_forward(model, variables, 8, f32_path,
                             nclass=nclass)
  n_int8 = aot.export_forward(model, variables, 8, int8_path,
                              nclass=nclass, quantize=True)
  # lenet's fc stack dominates its bytes; int8 kernels should cut the
  # artifact well below the f32 one.
  assert n_int8 < 0.55 * n_f32, (n_int8, n_f32)

  images = jax.random.uniform(jax.random.PRNGKey(3), (8, 28, 28, 3))
  want = np.asarray(aot.load_forward(f32_path)(images))
  got = np.asarray(aot.load_forward(int8_path)(images))
  # Weight-only int8: logits drift by quantization noise only.
  assert np.mean(np.abs(got - want)) < 0.05 * max(
      np.mean(np.abs(want)), 1e-3), (got - want)
  # The decision (argmax) should survive quantization on most inputs.
  agree = np.mean(np.argmax(got, -1) == np.argmax(want, -1))
  assert agree >= 0.875, agree


def test_int8_accuracy_delta_on_trained_model(trained_lenet):
  # The reference validates the TRT-converted graph by its predictions;
  # the analog: top-1 on a probe batch moves by at most a few points
  # between the float and the quantized forward.
  from kf_benchmarks_tpu import quantization as q_lib
  model, variables, nclass = trained_lenet
  module = model.make_module(nclass=nclass, phase_train=False,
                             data_format="NHWC")
  images = jax.random.uniform(jax.random.PRNGKey(4), (32, 28, 28, 3))
  f_logits, _ = module.apply(variables, images)
  qvars = q_lib.quantize_variables(variables)
  q_logits, _ = module.apply(q_lib.dequantize_variables(qvars), images)
  f_top1 = np.argmax(np.asarray(f_logits), -1)
  q_top1 = np.argmax(np.asarray(q_logits), -1)
  assert np.mean(f_top1 == q_top1) >= 0.9, (f_top1, q_top1)
