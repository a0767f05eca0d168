"""Model-graph unit tests: forward-pass shape/dtype per model.

Mirrors the reference's TfCnnBenchmarksModelTest.testModel forward
shape/type checks (ref: benchmark_cnn_test.py:74-160) plus registry tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import model_config


def _forward(model, nclass=10, batch=2, train=True):
  model.set_batch_size(batch)
  rng = jax.random.PRNGKey(0)
  images, labels = model.get_synthetic_inputs(rng, nclass)
  module = model.make_module(nclass=nclass, phase_train=train)
  variables = module.init({"params": rng, "dropout": rng}, images)
  out, updates = module.apply(
      variables, images, mutable=["batch_stats"],
      rngs={"dropout": rng} if train else None)
  return out, labels, variables, updates


@pytest.mark.parametrize("name", [
    "trivial", "resnet50", "resnet50_v2", "vgg11", "vgg16", "vgg19",
    "lenet", "overfeat", "alexnet",
    # Whole-graph builds of the branchiest families take tens of CPU
    # seconds each; they ride the slow tier (run_tests.py --full_tests)
    # so tier-1 stays inside its wall budget.
    pytest.param("googlenet", marks=pytest.mark.slow),
    pytest.param("inception3", marks=pytest.mark.slow),
    pytest.param("inception4", marks=pytest.mark.slow),
])
def test_imagenet_model_forward(name):
  model = model_config.get_model_config(name, "imagenet")
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10)
  assert logits.dtype == jnp.float32
  loss = model.loss_function(
      __import__("kf_benchmarks_tpu.models.model",
                 fromlist=["BuildNetworkResult"]).BuildNetworkResult(
                     logits=(logits, aux)), labels)
  assert loss.shape == () and jnp.isfinite(loss)


@pytest.mark.parametrize("name", [
    "trivial", "resnet20", "resnet20_v2", "alexnet",
    pytest.param("densenet40_k12", marks=pytest.mark.slow),
])
def test_cifar_model_forward(name):
  model = model_config.get_model_config(name, "cifar10")
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10)


@pytest.mark.parametrize("name", [
    "official_resnet18", "official_resnet50", "official_resnet50_v2",
])
def test_official_resnet_forward(name):
  """The official-models wrapper family (ref:
  models/official_resnet_model.py:26-77) builds and classifies."""
  model = model_config.get_model_config(name, "imagenet")
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10) and aux is None
  assert jnp.all(jnp.isfinite(logits))


@pytest.mark.slow
def test_nasnetlarge_forward():
  """NASNet-A large variant (ref: models/nasnet_model.py:557-578)."""
  model = model_config.get_model_config("nasnetlarge", "imagenet")
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=1)
  assert logits.shape == (1, 10)


@pytest.mark.parametrize("name,dataset", [
    # The mobilenet/densenet backward builds are the two slowest tests
    # in the whole suite on a CPU box; slow tier.
    pytest.param("mobilenet", "imagenet", marks=pytest.mark.slow),
    pytest.param("densenet40_k12", "cifar10", marks=pytest.mark.slow),
    ("official_resnet18", "imagenet"),  # official-models wrapper family
])
def test_model_gradient_step(name, dataset):
  """One real gradient step per family representative: grads exist for
  every parameter leaf and are finite (the backward-pass analog of the
  reference's testModel forward checks). Representatives chosen for CPU
  cost; plain-residual backward is covered by the resnet20/trivial e2e
  and equivalence suites."""
  model = model_config.get_model_config(name, dataset)
  model.set_batch_size(2)
  rng = jax.random.PRNGKey(0)
  images, labels = model.get_synthetic_inputs(rng, 10)
  module = model.make_module(nclass=10, phase_train=True)
  variables = module.init({"params": rng, "dropout": rng}, images)
  params, batch_stats = variables["params"], variables.get("batch_stats", {})
  from kf_benchmarks_tpu.models.model import BuildNetworkResult

  def loss_fn(p):
    v = {"params": p}
    if batch_stats:
      v["batch_stats"] = batch_stats
    (logits, aux), _ = module.apply(v, images, mutable=["batch_stats"],
                                    rngs={"dropout": rng})
    return model.loss_function(
        BuildNetworkResult(logits=(logits, aux)), labels)

  grads = jax.grad(loss_fn)(params)
  leaves = jax.tree.leaves(grads)
  assert leaves and len(leaves) == len(jax.tree.leaves(params))
  assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
  assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_mobilenet_forward():
  """MobileNet v2 builds, classifies, and has the expected scale
  (ref: models/mobilenet_v2.py:188-198)."""
  model = model_config.get_model_config("mobilenet", "imagenet")
  (logits, aux), labels, variables, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10) and aux is None
  n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
  assert 1.5e6 < n_params < 3.5e6  # ~2.2M backbone at multiplier 1.0


def test_mobilenet_make_divisible():
  from kf_benchmarks_tpu.models import mobilenet_v2
  assert mobilenet_v2.make_divisible(32 * 1.0) == 32
  assert mobilenet_v2.make_divisible(32 * 0.35) == 16
  # Never drops more than 10% below the requested width.
  for c in (24, 32, 64, 96, 160, 320):
    for m in (0.35, 0.5, 0.75, 1.0, 1.4):
      assert mobilenet_v2.make_divisible(c * m) >= 0.9 * c * m


@pytest.mark.slow
def test_nasnet_cifar_forward():
  """NASNet-A cifar builds with an aux head feeding the 0.4-weighted
  loss (ref: models/nasnet_model.py:566-578, nasnet_utils cells)."""
  model = model_config.get_model_config("nasnet", "cifar10")
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10)
  assert aux is not None and aux.shape == (2, 10)


def test_nasnet_reduction_layers():
  from kf_benchmarks_tpu.models import nasnet_model
  assert nasnet_model.calc_reduction_layers(12, 2) == [4, 8]
  assert nasnet_model.calc_reduction_layers(18, 2) == [6, 12]


def test_nasnet_drop_path_global_step_ramp():
  """Keep-prob composes the cell-depth schedule with the global-step
  ramp (ref: nasnet_utils.py:407-439; VERDICT r2 #8): no drop at 0%
  progress, half the final drop rate at 50%, the full cell-depth value
  at 100%, clamped beyond."""
  from kf_benchmarks_tpu.models.nasnet_model import drop_path_keep_prob
  base, cell, total = 0.6, 5, 12
  depth_kp = 1.0 - (cell + 1) / 12.0 * (1.0 - base)  # cell-depth alone
  assert float(drop_path_keep_prob(base, cell, total, 0.0)) == 1.0
  assert np.isclose(float(drop_path_keep_prob(base, cell, total, 0.5)),
                    1.0 - 0.5 * (1.0 - depth_kp))
  assert np.isclose(float(drop_path_keep_prob(base, cell, total, 1.0)),
                    depth_kp)
  # Clamped at 1: running past total_training_steps does not over-drop.
  assert np.isclose(float(drop_path_keep_prob(base, cell, total, 1.7)),
                    depth_kp)
  # No progress argument (eval / non-ramped callers): cell-depth alone.
  assert np.isclose(float(drop_path_keep_prob(base, cell, total)), depth_kp)
  # Deeper cells keep less.
  assert (float(drop_path_keep_prob(base, 11, total, 1.0)) <
          float(drop_path_keep_prob(base, 0, total, 1.0)))


@pytest.mark.slow
def test_nasnet_module_accepts_progress():
  """The module threads ``progress`` to every drop-path site; the traced
  scalar must not leak into shapes (jit-compatible ramp)."""
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.models import nasnet_model
  mod = nasnet_model.NasnetModule(
      nclass=10, phase_train=True, num_cells=2, num_conv_filters=8,
      stem_multiplier=1.0, stem_type="cifar", dense_dropout_keep_prob=1.0,
      drop_path_keep_prob=0.6, use_aux_head=False)
  rng = jax.random.PRNGKey(0)
  x = jnp.ones((2, 32, 32, 3), jnp.float32)
  variables = mod.init({"params": rng, "dropout": rng}, x)

  @jax.jit
  def fwd(progress):
    (logits, _), _ = mod.apply(variables, x, progress=progress,
                               rngs={"dropout": rng},
                               mutable=["batch_stats"])
    return logits

  # progress=0 -> keep_prob 1 everywhere -> drop-path is exactly identity,
  # so two different progress values differ only via the ramp.
  l0 = fwd(jnp.float32(0.0))
  l1 = fwd(jnp.float32(1.0))
  assert l0.shape == (2, 10)
  assert not np.allclose(np.asarray(l0), np.asarray(l1))


@pytest.mark.slow  # ~21 s: tiered for the 870 s tier-1 wall budget
def test_inception3_aux_head():
  """The auxiliary head produces aux logits and a 0.4-weighted loss
  contribution (ref: models/model.py:297-302, inception_model.py:95-104)."""
  from kf_benchmarks_tpu.models import inception_model
  from kf_benchmarks_tpu.models.model import BuildNetworkResult
  model = inception_model.Inceptionv3Model(auxiliary=True)
  (logits, aux), labels, _, _ = _forward(model, nclass=10, batch=2)
  assert logits.shape == (2, 10)
  assert aux is not None and aux.shape == (2, 10)
  loss_with_aux = model.loss_function(
      BuildNetworkResult(logits=(logits, aux)), labels)
  loss_no_aux = model.loss_function(
      BuildNetworkResult(logits=(logits, None)), labels)
  assert float(loss_with_aux) > float(loss_no_aux)


def test_model_default_lr_schedules():
  """Model-default LR schedule hooks (alexnet-cifar exponential decay,
  densenet piecewise; ref: models/alexnet_model.py:80-92,
  densenet_model.py:78-85)."""
  alexnet = model_config.get_model_config("alexnet", "cifar10")
  assert abs(float(alexnet.get_learning_rate(0, 128)) - 0.1) < 1e-7
  decay_steps = int(100 * 50000 / 128)
  assert abs(float(alexnet.get_learning_rate(decay_steps, 128)) - 0.01) < 1e-7

  densenet = model_config.get_model_config("densenet40_k12", "cifar10")
  batches_per_epoch = int(50000 / 64)
  assert abs(float(densenet.get_learning_rate(0, 64)) - 0.1) < 1e-7
  assert abs(float(densenet.get_learning_rate(
      151 * batches_per_epoch, 64)) - 0.01) < 1e-7
  assert abs(float(densenet.get_learning_rate(
      301 * batches_per_epoch, 64)) - 0.0001) < 1e-8


def test_accuracy_function():
  from kf_benchmarks_tpu.models.model import BuildNetworkResult
  model = model_config.get_model_config("trivial", "imagenet")
  logits = jnp.array([[5.0, 1.0, 0.0, 0.0, 0.0, 0.0],
                      [3.0, 1.0, 5.0, 2.0, 2.0, 0.0]])
  labels = jnp.array([0, 0])
  acc = model.accuracy_function(
      BuildNetworkResult(logits=(logits, None)), labels)
  assert acc["top_1_accuracy"] == 0.5
  assert acc["top_5_accuracy"] == 1.0


def test_registry_rejects_unknown():
  with pytest.raises(ValueError, match="Invalid model name"):
    model_config.get_model_config("resnet9000", "imagenet")
  with pytest.raises(ValueError, match="Invalid dataset"):
    model_config.get_model_config("trivial", "mnist")


def test_register_model():
  sentinel = object()
  model_config.register_model("custom_test_model", "imagenet",
                              lambda params=None: sentinel)
  try:
    assert model_config.get_model_config("custom_test_model",
                                         "imagenet") is sentinel
    with pytest.raises(ValueError, match="already registered"):
      model_config.register_model("custom_test_model", "imagenet",
                                  lambda params=None: None)
  finally:
    del model_config._model_name_to_imagenet_model["custom_test_model"]


def test_resnet_lr_schedule():
  model = model_config.get_model_config("resnet50", "imagenet")
  bs = 256
  steps_per_epoch = 1281167 / bs
  # During warmup (first 5 epochs) LR ramps linearly from 0.
  lr0 = model.get_learning_rate(0, bs)
  assert float(lr0) == 0.0
  lr_mid = model.get_learning_rate(int(10 * steps_per_epoch), bs)
  assert abs(float(lr_mid) - 0.1) < 1e-6
  lr_late = model.get_learning_rate(int(65 * steps_per_epoch), bs)
  assert abs(float(lr_late) - 0.001) < 1e-7


def test_batch_stats_updated_in_train():
  model = model_config.get_model_config("resnet20", "cifar10")
  _, _, variables, updates = _forward(model, nclass=10, batch=2, train=True)
  assert "batch_stats" in updates
  # Running stats must move from their init values during training.
  leaves = jax.tree_util.tree_leaves(updates["batch_stats"])
  assert leaves
