"""Abstract model API.

TPU-native re-design of the reference model base classes (ref:
scripts/tf_cnn_benchmarks/models/model.py:31-312). The TF graph-mode
``build_network`` becomes a flax.linen module factory: the benchmark
runtime owns init/apply and parameter state, models only describe
architecture + loss/accuracy/LR-policy.

Note: the reference fork commented out the final affine layer
(models/model.py:268-272, a debugging leftover); this rebuild restores it
(``skip_final_affine_layer`` defaults False like the TF1 original,
models/model_legacy.py).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from kf_benchmarks_tpu.models import builder as builder_lib


class BuildNetworkResult(NamedTuple):
  """Result of a forward pass (ref: models/model.py:23-28)."""
  logits: Any
  extra_info: Any = None


class Model:
  """Base model: name, shapes, losses, metrics (ref: models/model.py:31)."""

  def __init__(self, name: str, batch_size: int, learning_rate: float,
               fp16_loss_scale: float = 128.0, params=None):
    self.name = name
    self.batch_size = batch_size
    self.default_batch_size = batch_size
    self.learning_rate = learning_rate
    # bfloat16 needs no loss scaling; the reference's fp16 default is kept
    # for fp16_vars mode (ref: models/model.py:55-60).
    self.fp16_loss_scale = fp16_loss_scale
    self.params = params

  def get_name(self) -> str:
    return self.name

  def get_batch_size(self) -> int:
    return self.batch_size

  def set_batch_size(self, batch_size: int) -> None:
    self.batch_size = batch_size

  def get_default_batch_size(self) -> int:
    return self.default_batch_size

  def get_fp16_loss_scale(self) -> float:
    return self.fp16_loss_scale

  def get_learning_rate(self, global_step, batch_size):
    """Model-default LR schedule; scalar or step-indexed (ref :70-75)."""
    del global_step, batch_size
    return self.learning_rate

  def step_counters(self, batch_stats):
    """The model's own per-step counters as one small float32 vector,
    computed inside the step from what its forward left in
    ``batch_stats`` (train_step.make_step_fns: ``metrics["counters"]``);
    None for a model without any."""
    del batch_stats
    return None

  def counter_blocks(self, rows):
    """``rows`` (timed steps, counters) of ``step_counters`` as what the
    timed steps add to the run's blocks, by key: each is merged into the
    ``stats`` entry of the block the model stated under that key
    (``RunTrace.state``)."""
    del rows
    return {}

  def logits_bytes(self, batch_shape, dtype) -> Optional[int]:
    """The bytes of the logits of a batch of ``batch_shape`` (the first
    input's) over the vocabulary, at ``dtype``: the tensor a fused-head
    language model's program must not materialise (the contract
    auditor's bound). None for a model without one."""
    del batch_shape, dtype
    return None

  def get_input_shapes(self, subset: str) -> Sequence[Sequence[int]]:
    raise NotImplementedError

  def get_input_data_types(self, subset: str) -> Sequence[Any]:
    raise NotImplementedError

  def get_synthetic_inputs(self, rng, nclass: int):
    raise NotImplementedError

  def make_module(self, nclass: int, phase_train: bool, data_format: str,
                  dtype, param_dtype) -> nn.Module:
    """Return the flax module computing logits for this model."""
    raise NotImplementedError

  def loss_function(self, build_network_result: BuildNetworkResult, labels):
    raise NotImplementedError

  def accuracy_function(self, build_network_result: BuildNetworkResult,
                        labels):
    raise NotImplementedError

  def postprocess(self, results: dict) -> dict:
    """Hook to postprocess eval results (ref :121-124)."""
    return results

  def reached_target(self) -> bool:
    return False


class _CNNModule(nn.Module):
  """Linen wrapper running a CNNModel's ``add_inference`` through a builder.

  Equivalent of the reference's ``cg/`` variable-scope + ConvNetBuilder
  instantiation (ref: models/model.py:239-276), as one compact module so
  XLA sees a single fusable graph.
  """
  model: Any
  nclass: int
  phase_train: bool
  data_format: str = "NHWC"
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, images):
    if self.data_format == "NCHW" and images.shape[-1] <= 4:
      # Inputs arrive NHWC from the data layer; transpose into the
      # requested compute layout (ref: CNNModel NCHW transpose,
      # models/model.py:239-276).
      images = jnp.transpose(images, (0, 3, 1, 2))
    cnn = builder_lib.ConvNetBuilder(
        input_layer=images,
        phase_train=self.phase_train,
        data_format=self.data_format,
        dtype=self.dtype,
        param_dtype=self.param_dtype,
    )
    self.model.add_inference(cnn)
    if not self.model.skip_final_affine_layer():
      # Restored final classifier layer (see module docstring).
      logits = cnn.affine(self.nclass, activation="linear")
    else:
      logits = cnn.top_layer
    aux_logits = None
    if cnn.aux_top_layer is not None:
      with cnn.switch_to_aux_top_layer():
        aux_logits = cnn.affine(self.nclass, activation="linear")
    logits = logits.astype(jnp.float32)
    if aux_logits is not None:
      aux_logits = aux_logits.astype(jnp.float32)
    return logits, aux_logits


class CNNModel(Model):
  """Convolutional model base (ref: models/model.py:134-312)."""

  def __init__(self, name, image_size, batch_size, learning_rate,
               layer_counts=None, fp16_loss_scale=128.0, params=None,
               depth=3, label_smoothing=0.0):
    super().__init__(name, batch_size, learning_rate,
                     fp16_loss_scale=fp16_loss_scale, params=params)
    self.image_size = image_size
    self.depth = depth
    self.layer_counts = layer_counts
    self.label_smoothing = label_smoothing

  def skip_final_affine_layer(self) -> bool:
    """Subclasses that build their own classifier return True (ref :241-249)."""
    return False

  def add_inference(self, cnn) -> None:
    """Build the network body with the ConvNetBuilder (ref :251-258)."""
    raise NotImplementedError

  def get_input_shapes(self, subset: str):
    del subset
    n = self.get_batch_size()
    # NHWC: the TPU-native layout (reference defaults NCHW for cuDNN).
    return [[n, self.image_size, self.image_size, self.depth], [n]]

  def get_input_data_types(self, subset: str):
    del subset
    return [jnp.float32, jnp.int32]

  def get_synthetic_inputs(self, rng, nclass: int):
    """Truncated-normal device-resident synthetic batch (ref :220-237)."""
    image_shape, label_shape = self.get_input_shapes("train")
    r_img, r_lbl = jax.random.split(rng)
    # Within [0, 255]: mean 127, stddev 60 (ref: models/model.py:220-237).
    images = jax.random.truncated_normal(
        r_img, -2.0, 2.0, image_shape, jnp.float32) * 60.0 + 127.0
    labels = jax.random.randint(r_lbl, label_shape, 0, nclass, jnp.int32)
    return images, labels

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32) -> nn.Module:
    return _CNNModule(model=self, nclass=nclass, phase_train=phase_train,
                      data_format=data_format, dtype=dtype,
                      param_dtype=param_dtype)

  def loss_function(self, build_network_result: BuildNetworkResult, labels):
    """Sparse softmax cross-entropy, + 0.4-weighted aux head (ref :287-302)."""
    logits, aux_logits = build_network_result.logits
    labels_onehot = jax.nn.one_hot(labels, logits.shape[-1],
                                   dtype=logits.dtype)
    if self.label_smoothing:
      n = logits.shape[-1]
      labels_onehot = (labels_onehot * (1.0 - self.label_smoothing)
                       + self.label_smoothing / n)
    xent = -jnp.sum(labels_onehot * jax.nn.log_softmax(logits), axis=-1)
    loss = jnp.mean(xent)
    if aux_logits is not None:
      aux_xent = -jnp.sum(
          labels_onehot * jax.nn.log_softmax(aux_logits), axis=-1)
      loss = loss + 0.4 * jnp.mean(aux_xent)
    return loss

  def accuracy_function(self, build_network_result: BuildNetworkResult,
                        labels):
    """top-1 / top-5 fractions (ref :305-312)."""
    logits, _ = build_network_result.logits
    top1 = jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))
    top5_pred = jax.lax.top_k(logits, min(5, logits.shape[-1]))[1]
    top5 = jnp.mean(jnp.any(top5_pred == labels[:, None], axis=-1)
                    .astype(jnp.float32))
    return {"top_1_accuracy": top1, "top_5_accuracy": top5}
