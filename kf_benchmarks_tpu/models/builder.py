"""ConvNetBuilder: imperative layer builder over flax.linen.

TPU-native re-design of the reference's ConvNetBuilder (ref:
scripts/tf_cnn_benchmarks/convnet_builder.py:29-468). Keeps the stateful
``top_layer``/``top_size`` + auto-naming imperative style that makes the
reference model zoo cheap to express, but each op instantiates flax
submodules inside the enclosing module's compact scope, so the whole
network is one traced function XLA can fuse and tile onto the MXU.

Layout: NHWC is the default (TPU-native); NCHW accepted for parity.
Reduced precision: activations/compute in ``dtype`` (bfloat16 on TPU when
--use_fp16), parameters in ``param_dtype`` (fp32 master copies), which is
the equivalent of the reference's fp16 custom-getter variable cast
(ref: convnet_builder.py:56-86).
"""

from __future__ import annotations

import contextlib
from collections import defaultdict
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from kf_benchmarks_tpu.parallel import kungfu


def _activate(x, activation: Optional[str]):
  if activation in (None, "linear"):
    return x
  if activation == "relu":
    return nn.relu(x)
  if activation == "relu6":
    return nn.relu6(x)
  if activation == "tanh":
    return jnp.tanh(x)
  if activation == "sigmoid":
    return nn.sigmoid(x)
  raise KeyError(f"Invalid activation type {activation!r}")


class CompactBatchNorm(nn.Module):
  """Batch norm that keeps activations in the compute dtype.

  flax's nn.BatchNorm upcasts the full activation tensor to float32 for
  both the statistics and the normalize arithmetic; on TPU the resulting
  f32 activation traffic is pure HBM cost on a benchmark that is
  bandwidth-bound (see PERF.md). Here the statistics are still accumulated
  in float32 -- the upcast fuses into the reduction so the tensor is read
  once at compute precision -- and the normalize runs subtract-first in
  the compute dtype ((x - mean) * inv*scale + bias: the subtraction of
  nearby values is exact, preserving full relative precision on the
  normalized output), which XLA fuses with the neighboring ReLU/residual
  ops.

  Leaf layout matches nn.BatchNorm (params: scale/bias, batch_stats:
  mean/var, float32), so a checkpoint is interchangeable wherever the
  module is given an explicit name (the builder passes name=). Call
  sites that relied on nn.BatchNorm's auto-generated ``BatchNorm_N``
  scope names use the ``BatchNorm`` subclass below instead. Semantics
  match the reference's batch norm (ref: convnet_builder.py:408-462)
  with use_fast_variance statistics.
  """
  use_running_average: bool
  momentum: float = 0.999
  epsilon: float = 0.001
  use_scale: bool = False
  use_bias: bool = True
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    feat = x.shape[-1]
    ra_mean = self.variable("batch_stats", "mean",
                            lambda s: jnp.zeros(s, jnp.float32), (feat,))
    ra_var = self.variable("batch_stats", "var",
                           lambda s: jnp.ones(s, jnp.float32), (feat,))
    if self.use_running_average:
      mean, var = ra_mean.value, ra_var.value
    else:
      axes = tuple(range(x.ndim - 1))
      xf = x.astype(jnp.float32)
      mean = jnp.mean(xf, axes)
      mean2 = jnp.mean(jnp.square(xf), axes)
      var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
      if not self.is_initializing():
        m = self.momentum
        ra_mean.value = m * ra_mean.value + (1 - m) * mean
        ra_var.value = m * ra_var.value + (1 - m) * var
    inv = jax.lax.rsqrt(var + self.epsilon)
    scale = jnp.ones((feat,), jnp.float32)
    if self.use_scale:
      scale = self.param("scale", nn.initializers.ones, (feat,),
                         self.param_dtype).astype(jnp.float32)
    bias = jnp.zeros((feat,), jnp.float32)
    if self.use_bias:
      bias = self.param("bias", nn.initializers.zeros, (feat,),
                        self.param_dtype).astype(jnp.float32)
    # Subtract-first normalize in the compute dtype:
    # y = (x - mean) * (inv*scale) + bias. Subtraction of nearby values
    # is exact in floating point, so this keeps full relative precision
    # on the O(1) normalized output; the folded y = x*a + b form loses
    # ~mean/std relative bits to cancellation of two rounded bf16
    # products when channel means are large.
    a = (inv * scale).astype(self.dtype)
    return ((x.astype(self.dtype) - mean.astype(self.dtype)) * a +
            bias.astype(self.dtype))


class BatchNorm(CompactBatchNorm):
  """Checkpoint-name-compatible alias: flax auto-names modules by class,
  so call sites that relied on nn.BatchNorm's auto-generated
  ``BatchNorm_N`` scope names (mobilenet/nasnet/deepspeech) use this
  subclass and keep their parameter tree layout."""


class FactorDense(nn.Module):
  """nn.Dense's parameters (``kernel``, ``bias``: same names, shapes and
  dtypes) and forward pass, with the kernel's gradient formed on the
  factor data plane of the mean gradient (parallel/kungfu.py
  ``factor_mean_dot``): it leaves the backward pass as the replica mean
  over ``axis_name`` already. The bias gradient is autodiff's, local.
  ``affine`` applies it in place of nn.Dense where the step's
  ``kungfu.FactorExchange`` admits the layer's shape; initialisation
  never sees it."""
  features: int
  axis_name: Any
  kernel_init: Any
  bias_init: Any
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    kernel = self.param("kernel", self.kernel_init,
                        (x.shape[-1], self.features), self.param_dtype)
    bias = self.param("bias", self.bias_init, (self.features,),
                      self.param_dtype)
    y = kungfu.factor_mean_dot(x.astype(self.dtype), kernel, self.axis_name,
                               self.dtype)
    return y + bias.astype(self.dtype)


class ConvNetBuilder:
  """Builds a ConvNet anchored at ``self.top_layer`` (ref: convnet_builder.py:29)."""

  def __init__(self, input_layer, phase_train: bool, data_format: str = "NHWC",
               dtype=jnp.float32, param_dtype=jnp.float32,
               use_batch_norm: bool = False,
               batch_norm_config: Optional[dict] = None):
    if data_format not in ("NHWC", "NCHW"):
      raise ValueError(f"Invalid data_format {data_format!r}")
    self.data_format = data_format
    self.channel_axis = 3 if data_format == "NHWC" else 1
    self.top_layer = jnp.asarray(input_layer, dtype)
    self.top_size = int(input_layer.shape[self.channel_axis])
    self.phase_train = phase_train
    self.dtype = dtype
    self.param_dtype = param_dtype
    self.use_batch_norm = use_batch_norm
    # Reference batch-norm defaults (ref: convnet_builder.py:408-420).
    self.batch_norm_config = {"decay": 0.999, "epsilon": 0.001,
                              "scale": False}
    self.batch_norm_config.update(batch_norm_config or {})
    self.counts = defaultdict(int)
    self.aux_top_layer = None
    self.aux_top_size = 0

  # -- helpers -------------------------------------------------------------

  def _name(self, kind: str) -> str:
    n = self.counts[kind]
    self.counts[kind] += 1
    return f"{kind}{n}"

  def _spatial(self, x):
    if self.data_format == "NHWC":
      return x
    return jnp.transpose(x, (0, 2, 3, 1))  # to NHWC for the op

  def _unspatial(self, x):
    if self.data_format == "NHWC":
      return x
    return jnp.transpose(x, (0, 3, 1, 2))

  @contextlib.contextmanager
  def switch_to_aux_top_layer(self):
    """Context that redirects ops onto the auxiliary head
    (ref: convnet_builder.py:88-101)."""
    if self.aux_top_layer is None:
      raise RuntimeError("aux_top_layer not set")
    self.top_layer, self.aux_top_layer = self.aux_top_layer, self.top_layer
    self.top_size, self.aux_top_size = self.aux_top_size, self.top_size
    try:
      yield
    finally:
      self.top_layer, self.aux_top_layer = self.aux_top_layer, self.top_layer
      self.top_size, self.aux_top_size = self.aux_top_size, self.top_size

  # -- layers --------------------------------------------------------------

  def conv(self, num_out_channels: int, k_height: int, k_width: int,
           d_height: int = 1, d_width: int = 1, mode: str = "SAME",
           input_layer=None, num_channels_in: Optional[int] = None,
           use_batch_norm: Optional[bool] = None, stddev: Optional[float] = None,
           activation: Optional[str] = "relu", bias: Optional[float] = 0.0,
           kernel_initializer=None, name: Optional[str] = None):
    """2-D convolution (ref: convnet_builder.py:154-242).

    ``SAME_RESNET`` mode reproduces the v1.5 stride-2 padding: explicit
    (k-1) total padding before a VALID conv (ref: convnet_builder.py:205-223).
    """
    if input_layer is None:
      input_layer = self.top_layer
    name = name or self._name("conv")
    use_bn = self.use_batch_norm if use_batch_norm is None else use_batch_norm
    if kernel_initializer is None:
      if stddev is None:
        # Glorot uniform, the Keras Conv2D default the reference inherits
        # (ref: convnet_builder.py:107-113 keras Conv2D w/o initializer).
        kernel_initializer = nn.initializers.variance_scaling(
            1.0, "fan_avg", "uniform")
      else:
        kernel_initializer = nn.initializers.truncated_normal(stddev=stddev)
    x = self._spatial(jnp.asarray(input_layer, self.dtype))
    if mode == "SAME_RESNET":
      if d_height > 1 or d_width > 1:
        pad_h, pad_w = k_height - 1, k_width - 1
        padding = [(pad_h // 2, pad_h - pad_h // 2),
                   (pad_w // 2, pad_w - pad_w // 2)]
      else:
        padding = "SAME"
    else:
      padding = mode
    x = nn.Conv(
        features=num_out_channels,
        kernel_size=(k_height, k_width),
        strides=(d_height, d_width),
        padding=padding,
        use_bias=(not use_bn and bias is not None),
        bias_init=nn.initializers.constant(bias or 0.0),
        kernel_init=kernel_initializer,
        dtype=self.dtype,
        param_dtype=self.param_dtype,
        name=name)(x)
    x = self._unspatial(x)
    if use_bn:
      x = self._batch_norm_impl(x, name=name + "_bn")
    x = _activate(x, activation)
    self.top_layer = x
    self.top_size = num_out_channels
    return x

  def _pool(self, pool: str, k_height: int, k_width: int, d_height: int,
            d_width: int, mode: str, input_layer, name: Optional[str]):
    if input_layer is None:
      input_layer = self.top_layer
    else:
      # Pooling keeps channel count; re-anchor top_size to the explicit
      # input (ref: convnet_builder.py:215-230 num_channels_in handling).
      self.top_size = int(input_layer.shape[self.channel_axis])
    name = name or self._name(pool)
    x = self._spatial(input_layer)
    window = (1, k_height, k_width, 1)
    strides = (1, d_height, d_width, 1)
    if pool == "mpool":
      init, op = -jnp.inf, jax.lax.max
      x = jax.lax.reduce_window(x, init, op, window, strides, mode)
    else:
      summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                     mode)
      ones = jnp.ones(x.shape[1:3] + (1,), x.dtype)[None]
      counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides,
                                     mode)
      x = summed / counts
    x = self._unspatial(x)
    self.top_layer = x
    return x

  def mpool(self, k_height, k_width, d_height=2, d_width=2, mode="VALID",
            input_layer=None, num_channels_in=None, name=None):
    """Max pool (ref: convnet_builder.py:243-254)."""
    del num_channels_in  # channel count inferred from the input's shape
    return self._pool("mpool", k_height, k_width, d_height, d_width, mode,
                      input_layer, name)

  def apool(self, k_height, k_width, d_height=2, d_width=2, mode="VALID",
            input_layer=None, num_channels_in=None, name=None):
    """Average pool (ref: convnet_builder.py:256-266)."""
    del num_channels_in
    return self._pool("apool", k_height, k_width, d_height, d_width, mode,
                      input_layer, name)

  def reshape(self, shape, input_layer=None):
    """(ref: convnet_builder.py:268-273)"""
    if input_layer is None:
      input_layer = self.top_layer
    x = jnp.reshape(input_layer, shape)
    self.top_layer = x
    self.top_size = int(x.shape[-1])
    return x

  def affine(self, num_out_channels: int, input_layer=None,
             num_channels_in: Optional[int] = None, bias: float = 0.0,
             stddev: Optional[float] = None, activation: Optional[str] = "relu",
             name: Optional[str] = None):
    """Fully connected layer (ref: convnet_builder.py:311-345)."""
    if input_layer is None:
      input_layer = self.top_layer
    name = name or self._name("affine")
    x = jnp.asarray(input_layer, self.dtype)
    if x.ndim > 2:
      x = jnp.reshape(x, (x.shape[0], -1))
    if stddev is None:
      # He-style fan-in truncated normal, matching the reference's affine
      # default: sqrt(init_factor / num_channels_in), init_factor 2 for
      # relu else 1 (ref: convnet_builder.py affine).
      init_factor = 2.0 if activation == "relu" else 1.0
      stddev = float(init_factor / int(x.shape[-1])) ** 0.5
    kernel_init = nn.initializers.truncated_normal(stddev=stddev)
    layer = dict(features=num_out_channels, kernel_init=kernel_init,
                 bias_init=nn.initializers.constant(bias), dtype=self.dtype,
                 param_dtype=self.param_dtype, name=name)
    # Two data planes for the kernel's mean gradient (parallel/kungfu.py):
    # where the step has opened a FactorExchange and this layer's shape
    # passes its rule, the backward exchanges the factors x and dy
    # instead of their product, and the step's all-reduce skips the leaf.
    shape = (x.shape[0], x.shape[-1], num_out_channels, self.dtype,
             self.param_dtype)
    plan = kungfu.active_factor_exchange()
    if plan is not None and plan.admits(*shape):
      dense = FactorDense(axis_name=plan.axis_name, **layer)
      plan.claim(dense.path + ("kernel",), *shape)
    else:
      dense = nn.Dense(**layer)
    x = dense(x)
    x = _activate(x, activation)
    self.top_layer = x
    self.top_size = num_out_channels
    return x

  def inception_module(self, name: str, cols: Sequence[Sequence]):
    """Column-parallel spec interpreter (ref: convnet_builder.py:347-382).

    Each column is a list of (op_name, *args) tuples over ops of this
    builder; column outputs are concatenated on the channel axis. A
    ``('share',)`` entry reuses the previous column's layer at the same
    depth index (enabling split-then-branch structures like Inception
    v3's mixed_9/10 blocks).
    """
    start_layer = self.top_layer
    start_size = self.top_size
    col_layers: list = []
    col_sizes: list = []
    for c, column in enumerate(cols):
      col_layers.append([])
      col_sizes.append([])
      for l, op_spec in enumerate(column):
        op_name, args = op_spec[0], op_spec[1:]
        kwargs = {"input_layer": start_layer} if l == 0 else {}
        if op_name == "share":
          self.top_layer = col_layers[c - 1][l]
          self.top_size = col_sizes[c - 1][l]
        elif op_name in ("conv", "mpool", "apool"):
          getattr(self, op_name)(*args, **kwargs)
        else:
          raise KeyError(
              f"Invalid layer type for inception module: {op_name!r}")
        col_layers[c].append(self.top_layer)
        col_sizes[c].append(self.top_size)
    self.top_layer = jnp.concatenate([layers[-1] for layers in col_layers],
                                     axis=self.channel_axis)
    self.top_size = sum(sizes[-1] for sizes in col_sizes)
    return self.top_layer

  def spatial_mean(self, keep_dims: bool = False, input_layer=None):
    """Global average pool over H,W (ref: convnet_builder.py:385-393)."""
    if input_layer is None:
      input_layer = self.top_layer
    axes = (1, 2) if self.data_format == "NHWC" else (2, 3)
    x = jnp.mean(input_layer, axis=axes, keepdims=keep_dims)
    self.top_layer = x
    return x

  def dropout(self, keep_prob: float = 0.5, input_layer=None):
    """(ref: convnet_builder.py:395-406). Note keep_prob, not rate."""
    if input_layer is None:
      input_layer = self.top_layer
    name = self._name("dropout")
    x = nn.Dropout(rate=1.0 - keep_prob, name=name)(
        input_layer, deterministic=not self.phase_train)
    self.top_layer = x
    return x

  def _batch_norm_impl(self, x, name, decay=None, scale=None, epsilon=None):
    cfg = self.batch_norm_config
    decay = cfg["decay"] if decay is None else decay
    scale = cfg["scale"] if scale is None else scale
    epsilon = cfg["epsilon"] if epsilon is None else epsilon
    x = self._spatial(x)
    x = CompactBatchNorm(
        use_running_average=not self.phase_train,
        momentum=decay,
        epsilon=epsilon,
        use_scale=scale,
        use_bias=True,
        dtype=self.dtype,
        param_dtype=self.param_dtype,
        name=name)(x)
    return self._unspatial(x)

  def batch_norm(self, input_layer=None, decay=None, scale=None,
                 epsilon=None, name=None):
    """Batch normalization (ref: convnet_builder.py:408-462)."""
    if input_layer is None:
      input_layer = self.top_layer
    name = name or self._name("batchnorm")
    x = self._batch_norm_impl(input_layer, name, decay=decay, scale=scale,
                              epsilon=epsilon)
    self.top_layer = x
    return x

  def lrn(self, depth_radius: int, bias: float, alpha: float, beta: float,
          input_layer=None):
    """Local response normalization (ref: convnet_builder.py:463-468).

    Matches tf.nn.lrn semantics: sqr_sum[b,h,w,c] = sum over the
    [c-r, c+r] channel window of squares; out = x / (bias + alpha*sqr_sum)^beta.
    """
    if input_layer is None:
      input_layer = self.top_layer
    x = self._spatial(input_layer)
    squares = jnp.square(x)
    window = 2 * depth_radius + 1
    sqr_sum = jax.lax.reduce_window(
        squares, 0.0, jax.lax.add,
        (1, 1, 1, window), (1, 1, 1, 1),
        [(0, 0), (0, 0), (0, 0), (depth_radius, depth_radius)])
    x = x / jnp.power(bias + alpha * sqr_sum, beta)
    x = self._unspatial(x)
    self.top_layer = x
    return x
