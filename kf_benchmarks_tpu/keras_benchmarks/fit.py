"""Minimal Sequential-style fit loop over flax/optax.

The shared trainer behind the three benchmark models (the model.compile +
model.fit role of the reference suite). Data parallelism over multiple
devices uses a batch NamedSharding and lets the XLA SPMD partitioner
insert the gradient collectives (the multi_gpu_model analog,
ref: run_benchmark.py / gpu_mode.py).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu.keras_benchmarks.models.timehistory import TimeHistory
from kf_benchmarks_tpu.utils import sync


def fit(module, x_train, y_train, *, batch_size: int, epochs: int,
        tx: optax.GradientTransformation,
        loss: str = "categorical_crossentropy",
        time_callback: Optional[TimeHistory] = None,
        num_devices: int = 1, seed: int = 0):
  """Train; returns (final_params, history dict)."""
  n = x_train.shape[0]
  # Drop the ragged tail so every step has a static shape (XLA-friendly;
  # with the reference's sample counts the tail is at most one batch).
  steps = n // batch_size
  if num_devices > 1:
    devices = jax.devices()[:num_devices]
    mesh = Mesh(np.asarray(devices), ("batch",))
    data_sharding = NamedSharding(mesh, P("batch"))
  else:
    data_sharding = None

  rng = jax.random.PRNGKey(seed)
  sample = jnp.asarray(x_train[:batch_size], jnp.float32)
  variables = module.init({"params": rng, "dropout": rng}, sample)
  params = variables["params"]
  opt_state = tx.init(params)

  def loss_fn(params, x, y, rng):
    preds = module.apply({"params": params}, x, rngs={"dropout": rng})
    if loss == "categorical_crossentropy":
      logp = jax.nn.log_softmax(preds)
      return -jnp.mean(jnp.sum(y * logp, axis=-1))
    raise ValueError(f"Unsupported loss {loss!r}")

  @jax.jit
  def train_step(params, opt_state, x, y, rng):
    value, grads = jax.value_and_grad(loss_fn)(params, x, y, rng)
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, value

  history = {"loss": []}
  if time_callback is not None:
    time_callback.on_train_begin()
  for epoch in range(epochs):
    if time_callback is not None:
      time_callback.on_epoch_begin()
    epoch_losses = []
    for step in range(steps):
      lo = step * batch_size
      x = jnp.asarray(x_train[lo:lo + batch_size], jnp.float32)
      y = jnp.asarray(y_train[lo:lo + batch_size], jnp.float32)
      if data_sharding is not None:
        x = jax.device_put(x, data_sharding)
        y = jax.device_put(y, data_sharding)
      rng, step_rng = jax.random.split(rng)
      params, opt_state, value = train_step(params, opt_state, x, y,
                                            step_rng)
      epoch_losses.append(value)
    # The epoch timing callback fires right after this sync.
    sync.drain(params)
    history["loss"].append(float(jnp.mean(jnp.stack(epoch_losses))))
    if time_callback is not None:
      time_callback.on_epoch_end()
  return params, history
