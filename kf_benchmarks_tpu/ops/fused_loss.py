"""Chunked fused LM-head cross-entropy: loss and top-k metrics computed
from (hidden, head-kernel) without ever materializing the full
(B, T, V) logits tensor.

BEYOND-REFERENCE: the reference zoo has no LM family and its losses all
fit comfortably in device memory (ref: models/model.py:287-302 sparse
softmax xent over nclass <= 1001). At transformer_lm scale the f32
logits tensor IS the HBM peak: (8, 2048, 32768) f32 = 2 GiB before the
softmax-backward temps double it (measured OOM at bs=8 on the 16 GiB
chip, PERF.md round 4). The round-6 loss already chunked the softmax,
but the Dense head still materialized the full logits; this module
fuses the head matmul INTO the chunked scan, so peak temp is
O(B * chunk * V) on the forward AND the backward path:

* ``lax.scan`` over sequence slices: each iteration computes the
  slice's logits (hidden_chunk @ kernel), upcasts to f32, log-softmax,
  gathers the label log-probs, and adds the slice sum to a scalar
  carry.
* ``jax.checkpoint`` on the scan body: the backward pass recomputes
  each slice's logits/softmax instead of keeping every slice's
  residuals alive -- the same schedule flash-attention applies to the
  score matrix (Dao et al. 2022), applied to the vocabulary axis.
* The kernel gradient accumulates per-slice through the scan
  transpose (one (D, V) accumulator), never a logits-sized cotangent.

Numerics contract (pinned by tests/test_fused_loss.py): in f32 the
loss AND the gradients are bit-exact against a monolithic head that
materializes the full logits tensor and reduces in the same chunk
order (``monolithic_softmax_xent`` below) -- chunking a matmul along
rows and log-softmax along its batch axes is exact, so the only
freedom is summation order, which both sides fix identically.

Packed sequences (--packed_sequences): both reductions take optional
per-token ``weights`` (data/packing.py token_weights_from_segments --
0 at padding and document-final slots) and normalize by the REAL-token
count; ``weights=None`` keeps the exact unweighted program, so every
pre-packing pin is untouched.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from kf_benchmarks_tpu.parallel import sequence as sequence_lib


class FusedLMHead(NamedTuple):
  """A model head deferred into the loss: final hidden states plus the
  unembedding kernel, in place of materialized logits.

  Models whose vocabulary makes (B, T, V) logits the memory peak return
  this from their module as the ``logits`` slot of BuildNetworkResult;
  their loss/accuracy functions dispatch on it and reduce chunk-wise
  (models/transformer_lm.py is the zoo member that does).
  """
  hidden: Any  # (B, T, D) final hidden states (model compute dtype)
  kernel: Any  # (D, V) unembedding matrix (param dtype)


def chunk_of(t: int, limit: int) -> int:
  """Largest divisor of ``t`` within ``limit``: the bounded-memory
  guarantee must hold for EVERY sequence length (never a silent
  full-tensor fallback; worst case chunk=1)."""
  return max(c for c in range(1, min(limit, t) + 1) if t % c == 0)


def _chunked(x, chunk: int):
  """(B, T, ...) -> (T/chunk, B, chunk, ...) scan layout."""
  b, t = x.shape[:2]
  return x.reshape((b, t // chunk, chunk) + x.shape[2:]).swapaxes(0, 1)


def fused_softmax_xent(hidden, kernel, labels, chunk_size: int = 256,
                       weights=None):
  """Mean next-token NLL from (hidden, kernel) with O(B*chunk*V) temps.

  ``hidden`` (B, T, D) stays in the model compute dtype through the
  per-chunk head matmul (bf16 on TPU under --use_fp16: the head computes
  in the model dtype, exactly like the Dense head it replaces); the
  softmax upcasts the CHUNK to f32. Returns a f32 scalar.

  ``weights`` (B, T) engages packed-sequence masking (data/packing.py
  token_weights_from_segments): each slot's log-likelihood is scaled by
  its weight inside the scan and the mean normalizes by the REAL-token
  count ``sum(weights)`` instead of B*T -- padding and document-final
  slots (weight 0) contribute exact zeros, so a packed document's
  contribution is bit-identical to the same document alone. ``None``
  keeps the exact unweighted program (the pinned fused-head oracle).
  """
  labels = labels.astype(jnp.int32)
  b, t, _ = hidden.shape
  chunk = chunk_of(t, chunk_size)
  hc = _chunked(hidden, chunk)
  yc = _chunked(labels, chunk)
  wc = None if weights is None else _chunked(
      weights.astype(jnp.float32), chunk)

  @jax.checkpoint
  def body(carry, xs):
    hh, yy, ww = xs
    # Per-chunk head matmul: rows of the monolithic logits, bit-exact
    # (matmul output rows depend only on their own input rows).
    lg = hh @ kernel.astype(hh.dtype)
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, yy[..., None], axis=-1)
    if ww is not None:
      ll = ll * ww[..., None]
    return carry + jnp.sum(ll), None

  # Inside a shard_map body the hidden states are device-varying, so the
  # carry must be pcast to match (sequence.py vary_like).
  (zero,) = sequence_lib.vary_like(hidden,
                                   (jnp.zeros((), jnp.float32),))
  total, _ = jax.lax.scan(body, zero, (hc, yc, wc))
  if weights is None:
    return -total / (b * t)
  return -total / jnp.maximum(jnp.sum(weights.astype(jnp.float32)), 1.0)


def fused_top_k_accuracy(hidden, kernel, labels, chunk_size: int = 256,
                         weights=None):
  """top-1/top-5 fractions from (hidden, kernel), chunk at a time.

  argmax/top_k reduce away the vocab axis inside the scan, so the live
  set per iteration is one (B, chunk, V) logits slice -- no f32 upcast
  is needed for an order statistic, matching the Dense-head accuracy
  path's dtype behavior. ``weights`` (B, T): packed-sequence masking --
  hits are weighted and the fractions normalize by the real-token count
  (see ``fused_softmax_xent``).
  """
  labels = labels.astype(jnp.int32)
  b, t, _ = hidden.shape
  chunk = chunk_of(t, chunk_size)
  hc = _chunked(hidden, chunk)
  yc = _chunked(labels, chunk)
  wc = None if weights is None else _chunked(
      weights.astype(jnp.float32), chunk)

  def body(carry, xs):
    hh, yy, ww = xs
    lg = hh @ kernel.astype(hh.dtype)
    hit1 = (jnp.argmax(lg, -1) == yy).astype(jnp.float32)
    hit5 = jnp.any(jax.lax.top_k(lg, 5)[1] == yy[..., None],
                   axis=-1).astype(jnp.float32)
    if ww is not None:
      hit1 = hit1 * ww
      hit5 = hit5 * ww
    c1, c5 = carry
    return (c1 + jnp.sum(hit1), c5 + jnp.sum(hit5)), None

  zeros = sequence_lib.vary_like(
      hidden, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
  (n1, n5), _ = jax.lax.scan(body, tuple(zeros), (hc, yc, wc))
  denom = (jnp.float32(b * t) if weights is None else
           jnp.maximum(jnp.sum(weights.astype(jnp.float32)), 1.0))
  return {"top_1_accuracy": n1 / denom, "top_5_accuracy": n5 / denom}


def monolithic_softmax_xent(hidden, kernel, labels,
                            chunk_size: int = 256):
  """The memory-unbounded oracle: materialize the FULL (B, T, V) logits
  tensor, then reduce in the same chunk order as the fused scan.

  Built from per-chunk matmuls concatenated into the full tensor so the
  backward pass accumulates the kernel gradient chunk-by-chunk in the
  same order as the scan transpose -- which is what makes the fused
  head's f32 gradients BIT-exact against it, not merely close
  (tests/test_fused_loss.py pins this). Peak memory is O(B*T*V): tests
  compile it to measure the logits-sized footprint the fused path
  eliminates.
  """
  labels = labels.astype(jnp.int32)
  b, t, _ = hidden.shape
  chunk = chunk_of(t, chunk_size)
  n = t // chunk
  logits = jnp.concatenate(
      [hidden[:, i * chunk:(i + 1) * chunk] @ kernel.astype(hidden.dtype)
       for i in range(n)], axis=1)
  total = jnp.zeros((), jnp.float32)
  for i in range(n):
    lg = logits[:, i * chunk:(i + 1) * chunk]
    yy = labels[:, i * chunk:(i + 1) * chunk]
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    total = total + jnp.sum(
        jnp.take_along_axis(logp, yy[..., None], axis=-1))
  return -total / (b * t)
