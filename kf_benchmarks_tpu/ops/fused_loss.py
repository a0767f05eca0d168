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
  carry. The f32 temporaries are one ``chunk`` of positions.
* The backward pass is written out (a ``custom_vjp``; the softmax's own
  gradient is still autodiff's, taken a slice at a time): it keeps
  nothing of the forward pass but its inputs and recomputes each slice's
  logits and softmax instead of keeping every slice's residuals alive
  -- the same schedule flash-attention applies to the score matrix (Dao
  et al. 2022), applied to the vocabulary axis.
* The kernel gradient accumulates per GROUP of slices
  (``chunks_per_group``): the slices' ``dlogits`` (model dtype, bf16 on
  the chip: rows x V x 2 bytes, never f32) are held together for a
  group, and the group's gradient is ONE product (D, rows) x (rows, V)
  added once into the one f32 (D, V) accumulator -- never a
  logits-sized cotangent. Why groups: a pass over the accumulator reads
  and writes D x V x 4 bytes whatever the rows, so a product over one
  slice's few rows is bound by the accumulator and not by its
  arithmetic (``WEIGHT_GRAD_ROWS`` has the balance). Autodiff of the
  scan cannot do this: its transpose multiplies slice by slice.

Numerics contract (pinned by tests/test_fused_loss.py): in f32 the
loss AND the gradients are bit-exact against a monolithic head that
materializes the full logits tensor, reduces in the same chunk order
and differentiates through the same groups' products
(``monolithic_softmax_xent`` below) -- chunking a matmul along rows
and log-softmax along its batch axes is exact, so the only freedom is
summation order, which both sides fix identically.

Packed sequences (--packed_sequences): both reductions take optional
per-token ``weights`` (data/packing.py token_weights_from_segments --
0 at padding and document-final slots) and normalize by the REAL-token
count; ``weights=None`` keeps the exact unweighted program, so every
pre-packing pin is untouched.
"""

from __future__ import annotations

import functools
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


# The rows (batch x positions) of one weight-gradient product. The head
# kernel's gradient is a sum of products hidden^T (D, rows) x dlogits
# (rows, V) into one f32 (D, V) accumulator, and every product reads and
# writes the accumulator whole: 2 x D x V x 4 bytes for 2 x rows x D x V
# operations. On a TPU v5e (197 TFLOP/s, 819 GB/s) the two take the same
# time at rows = 4 x 197e12 / 819e9 = 962; under that the accumulator's
# traffic bounds the product (at 512 rows, 43% of the peak). 2,048 is
# twice the balance: the product is bound by its arithmetic with margin,
# and what is held for it is 2,048 x V in the model dtype (and at most a
# quarter of the sequence: ``chunks_per_group``).
WEIGHT_GRAD_ROWS = 2048


def chunks_per_group(rows_per_chunk: int, n_chunks: int,
                     rows: int = WEIGHT_GRAD_ROWS) -> int:
  """Chunks whose rows go through ONE product of the head kernel's
  gradient: the smallest whole number of chunks with ``rows`` rows or
  more, and never more than a quarter of the sequence's, so that what a
  group holds stays a bounded share of the logits for EVERY sequence
  length, as a chunk does (``chunk_of``). From the shapes alone."""
  return max(1, min(n_chunks // 4, -(-rows // rows_per_chunk)))


def weight_grad_stats(batch: int, t: int, chunk_size: int, vocab: int,
                      losses: int, dtype,
                      weight_grad_rows: int = WEIGHT_GRAD_ROWS) -> dict:
  """What ``fused_softmax_xent`` (``losses`` 1) or the pair (2) does at
  these shapes, as the run's ``stats["lm_head"]``: static, because the
  grouping always engages. ``weight_grad_passes`` counts, for each loss,
  the products of the kernel's shape in the backward pass, each one pass
  over the f32 accumulator; ``dlogits_bytes_held`` is the largest
  group's ``dlogits`` of all losses in the model dtype."""
  chunk = chunk_of(t, chunk_size)
  n = t // chunk
  per_group = chunks_per_group(batch * chunk, n, weight_grad_rows)
  rows = per_group * batch * chunk
  return {"chunk": chunk, "rows_per_weight_grad_product": rows,
          "weight_grad_passes": -(-n // per_group),
          "dlogits_bytes_held":
              losses * rows * vocab * jnp.dtype(dtype).itemsize,
          "losses": losses}


def _chunked(x, chunk: int):
  """(B, T, ...) -> (T/chunk, B, chunk, ...) scan layout."""
  b, t = x.shape[:2]
  return x.reshape((b, t // chunk, chunk) + x.shape[2:]).swapaxes(0, 1)


def _chunk_sum(logits, labels, weights):
  """Sum of one chunk's (weighted) label log-likelihoods from its
  logits: the float32 softmax, O(B*chunk*V) float32 temporaries."""
  logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
  ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
  if weights is not None:
    ll = ll * weights[..., None]
  return jnp.sum(ll)


def _group_logits(hidden, kernel):
  """(chunks, B, chunk, D) x (D, V): a group's logits as ONE product
  over all its rows. The backward pass never forms them (it recomputes
  the logits chunk by chunk, as the forward pass made them); it runs
  this product's two transposes, over the group's whole ``dlogits``."""
  return hidden @ kernel.astype(hidden.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scan_sums(hiddens, kernel, labels, weights, per_group: int):
  """``_log_likelihood_sums`` on the scan layout (T/chunk, B, chunk,
  ...): one scan over the chunks, each head's logits a chunk at a time.
  Its gradient is written out below (``_scan_sums_bwd``)."""
  del per_group
  w = kernel.astype(hiddens[0].dtype)

  def body(totals, xs):
    # Per-chunk head matmul: rows of the monolithic logits (matmul output
    # rows depend only on their own input rows).
    return tuple(total + _chunk_sum(hh @ w, yy, ww)
                 for total, hh, yy, ww in zip(totals, *xs)), None

  # Inside a shard_map body the hidden states are device-varying, so the
  # carry must be pcast to match (sequence.py vary_like).
  zeros = sequence_lib.vary_like(
      hiddens[0], tuple(jnp.zeros((), jnp.float32) for _ in hiddens))
  return jax.lax.scan(body, tuple(zeros), (hiddens, labels, weights))[0]


def _scan_sums_fwd(hiddens, kernel, labels, weights, per_group):
  # Nothing but the inputs is kept: the backward pass recomputes each
  # chunk's logits and softmax.
  return (_scan_sums(hiddens, kernel, labels, weights, per_group),
          (hiddens, kernel, labels, weights))


def _scan_sums_bwd(per_group, residuals, cotangents):
  """Group by group, last group first (the order in which autodiff would
  sum the groups of the monolithic oracle): an inner scan recomputes each
  chunk's logits and takes ``dlogits`` from its softmax (autodiff of
  ``_chunk_sum``, in the model dtype), the group's ``dlogits`` are held
  together, and TWO products a head use them whole: hidden^T x dlogits
  into the one float32 accumulator of the kernel's gradient, and
  dlogits x kernel^T, the group's hidden-state gradient. Where the
  chunks are no whole number of groups the short group leads the
  sequence, so it comes last here."""
  hiddens, kernel, labels, weights = residuals
  w = kernel.astype(hiddens[0].dtype)
  lead = hiddens[0].shape[0] % per_group

  def chunk_grads(_, xs):
    d_hiddens, dlogits = [], []
    for ct, hh, yy, ww in zip(cotangents, *xs):
      logits, product_vjp = jax.vjp(lambda h: h @ w, hh)
      (dl,) = jax.vjp(lambda lg: _chunk_sum(lg, yy, ww), logits)[1](ct)
      (d_hh,) = product_vjp(dl)
      dlogits.append(dl)
      d_hiddens.append(d_hh)
    return None, (tuple(d_hiddens), tuple(dlogits))

  def group_grads(d_kernel, xs):
    _, (d_hiddens, dlogits) = jax.lax.scan(chunk_grads, None, xs)
    for hh, dl in zip(xs[0], dlogits):
      (d_k,) = jax.vjp(lambda k: _group_logits(hh, k), kernel)[1](dl)
      d_kernel = d_kernel + d_k
    return d_kernel, d_hiddens

  xs = (hiddens, labels, weights)
  (d_kernel,) = sequence_lib.vary_like(
      hiddens[0], (jnp.zeros(kernel.shape, jnp.float32),))
  d_kernel, d_hiddens = jax.lax.scan(
      group_grads, d_kernel,
      jax.tree.map(lambda x: x[lead:].reshape(
          (-1, per_group) + x.shape[1:]), xs), reverse=True)
  # (groups, chunks a group, ...) -> (chunks, ...)
  d_hiddens = tuple(d.reshape((-1,) + d.shape[2:]) for d in d_hiddens)
  if lead:
    d_kernel, d_lead = group_grads(
        d_kernel, jax.tree.map(lambda x: x[:lead], xs))
    d_hiddens = tuple(jnp.concatenate(pair) for pair in zip(d_lead,
                                                            d_hiddens))
  return d_hiddens, d_kernel.astype(kernel.dtype), None, None


_scan_sums.defvjp(_scan_sums_fwd, _scan_sums_bwd)


def _log_likelihood_sums(hiddens, kernel, labels, weights, chunk_size: int,
                         weight_grad_rows: int):
  """Sum over all positions of the (weighted) label log-likelihood, one
  float32 scalar for each of the L heads that share ``kernel``:
  ``hiddens``, ``labels`` and ``weights`` are L-tuples of (B, T, D),
  (B, T) int32 and (B, T) float32 or None."""
  b, t, _ = hiddens[0].shape
  chunk = chunk_of(t, chunk_size)
  per_group = chunks_per_group(b * chunk, t // chunk, weight_grad_rows)
  # The written-out backward returns the kernel's cotangent as varying
  # as the hidden states are; inside a shard_map body the kernel is cast
  # to match here, and autodiff sums over the devices as it did.
  (kernel,) = sequence_lib.vary_like(hiddens[0], (kernel,))
  hiddens, labels, weights = jax.tree.map(
      lambda x: _chunked(x, chunk), (hiddens, labels, weights))
  return _scan_sums(hiddens, kernel, labels, weights, per_group)


def fused_softmax_xent(hidden, kernel, labels, chunk_size: int = 256,
                       weights=None,
                       weight_grad_rows: int = WEIGHT_GRAD_ROWS):
  """Mean next-token NLL from (hidden, kernel) with O(B*chunk*V) f32
  temps (and, in the backward pass, one group's dlogits in the model
  dtype).

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
  The weights are data: no gradient flows to them.

  ``weight_grad_rows`` is ``WEIGHT_GRAD_ROWS`` in every caller; the
  tests pass others to pin every schedule of chunks and groups against
  the monolithic oracle.
  """
  b, t, _ = hidden.shape
  if weights is not None:
    weights = weights.astype(jnp.float32)
  (total,) = _log_likelihood_sums(
      (hidden,), kernel, (labels.astype(jnp.int32),), (weights,),
      chunk_size, weight_grad_rows)
  if weights is None:
    return -total / (b * t)
  return -total / jnp.maximum(jnp.sum(weights), 1.0)


def fused_softmax_xent_pair(hiddens, kernel, labels, chunk_size: int = 256,
                            weight_grad_rows: int = WEIGHT_GRAD_ROWS):
  """Two next-token losses through ONE head kernel: ``hiddens`` is the
  pair (main, multi-token-prediction or None) of (B, T, D) final hidden
  states; both heads share ``kernel`` (D, V). Returns ``(main, mtp)``
  float32 scalars (``mtp`` None without its hidden states).

  The main head at position i predicts ``labels[i]``, the token after
  i. The MTP head at position i predicts the token after NEXT,
  ``labels[i + 1]``; position T-1 has no such label in the batch and is
  left out, so the MTP loss is the mean over B x (T-1) positions. One
  scan over sequence chunks computes both heads' logits chunk by chunk
  from the one cast of the kernel: neither loss ever holds a (B, T, V)
  tensor, forward or backward, and both heads' products of a group go
  into the one accumulator of the kernel's gradient."""
  main, mtp = hiddens
  if mtp is None:
    return fused_softmax_xent(main, kernel, labels, chunk_size,
                              weight_grad_rows=weight_grad_rows), None
  labels = labels.astype(jnp.int32)
  b, t, _ = main.shape
  last = jnp.arange(t) == t - 1
  mtp_weight = jnp.broadcast_to(jnp.where(last, 0.0, 1.0), (b, t))
  total_main, total_mtp = _log_likelihood_sums(
      (main, mtp), kernel, (labels, jnp.roll(labels, -1, axis=1)),
      (None, mtp_weight.astype(jnp.float32)), chunk_size, weight_grad_rows)
  return -total_main / (b * t), -total_mtp / (b * max(t - 1, 1))


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
                            chunk_size: int = 256,
                            weight_grad_rows: int = WEIGHT_GRAD_ROWS):
  """The memory-unbounded oracle: materialize the FULL (B, T, V) logits
  tensor, then reduce in the same chunk order as the fused scan.

  Plain autodiff, no ``custom_vjp``. The logits' VALUES are per-chunk
  matmuls concatenated into the full tensor, as the fused scan forms
  them; their GRADIENT flows through per-group matmuls
  (``chunks_per_group``; the short group leads), so the backward pass
  multiplies over a group's rows at once and accumulates the kernel
  gradient group-by-group in the fused head's order -- which is what
  makes the fused head's f32 gradients BIT-exact against it, not merely
  close (tests/test_fused_loss.py pins this; a backend's product of
  other shapes rounds otherwise). Peak memory is O(B*T*V): tests compile
  it to measure the logits-sized footprint the fused path eliminates.
  """
  labels = labels.astype(jnp.int32)
  b, t, _ = hidden.shape
  chunk = chunk_of(t, chunk_size)
  n = t // chunk
  per_group = chunks_per_group(b * chunk, n, weight_grad_rows)
  edges = [0] + list(range(n % per_group or per_group, n + 1, per_group))
  w = kernel.astype(hidden.dtype)
  by_chunk = jnp.concatenate(
      [hidden[:, i * chunk:(i + 1) * chunk] @ w for i in range(n)], axis=1)
  # A group's rows in the scan's own order (chunk, batch, position): the
  # sum over a product's rows is taken in the order they lie.
  by_group = jnp.concatenate(
      [_group_logits(_chunked(hidden[:, lo * chunk:hi * chunk], chunk),
                     kernel).swapaxes(0, 1).reshape(b, (hi - lo) * chunk, -1)
       for lo, hi in zip(edges, edges[1:])], axis=1)
  # The chunks' values, the groups' gradients (x - x is an exact zero).
  logits = jax.lax.stop_gradient(by_chunk) + (
      by_group - jax.lax.stop_gradient(by_group))
  total = jnp.zeros((), jnp.float32)
  for i in range(n):
    lg = logits[:, i * chunk:(i + 1) * chunk]
    yy = labels[:, i * chunk:(i + 1) * chunk]
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    total = total + jnp.sum(
        jnp.take_along_axis(logp, yy[..., None], axis=-1))
  return -total / (b * t)
