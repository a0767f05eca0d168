"""Sequence / context parallelism: ring attention + Ulysses all-to-all.

Beyond-reference capability. The reference predates ring attention and
splits nothing across the sequence axis (SURVEY 5.7: its only
sequence-dimension machinery is DeepSpeech2 utterance padding,
ref preprocessing.py:977-1112); on TPU, long-context work is
first-class, so the framework ships the two standard context-parallel
schedules as shard_map collectives over a named ``seq`` mesh axis:

* ``ring_attention`` -- blockwise attention with an online (streaming)
  softmax; K/V blocks rotate around the ring via ``lax.ppermute`` while
  every device keeps only its own Q block. Per-device score memory is
  O(Lq_local * Lk_local), so sequence length scales linearly with ring
  size. The schedule is the TPU-native form of Ring Attention (Liu et
  al.) -- ppermute rides the ICI ring; XLA overlaps the permute with
  the block matmuls.
* ``ulysses_attention`` -- the all-to-all schedule (DeepSpeed-Ulysses):
  two ``lax.all_to_all`` calls swap the sharded axis seq<->heads, local
  full attention runs on every device over the whole sequence for its
  head slice. Cheaper collectives for moderate L when heads divide the
  axis size.

Both are differentiable (ppermute/all_to_all have transpose rules, the
online softmax is plain jnp), accumulate in float32 regardless of input
dtype, and match ``full_attention`` to numerical tolerance -- pinned by
tests/test_sequence_parallel.py on the 8-device virtual mesh.

On ONE chip the attention core of the language models is
``pallas_flash_attention``: the library's splash kernel with its fused
backward behind this module's (B, L, H, D) layout, tiled by
``flash_plan`` from the shapes. Its mask is causal, full, or a causal
BAND of ``window`` keys whose out-of-band tiles the kernel skips, forward
and backward; k and v may hold fewer heads than q (grouped queries,
never repeated in memory). ``full_attention`` is its CPU form, with the
same band and the same grouping.

Memory: every block update runs under ``jax.checkpoint``
(flash-style recompute-in-backward), so the blockwise bound holds for
TRAINING too -- autodiff recomputes the per-block score/probability
tensors instead of saving them as residuals; what the backward pass
stores per step is the O(block) carry/operand set, not the score tile
(pinned by test_blockwise_grad_memory_is_blockwise).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

SEQ_AXIS = "seq"

# Finite stand-in for -inf: exp(_NEG - _NEG) stays defined (=1, zeroed
# by the explicit mask on p) where a fully-masked row would otherwise
# produce NaN via inf - inf.
_NEG = -1e30


def full_attention(q, k, v, causal: bool = False,
                   scale: Optional[float] = None, segment_ids=None,
                   window: Optional[int] = None):
  """Plain O(L^2) multi-head attention; (batch, seq, heads, head_dim).

  The single-device reference the parallel schedules are tested
  against, and the local inner step of ``ulysses_attention``.

  ``segment_ids`` (B, L) int: packed-sequence masking -- a query
  attends only keys of ITS segment (equality, the Pallas SegmentIds
  convention: padding id 0 attends padding, so no row is ever fully
  masked and the causal diagonal keeps every row finite).

  ``window`` (with ``causal``): query i sees key j iff
  ``0 <= i - j < window``, the query's own position included. k and v
  may hold fewer heads than q (grouped queries): query head n reads key
  head ``n // (q heads / key heads)``.
  """
  d = q.shape[-1]
  scale = (1.0 / math.sqrt(d)) if scale is None else scale
  if k.shape[2] != q.shape[2]:
    k, v = (jnp.repeat(x, q.shape[2] // k.shape[2], axis=2) for x in (k, v))
  s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  mask = None
  if causal:
    lq, lk = q.shape[1], k.shape[1]
    mask = (jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :])[None, None]
    if window is not None:
      mask &= (jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :] <
               window)[None, None]
  elif window is not None:
    raise ValueError("a window is a causal band: pass causal=True")
  if segment_ids is not None:
    seg_mask = (segment_ids[:, :, None] ==
                segment_ids[:, None, :])[:, None]
    mask = seg_mask if mask is None else (mask & seg_mask)
  if mask is not None:
    s = jnp.where(mask, s, _NEG)
  p = jax.nn.softmax(s, axis=-1)
  out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
  return out.astype(q.dtype)


def vary_like(ref, arrays, default_axes=(), extra_axes=()):
  """pcast zero-initialised accumulators to ``ref``'s varying set.

  Inside a shard_map body the Q operand is device-varying and so are
  the softmax accumulators after one update; constants must be pcast
  up front or scan/cond type checks reject the carry. ``default_axes``
  applies when ref carries no vma information (identity if also empty);
  ``extra_axes`` are unioned in regardless (e.g. the pipeline's stage
  axis, which the input does not vary on but the carries will). Only
  the axes each array is MISSING are pcast -- pcast rejects
  already-varying axes.
  """
  want = (set(getattr(ref.aval, "vma", ()) or default_axes)
          | set(extra_axes))
  if not want:
    return arrays

  def cast(x):
    missing = tuple(sorted(want - set(getattr(x.aval, "vma", ()))))
    return lax.pcast(x, missing, to="varying") if missing else x

  return tuple(cast(x) for x in arrays)


def _block_update(q, k, v, m, l, o, scale, mask):
  """One online-softmax accumulation step over a K/V block.

  q: (B,Tq,H,D); k,v: (B,Tk,H,D); running max m and denominator l:
  (B,H,Tq); running unnormalised output o: (B,Tq,H,D) float32.

  MXU-native mixed precision: the matmul MULTIPLICANDS stay in the
  input dtype (bf16 on TPU runs at full MXU rate) and only the
  ACCUMULATION is f32, via preferred_element_type -- upcasting the
  inputs to f32 first would force f32 matmuls at a fraction of peak
  (the signature of the round-4 ~29 TFLOP/s long-context measurement).
  The probability tile is cast to v's dtype for the PV matmul, the
  standard flash-attention precision class; softmax statistics (max,
  exp, denominators) remain f32 throughout. With f32 inputs every step
  is bit-identical to the previous all-f32 form.
  """
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                 preferred_element_type=jnp.float32) * scale
  if mask is not None:
    s = jnp.where(mask, s, _NEG)
  m_new = jnp.maximum(m, jnp.max(s, axis=-1))
  corr = jnp.exp(m - m_new)                      # (B,H,Tq)
  p = jnp.exp(s - m_new[..., None])              # (B,H,Tq,Tk)
  if mask is not None:
    # Where the whole row is masked m_new == _NEG and exp(s-m_new) == 1;
    # zero those entries so they never enter l or o.
    p = jnp.where(mask, p, 0.0)
  l_new = l * corr + jnp.sum(p, axis=-1)
  pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                  preferred_element_type=jnp.float32)
  o_new = o * corr.swapaxes(1, 2)[..., None] + pv
  return m_new, l_new, o_new


def _block_update_remat(q, k, v, m, l, o, scale, offsets=None,
                        prevent_cse=True, seg_q=None, seg_k=None):
  """``_block_update`` with recompute-in-backward (flash-style remat).

  Without this, autodiff saves the (.., Tq, Tk) score/probability
  tensors of EVERY block step as residuals -- ~5 full score-tensor
  copies across a scan/ring, erasing the blockwise memory win exactly
  when it matters (training). jax.checkpoint drops those residuals and
  recomputes the block matmuls in the backward pass; what remains per
  step is the O(Tq + Tk) carry/operand set.

  ``offsets`` is None (no mask) or the scalar (q_off, k_off) GLOBAL
  position offsets of the two blocks; the causal mask is rebuilt
  INSIDE the checkpointed region from them, so the per-step residual
  is two scalars -- passing a materialised (Tq, Tk) mask as an operand
  would make checkpoint save it, stacking an O(L^2) bool residual
  across the scan/ring. ``seg_q``/``seg_k`` are the two blocks'
  (B, Tq)/(B, Tk) packed segment ids; the cross-segment mask (id
  equality, the Pallas SegmentIds convention) is likewise rebuilt
  inside the checkpointed region from the O(Tq + Tk) id operands.
  ``prevent_cse=False`` is for lax.scan bodies, where scan already
  prevents the problematic CSE (per the jax.checkpoint docs) and the
  default would only wall off fusion.
  """
  def inner(q_, k_, v_, m_, l_, o_, off, sq, sk):
    if off is None:
      mask = None
    else:
      q_off, k_off = off
      qpos = q_off + jnp.arange(q_.shape[1])
      kpos = k_off + jnp.arange(k_.shape[1])
      mask = (qpos[:, None] >= kpos[None, :])[None, None]
    if sq is not None:
      seg_mask = (sq[:, :, None] == sk[:, None, :])[:, None]
      mask = seg_mask if mask is None else (mask & seg_mask)
    return _block_update(q_, k_, v_, m_, l_, o_, scale, mask)

  return jax.checkpoint(inner, prevent_cse=prevent_cse)(
      q, k, v, m, l, o, offsets, seg_q, seg_k)


def _scan_kv_blocks(q, k, v, m, l, o, scale, block: int, offsets):
  """Accumulate a LOCAL K/V shard in ``block``-sized sub-blocks.

  The inner level of the two-level tiling inside one ring step: the
  softmax carries stay q-sized while each score tile is (Tq, block).
  ``offsets`` is None (unmasked) or the scalar (q_off, k_off) GLOBAL
  offsets of q and of the K/V shard's first position; causal sub-blocks
  strictly in the q rows' future are skipped via lax.cond.
  """
  b, tk, h, d = k.shape
  if tk % block != 0:
    raise ValueError(f"local K/V length {tk} not divisible by inner "
                     f"block {block}")
  nb = tk // block
  kb = k.reshape(b, nb, block, h, d).swapaxes(0, 1)
  vb = v.reshape(b, nb, block, h, d).swapaxes(0, 1)

  def stepf(carry, inp):
    j, kj, vj = inp
    if offsets is None:
      return _block_update_remat(q, kj, vj, *carry, scale, None,
                                 prevent_cse=False), None
    q_off, k_off = offsets
    has_work = k_off + j * block <= q_off + q.shape[1] - 1
    carry = lax.cond(
        has_work,
        lambda c: _block_update_remat(q, kj, vj, *c, scale,
                                      (q_off, k_off + j * block),
                                      prevent_cse=False),
        lambda c: c, carry)
    return carry, None

  (m, l, o), _ = lax.scan(stepf, (m, l, o), (jnp.arange(nb), kb, vb))
  return m, l, o


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   inner_block: Optional[int] = None):
  """Blockwise ring attention inside a shard_map body.

  Arguments are the LOCAL sequence shards, (batch, seq/n, heads,
  head_dim); the result is the local shard of exact (not approximate)
  attention over the full sequence. ``causal`` masks by GLOBAL
  position: block offsets follow each K/V block as it travels the ring.

  The n-step rotation is a Python loop: n is the static mesh-axis size,
  so the program holds n ppermute+matmul pairs XLA can pipeline --
  while-loop carries would serialize against the permute instead.

  ``inner_block`` composes the single-chip two-level tiling into each
  ring step: the local K/V shard is scanned in sub-blocks so the
  per-device score tile is (Tq, inner_block) instead of (Tq, Tk) --
  the multi-chip long-context memory knob (at 64k over 8 devices the
  per-step score tile drops from 8k x 8k to 8k x inner_block).
  """
  n = lax.axis_size(axis_name)
  idx = lax.axis_index(axis_name)
  tq, tk = q.shape[1], k.shape[1]
  d = q.shape[-1]
  scale = (1.0 / math.sqrt(d)) if scale is None else scale

  b, h = q.shape[0], q.shape[2]
  # Under a composed mesh (e.g. dp x sp x tp) q varies over more axes
  # than the ring's own, and the accumulators must match from step 0.
  m, l, o = vary_like(
      q,
      (jnp.full((b, h, tq), _NEG, jnp.float32),
       jnp.zeros((b, h, tq), jnp.float32),
       jnp.zeros((b, tq, h, d), jnp.float32)),
      default_axes=(axis_name,))

  kc, vc = k, v
  perm = [(i, (i + 1) % n) for i in range(n)]
  for step in range(n):
    # After `step` +1-shifts, device idx holds the block that started on
    # device (idx - step) mod n; global key positions follow it.
    if causal:
      src = (idx - step) % n
      # A block strictly in this device's future (src > idx) is fully
      # masked; skip its matmuls entirely. The predicate is per-device,
      # so the conditional runs the update only where work exists --
      # without this, (n-1)/2n of the ring's block updates would be
      # dead FLOPs at large n. (The zigzag variant balances the skip
      # across devices.)
      if inner_block is None:
        update = lambda ops: _block_update_remat(
            *ops, scale, (idx * tq, src * tk))
      else:
        update = lambda ops: _scan_kv_blocks(
            *ops, scale, inner_block, (idx * tq, src * tk))
      m, l, o = lax.cond(
          src <= idx, update,
          lambda ops: (ops[3], ops[4], ops[5]),
          (q, kc, vc, m, l, o))
    elif inner_block is None:
      m, l, o = _block_update_remat(q, kc, vc, m, l, o, scale, None)
    else:
      m, l, o = _scan_kv_blocks(q, kc, vc, m, l, o, scale,
                                inner_block, None)
    if step != n - 1:
      kc = lax.ppermute(kc, axis_name, perm)
      vc = lax.ppermute(vc, axis_name, perm)

  out = o / jnp.maximum(l, 1e-30).swapaxes(1, 2)[..., None]
  return out.astype(q.dtype)


def zigzag_order(seq_len: int, n: int):
  """Permutation putting stripe pair (j, 2n-1-j) on device j.

  The causal load-balance placement (Megatron context-parallel style):
  the global sequence is cut into 2n stripes; device j's contiguous
  shard becomes [stripe j, stripe 2n-1-j], pairing an early stripe
  (little causal work) with a late one (much causal work) so every
  device executes ~2 block updates per ring step instead of device
  n-1 executing all n. Apply with jnp.take along the sequence axis
  before sharding; invert with ``zigzag_inverse``.
  """
  if seq_len % (2 * n) != 0:
    raise ValueError(f"seq len {seq_len} not divisible by 2n={2 * n}")
  t = seq_len // (2 * n)
  order = []
  for j in range(n):
    order.extend(range(j * t, (j + 1) * t))
    order.extend(range((2 * n - 1 - j) * t, (2 * n - j) * t))
  return jnp.asarray(order)


def zigzag_inverse(seq_len: int, n: int):
  order = zigzag_order(seq_len, n)
  inv = jnp.zeros_like(order)
  return inv.at[order].set(jnp.arange(seq_len))


def ring_attention_zigzag(q, k, v, axis_name: str = SEQ_AXIS,
                          scale: Optional[float] = None,
                          inner_block: Optional[int] = None):
  """Causal ring attention over ZIGZAG-placed shards, load-balanced.

  Local shards are [stripe idx, stripe 2n-1-idx] of the zigzag_order
  permutation (length 2t each). Per ring step each device runs two
  block updates (three on its one diagonal step src == idx) -- (2n+1)
  total per device, identical for every idx -- where the contiguous
  placement leaves device n-1 doing all n updates while device 0 idles
  (the wall-time bound of the lockstep ring). Returns the local shard
  of exact causal attention in the same zigzag layout.
  """
  n = lax.axis_size(axis_name)
  idx = lax.axis_index(axis_name)
  tq2 = q.shape[1]
  if tq2 % 2 != 0:
    raise ValueError(f"zigzag local shard length must be even, got {tq2}")
  t = tq2 // 2
  d = q.shape[-1]
  scale = (1.0 / math.sqrt(d)) if scale is None else scale
  b, h = q.shape[0], q.shape[2]
  z = 2 * n - 1  # stripe index of the latest stripe

  # Split the local shard into its early (stripe idx) and late
  # (stripe z-idx) halves; each accumulates independently.
  q1, q2 = q[:, :t], q[:, t:]
  acc1 = vary_like(
      q, (jnp.full((b, h, t), _NEG, jnp.float32),
          jnp.zeros((b, h, t), jnp.float32),
          jnp.zeros((b, t, h, d), jnp.float32)),
      default_axes=(axis_name,))
  acc2 = tuple(jnp.copy(x) for x in acc1)

  if inner_block is None:
    upd = lambda qq, kk, vv, acc, offs: _block_update_remat(
        qq, kk, vv, *acc, scale, offs)
  else:
    # Stripe-sized tiles shrink to (t, inner_block) -- the same knob as
    # the contiguous ring's, but dividing the STRIPE length t (= local
    # shard / 2), not the shard length.
    if t % inner_block != 0:
      raise ValueError(
          f"zigzag inner_block must divide the stripe length {t} "
          f"(= local shard {tq2} / 2), got {inner_block}")
    upd = lambda qq, kk, vv, acc, offs: _scan_kv_blocks(
        qq, kk, vv, *acc, scale, inner_block, offs)

  kc, vc = k, v
  perm = [(i, (i + 1) % n) for i in range(n)]
  for step in range(n):
    src = (idx - step) % n
    k1, k2 = kc[:, :t], kc[:, t:]
    v1, v2 = vc[:, :t], vc[:, t:]
    # Stripe indices: q1 -> idx, q2 -> z-idx; kv1 -> src, kv2 -> z-src.
    # q1 vs kv2 (z-src >= n > idx) is ALWAYS fully masked: skipped
    # statically. q2 vs kv1 (z-idx >= n > src) is ALWAYS fully
    # unmasked: runs mask-free. The two same-kind pairs gate on the
    # device-varying stripe comparison (diagonal => triangular mask).
    acc1 = lax.cond(
        idx >= src,
        lambda ops: upd(q1, k1, v1, ops, (idx * t, src * t)),
        lambda ops: ops, acc1)
    acc2 = upd(q2, k1, v1, acc2, None)
    acc2 = lax.cond(
        src >= idx,
        lambda ops: upd(q2, k2, v2, ops,
                        ((z - idx) * t, (z - src) * t)),
        lambda ops: ops, acc2)
    if step != n - 1:
      kc = lax.ppermute(kc, axis_name, perm)
      vc = lax.ppermute(vc, axis_name, perm)

  def finish(acc):
    m_, l_, o_ = acc
    return o_ / jnp.maximum(l_, 1e-30).swapaxes(1, 2)[..., None]

  out = jnp.concatenate([finish(acc1), finish(acc2)], axis=1)
  return out.astype(q.dtype)


def blockwise_attention(q, k, v, block_size: int, causal: bool = False,
                        scale: Optional[float] = None,
                        q_block_size: Optional[int] = None,
                        segment_ids=None):
  """Single-device flash-style attention: lax.scan over K/V blocks with
  the same online softmax as the ring schedule, so forward peak memory
  is O(L * block) instead of O(L^2) and long contexts fit in HBM on one
  chip. Exact (not windowed): every query still attends to every key.
  The scan body is rematerialised (``_block_update_remat``), so the
  backward pass recomputes each block's scores rather than stacking
  nblk full-score residuals; its stored state is the scan carry stack,
  O(L^2 * D / block) -- ~5*block/D x smaller than unrematerialised
  residuals (block=512, D=64: ~40x).

  ``q_block_size`` selects the two-level (flash-style) tiling: an
  outer scan over q blocks, an inner scan over K/V blocks, so the
  softmax accumulators are (.., q_block) tiles instead of full-length
  (.., L) arrays -- the single-level path re-reads O(L)-sized m/l/o
  from HBM on every K/V step, which is what made the measured
  long-context MFU bandwidth-lean (PERF.md round 4). Under ``causal``
  the inner scan also SKIPS K/V blocks strictly in the q block's
  future via lax.cond, recovering the ~2x of FLOPs the single-level
  path spends on fully-masked tiles.

  ``segment_ids`` (B, L) int engages packed-sequence masking: queries
  attend only keys of their own segment (id equality, the Pallas
  SegmentIds convention -- padding id 0 attends padding, so no row is
  ever fully masked). The two-level path additionally SKIPS any K/V
  tile that is fully cross-segment for EVERY batch row (per-block
  segment-id min/max interval test via lax.cond) -- first-fit packing
  lays segments contiguously with padding at the row tail, so most
  (q block, kv block) pairs outside the block-diagonal band carry no
  same-segment pair and their matmuls are dead FLOPs; this is what
  lets packing COMPOSE with the flash-style schedule instead of
  falling back to a dense (L, L) mask.

  (B, L, H, D) -> (B, L, H, D); L % block_size == 0. Composes with
  ring_attention -- inside a ring step each device could scan its local
  block -- but is exposed standalone as the single-chip long-context
  path.
  """
  b, l, h, d = q.shape
  if l % block_size != 0:
    raise ValueError(f"seq len {l} not divisible by block {block_size}")
  nblk = l // block_size
  scale_ = (1.0 / math.sqrt(d)) if scale is None else scale

  kb = k.reshape(b, nblk, block_size, h, d).swapaxes(0, 1)
  vb = v.reshape(b, nblk, block_size, h, d).swapaxes(0, 1)
  segb = seg_min = seg_max = None
  if segment_ids is not None:
    # Per-KV-block segment ids (nblk, B, block) plus their per-row
    # min/max -- the interval test the tile-skip cond keys on.
    segb = segment_ids.reshape(b, nblk, block_size).swapaxes(0, 1)
    seg_min = segb.min(axis=2)  # (nblk, B)
    seg_max = segb.max(axis=2)

  if q_block_size is None:
    m0, l0, o0 = vary_like(
        q,
        (jnp.full((b, h, l), _NEG, jnp.float32),
         jnp.zeros((b, h, l), jnp.float32),
         jnp.zeros((b, l, h, d), jnp.float32)))

    def step(carry, inp):
      m, acc_l, o = carry
      j, kj, vj, sj = inp
      offsets = (0, j * block_size) if causal else None
      m, acc_l, o = _block_update_remat(q, kj, vj, m, acc_l, o, scale_,
                                        offsets, prevent_cse=False,
                                        seg_q=(segment_ids
                                               if segb is not None
                                               else None),
                                        seg_k=sj)
      return (m, acc_l, o), None

    (m, acc_l, o), _ = lax.scan(
        step, (m0, l0, o0), (jnp.arange(nblk), kb, vb, segb))
    out = o / jnp.maximum(acc_l, 1e-30).swapaxes(1, 2)[..., None]
    return out.astype(q.dtype)

  if l % q_block_size != 0:
    raise ValueError(
        f"seq len {l} not divisible by q block {q_block_size}")
  nq = l // q_block_size
  qb = q.reshape(b, nq, q_block_size, h, d).swapaxes(0, 1)
  sqb = None
  if segb is not None:
    sqb = segment_ids.reshape(b, nq, q_block_size).swapaxes(0, 1)

  def q_step(_, q_inp):
    if segb is None:
      qi, qi_blk = q_inp
      sq_blk = None
    else:
      qi, qi_blk, sq_blk = q_inp
      q_min, q_max = sq_blk.min(axis=1), sq_blk.max(axis=1)  # (B,)
    acc0 = vary_like(
        q,
        (jnp.full((b, h, q_block_size), _NEG, jnp.float32),
         jnp.zeros((b, h, q_block_size), jnp.float32),
         jnp.zeros((b, q_block_size, h, d), jnp.float32)))

    def kv_step(carry, kv_inp):
      if segb is None:
        j, kj, vj = kv_inp
        sj = None
      else:
        j, kj, vj, sj, k_min, k_max = kv_inp

      def do(c):
        offs = (qi * q_block_size, j * block_size) if causal else None
        return _block_update_remat(qi_blk, kj, vj, *c, scale_, offs,
                                   prevent_cse=False, seg_q=sq_blk,
                                   seg_k=sj)

      has_work = None
      if causal:
        # K/V block j is strictly in this q block's future iff its
        # first key position exceeds the q block's last row.
        has_work = j * block_size <= qi * q_block_size + (
            q_block_size - 1)
      if segb is not None:
        # The tile is fully cross-segment when NO batch row's q-block
        # segment interval intersects its kv-block interval (segments
        # are contiguous per row, so min/max intervals are exact);
        # such a tile is all-masked and its matmuls are skipped.
        seg_work = jnp.any((k_min <= q_max) & (k_max >= q_min))
        has_work = seg_work if has_work is None else (has_work &
                                                      seg_work)
      if has_work is not None:
        carry = lax.cond(has_work, do, lambda c: c, carry)
      else:
        carry = do(carry)
      return carry, None

    kv_xs = ((jnp.arange(nblk), kb, vb) if segb is None else
             (jnp.arange(nblk), kb, vb, segb, seg_min, seg_max))
    (m, acc_l, o), _ = lax.scan(kv_step, acc0, kv_xs)
    out = o / jnp.maximum(acc_l, 1e-30).swapaxes(1, 2)[..., None]
    return None, out

  q_xs = ((jnp.arange(nq), qb) if segb is None else
          (jnp.arange(nq), qb, sqb))
  _, outs = lax.scan(q_step, None, q_xs)
  # (nq, B, qb, H, D) -> (B, L, H, D)
  return outs.swapaxes(0, 1).reshape(b, l, h, d).astype(q.dtype)


def decode_attention(q, k, v, pos, block: Optional[int] = None,
                     impl: str = "tiled", scale: Optional[float] = None,
                     cpu_fallback: Optional[bool] = None,
                     exact: bool = False, q_block: Optional[int] = None,
                     page_table=None):
  """Single-query attention over a KV ring buffer -- the serving decode
  step's core (serving/decode.py threads the cache through it).

  ``q`` is the current token's query, (B, 1, H, D); ``k``/``v`` are the
  (B, T, H, D) ring buffers with the current token's K/V already
  written; ``pos`` (B,) int32 is each slot's absolute position. A key
  slot ``s`` participates iff ``s <= pos[b]`` -- masked slots
  contribute EXACTLY zero on both paths (the ``_NEG`` -> zeroed-p /
  exp-underflow arithmetic), so stale ring contents and a foreign
  packed-prefill neighbor never perturb the result.

  ``impl='tiled'`` runs the ``_block_update`` online softmax over
  ``block``-sized key blocks; ``impl='flash'`` is the Pallas flash
  kernel's decode mode on TPU (SegmentIds masking, q length 1) with the
  :func:`full_attention`-style masked softmax as the CPU fallback --
  the same fallback split as :func:`pallas_flash_attention`.

  ``exact=True`` is the oracle mode: the single query is scattered into
  a zero q tile of the FULL ring length and run through the exact
  full-sequence attention program (:func:`blockwise_attention` /
  :func:`full_attention` -- identical shapes, identical op schedule),
  then its one row is gathered back. Per-row results of a fixed-shape
  XLA program are deterministic and row-independent, so exact-mode
  decode at position ``p`` is BIT-IDENTICAL to row ``p`` of the full
  forward -- the KV-cache correctness contract tests/test_serving.py
  pins. The fast default (``exact=False``) computes the 1-row program
  instead; XLA schedules the (1, T) contraction differently from the
  (T, T) one, so it agrees to float rounding (~1e-6 rel), not bitwise
  -- ~T x cheaper, the production serving path.

  ``page_table`` switches on the PAGED KV layout (the vLLM block-table
  idea on JAX gather indices; serving/decode.py paged caches): ``k``/
  ``v`` are then fixed-size page POOLS (P, page, H, D) shared across
  slots, and ``page_table`` (B, pages_per_slot) int32 maps each slot's
  logical page ``j`` to a pool row. The fast path is the SAME
  ``_block_update`` online-softmax scan as the dense tiled schedule
  with the reshape-slice replaced by a pool gather and the block size
  pinned to the page size -- per-block inputs are value-identical to a
  dense ring holding the same tokens, which is the paged/dense
  bit-identity contract tests/test_serving_variants.py pins at gemm
  shapes. Entries of unallocated table slots point at pool row 0 (the
  never-allocated scratch page); the position mask makes them
  contribute exactly zero, same as stale dense ring rows. Paged fast
  mode always runs the tiled gather schedule (the Pallas flash kernel
  has no block-table mode here); ``exact=True`` gathers the dense
  (B, T, H, D) view back out of the pool first and runs the dense
  oracle on it -- oracle/test mode only, since materializing the dense
  slab is exactly what paging exists to avoid.
  """
  b, tq, h, d = q.shape
  scale = (1.0 / math.sqrt(d)) if scale is None else scale
  if impl not in ("tiled", "flash"):
    raise ValueError(f"impl must be 'tiled' or 'flash', got {impl!r}")
  if page_table is not None:
    page = k.shape[1]
    pages_per_slot = page_table.shape[1]
    if exact:
      # Dense-view reconstruction: pool rows gathered back into each
      # slot's (T, page) layout. k[page_table] is (B, pps, page, H, D).
      kd = k[page_table].reshape(b, pages_per_slot * page, k.shape[2],
                                 k.shape[3])
      vd = v[page_table].reshape(b, pages_per_slot * page, v.shape[2],
                                 v.shape[3])
      return decode_attention(q, kd, vd, pos, block=block, impl=impl,
                              scale=scale, cpu_fallback=cpu_fallback,
                              exact=True, q_block=q_block)
    m0 = jnp.full((b, k.shape[2], tq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, k.shape[2], tq), jnp.float32)
    o0 = jnp.zeros((b, tq, k.shape[2], d), jnp.float32)

    def page_step(carry, j):
      ids = lax.dynamic_index_in_dim(page_table, j, axis=1,
                                     keepdims=False)       # (B,)
      kj, vj = k[ids], v[ids]                    # (B, page, H, D)
      mask = (pos[:, None, None, None] >=
              (j * page + jnp.arange(page))[None, None, None, :])
      return _block_update(q, kj, vj, *carry, scale, mask), None

    (m, l, o), _ = lax.scan(page_step, (m0, l0, o0),
                            jnp.arange(pages_per_slot))
    out = o / jnp.maximum(l, 1e-30).swapaxes(1, 2)[..., None]
    return out.astype(q.dtype)
  t = k.shape[1]
  if exact:
    # Scatter row clamped to the LAST ring row once pos wraps past the
    # buffer: the causal mask at row t-1 admits every slot, which is
    # exactly the fast path's valid set for a wrapped ring (all slots
    # hold trailing-window keys). Below the wrap the row IS pos and
    # the full-forward graph identity holds bitwise; past it the mode
    # degrades to the same windowed semantics as the fast path.
    rows = jnp.minimum(pos, t - 1)
    qfull = jnp.zeros((b, t, h, d), q.dtype)
    qfull = qfull.at[jnp.arange(b), rows].set(q[:, 0])
    if impl == "flash":
      # The kernel's own reference form (pallas_flash_attention's CPU
      # fallback) -- the op graph the flash full forward executes off
      # TPU, so the oracle holds where it can actually run.
      out = full_attention(qfull, k, v, causal=True, scale=scale)
    else:
      blk = min(block or t, t)
      out = blockwise_attention(qfull, k, v, block_size=blk, causal=True,
                                scale=scale,
                                q_block_size=min(q_block or blk, t))
    return out[jnp.arange(b), rows][:, None]
  kpos = jnp.arange(t)
  if impl == "flash":
    if cpu_fallback is None:
      cpu_fallback = jax.default_backend() != "tpu"
    if not cpu_fallback:
      from jax.experimental.pallas.ops.tpu import flash_attention as fa
      seg = fa.SegmentIds(
          q=jnp.ones((b, tq), jnp.int32),
          kv=(kpos[None, :] <= pos[:, None]).astype(jnp.int32))
      blk = min(block or t, tq, t)
      qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
      out = fa.flash_attention(qt, kt, vt, None, seg, causal=False,
                               sm_scale=scale,
                               block_sizes=uniform_flash_block_sizes(blk))
      return out.swapaxes(1, 2).astype(q.dtype)
    # CPU fallback: the full_attention op sequence, row-for-row.
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (kpos[None, None, None, :] <= pos[:, None, None, None])
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
  blk = min(block or t, t)
  if t % blk != 0:
    raise ValueError(f"cache length {t} not divisible by block {blk}")
  nb = t // blk
  kb = k.reshape(b, nb, blk, h, d).swapaxes(0, 1)
  vb = v.reshape(b, nb, blk, h, d).swapaxes(0, 1)
  m0 = jnp.full((b, h, tq), _NEG, jnp.float32)
  l0 = jnp.zeros((b, h, tq), jnp.float32)
  o0 = jnp.zeros((b, tq, h, d), jnp.float32)

  def step(carry, inp):
    j, kj, vj = inp
    # Mask rebuilt per block from the scalar offset, exactly as the
    # training path's _block_update_remat does; fully-masked blocks
    # no-op bitwise (m stays, corr == 1, p == 0), which is why decode
    # over the FULL ring matches the full forward's cond-skipped scan.
    mask = (pos[:, None, None, None] >=
            (j * blk + jnp.arange(blk))[None, None, None, :])
    return _block_update(q, kj, vj, *carry, scale, mask), None

  (m, l, o), _ = lax.scan(step, (m0, l0, o0), (jnp.arange(nb), kb, vb))
  out = o / jnp.maximum(l, 1e-30).swapaxes(1, 2)[..., None]
  return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS,
                      causal: bool = False,
                      scale: Optional[float] = None,
                      local_block: Optional[int] = None):
  """All-to-all (Ulysses) attention inside a shard_map body.

  Sequence-sharded (B, L/n, H, D) inputs are re-sharded over heads --
  one tiled all_to_all each -- so every device runs full attention over
  the complete sequence for H/n heads, then the output is swapped back.
  Requires heads % axis_size == 0.

  ``local_block`` replaces the O(L^2) local score tensor with the
  blockwise (flash-style) schedule: without it, Ulysses at long L is
  exactly the full-attention OOM the blockwise path exists to avoid
  (the ring schedule never materialises it; this closes the same hole
  for the all-to-all schedule).
  """
  n = lax.axis_size(axis_name)
  h = q.shape[2]
  if h % n != 0:
    raise ValueError(
        f"ulysses_attention needs heads % axis_size == 0, got heads={h} "
        f"over {n} '{axis_name}' devices; use ring_attention for "
        f"head-count-agnostic sequence parallelism")

  def seq_to_heads(x):
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)

  qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
  if local_block is None:
    out = full_attention(qh, kh, vh, causal=causal, scale=scale)
  else:
    out = blockwise_attention(qh, kh, vh, block_size=local_block,
                              causal=causal, scale=scale,
                              q_block_size=local_block)
  return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)


def uniform_flash_block_sizes(block: int):
  """All-fields-equal BlockSizes for the library's older flash kernel,
  which ``decode_attention`` calls forward only (one query a slot: the
  kernel of ``pallas_flash_attention`` tiles queries by 128 and more)."""
  from jax.experimental.pallas.ops.tpu import flash_attention as fa
  return fa.BlockSizes(
      block_q=block, block_k_major=block, block_k=block, block_b=1,
      block_q_major_dkv=block, block_k_major_dkv=block,
      block_k_dkv=block, block_q_dkv=block, block_k_major_dq=block,
      block_k_dq=block, block_q_dq=block)


# The largest tile of queries or of keys (rows x head size, the head size
# padded to the lanes) that the kernels hold in the v5e's VMEM beside
# their other tiles, at head size 256 (the TPU's compiler, no chip
# needed: tests/test_tpu_step_compile.py). Forward: 1024 queries and 1024
# keys fit together; 2048 keys fit beside 512 queries and not beside
# 1024; 4096 keys do not fit. Backward: 1024 keys (with their values, two
# float32 accumulators and the two gradients' tiles) fit beside 512
# queries; 2048 do not, with 512 or with 256 queries; nor do 1024 beside
# 1024 queries. At head size 128 twice the rows fit each.
_FLASH_TILE = 1024 * 256
_FLASH_DEFAULT_BLOCK = 512
_LANES = 128
# What a score tile and a partial dq cost the backward of a BANDED mask
# on the v5e, for ``flash_plan``'s choice of the keys held (a visited
# tile is computed whole, so larger tiles compute more of what lies
# outside the band; smaller ones leave more partial dq to write and to
# sum): the kernels' own rate in the glm-4.7-flash cell, 69% of 197
# TFLOP/s over 5 products, and 819 GB/s written and read once each
# (PERF.md section 6, PR 30 and PR 32).
_BAND_FLOPS_PER_S = 0.69 * 197e12
_BAND_BYTES_PER_S = 819e9


@dataclasses.dataclass(frozen=True)
class FlashPlan:
  """What ``pallas_flash_attention`` runs for one shape: ``flash_plan``
  decides it from the shapes alone, and a model states it in its run's
  ``stats["attention"]``.

  ``backward_kernel_passes``: 1 = ONE backward kernel forms each (query
  block, key block) tile's scores, probabilities and probability
  gradient once and takes dq, dk and dv from them (5 products of
  seq^2 x head size with the forward's 2); 0 = no kernel at all, the
  materialised scores of ``full_attention`` (off the TPU).
  ``block`` is the tile of scores either kernel computes at a time
  (block x block), and the backward's tile of queries. The forward
  fetches ``block_q`` queries and ``block_kv`` keys a grid step.
  ``block_kv_dkv`` keys stay in VMEM across a backward sweep over the
  queries, which leaves ``dq_partials`` = kv_len / block_kv_dkv partial
  dq of the queries' shape, summed by XLA outside the kernel."""
  backward_kernel_passes: int
  block: int = 0
  block_q: int = 0
  block_kv: int = 0
  block_kv_dkv: int = 0
  dq_partials: int = 0


def band_tiles(q_len: int, kv_len: int, block_q: int, block_kv: int,
               window: Optional[int] = None) -> int:
  """How many (block_q x block_kv) tiles of the scores hold a pair
  (i, j) with ``0 <= i - j < window`` (no window: the causal half): the
  tiles a kernel over that mask visits; the others it skips whole."""
  first_q = np.arange(0, q_len, block_q)[:, None]
  first_k = np.arange(0, kv_len, block_kv)[None, :]
  seen = first_q + block_q - 1 >= first_k          # some i >= some j
  if window is not None:
    seen &= first_q - (first_k + block_kv - 1) < window
  return int(seen.sum())


def plan_tiles(q_len: int, kv_len: int, plan: FlashPlan,
               window: Optional[int] = None) -> int:
  """The score tiles (``plan.block`` x ``plan.block``) that the kernels
  of ``plan`` visit for one head of one sequence under a causal mask of
  ``window`` keys (None: the causal half), the forward's grid and the
  fused backward's together: a visited tile of either grid counts at its
  area, whatever part of it the mask leaves."""
  forward = band_tiles(q_len, kv_len, plan.block_q, plan.block_kv, window)
  backward = band_tiles(q_len, kv_len, plan.block, plan.block_kv_dkv, window)
  return (forward * plan.block_q * plan.block_kv +
          backward * plan.block * plan.block_kv_dkv) // plan.block ** 2


def flash_plan(q_len: int, kv_len: int, head_dim: int,
               block: Optional[int] = None,
               cpu_fallback: bool = False,
               window: Optional[int] = None) -> FlashPlan:
  """The kernel plan of ``pallas_flash_attention`` for these shapes.

  ``block`` (default 512) is clamped to both lengths. Both kernels work
  on block x block scores at a time and fetch more a grid step where it
  divides the lengths and fits the VMEM (``_FLASH_TILE``): the forward
  twice the block in queries and in keys (fewer grid steps: 2.90 ms for
  3.31 a pass at the glm-4.7-flash cell's shape, PERF.md section 6,
  PR 30), the backward the largest power-of-two multiple in keys (fewer
  partial dq to write and to sum). Under a ``window`` shorter than the
  keys a larger tile is also more work, since a visited tile is computed
  whole: the forward then fetches what it computes (block x block), and
  the backward holds the keys that cost least by ``_band_backward_s``.
  Shapes the kernel cannot tile are refused here, by name, and not
  inside its lowering."""
  if cpu_fallback:
    return FlashPlan(backward_kernel_passes=0)
  block = min(block or _FLASH_DEFAULT_BLOCK, q_len, kv_len)
  if block % _LANES or q_len % block or kv_len % block:
    raise ValueError(
        f"the flash kernel tiles queries and keys in blocks that are a "
        f"multiple of {_LANES} and divide both lengths; got block "
        f"{block} for {q_len} queries and {kv_len} keys")
  width = -(-head_dim // _LANES) * _LANES   # as VMEM pads it
  fits = lambda rows: rows * width <= _FLASH_TILE
  held = [block]
  while fits(2 * held[-1]) and kv_len % (2 * held[-1]) == 0:
    held.append(2 * held[-1])
  banded = window is not None and window < kv_len
  fwd = block
  if not banded and fits(2 * block) and \
      q_len % (2 * block) == kv_len % (2 * block) == 0:
    fwd = 2 * block
  dkv = held[-1] if not banded else min(
      held, key=lambda rows: _band_backward_s(q_len, kv_len, width, block,
                                              rows, window))
  return FlashPlan(backward_kernel_passes=1, block=block, block_q=fwd,
                   block_kv=fwd, block_kv_dkv=dkv,
                   dq_partials=kv_len // dkv)


def _band_backward_s(q_len, kv_len, width, block, held, window) -> float:
  """Seconds a head that the fused backward takes over a banded mask
  with ``held`` keys a sweep, as far as ``held`` moves them: 5 products
  of every visited (block x held) tile, and kv_len / held partial dq of
  the queries' shape in bfloat16, written by the kernel and read by the
  sum."""
  pairs = band_tiles(q_len, kv_len, block, held, window) * block * held
  partials = (kv_len // held) * q_len * width * 2
  return (5 * 2.0 * pairs * width / _BAND_FLOPS_PER_S +
          2.0 * partials / _BAND_BYTES_PER_S)


@functools.lru_cache(maxsize=16)
def _splash_kernel(q_len: int, kv_len: int, heads: int, causal: bool,
                   plan: FlashPlan, interpret: bool,
                   window: Optional[int] = None):
  """The library's splash kernel object for one (lengths, heads, plan,
  window): built once a process and shared by every layer and every
  trace, so the mask's host-side pre-processing (0.2 s at 4096 x 4096)
  is paid once. ``heads`` are the query heads; the kernel reads how many
  key heads they share from its operands."""
  from jax.experimental.pallas.ops.tpu.splash_attention import (
      splash_attention_kernel as splash, splash_attention_mask as masks)
  if window is not None:
    # The band: ``window`` keys INCLUDING the query's own position.
    mask = masks.LocalMask((q_len, kv_len), window_size=(window - 1, 0),
                           offset=0)
  else:
    mask = (masks.CausalMask if causal else masks.FullMask)((q_len, kv_len))
  sizes = splash.BlockSizes(
      block_q=plan.block_q, block_kv=plan.block_kv,
      block_kv_compute=plan.block, block_q_dkv=plan.block,
      block_kv_dkv=plan.block_kv_dkv, block_kv_dkv_compute=plan.block,
      use_fused_bwd_kernel=True)
  # The mask's tables become constants of whatever program is being
  # traced when the first layer asks; the next trace reuses them.
  with jax.ensure_compile_time_eval():
    return splash.make_splash_mha_single_device(
        masks.MultiHeadMask([mask] * heads), block_sizes=sizes,
        interpret=interpret)


def pallas_flash_attention(q, k, v, causal: bool = False,
                           scale: Optional[float] = None,
                           block: Optional[int] = None,
                           segment_ids=None,
                           cpu_fallback: Optional[bool] = None,
                           interpret: bool = False,
                           window: Optional[int] = None,
                           plan: Optional[FlashPlan] = None):
  """JAX's TPU Pallas attention kernel behind this module's
  (B, L, H, D) layout: the core of models/mla_moe_lm.py, the ``flash``
  arm of models/transformer_lm.py, and the hand-tiled alternative to the
  XLA-scan blockwise schedule (experiments/long_context_probe.py
  --impls flash).

  The kernel is the library's splash attention (jax.experimental.pallas.
  ops.tpu.splash_attention) with its fused backward: differentiated, it
  is ONE forward kernel and ONE backward kernel, which forms each tile's
  scores once and takes dq, dk and dv from them (``flash_plan`` has the
  tiling; bfloat16 or float32 in, float32 accumulation and softmax
  statistics inside). The kernel takes no scale, so q is scaled before
  it, in q's dtype: exact for a power of two (head sizes 64 and 256); a
  caller whose scale is none folds it into q in float32 and passes
  ``scale=1.0``, which multiplies nothing.

  ``window`` (with ``causal``): query i sees key j iff
  ``0 <= i - j < window``. The mask is the kernel's own band, so a tile
  that lies wholly outside it is SKIPPED, forward and backward, not
  computed and masked. k and v may hold fewer heads than q (grouped
  queries, head n reading key head n // group): the kernel fetches each
  key head's tiles for its group of query heads, and K and V are never
  repeated in memory.

  ``segment_ids`` (B, L) int rides the kernel's own SegmentIds (packed
  sequences): cross-segment tiles are masked inside its grid, without a
  dense (L, L) mask.

  The kernel has no CPU lowering. ``cpu_fallback=None`` (the default)
  therefore routes non-TPU backends to ``full_attention`` with the
  identical mask semantics -- the kernel's own reference form -- so CPU
  suites can EXECUTE flash-configured models (the packed-sequence oracle
  tests), not just trace them; ``False`` forces the kernel path (with
  ``interpret=True`` the CPU runs the kernel's body itself, a test's
  way to hold its numbers to the reference), and ``True`` forces the
  reference path on any backend. Differentiable on both paths.
  ``plan`` replaces ``flash_plan``'s own choice (the probe's way to time
  another tiling).

  This is a CPU path for the CPU suites, not a fallback that can hide
  the device: under ``--device=tpu`` benchmark.setup() has already
  refused anything but a TPU, so the default cannot take the reference
  path there, and no caller on the TPU path passes ``cpu_fallback=True``.
  """
  if cpu_fallback is None:
    cpu_fallback = jax.default_backend() != "tpu"
  if window is not None and not causal:
    raise ValueError("a window is a causal band: pass causal=True")
  d = q.shape[-1]
  scale = (1.0 / math.sqrt(d)) if scale is None else scale
  if plan is None:
    plan = flash_plan(q.shape[1], k.shape[1], d, block, cpu_fallback, window)
  if not plan.backward_kernel_passes:
    return full_attention(q, k, v, causal=causal, scale=scale,
                          segment_ids=segment_ids, window=window)
  if window is not None and window >= k.shape[1]:
    window = None     # the band is the causal half: one kernel for both
  kernel = _splash_kernel(q.shape[1], k.shape[1], q.shape[2], causal, plan,
                          interpret, window)
  if scale != 1.0:
    q = q * jnp.asarray(scale, q.dtype)
  qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
  if segment_ids is None:
    out = jax.vmap(kernel)(qt, kt, vt)
  else:
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash)
    out = jax.vmap(lambda q_, k_, v_, s: kernel(
        q_, k_, v_, segment_ids=splash.SegmentIds(q=s, kv=s)))(
            qt, kt, vt, segment_ids)
  return out.swapaxes(1, 2).astype(q.dtype)


_IMPLS = {"ring": ring_attention, "ulysses": ulysses_attention}


def make_sequence_parallel_attention(mesh: Mesh, impl: str = "ring",
                                     axis_name: str = SEQ_AXIS,
                                     causal: bool = False,
                                     scale: Optional[float] = None,
                                     inner_block: Optional[int] = None):
  """Jitted attention over GLOBAL (B, L, H, D) arrays sequence-sharded
  on ``axis_name`` of ``mesh``; batch/heads stay replicated across the
  seq axis (compose with a 'replica' batch axis for dp x sp).
  ``inner_block`` is the multi-chip long-context memory knob: ring
  scans each ring step's local K/V in sub-blocks; ulysses bounds its
  local full-sequence step with the blockwise schedule."""
  if impl not in _IMPLS:
    raise ValueError(f"impl must be one of {sorted(_IMPLS)}, got {impl!r}")
  fn = _IMPLS[impl]
  spec = P(None, axis_name, None, None)

  def body(q, k, v):
    if impl == "ring":
      return fn(q, k, v, axis_name=axis_name, causal=causal,
                scale=scale, inner_block=inner_block)
    # ulysses: the blockwise knob bounds its LOCAL full-sequence step.
    return fn(q, k, v, axis_name=axis_name, causal=causal, scale=scale,
              local_block=inner_block)

  sharded = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
  return jax.jit(sharded)


def make_zigzag_attention(mesh: Mesh, axis_name: str = SEQ_AXIS,
                          scale: Optional[float] = None,
                          inner_block: Optional[int] = None):
  """Jitted load-balanced causal ring attention over GLOBAL (B, L, H,
  D) arrays in NORMAL sequence order.

  The zigzag permutation is applied (and inverted) inside the jit for
  convenience -- XLA lowers it to a cross-shard gather, so pipelines
  that can store their sequences pre-permuted (zigzag_order) should
  call ring_attention_zigzag directly inside their own shard_map and
  skip both gathers.
  """
  spec = P(None, axis_name, None, None)
  n = mesh.shape[axis_name]

  def body(q, k, v):
    return ring_attention_zigzag(q, k, v, axis_name=axis_name,
                                 scale=scale, inner_block=inner_block)

  sharded = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)

  def call(q, k, v):
    order = zigzag_order(q.shape[1], n)
    inv = jnp.argsort(order)
    qz, kz, vz = (jnp.take(x, order, axis=1) for x in (q, k, v))
    return jnp.take(sharded(qz, kz, vz), inv, axis=1)

  return jax.jit(call)
