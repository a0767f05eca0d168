"""Sequence/context parallelism: ring + Ulysses attention equivalence.

Beyond-reference capability (SURVEY 5.7: the reference has no
sequence-axis parallelism); tested the same way the repo tests every
collective schedule -- numerical equivalence against a single-device
reference implementation on the 8-device virtual mesh (conftest.py),
forward AND backward.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu.parallel import sequence


def _mesh(n=8, axis=sequence.SEQ_AXIS):
  return Mesh(np.array(jax.devices()[:n]), (axis,))


def _qkv(b=2, l=32, h=8, d=16, dtype=jnp.float32, seed=0):
  ks = jax.random.split(jax.random.PRNGKey(seed), 3)
  shape = (b, l, h, d)
  return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_matches_full_attention(impl, causal):
  q, k, v = _qkv()
  want = sequence.full_attention(q, k, v, causal=causal)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl=impl, causal=causal)
  got = fn(q, k, v)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_gradients_match_full_attention(impl):
  q, k, v = _qkv()

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl=impl, causal=True)

  def par_loss(q, k, v):
    return jnp.sum(fn(q, k, v) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(par_loss, argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def test_ring_handles_heads_not_divisible_by_devices():
  # 3 heads over 8 devices: ring never touches the head axis.
  q, k, v = _qkv(h=3)
  want = sequence.full_attention(q, k, v, causal=True)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ring", causal=True)
  np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_ulysses_rejects_indivisible_heads():
  q, k, v = _qkv(h=3)
  fn = sequence.make_sequence_parallel_attention(_mesh(), impl="ulysses")
  with pytest.raises(ValueError, match="heads % axis_size"):
    fn(q, k, v)


def test_bf16_inputs_accumulate_in_f32():
  q, k, v = _qkv(dtype=jnp.bfloat16)
  want = sequence.full_attention(q, k, v, causal=True)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ring", causal=True)
  got = fn(q, k, v)
  assert got.dtype == jnp.bfloat16
  np.testing.assert_allclose(
      np.asarray(got, np.float32), np.asarray(want, np.float32),
      rtol=2e-2, atol=2e-2)


def test_zigzag_order_inverse_roundtrip():
  order = np.asarray(sequence.zigzag_order(32, 8))
  inv = np.asarray(sequence.zigzag_inverse(32, 8))
  assert sorted(order) == list(range(32))
  np.testing.assert_array_equal(order[inv], np.arange(32))
  # Device 0's shard pairs the first and last stripes.
  np.testing.assert_array_equal(order[:4], [0, 1, 30, 31])


def test_zigzag_ring_matches_full_attention():
  q, k, v = _qkv(l=32)
  want = sequence.full_attention(q, k, v, causal=True)
  fn = sequence.make_zigzag_attention(_mesh())
  np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_zigzag_ring_gradients_match_full_attention():
  q, k, v = _qkv(l=32)
  fn = sequence.make_zigzag_attention(_mesh())

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  def zz_loss(q, k, v):
    return jnp.sum(fn(q, k, v) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(zz_loss, argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # ~26 s: tiered for the 870 s tier-1 wall budget
def test_zigzag_inner_block_matches_full():
  # The K/V sub-block tiling composed into the zigzag ring: stripes
  # scan their travelling K/V in tiles, result stays exact causal
  # attention in normal order.
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=True)
  fn = sequence.make_zigzag_attention(_mesh(), inner_block=2)
  np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(want),
                             rtol=1e-5, atol=1e-5)
  g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
               argnums=(0, 1, 2))(q, k, v)
  w = jax.grad(lambda q, k, v: jnp.sum(
      sequence.full_attention(q, k, v, causal=True) ** 2),
      argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(g, w):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_zigzag_rejects_indivisible_length():
  with pytest.raises(ValueError, match="not divisible"):
    sequence.zigzag_order(30, 8)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_full(causal):
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=causal)
  got = jax.jit(lambda q, k, v: sequence.blockwise_attention(
      q, k, v, block_size=16, causal=causal))(q, k, v)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_blockwise_attention_gradients_match_full():
  q, k, v = _qkv(l=64)

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  def blk_loss(q, k, v):
    return jnp.sum(sequence.blockwise_attention(
        q, k, v, block_size=16, causal=True) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(blk_loss, argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def test_blockwise_bf16_stays_close_to_f32_reference():
  # The MXU-native precision class (bf16 multiplicands, f32
  # accumulation/softmax stats) must stay within bf16 rounding of the
  # exact f32 computation -- and the f32 path itself is bit-compatible
  # with the old upcast-everything form (pinned by the exact-equality
  # tests above running in f32).
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=True)
  got = sequence.blockwise_attention(
      q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
      v.astype(jnp.bfloat16), block_size=16, causal=True,
      q_block_size=16)
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(want), rtol=5e-2, atol=5e-2)


def test_blockwise_rejects_indivisible_length():
  q, k, v = _qkv(l=32)
  with pytest.raises(ValueError, match="not divisible"):
    sequence.blockwise_attention(q, k, v, block_size=5)
  with pytest.raises(ValueError, match="q block"):
    sequence.blockwise_attention(q, k, v, block_size=16,
                                 q_block_size=5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("q_block", [16, 32])
def test_two_level_blockwise_matches_full(causal, q_block):
  # The q-tiled (two-level) schedule is the same exact attention; the
  # causal variant must also match even though it SKIPS future blocks.
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=causal)
  got = jax.jit(lambda q, k, v: sequence.blockwise_attention(
      q, k, v, block_size=16, causal=causal,
      q_block_size=q_block))(q, k, v)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_inner_block_matches_full(causal):
  # The two-level tiling composed INTO the ring: each ring step scans
  # its local K/V shard in sub-blocks; result stays exact attention.
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=causal)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ring", causal=causal, inner_block=4)
  np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_ring_inner_block_gradients_match_full():
  q, k, v = _qkv(l=64)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ring", causal=True, inner_block=4)

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def test_ring_inner_block_rejects_indivisible():
  q, k, v = _qkv(l=64)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ring", inner_block=3)  # 8 local not divisible by 3
  with pytest.raises(ValueError, match="inner"):
    fn(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_local_block_matches_full(causal):
  # inner_block on the ulysses impl bounds its LOCAL full-sequence step
  # with the blockwise schedule; the result stays exact attention.
  q, k, v = _qkv(l=64)
  want = sequence.full_attention(q, k, v, causal=causal)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ulysses", causal=causal, inner_block=16)
  np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_ulysses_local_block_gradients_match_full():
  # The transposed all_to_all composition must backprop exactly like
  # dense attention -- the same grad pin every other schedule knob in
  # this file carries.
  q, k, v = _qkv(l=64)
  fn = sequence.make_sequence_parallel_attention(
      _mesh(), impl="ulysses", causal=True, inner_block=16)

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("KF_TPU_TESTS") != "1",
                    reason="Pallas flash kernel is TPU-only; opt-in "
                           "with KF_TPU_TESTS=1 (serialize TPU work)")
def test_pallas_flash_matches_full_on_tpu():
  # The hand-tiled kernel vs dense attention, forward and backward, on
  # the real chip (the CPU suite exercises only the layout wrapper).
  import subprocess
  import sys
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  prog = r"""
import jax, jax.numpy as jnp, numpy as np
from kf_benchmarks_tpu.parallel import sequence
key = jax.random.PRNGKey(0)
q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                             (1, 1024, 8, 128), jnp.float32)
           for i in range(3))
want = sequence.full_attention(q, k, v, causal=True)
got = sequence.pallas_flash_attention(q, k, v, causal=True)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-2, atol=2e-2)
gw = jax.grad(lambda q: jnp.sum(
    sequence.full_attention(q, k, v, causal=True) ** 2))(q)
gg = jax.grad(lambda q: jnp.sum(
    sequence.pallas_flash_attention(q, k, v, causal=True) ** 2))(q)
np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                           rtol=5e-2, atol=5e-2)
print("FLASH_OK")
"""
  env = dict(os.environ)
  env.pop("XLA_FLAGS", None)
  env.pop("JAX_PLATFORMS", None)
  # No subprocess timeout: a first Pallas compile has no bound this
  # test could know; a hung run is the operator's call to abandon.
  r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                     text=True, env=env, cwd=repo)
  assert r.returncode == 0 and "FLASH_OK" in r.stdout, (
      r.stdout[-2000:], r.stderr[-2000:])


def test_two_level_blockwise_gradients_match_full():
  q, k, v = _qkv(l=64)

  def ref_loss(q, k, v):
    return jnp.sum(sequence.full_attention(q, k, v, causal=True) ** 2)

  def blk_loss(q, k, v):
    return jnp.sum(sequence.blockwise_attention(
        q, k, v, block_size=16, causal=True, q_block_size=16) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
  got = jax.grad(blk_loss, argnums=(0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def test_ring_score_memory_is_blockwise():
  # The point of the ring schedule: no (L, L) score tensor is ever
  # materialised. At L=512 over 8 devices the largest live f32 buffer in
  # the per-device program must be the (B, H, L/8, L/8) block scores,
  # not (L, L) or (L/8, L).
  b, l, h, d = 1, 512, 2, 8
  q, k, v = _qkv(b=b, l=l, h=h, d=d)
  mesh = _mesh()
  spec = P(None, sequence.SEQ_AXIS, None, None)
  body = jax.shard_map(
      lambda q, k, v: sequence.ring_attention(q, k, v),
      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
  compiled = jax.jit(body).lower(q, k, v).compile()
  peak_bytes = compiled.memory_analysis().temp_size_in_bytes
  full_score_bytes = 4 * b * h * l * l
  # Peak temp covers the K/V ring buffers and block scores -- a small
  # multiple of the (L/8, L/8) block, far under the 2 MiB full score
  # tensor a non-blockwise schedule would materialise.
  assert peak_bytes < full_score_bytes // 4, (
      f"peak temp {peak_bytes} is within 4x of the full (L,L) score "
      f"tensor ({full_score_bytes}); the schedule is not blockwise")


def test_blockwise_grad_memory_is_blockwise():
  # The ADVICE round-4 finding: without remat, autodiff saves ~5 full
  # (L, L)-score-sized residual stacks across the scan, so TRAINING
  # memory was worse than plain attention. With _block_update_remat the
  # backward pass recomputes block scores; the grad program's peak temp
  # must stay well under one full score tensor, let alone five.
  b, l, h, d = 1, 512, 2, 8
  q, k, v = _qkv(b=b, l=l, h=h, d=d)

  def loss(q, k, v):
    return jnp.sum(sequence.blockwise_attention(
        q, k, v, block_size=64, causal=True) ** 2)

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      q, k, v).compile()
  peak_bytes = compiled.memory_analysis().temp_size_in_bytes
  full_score_bytes = 4 * b * h * l * l
  assert peak_bytes < full_score_bytes, (
      f"grad peak temp {peak_bytes} >= one full (L,L) score tensor "
      f"({full_score_bytes}); backward residuals are not blockwise")


def test_two_level_grad_memory_is_blockwise():
  # The production transformer_lm path (q_block_size set) must keep the
  # same training-memory property as the single-level schedule: a
  # future change to the nested scan + cond skip that stacks score
  # residuals would silently regress exactly what the round-4 ADVICE
  # finding caught.
  b, l, h, d = 1, 512, 2, 8
  q, k, v = _qkv(b=b, l=l, h=h, d=d)

  def loss(q, k, v):
    return jnp.sum(sequence.blockwise_attention(
        q, k, v, block_size=64, causal=True, q_block_size=64) ** 2)

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      q, k, v).compile()
  peak_bytes = compiled.memory_analysis().temp_size_in_bytes
  full_score_bytes = 4 * b * h * l * l
  assert peak_bytes < full_score_bytes, (
      f"two-level grad peak temp {peak_bytes} >= one full (L,L) score "
      f"tensor ({full_score_bytes}); backward residuals not blockwise")


def test_ring_grad_memory_is_blockwise():
  # Same property for the ring schedule: backward residuals per ring
  # step are the travelling K/V operands and carries, never the
  # (Lq_local, L_global) score stack the unrematerialised loop held.
  b, l, h, d = 1, 512, 2, 8
  q, k, v = _qkv(b=b, l=l, h=h, d=d)
  mesh = _mesh()
  spec = P(None, sequence.SEQ_AXIS, None, None)
  body = jax.shard_map(
      lambda q, k, v: sequence.ring_attention(q, k, v, causal=True),
      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

  def loss(q, k, v):
    return jnp.sum(body(q, k, v) ** 2)

  compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      q, k, v).compile()
  peak_bytes = compiled.memory_analysis().temp_size_in_bytes
  full_score_bytes = 4 * b * h * l * l
  assert peak_bytes < full_score_bytes, (
      f"ring grad peak temp {peak_bytes} >= one full (L,L) score "
      f"tensor ({full_score_bytes}); backward residuals not blockwise")
