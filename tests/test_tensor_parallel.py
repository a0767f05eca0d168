"""Tensor parallelism: Megatron-style sharded layers vs dense math.

Beyond-reference capability (the reference's model parallelism is
parameter-server placement only, SURVEY 2.3); equivalence-tested the
repo's standard way -- against hand-rolled single-device math on the
8-device virtual mesh, forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kf_benchmarks_tpu.parallel import tensor


def _mesh(n=8):
  return Mesh(np.array(jax.devices()[:n]), (tensor.TENSOR_AXIS,))


def _rand(key, *shape):
  return jax.random.normal(key, shape, jnp.float32) * 0.1


def test_parallel_mlp_matches_dense():
  ks = jax.random.split(jax.random.PRNGKey(0), 5)
  d_in, d_hidden, d_out = 16, 64, 16
  x = _rand(ks[0], 4, 10, d_in)
  w1, b1 = _rand(ks[1], d_in, d_hidden), _rand(ks[2], d_hidden)
  w2, b2 = _rand(ks[3], d_hidden, d_out), _rand(ks[4], d_out)

  want = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
  got = tensor.make_parallel_mlp(_mesh())(x, w1, b1, w2, b2)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


def test_parallel_mlp_gradients_match_dense():
  ks = jax.random.split(jax.random.PRNGKey(1), 5)
  d_in, d_hidden = 8, 32
  x = _rand(ks[0], 2, 6, d_in)
  args = (_rand(ks[1], d_in, d_hidden), _rand(ks[2], d_hidden),
          _rand(ks[3], d_hidden, d_in), _rand(ks[4], d_in))

  def ref_loss(w1, b1, w2, b2):
    return jnp.sum((jax.nn.gelu(x @ w1 + b1) @ w2 + b2) ** 2)

  fn = tensor.make_parallel_mlp(_mesh())

  def par_loss(w1, b1, w2, b2):
    return jnp.sum(fn(x, w1, b1, w2, b2) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(*args)
  got = jax.grad(par_loss, argnums=(0, 1, 2, 3))(*args)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_parallel_attention_matches_dense(causal):
  ks = jax.random.split(jax.random.PRNGKey(2), 4)
  b, t, d_model, heads, head_dim = 2, 12, 16, 8, 4
  x = _rand(ks[0], b, t, d_model)
  wqkv = _rand(ks[1], d_model, 3, heads, head_dim)
  wo = _rand(ks[2], heads, head_dim, d_model)
  bo = _rand(ks[3], d_model)

  # Dense reference from the same global weights.
  from kf_benchmarks_tpu.parallel import sequence
  qkv = jnp.einsum("btd,dchk->btchk", x, wqkv)
  q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,T,H,hd)
  att = sequence.full_attention(q, k, v, causal=causal)
  want = jnp.einsum("bthk,hkd->btd", att, wo) + bo

  fn = tensor.make_parallel_attention(_mesh(), num_heads=heads,
                                      causal=causal)
  got = fn(x, wqkv, wo, bo)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_parallel_attention_gradients_match_dense():
  ks = jax.random.split(jax.random.PRNGKey(3), 4)
  b, t, d_model, heads, head_dim = 2, 8, 8, 8, 2
  x = _rand(ks[0], b, t, d_model)
  wqkv = _rand(ks[1], d_model, 3, heads, head_dim)
  wo = _rand(ks[2], heads, head_dim, d_model)
  bo = _rand(ks[3], d_model)

  from kf_benchmarks_tpu.parallel import sequence

  def ref_loss(wqkv, wo):
    qkv = jnp.einsum("btd,dchk->btchk", x, wqkv)
    att = sequence.full_attention(qkv[:, :, 0], qkv[:, :, 1],
                                  qkv[:, :, 2], causal=True)
    return jnp.sum((jnp.einsum("bthk,hkd->btd", att, wo) + bo) ** 2)

  fn = tensor.make_parallel_attention(_mesh(), num_heads=heads,
                                      causal=True)

  def par_loss(wqkv, wo):
    return jnp.sum(fn(x, wqkv, wo, bo) ** 2)

  want = jax.grad(ref_loss, argnums=(0, 1))(wqkv, wo)
  got = jax.grad(par_loss, argnums=(0, 1))(wqkv, wo)
  for g, w in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def test_parallel_attention_rejects_indivisible_heads():
  with pytest.raises(ValueError, match="num_heads % axis_size"):
    tensor.make_parallel_attention(_mesh(), num_heads=6)


def test_mlp_runs_one_collective():
  # The Megatron property: the whole MLP lowers to exactly one
  # all-reduce on the per-device program.
  ks = jax.random.split(jax.random.PRNGKey(4), 5)
  d = 16
  x = _rand(ks[0], 2, 4, d)
  args = (x, _rand(ks[1], d, 4 * d), _rand(ks[2], 4 * d),
          _rand(ks[3], 4 * d, d), _rand(ks[4], d))
  fn = tensor.make_parallel_mlp(_mesh())
  hlo = jax.jit(fn).lower(*args).compile().as_text()
  assert hlo.count("all-reduce") == 1, (
      f"expected exactly 1 all-reduce, got {hlo.count('all-reduce')}")
