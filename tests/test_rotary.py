"""The rotary stage (ops/rotary.py): head norm, factor, RoPE and the
cast between a projection and the attention core, as one function with a
written-out backward.

The reference here is the composition the two attention modules ran
until PR 38: ``RMSNorm`` (the model's own class, unchanged), the factor,
a plain ``rope`` that slices the last axis into halves and joins them,
and the cast; differentiated by autodiff. The four call shapes are the
modules': all dimensions rotated under a norm and a factor (a window
layer's q), a norm and no rotation (a full layer's q and k), the
trailing 64 of 256 without a norm (latent attention's q), and one shared
head of 64 (its rotary key)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import mla_moe_lm as lm
from kf_benchmarks_tpu.ops import rotary

THETA = 10000.0
EPS = 1e-5

# name: (B, T, H, D), rot_dims, normed, factor
SHAPES = {
    "all_rotated_normed_factor": ((2, 16, 4, 128), 128, True, 128 ** -0.5),
    "normed_not_rotated": ((2, 16, 2, 128), 0, True, 1.0),
    "trailing_64_of_256": ((2, 16, 3, 256), 64, False, 1.0),
    "one_shared_head_of_64": ((2, 16, 1, 64), 64, False, 1.0),
}


def plain_rope(x, theta):
  """RoPE as the modules had it: halves sliced, negated and joined."""
  r = x.shape[-1]
  inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
  ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
  cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
  sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
  x32 = x.astype(jnp.float32)
  x1, x2 = x32[..., :r // 2], x32[..., r // 2:]
  return x32 * cos + jnp.concatenate([-x2, x1], -1) * sin


def composition(x, scale, rot_dims, factor):
  """Norm, factor, RoPE on the trailing ``rot_dims``, cast."""
  n = x.astype(jnp.float32)
  if scale is not None:
    n = lm.RMSNorm(EPS).apply({"params": {"scale": scale}}, x)
  n = n * factor if factor != 1.0 else n
  if rot_dims:
    lead = x.shape[-1] - rot_dims
    n = jnp.concatenate([n[..., :lead], plain_rope(n[..., lead:], THETA)],
                        -1)
  return n.astype(x.dtype)


def stage(x, scale, rot_dims, factor):
  tabs = rotary.stage_tables(x.shape, rot_dims, THETA, scale is not None,
                             x.dtype)
  return rotary.rotary_stage(x, tabs, scale, rot_dims=rot_dims, eps=EPS,
                             factor=factor)


def inputs(name, dtype):
  shape, rot_dims, normed, factor = SHAPES[name]
  kx, ks, kw = jax.random.split(jax.random.PRNGKey(len(name)), 3)
  x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
  scale = (1.0 + 0.2 * jax.random.normal(ks, shape[-1:])) if normed else None
  weights = jax.random.normal(kw, shape, jnp.float32)
  return x, scale, weights, rot_dims, factor


def loss_of(fn, weights, rot_dims, factor):
  return lambda x, scale: jnp.sum(
      fn(x, scale, rot_dims, factor).astype(jnp.float32) * weights)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_float32_forward_and_gradients_are_the_compositions(name):
  x, scale, weights, rot_dims, factor = inputs(name, jnp.float32)
  np.testing.assert_allclose(stage(x, scale, rot_dims, factor),
                             composition(x, scale, rot_dims, factor),
                             rtol=1e-6, atol=1e-6)
  argnums = (0, 1) if scale is not None else (0,)
  got = jax.grad(loss_of(stage, weights, rot_dims, factor), argnums)(x, scale)
  want = jax.grad(loss_of(composition, weights, rot_dims, factor),
                  argnums)(x, scale)
  for g, w in zip(got, want):
    np.testing.assert_allclose(g, w, rtol=2e-6,
                               atol=2e-6 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_bfloat16_is_one_rounding(name):
  # The forward is the float32 result rounded once: the very bits of the
  # composition, whose arithmetic the stage keeps in its order. The input
  # gradient is float32 arithmetic rounded once too, in another order
  # than autodiff's: equal to a unit in the last place of bfloat16.
  x, scale, weights, rot_dims, factor = inputs(name, jnp.bfloat16)
  got = stage(x, scale, rot_dims, factor)
  assert got.dtype == jnp.bfloat16
  np.testing.assert_array_equal(
      np.asarray(got, np.float32),
      np.asarray(composition(x, scale, rot_dims, factor), np.float32))
  dx = jax.grad(loss_of(stage, weights, rot_dims, factor))(x, scale)
  want = jax.grad(loss_of(composition, weights, rot_dims, factor))(x, scale)
  assert dx.dtype == jnp.bfloat16
  dx, want = np.asarray(dx, np.float32), np.asarray(want, np.float32)
  assert np.max(np.abs(dx - want)) <= 2 ** -7 * np.max(np.abs(want))
  assert np.mean(dx != want) < 0.05


@pytest.mark.parametrize("rot_dims,width", [(128, 128), (64, 128), (64, 64),
                                            (64, 256)])
def test_written_out_backward_is_the_rotations_transpose(rot_dims, width):
  tabs = rotary.tables(16, rot_dims, THETA, width)
  cos, sin = (t[None, :, None, :] for t in tabs)
  partners = rotary._partners(sin, rot_dims)
  forward = lambda n: rotary._rotate(n, cos, partners, rotary._matmul_roll)
  g = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, width))
  transposed, = jax.linear_transpose(forward, g)(g)
  np.testing.assert_allclose(
      rotary._unrotate(g, cos, partners, rotary._matmul_roll), transposed,
      rtol=1e-6, atol=1e-6)
  # ... and the rotation itself is the plain one on the rotated
  # dimensions, the leading ones passing through.
  lead = width - rot_dims
  np.testing.assert_allclose(forward(g)[..., lead:],
                             plain_rope(g[..., lead:], THETA), rtol=1e-6,
                             atol=1e-6)
  np.testing.assert_array_equal(forward(g)[..., :lead], g[..., :lead])


def test_tables_are_ropes_own_and_hold_the_sign():
  cos, sin = rotary.tables(32, 64, THETA, 64)
  inv_freq = 1.0 / (THETA ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))
  ang = jnp.arange(32, dtype=jnp.float32)[:, None] * inv_freq[None]
  np.testing.assert_array_equal(cos, jnp.concatenate([jnp.cos(ang)] * 2, -1))
  np.testing.assert_array_equal(
      sin, jnp.concatenate([-jnp.sin(ang), jnp.sin(ang)], -1))
  assert rotary.tables(32, 0, THETA, 0) is None


# -- the kernel's body, interpreted ---------------------------------------------

KERNEL_SHAPES = {
    # (B, T, H, D), rot_dims, normed, factor, rows a block (16 heads go
    # in two groups of 8, 10 in two of 5)
    "window_q": ((2, 64, 16, 128), 128, True, 128 ** -0.5, 32),
    "full_k": ((1, 64, 2, 128), 0, True, 1.0, 16),
    "latent_q": ((2, 32, 10, 256), 64, False, 1.0, 16),
    "latent_q_scaled": ((1, 32, 2, 256), 64, False, 0.5, 16),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(KERNEL_SHAPES))
def test_kernel_body_interpreted_is_the_jnp_form(name, dtype):
  shape, rot_dims, normed, factor, rows = KERNEL_SHAPES[name]
  b, t, heads, head_dim = shape
  plan = rotary.rotary_plan(t, heads, head_dim, rot_dims, normed, dtype,
                            on_tpu=True)
  assert plan.implementation == "pallas"
  plan = dataclasses.replace(plan, block_rows=rows)
  kx, ks, kg = jax.random.split(jax.random.PRNGKey(7), 3)
  x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
  dy = jax.random.normal(kg, shape, jnp.float32).astype(dtype)
  scale = (1.0 + 0.2 * jax.random.normal(ks, (head_dim,))) if normed else None
  narrow = rotary.tables(t, rot_dims, THETA, plan.table_width)
  whole = rotary.tables(t, rot_dims, THETA, head_dim)
  got = rotary._pallas_forward(x, scale, narrow, rot_dims, EPS, factor, plan,
                               interpret=True)
  want = rotary._xla_forward(x, scale, whole, rot_dims, EPS, factor)
  tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(
      rtol=2 ** -7, atol=2 ** -7)
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(want, np.float32), **tol)
  got = rotary._pallas_backward(x, scale, narrow, rot_dims, EPS, factor, plan,
                                dy, interpret=True)
  want = rotary._xla_backward(x, scale, whole, rot_dims, EPS, factor, dy)
  np.testing.assert_allclose(np.asarray(got[0], np.float32),
                             np.asarray(want[0], np.float32), **tol)
  if normed:
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
  else:
    assert got[1] is None and want[1] is None


@pytest.mark.parametrize("case,want", [
    # the trinity-mini cell's q and k, its full layer's, the glm cell's q
    # and shared rotary key; then what the kernel does not tile.
    (dict(seq_len=8192, heads=32, head_dim=128, rot_dims=128, normed=True),
     ("pallas", 1024, 8, 128)),
    (dict(seq_len=8192, heads=4, head_dim=128, rot_dims=128, normed=True),
     ("pallas", 1024, 4, 128)),
    (dict(seq_len=8192, heads=32, head_dim=128, rot_dims=0, normed=True),
     ("pallas", 1024, 8, 0)),
    (dict(seq_len=4096, heads=20, head_dim=256, rot_dims=64, normed=False),
     ("pallas", 512, 5, 128)),
    (dict(seq_len=4096, heads=1, head_dim=64, rot_dims=64, normed=False),
     ("xla", 0, 0, 64)),
    (dict(seq_len=4096, heads=20, head_dim=256, rot_dims=64, normed=True),
     ("xla", 0, 0, 256)),
    (dict(seq_len=24, heads=4, head_dim=128, rot_dims=128, normed=True),
     ("xla", 0, 0, 128)),
])
def test_plan_follows_from_the_shapes(case, want):
  plan = rotary.rotary_plan(dtype=jnp.bfloat16, on_tpu=True, **case)
  assert dataclasses.astuple(plan) == want
  off = rotary.rotary_plan(dtype=jnp.bfloat16, on_tpu=False, **case)
  assert off.implementation == "xla" and off.block_rows == 0


def test_plan_refuses_an_odd_count_of_rotated_dimensions():
  with pytest.raises(ValueError, match="rot_dims=63"):
    rotary.rotary_plan(16, 1, 64, 63, False, jnp.float32)


# -- the modules that call it ---------------------------------------------------

def _half_width_joins(jaxpr, half, found):
  """Every ``concatenate`` or ``pad`` of the jaxpr, nested ones included,
  with an operand whose last dimension is ``half``."""
  for eqn in jaxpr.eqns:
    if eqn.primitive.name in ("concatenate", "pad") and any(
        getattr(v.aval, "shape", ())[-1:] == (half,) for v in eqn.invars):
      found.append(str(eqn))
    for sub in jax.core.jaxprs_in_params(eqn.params):
      _half_width_joins(sub, half, found)
  return found


@pytest.mark.parametrize("window", [8, None], ids=["window", "full"])
def test_gqattention_gradient_joins_no_halves(window):
  cfg = dataclasses.replace(
      lm.load_lm_config("trinity-mini", 5, 8, 0, 1), hidden_size=64,
      num_attention_heads=4, num_key_value_heads=2, head_dim=32,
      sliding_window=8)
  attend = lm.GQAttention(cfg=cfg, window=window)
  x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
  params = attend.init({"params": jax.random.PRNGKey(2)}, x)["params"]
  assert sorted(params) == ["gate_proj", "k_norm", "k_proj", "o_proj",
                            "q_norm", "q_proj", "v_proj"]
  assert params["q_norm"]["scale"].shape == (32,)
  loss = lambda p, x: jnp.sum(jnp.sin(attend.apply({"params": p}, x)))
  jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
  assert _half_width_joins(jaxpr.jaxpr, 16, []) == []
  # (the plain composition does join halves: the walk sees them)
  plain = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(plain_rope(x, THETA))))(
      jnp.zeros((1, 4, 2, 32)))
  assert _half_width_joins(plain.jaxpr, 16, [])
