"""``parallel/sequence.pallas_flash_attention`` on its kernel path, on the
CPU: the library's splash kernel with its fused backward runs in
interpret mode at small sizes, and its output and dq, dk, dv are held to
``full_attention`` (materialised float32 scores); the plan that chooses
the kernel's tiles (``flash_plan``) is a function of shapes, over a
table. What the chip's compiler makes of the same call at the glm-4.7-
flash cell's size is in tests/test_tpu_step_compile.py."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.parallel import sequence


def _inputs(b, t, h, d, dtype=jnp.float32, kv_len=None):
  keys = jax.random.split(jax.random.PRNGKey(0), 4)
  shape = lambda n: (b, n, h, d)
  q, w = (jax.random.normal(k, shape(t), dtype) for k in keys[:2])
  k, v = (jax.random.normal(k, shape(kv_len or t), dtype) for k in keys[2:])
  return q, k, v, w


def _segments(b, t, n):
  """Contiguous segments a row, as first-fit packing lays them."""
  return jnp.asarray(np.sort(np.random.default_rng(0).integers(
      0, n, (b, t)), axis=1), jnp.int32)


def _err(got, want):
  got, want = (np.asarray(x, np.float32) for x in (got, want))
  return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _both(q, k, v, w, block, **kwargs):
  """(output, dq, dk, dv) of the kernel in interpret mode, and of the
  reference."""
  def run(attn):
    loss = lambda q_, k_, v_: jnp.sum(
        attn(q_, k_, v_).astype(jnp.float32) * w)
    return (attn(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)
  kernel = lambda q_, k_, v_: sequence.pallas_flash_attention(
      q_, k_, v_, block=block, cpu_fallback=False, interpret=True, **kwargs)
  reference = lambda q_, k_, v_: sequence.full_attention(q_, k_, v_, **kwargs)
  return run(kernel), run(reference)


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["one_segment", "packed_segments"])
def test_kernel_and_its_one_backward_agree_with_materialised_scores(
    segmented):
  # T 256 in blocks of 128: the forward fetches 256 queries and keys a
  # grid step, the backward holds all 256 keys (one partial dq); two
  # heads of 128; the cell's scale.
  q, k, v, w = _inputs(2, 256, 2, 128)
  seg = _segments(2, 256, 3) if segmented else None
  got, want = _both(q, k, v, w, causal=True, scale=1 / 16, block=128,
                    segment_ids=seg)
  for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
    assert a.shape == b.shape and a.dtype == b.dtype, name
    assert _err(a, b) < 2e-5, (name, _err(a, b))


def test_partial_dq_of_several_key_tiles_sum_to_dq():
  # Head size 256 holds 1,024 keys a backward sweep: T 2048 leaves two
  # partial dq, which XLA sums outside the kernel. One head, one
  # sequence, blocks of 512 as in the cell.
  assert sequence.flash_plan(2048, 2048, 256, 512).dq_partials == 2
  q, k, v, w = _inputs(1, 2048, 1, 256)
  got, want = _both(q, k, v, w, causal=True, scale=1 / 16, block=512)
  for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
    assert _err(a, b) < 2e-5, (name, _err(a, b))


def test_bfloat16_in_and_out_with_float32_inside():
  q, k, v, w = _inputs(1, 256, 2, 128, jnp.bfloat16)
  got, want = _both(q, k, v, w, causal=True, scale=1 / 16, block=128)
  for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
    assert a.dtype == jnp.bfloat16, name
    # One bfloat16 rounding of the largest entry is 2**-8.
    assert _err(a, b) < 2 ** -7, (name, _err(a, b))


def test_unmasked_and_a_head_size_under_the_lanes():
  # The other callers' shapes: transformer_lm's head size 64, and a
  # call without the causal mask (scale from the head size).
  q, k, v, w = _inputs(1, 256, 2, 64)
  got, want = _both(q, k, v, w, causal=False, block=128)
  for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
    assert _err(a, b) < 2e-5, (name, _err(a, b))


# (q_len, kv_len, head size, block asked) -> (block, the forward's tile
# of queries and of keys, keys held across a backward sweep, partial dq).
# A tile of rows x head size (padded to 128 lanes) holds at most 1024 x
# 256 elements, what the v5e's VMEM held at head size 256 on the TPU's own
# compiler: the forward fetches twice the block where that fits and
# divides both lengths, the backward holds the largest power-of-two
# multiple of the block in keys.
PLANS = [
    ((4096, 4096, 256, 512), (512, 1024, 1024, 4)),   # the glm-4.7-flash cell
    ((4096, 4096, 256, None), (512, 1024, 1024, 4)),  # the default block
    ((4096, 4096, 256, 1024), (1024, 1024, 1024, 4)),  # asked: never shrunk
    ((2048, 2048, 256, 512), (512, 1024, 1024, 2)),
    ((1024, 1024, 128, 512), (512, 1024, 1024, 1)),   # all keys: one partial
    ((8192, 8192, 128, 512), (512, 1024, 2048, 4)),   # long_context_probe
    ((2048, 2048, 64, 256), (256, 512, 2048, 1)),     # 64 pads to 128 lanes
    ((8192, 8192, 64, 256), (256, 512, 2048, 4)),
    ((4096, 4096, 192, 512), (512, 1024, 1024, 4)),   # 192 pads to 256
    ((256, 256, 128, 512), (256, 256, 256, 1)),       # clamped to the length
    ((128, 1024, 128, 512), (128, 128, 1024, 1)),     # ... to the shorter one
    ((3072, 3072, 256, 512), (512, 1024, 1024, 3)),   # 2048 does not fit
    ((1536, 1536, 128, 512), (512, 512, 512, 3)),     # 1024 does not divide
    ((4096, 4096, 512, 512), (512, 512, 512, 8)),     # 1024 x 512 do not fit
]


@pytest.mark.parametrize("shape, want", PLANS)
def test_the_plan_is_a_function_of_shapes(shape, want):
  plan = sequence.flash_plan(*shape)
  assert plan.backward_kernel_passes == 1
  assert (plan.block, plan.block_q, plan.block_kv_dkv,
          plan.dq_partials) == want
  assert plan.block_kv == plan.block_q
  assert plan.block_kv_dkv * plan.dq_partials == shape[1]
  lanes = -(-shape[2] // 128) * 128
  for rows in (plan.block_q, plan.block_kv_dkv):
    assert rows * lanes <= 1024 * 256 or rows == plan.block
    assert rows % plan.block == 0 and shape[1] % rows == 0


@pytest.mark.parametrize("shape", [
    (64, 64, 128, 512),       # shorter than the 128 lanes of a tile
    (192, 192, 128, 512),     # clamped to 192: not a multiple of 128
    (4096, 4096, 256, 320),   # a block that is not a multiple of 128
    (4096, 4096, 256, 384),   # ... or does not divide the length
    (1, 4096, 128, 512),      # one query: the decode path has its own call
])
def test_the_plan_refuses_what_the_kernel_cannot_tile(shape):
  with pytest.raises(ValueError, match="multiple of 128"):
    sequence.flash_plan(*shape)
  q, k, v, _ = _inputs(1, shape[0], 1, shape[2], kv_len=shape[1])
  with pytest.raises(ValueError, match="multiple of 128"):
    jax.eval_shape(lambda *a: sequence.pallas_flash_attention(
        *a, causal=True, block=shape[3], cpu_fallback=False), q, k, v)


def test_off_the_tpu_the_plan_is_no_kernel_and_the_reference_runs():
  plan = sequence.flash_plan(64, 64, 32, 512, cpu_fallback=True)
  assert plan == sequence.FlashPlan(backward_kernel_passes=0)
  # A shape no kernel would take runs on the reference path, which is
  # where the default sends every CPU caller.
  q, k, v, _ = _inputs(1, 64, 2, 32)
  seg = _segments(1, 64, 2)
  got = sequence.pallas_flash_attention(q, k, v, causal=True, block=512,
                                        segment_ids=seg)
  want = sequence.full_attention(q, k, v, causal=True, segment_ids=seg)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_one_kernel_object_serves_every_layer_and_every_trace():
  sequence._splash_kernel.cache_clear()
  q, k, v, _ = _inputs(1, 256, 2, 128)
  call = lambda *a: sequence.pallas_flash_attention(
      *a, causal=True, block=128, cpu_fallback=False)
  for _ in range(3):           # three traces, as init / step / remat make
    jax.eval_shape(lambda *a: call(*a) + call(*a), q, k, v)
  info = sequence._splash_kernel.cache_info()
  assert (info.misses, info.hits) == (1, 5), info
  # The mask's tables are concrete arrays, not tracers of the first trace.
  kernel = sequence._splash_kernel(
      256, 256, 2, True, sequence.flash_plan(256, 256, 128, 128), False)
  assert all(isinstance(x, jax.Array) and not isinstance(
      x, jax.core.Tracer) for x in jax.tree.leaves(kernel))


def test_differentiated_it_is_one_forward_and_one_backward_kernel():
  # On the jaxpr, where the CPU can see it: two pallas_calls in the
  # gradient program, and no dq kernel among them.
  q, k, v, _ = _inputs(2, 256, 2, 128, jnp.bfloat16)
  text = str(jax.make_jaxpr(jax.grad(
      lambda *a: jnp.sum(sequence.pallas_flash_attention(
          *a, causal=True, block=128, cpu_fallback=False).astype(
              jnp.float32)), (0, 1, 2)))(q, k, v))
  names = sorted(set(re.findall(r"splash_mha_\w+", text)))
  assert text.count("pallas_call[") == 2, text.count("pallas_call[")
  assert names == ["splash_mha_dkv_no_residuals",
                   "splash_mha_fwd_residuals"], names


# -- a causal band, and fewer key heads than query heads (PR 32) --------------

def _grouped_inputs(t, heads, kv_heads, d=128):
  keys = jax.random.split(jax.random.PRNGKey(1), 4)
  q, w = (jax.random.normal(k, (1, t, heads, d)) for k in keys[:2])
  k, v = (jax.random.normal(k, (1, t, kv_heads, d)) for k in keys[2:])
  return q, k, v, w


@pytest.mark.parametrize("window", [
    128,    # a whole tile
    129,    # one key into the tile before
    200,    # mid-tile
    256,    # two whole tiles
    512,    # the sequence: the causal half
], ids=lambda w: f"window_{w}")
def test_band_edges_agree_with_materialised_scores(window):
  # T 512 in blocks of 128, 4 query heads over 2 key heads: output and
  # the ONE backward's dq, dk, dv under the band of `window` keys (the
  # query's own included) against scores materialised and masked.
  q, k, v, w = _grouped_inputs(512, 4, 2)
  got, want = _both(q, k, v, w, causal=True, scale=1.0, block=128,
                    window=window)
  for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
    assert a.shape == b.shape and a.dtype == b.dtype, name
    assert _err(a, b) < 2e-5, (name, _err(a, b))
  # ... and the reference form's band is the definition itself.
  s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2))
  i, j = jnp.arange(512)[:, None], jnp.arange(512)[None, :]
  p = jax.nn.softmax(jnp.where((i - j >= 0) & (i - j < window), s, -jnp.inf),
                     -1)
  assert _err(want[0], jnp.einsum("bhqk,bkhd->bqhd", p,
                                  jnp.repeat(v, 2, axis=2))) < 2e-6


def test_a_key_one_past_the_window_moves_nothing():
  # The edge itself: moving key j moves query j + window - 1 (the last
  # that sees it) and leaves query j + window exactly where it was.
  q, k, v, _ = _grouped_inputs(512, 2, 1)
  window, j = 200, 100
  run = lambda v_: sequence.pallas_flash_attention(
      q, k, v_, causal=True, scale=0.05, block=128, window=window,
      cpu_fallback=False, interpret=True)
  delta = np.abs(np.asarray(run(v.at[0, j].add(3.0)) - run(v))).max(
      axis=(0, 2, 3))
  assert np.all(delta[:j] == 0) and np.all(delta[j + window:] == 0)
  assert np.all(delta[j:j + window] > 1e-5)


def test_grouped_heads_agree_with_repeated_keys_and_values():
  q, k, v, w = _grouped_inputs(256, 8, 2)
  grouped, _ = _both(q, k, v, w, causal=True, scale=0.25, block=128)
  rep = lambda x: jnp.repeat(x, 4, axis=2)
  repeated, _ = _both(q, rep(k), rep(v), w, causal=True, scale=0.25,
                      block=128)
  fold = lambda x: x.reshape(1, 256, 2, 4, 128).sum(3)
  for name, a, b in zip(("out", "dq", "dk", "dv"), grouped,
                        repeated[:2] + tuple(map(fold, repeated[2:]))):
    assert _err(a, b) < 2e-5, (name, _err(a, b))
  # (That K and V are never repeated in memory is the chip's compiler's
  # to show: tests/test_tpu_step_compile.py.)


# (lengths, head size, block, window) -> (block, forward tile, keys held
# a backward sweep, partial dq). A full layer at head size 128 holds
# twice the rows of head size 256; under a band a larger tile is also
# more work, since a visited tile is computed whole.
WINDOW_PLANS = [
    ((8192, 8192, 128, 512, None), (512, 1024, 2048, 4)),
    ((8192, 8192, 128, 512, 2048), (512, 512, 1024, 8)),
    ((8192, 8192, 128, 512, 8192), (512, 1024, 2048, 4)),   # no band at all
    ((8192, 8192, 128, 512, 9000), (512, 1024, 2048, 4)),
    ((4096, 4096, 128, 512, 2048), (512, 512, 512, 8)),
    ((4096, 4096, 256, 512, 1024), (512, 512, 512, 8)),
    ((512, 512, 128, 128, 200), (128, 128, 256, 2)),
]


@pytest.mark.parametrize("shape, want", WINDOW_PLANS)
def test_the_plan_under_a_window(shape, want):
  q_len, kv_len, head, block, window = shape
  plan = sequence.flash_plan(q_len, kv_len, head, block, window=window)
  assert (plan.block, plan.block_q, plan.block_kv_dkv,
          plan.dq_partials) == want
  assert plan.block_kv == plan.block_q
  assert plan.block_kv_dkv * plan.dq_partials == kv_len


@pytest.mark.parametrize("window", [None, 200, 256, 129])
def test_tiles_visited_are_the_kernels_own_tables(window):
  # What `band_tiles` counts from the shapes is what the kernel's mask
  # tables hold: the forward's grid (shrunk to the visited tiles) and the
  # fused backward's.
  plan = sequence.flash_plan(512, 512, 128, 128, window=window)
  kernel = sequence._splash_kernel(512, 512, 2, True, plan, True, window)
  visited = lambda info: int((np.asarray(info.block_mask) != 0).sum())
  assert visited(kernel.fwd_mask_info) == sequence.band_tiles(
      512, 512, plan.block_q, plan.block_kv, window)
  assert visited(kernel.dkv_mask_info) == sequence.band_tiles(
      512, 512, plan.block, plan.block_kv_dkv, window)


def test_band_tiles_at_the_trinity_cells_shape():
  # ISSUE 32's table: of the causal tiles at T 8,192 a band of 2,048
  # visits 0.51 at 512 x 512, 0.58 at 1,024 keys, 0.70 at 2,048 keys
  # held; 0.44 of the pairs lie in the band.
  share = lambda bq, bkv: (sequence.band_tiles(8192, 8192, bq, bkv, 2048) /
                           sequence.band_tiles(8192, 8192, bq, bkv))
  assert round(share(512, 512), 2) == 0.51
  assert round(share(512, 1024), 2) == round(share(1024, 1024), 2) == 0.58
  assert round(share(512, 2048), 2) == 0.70
  assert round(share(1, 1), 2) == 0.44


def test_a_window_is_a_causal_band():
  q, k, v, _ = _inputs(1, 256, 2, 128)
  for attn in (sequence.pallas_flash_attention, sequence.full_attention):
    with pytest.raises(ValueError, match="causal band"):
      attn(q, k, v, causal=False, window=64)
