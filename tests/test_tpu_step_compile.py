"""The four-chip vgg16 cell's step, compiled for a TPU that is described
and not attached (jax.experimental.topologies, v5e:2x2): what the chip's
own compiler makes of the factor data plane (parallel/kungfu.py), at the
benchmark's real shapes and at no chip time.

Nothing runs, so this says nothing about results or times. It holds two
things that only the TPU compiler decides and that PR 25 found by this
very compile (PERF.md section 6):

* the collectives: ONE combined all-reduce that does not carry a dense
  kernel (the parent had three, ``f32[25088,4096]`` alone), and the six
  all-gathers of the factors;
* the device memory of the step program: at most the parent's. The first
  version of the mechanism had the same live bytes at the peak and 63 MB
  MORE in XLA's assignment (the scheduler threaded the small all-gathers
  through every backward fusion), which would have broken the benchmark's
  1% bound on ``peak_hbm_gib``; one optimization barrier in the layer's
  backward put it 143 MB UNDER the parent.

The TPU's library loads in the worker that runs this file and nowhere
else: the topology is described inside a fixture, never at import, and
this is the only test file that does it.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kf_benchmarks_tpu import benchmark
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu.analysis import contracts
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS

# XLA's memory analysis of the PARENT's step (commit 9f5d874, the same
# compile): temporaries, and the peak with arguments and outputs.
PARENT_TEMP_BYTES = 4_257_519_104
PARENT_PEAK_BYTES = 5_395_633_664
# ... and of the glm-4.7-flash cell's attention core alone, forward and
# backward, on the parent of PR 30 (commit 0ea3274).
PARENT_CORE_TEMP_BYTES = 503_380_992


@pytest.fixture(scope="module")
def topo():
  import os
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  from jax.experimental import topologies
  try:
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_vgg16_four_chip_step_on_the_tpu_compiler(topo):
  p = params_lib.make_params(
      device="cpu", num_devices=4, num_batches=3, model="vgg16",
      batch_size=64, use_fp16=True, optimizer="momentum",
      variable_update="kungfu", kungfu_option="sync_sgd")
  bench = benchmark.BenchmarkCNN(p)
  # The cell as the chip runs it: bfloat16 compute (--device=cpu would
  # take float16) over a mesh of the four described chips.
  bench.compute_dtype = jnp.bfloat16
  bench.mesh = Mesh(np.asarray(topo.devices), (REPLICA_AXIS,))
  compiled = contracts.lower_step_program(bench)[1].compile()

  text = compiled.as_text()
  all_reduces = [ln for ln in text.splitlines() if " all-reduce(" in ln]
  assert len(all_reduces) == 1, len(all_reduces)
  for shape in ("25088,4096", "4096,4096]", "4096,1001"):
    assert shape not in all_reduces[0].split("all-reduce(")[0], shape
  assert "f32[3,3,512,512]" in all_reduces[0]  # the convolutions are
  # The six gathers: x and dy of fc6, fc7, fc8 over the four chips.
  gathered = set(re.findall(r"= (bf16\[256,\d+\])\S* all-gather\(", text))
  assert gathered == {"bf16[256,25088]", "bf16[256,4096]",
                      "bf16[256,1001]"}, gathered

  memory = compiled.memory_analysis()
  assert memory.temp_size_in_bytes <= PARENT_TEMP_BYTES, (
      memory.temp_size_in_bytes, PARENT_TEMP_BYTES)
  assert memory.peak_memory_in_bytes <= PARENT_PEAK_BYTES, (
      memory.peak_memory_in_bytes, PARENT_PEAK_BYTES)


def test_mla_moe_lm_kernels_on_the_tpu_compiler(topo):
  """The two kernels of the glm-4.7-flash cell at its real widths, forward
  and backward, on the chip's own compiler (PR 27): the grouped product of
  the routed experts over one round of the sorted buffer (8,192 of the
  8,192 tokens x 4 pairs, 8 of 64 experts of 2048 x 1536), whose tiling
  has to fit the v5e's VMEM (1024 x 1536 of the weight did not), the
  whole routed path around it (PR 28: rounds in a while loop, and no
  array of all the pairs times a model width in what the compiler makes
  of it), and the attention core at head size 256 for queries, keys and
  values (PR 30: ONE forward and ONE backward kernel, the backward
  holding 1,024 keys across its sweep over the queries; 2,048 did not
  fit), in no more of XLA's temporaries than the two-kernel backward it
  replaced."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.models import mla_moe_lm
  from kf_benchmarks_tpu.parallel import expert as expert_lib
  from kf_benchmarks_tpu.parallel import sequence as sequence_lib
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  tokens, k, d, f, g = 8192, 4, 2048, 1536, 8
  rows = expert_lib.compact_rows(tokens * k, g, 64)
  assert rows == 8192

  def experts(xs, w_up, w_down, sizes, live):
    h = expert_lib.grouped_matmul(xs, w_up, sizes, live, "gmm")
    return jnp.sum(expert_lib.grouped_matmul(
        h, w_down, sizes, live, "gmm").astype(jnp.float32))
  text = jax.jit(jax.grad(experts, argnums=(0, 1, 2))).lower(
      sds((rows, d), jnp.bfloat16), sds((g, d, f), jnp.bfloat16),
      sds((g, f, d), jnp.bfloat16), sds((g,), jnp.int32),
      sds((rows,), jnp.bool_)).compile().as_text()
  # The first product forward, and for each of the two the rows' and the
  # weights' gradient (nothing reads the second's own output).
  assert text.count('custom_call_target="tpu_custom_call"') >= 5

  def routed(x, weights, idx, w_gate, w_up, w_down):
    return jnp.sum(expert_lib.held_experts_ffn(
        x, weights, idx, w_gate, w_up, w_down, 0, impl="gmm",
        rows=rows)[0].astype(jnp.float32))
  weight = lambda shape: sds(shape, jnp.float32)
  text = jax.jit(jax.grad(routed, argnums=(0, 1, 3, 4, 5))).lower(
      sds((tokens, d), jnp.bfloat16), weight((tokens, k)),
      sds((tokens, k), jnp.int32), weight((g, d, f)), weight((g, d, f)),
      weight((g, f, d))).compile().as_text()
  # The backward's loop holds a round once: its three products forward
  # again and six backward (nothing reads the forward loop's own output).
  assert text.count('custom_call_target="tpu_custom_call"') == 9
  assert " while(" in text
  assert not re.search(rf"\[{tokens * k},({d}|{f})\]", text)
  # The combine gathers the round as the products stored it (PR 33): no
  # copy of it with a zero row appended, of either type.
  assert f"[{rows + 1},{d}]" not in text

  def core(q, k, v):
    return jnp.sum(sequence_lib.pallas_flash_attention(
        q, k, v, causal=True, scale=1 / 16.0, block=mla_moe_lm.ATTN_BLOCK,
        cpu_fallback=False).astype(jnp.float32))
  qkv = sds((2, 4096, 20, 256), jnp.bfloat16)
  compiled = jax.jit(jax.grad(core, argnums=(0, 1, 2))).lower(
      qkv, qkv, qkv).compile()
  text = compiled.as_text()
  assert text.count('custom_call_target="tpu_custom_call"') == 2
  assert "splash_mha_fwd_residuals" in text
  assert "splash_mha_dkv_no_residuals" in text
  # The four partial dq of the queries' shape are the kernel's, summed
  # by XLA; the pair of kernels it replaced left 503,380,992 bytes of
  # temporaries in the same compile (parent 0ea3274).
  assert "bf16[2,4,20,4096,256]" in text
  assert compiled.memory_analysis().temp_size_in_bytes <= PARENT_CORE_TEMP_BYTES


def test_lm_head_weight_gradient_on_the_tpu_compiler(topo):
  """The fused head alone at the trinity-mini cell's shapes (1 x 8,192
  positions of width 2,048 in bfloat16, 25,024 vocabulary rows, chunks of
  512), loss and gradients, on the chip's own compiler (PR 36): the
  kernel's gradient is ONE product with its float32 accumulate as the
  epilogue, fed a group's four stacked chunks of ``dlogits`` as the inner
  loop wrote them -- no copy of the group to another layout (the first
  form of the grouping had one, 102 MB read and written a group, and lost
  most of the gain to it on the chip) -- and what the grouping holds
  beyond a chunk's temporaries is that one stack, 2,048 x 25,024 x 2
  bytes."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.ops import fused_loss
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  t, d, v, chunk = 8192, 2048, 25024, 512
  stats = fused_loss.weight_grad_stats(1, t, chunk, v, 1, jnp.bfloat16)
  assert stats["weight_grad_passes"] == 4

  def loss(hidden, kernel, labels):
    return fused_loss.fused_softmax_xent(hidden, kernel, labels,
                                         chunk_size=chunk)
  compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
      sds((1, t, d), jnp.bfloat16), sds((d, v), jnp.float32),
      sds((1, t), jnp.int32)).compile()
  text = compiled.as_text()
  group = rf"bf16\[4,1,{chunk},{v}\]"
  products = re.findall(
      rf"= f32\[{d},{v}\]\S* fusion\(([^)]*)\), kind=kOutput", text)
  assert len(products) == 1, products
  operand = products[0].split(", ")[1]
  assert re.search(rf"{re.escape(operand)} = {group}", text), operand
  assert not re.search(rf"= {group}\S* copy\(", text)
  # The parent's head left 102,595,072 bytes of temporaries in the same
  # compile (commit 8202903): a chunk's float32 softmax and its logits.
  assert (compiled.memory_analysis().temp_size_in_bytes
          < stats["dlogits_bytes_held"] + 2 ** 27)


def test_trinity_mini_kernels_on_the_tpu_compiler(topo):
  """The kernels of the trinity-mini cell at its real widths, forward
  and backward, on the chip's own compiler (PR 32): the attention core at
  head size 128 over 8,192 positions, 32 query heads over 4 key heads,
  under the window of 2,048 and without one, each ONE forward and ONE
  backward kernel in the plan ``flash_plan`` gives it (2,048 keys held a
  backward sweep fit at this head size; they did not at 256), with no
  copy of K or V at the query heads' count; and the routed path at its
  second shape, 16 of 128 experts of 2048 x 1024 at top-8: one round of
  16,384 of the 65,536 sorted rows."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.models import mla_moe_lm
  from kf_benchmarks_tpu.parallel import expert as expert_lib
  from kf_benchmarks_tpu.parallel import sequence as sequence_lib
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  t, heads, kv_heads, size = 8192, 32, 4, 128
  for window in (2048, None):
    def core(q, k, v):
      return jnp.sum(sequence_lib.pallas_flash_attention(
          q, k, v, causal=True, scale=1.0, block=mla_moe_lm.ATTN_BLOCK,
          cpu_fallback=False, window=window).astype(jnp.float32))
    compiled = jax.jit(jax.grad(core, argnums=(0, 1, 2))).lower(
        sds((1, t, heads, size), jnp.bfloat16),
        sds((1, t, kv_heads, size), jnp.bfloat16),
        sds((1, t, kv_heads, size), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, window
    assert "splash_mha_fwd_residuals" in text
    assert "splash_mha_dkv_no_residuals" in text
    # The partial dq are the kernel's, at the query heads; K, V, dk and dv
    # stay at the 4 key heads: nothing of (32 heads x 8192 x 128) exists
    # beyond q, the output, their gradients and the partials.
    plan = sequence_lib.flash_plan(t, t, size, mla_moe_lm.ATTN_BLOCK,
                                   window=window)
    assert f"bf16[{plan.dq_partials},{heads},{t},{size}]" in text
    assert f"bf16[{kv_heads},{t},{size}]" in text
    partials = plan.dq_partials * heads * t * size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < partials + 2 ** 24

  tokens, k, d, f, g, experts = 8192, 8, 2048, 1024, 16, 128
  rows = expert_lib.compact_rows(tokens * k, g, experts)
  assert rows == 16384
  assert expert_lib.gmm_tiling(rows, d, f) == (512, 1024, 512)

  def routed(x, weights, idx, w_gate, w_up, w_down):
    return jnp.sum(expert_lib.held_experts_ffn(
        x, weights, idx, w_gate, w_up, w_down, 0, impl="gmm",
        rows=rows)[0].astype(jnp.float32))
  weight = lambda shape: sds(shape, jnp.float32)
  text = jax.jit(jax.grad(routed, argnums=(0, 1, 3, 4, 5))).lower(
      sds((tokens, d), jnp.bfloat16), weight((tokens, k)),
      sds((tokens, k), jnp.int32), weight((g, d, f)), weight((g, d, f)),
      weight((g, f, d))).compile().as_text()
  assert text.count('custom_call_target="tpu_custom_call"') == 9
  assert " while(" in text
  assert not re.search(rf"\[{tokens * k},({d}|{f})\]", text)
  # The combine gathers the round as the products stored it (PR 33): no
  # copy of it with a zero row appended, of either type.
  assert f"[{rows + 1},{d}]" not in text


@pytest.mark.parametrize("site,shape,rot_dims,normed,factor", [
    ("trinity-mini window q", (1, 8192, 32, 128), 128, True, 128 ** -0.5),
    ("trinity-mini window k", (1, 8192, 4, 128), 128, True, 1.0),
    ("trinity-mini full q", (1, 8192, 32, 128), 0, True, 128 ** -0.5),
    ("glm-4.7-flash q", (2, 4096, 20, 256), 64, False, 1.0),
])
def test_rotary_stage_on_the_tpu_compiler(topo, monkeypatch, site, shape,
                                          rot_dims, normed, factor):
  """The rotary stage at the two language-model cells' call shapes,
  forward and backward, on the chip's own compiler (PR 38): ONE kernel
  each way (``rotary_fwd``, ``rotary_bwd``), reading the projection's
  output as the product leaves it, (T, H x D), and writing q head by
  head as the core reads it, with no copy between; nothing float32 of
  q's size and no array of half the rotated width exists outside the
  kernels (autodiff of the plain composition made
  ``slice_negate_fusion f32[1,8192,32,64]`` and kept float32 copies of
  q); the backward's only other output is the scale's gradient by
  block."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.ops import rotary
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  b, t, heads, head_dim = shape
  plan = rotary.rotary_plan(t, heads, head_dim, rot_dims, normed,
                            jnp.bfloat16)
  assert plan.implementation == "pallas", site

  def both(x, scale, dy):
    # As the modules meet it: x a product's output, (B, T, H x D); the
    # core takes q, and hands dq back, head by head.
    tabs = rotary.stage_tables(shape, rot_dims, 10000.0, normed, x.dtype)
    y, pull = jax.vjp(lambda x, scale: rotary.rotary_stage(
        x.reshape(shape), tabs, scale if normed else None, rot_dims=rot_dims,
        eps=1e-5, factor=factor), x, scale)
    return y.swapaxes(1, 2), pull(dy.swapaxes(1, 2))
  compiled = jax.jit(both).lower(
      sds((b, t, heads * head_dim), jnp.bfloat16),
      sds((head_dim,), jnp.float32),
      sds((b, heads, t, head_dim), jnp.bfloat16)).compile()
  text = compiled.as_text()
  assert text.count('custom_call_target="tpu_custom_call"') == 2, site
  assert "rotary_fwd" in text and "rotary_bwd" in text
  assert "splash_mha" not in text
  entry = text[text.index("ENTRY"):]
  assert not re.search(r" copy\(| transpose\(", entry), site
  size = rf"(?:{t},{heads},{head_dim}|{heads},{t},{head_dim}|"\
         rf"{t},{heads * head_dim})\]"
  assert not re.search(rf"f32\[{b},{size}", text), site
  if rot_dims:
    assert not re.search(rf"\[[\d,]*,{rot_dims // 2}\]", entry), site
  # Beyond arguments and outputs: the tables and the scale's gradient by
  # block, a few megabytes.
  assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24, site


def test_nemotron_h_kernels_on_the_tpu_compiler(topo):
  """What the nemotron-3-nano-30b-a3b cell adds at its real widths,
  forward and backward, on the chip's own compiler (PR 39): the routed
  path over TWO-matrix experts at a width that no tile of the grouped
  product divides (8 of 128 experts of 2688 x 1856 = 29 x 64: tiles of
  384 with a masked last one, which the kernel's interpreted form passes
  at small sizes in tests/test_nemotron_h_lm.py and only this compile
  holds to the v5e's VMEM and tiling rules), and one Mamba-2 mixer's
  inside (``ssd.mamba_core`` at 64 heads of 64 over 8 groups, state 128,
  64 chunks of 128 positions): a program the compiler takes, in under
  2 GB of temporaries a layer (1,697,026,048 bytes as written)."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.ops import ssd
  from kf_benchmarks_tpu.parallel import expert as expert_lib
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  tokens, k, d, f, g = 8192, 6, 2688, 1856, 8
  rows = expert_lib.compact_rows(tokens * k, g, 128)
  assert rows == 6144
  assert expert_lib.gmm_tiling(rows, d, f) == (512, 896, 384)

  def routed(x, weights, idx, w_up, w_down):
    return jnp.sum(expert_lib.held_experts_ffn(
        x, weights, idx, None, w_up, w_down, 0, impl="gmm", rows=rows,
        activation="relu2")[0].astype(jnp.float32))
  weight = lambda shape: sds(shape, jnp.float32)
  text = jax.jit(jax.grad(routed, argnums=(0, 1, 3, 4))).lower(
      sds((tokens, d), jnp.bfloat16), weight((tokens, k)),
      sds((tokens, k), jnp.int32), weight((g, d, f)),
      weight((g, f, d))).compile().as_text()
  # The backward's loop holds a round once: its two products forward
  # again and four backward.
  assert text.count('custom_call_target="tpu_custom_call"') == 6
  assert " while(" in text
  assert not re.search(rf"\[{tokens * k},({d}|{f})\]", text)

  heads, p, groups, state, chunk = 64, 64, 8, 128, 128
  inner, conv = heads * p, heads * p + 2 * groups * state

  def mixer(zxbcdt, kernel, bias, a_log, skip, dt_bias, scale):
    return jnp.sum(jax.checkpoint(lambda *a: ssd.mamba_core(
        *a, heads=heads, head_dim=p, groups=groups, state=state,
        chunk=chunk, eps=1e-5))(zxbcdt, kernel, bias, a_log, skip, dt_bias,
                                scale).astype(jnp.float32))
  compiled = jax.jit(jax.grad(mixer, argnums=tuple(range(7)))).lower(
      sds((1, tokens, 2 * inner + 2 * groups * state + heads), jnp.bfloat16),
      weight((4, conv)), weight((conv,)), weight((heads,)), weight((heads,)),
      weight((heads,)), weight((inner,))).compile()
  assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


def test_ssd_scan_kernels_on_the_tpu_compiler(topo, monkeypatch):
  """One Mamba-2 mixer's inside at the nemotron cell's shapes with the
  scan as ``ssd.scan_plan`` plans it on a TPU (PR 40): ONE kernel forward
  (``ssd_scan_fwd``; the rematerialised inside runs it in the backward
  pass, where it also writes the entering states) and ONE backward
  (``ssd_scan_bwd``), which the chip's compiler takes inside its VMEM; no
  array of a chunk's decay tables
  ((64, 8, 8, 128, 128): 268 MB a layer in float32) and no relayout of
  the scan's float32 output in front of the grouped norm (``copy
  f32[1024,8,8,512]``: the norm's statistics are products with the
  groups' membership) exist around them, in little over half of the einsums'
  temporaries (875,506,176 bytes as written, 1,697,026,048 before)."""
  import jax
  from jax.sharding import SingleDeviceSharding
  from kf_benchmarks_tpu.ops import ssd
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
  weight = lambda shape: sds(shape, jnp.float32)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  tokens, heads, p, groups, state, chunk = 8192, 64, 64, 8, 128, 128
  assert ssd.scan_plan(tokens, heads, groups, chunk, p, state) == (
      ssd.ScanPlan("pallas", 128, 64))
  inner, conv = heads * p, heads * p + 2 * groups * state

  def mixer(zxbcdt, kernel, bias, a_log, skip, dt_bias, scale):
    return jnp.sum(jax.checkpoint(lambda *a: ssd.mamba_core(
        *a, heads=heads, head_dim=p, groups=groups, state=state,
        chunk=chunk, eps=1e-5))(zxbcdt, kernel, bias, a_log, skip, dt_bias,
                                scale).astype(jnp.float32))
  compiled = jax.jit(jax.grad(mixer, argnums=tuple(range(7)))).lower(
      sds((1, tokens, 2 * inner + 2 * groups * state + heads), jnp.bfloat16),
      weight((4, conv)), weight((conv,)), weight((heads,)), weight((heads,)),
      weight((heads,)), weight((inner,))).compile()
  text = compiled.as_text()
  assert text.count('custom_call_target="tpu_custom_call"') == 2
  assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
  # Both carry the scope the benchmark reads them by.
  calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
  assert all("ssd_scan/" in ln for ln in calls), calls
  assert not re.search(r"\[1,64,8,8,128,128\]|\[64,8,8,128,128\]", text)
  assert "f32[1024,8,8,512]" not in text
  assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
