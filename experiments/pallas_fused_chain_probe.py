"""Gate experiment 2: fused conv+BN chain in halo layout vs the XLA chain.

PERF.md's remaining path to 3,500+ img/s was fusing BN stats/normalize into
the convs so each conv+BN unit touches HBM twice (read input, write raw
output) instead of five times. This probe builds the redesigned kernel the
first probe (pallas_conv_probe.py) said was needed. Findings (TPU v5e,
stage-3 ResNet-50 shape x[256,28,28,128] * w[3,3,128,128]; history from an
earlier shared-chip setup, not re-measured on the current machine):

1. **Isolated-op numbers are measured differentially**: jit a lax.scan of
   K chained units, fetch a scalar, and difference two K values so the
   per-dispatch host cost cancels.

2. **Measured honestly, the XLA conv+BN unit is compute-bound here.** One
   relu+conv is 0.27-0.32 ms/unit = 184-219 TFLOP/s effective (the conv
   alone is AT the MXU roofline; the earlier "2.64 ms isolated" figure
   was dispatch). With the stats + normalize passes included the XLA
   unit is 0.33-0.47 ms across runs (never above the fused kernel's
   floor story below).

3. **The fused kernel cannot win at this shape.** Halo layout (zeroed
   1-pixel border, taps as whole-tile row rolls -- no misaligned sublane
   slicing) with BN-apply+ReLU prologue, in-kernel scale/shift from raw
   stats, one operand cast feeding all 9 matmuls (roll commutes with
   row-wise matmul, so the f32 *outputs* are rolled), and a stats
   epilogue accumulated across a sequential grid: 0.46-0.50 ms/unit,
   numerics matching XLA to 1 bf16 ulp. Its MXU floor is already
   0.345 ms because the halo adds 15% waste rows (900 vs 784), which
   cancels the entire HBM saving the fusion buys; the VPU work
   (prologue, rolls, stats) accounts for the rest. Ad-hoc variants
   (measured during development, scripts not retained): rolled-input +
   per-tap f32-roll+cast 0.47 ms; sublane-packed int32-bitcast rolls of
   pre-cast bf16 1.4x worse (the bitcast materializes); IMGS 4 vs 8 per
   grid step within noise. The committed script reproduces the three
   load-bearing arms: fused kernel, XLA full unit, XLA relu+conv-only.

Conclusion: at C>=128 stages the conv+BN chain is MXU-bound and XLA is
already at the roofline -- there is no headroom for a fused kernel to
recover. Only the C=64 stage-2 blocks are bandwidth-heavy enough for
fusion to pay in principle, and there the K=64 matmuls halve MXU
utilization unless taps are K-packed in pairs; the projected end-to-end
gain shrinks to single-digit percent on the forward pass for a large
engineering risk. The ~2,650 img/s bound in PERF.md therefore stands,
now backed by a direct head-to-head rather than a traffic model.

Run: python experiments/pallas_fused_chain_probe.py  (on the chip)
"""
import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B, H, W, C = 256, 28, 28, 128
CO = 128
Hp, Wp = H + 2, W + 2
ROWS = Hp * Wp  # 900 flattened halo rows per image
IMGS = 8        # images per grid step
N_VALID = float(B * H * W)


def _valid_mask():
  """(ROWS, 1) float32: 1.0 on interior rows, 0.0 on the halo border."""
  r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
  row, col = r // Wp, r % Wp
  valid = (row >= 1) & (row <= H) & (col >= 1) & (col <= W)
  return valid.astype(jnp.float32)


def fused_kernel(x_ref, w_ref, st_in_ref, m_ref, y_ref, st_ref):
  """One conv+BN unit: in-kernel BN params from the producer's raw stats,
  prologue normalize+ReLU+border-scrub, 9 matmuls off one cast operand
  with the f32 results rolled into place, stats epilogue.

  x_ref:     (IMGS, ROWS, C)  raw (un-normalized) halo-layout input
  w_ref:     (9, C, CO)       conv taps, tap-major
  st_in_ref: (2, C)           [sum, sumsq] of the input's BN statistics
  m_ref:     (ROWS, 1)        interior-row mask
  y_ref:     (IMGS, ROWS, CO) raw conv output, halo layout (border garbage)
  st_ref:    (2, CO)          running [sum, sumsq] of valid output rows
  """
  first = pl.program_id(0) == 0

  @pl.when(first)
  def _():
    st_ref[...] = jnp.zeros_like(st_ref)

  mask = m_ref[...]
  mean = st_in_ref[0:1] / N_VALID
  var = st_in_ref[1:2] / N_VALID - mean * mean
  sc = jax.lax.rsqrt(var + 1e-5)
  sh = -mean * sc
  s_sum = jnp.zeros((1, CO), jnp.float32)
  s_sq = jnp.zeros((1, CO), jnp.float32)
  for i in range(IMGS):
    x = x_ref[i].astype(jnp.float32)
    # Prologue: BN-apply + ReLU, border re-zeroed (this also scrubs the
    # producer kernel's wrap-around garbage rows). One bf16 cast feeds
    # all 9 matmuls.
    xn = (jnp.maximum(x * sc + sh, 0.0) * mask).astype(jnp.bfloat16)
    # roll(A) @ W == roll(A @ W) along rows, so shift the f32 outputs:
    # 6 inner +-1-row rolls grouped per dy, then 2 outer +-Wp rolls.
    # (Mosaic can't rotate bf16, so rolling the bf16 input would need a
    # per-tap f32 roll + cast -- measured slower.)
    taps = [[jnp.dot(xn, w_ref[dy * 3 + dx],
                     preferred_element_type=jnp.float32)
             for dx in range(3)] for dy in range(3)]
    acc = jnp.zeros((ROWS, CO), jnp.float32)
    for dy in range(3):
      s = taps[dy][1]
      s = s + pltpu.roll(taps[dy][0], 1, 0)        # [r] = P[r-1] (dx=0)
      s = s + pltpu.roll(taps[dy][2], ROWS - 1, 0)  # [r] = P[r+1] (dx=2)
      off = (dy - 1) * Wp
      acc = acc + (pltpu.roll(s, (ROWS - off) % ROWS, 0) if off else s)
    y_ref[i] = acc.astype(y_ref.dtype)
    # Epilogue: accumulate BN statistics over valid rows only.
    vacc = acc * mask
    s_sum += jnp.sum(vacc, axis=0, keepdims=True)
    s_sq += jnp.sum(vacc * vacc, axis=0, keepdims=True)
  st_ref[0:1] += s_sum
  st_ref[1:2] += s_sq


@jax.jit
def pallas_unit(x, w9, st_in, mask):
  """(raw halo input, raw input stats) -> (raw halo output, output stats)."""
  return pl.pallas_call(
      fused_kernel,
      grid=(B // IMGS,),
      in_specs=[
          pl.BlockSpec((IMGS, ROWS, C), lambda b: (b, 0, 0)),
          pl.BlockSpec((9, C, CO), lambda b: (0, 0, 0)),
          pl.BlockSpec((2, C), lambda b: (0, 0)),
          pl.BlockSpec((ROWS, 1), lambda b: (0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((IMGS, ROWS, CO), lambda b: (b, 0, 0)),
          pl.BlockSpec((2, CO), lambda b: (0, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((B, ROWS, CO), jnp.bfloat16),
          jax.ShapeDtypeStruct((2, CO), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary",)),
  )(x, w9, st_in, mask)


def xla_unit(xc, st, w):
  """The same conv+BN unit as XLA emits it: normalize+ReLU pass, conv,
  stats reduction -- standard (B,H,W,C) layout."""
  mean = st[0] / N_VALID
  var = st[1] / N_VALID - mean * mean
  sc = jax.lax.rsqrt(var + 1e-5)
  sh = -mean * sc
  xn = jnp.maximum(xc.astype(jnp.float32) * sc + sh, 0.0).astype(jnp.bfloat16)
  y = jax.lax.conv_general_dilated(
      xn, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
      preferred_element_type=jnp.bfloat16)
  yf = y.astype(jnp.float32)
  return y, jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                       jnp.sum(yf * yf, axis=(0, 1, 2))])


def to_halo(x):
  return jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))).reshape(B, ROWS, C)


def from_halo(xh, co):
  return xh.reshape(B, Hp, Wp, co)[:, 1:-1, 1:-1, :]


def main():
  key = jax.random.PRNGKey(0)
  x = jax.random.normal(key, (B, H, W, C), jnp.bfloat16)
  w = (jax.random.normal(key, (3, 3, C, CO), jnp.bfloat16) *
       (2.0 / (9 * C)) ** 0.5)
  w9 = w.reshape(9, C, CO)
  mask = _valid_mask()
  # Identity input-BN for the first unit: stats with mean 0, var 1.
  st0 = jnp.stack([jnp.zeros((C,), jnp.float32),
                   jnp.full((C,), N_VALID, jnp.float32)])

  # -- parity ---------------------------------------------------------------
  y_pal, s_pal = pallas_unit(to_halo(x), w9, st0, mask)
  y_xla, s_xla = jax.jit(xla_unit)(x, st0, w)
  err = float(jnp.max(jnp.abs(from_halo(y_pal, CO).astype(jnp.float32) -
                              y_xla.astype(jnp.float32))))
  serr = float(jnp.max(jnp.abs(s_pal - s_xla) / (jnp.abs(s_xla) + 1.0)))
  print(f"fused unit vs XLA: max abs diff {err:.4f}, "
        f"stats rel diff {serr:.2e}")

  # -- differential timing --------------------------------------------------
  # Scan K chained units inside one jit, fetch a scalar, and difference
  # two K values so the per-dispatch host cost cancels.
  @functools.partial(jax.jit, static_argnums=(2,))
  def pal_rep(xi, w9, k):
    def body(c, _):
      xi, st = c
      y, st2 = pallas_unit(xi, w9, st, mask)
      return (y * jnp.bfloat16(0.5), st2), None
    (y, _), _ = jax.lax.scan(body, (xi, st0), None, length=k)
    return jnp.sum(y.astype(jnp.float32))

  @functools.partial(jax.jit, static_argnums=(2,))
  def xla_rep(xc, w9, k):
    w = w9.reshape(3, 3, C, CO)
    def body(c, _):
      xc, st = c
      y, st2 = xla_unit(xc, st, w)
      return (y * jnp.bfloat16(0.5), st2), None
    (y, _), _ = jax.lax.scan(body, (xc, st0), None, length=k)
    return jnp.sum(y.astype(jnp.float32))

  @functools.partial(jax.jit, static_argnums=(2,))
  def xla_conv_only_rep(xc, w9, k):
    """relu+conv with no BN stats/normalize: the conv's own roofline."""
    w = w9.reshape(3, 3, C, CO)
    def body(c, _):
      xn = jnp.maximum(c.astype(jnp.float32), 0.0).astype(jnp.bfloat16)
      y = jax.lax.conv_general_dilated(
          xn, w, (1, 1), "SAME",
          dimension_numbers=("NHWC", "HWIO", "NHWC"),
          preferred_element_type=jnp.bfloat16)
      return y * jnp.bfloat16(0.5), None
    y, _ = jax.lax.scan(body, xc, None, length=k)
    return jnp.sum(y.astype(jnp.float32))

  def sync_time(f, *a, iters=6):
    float(f(*a))
    ts = []
    for _ in range(iters):
      t0 = time.time()
      float(f(*a))
      ts.append(time.time() - t0)
    return min(ts)

  flops = 2 * B * H * W * C * CO * 9
  for name, f, inp in (("pallas fused      ", pal_rep, to_halo(x)),
                       ("xla unfused       ", xla_rep, x),
                       ("xla relu+conv only", xla_conv_only_rep, x)):
    t_small = sync_time(f, inp, w9, 8)
    t_big = sync_time(f, inp, w9, 88)
    per_unit = (t_big - t_small) / 80
    print(f"{name}: {per_unit*1e3:.3f} ms/unit "
          f"({flops/per_unit/1e12:.0f} TFLOP/s effective)")


if __name__ == "__main__":
  main()
