"""Gate experiment 3: fused conv+BN at the stage-2 shape (56x56, C=64).

pallas_fused_chain_probe.py closed the fusion question for C>=128: the
unit is MXU-bound and XLA's conv is at the roofline. Stage 2 is the one
place fusion could still pay -- its tensors are 4x larger per channel
pass (bandwidth-heavy) and its K=64 matmuls leave XLA's conv at half MXU
width. This probe measures that remaining corner:

* Same halo layout / roll structure as the stage-3 probe, at
  x[256,56,56,64] * w[3,3,64,64] (the 3x3 of every stage-2 bottleneck).
* **N-packing**: C=64 fills half the 128-lane MXU width, so taps are
  paired along the OUTPUT dimension -- one matmul of the shared operand
  against two taps' weights concatenated to (64,128), then the two f32
  output halves are rolled into place separately (roll commutes with
  row-wise matmul, the stage-3 trick): 4 pairs + 1 single per tile.
  (The first attempt packed along K -- concat two differently-rolled
  operands along lanes -- which Mosaic miscompiled: the TPU build
  produced wrong values for the concat of roll-offset layouts while
  interpret mode matched XLA to bf16 rounding.  N-packing keeps every
  concat on host-side weights and every roll on a plain f32 value.)
* Same differential timing (scan K units, difference two K values) and
  the same three arms: fused kernel, XLA full unit, XLA relu+conv only.

Run: python experiments/pallas_stage2_probe.py  (on the chip; results
recorded in PERF.md once measured)
"""
import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B, H, W, C = 256, 56, 56, 64
CO = 64
Hp, Wp = H + 2, W + 2
ROWS = Hp * Wp  # 3364 flattened halo rows per image
IMGS = 1        # images per grid step (VMEM: ~0.9 MB per f32 plane;
                # 2 images + f32 temporaries exceeded the 16M scoped limit)
N_VALID = float(B * H * W)

# Tap pairing for N-packed matmuls: 4 pairs + 1 single (tap 8).
PAIRS = [(0, 1), (2, 3), (4, 5), (6, 7)]
SINGLE = 8


def _valid_mask():
  r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
  row, col = r // Wp, r % Wp
  valid = (row >= 1) & (row <= H) & (col >= 1) & (col <= W)
  return valid.astype(jnp.float32)


def _tap_off(t):
  dy, dx = t // 3, t % 3
  return (dy - 1) * Wp + (dx - 1)


def fused_kernel(x_ref, wp_ref, ws_ref, st_in_ref, m_ref, y_ref, st_ref):
  """One stage-2 conv+BN unit with N-packed tap pairs.

  x_ref:     (IMGS, ROWS, C)   raw halo-layout input
  wp_ref:    (4, C, 2*CO)      CO-concatenated weights for the 4 pairs
  ws_ref:    (C, CO)           weights for the single tap 8
  st_in_ref: (2, C)            input BN statistics [sum, sumsq]
  m_ref:     (ROWS, 1)         interior-row mask
  y_ref:     (IMGS, ROWS, CO)  raw conv output, halo layout
  st_ref:    (2, CO)           running output statistics
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
    xn = (jnp.maximum(x * sc + sh, 0.0) * mask).astype(jnp.bfloat16)

    def place(out, t):
      # roll(A) @ W == roll(A @ W) along rows: shift the f32 output so
      # row r accumulates the tap's contribution from row r + off.
      off = _tap_off(t)
      return pltpu.roll(out, (ROWS - off) % ROWS, 0) if off else out

    acc = jnp.zeros((ROWS, CO), jnp.float32)
    # N-packed pairs: one matmul against two taps' weights side by side
    # runs the MXU at full 128-lane output width; the halves then roll
    # into place independently.
    for p, (ta, tb) in enumerate(PAIRS):
      out = jnp.dot(xn, wp_ref[p], preferred_element_type=jnp.float32)
      acc += place(out[:, :CO], ta) + place(out[:, CO:], tb)
    acc += place(jnp.dot(xn, ws_ref[...],
                         preferred_element_type=jnp.float32), SINGLE)
    y_ref[i] = acc.astype(y_ref.dtype)
    vacc = acc * mask
    s_sum += jnp.sum(vacc, axis=0, keepdims=True)
    s_sq += jnp.sum(vacc * vacc, axis=0, keepdims=True)
  st_ref[0:1] += s_sum
  st_ref[1:2] += s_sq


@jax.jit
def pallas_unit(x, wp, ws, st_in, mask):
  return pl.pallas_call(
      fused_kernel,
      grid=(B // IMGS,),
      in_specs=[
          pl.BlockSpec((IMGS, ROWS, C), lambda b: (b, 0, 0)),
          pl.BlockSpec((4, C, 2 * CO), lambda b: (0, 0, 0)),
          pl.BlockSpec((C, CO), lambda b: (0, 0)),
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
          dimension_semantics=("arbitrary",),
          vmem_limit_bytes=64 * 1024 * 1024),
  )(x, wp, ws, st_in, mask)


def pack_weights(w9):
  """(9, C, CO) -> pair-concatenated (4, C, 2CO) + single (C, CO)."""
  wp = jnp.stack([jnp.concatenate([w9[a], w9[b]], axis=1)
                  for a, b in PAIRS])
  return wp, w9[SINGLE]


def xla_unit(xc, st, w):
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
  wp, ws = pack_weights(w9)
  mask = _valid_mask()
  st0 = jnp.stack([jnp.zeros((C,), jnp.float32),
                   jnp.full((C,), N_VALID, jnp.float32)])

  y_pal, s_pal = pallas_unit(to_halo(x), wp, ws, st0, mask)
  y_xla, s_xla = jax.jit(xla_unit)(x, st0, w)
  err = float(jnp.max(jnp.abs(from_halo(y_pal, CO).astype(jnp.float32) -
                              y_xla.astype(jnp.float32))))
  serr = float(jnp.max(jnp.abs(s_pal - s_xla) / (jnp.abs(s_xla) + 1.0)))
  print(f"fused unit vs XLA: max abs diff {err:.4f}, "
        f"stats rel diff {serr:.2e}")

  @functools.partial(jax.jit, static_argnums=(3,))
  def pal_rep(xi, wp, ws, k):
    def body(c, _):
      xi, st = c
      y, st2 = pallas_unit(xi, wp, ws, st, mask)
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
  arms = (("pallas fused (N-packed)", lambda k: pal_rep(to_halo(x), wp, ws, k)),
          ("xla unfused            ", lambda k: xla_rep(x, w9, k)),
          ("xla relu+conv only     ", lambda k: xla_conv_only_rep(x, w9, k)))
  for name, f in arms:
    t_small = sync_time(f, 8)
    t_big = sync_time(f, 48)
    per_unit = (t_big - t_small) / 40
    print(f"{name}: {per_unit*1e3:.3f} ms/unit "
          f"({flops/per_unit/1e12:.0f} TFLOP/s effective)")


if __name__ == "__main__":
  main()
