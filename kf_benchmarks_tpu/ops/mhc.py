"""The arithmetic of a manifold-constrained hyper-connection (mHC), as
two functions of arrays: what lies around each sublayer of a stack whose
residual path is ``n`` streams.

BEYOND-REFERENCE: the reference zoo has no multi-stream residual; this
serves the configured decoder's ``xing4_0`` family
(``models/mla_moe_lm.py``), whose ``HyperConnection`` holds the
parameters and the check's probe.

For the streams X (B, T, n, C) in the compute dtype and one sublayer f,
with the coefficients in float32 (``Mixing.coef_dtype``; bfloat16 is the
lower-precision control of the tests and the benchmark's check):

    x^     = RMSNorm(vec(X))                     (no learned scale)
    z      = x^ phi                              phi (nC, 2n + n^2)
    h_pre  = sigmoid(alpha_pre  z_pre  + b_pre)                  (n,)
    h_post = 2 sigmoid(alpha_post z_post + b_post)               (n,)
    H_res  = Sinkhorn(exp(clamp(alpha_res mat(z_res) + b_res)))  (n, n)
    h      = sum_i h_pre[i] X[i]                 (f's input, cast once)
    X'[i]  = sum_j H_res[i, j] X[j] + h_post[i] f(h)   (float32, cast once)

``mhc_pre`` forms h and the three coefficients, ``mhc_post`` the mixed
streams. The norm's statistic multiplies the PRODUCT (mHC's own
reordering: x^ phi = r (x phi) for the row's 1/rms r), and the product
is float32-accurate.

Which form runs follows from the shapes, the streams' dtype, the
coefficients' dtype and the backend (``mhc_plan``, as
``ssd.scan_plan`` and ``rotary.rotary_plan`` decide theirs): no flag, no
model name. There are TWO forms of each function, which share the plan
(decided once a sublayer, by ``mhc_pre``) and nothing else:

* ``"pallas"`` (a TPU, bfloat16 streams, C whole lane tiles, n small
  enough for the kernels' (128, 128) transposes, tokens a whole number
  of blocks): ONE kernel a function each way (``jax.custom_vjp``, the
  backward written out), over blocks of ``token_block`` tokens with all
  n x C lanes of the streams viewed as (tokens, n C). The streams cross
  HBM in bfloat16 once a pass; float32 exists only in VMEM.
  ``mhc_pre_fwd`` reads a block once for the statistic, the product
  with phi (ONE bfloat16 MXU product against phi's three bfloat16 terms,
  ``split3``, stacked: x is exact in bfloat16 and hi + mid + lo is phi
  exactly, so the float32 sum of the three is the float32 product up to
  the order of its sums, where HIGHEST spends six passes), the sigmoids,
  the clamp, the Sinkhorn-Knopp iterations and the pre-mix, writing h
  and the coefficients (float32, a token a lane). ``mhc_post_fwd`` reads
  the streams and f's output and writes the mixed streams.
  ``mhc_pre_bwd`` forms all of that again from its inputs, keeps the
  iterations in VMEM and walks them back, and accumulates phi's gradient
  over the token blocks in a float32 block that never leaves VMEM until
  the last; ``mhc_post_bwd`` reads the streams, f's output and the mixed
  streams' cotangent once and writes the streams' and f's cotangents and
  the coefficients'. What each backward keeps is its function's inputs:
  nothing float32 of the streams' size.
* ``"xla"`` (every other backend and shape, and the bfloat16 control):
  ``jax.numpy`` under autodiff, the product with phi at HIGHEST. What
  the tests compare the kernels with.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
_TOKEN_BLOCK = 128
# What one pass of a kernel's inner loop holds of a stream: a lane tile.
_CHUNK = _LANES
# phi's bfloat16 terms against the backward product's five stacked
# copies of the coefficients' cotangent, all inside ONE pass of the MXU
# (_LANES rows): hi hi hi mid mid against du's hi mid lo hi mid.
_BACKWARD_TERMS = 5
_VMEM_LIMIT_BYTES = 96 * 2 ** 20

# What ``h_post``'s sigmoid is multiplied by (mHC: 2 sigmoid, so that a
# stream's share of a sublayer's output lies in (0, 2)). Read at each
# call, by both forms.
H_POST_SCALE = 2.0


class Mixing(NamedTuple):
  """The configuration's constants of the coefficients: Sinkhorn-Knopp's
  ``iters`` and ``eps`` (in each denominator), the logits' clamp, the
  norm's epsilon, and the dtype of the coefficient path."""
  iters: int
  eps: float
  clamp: Tuple[float, float]
  norm_eps: float
  coef_dtype: object = jnp.float32


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class MhcPlan:
  """What ``mhc_pre`` and ``mhc_post`` run for one shape: ``mhc_plan``
  decides it once, in ``mhc_pre``, which hands it to ``mhc_post`` in its
  ``Premixed`` (a static part of that pytree, no array); a model states
  it in its run's ``stats["mhc"]``. ``implementation``: ``"pallas"`` (the
  kernels, over blocks of ``token_block`` tokens) or ``"xla"``
  (``jax.numpy`` under autodiff; ``token_block`` 0)."""
  implementation: str
  token_block: int


def mhc_plan(tokens: int, streams: int, width: int, dtype,
             coef_dtype=jnp.float32) -> MhcPlan:
  """The plan for ``tokens`` of ``streams`` x ``width``, from the shapes,
  the streams' dtype, the coefficients' dtype and the backend. The
  kernels take bfloat16 streams whose width is whole lane tiles, float32
  coefficients, tokens that are whole blocks, and n small enough that
  the backward's stacked cotangent (five copies of the 2n + n^2
  coefficients, the statistic's and the n pre-mix weights) is one
  (128, 128) transpose; everything else, and every backend but a TPU,
  runs ``jax.numpy``."""
  cols = 2 * streams + streams * streams
  tiled = (width % _LANES == 0 and tokens % _TOKEN_BLOCK == 0 and
           _BACKWARD_TERMS * cols + 1 + streams <= _LANES)
  kernel = (tiled and jnp.dtype(dtype) == jnp.bfloat16 and
            jnp.dtype(coef_dtype) == jnp.float32 and
            jax.default_backend() == "tpu")
  return MhcPlan("pallas", _TOKEN_BLOCK) if kernel else MhcPlan("xla", 0)


def mhc_stats(tokens: int, streams: int, width: int, dtype,
              coef_dtype=jnp.float32) -> dict:
  """What a run states of the plan at the job's shapes (the sublayers
  share one shape, so ``kernel_share``, the share of a step's sublayers
  whose mixing takes the kernels, is 1 or 0)."""
  plan = mhc_plan(tokens, streams, width, dtype, coef_dtype)
  return {"implementation": plan.implementation,
          "token_block": plan.token_block,
          "kernel_share": float(plan.implementation == "pallas")}


class Premixed(NamedTuple):
  """What ``mhc_pre`` gives a sublayer: its input h (B, T, C) in the
  compute dtype, the coefficients (B, T, n), (B, T, n), (B, T, n, n)
  float32, the streams as ``mhc_post`` takes them (the same array;
  through the kernels their cotangent joins ``mhc_pre``'s backward), and
  the plan both halves run."""
  h: jax.Array
  h_pre: jax.Array
  h_post: jax.Array
  h_res: jax.Array
  streams: jax.Array
  plan: MhcPlan


def mhc_pre(streams, phi, bias, alpha, mixing: Mixing, dtype) -> Premixed:
  """h and the three coefficients of the streams (B, T, n, C), h in
  ``dtype``. ``phi`` (n C, 2n + n^2) has its columns [pre | post | res]
  (the row-major n x n of H_res), ``bias`` the same order, ``alpha`` the
  three scales (pre, post, res)."""
  b, t, n, d = streams.shape
  plan = mhc_plan(b * t, n, d, streams.dtype, mixing.coef_dtype)
  if plan.implementation == "pallas":
    return _pallas_pre(streams, phi, bias, alpha, mixing, H_POST_SCALE,
                       dtype, plan.token_block)
  h_pre, h_post, h_res = coefficients(streams, phi, bias, alpha, mixing,
                                      H_POST_SCALE)
  return Premixed(premix(streams, h_pre, dtype), h_pre, h_post, h_res,
                  streams, plan)


def mhc_post(pre: Premixed, y):
  """X'[i] = sum_j H_res[i, j] X[j] + h_post[i] y of ``mhc_pre``'s
  streams and coefficients and the sublayer's output y, in the streams'
  dtype, by the plan ``mhc_pre`` chose."""
  if pre.plan.implementation == "pallas":
    return _pallas_post(pre.streams, pre.h_res, pre.h_post, y,
                        pre.plan.token_block)
  return residual_mix(pre.streams, pre.h_res, pre.h_post, y)


# -- jax.numpy -----------------------------------------------------------------

def sinkhorn(logits, iters: int, eps: float):
  """Sinkhorn-Knopp over the last two axes of ``exp(logits)``: each
  iteration divides by the row sums, then by the column sums, ``eps`` in
  each denominator (mHC's projection onto the doubly stochastic
  matrices)."""
  m = jnp.exp(logits)
  for _ in range(iters):
    m = m / (jnp.sum(m, -1, keepdims=True) + eps)
    m = m / (jnp.sum(m, -2, keepdims=True) + eps)
  return m


def sinkhorn_residual(h_res):
  """The largest |row or column sum - 1| of any matrix in ``h_res``."""
  return jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, -1) - 1)),
                     jnp.max(jnp.abs(jnp.sum(h_res, -2) - 1)))


def coefficients(streams, phi, bias, alpha, mixing: Mixing,
                 post_scale: float):
  """(h_pre, h_post, h_res) float32 of the streams (B, T, n, C), in
  ``mixing.coef_dtype`` throughout, the product at HIGHEST precision (on
  a TPU a float32 product otherwise takes bfloat16 passes)."""
  b, t, n, d = streams.shape
  dt = mixing.coef_dtype
  x = streams.reshape(b, t, n * d).astype(dt)
  inv_rms = lax.rsqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                               keepdims=True) + mixing.norm_eps)
  z = jnp.dot(x, phi.astype(dt), precision=lax.Precision.HIGHEST,
              preferred_element_type=dt) * inv_rms.astype(dt)
  alpha, bias = alpha.astype(dt), bias.astype(dt)
  h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
  h_post = post_scale * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] +
                                       bias[n:2 * n])
  logits = (alpha[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
  h_res = sinkhorn(jnp.clip(logits, *mixing.clamp), mixing.iters,
                   mixing.eps)
  return tuple(a.astype(jnp.float32) for a in (h_pre, h_post, h_res))


def premix(streams, h_pre, dtype):
  """A sublayer's input: sum_i h_pre[i] X[i], in float32, cast once."""
  return jnp.sum(h_pre[..., None] * streams.astype(jnp.float32),
                 -2).astype(dtype)


def residual_mix(streams, h_res, h_post, y):
  """X'[i] = sum_j H_res[i, j] X[j] + h_post[i] y, in float32 as
  elementwise products (a 4 x 4 mix is no work for the MXU), cast once
  to the streams' dtype."""
  x = streams.astype(jnp.float32)
  mixed = jnp.sum(h_res[..., None] * x[..., None, :, :], -2)
  return (mixed + h_post[..., None] *
          y.astype(jnp.float32)[..., None, :]).astype(streams.dtype)


def _top_bits(a):
  """float32 ``a`` with all but the top 8 bits of its significand cleared
  by a mask, not rounded: a bfloat16 value that no allowance for excess
  precision can skip. The TPU's code generator takes ``f32(bf16(a))`` for
  ``a`` inside a fusion, so a split by rounding leaves its second and
  third terms 0 and the product at bfloat16's precision
  (tests/test_tpu_step_compile.py reads it in the generated code)."""
  bits = lax.bitcast_convert_type(a, jnp.int32)
  return lax.bitcast_convert_type(bits & jnp.int32(-65536), jnp.float32)


def split3(a):
  """float32 ``a`` as three bfloat16 terms whose sum is ``a`` exactly
  (each term takes the next 8 of the 24 bits of the significand; the
  differences are exact in float32)."""
  hi = _top_bits(a)
  rest = a - hi
  mid = _top_bits(rest)
  return tuple(t.astype(jnp.bfloat16) for t in (hi, mid, rest - mid))


# -- the kernels ---------------------------------------------------------------
#
# A grid step is a block of tokens: the streams (tb, n C) bfloat16 as the
# carry holds them (stream i on lanes [i C, (i + 1) C)), h or y (tb, C).
# The coefficients are ROWS, a token a lane: ``rows`` (2n + n^2, tb),
# [pre | post | res], so that Sinkhorn-Knopp's arithmetic is a few vector
# registers wide; matrix row i of H_res is the (n, tb) slab
# ``rows[2n + n i : 2n + n (i + 1)]``, sublane j its column j. What
# multiplies a stream goes over to COLUMNS, a token a sublane, through
# one transpose of a (128, tb) scratch. The inner loops walk the streams
# a lane tile at a time.


class _Static(NamedTuple):
  """A kernel's static parameters (hashable: the kernels' jits key on
  them)."""
  streams: int
  width: int
  block: int
  iters: int = 0
  eps: float = 0.0
  clamp: Tuple[float, float] = (0.0, 0.0)
  norm_eps: float = 0.0
  post_scale: float = 0.0
  interpret: bool = False

  @property
  def cols(self):
    return 2 * self.streams + self.streams * self.streams


def _lanes(s: _Static, stream, q):
  """The lane tile ``q`` of stream ``stream`` in a (tb, n C) block."""
  return _ds(stream * s.width + q * _CHUNK)


def _ds(start):
  from jax.experimental import pallas as pl
  return pl.ds(pl.multiple_of(start, _CHUNK), _CHUNK)


def _over_tiles(s: _Static, body, init):
  """``body(q, carry)`` over the C / 128 lane tiles of a stream."""
  return lax.fori_loop(0, s.width // _CHUNK, body, init)


def _to_rows(scratch, cols):
  """Columns (tb, 1) as rows (len(cols), tb), through the (tb, 128)
  scratch's transpose."""
  for j, col in enumerate(cols):
    scratch[:, j:j + 1] = col
  return scratch[...].T[:len(cols)]


def _to_cols(scratch, rows):
  """Rows (k, tb) stacked as the first rows of the (128, tb) scratch,
  zeros below, as its transpose (tb, 128)."""
  at = 0
  for r in rows:
    scratch[at:at + r.shape[0]] = r
    at += r.shape[0]
  scratch[at:] = jnp.zeros((scratch.shape[0] - at, scratch.shape[1]),
                           jnp.float32)
  return scratch[...].T


def _sums_of_products(s: _Static, pairs, tb):
  """sum over a stream's lanes of a * b for each pair of callables
  ``(q -> (tb, 128) float32)``: (tb, 1) each, a lane tile at a time."""
  def body(q, accs):
    return tuple(acc + a(q) * b(q) for acc, (a, b) in zip(accs, pairs))
  accs = _over_tiles(s, body, tuple(jnp.zeros((tb, _CHUNK), jnp.float32)
                                    for _ in pairs))
  return [jnp.sum(acc, axis=1, keepdims=True) for acc in accs]


def _coefficient_rows(s: _Static, x_ref, w_ref, aff, col_scr, ss, hist=None):
  """The coefficients of a block as rows, from the streams' block and
  phi's three stacked terms (3 cols, n C); ``ss`` the tokens' sums of
  squares (tb, 1). Returns (pre, post (n, tb), H_res's rows [(n, tb)] x
  n, and what the backward reads: z, u = x phi, 1/rms as a row, and the
  sigmoids' and the clamp's arguments a = alpha z + b).
  ``hist``: where the iterations leave each half step's matrix."""
  f32, n, c = jnp.float32, s.streams, s.cols
  inv = lax.rsqrt(ss * (1.0 / (n * s.width)) + s.norm_eps)
  inv_row = _to_rows(col_scr, [inv])
  stacked = lax.dot_general(w_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)    # (3 cols, tb)
  u = stacked[:c] + stacked[c:2 * c] + stacked[2 * c:]
  z = u * inv_row
  a = z * aff[:, 0:1] + aff[:, 1:2]
  pre = jax.nn.sigmoid(a[:n])
  post = s.post_scale * jax.nn.sigmoid(a[n:2 * n])
  logits = a[2 * n:]
  mats = [jnp.exp(jnp.clip(logits[n * i:n * (i + 1)], *s.clamp))
          for i in range(n)]
  return pre, post, _sinkhorn_rows(mats, s, hist), (z, u, inv_row, a)


def _sinkhorn_rows(mats, s: _Static, hist=None):
  """Sinkhorn-Knopp on H's rows (n, tb) each, as ``sinkhorn``; ``hist``
  (2 iters, n, n, tb) keeps the matrix before each half step."""
  def body(t, mats):
    if hist is not None:
      for i, m in enumerate(mats):
        hist[2 * t, i] = m
    mats = [m / (jnp.sum(m, axis=0, keepdims=True) + s.eps) for m in mats]
    if hist is not None:
      for i, m in enumerate(mats):
        hist[2 * t + 1, i] = m
    col = functools.reduce(jnp.add, mats) + s.eps
    return tuple(m / col for m in mats)
  return list(lax.fori_loop(0, s.iters, body, tuple(mats)))


def _sinkhorn_back(grads, hist, s: _Static):
  """The cotangent of exp(clamp(logits)) from that of Sinkhorn-Knopp's
  result, the half steps walked back from ``hist``."""
  n = s.streams

  def body(k, g):
    t = s.iters - 1 - k
    rowed = [hist[2 * t + 1, i] for i in range(n)]
    col = functools.reduce(jnp.add, rowed) + s.eps
    out = [r / col for r in rowed]
    along = functools.reduce(jnp.add, [gi * oi for gi, oi in zip(g, out)])
    g = [(gi - along) / col for gi in g]
    before = [hist[2 * t, i] for i in range(n)]
    return tuple(
        (gi - jnp.sum(gi * ri, axis=0, keepdims=True)) /
        (jnp.sum(bi, axis=0, keepdims=True) + s.eps)
        for gi, ri, bi in zip(g, rowed, before))
  return list(lax.fori_loop(0, s.iters, body, tuple(grads)))


def _pre_fwd_kernel(x_ref, w_ref, aff_ref, h_ref, rows_ref, col_scr,
                    row_scr, *, s: _Static):
  """h and the coefficients' rows of one block of tokens."""
  f32, n, tb = jnp.float32, s.streams, x_ref.shape[0]
  stream = lambda i: lambda q: x_ref[:, _lanes(s, i, q)].astype(f32)
  ss = functools.reduce(jnp.add, _sums_of_products(
      s, [(stream(i), stream(i)) for i in range(n)], tb))
  pre, post, mats, _ = _coefficient_rows(s, x_ref, w_ref, aff_ref[...],
                                         col_scr, ss)
  rows_ref[0:n] = pre
  rows_ref[n:2 * n] = post
  for i, m in enumerate(mats):
    rows_ref[2 * n + n * i:2 * n + n * (i + 1)] = m
  weights = _to_cols(row_scr, [pre])

  def mix(q, carry):
    h = weights[:, 0:1] * stream(0)(q)
    for i in range(1, n):
      h = h + weights[:, i:i + 1] * stream(i)(q)
    h_ref[:, _ds(q * _CHUNK)] = h.astype(h_ref.dtype)
    return carry
  _over_tiles(s, mix, 0)


def _pre_bwd_kernel(x_ref, w_ref, w5_ref, aff_ref, dh_ref, drows_ref,
                    dpass_ref, dx_ref, dwt_ref, dstats_ref, col_scr, row_scr,
                    hist, gather, d_stream, *, s: _Static):
  """The transpose of ``_pre_fwd_kernel`` for one block of tokens,
  everything of the forward formed again in VMEM: dx (with the passed
  streams' cotangent ``dpass`` added before the one rounding), and phi's
  (transposed) gradient and the sums of the scales' and biases'
  cotangents accumulated over the blocks."""
  from jax.experimental import pallas as pl
  f32, bf16 = jnp.float32, jnp.bfloat16
  n, c, tb, k = s.streams, s.cols, x_ref.shape[0], s.streams * s.width

  @pl.when(pl.program_id(0) == 0)
  def _():
    dwt_ref[...] = jnp.zeros_like(dwt_ref)
    dstats_ref[...] = jnp.zeros_like(dstats_ref)
  stream = lambda i: lambda q: x_ref[:, _lanes(s, i, q)].astype(f32)
  dh = lambda q: dh_ref[:, _ds(q * _CHUNK)].astype(f32)
  sums = _sums_of_products(s, [(stream(i), stream(i)) for i in range(n)] +
                           [(stream(i), dh) for i in range(n)], tb)
  ss = functools.reduce(jnp.add, sums[:n])
  aff = aff_ref[...]
  pre, _, _, (z, u, inv_row, a) = _coefficient_rows(s, x_ref, w_ref, aff,
                                                    col_scr, ss, hist)
  g = drows_ref[...]
  # h = sum_i pre_i X_i reads pre: its cotangent adds to the one given.
  g_pre = g[:n] + _to_rows(col_scr, sums[n:])
  gather[0:n] = g_pre * pre * (1 - pre)
  sig = jax.nn.sigmoid(a[n:2 * n])
  gather[n:2 * n] = g[n:2 * n] * s.post_scale * sig * (1 - sig)
  d_exp = _sinkhorn_back([g[2 * n + n * i:2 * n + n * (i + 1)]
                          for i in range(n)], hist, s)
  logits = a[2 * n:]
  inside = (logits > s.clamp[0]) & (logits < s.clamp[1])
  for i in range(n):
    at = slice(2 * n + n * i, 2 * n + n * (i + 1))
    gather[at] = jnp.where(inside[n * i:n * (i + 1)],
                           d_exp[i] * hist[0, i], 0.0)
  da = gather[...]
  dstats_ref[0:c] += da * z
  dstats_ref[c:2 * c] += da
  dz = da * aff[:, 0:1]
  du = dz * inv_row
  # z = u / rms: the statistic's share of dx is a multiple of x.
  d_inv = jnp.sum(dz * u, axis=0, keepdims=True)
  scale_x = -d_inv * inv_row * inv_row * inv_row * (1.0 / k)
  hi, mid, lo = (v.astype(f32) for v in split3(du))
  across = _to_cols(row_scr, [hi, mid, lo, hi, mid, scale_x, pre])
  lhs = across.astype(bf16)
  du3 = row_scr[0:3 * c].astype(bf16)
  at_x = _BACKWARD_TERMS * c
  times_x = jnp.broadcast_to(across[:, at_x:at_x + 1], (tb, _CHUNK))
  for i in range(n):
    # The two products a stream: whole (a product a lane tile keeps the
    # MXU filling and draining), the x-side one into the scratch that
    # the elementwise loop reads.
    stream_i = slice(i * s.width, (i + 1) * s.width)
    x = x_ref[:, stream_i]
    terms = lax.dot_general(du3, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)    # (3 cols, C)
    dwt_ref[:, stream_i] += terms[:c] + terms[c:2 * c] + terms[2 * c:]
    d_stream[...] = lax.dot_general(lhs, w5_ref[:, stream_i],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=f32)
    weight = jnp.broadcast_to(across[:, at_x + 1 + i:at_x + 2 + i],
                              (tb, _CHUNK))

    def body(q, carry, i=i, weight=weight):
      lanes = _lanes(s, i, q)
      dx = (d_stream[:, _ds(q * _CHUNK)] +
            times_x * x_ref[:, lanes].astype(f32) + weight * dh(q) +
            dpass_ref[:, lanes].astype(f32))
      dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
      return carry
    _over_tiles(s, body, 0)


def _post_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, *, s: _Static):
  """The mixed streams of one block of tokens."""
  f32, n, tb = jnp.float32, s.streams, x_ref.shape[0]
  coef = coef_ref[...]
  at = lambda k: jnp.broadcast_to(coef[:, k:k + 1], (tb, _CHUNK))
  post = [at(i) for i in range(n)]
  res = [[at(n + n * i + j) for j in range(n)] for i in range(n)]

  def body(q, carry):
    xs = [x_ref[:, _lanes(s, j, q)].astype(f32) for j in range(n)]
    y = y_ref[:, _ds(q * _CHUNK)].astype(f32)
    for i in range(n):
      mixed = res[i][0] * xs[0]
      for j in range(1, n):
        mixed = mixed + res[i][j] * xs[j]
      out_ref[:, _lanes(s, i, q)] = (mixed + post[i] * y).astype(
          out_ref.dtype)
    return carry
  _over_tiles(s, body, 0)


def _post_bwd_kernel(x_ref, y_ref, coef_ref, g_ref, dx_ref, dy_ref,
                     dcoef_ref, *, s: _Static):
  """The transpose of ``_post_fwd_kernel`` for one block of tokens: the
  streams' and y's cotangents, and the coefficients' as sums over the
  lanes."""
  f32, n, tb = jnp.float32, s.streams, x_ref.shape[0]
  coef = coef_ref[...]
  at = lambda k: jnp.broadcast_to(coef[:, k:k + 1], (tb, _CHUNK))
  post = [at(i) for i in range(n)]
  res = [[at(n + n * i + j) for j in range(n)] for i in range(n)]

  def body(q, accs):
    xs = [x_ref[:, _lanes(s, j, q)].astype(f32) for j in range(n)]
    gs = [g_ref[:, _lanes(s, i, q)].astype(f32) for i in range(n)]
    y = y_ref[:, _ds(q * _CHUNK)].astype(f32)
    for j in range(n):
      dx = res[0][j] * gs[0]
      for i in range(1, n):
        dx = dx + res[i][j] * gs[i]
      dx_ref[:, _lanes(s, j, q)] = dx.astype(dx_ref.dtype)
    dy = post[0] * gs[0]
    for i in range(1, n):
      dy = dy + post[i] * gs[i]
    dy_ref[:, _ds(q * _CHUNK)] = dy.astype(dy_ref.dtype)
    terms = [gs[i] * y for i in range(n)] + [
        gs[i] * xs[j] for i in range(n) for j in range(n)]
    return tuple(acc + term for acc, term in zip(accs, terms))
  accs = _over_tiles(s, body, tuple(jnp.zeros((tb, _CHUNK), f32)
                                    for _ in range(n + n * n)))
  for k, acc in enumerate(accs):
    dcoef_ref[:, k:k + 1] = jnp.sum(acc, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kernel", "name", "s", "outs",
                                             "ins", "scratch", "reduces",
                                             "aliases"))
def _call(*arrays, kernel, name, s: _Static, ins, outs, scratch=(),
          reduces=False, aliases=None):
  """One pass of ``kernel`` over the blocks of tokens. ``ins`` / ``outs``
  name each array's kind: ``"tokens"`` (N, k) a block of tokens with all
  their k lanes a step, ``"rows"`` (k, N) a block of lanes a step,
  ``"whole"`` the whole array every step; ``outs`` are (kind, shape,
  dtype). ``reduces``: an output accumulates over the blocks (the grid
  runs in order).
  ``aliases``: {input: output} written in place (``pallas_call``'s). A
  ``jit`` of its own: the sublayers of a model that call it alike trace
  and lower it once."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu
  tokens, tb = arrays[0].shape[0], s.block

  def spec(kind, shape):
    if kind == "tokens":
      return pl.BlockSpec((tb, shape[1]), lambda i: (i, 0))
    if kind == "rows":
      return pl.BlockSpec((shape[0], tb), lambda i: (0, i))
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))
  return pl.pallas_call(
      functools.partial(kernel, s=s),
      grid=(tokens // tb,),
      in_specs=[spec(kind, a.shape) for kind, a in zip(ins, arrays)],
      out_specs=[spec(kind, shape) for kind, shape, _ in outs],
      out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                 for _, shape, dtype in outs],
      scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
      input_output_aliases=dict(aliases or ()),
      interpret=s.interpret,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary" if reduces else "parallel",),
          vmem_limit_bytes=_VMEM_LIMIT_BYTES),
      name=name)(*arrays)


def _phi_terms(phi):
  """phi (n C, cols) as its three bfloat16 terms, transposed and stacked
  (3 cols, n C), and the backward's five (128, n C): hi hi hi mid mid,
  zeros below."""
  hi, mid, lo = split3(phi.T)
  k = phi.shape[0]
  five = jnp.concatenate(
      [hi, hi, hi, mid, mid,
       jnp.zeros((_LANES - _BACKWARD_TERMS * phi.shape[1], k), jnp.bfloat16)])
  return jnp.concatenate([hi, mid, lo]), five


def _pre_forward(x, phi, scale, bias, s: _Static, dtype):
  tokens, k = x.shape
  w3, _ = _phi_terms(phi)
  return _call(x, w3, jnp.stack([scale, bias], axis=1),
               kernel=_pre_fwd_kernel, name="mhc_pre_fwd", s=s,
               ins=("tokens", "whole", "whole"),
               outs=(("tokens", (tokens, s.width), dtype),
                     ("rows", (s.cols, tokens), jnp.float32)),
               scratch=((s.block, _LANES), (_LANES, s.block)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pre(x, phi, scale, bias, s: _Static, dtype):
  """(h (N, C), the coefficients' rows (cols, N) float32, x) of the
  streams x (N, n C), phi (n C, cols), the scale and bias of each
  coefficient (cols,). x is passed through untouched, so that what the
  caller does with it after (the residual mix) hands its cotangent to
  this function's backward, which adds it to its own inside the kernel:
  the streams' cotangent is written once, in bfloat16, where autodiff
  would add two."""
  return _pre_forward(x, phi, scale, bias, s, dtype) + (x,)


def _pre_fwd(x, phi, scale, bias, s, dtype):
  return (_pre_forward(x, phi, scale, bias, s, dtype) + (x,),
          (x, phi, scale, bias))


def _pre_bwd(s, dtype, residuals, cotangents):
  x, phi, scale, bias = residuals
  dh, drows, dpass = cotangents
  tokens, k = x.shape
  c = s.cols
  w3, w5 = _phi_terms(phi)
  dx, dwt, dstats = _call(
      x, w3, w5, jnp.stack([scale, bias], axis=1), dh.astype(dtype),
      drows.astype(jnp.float32), dpass.astype(x.dtype),
      kernel=_pre_bwd_kernel, name="mhc_pre_bwd", s=s,
      ins=("tokens", "whole", "whole", "whole", "tokens", "rows", "tokens"),
      outs=(("tokens", (tokens, k), x.dtype),
            ("whole", (c, k), jnp.float32),
            ("whole", (2 * c, s.block), jnp.float32)),
      scratch=((s.block, _LANES), (_LANES, s.block),
               (2 * s.iters, s.streams, s.streams, s.block), (c, s.block),
               (s.block, s.width)),
      reduces=True, aliases=((6, 0),))
  return (dx, dwt.T, jnp.sum(dstats[:c], axis=1),
          jnp.sum(dstats[c:], axis=1))


_pre.defvjp(_pre_fwd, _pre_bwd)


def _pallas_pre(streams, phi, bias, alpha, mixing: Mixing, post_scale,
                dtype, block, interpret=False):
  """``mhc_pre`` through the kernels: the scales of the coefficients are
  alpha spread over their columns here, under autodiff."""
  b, t, n, d = streams.shape
  s = _Static(n, d, block, mixing.iters, mixing.eps, tuple(mixing.clamp),
              mixing.norm_eps, post_scale, interpret)
  f32 = jnp.float32
  scale = jnp.concatenate([jnp.full((m,), alpha[g], f32)
                           for g, m in enumerate((n, n, n * n))])
  h, rows, passed = _pre(streams.reshape(b * t, n * d), phi.astype(f32),
                         scale, bias.astype(f32), s, dtype)
  coef = rows.T
  return Premixed(h.reshape(b, t, d), coef[:, :n].reshape(b, t, n),
                  coef[:, n:2 * n].reshape(b, t, n),
                  coef[:, 2 * n:].reshape(b, t, n, n),
                  passed.reshape(b, t, n, d), MhcPlan("pallas", block))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(x, y, coef, s: _Static):
  """The mixed streams (N, n C) of x (N, n C), y (N, C) and the
  coefficients (N, n + n^2), [h_post | H_res row-major]."""
  return _call(x, y, coef, kernel=_post_fwd_kernel, name="mhc_post_fwd",
               s=s, ins=("tokens", "tokens", "tokens"),
               outs=(("tokens", x.shape, x.dtype),))[0]


def _post_fwd(x, y, coef, s):
  return _post(x, y, coef, s), (x, y, coef)


def _post_bwd(s, residuals, g):
  x, y, coef = residuals
  return tuple(_call(x, y, coef, g.astype(x.dtype), kernel=_post_bwd_kernel,
                     name="mhc_post_bwd", s=s,
                     ins=("tokens", "tokens", "tokens", "tokens"),
                     outs=(("tokens", x.shape, x.dtype),
                           ("tokens", y.shape, y.dtype),
                           ("tokens", coef.shape, jnp.float32))))


_post.defvjp(_post_fwd, _post_bwd)


def _pallas_post(streams, h_res, h_post, y, block, interpret=False):
  """``mhc_post`` through the kernels."""
  b, t, n, d = streams.shape
  s = _Static(n, d, block, interpret=interpret)
  coef = jnp.concatenate([h_post.reshape(b * t, n),
                          h_res.reshape(b * t, n * n)], axis=1)
  out = _post(streams.reshape(b * t, n * d), y.reshape(b * t, d),
              coef.astype(jnp.float32), s)
  return out.reshape(b, t, n, d)
