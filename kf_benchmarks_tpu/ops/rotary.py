"""The rotary stage of an attention layer: what lies between a
projection and the attention core, as ONE function with a written-out
backward, one pass over full-width arrays each way.

BEYOND-REFERENCE: the reference zoo has no attention; this serves the
configured decoder (``models/mla_moe_lm.py``), both its attentions.

What it computes, in float32 with ONE rounding at the cast back to the
input's dtype (``x`` is a projection's output, (B, T, H, D)):

* optionally a norm over each head (``scale`` given: RMSNorm's
  arithmetic, ``x * rsqrt(mean(x^2) + eps) * scale``),
* optionally a factor (the scores' scale where it is no power of two),
* RoPE over the trailing ``rot_dims`` dimensions of each head, pairing
  dimension i with i + rot_dims/2 (``rotate_half``), the leading ones
  passing through: ``n * cos + rotate_half(n) * sin``.

``rotate_half`` never slices the lane axis into halves: it is a lane
rotation of the whole row, and its sign lives in the sine table (the
first half of the rotated dimensions holds -sin, so ``(-x2) * sin`` is
``x2 * (-sin)``, the same bits). On a TPU the stage is a Pallas pass
over blocks of the projection's output as the product left it, (rows,
a group of heads x D), a head at a time over aligned lane tiles
(``pltpu.roll``), written head by head as the core reads q;
elsewhere, and for shapes the kernel does not tile (a head narrower
than the 128 lanes), the same arithmetic as plain ``jnp`` with the
rotation as a 0/1 permutation product (exact at ``highest``). Which of
the two, and the kernel's block of rows, follows from the shapes and
the backend (``rotary_plan``, beside ``sequence.flash_plan``): no flag.

The backward pass is written out (``jax.custom_vjp``): the rotation's
transpose is the rotation by the negative angle, with the same tables,
and the norm's backward recomputes its statistics from the SAVED INPUT.
Under a norm the residuals are the input in its own dtype and the
scale; without one nothing is kept but the tables. Nothing float32 of
the input's size exists outside the pass, forward or backward.
Autodiff of the plain composition kept float32 copies of q and worked
on half-width float32 arrays (a 64-wide array fills half a vector
register's lanes): 15.8 ms a step for 2.2 ms of bytes in the
trinity-mini cell (PERF.md section 6, PR 38).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_LANES = 128
# What one grid step of the kernel moves: its input and output blocks
# together. Twice this is in VMEM (both are double-buffered) beside the
# tables' blocks; a block of a megabyte and more keeps the 0.35 us a grid
# step under a tenth of its transfer.
_BLOCK_BYTES = 4 * 2 ** 20
_MAX_BLOCK_ROWS = 1024
# The heads of one block: the kernel's body is written out a head, so
# its heads are what a trace of the model pays for at every build, warm
# cache or not (32 heads a body and a body a layer added 15 s to the
# trinity-mini cell's set-up: PERF.md section 6, PR 38).
_MAX_BLOCK_HEADS = 8
_VMEM_LIMIT_BYTES = 48 * 2 ** 20


class Tables(NamedTuple):
  """cos and the SIGNED sin of ``tables``: (T, width) float32 each."""
  cos: jax.Array
  sin: jax.Array


@dataclasses.dataclass(frozen=True)
class RotaryPlan:
  """What ``rotary_stage`` runs for one shape: ``rotary_plan`` decides it
  from the shapes and the backend, and a model states it in its run's
  ``stats["rotary"]``. ``implementation``: ``"pallas"`` (the kernel, over
  ``block_rows`` positions of ``block_heads`` heads a grid step) or
  ``"xla"`` (plain ``jnp``, both 0). ``table_width``: the trailing dimensions of a head
  that the tables cover (0: nothing is rotated): the rotated ones rounded
  up to whole lane tiles for the kernel, the whole head for ``jnp``."""
  implementation: str
  block_rows: int
  block_heads: int
  table_width: int


def rotary_plan(seq_len: int, heads: int, head_dim: int, rot_dims: int,
                normed: bool, dtype, on_tpu: Optional[bool] = None
                ) -> RotaryPlan:
  """The plan of ``rotary_stage`` for these shapes. The kernel takes
  heads that are whole lane tiles, positions that a block of rows
  divides, and a norm only over a head it rotates whole or not at all
  (what the two attention modules ask for); everything else, and every
  backend but a TPU, runs the ``jnp`` form."""
  if rot_dims % 2 or not 0 <= rot_dims <= head_dim:
    raise ValueError(f"rot_dims={rot_dims} of a head of {head_dim}: the "
                     f"rotated dimensions are an even count of them")
  if on_tpu is None:
    on_tpu = jax.default_backend() == "tpu"
  width = min(head_dim, -(-rot_dims // _LANES) * _LANES)
  itemsize = jnp.dtype(dtype).itemsize
  group = max(g for g in range(1, _MAX_BLOCK_HEADS + 1) if heads % g == 0)
  rows = min(seq_len, _MAX_BLOCK_ROWS,
             _BLOCK_BYTES // (2 * group * head_dim * itemsize))
  rows = 1 << max(rows, 1).bit_length() - 1
  tiled = (head_dim % _LANES == 0 and rows >= 8 * (4 // itemsize) and
           seq_len % rows == 0 and (not normed or width in (0, head_dim)))
  if on_tpu and tiled:
    return RotaryPlan("pallas", rows, group, width)
  return RotaryPlan("xla", 0, 0, head_dim if rot_dims else 0)


def tables(seq_len: int, rot_dims: int, theta: float,
           width: int) -> Optional[Tables]:
  """cos and SIGNED sin of RoPE's angles for positions 0..seq_len-1 over
  the trailing ``width`` dimensions of a head, of which the last
  ``rot_dims`` are rotated (angles in float32, position x
  theta^(-2i / rot_dims) for the pair (i, i + rot_dims/2)). The leading
  ``width - rot_dims`` pass through: cos 1, sin 0. The sine's sign is
  ``rotate_half``'s: minus on the first half of the rotated dimensions.
  Every entry is computed in place from its own index: no half is built
  and joined. None where nothing is rotated."""
  if not rot_dims:
    return None
  lane = jnp.arange(width, dtype=jnp.int32) - (width - rot_dims)
  half = rot_dims // 2
  pair = (2 * (lane % half)).astype(jnp.float32)
  inv_freq = 1.0 / (theta ** (pair / rot_dims))
  ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None]
  rotated = (lane >= 0)[None]
  cos = jnp.where(rotated, jnp.cos(ang), 1.0)
  sin = jnp.where(rotated, jnp.where((lane < half)[None], -jnp.sin(ang),
                                     jnp.sin(ang)), 0.0)
  return Tables(cos, sin)


# -- the arithmetic, shared by the kernel's body and the jnp form -------------
#
# ``roll(a, k)`` rotates the last axis: result[..., i] = a[..., i - k].
# The tables' last axis is the rotated dimensions preceded by
# ``width - rot_dims`` that pass through (sin 0 there).

def _partners(sin, rot_dims):
  """The sine table split by where a dimension's partner lies: rot_dims/2
  above it (the first half) or below it (the second). One table serves
  both where the rotated dimensions are the whole width: the two
  rotations are then the same one."""
  width, half = sin.shape[-1], rot_dims // 2
  if rot_dims == width:
    return ((half, sin),)
  lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, sin.ndim - 1)
  first = lane < width - half
  return ((width - half, jnp.where(first, sin, 0.0)),
          (half, jnp.where(first, 0.0, sin)))


def _rotate(n, cos, partners, roll):
  """n * cos + rotate_half(n) * sin."""
  out = n * cos
  for shift, sin in partners:
    out = out + roll(n, shift) * sin
  return out


def _unrotate(g, cos, partners, roll):
  """The transpose of ``_rotate``: the rotation by the negative angle,
  ``g * cos - rotate_half(g) * sin``, with the same tables."""
  width = g.shape[-1]
  out = g * cos
  for shift, sin in partners:
    out = out + roll(g * sin, width - shift)
  return out


def _inv_rms(x32, eps):
  return jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


def _norm_backward(dn, x32, weight, eps):
  """(dx, d weight's summand) of ``x * rsqrt(mean(x^2) + eps) * weight``
  from the saved x: the statistic is formed again, not kept."""
  r = _inv_rms(x32, eps)
  dw = dn * weight
  dx = r * dw - x32 * (r * r * r) * jnp.mean(dw * x32, axis=-1,
                                             keepdims=True)
  return dx, dn * x32 * r


def _matmul_roll(a, shift):
  """``jnp.roll(a, shift, -1)`` as a product with a 0/1 permutation, so
  that XLA rotates whole rows and forms no half: exact at ``highest``
  (every output is one input times one)."""
  width = a.shape[-1]
  i = jnp.arange(width)
  perm = (i[:, None] == (i[None, :] - shift) % width).astype(a.dtype)
  return jnp.matmul(a, perm, precision=jax.lax.Precision.HIGHEST)


# -- the jnp form --------------------------------------------------------------

def _lift(table):
  """(T, W) -> (1, T, 1, W), to meet (B, T, H, W)."""
  return table[None, :, None, :]


def _xla_forward(x, scale, tabs, rot_dims, eps, factor):
  n = x.astype(jnp.float32)
  if scale is not None:
    n = n * _inv_rms(n, eps) * scale.astype(jnp.float32)
  if factor != 1.0:
    n = n * factor
  if rot_dims:
    n = _rotate(n, _lift(tabs.cos), _partners(_lift(tabs.sin), rot_dims),
                _matmul_roll)
  return n.astype(x.dtype)


def _xla_backward(x, scale, tabs, rot_dims, eps, factor, dy):
  """``x`` is read by a norm's backward alone (None without one)."""
  dn = dy.astype(jnp.float32)
  if rot_dims:
    dn = _unrotate(dn, _lift(tabs.cos), _partners(_lift(tabs.sin), rot_dims),
                   _matmul_roll)
  if scale is None:
    return (dn * factor if factor != 1.0 else dn).astype(dy.dtype), None
  weight = scale.astype(jnp.float32) * factor
  dx, dweight = _norm_backward(dn, x.astype(jnp.float32), weight, eps)
  dscale = jnp.sum(dweight, axis=(0, 1, 2)) * factor
  return dx.astype(x.dtype), dscale.astype(scale.dtype)


# -- the kernel ----------------------------------------------------------------

def _lane_roll(a, shift):
  from jax.experimental.pallas import tpu as pltpu
  return pltpu.roll(a, shift, a.ndim - 1)


def _at(h, head_dim, lo, hi, by_head):
  """Where dimensions lo..hi of head h lie in a block: (H, rows, D) head
  by head, or (rows, H x D)."""
  if by_head:
    return (h, slice(None), slice(lo, hi))
  return (slice(None), slice(h * head_dim + lo, h * head_dim + hi))


def _forward_kernel(*refs, heads, head_dim, rot_dims, width, eps, factor,
                    normed):
  refs = list(refs)
  x_ref = refs.pop(0)
  scale = refs.pop(0)[...] if normed else None
  cos, partners = None, None
  if rot_dims:
    cos = refs.pop(0)[...]
    partners = _partners(refs.pop(0)[...], rot_dims)
  y_ref, = refs
  # The leading dimensions of a head that the tables do not cover: whole
  # lane tiles that pass through (never normed: ``rotary_plan``).
  lead = head_dim - width if rot_dims else 0
  for h in range(heads):
    n = x_ref[_at(h, head_dim, 0, head_dim, False)].astype(jnp.float32)
    if normed:
      n = n * _inv_rms(n, eps) * scale
    if factor != 1.0:
      n = n * factor
    if lead:
      y_ref[_at(h, head_dim, 0, lead, True)] = n[:, :lead].astype(y_ref.dtype)
      n = n[:, lead:]
    if rot_dims:
      n = _rotate(n, cos, partners, _lane_roll)
    y_ref[_at(h, head_dim, lead, head_dim, True)] = n.astype(y_ref.dtype)


def _backward_kernel(*refs, heads, head_dim, rot_dims, width, eps, factor,
                     normed):
  refs = list(refs)
  dy_ref = refs.pop(0)
  x_ref, scale = (refs.pop(0), refs.pop(0)[...]) if normed else (None, None)
  cos, partners = None, None
  if rot_dims:
    cos = refs.pop(0)[...]
    partners = _partners(refs.pop(0)[...], rot_dims)
  dx_ref = refs.pop(0)
  lead = head_dim - width if rot_dims else 0
  dscale = None
  for h in range(heads):
    g = dy_ref[_at(h, head_dim, 0, head_dim, True)].astype(jnp.float32)
    if lead:
      passed = g[:, :lead] * factor if factor != 1.0 else g[:, :lead]
      dx_ref[_at(h, head_dim, 0, lead, False)] = passed.astype(dx_ref.dtype)
      g = g[:, lead:]
    if rot_dims:
      g = _unrotate(g, cos, partners, _lane_roll)
    if normed:
      g, dweight = _norm_backward(
          g, x_ref[_at(h, head_dim, 0, head_dim, False)].astype(jnp.float32),
          scale * factor, eps)
      dscale = dweight if dscale is None else dscale + dweight
    elif factor != 1.0:
      g = g * factor
    dx_ref[_at(h, head_dim, lead, head_dim, False)] = g.astype(dx_ref.dtype)
  if normed:
    # This block's rows summed down to one sublane tile; XLA adds the
    # blocks' tiles (a few kilobytes each).
    rows = dscale.shape[0]
    refs.pop(0)[...] = jnp.sum(
        dscale.reshape(rows // 8, 8, head_dim), axis=0) * factor


@functools.partial(jax.jit, static_argnames=(
    "kernel", "name", "shape", "kinds", "outputs", "rot_dims", "width", "eps",
    "factor", "normed", "block_rows", "block_heads", "interpret"))
def _pallas_pass(*arrays, kernel, name, shape, kinds, outputs, rot_dims,
                 width, eps, factor, normed, block_rows, block_heads,
                 interpret):
  """One pass of ``kernel`` over blocks of ``block_rows`` positions of
  ``block_heads`` heads. ``kinds`` names the block of each of ``arrays``,
  ``outputs`` is (kind, dtype) pairs. A projection's side of the stage
  (``flat``) is (B, T, H, D) viewed as (B, T, H x D), the layout a
  product leaves: positions on the sublanes and each head a run of whole
  lane tiles. The core's side (``by_head``: the forward's output, the
  backward's cotangent) is (B, H, T, D), as the core's kernels read q
  and write dq: the transposition costs the pass nothing, it is where a
  head's tile is stored, and XLA's own transposes on both sides of the
  core meet their inverses here and vanish. The heads' groups are the
  grid's last axis, so a block of the tables serves all of them in turn
  without being fetched again. A ``jit`` of its own: the layers of a
  model that call it alike trace and lower it once."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu
  b, t, heads, head_dim = shape
  groups = heads // block_heads
  blocks = {
      "flat": ((b, t, heads * head_dim), pl.BlockSpec(
          (None, block_rows, block_heads * head_dim),
          lambda i, j, g: (i, j, g))),
      "by_head": ((b, heads, t, head_dim), pl.BlockSpec(
          (None, block_heads, block_rows, head_dim),
          lambda i, j, g: (i, g, j, 0))),
      "scale": ((1, head_dim), pl.BlockSpec((1, head_dim),
                                            lambda i, j, g: (0, 0))),
      "table": ((t, width), pl.BlockSpec((block_rows, width),
                                         lambda i, j, g: (j, 0))),
      "by_block": ((b, t // block_rows, groups, 8, head_dim), pl.BlockSpec(
          (None, None, None, 8, head_dim),
          lambda i, j, g: (i, j, g, 0, 0)))}
  return pl.pallas_call(
      functools.partial(kernel, heads=block_heads, head_dim=head_dim,
                        rot_dims=rot_dims, width=width, eps=eps,
                        factor=factor, normed=normed),
      grid=(b, t // block_rows, groups),
      in_specs=[blocks[kind][1] for kind in kinds],
      out_specs=[blocks[kind][1] for kind, _ in outputs],
      out_shape=[jax.ShapeDtypeStruct(blocks[kind][0], dtype)
                 for kind, dtype in outputs],
      interpret=interpret,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "parallel"),
          vmem_limit_bytes=_VMEM_LIMIT_BYTES),
      name=name)(*(a.reshape(blocks[kind][0])
                   for kind, a in zip(kinds, arrays)))


def _shared_operands(scale, tabs):
  """The scale and the tables as both kernels take them, after the
  arrays of the input's size: (kind, array) pairs."""
  return ((() if scale is None else (("scale", scale.astype(jnp.float32)),))
          + (() if tabs is None else (("table", tabs.cos),
                                      ("table", tabs.sin))))


def _run_pass(kernel, name, shape, operands, outputs, scale, tabs, plan,
              **static):
  kinds, arrays = zip(*(operands + _shared_operands(scale, tabs)))
  return _pallas_pass(
      *arrays, kernel=kernel, name=name, shape=tuple(shape), kinds=kinds,
      outputs=outputs, width=tabs.cos.shape[-1] if tabs else 0,
      normed=scale is not None, block_rows=plan.block_rows,
      block_heads=plan.block_heads, **static)


def _pallas_forward(x, scale, tabs, rot_dims, eps, factor, plan,
                    interpret=False):
  y, = _run_pass(_forward_kernel, "rotary_fwd", x.shape, (("flat", x),),
                 (("by_head", x.dtype),), scale, tabs, plan,
                 rot_dims=rot_dims, eps=eps, factor=factor,
                 interpret=interpret)
  return y.swapaxes(1, 2)


def _pallas_backward(x, scale, tabs, rot_dims, eps, factor, plan, dy,
                     interpret=False):
  """``x`` is read by a norm's backward alone (None without one)."""
  normed = scale is not None
  out = _run_pass(
      _backward_kernel, "rotary_bwd", dy.shape,
      (("by_head", dy.swapaxes(1, 2)),) + ((("flat", x),) if normed else ()),
      (("flat", dy.dtype),) + ((("by_block", jnp.float32),) if normed
                               else ()),
      scale, tabs, plan, rot_dims=rot_dims, eps=eps, factor=factor,
      interpret=interpret)
  dx = out[0].reshape(dy.shape)
  if not normed:
    return dx, None
  return dx, jnp.sum(out[1], axis=(0, 1, 2, 3)).astype(scale.dtype)


# -- the stage -----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _stage(x, scale, tabs, rot_dims, eps, factor, plan):
  if plan.implementation == "pallas":
    return _pallas_forward(x, scale, tabs, rot_dims, eps, factor, plan)
  return _xla_forward(x, scale, tabs, rot_dims, eps, factor)


def _stage_fwd(x, scale, tabs, rot_dims, eps, factor, plan):
  # The input is kept for a norm's backward alone: a rotation's needs
  # nothing but the tables.
  return (_stage(x, scale, tabs, rot_dims, eps, factor, plan),
          (x if scale is not None else None, scale, tabs))


def _stage_bwd(rot_dims, eps, factor, plan, residuals, dy):
  x, scale, tabs = residuals
  backward = (functools.partial(_pallas_backward, plan=plan)
              if plan.implementation == "pallas" else _xla_backward)
  dx, dscale = backward(x, scale, tabs, rot_dims, eps, factor, dy=dy)
  # The tables are positions and constants: nothing learns through them.
  return dx, dscale, jax.tree.map(jnp.zeros_like, tabs)


_stage.defvjp(_stage_fwd, _stage_bwd)


def rotary_stage(x, tabs: Optional[Tables] = None, scale=None, *,
                 rot_dims: int = 0, eps: float = 0.0, factor: float = 1.0):
  """Head norm (``scale`` given), ``factor``, RoPE over the trailing
  ``rot_dims`` of each head and the cast, over x (B, T, H, D): one pass
  forward, one backward (the module's docstring).

  ``tabs``: ``tables(T, rot_dims, theta, width)`` at the width of
  ``rotary_plan`` for this shape, built once for all the layers of a
  model's call (``stage_tables`` for one call site alone); unread where
  nothing is rotated."""
  _, t, heads, head_dim = x.shape
  plan = rotary_plan(t, heads, head_dim, rot_dims, scale is not None,
                     x.dtype)
  if rot_dims and tabs.cos.shape != (t, plan.table_width):
    raise ValueError(
        f"tables of shape {tabs.cos.shape} for {t} positions and "
        f"{plan.table_width} trailing dimensions of a head of {head_dim} "
        f"({rot_dims} rotated): build them with rotary.tables at "
        f"rotary_plan's table_width")
  return _stage(x, scale, tabs if rot_dims else None, rot_dims, float(eps),
                float(factor), plan)


def stage_tables(shape, rot_dims: int, theta: float, normed: bool,
                 dtype) -> Optional[Tables]:
  """The tables ``rotary_stage`` wants for an x of ``shape`` (B, T, H, D)
  and ``dtype``: ``tables`` at its plan's width."""
  _, t, heads, head_dim = shape
  return tables(t, rot_dims, theta, rotary_plan(
      t, heads, head_dim, rot_dims, normed, dtype).table_width)


def stage_stats(batch: int, seq_len: int, heads: int, head_dim: int,
                rot_dims: int, normed: bool, dtype, layers: int) -> dict:
  """One call site's row of a run's ``stats["rotary"]``: what the stage
  runs there (``rotary_plan``, from the shapes and the backend, so it
  cannot vary by step) and what it moves: the bytes one call reads and
  writes in the forward pass, and what a layer keeps for its backward
  (under a norm the input in its own dtype, the statistic being formed
  again; without one nothing)."""
  plan = rotary_plan(seq_len, heads, head_dim, rot_dims, normed, dtype)
  size = batch * seq_len * heads * head_dim * jnp.dtype(dtype).itemsize
  return {"calls_per_layer": 1, "layers": layers,
          "rot_dims": rot_dims, "heads": heads, "head_dim": head_dim,
          "normed": normed, "implementation": plan.implementation,
          "block_rows": plan.block_rows, "block_heads": plan.block_heads,
          "bytes_read_and_written_per_call": 2 * size,
          "residual_bytes_per_layer": size * normed}
