"""The sequence mixing of a Mamba-2 layer: the causal depthwise
convolution, the state-space scan in its CHUNKED form (SSD, "state-space
duality": Dao & Gu, arXiv:2405.21060 section 6) and the gated norm
behind it, as functions of arrays.

BEYOND-REFERENCE: the reference zoo has no sequence model; this serves
the configured decoder's third family (``models/mla_moe_lm.py``,
``nemotron_h``), whose ``Mamba2Mixer`` holds the parameters and the two
projections around these functions.

The recurrence, per head h of ``head_dim`` P with a state of P x N
(``x`` (B, T, H, P), ``dt`` (B, T, H) after its softplus, ``A`` (H,)
negative, ``b`` and ``c`` (B, T, G, N), head h reading group
h // (H / G)):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t b_t^T        y_t = S_t c_t

It is never run a position at a time. With a = dt A and cs its running
sum INSIDE a chunk of ``chunk`` positions (``ssd_scan``):

(i)   inside a chunk  y_t += sum_{s<=t} (c_t . b_s) exp(cs_t - cs_s) dt_s x_s
(ii)  a chunk's own state  sum_s exp(cs_end - cs_s) dt_s x_s b_s^T
(iii) the states carried from chunk to chunk  S_c = exp(cs_end) S_{c-1} + (ii)
(iv)  y_t += exp(cs_t) c_t . S_{c-1}

(i), (ii) and (iv) are matrix products over a chunk's positions and
(iii) is the one sequential part, T / chunk links (64 at 8,192 tokens).
``dt``, ``A``, the running sums, every exponential and the carried state
are float32 (``scan_dtype`` is the type of the steps, their running sums
and the exponentials: bfloat16 is the lower-precision control of the
tests and the benchmark's check); the products take operands of ``x``'s
dtype into float32.

Which form runs follows from the shapes, the steps' type and the backend
(``scan_plan``, as ``rotary.rotary_plan`` and ``sequence.flash_plan``
decide theirs): no flag, no model name. There are TWO forms of the one
function, which share the plan and the stats and nothing else:

* ``"pallas"`` (a TPU, float32 steps, shapes that tile): ONE kernel
  forward and ONE backward (``jax.custom_vjp``, the backward written
  out) over the grid (batch, group, chunk), the chunks in turn. A grid
  step reads its chunk of x, B and C as the convolution left them, forms the group's scores once and each head's decay table in
  VMEM (masked before the exponential; nothing (chunk x chunk) a head is
  ever written), and carries the group's state (state x heads x
  head_dim, float32, 256 KB) in a VMEM scratch from chunk to chunk:
  (iii) is one multiply-add a link and no state array exists in the
  forward that keeps nothing; the mixer's skip ``D x`` is its epilogue.
  The rule's forward also writes the state ENTERING each chunk
  (float32); the backward walks the chunks from the last to the first with
  the state's cotangent in the scratch, forms scores and tables again and
  writes dx, dB and dC (summed over the group's heads) and the cotangents
  of the steps' small arrays and of the skip. The steps, their running
  sums and the exponentials of a position or a chunk are made outside
  over (B, T, H), and their backward (the sums', the softplus's) is
  autodiff's.
* ``"xla"`` (every other backend and shape, and the bfloat16 control):
  ``jax.numpy`` einsums, differentiated by autodiff, (iii) as ONE product
  with the chunks' lower-triangular decay matrix (``carried_states``:
  float32 operands at ``highest``, no loop in the program). What the
  tests compare the kernels with.

``mamba_core`` is rematerialised from its inputs by its caller, so
whichever form runs, nothing of the scan outlives the pass that forms it
but the kernels' entering states inside one layer's backward.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
# The heads of one group are written out in the kernels' bodies (what a
# trace of the model pays for at every build: PERF.md section 6, PR 38).
_MAX_GROUP_HEADS = 16
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class ScanPlan:
  """What ``ssd_scan`` runs for one shape: ``scan_plan`` decides it, a
  model states it in its run's ``stats["mamba"]``. ``implementation``:
  ``"pallas"`` (one kernel each way over a group's heads, the chunks in
  turn) or ``"xla"`` (einsums under autodiff); ``chunk`` positions a
  chunk, ``chunks`` of them a sequence (the links of the carried
  state, and the kernels' grid steps a group)."""
  implementation: str
  chunk: int
  chunks: int


def refusal(seq_len: int, chunk: int):
  """Why the scan cannot take ``seq_len`` positions in chunks of
  ``chunk`` (None: it can). One sentence for every caller
  (``scan_plan``, ``validation.py``)."""
  if seq_len % chunk:
    return (f"the chunked state-space scan takes whole chunks: a sequence "
            f"of {seq_len} positions is no multiple of chunk_size={chunk} "
            f"(the nearest are {seq_len - seq_len % chunk} and "
            f"{seq_len + chunk - seq_len % chunk})")
  return None


def scan_plan(seq_len: int, heads: int, groups: int, chunk: int,
              head_dim: int = 0, state: int = 0,
              scan_dtype=jnp.float32) -> ScanPlan:
  """The plan for one shape, from the shapes, the steps' type and the
  backend. The kernels take a chunk and a state that are whole lane
  tiles (a chunk's decay table and a group's B and C are (chunk, chunk)
  and (chunk, state) blocks), a group whose heads side by side are whole
  lane tiles too, each head a whole tile or a whole share of one, at most
  ``_MAX_GROUP_HEADS`` of them, and float32 steps; everything else, and
  every backend but a TPU, runs the einsums."""
  why = refusal(seq_len, chunk)
  if why:
    raise ValueError(why)
  if heads % groups:
    raise ValueError(f"{groups} groups do not divide {heads} heads")
  r = heads // groups
  tiled = (chunk % _LANES == 0 and state > 0 and state % _LANES == 0 and
           head_dim >= 8 and (r * head_dim) % _LANES == 0 and
           (_LANES % head_dim == 0 or head_dim % _LANES == 0) and
           r <= _MAX_GROUP_HEADS)
  kernel = (tiled and jnp.dtype(scan_dtype) == jnp.float32 and
            jax.default_backend() == "tpu")
  return ScanPlan("pallas" if kernel else "xla", chunk, seq_len // chunk)


def causal_conv(x, kernel, bias):
  """Depthwise causal convolution over positions: ``y[t, ch] = bias[ch] +
  sum_j kernel[j, ch] x[t - (K - 1) + j, ch]`` for x (B, T, CH) and
  kernel (K, CH): each channel sees its own last K positions, positions
  before the sequence being zero. K shifted copies, summed in float32
  (K is 4: no kernel pays for itself here)."""
  k = kernel.shape[0]
  t = x.shape[1]
  padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
  y = bias.astype(jnp.float32)
  for j in range(k):
    y = y + (lax.slice_in_dim(padded, j, j + t, axis=1).astype(jnp.float32)
             * kernel[j].astype(jnp.float32))
  return y


def _segment_decay(cs):
  """exp(cs_t - cs_s) for s <= t and 0 above the diagonal, for running
  sums ``cs`` (..., L): (..., L, L). Masked BEFORE the exponential: above
  the diagonal the difference is positive and may overflow."""
  size = cs.shape[-1]
  diff = cs[..., :, None] - cs[..., None, :]
  lower = jnp.tril(jnp.ones((size, size), bool))
  return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def carried_states(own, total):
  """(iii): the state ENTERING each chunk, (B, C, ...), from the chunks'
  own states ``own`` (B, C, G, R, P, N) and each chunk's whole decay
  ``total`` (B, C, G, R) (its cs_end), both float32: ``S_c = exp(total_c)
  S_{c-1} + own_c`` from S_{-1} = 0, returned shifted (entry c is
  S_{c-1}). One product with the chunks' decay matrix, float32 at
  ``highest``."""
  # decay[z, c] = exp(sum of total over chunks c+1 .. z-1), c < z: what
  # is left at the entry of chunk z of chunk c's own state.
  moved = jnp.moveaxis(total, 1, -1)                     # (B, G, R, C)
  cs = jnp.cumsum(moved, -1)
  before = cs - moved                                    # sums over < z
  diff = before[..., :, None] - cs[..., None, :]
  chunks = moved.shape[-1]
  strictly = jnp.tril(jnp.ones((chunks, chunks), bool), -1)
  decay = jnp.exp(jnp.where(strictly, diff, -jnp.inf))   # (B, G, R, Z, C)
  return jnp.einsum("bgrzc,bcgrpn->bzgrpn", decay, own,
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)


def ssd_scan(x, dt, a, b, c, chunk: int, scan_dtype=jnp.float32, skip=None):
  """The chunked scan (the module's docstring): y (B, T, H, P) float32
  for x (B, T, H, P), dt (B, T, H), a (H,), b and c (B, T, G, N), plus
  ``skip[h] x`` where a skip (H,) is given (the mixer's ``D``). No gate:
  the caller's."""
  _, t, heads, p = x.shape
  groups, n = b.shape[2], b.shape[3]
  plan = scan_plan(t, heads, groups, chunk, p, n, scan_dtype)
  if plan.implementation == "pallas":
    return _pallas_scan(x, dt, a, b, c, plan, skip)
  y = _xla_scan(x, dt, a, b, c, plan, scan_dtype)
  if skip is None:
    return y
  return y + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)


# -- the einsums ---------------------------------------------------------------

def _xla_scan(x, dt, a, b, c, plan: ScanPlan, scan_dtype):
  """The scan as ``jax.numpy`` einsums, differentiated by autodiff."""
  batch, t, heads, p = x.shape
  groups, n = b.shape[2], b.shape[3]
  r = heads // groups
  z, l = plan.chunks, plan.chunk
  f32 = jnp.float32
  operand = x.dtype
  xs = x.reshape(batch, z, l, groups, r, p)
  bs = b.reshape(batch, z, l, groups, n)
  cs_ = c.reshape(batch, z, l, groups, n)
  dts = dt.astype(scan_dtype).reshape(batch, z, l, groups, r)
  # Running sums of a = dt A inside each chunk, positions last.
  steps = jnp.moveaxis(dts * a.astype(scan_dtype).reshape(groups, r), 2, -1)
  run = jnp.cumsum(steps, -1)                            # (B, Z, G, R, L)
  dt_s = jnp.moveaxis(dts, 2, -1)                        # (B, Z, G, R, L)

  # (i) inside a chunk: the scores c_t . b_s once a group, under each
  # head's decay and step.
  scores = jnp.einsum("bzlgn,bzsgn->bzgls", cs_, bs,
                      preferred_element_type=f32)
  mix = (scores[:, :, :, None] * _segment_decay(run).astype(f32) *
         dt_s[..., None, :].astype(f32))                 # (B, Z, G, R, L, S)
  y = jnp.einsum("bzgrls,bzsgrp->bzlgrp", mix.astype(operand), xs,
                 preferred_element_type=f32)

  # (ii) each chunk's own state: what its positions leave at its end.
  to_end = jnp.exp(run[..., -1:] - run) * dt_s           # (B, Z, G, R, S)
  weighted = (xs.astype(f32) * jnp.moveaxis(to_end, -1, 2)[..., None].astype(
      f32)).astype(operand)
  own = jnp.einsum("bzsgrp,bzsgn->bzgrpn", weighted, bs,
                   preferred_element_type=f32)

  # (iii) the carry, then (iv) what the entering state adds.
  entering = carried_states(own, run[..., -1].astype(f32))
  from_state = jnp.einsum("bzlgn,bzgrpn->bzlgrp", cs_,
                          entering.astype(operand),
                          preferred_element_type=f32)
  y = y + from_state * jnp.moveaxis(jnp.exp(run), -1, 2)[..., None].astype(
      f32)
  return y.reshape(batch, t, heads, p)

# -- the kernels ---------------------------------------------------------------
#
# One grid step is one chunk of one group: x (L, R P), B and C (L, N) and
# y (L, R P) as the convolution's output holds them, and the group's
# state (N, R P) float32 in a scratch that outlives the step. The state lies TRANSPOSED (the state's N on the
# sublanes, the heads side by side on the lanes), so that (ii) and (iv)
# are one full-width product a group: own = B^T (x w), from_state = C S.
# Only (i) goes head by head: every head has its own decay table.
#
# The steps reach the kernels as small float32 arrays made outside (over
# (B, T, H): the running sums' and the exponentials' backward are
# autodiff's), a chunk a leading index: ``cols`` (B, G, Z, L, 3 R),
# positions on the sublanes, a head a lane: the running sum, its
# exponential (what (iv) scales by) and the weight exp(run_end - run_s)
# dt_s of (ii); ``rows`` (B, G, Z, 2 R, L), positions on the lanes: the
# step and the running sum (a decay table's s side); ``decay`` (B, Z, G,
# 1, R P): exp(run_end) a chunk and head over the head's lanes, one link
# of (iii); ``skip`` (G, 1, R P): the mixer's D over each head's lanes.

_RUN, _GROWN, _TO_END = 0, 1, 2   # the thirds of ``cols``
_STEP, _RUN_ROW = 0, 1            # the halves of ``rows``


def _over_heads(cols, third, heads, p):
  """Column ``third * heads + h`` of cols (L, 3 R) over the p lanes of
  head h, the heads side by side: (L, heads x p)."""
  rows = cols.shape[0]
  col = lambda h: cols[:, third * heads + h:third * heads + h + 1]
  if p % _LANES == 0:
    return jnp.concatenate(
        [jnp.broadcast_to(col(h), (rows, p)) for h in range(heads)], axis=1)
  per = _LANES // p
  lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
  tiles = []
  for first in range(0, heads, per):
    tile = jnp.broadcast_to(col(first), (rows, _LANES))
    for i in range(1, per):
      tile = jnp.where(lane >= i * p, col(first + i), tile)
    tiles.append(tile)
  return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _decay_table(cols, rows, h, heads, lower):
  """exp(run_t - run_s) of head h for s <= t, 0 above the diagonal
  (masked BEFORE the exponential, as ``_segment_decay``), and the row of
  its steps dt_s."""
  run_t = cols[:, _RUN * heads + h:_RUN * heads + h + 1]
  run_s = rows[_RUN_ROW * heads + h:_RUN_ROW * heads + h + 1, :]
  step_s = rows[_STEP * heads + h:_STEP * heads + h + 1, :]
  return jnp.exp(jnp.where(lower, run_t - run_s, -jnp.inf)), step_s


def _dot(a, b, contract=(1, 0)):
  """a . b into float32, over a's and b's dimension ``contract``."""
  return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                         preferred_element_type=jnp.float32)


def _lower_triangle(size):
  return (lax.broadcasted_iota(jnp.int32, (size, size), 0) >=
          lax.broadcasted_iota(jnp.int32, (size, size), 1))


def _forward_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, decay_ref,
                    skip_ref, y_ref, *rest, heads, p):
  """(i)-(iv) for one chunk of one group. ``rest``: the scratch that
  carries the state, preceded, in the rule's forward, by the output
  that keeps the state ENTERING the chunk for the backward pass."""
  from jax.experimental import pallas as pl
  state = rest[-1]

  @pl.when(pl.program_id(2) == 0)
  def _():
    state[...] = jnp.zeros_like(state)
  x, b, c = x_ref[...], b_ref[...], c_ref[...]
  cols, rows = cols_ref[...], rows_ref[...]
  operand, f32 = x.dtype, jnp.float32
  entering = state[...]
  if len(rest) == 2:
    rest[0][...] = entering
  x32 = x.astype(f32)
  # (iv): what the entering state adds, all heads in one product; with it
  # the skip ``D x``.
  grown = (_dot(c, entering.astype(operand)) *
           _over_heads(cols, _GROWN, heads, p) + skip_ref[...] * x32)
  # (i): the scores once a group, under each head's decay and step.
  scores = _dot(c, b, (1, 1))
  lower = _lower_triangle(x.shape[0])
  for h in range(heads):
    at = slice(h * p, (h + 1) * p)
    decay, step_s = _decay_table(cols, rows, h, heads, lower)
    mix = (scores * decay * step_s).astype(operand)
    y_ref[:, at] = _dot(mix, x[:, at]) + grown[:, at]
  # (ii) the chunk's own state and (iii) one link of the carry.
  weighted = (x32 * _over_heads(cols, _TO_END, heads, p)).astype(operand)
  state[...] = entering * decay_ref[...] + _dot(b, weighted, (0, 0))


def _backward_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, decay_ref,
                     skip_ref, entering_ref, dy_ref, dx_ref, db_ref, dc_ref,
                     dcols_ref, drows_ref, ddecay_ref, dskip_ref, dstate, *,
                     heads, p):
  """The transpose of ``_forward_kernel`` for one chunk of one group, the
  grid walking from the last chunk to the first: ``dstate`` carries the
  cotangent of the state LEAVING the chunk. The scores and each head's
  decay table are formed again; products take operands of x's type into
  float32, as the forward's."""
  from jax.experimental import pallas as pl

  @pl.when(pl.program_id(2) == 0)
  def _():
    dstate[...] = jnp.zeros_like(dstate)
  x, b, c = x_ref[...], b_ref[...], c_ref[...]
  cols, rows = cols_ref[...], rows_ref[...]
  operand, f32 = x.dtype, jnp.float32
  entering, leaving = entering_ref[...], dstate[...]
  entering_op, leaving_op = entering.astype(operand), leaving.astype(operand)
  dy = dy_ref[...]
  dy_op = dy.astype(operand)
  grown = _over_heads(cols, _GROWN, heads, p)
  to_end = _over_heads(cols, _TO_END, heads, p)
  # (iv) y += grown * (C S): C, S and the scale.
  from_state = _dot(c, entering_op)
  d_from = (dy * grown).astype(operand)
  dc = _dot(d_from, entering_op, (1, 1))
  d_entering = _dot(c, d_from, (0, 0))
  d_grown = dy * from_state
  # (ii) own = B^T (x w) under the leaving state's cotangent, the skip,
  # and (iii).
  x32 = x.astype(f32)
  weighted = (x32 * to_end).astype(operand)
  d_weighted = _dot(b, leaving_op)
  db = _dot(weighted, leaving_op, (1, 1))
  dx_own = d_weighted * to_end + skip_ref[...] * dy
  d_to_end = d_weighted * x32
  dskip_ref[...] = jnp.sum(dy * x32, axis=0, keepdims=True)
  ddecay_ref[...] = jnp.sum(leaving * entering, axis=0, keepdims=True)
  dstate[...] = leaving * decay_ref[...] + d_entering
  # (i) head by head; the scores' cotangent is summed over the group.
  scores = _dot(c, b, (1, 1))
  lower = _lower_triangle(x.shape[0])
  d_scores = jnp.zeros_like(scores)
  for h in range(heads):
    at = slice(h * p, (h + 1) * p)
    decay, step_s = _decay_table(cols, rows, h, heads, lower)
    damped = scores * decay
    mix = damped * step_s
    d_mix = _dot(dy_op[:, at], x[:, at], (1, 1))
    dx_ref[:, at] = (_dot(mix.astype(operand), dy_op[:, at], (0, 0)) +
                     dx_own[:, at]).astype(dx_ref.dtype)
    d_scores = d_scores + d_mix * (decay * step_s)
    d_step = d_mix * damped
    d_run = d_step * step_s
    step_row, run_row = _STEP * heads + h, _RUN_ROW * heads + h
    drows_ref[step_row:step_row + 1, :] = jnp.sum(d_step, axis=0,
                                                  keepdims=True)
    drows_ref[run_row:run_row + 1, :] = -jnp.sum(d_run, axis=0, keepdims=True)
    for third, summand in ((_RUN, d_run), (_GROWN, d_grown[:, at]),
                           (_TO_END, d_to_end[:, at])):
      dcols_ref[:, third * heads + h:third * heads + h + 1] = jnp.sum(
          summand, axis=1, keepdims=True)
  d_scores = d_scores.astype(operand)
  dc_ref[...] = (dc + _dot(d_scores, b)).astype(dc_ref.dtype)
  db_ref[...] = (db + _dot(d_scores, c, (0, 0))).astype(db_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kernel", "name", "outputs",
                                             "backward", "interpret"))
def _pallas_pass(*arrays, kernel, name, outputs, backward, interpret):
  """One pass of ``kernel`` over the grid (batch, group, chunk), the
  chunks in turn (``backward``: from the last to the first) with a
  (state, R P) float32 scratch carried between them. ``arrays``: x, B, C,
  cols, rows, decay, skip and, backward, the entering states and dy;
  ``outputs`` names the kinds of what is written. What is of x's size
  keeps the shape the convolution gave it, (B, T, .), a chunk a block of
  rows (a view with the chunks as a dimension of their own made XLA copy
  x and y: 4.7 ms a step); the small arrays have a chunk ahead of what a
  block holds. A ``jit`` of its own: the layers of a model that call it
  alike trace and lower it once."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu
  x, b, cols = arrays[0], arrays[1], arrays[3]
  batch, t, width = x.shape
  _, groups, z, l, thirds = cols.shape
  heads = thirds // 3
  n, wide = b.shape[2] // groups, width // groups
  at = (lambda k: z - 1 - k) if backward else (lambda k: k)
  f32 = jnp.float32
  kinds = {
      "x": ((batch, t, width), pl.BlockSpec(
          (None, l, wide), lambda i, g, k: (i, at(k), g))),
      "bc": ((batch, t, groups * n), pl.BlockSpec(
          (None, l, n), lambda i, g, k: (i, at(k), g))),
      "cols": ((batch, groups, z, l, 3 * heads), pl.BlockSpec(
          (None, None, None, l, 3 * heads),
          lambda i, g, k: (i, g, at(k), 0, 0))),
      "rows": ((batch, groups, z, 2 * heads, l), pl.BlockSpec(
          (None, None, None, 2 * heads, l),
          lambda i, g, k: (i, g, at(k), 0, 0))),
      "decay": ((batch, z, groups, 1, wide), pl.BlockSpec(
          (None, None, None, 1, wide), lambda i, g, k: (i, at(k), g, 0, 0))),
      "skip": ((groups, 1, wide), pl.BlockSpec(
          (None, 1, wide), lambda i, g, k: (g, 0, 0))),
      "states": ((batch, z, groups, n, wide), pl.BlockSpec(
          (None, None, None, n, wide), lambda i, g, k: (i, at(k), g, 0, 0)))}
  inputs = ("x", "bc", "bc", "cols", "rows", "decay", "skip", "states", "x")
  return pl.pallas_call(
      functools.partial(kernel, heads=heads, p=wide // heads),
      grid=(batch, groups, z),
      in_specs=[kinds[kind][1] for kind in inputs[:len(arrays)]],
      out_specs=[kinds[kind][1] for kind, _ in outputs],
      out_shape=[jax.ShapeDtypeStruct(kinds[kind][0], dtype)
                 for kind, dtype in outputs],
      scratch_shapes=[pltpu.VMEM((n, wide), f32)],
      interpret=interpret,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary"),
          vmem_limit_bytes=_VMEM_LIMIT_BYTES),
      name=name)(*arrays)


def _forward_pass(operands, keep: bool, interpret):
  """y and, where ``keep``, the float32 state ENTERING each chunk."""
  f32 = jnp.float32
  return _pallas_pass(
      *operands, kernel=_forward_kernel, name="ssd_scan_fwd",
      outputs=(("x", f32),) + ((("states", f32),) if keep else ()),
      backward=False, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _chunks(x, b, c, cols, rows, decay, skip, interpret):
  """y (B, T, H P) float32 from x (B, T, H P), B and C (B, T, G N), the
  steps' three small arrays (above) and the skip's factor over each
  head's lanes (G, 1, R P). Not differentiated, it keeps nothing: no
  state array exists outside the kernel."""
  return _forward_pass((x, b, c, cols, rows, decay, skip), False,
                       interpret)[0]


def _chunks_fwd(*args):
  # The rule's forward also writes the states entering the chunks, which
  # the backward pass reads beside the inputs.
  *operands, interpret = args
  y, entering = _forward_pass(operands, True, interpret)
  return y, (*operands, entering)


def _chunks_bwd(interpret, residuals, dy):
  x, b, c = residuals[:3]
  f32 = jnp.float32
  *grads, dskip = _pallas_pass(
      *residuals, dy, kernel=_backward_kernel, name="ssd_scan_bwd",
      outputs=(("x", x.dtype), ("bc", b.dtype), ("bc", c.dtype),
               ("cols", f32), ("rows", f32), ("decay", f32), ("decay", f32)),
      backward=True, interpret=interpret)
  # The skip's factor is one a head: the chunks' partial sums, added here.
  return (*grads, jnp.sum(dskip, axis=(0, 1)))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _pallas_scan(x, dt, a, b, c, plan: ScanPlan, skip=None,
                 interpret=False):
  """``ssd_scan`` through the kernels: the steps, their running sums
  inside each chunk and the exponentials of a position or a chunk in
  float32 over (B, T, H) here, under autodiff; everything of x's size in
  ``_chunks`` (the skip ``D x`` is the forward kernel's epilogue: x is
  there)."""
  batch, t, heads, p = x.shape
  groups, n = b.shape[2], b.shape[3]
  r = heads // groups
  z, l = plan.chunks, plan.chunk
  f32 = jnp.float32
  # Positions last, a chunk a row: the running sums inside a chunk are ONE
  # product with the lower triangle (float32 at ``highest``: the ones
  # are exact and the sums float32), which the chip takes as it stands.
  steps = dt.astype(f32).reshape(batch, z, l, groups, r).transpose(
      0, 3, 4, 1, 2)                                     # (B, G, R, Z, L)
  run = jnp.einsum("bgrzs,ts->bgrzt",
                   steps * a.astype(f32).reshape(groups, r, 1, 1),
                   jnp.tril(jnp.ones((l, l), f32)),
                   precision=lax.Precision.HIGHEST)
  total = run[..., -1:]
  to_end = jnp.exp(total - run) * steps
  # A chunk ahead of what a grid step's block holds: (B, G, Z, ., .).
  by_group = lambda parts: jnp.moveaxis(jnp.concatenate(parts, axis=2), 3, 2)
  rows = by_group([steps, run])                          # (., 2 R, L)
  cols = by_group([run, jnp.exp(run), to_end]).swapaxes(3, 4)
  decay = jnp.repeat(jnp.exp(total[..., 0]).transpose(0, 3, 1, 2), p,
                     axis=-1)[:, :, :, None]             # (B, Z, G, 1, R P)
  y = _chunks(x.reshape(batch, t, heads * p), b.reshape(batch, t, groups * n),
              c.reshape(batch, t, groups * n), cols, rows, decay,
              jnp.repeat((jnp.zeros((heads,)) if skip is None else skip
                          ).astype(f32), p).reshape(groups, 1, r * p),
              interpret)
  return y.reshape(batch, t, heads, p)


def group_norm(y, scale, groups: int, eps: float):
  """RMSNorm over each of ``groups`` equal groups of the channels of y
  (..., CH) float32, one learned ``scale`` over all of them. A group's
  mean square and its way back over the group's channels are products
  with the groups' 0/1 membership (float32 at ``highest``: exact), so
  the rows are reduced and scaled as they lie, channels on the lanes: a
  reduce over a (groups, CH / groups) view made XLA lay the scan's
  output out again, a copy of 134 MB a layer and pass (PERF.md section 6,
  PR 40)."""
  width = y.shape[-1] // groups
  member = (jnp.arange(y.shape[-1])[:, None] // width ==
            jnp.arange(groups)).astype(jnp.float32)      # (CH, groups)
  products = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
  inv = lax.rsqrt(products(jnp.square(y), member) / width + eps)
  return y * products(inv, member.T) * scale.astype(jnp.float32)


def gated_norm(y, z, scale, groups: int, eps: float):
  """``RMSNorm_groups(y * silu(z))``: the gate BEFORE the norm. y, z
  (B, T, CH); float32 out."""
  return group_norm(y.astype(jnp.float32) * jax.nn.silu(
      z.astype(jnp.float32)), scale, groups, eps)


def mamba_core(zxbcdt, conv_kernel, conv_bias, a_log, d, dt_bias, norm_scale,
               *, heads: int, head_dim: int, groups: int, state: int,
               chunk: int, eps: float, scan_dtype=jnp.float32):
  """Everything of a Mamba-2 mixer between its two projections:
  ``[z | xBC | dt] = zxbcdt`` (the output of ``in_proj``, (B, T, 2 H P +
  2 G N + H)); ``xBC <- silu(causal_conv(xBC))``; ``[x | B | C] = xBC``;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the scan with
  its skip ``+ D x``; the gated norm. Returns (B, T, H P) in
  ``zxbcdt``'s dtype, the input of ``out_proj``. Named scopes ``mamba_conv`` and
  ``ssd_scan``; the caller's ``mamba_mixer`` is around them."""
  inner, bc = heads * head_dim, groups * state
  dtype = zxbcdt.dtype
  z = zxbcdt[..., :inner]
  xbc = zxbcdt[..., inner:2 * inner + 2 * bc]
  dt = zxbcdt[..., 2 * inner + 2 * bc:]
  with jax.named_scope("mamba_conv"):
    xbc = jax.nn.silu(causal_conv(xbc, conv_kernel, conv_bias)).astype(dtype)
  lead = xbc.shape[:2]
  x = xbc[..., :inner].reshape(lead + (heads, head_dim))
  b = xbc[..., inner:inner + bc].reshape(lead + (groups, state))
  c = xbc[..., inner + bc:].reshape(lead + (groups, state))
  with jax.named_scope("ssd_scan"):
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    a = -jnp.exp(a_log.astype(jnp.float32))
    y = ssd_scan(x, dt, a, b, c, chunk, scan_dtype, d)
  return gated_norm(y.reshape(lead + (inner,)), z, norm_scale, groups,
                    eps).astype(dtype)


def scan_stats(batch: int, seq_len: int, heads: int, head_dim: int,
               groups: int, state: int, chunk: int, layers: int,
               dtype) -> dict:
  """A run's ``stats["mamba"]``: the scan's plan at the job's shapes and
  what it moves, from the shapes and the backend alone (it cannot vary
  by step). ``kernel_share``: the share of a step's scans that take the
  kernels (the layers share one shape: 1 or 0);
  ``carried_state_bytes_per_layer``: the float32 states entering the
  chunks of a step's sequences (the kernels write them in the backward
  pass's forward alone); ``residual_bytes_per_layer``: what a layer
  keeps for its backward pass, which is ``in_proj``'s output (the inside
  of the mixer is formed again from it)."""
  plan = scan_plan(seq_len, heads, groups, chunk, head_dim, state)
  width = 2 * heads * head_dim + 2 * groups * state + heads
  return {"layers": layers, "heads": heads, "head_dim": head_dim,
          "groups": groups, "state": state, "chunk": plan.chunk,
          "chunks_per_sequence": plan.chunks,
          "implementation": plan.implementation,
          "kernel_share": float(plan.implementation == "pallas"),
          "carried_state_bytes_per_layer":
              batch * plan.chunks * heads * head_dim * state * 4,
          "residual_bytes_per_layer":
              batch * seq_len * width * jnp.dtype(dtype).itemsize}
