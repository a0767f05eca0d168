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
(iii) is the one sequential part, T / chunk links (64 at 8,192 tokens),
written as ONE product with the chunks' lower-triangular decay matrix
(no loop in the program). ``dt``, ``A``, the running sums, every
exponential and the carried state are float32 (``scan_dtype`` is the
type of the steps, their running sums and the exponentials: bfloat16 is
the lower-precision control of the tests and the benchmark's check);
the products take operands of ``x``'s dtype into float32, (iii) float32
operands at ``highest``.

Which form runs follows from the shapes and the backend (``scan_plan``,
as ``rotary.rotary_plan`` and ``sequence.flash_plan`` decide theirs): no
flag, no model name. Today there is ONE form, ``"xla"``: ``jax.numpy``
einsums, differentiated by autodiff (``mamba_core`` is rematerialised
from its inputs, so nothing of a chunk's (chunk x chunk) decay tables
outlives the pass that forms it); a Pallas kernel for (i) + (ii) would
be a second ``implementation`` of the same plan.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class ScanPlan:
  """What ``ssd_scan`` runs for one shape: ``scan_plan`` decides it, a
  model states it in its run's ``stats["mamba"]``. ``implementation``:
  ``"xla"`` (einsums; the only form there is); ``chunk`` positions a
  chunk, ``chunks`` of them a sequence (the links of the carried
  state)."""
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


def scan_plan(seq_len: int, heads: int, groups: int, chunk: int) -> ScanPlan:
  """The plan for one shape, from the shapes (and, once there is a
  kernel, the backend)."""
  why = refusal(seq_len, chunk)
  if why:
    raise ValueError(why)
  if heads % groups:
    raise ValueError(f"{groups} groups do not divide {heads} heads")
  return ScanPlan("xla", chunk, seq_len // chunk)


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


def ssd_scan(x, dt, a, b, c, chunk: int, scan_dtype=jnp.float32):
  """The chunked scan (the module's docstring): y (B, T, H, P) float32
  for x (B, T, H, P), dt (B, T, H), a (H,), b and c (B, T, G, N). No
  ``D x`` term and no gate: the caller's."""
  batch, t, heads, p = x.shape
  groups, n = b.shape[2], b.shape[3]
  plan = scan_plan(t, heads, groups, chunk)
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


def group_norm(y, scale, groups: int, eps: float):
  """RMSNorm over each of ``groups`` equal groups of the channels of y
  (..., CH) float32, one learned ``scale`` over all of them."""
  shape = y.shape
  grouped = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
  var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
  return ((grouped * lax.rsqrt(var + eps)).reshape(shape) *
          scale.astype(jnp.float32))


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
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the scan;
  ``+ D x``; the gated norm. Returns (B, T, H P) in ``zxbcdt``'s dtype,
  the input of ``out_proj``. Named scopes ``mamba_conv`` and
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
    y = ssd_scan(x, dt, a, b, c, chunk, scan_dtype)
    y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
  return gated_norm(y.reshape(lead + (inner,)), z, norm_scale, groups,
                    eps).astype(dtype)


def scan_stats(batch: int, seq_len: int, heads: int, head_dim: int,
               groups: int, state: int, chunk: int, layers: int,
               dtype) -> dict:
  """A run's ``stats["mamba"]``: the scan's plan at the job's shapes and
  what it moves, from the shapes alone (it cannot vary by step).
  ``carried_state_bytes_per_layer``: the float32 states entering the
  chunks of a step's sequences; ``residual_bytes_per_layer``: what a
  layer keeps for its backward pass, which is ``in_proj``'s output (the
  inside of the mixer is formed again from it)."""
  plan = scan_plan(seq_len, heads, groups, chunk)
  width = 2 * heads * head_dim + 2 * groups * state + heads
  return {"layers": layers, "heads": heads, "head_dim": head_dim,
          "groups": groups, "state": state, "chunk": plan.chunk,
          "chunks_per_sequence": plan.chunks,
          "implementation": plan.implementation,
          "carried_state_bytes_per_layer":
              batch * plan.chunks * heads * head_dim * state * 4,
          "residual_bytes_per_layer":
              batch * seq_len * width * jnp.dtype(dtype).itemsize}
