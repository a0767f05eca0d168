"""Expert parallelism: switch-routed MoE over the ``expert`` mesh axis.

Beyond-reference capability (the reference has no conditional
computation). The TPU-native shape is the Switch/GShard pattern:
tokens are sharded over the 'expert' axis alongside data parallelism,
each device owns num_experts/n experts, and two ``lax.all_to_all``
calls carry the dispatch/combine permutation over ICI:

  gate (replicated matmul) -> top-1 expert + capacity mask
  -> dispatch einsum to (experts, capacity, d) slots
  -> all_to_all: token-sharded -> expert-sharded
  -> per-expert FFN (one batched einsum over the local expert slice)
  -> all_to_all back -> combine einsum * gate probability

Tokens over capacity are dropped (output 0 -- callers add the
residual), exactly the Switch Transformer semantic; the standard
load-balancing auxiliary loss is returned alongside. Equivalence vs a
hand-rolled per-token loop with identical capacity ordering is pinned
by tests/test_expert_parallel.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

EXPERT_AXIS = "expert"


def switch_moe(x, gate_w, w1, b1, w2, b2, capacity: int,
               axis_name: str = EXPERT_AXIS) -> Tuple[jnp.ndarray,
                                                      jnp.ndarray]:
  """Top-1 (Switch) MoE inside a shard_map body.

  x: (tokens_local, d) -- this device's token shard.
  gate_w: (d, num_experts_global) replicated router weights.
  w1/b1/w2/b2: this device's expert slice -- leading axis
  num_experts_local = num_experts_global / axis_size.
  capacity: per-expert slot count PER SOURCE DEVICE.

  Returns (out, aux_loss): out (tokens_local, d) with over-capacity
  tokens zeroed; aux_loss the Switch load-balance penalty (already
  pmean-ed over the axis).
  """
  n = lax.axis_size(axis_name)
  tokens, d = x.shape
  e_local = w1.shape[0]
  e_global = n * e_local
  f32 = jnp.float32

  logits = x.astype(f32) @ gate_w.astype(f32)        # (N, E)
  probs = jax.nn.softmax(logits, axis=-1)
  expert_idx = jnp.argmax(probs, axis=-1)            # (N,)
  gate = jnp.max(probs, axis=-1)                     # (N,)

  assign = jax.nn.one_hot(expert_idx, e_global, dtype=f32)   # (N, E)
  # Position of each token in its expert's queue, in token order --
  # the deterministic capacity-drop priority.
  pos = jnp.cumsum(assign, axis=0) - 1.0                     # (N, E)
  keep = assign * (pos < capacity)                           # (N, E)
  slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                        dtype=f32) * keep[..., None]         # (N, E, C)

  # Switch aux loss: E * sum_e( fraction_tokens_e * mean_prob_e ),
  # averaged over devices (token statistics are per-shard).
  frac_tokens = jnp.mean(assign, axis=0)
  frac_probs = jnp.mean(probs, axis=0)
  aux_loss = lax.pmean(
      e_global * jnp.sum(frac_tokens * frac_probs), axis_name)

  dispatch = jnp.einsum("nec,nd->ecd", slot, x.astype(f32))  # (E, C, d)
  # (E, C, d) -> (n, e_local, C, d); all_to_all swaps the leading
  # device-chunk axis so each device ends with ITS experts' slots from
  # every source device.
  dispatch = dispatch.reshape(n, e_local, capacity, d)
  dispatch = lax.all_to_all(dispatch, axis_name, split_axis=0,
                            concat_axis=0)          # (n_src, e_l, C, d)

  h = jnp.einsum("secd,edf->secf", dispatch, w1.astype(f32))
  h = jax.nn.gelu(h + b1.astype(f32)[None, :, None, :])
  y = jnp.einsum("secf,efd->secd", h, w2.astype(f32))
  y = y + b2.astype(f32)[None, :, None, :]

  y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
  y = y.reshape(e_global, capacity, d)
  out = jnp.einsum("nec,ecd->nd", slot, y) * gate[:, None]
  return out.astype(x.dtype), aux_loss


def make_switch_moe(mesh: Mesh, capacity: int,
                    axis_name: str = EXPERT_AXIS):
  """Jitted Switch MoE over GLOBAL arrays: tokens (N, d) sharded over
  ``axis_name``, expert stacks (E, ...) likewise, router replicated."""

  def body(x, gate_w, w1, b1, w2, b2):
    return switch_moe(x, gate_w, w1, b1, w2, b2, capacity,
                      axis_name=axis_name)

  sharded = jax.shard_map(
      body, mesh=mesh,
      in_specs=(P(axis_name), P(), P(axis_name), P(axis_name),
                P(axis_name), P(axis_name)),
      out_specs=(P(axis_name), P()))
  return jax.jit(sharded)


def reference_switch_moe(x_grouped, gate_w, w1, b1, w2, b2,
                         capacity: int):
  """Hand-rolled single-device reference with the same semantics.

  x_grouped: (groups, tokens_per_group, d) -- one group per device
  shard, capacity applies within each group (matching the per-shard
  queues of the SPMD version). Pure Python loops; test-only.
  """
  import numpy as np
  groups, tokens, d = x_grouped.shape
  e_global = gate_w.shape[1]
  out = np.zeros((groups, tokens, d), np.float32)
  aux = 0.0
  for g in range(groups):
    xg = np.asarray(x_grouped[g], np.float32)
    logits = xg @ np.asarray(gate_w, np.float32)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    counts = np.zeros(e_global, np.int64)
    for t in range(tokens):
      e = int(idx[t])
      if counts[e] >= capacity:
        counts[e] += 1
        continue
      counts[e] += 1
      h = xg[t] @ np.asarray(w1[e], np.float32) + np.asarray(
          b1[e], np.float32)
      h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
      y = h @ np.asarray(w2[e], np.float32) + np.asarray(
          b2[e], np.float32)
      out[g, t] = y * probs[t, e]
    frac_tokens = np.bincount(idx, minlength=e_global) / tokens
    aux += e_global * float((frac_tokens * probs.mean(0)).sum())
  return out, aux / groups


# -- dropless top-k routing over a held share of the experts ------------------
#
# The layer a chip of an expert-parallel deployment runs (model-configs
# guide, section 4): it is TOLD which contiguous block of the experts it
# holds, routes every token over ALL of them, and computes its own
# experts' part of the result. What the absent experts would add is left
# out here, and on one chip there is no exchange: no code stands in for
# the absent chips. Routing is dropless: every (token, expert) pair whose
# expert is held is computed, whatever the imbalance.
#
# The work follows the rows in use. All N x k pairs are sorted by held
# expert (int32 keys; absent experts' pairs last), so the pairs held here
# are a prefix of the sorted order, each held expert's rows starting on a
# boundary of the grouped products' row tile (``row_tile``; padding rows
# between the groups hold no pair), and the path works on that prefix in
# ROUNDS of ``rows`` sorted rows: ``compact_rows`` sizes a round at twice
# the share of the pairs these experts get under perfect balance, so one
# round is the common step, and a step whose pairs do not fit runs
# further rounds, decided on the chip (ONE ``lax.while_loop`` on a device
# scalar: no host sync, no recompilation, no pair dropped). No array of
# N x k rows times a model width exists, forward or backward: a round
# gathers ``rows`` rows of the tokens (``_rows_of_tokens``), runs the
# expert's grouped products on them (three for a gated expert, two for a
# plain one: ``expert_hidden``), and combines on the TOKEN side
# (``_rows_to_tokens``): each token gathers its k pairs' rows from the
# products' own output, in the type the products stored, and sums them
# in float32 under the pairs' weights, which are in token order already.
# A pair outside the round reads a row in range and is selected away: no
# zero row is appended, and no float32 copy of the round is made. Where
# ``rows`` is all N x k pairs (half of the experts or more held) there is
# one round and no loop in the program.
#
# The path's forward runs ONCE a step in both of ``mla_moe_lm``'s
# stacks. Under ``nn.remat`` it would run again in the backward pass
# wherever something reads its output there, because XLA merges no loop
# with its copy: a block that normalises the feed-forward's output reads
# it (8.8 + 5.6 ms a step of the trinity-mini cell until PR 35, whose
# unrolled layers are no longer rematerialised: PERF.md section 6); a
# pre-norm block, whose output goes to the residual sum alone, does not,
# and XLA drops the repeat (the glm cell's scanned stack).


def compact_rows(pairs: int, held: int, experts: int, tile: int = 512) -> int:
  """Rows of one round of the routed path's sorted buffer: twice the
  share of the ``pairs`` (token, expert) pairs of a step that ``held``
  of ``experts`` experts get under perfect balance, in whole row tiles
  of the grouped products, and never more than all the pairs."""
  share = -(-2 * pairs * held // experts)
  return min(pairs, -(-share // tile) * tile)


# Row tiles the layout chooses from, and the v5e's ridge in rows: a visit
# of a row tile fetches its expert's weight tile, ``tm`` flop a byte of it
# in bfloat16, against the chip's 197 TFLOP/s over 819 GB/s = 240.
ROW_TILES = (128, 256, 512)
RIDGE_ROWS = 240


def row_tile(pairs: int, held: int, experts: int) -> int:
  """Row tile of the routed path's grouped products, from the shapes: the
  smallest of ``ROW_TILES`` at or above the ridge and at most the rows a
  held expert expects under balance (``pairs / experts``), or the largest
  such tile that those rows fill four times over (its padding, half a
  tile an expert, an eighth of the rows). Every held expert's rows start
  on a boundary of it (``sort_pairs``), so that megablox visits each row
  tile for one expert. Where no tile fits (the CPU tests' tiny sizes) the
  round's rows: one tile, and the groups packed end to end."""
  expected = pairs // experts
  fits = [s for s in ROW_TILES if RIDGE_ROWS <= s <= expected]
  if not fits:
    return compact_rows(pairs, held, experts)
  return max([fits[0]] + [s for s in fits if 4 * s <= expected])


def combine_stats(tokens: int, k: int, rows: int, width: int, dtype):
  """What the combine of ONE round reads (``_rows_to_tokens``), from the
  shapes alone: ``gathers`` row gathers of ``tokens`` rows each
  (``rows_gathered`` together) from a table of the round's ``rows`` rows
  of ``width`` in the type the grouped products store, ``table_bytes``
  in all. It holds for every step: nothing here is chosen at run time."""
  dtype = jnp.dtype(dtype)
  return {"gathers": k, "rows_gathered": tokens * k,
          "table_dtype": dtype.name,
          "table_bytes": rows * width * dtype.itemsize}


def gmm_tiling(rows: int, contraction: int, columns: int):
  """(rows, contraction, columns) of one tile of the grouped product, for
  ``rows`` the layout's row tile (``row_tile``; the round's rows where the
  groups are packed): the largest listed size that divides each dimension
  (a tile edge that does not is masked work), 512 rows, and at most
  1024 x 768 of the
  weight so that a tile and its double buffer fit the v5e's 16 MiB of
  scoped VMEM (1024 x 1536 was refused by the chip's compiler, PR 27).
  A width NO listed size divides (1,856 = 29 x 64, a two-matrix expert's)
  takes the size whose last tile is masked least, the larger of equals
  (384: five tiles, 64 columns masked; the kernels mask a short last
  tile of the contraction and clip one of the columns); a dimension
  under the smallest size (the CPU tests' tiny sizes), and rows that no
  size divides, are one tile."""
  def tile(dim, sizes, masked=True):
    divides = next((s for s in sizes if dim % s == 0), None)
    fits = [s for s in sizes if s <= dim]
    if divides or not (masked and fits):
      return divides or dim
    return min(fits, key=lambda s: (-dim % s, -s))
  tk = tile(contraction, (1024, 896, 768, 512, 384, 256, 128))
  tn = tile(columns, (1024, 896, 768, 512, 384, 256, 128) if tk <= 768 else
            (768, 512, 384, 256, 128))
  return tile(rows, (512, 256, 128, 64, 32, 16, 8), masked=False), tk, tn


def route_topk(x, router_w, select_bias, k: int, scale: float,
               renormalise: bool = True, router_dtype=jnp.float32):
  """Sigmoid top-k routing with a selection bias ("noaux_tc", one
  group): ``scores = sigmoid(x @ router_w)``; the k experts are chosen
  by ``scores + select_bias``, which enters the SELECTION only; the
  weights are the chosen experts' own scores, renormalised to sum to 1
  and multiplied by ``scale``. All of it in ``router_dtype`` (float32,
  the matmul at HIGHEST precision: on a TPU a float32 matmul otherwise
  runs in bfloat16 passes). Returns (weights (N, k), idx (N, k) int32,
  scores (N, E))."""
  logits = jnp.dot(x.astype(router_dtype), router_w.astype(router_dtype),
                   precision=lax.Precision.HIGHEST)
  scores = jax.nn.sigmoid(logits)
  _, idx = lax.top_k(scores + lax.stop_gradient(
      select_bias.astype(router_dtype)), k)
  weights = jnp.take_along_axis(scores, idx, axis=-1)
  if renormalise:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  return weights * scale, idx.astype(jnp.int32), scores


class SortedPairs(NamedTuple):
  """A step's N x k (token, expert) pairs sorted by held expert (stable,
  so by token within an expert; absent experts' pairs last), each held
  expert's rows starting on a multiple of the layout's tile: the rows
  between one expert's last pair and the next tile boundary are PADDING,
  which holds no pair. ``order[r]`` is the pair (token x k + choice) in
  sorted row r, N x k or more in a padding row, and ``key[r]`` the held
  expert its pair chose (the number of held experts G for an absent
  one's and for padding); ``inv`` the sorted row of each of
  ``order``'s entries, the pairs' first; ``ends`` (G,) the row where each
  held expert's extent ends, its padding included, so the held experts'
  groups take rows ``[0, ends[-1])``."""
  key: jnp.ndarray
  order: jnp.ndarray
  inv: jnp.ndarray
  ends: jnp.ndarray


def _round_slice(v, start, rows: int, fill=0):
  """``v[start : start + rows]`` of a vector over the sorted pairs, filled
  past its end (the last round may be short)."""
  return lax.dynamic_slice_in_dim(
      jnp.pad(v, (0, -v.shape[0] % rows), constant_values=fill), start, rows)


def _sum_rows_by_token(rows, slot, weight=None):
  """(N, D) float32: each token's sum of the rows of a round that are
  its pairs', each times its pair's ``weight`` (N, k) where one is given.
  ``slot`` (N, k) is the row of each of a token's pairs in the round, or
  the row count for a pair outside it. k gathers of N rows from ``rows``
  as they are stored: a pair outside the round reads the last row and is
  selected away, so the table is neither copied to append a zero row nor
  widened to float32 (on the chip a gather of 8,192 rows of 2,048 took
  0.356 ms from a float32 table and 0.053 ms from a bfloat16 one:
  PERF.md section 6, PR 33; a segment sum over the rows' tokens, a
  scatter-add, took 0.8 ms a layer more than the gathers: PR 28)."""
  inside = slot < rows.shape[0]

  def term(j):
    row = jnp.take(rows, slot[:, j], axis=0, mode="clip").astype(jnp.float32)
    if weight is not None:
      row = row * weight[:, j, None]
    return jnp.where(inside[:, j, None], row, 0)
  return sum(term(j) for j in range(slot.shape[1]))


@jax.custom_vjp
def _rows_of_tokens(x, tok, slot):
  """Rows of ``x`` (N, D) for a round: row r is token ``tok[r]``'s. The
  backward sums a row's gradient into its token (autodiff would
  scatter-add)."""
  del slot
  return jnp.take(x, tok, axis=0, mode="clip")


def _rows_of_tokens_fwd(x, tok, slot):
  return _rows_of_tokens(x, tok, slot), slot


def _rows_of_tokens_bwd(slot, g):
  return _sum_rows_by_token(g, slot).astype(g.dtype), None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _sorted_values(v, order, inv):
  """``v[order]`` for a permutation ``order`` of the sorted rows and its
  inverse, as a sort of ``v`` by ``inv``, and backward a sort by
  ``order``: on the TPU a gather of 32,768 scalars takes 0.23 ms and a
  sort of them 0.025 ms (PERF.md section 6, PR 28)."""
  del order
  return lax.sort((inv, v), num_keys=1)[1]


def _sorted_values_fwd(v, order, inv):
  return _sorted_values(v, order, inv), order


def _sorted_values_bwd(order, g):
  return lax.sort((order, g), num_keys=1)[1], None, None


_sorted_values.defvjp(_sorted_values_fwd, _sorted_values_bwd)


@jax.custom_vjp
def _rows_to_tokens(ys, pair_w, tok, slot, start, plan):
  """The combine of a round, (N, D) float32: ``y[t] = sum_j pair_w[t, j]
  x ys[slot[t, j]]`` over token t's pairs inside the round. ``ys``
  (rows, D) is the products' output as they stored it, ``pair_w`` (N, k)
  float32 in token order. The weighted transpose of ``_rows_of_tokens``,
  whose backward is its gather: that side works on the SORTED rows (row
  r's gradient is its token's times its pair's weight, its weight's the
  inner product of the two rows), so it sorts the weights into the
  round, which the forward has no use for."""
  del tok, start, plan
  return _sum_rows_by_token(ys, slot, pair_w)


def _rows_to_tokens_fwd(ys, pair_w, tok, slot, start, plan):
  return (_rows_to_tokens(ys, pair_w, tok, slot, start, plan),
          (ys, pair_w, tok, start, plan))


def _rows_to_tokens_bwd(res, g):
  ys, pair_w, tok, start, plan = res
  # Padding rows take weight 0: the pairs' weights are padded to the
  # sorted rows before the sort.
  pad = plan.order.shape[0] - pair_w.size
  w, unsort = jax.vjp(lambda p: _round_slice(_sorted_values(
      jnp.pad(p.reshape(-1), (0, pad)), plan.order, plan.inv), start,
      ys.shape[0]), pair_w)
  g_rows = jnp.take(g, tok, axis=0, mode="clip")
  d_w = jnp.sum(g_rows * ys.astype(jnp.float32), axis=1)
  return ((g_rows * w[:, None]).astype(ys.dtype), *unsort(d_w),
          None, None, None, None)


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(lhs, rhs, group_sizes, live, impl="gmm", tile=None):
  """``out[r] = lhs[r] @ rhs[group of r]`` for the rows of a round in
  use (``live``), zero elsewhere; ``group_sizes`` (G,) int32 rows per
  held expert, in order; ``tile`` the layout's row tile (``row_tile``),
  all the rows by default, from which ``gmm_tiling`` takes the kernel's.
  ``impl``: ``gmm`` is the TPU kernel (Pallas
  megablox: its grid covers the tiles in use alone, and the rows it does
  not visit are left unwritten, hence the masks, which like everything
  XLA runs around the kernel pass over the whole round: the reason a
  round is sized by ``compact_rows`` and not for the worst case),
  ``gmm_interpret`` the same kernel interpreted (CPU tests),
  ``ragged_dot`` XLA's own grouped product (CPU; on a TPU it expands to
  one dense product per group)."""
  if impl == "ragged_dot":
    out = lax.ragged_dot(lhs, rhs, group_sizes,
                         preferred_element_type=jnp.float32)
  else:
    # The kernel accumulates in float32 and stores ``lhs.dtype``: the
    # value a float32 output cast afterwards has, without that pass.
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    out = gmm(lhs, rhs, group_sizes, lhs.dtype,
              gmm_tiling(tile or lhs.shape[0], rhs.shape[1], rhs.shape[2]),
              interpret=impl == "gmm_interpret")
  return jnp.where(live[:, None], out, 0).astype(lhs.dtype)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, live, impl, tile):
  return (grouped_matmul(lhs, rhs, group_sizes, live, impl, tile),
          (lhs, rhs, group_sizes, live))


def _grouped_matmul_bwd(impl, tile, res, g):
  lhs, rhs, group_sizes, live = res
  g = jnp.where(live[:, None], g, 0)
  if impl == "ragged_dot":
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(
        a, b, group_sizes, preferred_element_type=jnp.float32), lhs, rhs)
    dlhs, drhs = vjp(g.astype(jnp.float32))
  else:
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    interpret = impl == "gmm_interpret"
    rows, (_, d_in, d_out) = tile or lhs.shape[0], rhs.shape
    dlhs = gmm(g, rhs, group_sizes, lhs.dtype,
               gmm_tiling(rows, d_out, d_in), transpose_rhs=True,
               interpret=interpret)
    drhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                gmm_tiling(rows, d_in, d_out),
                num_actual_groups=rhs.shape[0], interpret=interpret)
  dlhs = jnp.where(live[:, None], dlhs, 0).astype(lhs.dtype)
  return dlhs, drhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def relu2(x):
  """``relu(x)^2``, the activation of a two-matrix expert."""
  return jnp.square(jax.nn.relu(x))


# What an expert applies between its products, by the name a
# configuration gives it (``hidden_act`` / ``mlp_hidden_act``).
ACTIVATIONS = {"silu": jax.nn.silu, "relu2": relu2}


def expert_hidden(xs, w_gate, w_up, product, activation: str):
  """An expert's hidden rows, in the FORM its weights state: gated
  (``w_gate`` given, three matrices: ``act(xs w_gate) * (xs w_up)``) or
  plain (``w_gate`` None, two: ``act(xs w_up)``). ``product(rows, w)`` is
  the grouped product."""
  act = ACTIVATIONS[activation]
  if w_gate is None:
    return act(product(xs, w_up))
  return act(product(xs, w_gate)) * product(xs, w_up)


@functools.partial(jax.jit,
                   static_argnames=("rows", "impl", "activation", "tile"))
def experts_round(i, x, pair_w, w_gate, w_up, w_down, plan: SortedPairs,
                  rows: int, impl: str, activation: str = "silu",
                  tile: Optional[int] = None):
  """Round ``i`` of the routed path: sorted rows ``[i x rows, (i + 1) x
  rows)``, handed to the products in row tiles of ``tile`` (all the rows
  by default). Returns ``(y, computed, tiled)``: y (N, D) float32, the
  weighted outputs of the held experts (``expert_hidden``'s form, then
  ``w_down``) for the pairs in these rows,
  summed into their tokens; ``computed`` the pairs the products computed
  for their expert (``pairs_inside_groups``); ``tiled`` the rows the
  kernel's row tiles cover (``rows_in_tiles``). A held pair outside the
  round is another round's: left out here, and not counted. (Jitted, as
  ``_round_pullback`` is, so that every mixture block of a model shares
  ONE trace and one lowering of it; XLA inlines the calls.)"""
  k = pair_w.shape[1]
  start = i * rows
  g = plan.ends.shape[0]
  cut = functools.partial(_round_slice, start=start, rows=rows)
  tok = cut(plan.order) // k
  ends = jnp.clip(plan.ends - start, 0, rows)
  sizes = jnp.diff(ends, prepend=0)
  key = cut(plan.key, fill=g)
  live = key < g                       # the rows that hold a held pair
  slot = plan.inv[:pair_w.size] - start
  slot = jnp.where((slot >= 0) & (slot < rows), slot, rows).reshape(-1, k)
  xs = jnp.where(live[:, None], _rows_of_tokens(x, tok, slot), 0)
  with jax.named_scope("moe_experts"):
    product = lambda rows_, w: grouped_matmul(rows_, w, sizes, live, impl,
                                              tile)
    ys = product(expert_hidden(xs, w_gate, w_up, product, activation),
                 w_down)
  y = _rows_to_tokens(ys, pair_w, tok, slot, start, plan)
  tm = gmm_tiling(tile or rows, *w_up.shape[1:])[0]
  return (y, pairs_inside_groups(key, sizes, live),
          rows_in_tiles(ends, tm))


def rows_in_tiles(ends, tm: int):
  """Rows the grouped product's kernel computes for groups ending at
  ``ends`` (G,) within a round, in row tiles of ``tm``: megablox visits
  every tile a group touches once for that group (a tile two groups share
  twice), so a group of ``[s, e)`` costs ``(e - 1) // tm - s // tm + 1``
  tiles, ``ceil((e - s) / tm)`` where it starts on a tile boundary."""
  starts = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
  tiles = jnp.where(ends > starts, (ends - 1) // tm - starts // tm + 1, 0)
  return jnp.sum(tiles) * tm


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _all_rounds(x, pair_w, w_gate, w_up, w_down, plan, rows, impl,
                activation, tile):
  """``experts_round`` summed over the rounds that hold a pair of a held
  expert. Differentiated as a whole: the backward runs each round's
  forward again inside its own loop, so nothing of a round outlives it
  and the backward needs nothing of this forward but its inputs. (A
  caller under ``nn.remat`` pays for this forward twice only where the
  backward pass reads y itself: the comment above ``compact_rows``.)"""
  args = (x, pair_w, w_gate, w_up, w_down, plan, rows, impl, activation,
          tile)
  return _while_pairs_left(lambda i: experts_round(i, *args), plan, rows)


def _while_pairs_left(one_round, plan, rows):
  """``one_round(0) + one_round(1) + ...`` (a tuple of arrays, or of
  None where a two-matrix expert has no gate) over the
  rounds that start before the held pairs end. The loop starts from
  zeros, so that the executable holds a round ONCE (a first round
  outside the loop saved the zero-fill and the sums, 1.1 ms a layer, and
  cost 2 s of every process's set-up: PERF.md section 6, PR 28)."""
  if plan.key.shape[0] <= rows:
    return one_round(jnp.int32(0))
  zeros = jax.tree.map(lambda o: jnp.zeros(o.shape, o.dtype),
                       jax.eval_shape(one_round, jnp.int32(0)))
  more = lambda carry: carry[0] * rows < plan.ends[-1]
  add = lambda carry: (carry[0] + 1, jax.tree.map(
      jnp.add, carry[1], one_round(carry[0])))
  return lax.while_loop(more, add, (jnp.int32(0), zeros))[1]


def _all_rounds_fwd(x, pair_w, w_gate, w_up, w_down, plan, rows, impl,
                    activation, tile):
  return (_all_rounds(x, pair_w, w_gate, w_up, w_down, plan, rows, impl,
                      activation, tile),
          (x, pair_w, w_gate, w_up, w_down, plan))


@functools.partial(jax.jit,
                   static_argnames=("rows", "impl", "activation", "tile"))
def _round_pullback(i, g, inputs, plan, rows, impl, activation="silu",
                    tile=None):
  """The gradients of round ``i``'s y with respect to ``inputs`` (x,
  pair_w and the weights; None for the gate a two-matrix expert lacks)
  at the cotangent ``g``."""
  _, vjp = jax.vjp(lambda *a: experts_round(i, *a, plan, rows, impl,
                                            activation, tile)[0], *inputs)
  return vjp(g)


def _all_rounds_bwd(rows, impl, activation, tile, res, g):
  *inputs, plan = res
  pull = lambda i: _round_pullback(i, g[0], tuple(inputs), plan, rows, impl,
                                   activation, tile)
  return tuple(_while_pairs_left(pull, plan, rows)) + (None,)


_all_rounds.defvjp(_all_rounds_fwd, _all_rounds_bwd)


def held_experts_ffn(x, weights, idx, w_gate, w_up, w_down,
                     first_expert: int, impl: str = "ragged_dot",
                     rows: Optional[int] = None, activation: str = "silu",
                     tile: Optional[int] = None):
  """The held experts' part of a dropless top-k layer.

  x (N, D) tokens; weights, idx (N, k) from ``route_topk`` over ALL the
  experts; w_gate, w_up (G, D, F) and w_down (G, F, D) the G experts
  held, which are experts ``first_expert .. first_expert + G - 1``;
  the expert's FORM is what the caller hands over: ``w_gate`` None is a
  two-matrix expert, ``w_down(act(w_up x))``, else the gated three-matrix
  one, ``w_down(act(w_gate x) * w_up x)``, ``activation`` naming ``act``
  (``ACTIVATIONS``);
  ``rows`` the sorted rows of one round (``compact_rows``; static), all
  N x k by default; ``tile`` the grouped products' row tile
  (``row_tile``; static), the round's rows by default. Where the tile is
  shorter than the round, every held expert's rows start on a multiple of
  it (``sort_pairs``), and a round is whole tiles; where it is the
  round, the groups are packed end to end. Returns ``(y, counts)``: y
  (N, D) the weighted sum of the held experts' outputs over each token's
  pairs that
  chose one (zero for a token that chose none), and ``counts`` a dict of
  int32 scalars: ``pairs_here``, the pairs whose expert is held (counted
  from the choices), ``pairs_computed``, counted from the other side:
  the rows of the rounds that ran that the grouped products were told
  are an expert's and that hold a pair of that expert
  (``pairs_inside_groups``), ``rows_in_tiles``, the rows the products'
  row tiles covered over the rounds that ran (``rows_in_tiles``), and
  ``compact``, 1 where the step's held experts' extents fit one round.
  ``pairs_here - pairs_computed`` is the drop count: 0
  while the rounds reach every held pair and the sort agrees with the
  group sizes.

  Scopes: the caller wraps this in ``moe_route``; the grouped products
  (three a gated expert, two a plain one) sit under ``moe_experts``
  inside it, in every round.
  """
  n, k = idx.shape
  rows = n * k if rows is None else rows
  tile = rows if tile is None else tile
  align = tile if tile < rows else 1
  if rows % align:
    raise ValueError(f"a round of {rows} rows is no whole number of row "
                     f"tiles of {tile}")
  plan, held = sort_pairs(idx, first_expert, w_up.shape[0], align)
  if rows >= n * k:     # one round of all the pairs holds their padding too
    rows = max(rows, -(-plan.key.shape[0] // align) * align)
  pair_w = jnp.where(held, weights, 0).astype(jnp.float32)
  y, computed, tiled = _all_rounds(
      x, pair_w, None if w_gate is None else w_gate.astype(x.dtype),
      w_up.astype(x.dtype), w_down.astype(x.dtype), plan, rows, impl,
      activation, tile)
  counts = {"pairs_here": jnp.sum(held.astype(jnp.int32)),
            "pairs_computed": computed, "rows_in_tiles": tiled,
            "compact": (plan.ends[-1] <= rows).astype(jnp.int32)}
  return y.astype(x.dtype), counts


def sort_pairs(idx, first_expert: int, g: int, tile: int = 1):
  """``idx`` (N, k), every token's chosen experts, as the ``SortedPairs``
  of the ``g`` experts held from ``first_expert`` on, each held expert's
  rows starting on a multiple of ``tile``; and ``held`` (N, k), whether a
  pair's expert is held. The loads are counted from the choices, not
  from the sort. The padding is ONE sort with the pairs: ``tile - 1``
  padding entries an expert join them, the first ``-load % tile`` under
  that expert's key, so that they sort right after its pairs (the sort
  is stable, and they come after every pair), the rest under a key past
  every other. ``tile`` 1 packs the groups end to end."""
  n, k = idx.shape
  local = idx - first_expert
  held = (local >= 0) & (local < g)
  # Absent experts' pairs sort to the end, under a key of their own.
  key = jnp.where(held, local, g).reshape(-1)
  experts = jnp.arange(g, dtype=key.dtype)
  held_load = jnp.sum((key[:, None] == experts[None, :]).astype(jnp.int32),
                      axis=0)
  pad = -held_load % tile
  spare = jnp.arange(tile - 1, dtype=key.dtype)[None, :]
  key = jnp.concatenate([key, jnp.where(
      spare < pad[:, None], experts[:, None], g + 1).reshape(-1)])
  sorted_key, order = lax.sort(
      (key, jnp.arange(key.shape[0], dtype=jnp.int32)), num_keys=1,
      is_stable=True)
  return SortedPairs(jnp.where(order < n * k, sorted_key, g), order,
                     jnp.argsort(order).astype(jnp.int32),
                     jnp.cumsum(held_load + pad)), held


def pairs_inside_groups(sorted_key, group_sizes, live):
  """How many (token, expert) pairs the grouped products computed FOR
  THEIR EXPERT: the products take rows ``[end_{e-1}, end_e)`` of the
  sorted buffer for held expert e, by the group sizes they are given,
  and nothing past the rows in use (``live``); the pair the sort put in
  row r chose held expert ``sorted_key[r]``. A row where the two
  disagree -- a buffer cut short, group sizes that disagree with the
  sort -- is a pair dropped or given another expert's weights, and is
  not counted. (Compares over rows x experts: no gather.)"""
  g = group_sizes.shape[0]
  ends = jnp.cumsum(group_sizes)
  rows = jnp.arange(sorted_key.shape[0], dtype=ends.dtype)
  row_expert = jnp.sum((rows[:, None] >= ends[None, :]).astype(jnp.int32),
                       axis=1)            # g for a row past the groups
  return jnp.sum(((sorted_key == row_expert) & (row_expert < g) &
                  live).astype(jnp.int32))
