"""Operations and bytes of a latent-attention mixture-of-experts decoder
(``model_type: glm4_moe_lite``), from a configuration's numbers.

Two kinds of count, kept apart:

* ``forward_flops_per_token``: what the MODEL requires for one forward
  pass, per token: the number ``mfu`` multiplies by 3 (recomputation not
  counted). A cell's file states it as a number
  (``forward_flops_per_sample``), with this function's derivation in
  words beside it, and ``tests/benchmarks/test_bench_lm.py`` holds the
  two together.
* ``*_executed``: what the PROGRAM runs in one training step under a
  scope, recomputation included, at the pairs the program's counter
  reports and by the kernel launches the traced run shows: the
  numerator of a kernel's share of its roofline, which therefore cannot
  read above 100% for work that was not done. How often a kernel runs
  (remat, a ``custom_vjp``'s own recomputation, XLA merging a repeat
  with the first) is decided when the step is compiled, so the count of
  passes is read from the trace and never assumed.

Only matrix products are counted (2 operations a multiply-accumulate):
norms, RoPE, SiLU, softmax, the router's top-k and the loss are bound by
memory, not by the MXU the peak describes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple


def attention_projection_params(c: Dict[str, Any]) -> int:
  h = c["num_attention_heads"]
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  return (c["hidden_size"] * c["q_lora_rank"] + c["q_lora_rank"] * h * qk +
          c["hidden_size"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) +
          c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) +
          h * c["v_head_dim"] * c["hidden_size"])


def attention_core_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """Causal scores and the weighted sum of values, one layer, per token:
  2 x heads x (qk + v) x seq_len / 2 (a token attends half the sequence
  on average)."""
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  return 2.0 * c["num_attention_heads"] * (qk + c["v_head_dim"]) * seq_len / 2


def forward_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """One forward pass per token of the model AS HELD (``c`` holds the
  counts held here: ``num_hidden_layers``, ``n_routed_experts``,
  ``vocab_size``; and ``published``/``deployment`` say of what):

  * every layer, and the MTP block: the attention projections at 2 x
    their parameters, and the causal core;
  * a dense layer: 2 x 3 x hidden x intermediate_size;
  * a mixture layer (and the MTP block): the router, the shared experts,
    and the routed experts at the EXPECTED number of held experts a
    token chooses, experts_per_tok x held / published (0.5 of 4 here);
  * the MTP module's projection 2 x (2 hidden x hidden);
  * one head per loss at 2 x hidden x rows of the vocabulary held.
  """
  d = c["hidden_size"]
  dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
  mtp = c["num_nextn_predict_layers"]
  mixture = c["num_hidden_layers"] - dense + mtp
  attention = (2.0 * attention_projection_params(c) +
               attention_core_flops_per_token(c, seq_len))
  expert = 3 * d * c["moe_intermediate_size"]
  held_per_token = (c["num_experts_per_tok"] * c["n_routed_experts"] /
                    c["published"]["n_routed_experts"])
  per_mixture = 2.0 * (d * c["published"]["n_routed_experts"] +
                       c["n_shared_experts"] * expert +
                       held_per_token * expert)
  return ((c["num_hidden_layers"] + mtp) * attention +
          dense * 2.0 * 3 * d * c["intermediate_size"] +
          mixture * per_mixture + mtp * 2.0 * 2 * d * d +
          (1 + mtp) * 2.0 * d * c["vocab_size"])


def _first(c: Dict[str, Any], *keys: str):
  """The value of the first of ``keys`` that ``c`` has: the two decoder
  families name the same counts differently."""
  return next(c[key] for key in keys if key in c)


def moe_experts_passes(layers: int, gmm: float, tgmm: float,
                       one_round: bool) -> Optional[float]:
  """Passes over a layer's pairs that ``gmm`` and ``tgmm`` launches a
  step make, or None where the launches are not the pattern this count
  stands on, so that a program that fuses or renames a kernel leaves the
  share silent and not wrong.

  The pattern: a pass over one round of one layer is three launches (a
  pair passes three products); a forward pass is three ``gmm``, the
  backward three ``gmm`` (the rows' gradients) and three ``tgmm`` (the
  weights'), two products' worth a product. So ``tgmm`` is 3 x layers x
  rounds, ``gmm`` a whole multiple of it (every pass runs the same
  rounds), at least twice (one forward and the backward), and the step
  makes (gmm + tgmm) / tgmm passes however many rounds a layer took.
  Held here: the multiple is whole and at least 2; ``tgmm`` is at least
  3 x ``layers``, and exactly that where the program's counter says
  every layer took ONE round in every step (``one_round``: a second
  round in some steps makes the mean a step no multiple of anything)."""
  if not tgmm or layers <= 0:
    return None
  forward_and_rows = gmm / tgmm
  each_layer = tgmm / (3.0 * layers)
  whole = abs(forward_and_rows - round(forward_and_rows)) < 1e-6
  if not whole or round(forward_and_rows) < 2 or each_layer < 1 - 1e-6:
    return None
  if one_round and abs(each_layer - 1) > 1e-6:
    return None
  return round(forward_and_rows) + 1.0


def moe_experts_executed(c: Dict[str, Any], pairs: float, gmm: float,
                         tgmm: float, one_round: bool = False
                         ) -> Optional[Tuple[float, float]]:
  """(operations, bytes) the grouped products run in ONE training step
  under the scope ``moe_experts``, over all mixture layers together,
  where ``pairs`` (token, expert) pairs a step were routed to held
  experts (the program's counter ``pairs_routed_here``) and the trace
  shows ``gmm`` and ``tgmm`` kernel launches a step under the scope
  (``lm_scopes.kernel_launches``). One function for both decoder
  families, told apart by the keys ``c`` has (``n_routed_experts``,
  ``first_k_dense_replace``, ``num_nextn_predict_layers`` of
  ``glm4_moe_lite``; ``num_experts``, ``num_dense_layers`` of ``afmoe``).

  None where the launches do not fit the pattern the count of passes
  stands on (``moe_experts_passes``; ``one_round``: the program's
  counter says every layer took one round in every step): the kernels'
  names are the library's (``jax.experimental.pallas.ops.tpu.megablox``),
  and a program that renames or fuses one silences the share.

  A pair passes three products of hidden x moe_intermediate_size, and a
  step runs ``moe_experts_passes`` of 3 x 2 x pairs x hidden x width,
  however many rounds a layer took. Seen (PR 34's traces of PR 33's
  program):
  glm-4.7-flash 45 ``gmm`` + 15 ``tgmm`` a step over 5 mixture layers =
  4 passes: the first forward, the forward that the routed path's own
  ``custom_vjp`` runs again inside its backward (``_round_pullback``
  takes ``jax.vjp`` of the round), and the backward's two; ``nn.remat``
  repeats the router and the sorts there but NOT the rounds. trinity-mini
  48 + 12 a step over 4 mixture layers = 5 passes: the same four and the
  forward that remat repeats, which XLA keeps because the post-norm
  reads the layer's output in the backward pass.

  Bytes: per product and pass the rows in and out at 2 bytes
  (bfloat16); per ``gmm`` pass the held experts' weights read once at 2
  bytes; the ``tgmm`` write the weights' gradient once at 2 (the kernels
  store bfloat16 from a float32 accumulator). At 512 rows an expert the
  products sit near the chip's ridge; the operations bound both cells."""
  d, f = c["hidden_size"], c["moe_intermediate_size"]
  held = c["num_hidden_layers"]
  layers = (held - min(_first(c, "first_k_dense_replace", "num_dense_layers"),
                       held) + c.get("num_nextn_predict_layers", 0))
  passes = moe_experts_passes(layers, gmm, tgmm, one_round)
  if passes is None:
    return None
  flops = passes * 3 * 2.0 * pairs * d * f
  weights = layers * _first(c, "n_routed_experts", "num_experts") * 3 * d * f
  bytes_ = (passes * 3 * pairs * (d + f) * 2.0 +
            (passes - 1) * weights * 2.0 + weights * 2.0)
  return flops, bytes_


# The attention core's kernels (jax's splash attention, under
# ``parallel/sequence.pallas_flash_attention``), by the start of their
# names: the forward; the backward for keys and values, which fused also
# makes the queries' gradient; the queries' backward where it is a kernel
# of its own.
SPLASH_KERNELS = ("splash_mha_fwd", "splash_mha_dkv", "splash_mha_dq")


def splash_launches(launches: Dict[str, float]
                    ) -> Tuple[float, float, float]:
  """(forward, dkv, dq) launches a step among ``launches``
  (``lm_scopes.kernel_launches`` of a core's scope). One launch works
  every head and sequence of one layer."""
  return tuple(sum(n for kernel, n in launches.items()
                   if kernel.startswith(prefix))
               for prefix in SPLASH_KERNELS)


def splash_products(fwd: float, dkv: float, dq: float) -> float:
  """Products of (one layer's scored pairs) x head size that those
  launches run. The forward kernel runs two (scores; weighted values).
  The backward kernel for keys and values, fused, runs five from one
  tile (scores, dv, dp, dk, dq); where the queries' gradient is a kernel
  of its own (the pair before PR 30), each rebuilds the scores and dp:
  four and three."""
  return 2 * fwd + (4 * dkv + 3 * dq if dq else 5 * dkv)


def attention_core_executed(c: Dict[str, Any], seq_len: int, sequences: int,
                            launches: Dict[str, float]
                            ) -> Tuple[float, float]:
  """(operations, bytes) under the scope ``attention_core`` in ONE
  training step, over every attention layer (the MTP block's too), by
  the kernel launches the trace shows under that scope.

  Per head and sequence one product over the causal half is seq_len^2 x
  head size operations (2 x seq_len^2 / 2); ``splash_products`` says how
  many the launches run. Blocks above the diagonal are skipped by the
  kernels and not counted, and the masked half of the blocks ON the
  diagonal is not counted either, so the share is of the useful work.
  Seen (PR 34's trace of PR 33's program, six layers): 10 forward and 6
  fused backward launches a step = 50 products, NOT 6 x 9: remat repeats
  the forward of the four scanned layers; the dense layer and the MTP
  block are unrolled, and outside a scan XLA merges the repeat with the
  first. (Until PR 34 this counted 6 x 11, the two backward kernels of
  PR 30's parent: the share read 66/50 of this.) Bytes: q, k, v, output
  and their gradients once per kernel at 2 bytes (forward 4 tensors,
  fused backward 8; the pair 8 and 7); far below the operations' time at
  these lengths."""
  h = c["num_attention_heads"]
  size = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]   # = v_head_dim
  fwd, dkv, dq = splash_launches(launches)
  flops = (splash_products(fwd, dkv, dq) * float(seq_len) ** 2 * size * h *
           sequences)
  tensor = seq_len * h * size * 2.0 * sequences
  bytes_ = (4 * fwd + 8 * dkv + 7 * dq) * tensor
  return flops, bytes_


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: Dict[str, Any]) -> float:
  """Percent: the least time the chip could take for this work (the
  larger of operations over its peak and bytes over its bandwidth) over
  the time it took."""
  least = max(flops / peaks["bf16_flops_per_s"],
              bytes_ / peaks["hbm_bytes_per_s"])
  return 100.0 * least / seconds
