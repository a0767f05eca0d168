"""Operations and bytes of a one-mixer decoder of Mamba-2, mixture and
attention layers (``model_type: nemotron_h``), from a configuration's
numbers: the twin of ``lm_flops.py`` and ``afmoe_flops.py`` for this
family's key names.

Three kinds of count, kept apart:

* ``forward_flops_per_token``: what the MODEL requires for one forward
  pass, per token: the number ``mfu`` multiplies by 3 (recomputation not
  counted). The cell's file states it as a number
  (``forward_flops_per_sample``) and
  ``tests/benchmarks/test_bench_nemotron_h.py`` holds the two together.
* ``ssd_scan_necessary``: what the state-space scan of a training step
  NEEDS, from the configuration alone, whatever implements it: the
  numerator of ``ssd_scan_roofline``. Not what the program runs (a first
  form in einsums runs the full square of (i) and 6 passes of (iii)):
  the share then says how far ANY form of the scan is from the chip's
  limits, and reads the same before and after a kernel replaces it.
* ``moe_experts_executed``: what the PROGRAM runs in one training step
  under ``moe_experts``, recomputation included, by the kernel launches
  the traced run shows: ``lm_flops.moe_experts_executed`` for an expert
  of TWO matrices (that function counts three products a pair).

``c`` is the configuration AS HELD (``benchmarks/configs/
nemotron-3-nano-30b-a3b.json``: ``num_hidden_layers``,
``hybrid_override_pattern``, ``n_routed_experts`` and ``vocab_size`` are
the counts held on the chip; ``published`` says of what). Only matrix
products are counted (2 operations a multiply-accumulate): norms, the
convolution's 4 taps, SiLU, softplus, the exponentials, softmax, top-k
and the loss are bound by memory, not by the MXU the peak describes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

MAMBA, MIXTURE, ATTENTION = "M", "E", "*"


def layers_of(c: Dict[str, Any], kind: str) -> int:
  assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"]
  return c["hybrid_override_pattern"].count(kind)


def mamba_inner(c: Dict[str, Any]) -> int:
  return c["mamba_num_heads"] * c["mamba_head_dim"]


def mamba_projection_params(c: Dict[str, Any]) -> int:
  """``in_proj`` to [z | xBC | dt] and ``out_proj``."""
  inner = mamba_inner(c)
  width = 2 * inner + 2 * c["n_groups"] * c["ssm_state_size"] + \
      c["mamba_num_heads"]
  return c["hidden_size"] * (width + inner)


def scan_flops_per_token(c: Dict[str, Any]) -> float:
  """The chunked scan's products (i)-(iv) of ``ops/ssd.py`` at the
  PUBLISHED chunk, one layer, forward, per token: (i) the causal half of
  a chunk's (L x L) pairs, L (L + 1) / 2: the scores c_t . b_s once a
  group (2 N) and their product with x once a head (2 P); (ii) and (iv)
  2 H P N each; (iii) one multiply-add of the (H, P, N) state a chunk."""
  h, p, g, n, l = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                   c["ssm_state_size"], c["chunk_size"])
  seen = (l + 1) / 2.0                   # positions a token sees on average
  return (2.0 * n * g * seen + 2.0 * p * h * seen + 2 * 2.0 * h * p * n +
          2.0 * h * p * n / l)


def attention_projection_params(c: Dict[str, Any]) -> int:
  """q and o at heads x head size, k and v at key heads x head size."""
  return c["hidden_size"] * c["head_dim"] * (
      2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])


def attention_core_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """Causal scores and the weighted sum of values of one layer, per
  token: 2 x heads x (head size + head size) x the (seq_len + 1) / 2 keys
  a token sees on average, its own included."""
  return 2.0 * c["num_attention_heads"] * 2 * c["head_dim"] * (seq_len + 1) / 2


def forward_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """One forward pass per token of the model AS HELD:

  * a Mamba-2 layer: its two projections at 2 x their parameters and the
    scan (``scan_flops_per_token``);
  * a mixture layer: the router over ALL the published experts, the
    shared expert (two matrices of its own width) and the routed experts
    (two matrices) at the EXPECTED number of held experts a token
    chooses, experts_per_tok x held / published (0.375 of 6 here);
  * an attention layer: the projections at 2 x their parameters and the
    causal core;
  * one head at 2 x hidden x rows of the vocabulary held.
  """
  d = c["hidden_size"]
  mamba = 2.0 * mamba_projection_params(c) + scan_flops_per_token(c)
  held_per_token = (c["num_experts_per_tok"] * c["n_routed_experts"] /
                    c["published"]["n_routed_experts"])
  mixture = 2.0 * (d * c["published"]["n_routed_experts"] +
                   2 * d * c["moe_shared_expert_intermediate_size"] +
                   held_per_token * 2 * d * c["moe_intermediate_size"])
  attention = (2.0 * attention_projection_params(c) +
               attention_core_flops_per_token(c, seq_len))
  return (layers_of(c, MAMBA) * mamba + layers_of(c, MIXTURE) * mixture +
          layers_of(c, ATTENTION) * attention + 2.0 * d * c["vocab_size"])


def parameters(c: Dict[str, Any]) -> int:
  """The parameters of the model AS HELD, the router's selection bias
  (``e_score_correction_bias``, which the program keeps as state)
  counted with the published model's."""
  d = c["hidden_size"]
  inner = mamba_inner(c)
  conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
  mamba = (mamba_projection_params(c) + conv * c["conv_kernel"] + conv +
           3 * c["mamba_num_heads"] + inner + d)
  experts = c["published"]["n_routed_experts"]
  mixture = (d * experts + experts +
             2 * d * c["moe_shared_expert_intermediate_size"] +
             c["n_routed_experts"] * 2 * d * c["moe_intermediate_size"] + d)
  attention = attention_projection_params(c) + d
  return (layers_of(c, MAMBA) * mamba + layers_of(c, MIXTURE) * mixture +
          layers_of(c, ATTENTION) * attention + 2 * d * c["vocab_size"] + d)


def ssd_scan_necessary(c: Dict[str, Any], tokens: int) -> Tuple[float, float]:
  """(operations, bytes) the state-space scans of ONE training step of
  ``tokens`` tokens need, over the Mamba layers together.

  Operations: ``scan_flops_per_token`` x 3 (forward, and twice that
  backward). Bytes: x, B and C in and y out once at 2 bytes and dt at 4,
  and the gradients of the five the same once: what any scan has to move
  between the convolution before it and the gated norm behind it (the
  carried state stays on the chip in a fused form: 2 MB a link)."""
  per_token = (2 * mamba_inner(c) +
               2 * c["n_groups"] * c["ssm_state_size"]) * 2.0 + \
      c["mamba_num_heads"] * 4.0
  layers = layers_of(c, MAMBA)
  return (3.0 * scan_flops_per_token(c) * tokens * layers,
          2.0 * per_token * tokens * layers)


def moe_experts_passes(layers: int, gmm: float, tgmm: float,
                       one_round: bool) -> Optional[float]:
  """``lm_flops.moe_experts_passes`` for TWO launches a pass over one
  round of one layer (a pair passes two products): ``tgmm`` is 2 x layers
  x rounds, ``gmm`` a whole multiple of it, at least twice, and the step
  makes (gmm + tgmm) / tgmm passes. None where the launches are not that
  pattern (a kernel fused or renamed, or a three-matrix expert's)."""
  if not tgmm or layers <= 0:
    return None
  forward_and_rows = gmm / tgmm
  each_layer = tgmm / (2.0 * layers)
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
  under ``moe_experts``, over all mixture layers together, at ``pairs``
  (token, expert) pairs a step routed to held experts and ``gmm`` /
  ``tgmm`` launches a step under the scope. A pair passes TWO products of
  hidden x moe_intermediate_size: a step runs ``moe_experts_passes`` of
  2 x 2 x pairs x hidden x width. Bytes as ``lm_flops`` counts them: per
  product and pass the rows in and out at 2 bytes; per ``gmm`` pass the
  held experts' weights read once; the ``tgmm`` write the weights'
  gradient once."""
  d, f = c["hidden_size"], c["moe_intermediate_size"]
  layers = layers_of(c, MIXTURE)
  passes = moe_experts_passes(layers, gmm, tgmm, one_round)
  if passes is None:
    return None
  flops = passes * 2 * 2.0 * pairs * d * f
  weights = layers * c["n_routed_experts"] * 2 * d * f
  bytes_ = (passes * 2 * pairs * (d + f) * 2.0 +
            (passes - 1) * weights * 2.0 + weights * 2.0)
  return flops, bytes_
