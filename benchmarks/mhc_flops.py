"""Operations and bytes of a latent-attention mixture-of-experts decoder
with ``hc_mult`` residual streams under manifold-constrained
hyper-connections (``model_type: xing4_0``), from a configuration's
numbers: the twin of ``lm_flops.py`` for this family.

Three kinds of count, kept apart:

* ``forward_flops_per_token``: what the MODEL requires for one forward
  pass, per token: the number ``mfu`` multiplies by 3 (recomputation not
  counted). The cell's file states it as a number
  (``forward_flops_per_sample``) and ``tests/benchmarks/test_bench_xing.py``
  holds the two together.
* ``mhc_necessary``: what the hyper-connections of a training step NEED,
  from the configuration alone, whatever implements them: the numerator
  of ``mhc_roofline``. The least bytes: per sublayer and token, forward,
  the n streams read once and written once, the sublayer's pre-mixed
  input written once and its output read once ((2n + 2) x C at 2 bytes);
  backward, the streams' gradient read once and written once, the
  streams read once more (the coefficients' gradients need them), the
  input's gradient read once and the output's gradient and the output
  itself once each ((3n + 3) x C at 2 bytes). A forward that remat runs
  again is not counted: the share says how far ANY form of the mixing is
  from the chip's limits, and what a form runs beyond the necessary
  lowers it.
* ``attention_core_executed``: what the PROGRAM's flash kernels run in
  one training step under ``attention_core``, by the kernel launches the
  traced run shows, with v at its OWN head size (``lm_flops``' count
  takes one head size for q, k and v).

``c`` is the configuration AS HELD (``benchmarks/configs/
xing4.0-29b-a4b.json``: ``num_hidden_layers``, ``first_k_dense_replace``,
``n_routed_experts``, ``num_attention_heads`` and ``vocab_size`` are the
counts held on the chip; ``published`` says of what). Only matrix
products are counted (2 operations a multiply-accumulate), the
coefficients' product among them: norms, RoPE, SiLU, sigmoids,
Sinkhorn-Knopp, softmax, top-k and the loss are bound by memory, not by
the MXU the peak describes.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks import lm_flops


def attention_projection_params(c: Dict[str, Any]) -> int:
  """The projections of the heads held: ``q_b_proj``, ``kv_b_proj`` and
  ``o_proj`` of those heads, the down-projections whole."""
  h = c["num_attention_heads"]
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  d = c["hidden_size"]
  return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk +
          d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) +
          c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) +
          h * c["v_head_dim"] * d)


def hc_params(c: Dict[str, Any]) -> int:
  """One sublayer's hyper-connection: phi (nC, 2n + n^2), its bias and
  the three scales."""
  n = c["hc_mult"]
  cols = 2 * n + n * n
  return n * c["hidden_size"] * cols + cols + 3


def forward_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """One forward pass per token of the model AS HELD:

  * every layer, and the MTP block: the projections of the heads held at
    2 x their parameters, the causal core (2 x heads x (qk + v) x
    seq_len / 2) and two hyper-connections' coefficient products (2 x nC
    x (2n + n^2) each);
  * a dense layer: 2 x 3 x hidden x intermediate_size;
  * a mixture layer (and the MTP block): the router over all the
    published experts, the shared expert, and the routed experts at the
    EXPECTED number of held experts a token chooses, experts_per_tok x
    held / published (0.5 of 4 here);
  * the MTP module's projection 2 x (2 hidden x hidden);
  * one head per loss at 2 x hidden x rows of the vocabulary held.
  """
  d, n = c["hidden_size"], c["hc_mult"]
  dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
  mtp = c["num_nextn_predict_layers"]
  mixture = c["num_hidden_layers"] - dense + mtp
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  attention = (2.0 * attention_projection_params(c) +
               2.0 * c["num_attention_heads"] * (qk + c["v_head_dim"]) *
               seq_len / 2)
  hyper = 2 * 2.0 * n * d * (2 * n + n * n)
  expert = 3 * d * c["moe_intermediate_size"]
  published = c["published"]["n_routed_experts"]
  held_per_token = c["num_experts_per_tok"] * c["n_routed_experts"] / published
  per_mixture = 2.0 * (d * published + c["n_shared_experts"] * expert +
                       held_per_token * expert)
  return ((c["num_hidden_layers"] + mtp) * (attention + hyper) +
          dense * 2.0 * 3 * d * c["intermediate_size"] +
          mixture * per_mixture + mtp * 2.0 * 2 * d * d +
          (1 + mtp) * 2.0 * d * c["vocab_size"])


def parameters(c: Dict[str, Any]) -> int:
  """The parameters of the model AS HELD, the router's selection bias
  left out (it is state the optimizer does not own)."""
  d = c["hidden_size"]
  attention = (attention_projection_params(c) + c["q_lora_rank"] +
               c["kv_lora_rank"])
  layer = attention + 2 * hc_params(c) + 2 * d
  expert = 3 * d * c["moe_intermediate_size"]
  dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
  mtp = c["num_nextn_predict_layers"]
  mixture = (layer + d * c["published"]["n_routed_experts"] +
             c["n_shared_experts"] * expert + c["n_routed_experts"] * expert)
  return (dense * (layer + 3 * d * c["intermediate_size"]) +
          (c["num_hidden_layers"] - dense) * mixture +
          mtp * (mixture + 2 * d * d + 3 * d) + 2 * d * c["vocab_size"] + d)


def sublayers(c: Dict[str, Any]) -> int:
  return 2 * (c["num_hidden_layers"] + c["num_nextn_predict_layers"])


def mhc_necessary(c: Dict[str, Any], tokens: int) -> Tuple[float, float]:
  """(operations, bytes) the hyper-connections of ONE training step of
  ``tokens`` tokens need, over every sublayer (the module's docstring):
  the coefficients' products x3 (forward, and twice that backward), and
  (2n + 2) + (3n + 3) rows of C at 2 bytes a sublayer and token."""
  n, d = c["hc_mult"], c["hidden_size"]
  flops = 3 * 2.0 * n * d * (2 * n + n * n)
  bytes_ = (5 * n + 5) * d * 2.0
  layers = sublayers(c)
  return flops * tokens * layers, bytes_ * tokens * layers


def attention_core_executed(c: Dict[str, Any], seq_len: int, sequences: int,
                            launches: Dict[str, float]
                            ) -> Tuple[float, float]:
  """(operations, bytes) under ``attention_core`` in ONE training step,
  over every attention layer, by the splash kernels' launches the trace
  shows there (``lm_flops.splash_launches``), q and k at nope + rope and
  v at ``v_head_dim``. Per head and sequence over the causal half
  (seq_len^2 / 2 pairs): the forward kernel runs the scores (qk) and the
  weighted values (v); the fused backward the scores again, dp and dv
  (v each), dk and dq (qk each); the pair of backward kernels before PR
  30 four and three products, counted the same way. Bytes, at 2 a value
  once per kernel: forward q, k, v, output; fused backward q, k, v,
  output, its gradient, dq, dk, dv."""
  h = c["num_attention_heads"]
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  v = c["v_head_dim"]
  fwd, dkv, dq = lm_flops.splash_launches(launches)
  pairs = float(seq_len) ** 2 * h * sequences      # 2 x seq^2 / 2 a product
  if dq:
    backward = dkv * (2 * qk + 2 * v) + dq * (2 * qk + v)
  else:
    backward = dkv * (3 * qk + 2 * v)
  flops = pairs * (fwd * (qk + v) + backward)
  row = seq_len * h * 2.0 * sequences
  bytes_ = row * (fwd * (2 * qk + 2 * v) + dkv * (4 * qk + 4 * v) +
                  dq * (3 * qk + 2 * v))
  return flops, bytes_
