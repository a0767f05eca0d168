"""Operations and bytes of a grouped-query window/full mixture-of-experts
decoder (``model_type: afmoe``), from a configuration's numbers: the
twin of ``lm_flops.py``, which reads the latent-attention family's key
names.

Two kinds of count, kept apart as there:

* ``forward_flops_per_token``: what the MODEL requires for one forward
  pass, per token: the number ``mfu`` multiplies by 3 (recomputation not
  counted). The cell's file states it as a number
  (``forward_flops_per_sample``) and ``tests/benchmarks/test_bench_afmoe.py``
  holds the two together.
* ``*_executed``: what the PROGRAM runs in one training step under a
  scope, recomputation included, by the kernel launches the traced run
  shows: the numerator of a kernel's share of its roofline, which
  therefore cannot read above 100% for work that was not done. (The
  grouped products' is ``lm_flops.moe_experts_executed``, one function
  for both families.)

``c`` is the configuration AS HELD (``benchmarks/configs/trinity-mini.json``:
``num_hidden_layers``, ``num_dense_layers``, ``layer_types``,
``num_experts`` and ``vocab_size`` are the counts held on the chip;
``published`` says of what). Only matrix products are counted (2
operations a multiply-accumulate).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from benchmarks import lm_flops

WINDOW, FULL = "sliding_attention", "full_attention"


def attention_projection_params(c: Dict[str, Any]) -> int:
  """q, gate and o at heads x head size, k and v at key heads x head
  size."""
  h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
              c["head_dim"])
  return c["hidden_size"] * hd * (3 * h + 2 * g)


def band_pairs(seq_len: int, window: Optional[int]) -> int:
  """(query, key) pairs a causal layer scores over one sequence, the
  query's own position included: every query sees min(position + 1,
  window) keys."""
  w = seq_len if window is None else min(window, seq_len)
  return w * (w + 1) // 2 + (seq_len - w) * w


def layers_of(c: Dict[str, Any], kind: str) -> int:
  return sum(t == kind for t in c["layer_types"])


def _window(c: Dict[str, Any], kind: str) -> Optional[int]:
  return c["sliding_window"] if kind == WINDOW else None


def attention_core_flops_per_token(c: Dict[str, Any], seq_len: int,
                                   kind: str) -> float:
  """Scores and the weighted sum of values of one layer of ``kind``, per
  token: 2 x heads x (head size + head size) x the keys a token sees on
  average."""
  keys = band_pairs(seq_len, _window(c, kind)) / seq_len
  return 2.0 * c["num_attention_heads"] * 2 * c["head_dim"] * keys


def forward_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
  """One forward pass per token of the model AS HELD:

  * every layer: the attention projections at 2 x their parameters, and
    the core of its own kind (window or full);
  * a dense layer: 2 x 3 x hidden x intermediate_size;
  * a mixture layer: the router over ALL the published experts, the
    shared experts, and the routed experts at the EXPECTED number of
    held experts a token chooses, experts_per_tok x held / published (1
    of 8 here);
  * one head at 2 x hidden x rows of the vocabulary held.
  """
  d = c["hidden_size"]
  layers = c["num_hidden_layers"]
  assert len(c["layer_types"]) == layers
  dense = min(c["num_dense_layers"], layers)
  cores = sum(layers_of(c, kind) *
              attention_core_flops_per_token(c, seq_len, kind)
              for kind in (WINDOW, FULL))
  expert = 3 * d * c["moe_intermediate_size"]
  held_per_token = (c["num_experts_per_tok"] * c["num_experts"] /
                    c["published"]["num_experts"])
  per_mixture = 2.0 * (d * c["published"]["num_experts"] +
                       c["num_shared_experts"] * expert +
                       held_per_token * expert)
  return (layers * 2.0 * attention_projection_params(c) + cores +
          dense * 2.0 * 3 * d * c["intermediate_size"] +
          (layers - dense) * per_mixture + 2.0 * d * c["vocab_size"])


def attention_core_executed(c: Dict[str, Any], seq_len: int, sequences: int,
                            kind: str, launches: Dict[str, float]
                            ) -> Tuple[float, float]:
  """(operations, bytes) under the scope ``attention_core_window`` or
  ``attention_core_full`` in ONE training step, over the layers of that
  ``kind`` together, by the kernel launches the trace shows under that
  scope (``lm_scopes.kernel_launches``; one launch is one layer).

  Per head and sequence one product is 2 x head size operations a
  (query, key) pair INSIDE the band (or the causal half): what a tile's
  masked part computes beside them is not counted, so the share is of
  the useful work. ``lm_flops.splash_products`` says how many products
  the launches run. Seen (PR 34's trace of PR 33's program): window 4
  forward and 4 fused backward launches a step over 4 layers, full 1 and
  1: 7 products a layer, the forward ONCE (the held layers are unrolled,
  and outside a scan XLA merges the forward that ``nn.remat`` would
  repeat with the first one). Bytes, at 2 a number: the forward reads q
  and writes the output at the query heads and reads K and V ONCE A KEY
  HEAD (grouped queries are not repeated in memory); the fused backward
  reads q, the output and its gradient and writes dq at the query heads,
  reads K and V and writes dk and dv at the key heads (a dq kernel of
  its own would read and write the same less dk and dv). Far below the
  operations' time at these lengths."""
  h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
              c["head_dim"])
  fwd, dkv, dq = lm_flops.splash_launches(launches)
  pairs = band_pairs(seq_len, _window(c, kind))
  flops = (lm_flops.splash_products(fwd, dkv, dq) * 2.0 * pairs * hd * h *
           sequences)
  at_q = seq_len * h * hd * 2.0 * sequences
  at_kv = seq_len * g * hd * 2.0 * sequences
  bytes_ = (fwd * (2 * at_q + 2 * at_kv) + dkv * (4 * at_q + 4 * at_kv) +
            dq * (4 * at_q + 2 * at_kv))
  return flops, bytes_
