"""A config-driven decoder with a dropless mixture of experts: the zoo's
``mla_moe_lm``. ONE decoder whose layer recipe comes from the
configuration's family (``model_type``, ``_FAMILIES``):

* ``glm4_moe_lite`` (the DeepSeek-V3 block): latent attention, pre-norm,
  one multi-token-prediction module;
* ``afmoe`` (Arcee's Trinity family): grouped-query gated attention over
  WINDOW and FULL layers in one stack, norms on both sides of each
  sublayer, muP's embedding scale, no MTP;
* ``nemotron_h`` (NVIDIA's Nemotron-H family): ONE mixer a layer,
  ``x + mixer(norm(x))``, its kind by the layer's own character of
  ``hybrid_override_pattern``: a Mamba-2 state-space mixer
  (``Mamba2Mixer`` over ``ops/ssd.py``), a mixture of TWO-matrix
  ``relu(.)^2`` experts, or grouped-query attention with no gate, no
  head norms and no positional rotation;
* ``xing4_0`` (XingChen's Xing4.0): the DeepSeek-V3 block with YaRN and
  ``hc_mult`` RESIDUAL STREAMS mixed around each sublayer by
  manifold-constrained hyper-connections (``HyperConnection``).

``MoE``, ``SwiGLU``, ``RMSNorm``, the rotary stage (``ops/rotary.py``:
head norm, RoPE and the cast between a projection and the core, one
pass each way), the losses and the share are the same code for all (the
zoo name stays ``mla_moe_lm``: the module grew a second attention and a
third kind of mixer, not a second decoder).

BEYOND-REFERENCE: the reference zoo has no language model. Every size
comes from ONE data file, the model's published ``config.json`` held
verbatim under ``lm_configs/`` (``--lm_config=<name>``); a second
configuration of a family is a second file there, not code, and a
family's own key names (``num_experts`` / ``n_routed_experts``,
``route_scale`` / ``routed_scaling_factor``, ...) are mapped onto one
set in ``_FAMILIES``.

    python -m kf_benchmarks_tpu.cli --model=mla_moe_lm \
        --lm_config=glm-4.7-flash --seq_len=4096 --batch_size=2 \
        --lm_layers_held=5 --lm_layer_shards=8 --lm_layer_shard_index=0 \
        --optimizer=adam --use_fp16=true
    python -m kf_benchmarks_tpu.cli --model=mla_moe_lm \
        --lm_config=trinity-mini --seq_len=8192 --batch_size=1 \
        --lm_first_layer_held=1 --lm_layers_held=5 --lm_layer_shards=8 \
        --optimizer=adam --use_fp16=true
    python -m kf_benchmarks_tpu.cli --model=mla_moe_lm \
        --lm_config=nemotron-3-nano-30b-a3b --seq_len=8192 --batch_size=1 \
        --lm_first_layer_held=0 --lm_layers_held=9 --lm_layer_shards=16 \
        --lm_vocab_shards=8 --lm_layer_shard_index=0 --optimizer=adam \
        --use_fp16=true
    python -m kf_benchmarks_tpu.cli --model=mla_moe_lm \
        --lm_config=xing4.0-29b-a4b --seq_len=4096 --batch_size=1 \
        --lm_first_layer_held=1 --lm_layers_held=5 --lm_layer_shards=8 \
        --lm_head_shards=8 --lm_layer_shard_index=0 --optimizer=adam \
        --use_fp16=true

The layer (RMSNorm, no biases but the state-space convolution's):

* latent attention (MLA, ``MLAttention``): queries through
  ``q_lora_rank`` (down, RMSNorm, up to heads x (nope + rope)); keys and
  values through ``kv_lora_rank`` plus ONE rotary key shared by the heads
  (``kv_a_proj_with_mqa``, RMSNorm on the latent, ``kv_b_proj`` to heads
  x (nope + v)); RoPE on the rotary dimensions only (the rotary stage
  over q's whole head, the trailing rope dimensions rotated and the
  others passing through, and over the shared rotary key); scores scaled
  by 1/sqrt(nope + rope); the core is ``parallel/sequence.py``'s flash
  path, causal;
* grouped-query gated attention (``GQAttention``): ``q_proj`` to heads x
  head size, ``k_proj`` and ``v_proj`` to FEWER key heads, ``gate_proj``
  to heads x head size; RMSNorm over each head of q and of k; a layer
  the configuration's ``layer_types`` calls ``sliding_attention`` rotates
  q and k (RoPE, all dimensions) and sees ``sliding_window`` keys, its
  own position included; a ``full_attention`` layer rotates nothing and
  sees every earlier position; query head n reads key head n // group
  (never repeated in memory: the core's kernel fetches a key head's
  tiles for its group, and under a window SKIPS the tiles outside the
  band); the head norms, 1/sqrt(head size) on q, RoPE and the cast are
  ONE pass over q and one over k (the rotary stage; ``q_norm`` and
  ``k_norm`` hold the learned scales alone); the core's output times
  sigmoid(gate) goes through ``o_proj``;
* the Mamba-2 mixer (``Mamba2Mixer``; H heads of P, G groups, state N):
  ``[z | xBC | dt] = in_proj(u)``; ``xBC <- silu(conv(xBC))``, a causal
  depthwise convolution over each channel's own last K positions;
  ``[x | B | C] = xBC``; ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``, computed in CHUNKS with the state carried
  between them (``ops/ssd.py``; float32 running sums, exponentials and
  carried state); ``y <- RMSNorm over each group of (y * silu(z))``, the
  gate BEFORE the norm; ``out_proj``. The mixer's inside is
  rematerialised from ``in_proj``'s output; a sequence is a whole number
  of chunks (``validation.py`` refuses any other with the reason);
* a block of ONE mixer (``MixerBlock``, ``nemotron_h``): ``norm`` then
  the mixer of the layer's kind, added to the residual; the final norm is
  ``norm_f``; a mixture layer's experts are ``down(relu(up(x))^2)``, two
  matrices at a width that no tile of the grouped product divides
  (``expert.gmm_tiling`` masks the last), the shared expert the same at
  its own width; the layers held are unrolled, each traced once;
* manifold-constrained hyper-connections (mHC, DeepSeek-AI 2025, on
  Hyper-Connections, Zhu et al. ICLR 2025; ``xing4_0``): each token
  carries ``hc_mult`` = n streams X (n, C), in the compute dtype; around
  each sublayer F (attention with ``input_layernorm``, the feed-forward
  with ``post_attention_layernorm``) float32 coefficients from the
  streams' RMS statistic and ONE product with phi (nC, 2n + n^2):
  ``h_pre = sigmoid(.)``, ``h_post = 2 sigmoid(.)``, ``H_res`` =
  ``hc_sinkhorn_iters`` Sinkhorn-Knopp iterations of ``exp(clamp(.))``,
  doubly stochastic; ``y = F(sum_i h_pre[i] X[i])``, ``X'[i] = sum_j
  H_res[i, j] X[j] + h_post[i] y``. The embedding is copied into the
  streams, which are summed before the final norm and before the MTP
  module's ``mtp_hnorm``; the MTP block expands and sums its own. The
  scanned stack's carry is (B, T, n, C). The arithmetic is
  ``ops/mhc.py``'s (``mhc_pre``, ``mhc_post``: two Pallas kernels each
  way on a TPU, ``mhc.mhc_plan``'s choice);
* YaRN (``rope_scaling`` of type ``yarn``, ``xing4_0``): the rotary
  stage's tables at YaRN's frequencies, and the scores' scale times its
  temperature squared, folded into q's float32 pass;
* norms: pre-norm (``input_layernorm``, ``post_attention_layernorm``
  before the feed-forward), or, ``post_norms``, one on each side of each
  sublayer (``input_layernorm`` / ``post_attention_layernorm`` around
  attention, ``pre_mlp_layernorm`` / ``post_mlp_layernorm`` around the
  feed-forward), the normed OUTPUT added to the residual;
* layers ``< first_k_dense_replace`` (``num_dense_layers``): a
  SiLU-gated feed-forward of ``intermediate_size``; the others a
  mixture: ``n_routed_experts`` of ``moe_intermediate_size``,
  top-``num_experts_per_tok`` by ``sigmoid(router)`` + a selection bias
  (``parallel/expert.py``), dropless, beside ``n_shared_experts`` shared
  ones every token passes;
* the mixture layers held are ONE scanned body where they are of one
  kind (every latent-attention stack), and unrolled where window and
  full layers mix (two bodies cannot be one scan body; a stage a chip
  holds is shorter than two periods of the pattern, so a scan over
  periods would have length 1); the scanned body is rematerialised, the
  unrolled layers keep their activations;
* ``num_nextn_predict_layers`` = 1 multi-token-prediction module:
  RMSNorm of the next token's embedding and of the last block's output,
  concatenated, projected back to the hidden size, one more mixture
  block, the main model's embedding and head, a second cross-entropy on
  the token after next (``ops/fused_loss.fused_softmax_xent_pair``: one
  head kernel, no (B, T, V) tensor in either loss); 0: one loss through
  the same kernel.

The share of a deployment (model-configs guide, section 4) is said by
flags: ``--lm_layers_held`` layers of the stack live here, from
published layer ``--lm_first_layer_held`` on (the others on further
chips, as pipeline stages; each held layer is dense or a mixture, window
or full, a state-space mixer or attention, by its OWN published index,
and the embedding feeds the first
of them), and each layer is divided over ``--lm_layer_shards`` chips of
which this is ``--lm_layer_shard_index``: it holds a contiguous block of
the routed experts and of the vocabulary's rows (token ids, labels,
logits and the losses are over the slice); ``--lm_vocab_shards`` gives
the vocabulary a number of chips of its own where a deployment divides
the experts further than the rows (16 and 8: a sixteenth of the
vocabulary would be under the guide's floor); ``--lm_head_shards`` divides
latent attention's heads (tensor parallelism: this chip's block of heads
in ``q_b_proj``, ``kv_b_proj`` and ``o_proj``, the down-projections
whole). Otherwise attention, the state-space mixers, the shared expert,
the router, the norms and the hyper-connections are held whole. What
the absent experts and heads would add is left out, and no code stands
in for the absent chips.

The router's selection bias is STATE THE OPTIMIZER DOES NOT OWN: it
lives in the ``batch_stats`` collection (the path batch-norm statistics
take through ``train_step.make_step_fns``), so it has no gradient, no
weight decay and no optimizer state, and is updated inside the step by
its own rule (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): after each
step ``bias_e += gamma * sign(mean load - load_e)``, loads counted over
all the experts from this chip's tokens. The same collection carries
what the step leaves for its readers: the expert loads, the pairs the
grouped products computed and whether the layer's pairs fit one round of
the routed path (``parallel/expert.compact_rows``), from which
``moe_counters`` makes the per-step counters that ride the step's
metrics (``stats["moe"]``), every token's chosen experts, and the
router's input and scores of the first ``ROUTER_PROBE_TOKENS`` tokens,
so that a check can hold the router of the TIMED program to a reference
(``benchmarks/named_checks/``: 5 MB a mixture layer, written once a
step).

Named scopes inside the step's ``forward`` scope, for the device trace
(``benchmarks/lm_scopes.py``): ``mla_attention`` (projections, the
rotary stage and the core) with ``attention_core`` inside it, or
``gqa_attention`` (projections, the rotary stage with the head norms in
it, gate and the core) with ``attention_core_window`` /
``attention_core_full`` inside it, so that each kind of layer is read
apart (the rotary stage's kernels, ``rotary_fwd`` / ``rotary_bwd``, lie
OUTSIDE the cores' scopes; its tables are built once a call of the
model, under no attention scope); ``moe_route`` (router, top-k, sort,
gather and scatter of rows), ``moe_experts`` inside it (the grouped
products), ``shared_expert``, ``mtp``, ``lm_head`` (the model's
``loss_function``); ``mamba_mixer`` (everything of a state-space mixer)
with ``mamba_conv`` and ``ssd_scan`` inside it; ``mhc`` (a
hyper-connection's coefficients, Sinkhorn-Knopp, the pre-mix and the
residual mix, around every sublayer). They are components
OTHER than the four ``train_step.STEP_SCOPES``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.ops import fused_loss as fused_loss_lib
from kf_benchmarks_tpu.ops import mhc
from kf_benchmarks_tpu.ops import rotary
from kf_benchmarks_tpu.ops import ssd
from kf_benchmarks_tpu.parallel import expert as expert_lib
from kf_benchmarks_tpu.parallel import sequence as sequence_lib

CONFIG_DIR = os.path.join(os.path.dirname(__file__), "lm_configs")
DEFAULT_CONFIG = "glm-4.7-flash"
DEFAULT_SEQ_LEN = 4096
# Not in config.json; the family's convention (DeepSeek-V3,
# arXiv:2412.19437): the MTP loss weight (section 2.2), the speed of the
# bias update (section 2.1.2), the initialiser's standard deviation.
MTP_LOSS_WEIGHT = 0.3
BIAS_UPDATE_SPEED = 0.001
INIT_STD = 0.02
# The block of scores the core's kernels compute at a time, forward and
# backward (parallel/sequence.flash_plan, which has what the v5e's VMEM
# allows at head size 256 field by field of the kernel's tiling). At this
# block the forward fetches 1024 queries and 1024 keys a grid step (2048
# keys fit only beside 512 queries, 4096 not at all), and the backward
# holds 1024 keys across its sweep over the queries in tiles of 512, which
# leaves 4 partial dq at 4096 keys (2048 keys held do not fit, nor 1024
# queries beside 1024 keys). The two-kernel backward this one replaced
# fitted 512 in every field and nothing larger.
ATTN_BLOCK = 512
ROUTER_PROBE_TOKENS = 1024
# The family's learning rate: a linear warm-up from 0 to the peak over
# 2,000 steps (arXiv:2412.19437 section 4.2), held there; the decay that
# follows lies beyond any run of this program.
PEAK_LEARNING_RATE = 2.2e-4
WARMUP_STEPS = 2000
# ``hybrid_override_pattern``'s characters: the kind of a layer's mixer.
MAMBA, MIXTURE, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class LMConfig:
  """The published keys the decoder reads, under ONE set of names (a
  family's own key names are mapped onto them by ``_FAMILIES``), the
  layer recipe of its family, and the share held here."""
  hidden_size: int
  intermediate_size: int
  moe_intermediate_size: int
  num_attention_heads: int
  n_routed_experts: int
  n_shared_experts: int
  num_experts_per_tok: int
  routed_scaling_factor: float
  norm_topk_prob: bool
  first_k_dense_replace: int
  num_hidden_layers: int
  rms_norm_eps: float
  rope_theta: float
  vocab_size: int
  # Latent attention (``attention == "mla"``).
  q_lora_rank: int = 0
  kv_lora_rank: int = 0
  qk_nope_head_dim: int = 0
  qk_rope_head_dim: int = 0
  v_head_dim: int = 0
  num_nextn_predict_layers: int = 0
  # Grouped-query gated attention (``attention == "gqa"``): key heads,
  # the head size, and per PUBLISHED layer whether it sees a window
  # (``sliding_attention``, with RoPE) or everything before it
  # (``full_attention``, with no positional rotation at all).
  num_key_value_heads: int = 0
  head_dim: int = 0
  layer_types: tuple = ()
  sliding_window: int = 0
  # The family's layer recipe: the attention module, a norm AFTER each
  # sublayer beside the one before it, what the embedding is multiplied
  # by, the speed of the router bias's update.
  attention: str = "mla"
  post_norms: bool = False
  embed_scale: float = 1.0
  bias_update_speed: float = BIAS_UPDATE_SPEED
  # Grouped-query attention's extras (``afmoe`` has both; ``nemotron_h``
  # neither, and no RoPE: its layers have no window).
  attention_gate: bool = True
  head_norms: bool = True
  # A block of ONE mixer (``nemotron_h``): ``layer_pattern`` gives the
  # KIND of each published layer's mixer, one character a layer
  # (``MAMBA``, ``MIXTURE``, ``ATTENTION``); empty for the families whose
  # every layer is attention THEN a feed-forward.
  layer_pattern: str = ""
  # The Mamba-2 mixer (``ops/ssd.py``): heads x head size channels,
  # ``n_groups`` groups of B and C of ``ssm_state_size``, a depthwise
  # convolution over ``conv_kernel`` positions, the scan's chunk, and the
  # range the step's initial value is drawn from.
  mamba_num_heads: int = 0
  mamba_head_dim: int = 0
  n_groups: int = 0
  ssm_state_size: int = 0
  conv_kernel: int = 0
  chunk_size: int = 0
  time_step_min: float = 0.001
  time_step_max: float = 0.1
  time_step_floor: float = 1e-4
  # The expert's form: its activation (``parallel/expert.ACTIVATIONS``),
  # gated by a third matrix or not, and the shared expert's width where
  # the configuration states one of its own.
  expert_activation: str = "silu"
  expert_gated: bool = True
  moe_shared_expert_intermediate_size: int = 0
  # Manifold-constrained hyper-connections (``xing4_0``): ``hc_mult``
  # residual streams (0: ONE stream, ``x + f(norm(x))``), the Sinkhorn
  # iterations that make each sublayer's residual mix doubly stochastic,
  # the epsilon of their denominators and the clamp of the mix's logits.
  hc_mult: int = 0
  hc_sinkhorn_iters: int = 0
  hc_eps: float = 0.0
  mhc_h_res_clamp_min: float = 0.0
  mhc_h_res_clamp_max: float = 0.0
  # YaRN (``rope_scaling`` of type ``yarn``; ``yarn_factor`` 0: plain
  # RoPE and the plain softmax scale).
  yarn_factor: float = 0.0
  yarn_beta_fast: float = 0.0
  yarn_beta_slow: float = 0.0
  yarn_original_max_position: int = 0
  yarn_mscale: float = 0.0
  yarn_mscale_all_dim: float = 0.0
  # The share: ``layers_held`` layers of num_hidden_layers from published
  # layer ``first_layer`` on, and which of how many chips that divide
  # each layer this is; ``vocab_shards`` chips divide the vocabulary
  # (0: as many as divide a layer); ``head_shards`` divide latent
  # attention's heads (tensor parallelism over heads; 1: held whole).
  layers_held: int = 0
  first_layer: int = 0
  shards: int = 1
  shard_index: int = 0
  vocab_shards: int = 0
  head_shards: int = 1

  @property
  def experts_held(self) -> int:
    return self.n_routed_experts // self.shards

  @property
  def first_expert(self) -> int:
    return self.shard_index * self.experts_held

  @property
  def vocab_rows(self) -> int:
    return self.vocab_size // (self.vocab_shards or self.shards)

  @property
  def heads_held(self) -> int:
    return self.num_attention_heads // self.head_shards

  @property
  def first_head(self) -> int:
    return self.shard_index * self.heads_held if self.head_shards > 1 else 0

  @property
  def yarn(self) -> Optional[rotary.Yarn]:
    if not self.yarn_factor:
      return None
    return rotary.Yarn(self.yarn_factor, self.yarn_beta_fast,
                       self.yarn_beta_slow, self.yarn_original_max_position,
                       self.yarn_mscale, self.yarn_mscale_all_dim)

  @property
  def softmax_scale(self) -> float:
    """Latent attention's scores' scale: 1/sqrt(nope + rope), times
    YaRN's temperature squared where the configuration states YaRN with
    ``mscale_all_dim`` (DeepSeek-V3's release)."""
    scale = 1.0 / math.sqrt(self.qk_head_dim)
    if self.yarn_factor and self.yarn_mscale_all_dim:
      scale *= rotary.yarn_mscale(self.yarn_factor,
                                  self.yarn_mscale_all_dim) ** 2
    return scale

  @property
  def sublayers(self) -> int:
    """Sublayers behind a hyper-connection: two a layer held and two in
    the MTP block (0 on one stream)."""
    if not self.hc_mult:
      return 0
    return 2 * (self.layers_held + self.num_nextn_predict_layers)

  @property
  def kinds(self) -> str:
    """The mixer of each layer held, one character a layer (empty where
    every layer is attention then a feed-forward)."""
    return self.layer_pattern[self.first_layer:
                              self.first_layer + self.layers_held]

  @property
  def dense_layers(self) -> int:
    """Of the layers held, those before ``first_k_dense_replace``."""
    return max(0, min(self.first_k_dense_replace,
                      self.first_layer + self.layers_held) - self.first_layer)

  @property
  def moe_layers(self) -> int:
    if self.layer_pattern:
      return self.kinds.count(MIXTURE)
    return self.layers_held - self.dense_layers

  @property
  def mamba_layers(self) -> int:
    return self.kinds.count(MAMBA)

  @property
  def expert_matrices(self) -> int:
    return 3 if self.expert_gated else 2

  @property
  def shared_width(self) -> int:
    return (self.moe_shared_expert_intermediate_size or
            self.moe_intermediate_size * self.n_shared_experts)

  @property
  def qk_head_dim(self) -> int:
    return self.qk_nope_head_dim + self.qk_rope_head_dim

  def window(self, held_layer: int) -> Optional[int]:
    """The window of the ``held_layer``-th layer held here (None: it
    sees every earlier position)."""
    kinds = self.layer_types
    if kinds and kinds[self.first_layer + held_layer] == "sliding_attention":
      return self.sliding_window
    return None

  @property
  def windows(self) -> tuple:
    return tuple(self.window(i) for i in range(self.layers_held))

  @property
  def attention_windows(self) -> tuple:
    """``windows`` of the layers held that HAVE attention (all of them,
    but in a one-mixer block)."""
    return tuple(w for i, w in enumerate(self.windows)
                 if not self.layer_pattern or self.kinds[i] == ATTENTION)


# A family (``model_type``) is a layer recipe, the names its config.json
# gives the keys above, and what its code here does not implement, which
# is refused, never ignored.
_FAMILIES = {
    # The DeepSeek-V3 block: latent attention, pre-norm, one MTP module.
    "glm4_moe_lite": dict(
        recipe=dict(attention="mla"), names={},
        required={"hidden_act": "silu", "attention_bias": False,
                  "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
                  "rope_scaling": None, "tie_word_embeddings": False,
                  "partial_rotary_factor": 1}),
    # Arcee's Trinity family: grouped-query gated attention over window
    # and full layers, norms on both sides of each sublayer, muP's
    # embedding scale, sigmoid routing with a balance bias.
    "afmoe": dict(
        recipe=dict(attention="gqa", post_norms=True),
        names={"num_experts": "n_routed_experts",
               "num_shared_experts": "n_shared_experts",
               "route_scale": "routed_scaling_factor",
               "route_norm": "norm_topk_prob",
               "num_dense_layers": "first_k_dense_replace",
               "load_balance_coeff": "bias_update_speed"},
        required={"hidden_act": "silu", "score_func": "sigmoid",
                  "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
                  "num_limited_groups": 1, "rope_scaling": None,
                  "tie_word_embeddings": False, "mup_enabled": True}),
    # NVIDIA's Nemotron-H family: ONE mixer a layer (a Mamba-2 state-space
    # mixer, a mixture of two-matrix relu^2 experts, or grouped-query
    # attention with no gate, no head norms and no positional rotation),
    # ``x + mixer(norm(x))``; sigmoid routing with a selection bias.
    "nemotron_h": dict(
        recipe=dict(attention="gqa", attention_gate=False, head_norms=False,
                    expert_activation="relu2", expert_gated=False,
                    first_k_dense_replace=0),
        names={"norm_eps": "rms_norm_eps",
               "hybrid_override_pattern": "layer_pattern"},
        required={"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
                  "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
                  "attention_bias": False, "mamba_proj_bias": False,
                  "mlp_bias": False, "use_bias": False,
                  "use_conv_bias": True, "norm_topk_prob": True,
                  "n_shared_experts": 1, "sliding_window": None,
                  "residual_in_fp32": False}),
    # XingChen's Xing4.0 family: the DeepSeek-V3 block with YaRN and
    # ``hc_mult`` residual streams mixed around each sublayer by
    # manifold-constrained hyper-connections (mHC, DeepSeek-AI 2025, on
    # Hyper-Connections, Zhu et al., ICLR 2025).
    "xing4_0": dict(
        recipe=dict(attention="mla"), names={}, rope="yarn", streams=True,
        required={"hidden_act": "silu", "attention_bias": False,
                  "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
                  "scoring_func": "sigmoid", "moe_layer_freq": 1,
                  "ep_size": 1, "tie_word_embeddings": False}),
}


def head_share_refusal(raw: dict, head_shards: int) -> Optional[str]:
  """Why ``--lm_head_shards=head_shards`` cannot divide the configuration
  ``raw`` (a config.json as a dict), or None: heads are divided in latent
  attention alone (its q, kv and o projections hold a block of heads;
  the grouped-query module has no such share), and into whole heads."""
  if head_shards == 1:
    return None
  family = _FAMILIES.get(raw.get("model_type"), {})
  if family.get("recipe", {}).get("attention") != "mla":
    return (f"--lm_head_shards={head_shards}: heads are divided in latent "
            f"attention alone, and model_type={raw.get('model_type')!r} "
            "has none")
  if raw["num_attention_heads"] % head_shards:
    return (f"--lm_head_shards={head_shards} does not divide "
            f"num_attention_heads={raw['num_attention_heads']}")
  return None


def read_config_file(name: str):
  """(path, ``lm_configs/<name>.json`` as a dict)."""
  path = os.path.join(CONFIG_DIR, name + ".json")
  if not os.path.isfile(path):
    have = sorted(f[:-5] for f in os.listdir(CONFIG_DIR)
                  if f.endswith(".json"))
    raise ValueError(f"--lm_config={name}: no file {path}; have {have}")
  with open(path, encoding="utf-8") as f:
    return path, json.load(f)


def load_lm_config(name: str, layers_held: Optional[int] = None,
                   shards: int = 1, shard_index: int = 0,
                   first_layer: int = 0,
                   vocab_shards: Optional[int] = None,
                   head_shards: int = 1) -> LMConfig:
  """``lm_configs/<name>.json`` as an LMConfig with the share applied
  (``vocab_shards`` None: the vocabulary is divided like a layer;
  ``head_shards``: chips that divide latent attention's heads)."""
  path, raw = read_config_file(name)
  family = _FAMILIES.get(raw.get("model_type"))
  if family is None:
    raise ValueError(f"{path}: model_type={raw.get('model_type')!r} is not "
                     f"a family this decoder builds ({sorted(_FAMILIES)})")
  for key, want in family["required"].items():
    if raw.get(key, want) != want:
      raise ValueError(f"{path}: {key}={raw[key]!r} is not implemented "
                       f"({raw['model_type']} here implements {want!r})")
  scaling = raw.get("rope_scaling")
  if family.get("rope") == "yarn":
    if not isinstance(scaling, dict) or scaling.get(
        "type", scaling.get("rope_type")) != "yarn":
      raise ValueError(f"{path}: rope_scaling={scaling!r} is not implemented "
                       f"({raw['model_type']} here implements YaRN alone)")
    raw.update(yarn_factor=scaling["factor"],
               yarn_beta_fast=scaling["beta_fast"],
               yarn_beta_slow=scaling["beta_slow"],
               yarn_original_max_position=scaling[
                   "original_max_position_embeddings"],
               yarn_mscale=scaling.get("mscale", 1),
               yarn_mscale_all_dim=scaling.get("mscale_all_dim", 0))
  streams = raw.get("hc_mult", 1)
  if family.get("streams") and streams < 2:
    raise ValueError(f"{path}: hc_mult={streams!r}: {raw['model_type']} here "
                     "implements hyper-connections over 2 or more streams")
  if not family.get("streams") and streams != 1:
    raise ValueError(f"{path}: hc_mult={streams!r} is not implemented "
                     f"({raw['model_type']} here has one residual stream)")
  why = head_share_refusal(raw, head_shards)
  if why:
    raise ValueError(f"{path}: {why}")
  raw = {family["names"].get(k, k): v for k, v in raw.items()}
  recipe = dict(family["recipe"])
  if recipe["attention"] == "mla":
    if raw.get("num_key_value_heads", raw["num_attention_heads"]) != \
        raw["num_attention_heads"]:
      raise ValueError(f"{path}: latent attention has one key per head")
    if raw["num_nextn_predict_layers"] not in (0, 1):
      raise ValueError(f"{path}: at most one MTP module is implemented")
  elif raw["num_attention_heads"] % raw["num_key_value_heads"]:
    raise ValueError(f"{path}: {raw['num_key_value_heads']} key heads do "
                     f"not divide {raw['num_attention_heads']} query heads")
  if "layer_pattern" in raw:
    pattern = raw["layer_pattern"]
    unknown = set(pattern) - {MAMBA, MIXTURE, ATTENTION}
    if unknown or len(pattern) != raw["num_hidden_layers"]:
      raise ValueError(
          f"{path}: hybrid_override_pattern names {sorted(unknown)} or is "
          f"not one character a layer ({len(pattern)})")
    if raw["mamba_num_heads"] % raw["n_groups"]:
      raise ValueError(f"{path}: {raw['n_groups']} groups do not divide "
                       f"{raw['mamba_num_heads']} Mamba heads")
  elif recipe["attention"] == "gqa":
    kinds = raw["layer_types"] = tuple(raw["layer_types"])
    unknown = set(kinds) - {"sliding_attention", "full_attention"}
    if unknown or len(kinds) != raw["num_hidden_layers"]:
      raise ValueError(f"{path}: layer_types names {sorted(unknown)} or is "
                       f"not one entry a layer ({len(kinds)})")
    # muP: the embedding's output times sqrt(hidden size) (assumed to be
    # all it changes in the forward pass).
    recipe["embed_scale"] = math.sqrt(raw["hidden_size"])
  fields = {f.name for f in dataclasses.fields(LMConfig)}
  cfg = LMConfig(**{k: v for k, v in raw.items()
                    if k in fields and v is not None}, **recipe)
  held = (cfg.num_hidden_layers - first_layer if layers_held is None
          else layers_held)
  if not (0 <= first_layer and 1 <= held and
          first_layer + held <= cfg.num_hidden_layers):
    raise ValueError(f"--lm_layers_held={held} from "
                     f"--lm_first_layer_held={first_layer}: the model has "
                     f"{cfg.num_hidden_layers} layers")
  if not 0 <= shard_index < shards:
    raise ValueError(f"--lm_layer_shard_index={shard_index} of "
                     f"--lm_layer_shards={shards}")
  over = shards if vocab_shards is None else vocab_shards
  for flag, n, what, count in (
      ("lm_layer_shards", shards, "n_routed_experts", cfg.n_routed_experts),
      ("lm_layer_shards" if vocab_shards is None else "lm_vocab_shards",
       over, "vocab_size", cfg.vocab_size)):
    if n < 1 or count % n:
      raise ValueError(f"--{flag}={n} does not divide {what}={count}")
  if not shard_index < over:
    # The embedding and the head are built here: a chip past the last
    # that holds rows of the vocabulary has neither.
    raise ValueError(f"--lm_layer_shard_index={shard_index} holds no rows "
                     f"of a vocabulary divided over --lm_vocab_shards="
                     f"{over} chips")
  if head_shards > 1 and not shard_index < head_shards:
    raise ValueError(f"--lm_layer_shard_index={shard_index} holds no heads "
                     f"of attention divided over --lm_head_shards="
                     f"{head_shards} chips")
  return dataclasses.replace(cfg, layers_held=held, first_layer=first_layer,
                             shards=shards, shard_index=shard_index,
                             vocab_shards=over, head_shards=head_shards)


def _normal():
  return nn.initializers.normal(INIT_STD)


class RMSNorm(nn.Module):
  eps: float
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + self.eps) * scale.astype(jnp.float32)


class HeadNormScale(nn.Module):
  """The learned scale of a norm over each head, under ``RMSNorm``'s own
  name and shape (``<name>/scale``: checkpoints and the parameter count
  do not move). The norm's arithmetic is the rotary stage's."""
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, width):
    return self.param("scale", nn.initializers.ones, (width,),
                      self.param_dtype)


def rotary_sites(c: "LMConfig") -> dict:
  """The call sites of the rotary stage in a layer, by name: heads, head
  size, the trailing dimensions rotated, whether a head norm rides the
  pass, and the layers held that have the site (the MTP block's too)."""
  if c.attention == "mla":
    layers = c.layers_held + c.num_nextn_predict_layers
    rot = c.qk_rope_head_dim
    return {
        "q": dict(heads=c.heads_held, head_dim=c.qk_head_dim,
                  rot_dims=rot, normed=False, layers=layers),
        "k_rot": dict(heads=1, head_dim=rot, rot_dims=rot, normed=False,
                      layers=layers)}
  sites = {}
  for kind, window in (("window", c.sliding_window), ("full", None)):
    layers = sum(w == window for w in c.attention_windows)
    for name, heads in (("q", c.num_attention_heads),
                        ("k", c.num_key_value_heads)):
      # A layer with neither head norms nor a window has no stage at all.
      if layers and (c.head_norms or window is not None):
        sites[f"{kind}_{name}"] = dict(
            heads=heads, head_dim=c.head_dim, normed=c.head_norms,
            layers=layers, rot_dims=c.head_dim if window is not None else 0)
  return sites


def rope_tables(c: "LMConfig", seq_len: int, dtype) -> dict:
  """RoPE's tables for every layer of one call of the model, by call
  site that rotates, at the width its plan asks for (sites that ask for
  the same one, grouped queries' q and k, state the same computation,
  which XLA forms once)."""
  return {name: rotary.stage_tables(
      (1, seq_len, site["heads"], site["head_dim"]), site["rot_dims"],
      c.rope_theta, site["normed"], dtype, c.yarn)
          for name, site in rotary_sites(c).items() if site["rot_dims"]}


class _Options(nn.Module):
  """Fields every submodule shares."""
  cfg: LMConfig
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32
  # parallel/expert.grouped_matmul's implementation.
  moe_impl: str = "ragged_dot"
  # The router's dtype: float32 is the configuration's; bfloat16 is the
  # lower-precision control of the tests and the benchmark's check.
  router_dtype: Any = jnp.float32
  # The dtype of the state-space scan's running sums, exponentials and
  # carried state (``ops/ssd.py``); bfloat16 is the control again.
  scan_dtype: Any = jnp.float32
  # The dtype of the hyper-connections' coefficient path (norm, product,
  # sigmoids, Sinkhorn); bfloat16 is the control again.
  mhc_dtype: Any = jnp.float32

  def dense(self, features, name, init=None):
    return nn.Dense(features, use_bias=False, name=name, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=init or _normal())

  def norm(self, name):
    return RMSNorm(self.cfg.rms_norm_eps, self.param_dtype, name=name)

  def head_scale(self, name, width):
    return HeadNormScale(self.param_dtype, name=name)(width)

  def options(self):
    return dict(cfg=self.cfg, dtype=self.dtype,
                param_dtype=self.param_dtype, moe_impl=self.moe_impl,
                router_dtype=self.router_dtype, scan_dtype=self.scan_dtype,
                mhc_dtype=self.mhc_dtype)


def exact_in_any_dtype(scale: float) -> bool:
  """Is multiplying by ``scale`` exact in every float type (a power of
  two)?"""
  return math.frexp(scale)[0] == 0.5


class MLAttention(_Options):
  """Latent attention over the heads held here (``heads_held``: all of
  them, or a chip's block of ``--lm_head_shards``: ``q_b_proj``,
  ``kv_b_proj`` and ``o_proj`` hold those heads' columns and rows alone,
  the down-projections and their norms whole). v enters the core at its
  own head size."""

  @nn.compact
  def __call__(self, x, tables=None):
    c = self.cfg
    b, t, _ = x.shape
    h, nope, rot, vd = (c.heads_held, c.qk_nope_head_dim,
                        c.qk_rope_head_dim, c.v_head_dim)
    tables = tables or rope_tables(c, t, self.dtype)
    with jax.named_scope("mla_attention"):
      q = self.dense(c.q_lora_rank, "q_a_proj")(x)
      q = self.norm("q_a_layernorm")(q).astype(self.dtype)
      q = self.dense(h * (nope + rot), "q_b_proj")(q)
      q = q.reshape(b, t, h, nope + rot)
      kv = self.dense(c.kv_lora_rank + rot, "kv_a_proj_with_mqa")(x)
      latent, k_rot = kv[..., :c.kv_lora_rank], kv[..., c.kv_lora_rank:]
      latent = self.norm("kv_a_layernorm")(latent).astype(self.dtype)
      kv = self.dense(h * (nope + vd), "kv_b_proj")(latent)
      kv = kv.reshape(b, t, h, nope + vd)
      # The rotary stage over q's whole head: the trailing rope dimensions
      # rotated, the others passing through the same pass. A scale that
      # is no power of two (YaRN's) is folded into that pass in float32,
      # as the grouped-query module folds its own; a power of two the
      # kernel applies exactly in q's dtype.
      scale = c.softmax_scale
      factor = 1.0
      if not exact_in_any_dtype(scale):
        factor, scale = scale, 1.0
      q = rotary.rotary_stage(q, tables["q"], rot_dims=rot, factor=factor)
      k_rot = rotary.rotary_stage(k_rot[:, :, None, :], tables["k_rot"],
                                  rot_dims=rot)
      k = jnp.concatenate(
          [kv[..., :nope], jnp.broadcast_to(k_rot, (b, t, h, rot))], -1)
      v = kv[..., nope:]
      with jax.named_scope("attention_core"):
        # The Pallas kernel on a TPU (one forward and ONE backward
        # kernel; v at its own head size, which the kernel takes beside
        # the queries' and keys': v_head_dim may differ from nope +
        # rope), materialised scores on the CPU (parallel/sequence.py).
        att = sequence_lib.pallas_flash_attention(
            q, k, v, causal=True, scale=scale, block=min(ATTN_BLOCK, t))
      return self.dense(c.hidden_size, "o_proj")(
          att.reshape(b, t, h * vd))


def gated(core, gate):
  """The attention core's output under its gate (``afmoe``)."""
  return core * jax.nn.sigmoid(gate)


class GQAttention(_Options):
  """Grouped-query attention: query head n reads key head
  n // (heads / key heads). ``afmoe``: RMSNorm over each head of q and
  of k (``head_norms``), RoPE in the WINDOW layers only, a full layer
  rotating nothing, and the core's output times sigmoid(gate_proj(x))
  before ``o_proj`` (``attention_gate``). ``nemotron_h``: none of the
  three (Nemotron-H, arXiv:2504.03624 section 2.1: no position
  embeddings): projections, the causal core, ``o_proj``."""
  window: Optional[int] = None

  @nn.compact
  def __call__(self, x, tables=None):
    c = self.cfg
    b, t, _ = x.shape
    h, g, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    rot = hd if self.window is not None else 0
    if rot:
      tables = tables or rope_tables(c, t, self.dtype)
    with jax.named_scope("gqa_attention"):
      q = self.dense(h * hd, "q_proj")(x).reshape(b, t, h, hd)
      k = self.dense(g * hd, "k_proj")(x).reshape(b, t, g, hd)
      v = self.dense(g * hd, "v_proj")(x).reshape(b, t, g, hd)
      gate = (self.dense(h * hd, "gate_proj")(x) if c.attention_gate
              else None)
      # The scores' scale 1/sqrt(head size) is no power of two at 128:
      # the kernel would scale q in q's dtype, a second bfloat16
      # rounding. It is folded into the head norm's float32 output (RoPE
      # is linear), one rounding at the cast, and the kernel scales
      # nothing. Norm, scale, RoPE and the cast are the rotary stage's
      # one pass (a full layer's rotates nothing).
      # Without head norms and a window there is no stage to fold the
      # scale into: the kernel's caller scales q, in q's dtype.
      scale = 1.0 / math.sqrt(hd)
      if c.head_norms or rot:
        stage = functools.partial(rotary.rotary_stage, rot_dims=rot,
                                  eps=c.rms_norm_eps)
        norm = lambda name: (self.head_scale(name, hd) if c.head_norms
                             else None)
        q = stage(q, tables["window_q"] if rot else None, norm("q_norm"),
                  factor=scale)
        k = stage(k, tables["window_k"] if rot else None, norm("k_norm"))
        scale = 1.0
      with jax.named_scope("attention_core_window" if self.window is not None
                           else "attention_core_full"):
        att = sequence_lib.pallas_flash_attention(
            q, k, v, causal=True, scale=scale, block=min(ATTN_BLOCK, t),
            window=self.window)
      att = att.reshape(b, t, h * hd)
      return self.dense(c.hidden_size, "o_proj")(
          att if gate is None else gated(att, gate))


class SwiGLU(_Options):
  width: int = 0

  @nn.compact
  def __call__(self, x):
    gate = self.dense(self.width, "gate_proj")(x)
    up = self.dense(self.width, "up_proj")(x)
    return self.dense(self.cfg.hidden_size, "down_proj")(
        nn.silu(gate) * up)


class PlainMLP(_Options):
  """A two-matrix feed-forward, ``down(act(up(x)))`` (``nemotron_h``'s
  shared expert: ``relu(.)^2``)."""
  width: int = 0

  @nn.compact
  def __call__(self, x):
    act = expert_lib.ACTIVATIONS[self.cfg.expert_activation]
    return self.dense(self.cfg.hidden_size, "down_proj")(
        act(self.dense(self.width, "up_proj")(x)))


class MoE(_Options):
  """The routed experts held here, beside the shared expert. The
  experts' FORM is the configuration's: gated by a third matrix
  (``experts_gate``, SiLU) or plain (two matrices, ``relu(.)^2``)."""

  @nn.compact
  def __call__(self, x):
    c = self.cfg
    b, t, d = x.shape
    e, g, f = c.n_routed_experts, c.experts_held, c.moe_intermediate_size
    router = self.param("router", _normal(), (d, e), self.param_dtype)
    expert = lambda name, shape: self.param(name, _normal(), (g,) + shape,
                                            self.param_dtype)
    w_gate = expert("experts_gate", (d, f)) if c.expert_gated else None
    w_up, w_down = expert("experts_up", (d, f)), expert("experts_down",
                                                        (f, d))
    bias = self.variable("batch_stats", "select_bias", jnp.zeros, (e,),
                         jnp.float32)
    load = self.variable("batch_stats", "load", jnp.zeros, (e,),
                         jnp.float32)
    computed = self.variable("batch_stats", "pairs_computed", jnp.zeros, (),
                             jnp.float32)
    compact = self.variable("batch_stats", "compact", jnp.zeros, (),
                            jnp.float32)
    tiled = self.variable("batch_stats", "rows_in_tiles", jnp.zeros, (),
                          jnp.float32)
    # For a check of the timed program: every token's choice, and the
    # router's input and scores of the first few tokens.
    probe = min(ROUTER_PROBE_TOKENS, b * t)
    # (float32, like all of this collection: the step averages it over
    # the replicas, and an average of integers is a float.)
    chosen = self.variable("batch_stats", "chosen", jnp.zeros,
                           (b * t, c.num_experts_per_tok), jnp.float32)
    probe_in = self.variable("batch_stats", "router_probe_in", jnp.zeros,
                             (probe, d), self.dtype)
    probe_scores = self.variable("batch_stats", "router_probe_scores",
                                 jnp.zeros, (probe, e), jnp.float32)
    flat = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
      weights, idx, scores = expert_lib.route_topk(
          flat, router, bias.value, c.num_experts_per_tok,
          c.routed_scaling_factor, c.norm_topk_prob, self.router_dtype)
      self.sow("intermediates", "router_scores", scores)
      self.sow("intermediates", "topk_idx", idx)
      pairs = b * t * c.num_experts_per_tok
      routed, counts = expert_lib.held_experts_ffn(
          flat, weights, idx, w_gate, w_up, w_down, c.first_expert,
          impl=self.moe_impl, rows=expert_lib.compact_rows(pairs, g, e),
          activation=c.expert_activation,
          tile=expert_lib.row_tile(pairs, g, e))
      if self.is_mutable_collection("batch_stats") and \
          not self.is_initializing():
        # The step's loads over ALL the experts from this chip's tokens,
        # and the bias's own update rule (no gradient reaches it).
        all_load = jnp.sum(jax.nn.one_hot(idx.reshape(-1), e,
                                          dtype=jnp.float32), axis=0)
        load.value = all_load
        computed.value = counts["pairs_computed"].astype(jnp.float32)
        compact.value = counts["compact"].astype(jnp.float32)
        tiled.value = counts["rows_in_tiles"].astype(jnp.float32)
        chosen.value = idx.astype(jnp.float32)
        probe_in.value = flat[:probe].astype(self.dtype)
        probe_scores.value = scores[:probe].astype(jnp.float32)
        bias.value = bias.value + c.bias_update_speed * jnp.sign(
            jnp.mean(all_load) - all_load)
    with jax.named_scope("shared_expert"):
      shared = (SwiGLU if c.expert_gated else PlainMLP)(
          width=c.shared_width, name="shared_experts", **self.options())(x)
    return routed.reshape(b, t, d) + shared


class ConvParams(nn.Module):
  """A depthwise convolution's kernel (K, channels) and bias, under one
  name (``conv1d/kernel``, ``conv1d/bias``); the arithmetic is
  ``ssd.causal_conv``'s. Initialised as the family's public code leaves a
  depthwise Conv1d: uniform within 1/sqrt(K)."""
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, taps, channels):
    bound = 1.0 / math.sqrt(taps)
    uniform = lambda key, shape, dtype: jax.random.uniform(
        key, shape, dtype, -bound, bound)
    return (self.param("kernel", uniform, (taps, channels), self.param_dtype),
            self.param("bias", uniform, (channels,), self.param_dtype))


def initial_dt_bias(c: "LMConfig"):
  """The Mamba-2 convention: a step drawn log-uniform in
  [``time_step_min``, ``time_step_max``], floored at ``time_step_floor``,
  through the inverse of softplus."""
  def init(key, shape, dtype):
    lo, hi = math.log(c.time_step_min), math.log(c.time_step_max)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                lo, hi)), c.time_step_floor)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
  return init


class Mamba2Mixer(_Options):
  """The Mamba-2 state-space mixer: ``in_proj`` to [z | xBC | dt], the
  causal depthwise convolution and SiLU over xBC, the chunked scan, the
  gated norm and ``out_proj`` (``ops/ssd.mamba_core`` has everything
  between the projections; the scan is ``ssd.scan_plan``'s choice: two
  Pallas kernels on a TPU at shapes that tile, einsums elsewhere). Its
  inside is REMATERIALISED from ``in_proj``'s output: the float32 copies
  of x, B and C, the scan's output and, in the einsums, a chunk's decay
  tables are formed again in the backward pass and never kept (a layer
  keeps 169 MB at 8,192 tokens where the tables alone are 400 MB), so
  the scan's forward runs twice a step and its backward once.
  ``jax.checkpoint`` with its barriers, not ``nn.remat(prevent_cse=
  False)``: outside a scan XLA would merge the repeat with its first
  copy and keep everything."""

  @nn.compact
  def __call__(self, u):
    c = self.cfg
    heads, p, g, n = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                      c.ssm_state_size)
    inner = heads * p
    with jax.named_scope("mamba_mixer"):
      zxbcdt = self.dense(2 * inner + 2 * g * n + heads, "in_proj")(u)
      kernel, bias = ConvParams(self.param_dtype, name="conv1d")(
          c.conv_kernel, inner + 2 * g * n)
      a_log = self.param("A_log", lambda key, shape, dtype: jnp.log(
          jnp.arange(1, shape[0] + 1, dtype=dtype)), (heads,),
                         self.param_dtype)
      d = self.param("D", nn.initializers.ones, (heads,), self.param_dtype)
      dt_bias = self.param("dt_bias", initial_dt_bias(c), (heads,),
                           self.param_dtype)
      core = functools.partial(
          ssd.mamba_core, heads=heads, head_dim=p, groups=g, state=n,
          chunk=c.chunk_size, eps=c.rms_norm_eps, scan_dtype=self.scan_dtype)
      y = jax.checkpoint(core)(zxbcdt, kernel, bias, a_log, d, dt_bias,
                               self.head_scale("norm", inner))
      # ``rescale_prenorm_residual``: the mixer's output projection starts
      # smaller by the square root of the PUBLISHED depth.
      return self.dense(c.hidden_size, "out_proj", nn.initializers.normal(
          INIT_STD / math.sqrt(c.num_hidden_layers)))(y)


# ``HyperConnection``'s initial values (not in config.json: the family's
# assumed ones, benchmarks/configs/xing4.0-29b-a4b.json ``assumed``): the
# scales of the token-dependent part (Hyper-Connections' small dynamic
# start), and the spread of the static biases of all three coefficients.
# A zero start would keep the streams equal copies of the embedding under
# random weights (every stream the same update), and a mix of equal
# streams is no mix: drawn biases part them from the first sublayer, as
# training has in a model that is measured.
HC_ALPHA_INIT = 0.01
HC_BIAS_STD = 1.0


class HyperConnection(_Options):
  """The front half of ONE sublayer's manifold-constrained
  hyper-connection, from the ``hc_mult`` streams X (B, T, n, C): the
  sublayer's input h and the coefficients, float32 (``ops/mhc.py`` has
  the arithmetic, ``mhc.mhc_pre``: two Pallas kernels on a TPU).

  ``phi`` (nC, 2n + n^2) is one matrix, its columns [pre | post | res]
  (the row-major n x n of H_res), ``bias`` one vector in the same order,
  ``alpha`` the three scales (pre, post, res).

  Returns ``mhc.Premixed``: h, h_pre, h_post, H_res and the streams as
  ``mhc.mhc_post`` takes them. Leaves in ``batch_stats`` the
  largest |row or column sum - 1| of H_res over the step's tokens
  (``sinkhorn_residual``) and a probe of the first
  ``ROUTER_PROBE_TOKENS`` tokens: their streams and their three
  coefficients (a check's way to hold the TIMED program's coefficients
  to a reference)."""

  @nn.compact
  def __call__(self, streams):
    c = self.cfg
    b, t, n, d = streams.shape
    cols = 2 * n + n * n
    phi = self.param("phi", _normal(), (n * d, cols), self.param_dtype)

    bias = self.param("bias", nn.initializers.normal(HC_BIAS_STD), (cols,),
                      self.param_dtype)
    alpha = self.param("alpha", nn.initializers.constant(HC_ALPHA_INIT),
                       (3,), self.param_dtype)
    probe = min(ROUTER_PROBE_TOKENS, b * t)
    residual = self.variable("batch_stats", "sinkhorn_residual", jnp.zeros,
                             (), jnp.float32)
    probe_in = self.variable("batch_stats", "probe_streams", jnp.zeros,
                             (probe, n, d), self.dtype)
    probe_pre = self.variable("batch_stats", "probe_pre", jnp.zeros,
                              (probe, n), jnp.float32)
    probe_post = self.variable("batch_stats", "probe_post", jnp.zeros,
                               (probe, n), jnp.float32)
    probe_res = self.variable("batch_stats", "probe_res", jnp.zeros,
                              (probe, n, n), jnp.float32)
    out = mhc.mhc_pre(streams, phi, bias, alpha, mhc.Mixing(
        c.hc_sinkhorn_iters, c.hc_eps,
        (c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max), c.rms_norm_eps,
        self.mhc_dtype), self.dtype)
    if self.is_mutable_collection("batch_stats") and \
        not self.is_initializing():
      residual.value = mhc.sinkhorn_residual(out.h_res)
      probe_in.value = streams.reshape(b * t, n, d)[:probe].astype(self.dtype)
      probe_pre.value = out.h_pre.reshape(b * t, n)[:probe]
      probe_post.value = out.h_post.reshape(b * t, n)[:probe]
      probe_res.value = out.h_res.reshape(b * t, n, n)[:probe]
    return out


def expand_streams(x, n: int):
  """The embedding copied into ``n`` streams: (B, T, C) -> (B, T, n, C)."""
  return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def merge_streams(streams):
  """The streams summed (float32), before a final norm."""
  return jnp.sum(streams.astype(jnp.float32), -2)


class MixerBlock(_Options):
  """One layer of a one-mixer stack (``nemotron_h``): ``x + mixer(norm(
  x))``, the mixer of the layer's own ``kind``."""
  kind: str = ATTENTION

  @nn.compact
  def __call__(self, x, tables=None):
    self.sow("intermediates", "hidden_in", x)
    mixer = {MAMBA: Mamba2Mixer, MIXTURE: MoE, ATTENTION: GQAttention}[
        self.kind](name="mixer", **self.options())
    h = self.norm("norm")(x).astype(self.dtype)
    if self.kind == MIXTURE:
      # ONE array for the router, the probe and the experts (``Block``
      # has the reason).
      h = jax.lax.optimization_barrier(h)
    return x + (mixer(h, tables) if self.kind == ATTENTION else mixer(h)), None


class Block(_Options):
  """One decoder layer; ``(carry, None) -> (carry, None)`` for nn.scan.
  ``window``: the keys a query sees in this layer (None: all before it;
  read by the grouped-query module alone)."""
  mixture: bool = True
  window: Optional[int] = None

  @nn.compact
  def __call__(self, x, tables=None):
    """``tables``: ``rope_tables`` of the model's call (None: the
    attention module builds its own)."""
    c = self.cfg
    self.sow("intermediates", "hidden_in", x)
    if c.attention == "mla":
      attend = MLAttention(name="self_attn", **self.options())
    else:
      attend = GQAttention(name="self_attn", window=self.window,
                           **self.options())
    if self.mixture:
      feed = MoE(name="mlp", **self.options())
    else:
      feed = SwiGLU(width=c.intermediate_size, name="mlp", **self.options())
    normed = lambda name, y: self.norm(name)(y).astype(self.dtype)
    if c.hc_mult:
      # ``hc_mult`` streams: each sublayer reads a mix of them and its
      # output, with the streams themselves, is mixed back (mHC).
      for hc, sublayer in (
          ("attn_hc", lambda h: attend(normed("input_layernorm", h),
                                       tables)),
          ("mlp_hc", lambda h: feed(normed("post_attention_layernorm", h)))):
        with jax.named_scope("mhc"):
          pre = HyperConnection(name=hc, **self.options())(x)
        y = sublayer(pre.h)
        # What a check holds a sublayer to on its own (sown values are
        # kept only where a caller asks for ``intermediates``).
        self.sow("intermediates", f"{hc}_in", pre.h)
        self.sow("intermediates", f"{hc}_out", y)
        with jax.named_scope("mhc"):
          x = mhc.mhc_post(pre, y)
      return x, None
    if c.post_norms:
      # A norm on both sides of each sublayer.
      x = x + normed("post_attention_layernorm",
                     attend(normed("input_layernorm", x), tables))
      # The feed-forward's input is ONE array for all its readers: XLA
      # otherwise formed the probe the step leaves for the check from a
      # float32 copy of the residual and the router's input from the
      # bfloat16 one, two roundings of one value (router scores 4.8e-3
      # apart on the chip, a bfloat16 router's reading: PERF.md section 6,
      # PR 32).
      h = jax.lax.optimization_barrier(normed("pre_mlp_layernorm", x))
      x = x + normed("post_mlp_layernorm", feed(h))
    else:
      x = x + attend(normed("input_layernorm", x), tables)
      x = x + feed(normed("post_attention_layernorm", x))
    return x, None


class MLAMoELM(_Options):
  scan_layers: bool = True
  remat: bool = True

  @nn.compact
  def __call__(self, tokens):
    c = self.cfg
    tokens = tokens.astype(jnp.int32)
    block_cls = nn.remat(Block, prevent_cse=False) if self.remat else Block
    embed = nn.Embed(c.vocab_rows, c.hidden_size, name="embed_tokens",
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     embedding_init=_normal())
    emb = embed(tokens)
    if c.embed_scale != 1.0:
      emb = (emb.astype(jnp.float32) * c.embed_scale).astype(self.dtype)
    x = expand_streams(emb, c.hc_mult) if c.hc_mult else emb
    # The streams are summed before a final norm (Hyper-Connections).
    merged = merge_streams if c.hc_mult else (lambda streams: streams)
    # RoPE's tables, once a call of the model: every layer reads them.
    tables = rope_tables(c, tokens.shape[1], self.dtype)
    if c.layer_pattern:
      # One mixer a layer, of the layer's own kind: unrolled like any
      # stack of several kinds, nothing rematerialised but the inside of
      # a Mamba mixer, which does that itself.
      for i, kind in enumerate(c.kinds):
        x, _ = MixerBlock(kind=kind, name=f"layer_{i}",
                          **self.options())(x, tables)
    else:
      windows = c.windows
      for i in range(c.dense_layers):
        x, _ = block_cls(mixture=False, window=windows[i], name=f"dense_{i}",
                         **self.options())(x, tables)
      mixture_windows = windows[c.dense_layers:]
      if c.moe_layers and self.scan_layers and len(set(mixture_windows)) == 1:
        # One block body whatever the depth; per-layer parameters, router
        # state and sown values stack on a leading layer axis.
        x, _ = nn.scan(
            block_cls,
            variable_axes={"params": 0, "batch_stats": 0, "intermediates": 0},
            split_rngs={"params": True}, in_axes=nn.broadcast,
            length=c.moe_layers)(
                name="layers", window=mixture_windows[0],
                **self.options())(x, tables)
      else:
        # Layers of two kinds are two bodies: the held layers unrolled,
        # each traced once (a deeper stage would scan one PERIOD of the
        # pattern; the stage a chip holds is shorter than two periods).
        # They are NOT rematerialised, whatever ``remat`` says of the
        # scanned stack. Outside a scan XLA merges every operation that
        # nn.remat(prevent_cse=False) repeats with its first copy, so the
        # activations are kept under it as well (five layers at 8,192
        # tokens fit the chip); what XLA does not merge would run twice:
        # the routed path's rounds loop wherever a block's backward pass
        # reads the feed-forward's output (a post-norm block's
        # ``post_mlp_layernorm`` does), and the gather of the chosen
        # experts' scores: 16 ms of the trinity-mini cell's 272 ms step,
        # for 0.08 GiB MORE memory (PERF.md section 6, PR 35). The stack's
        # form decides, and that follows from the configuration's
        # ``layer_types``: no flag.
        for i, window in enumerate(mixture_windows):
          x, _ = Block(window=window, name=f"layer_{i}",
                       **self.options())(x, tables)
    self.sow("intermediates", "hidden_last", x)
    h_main = self.norm("norm_f" if c.layer_pattern else "norm")(
        merged(x)).astype(self.dtype)
    h_mtp = None
    if c.num_nextn_predict_layers:
      with jax.named_scope("mtp"):
        # Position i joins the last block's output at i (before the
        # final norm) with the embedding of token i+1, and predicts
        # token i+2. The last position has no next token in the input:
        # it takes a rolled one and the loss leaves it out.
        nxt = jnp.roll(emb, -1, axis=1)
        joined = jnp.concatenate(
            [self.norm("mtp_enorm")(nxt), self.norm("mtp_hnorm")(
                merged(x))], -1).astype(self.dtype)
        m = self.dense(c.hidden_size, "mtp_eh_proj")(joined)
        if c.hc_mult:
          # The MTP block expands its own input and sums its own streams.
          m = expand_streams(m, c.hc_mult)
        m, _ = block_cls(name="mtp_block", **self.options())(m, tables)
        self.sow("intermediates", "hidden_mtp", m)
        h_mtp = self.norm("mtp_norm")(merged(m)).astype(self.dtype)
    head = self.param("lm_head", _normal(), (c.hidden_size, c.vocab_rows),
                      self.param_dtype)
    # No logits here: ``hidden`` is the pair (main, MTP or None), and both
    # losses go through the one kernel (ops/fused_loss.py).
    return fused_loss_lib.FusedLMHead(hidden=(h_main, h_mtp),
                                      kernel=head), None


def moe_counters(batch_stats, cfg: LMConfig):
  """The step's expert-layer counters from the loads the step left in
  ``batch_stats`` (every mixture layer, the MTP block included), as one
  float32 vector in the order of ``MOE_COUNTERS``."""
  leaves = jax.tree_util.tree_flatten_with_path(batch_stats)[0]
  load = jnp.concatenate([v.reshape(-1, v.shape[-1]) for path, v in leaves
                          if path[-1].key == "load"])
  held = load[:, cfg.first_expert:cfg.first_expert + cfg.experts_held]
  pairs_here = jnp.sum(held)
  total = lambda key: sum(jnp.sum(v) for path, v in leaves
                          if path[-1].key == key)
  return jnp.stack([
      pairs_here, pairs_here - total("pairs_computed"),
      jnp.max(jnp.max(held, -1) / jnp.maximum(jnp.mean(held, -1), 1e-9)),
      total("compact"), total("rows_in_tiles")])


MOE_COUNTERS = ("pairs_routed_here", "pairs_dropped", "load_max_over_mean",
                "compact_layers", "rows_in_tiles")


def mhc_counters(batch_stats):
  """The step's hyper-connection counter from ``batch_stats``: the
  largest |row or column sum - 1| of any sublayer's H_res, as a (1,)
  float32 vector (``MHC_COUNTERS``)."""
  leaves = jax.tree_util.tree_flatten_with_path(batch_stats)[0]
  return jnp.max(jnp.stack([jnp.max(v) for path, v in leaves
                            if path[-1].key == "sinkhorn_residual"]))[None]


MHC_COUNTERS = ("sinkhorn_residual",)


def _attention_lines(core):
  """``stats["attention"]``'s log lines: one kind of core, its fields;
  two, a line a kind."""
  return [
      f"attention core{kind}: {one['core_layers']} layer(s), " + (
          "backward in {backward_kernel_passes} kernel pass a layer; "
          "scores in blocks of {block}, the forward fetching "
          "{block_q} queries x {block_kv} keys a grid step, "
          "{block_kv_dkv} keys held across a backward sweep, "
          "{dq_partials} partial dq summed outside the kernel"
          if one["backward_kernel_passes"] else
          "materialised scores (no kernel off the TPU)").format(**one)
      + ("; window {window}, {query_heads} query heads over "
         "{key_heads} key heads".format(**one) if "key_heads" in one else "")
      + ("; {tiles_visited} of {tiles_causal} causal score tiles "
         "visited".format(**one) if "tiles_causal" in one else "")
      for kind, one in ([("", core)] if "core_layers" in core else
                        [(f" ({k})", v) for k, v in core.items()])]


def _rotary_lines(sites):
  """``stats["rotary"]``'s log line: every call site in one."""
  return ["attention rotary: " + "; ".join(
      "{0} in {layers} layer(s): {heads} head(s) of {head_dim}, "
      "{rot_dims} rotated, {1}, {implementation}{2}, "
      "{bytes_read_and_written_per_call} bytes read and written a call, "
      "{residual_bytes_per_layer} kept a layer".format(
          name, "normed" if one["normed"] else "no norm",
          " over {block_rows} positions of {block_heads} heads a "
          "block".format(**one)
          if one["block_rows"] else "", **one)
      for name, one in sites.items())]


class MLAMoELMModel(model_lib.Model):
  """Decoder-only mixture-of-experts LM over synthetic token streams."""

  # The hyper-connections' coefficient dtype (``_Options.mhc_dtype``), read
  # by the module and by ``stats["mhc"]`` alike; bfloat16 is the check's
  # lower-precision control.
  mhc_dtype = jnp.float32

  def __init__(self, params=None):
    # bfloat16 needs no loss scale.
    super().__init__("mla_moe_lm", batch_size=2,
                     learning_rate=PEAK_LEARNING_RATE, fp16_loss_scale=1,
                     params=params)
    get = lambda key, default: (getattr(params, key, None)
                                if params is not None else None) or default
    self.seq_len = get("seq_len", DEFAULT_SEQ_LEN)
    self._share = (get("lm_config", DEFAULT_CONFIG),
                   get("lm_layers_held", None), get("lm_layer_shards", 1),
                   get("lm_layer_shard_index", 0),
                   get("lm_first_layer_held", 0),
                   get("lm_vocab_shards", None),
                   get("lm_head_shards", 1))
    self._cfg = None
    self.scanned_param_prefixes = ("layers",)

  def get_learning_rate(self, global_step, batch_size):
    """The family's warm-up to its peak (--init_learning_rate overrides
    it with a constant: learning_rate.make_learning_rate_fn)."""
    del batch_size
    step = jnp.asarray(global_step, jnp.float32)
    return self.learning_rate * jnp.minimum(1.0, (step + 1) / WARMUP_STEPS)

  def step_counters(self, batch_stats):
    """train_step.make_step_fns: counters that ride the step's metrics
    (``MOE_COUNTERS``, then ``MHC_COUNTERS`` where there are streams)."""
    counters = moe_counters(batch_stats, self.cfg)
    if self.cfg.hc_mult:
      counters = jnp.concatenate([counters, mhc_counters(batch_stats)])
    return counters

  @property
  def cfg(self) -> LMConfig:
    """The configuration with the share applied, read at first use (the
    build, inside the run's trace session) under one live span."""
    if self._cfg is None:
      with tracing.active().span("setup", "load_lm_config",
                                 config=self._share[0]):
        self._cfg = load_lm_config(*self._share)
    return self._cfg

  @cfg.setter
  def cfg(self, value: LMConfig) -> None:
    self._cfg = value

  def state_blocks(self, dtype):
    """The run's static blocks, each ``stats[key]`` with its log lines,
    stated through the run's trace where the module's type is known
    (``RunTrace.state``: once, though the build makes a training and an
    evaluation module). ``moe`` is the share (experts and vocabulary
    rows held, a round's rows and their row tile) with what a round's
    combine gathers, and from a table of which type; the others are the
    ``*_stats`` below, stated where the configuration has them."""
    c = self.cfg
    tokens = self.get_batch_size() * self.seq_len
    rows = self._round_rows(c)
    moe = {"experts_held": c.experts_held, "vocab_rows": c.vocab_rows,
           "buffer_rows": rows, "row_tile": self._row_tile(c),
           "expert_matrices": c.expert_matrices,
           "combine": expert_lib.combine_stats(
               tokens, c.num_experts_per_tok, rows, c.hidden_size, dtype)}
    say = lambda line: lambda block: [line.format(**block)]
    blocks = (
        ("moe", moe, lambda moe: [self._share_line(c), (
            "moe combine: {gathers} gathers of {0} rows a round "
            "({rows_gathered} rows) from the products' own {table_dtype} "
            "rows ({table_bytes} bytes), weighted on the token side"
        ).format(tokens, **moe["combine"])]),
        ("attention", self.attention_core_stats(), _attention_lines),
        ("lm_head", self.lm_head_stats(dtype), say(
            "lm head: {losses} loss(es), float32 softmax over {chunk} "
            "positions at a time; the kernel's gradient from products over "
            "{rows_per_weight_grad_product} rows a loss, "
            "{weight_grad_passes} passes over its float32 accumulator a "
            "step, {dlogits_bytes_held} bytes of dlogits held")),
        ("rotary", self.rotary_stats(dtype), _rotary_lines),
        ("mamba", self.mamba_stats(dtype), say(
            "mamba scan: {layers} layer(s), {heads} heads of {head_dim} over "
            "{groups} groups, state {state}; {implementation} (kernel share "
            "{kernel_share}), {chunks_per_sequence} chunks of {chunk} "
            "positions a sequence, {carried_state_bytes_per_layer} bytes of "
            "carried state and {residual_bytes_per_layer} kept for the "
            "backward pass a layer")),
        ("mhc", self.mhc_stats(dtype), say(
            "mhc: {streams} residual streams of {hidden_size}, "
            "{sublayers} sublayers behind a hyper-connection, Sinkhorn "
            "{sinkhorn_iters} iterations (eps {eps}); {stream_bytes} bytes "
            "of streams a sublayer; {implementation} (kernel share "
            "{kernel_share})")))
    trace = tracing.active()
    for key, block, lines in blocks:
      if block:
        trace.state(key, block, *lines(block))

  def _share_line(self, c: LMConfig) -> str:
    last = c.first_layer + c.layers_held - 1
    held = (f"{c.mamba_layers} Mamba-2, {c.moe_layers} mixture, "
            f"{len(c.attention_windows)} attention, one mixer a layer"
            if c.layer_pattern else
            f"{c.dense_layers} dense, {c.moe_layers} mixture, "
            f"{c.num_nextn_predict_layers} MTP module(s)")
    heads = (f"heads {c.first_head}-{c.first_head + c.heads_held - 1} of "
             f"{c.num_attention_heads} (of {c.head_shards} over the "
             "heads); " if c.head_shards > 1 else "")
    return (
        f"mla_moe_lm share: {c.layers_held} of {c.num_hidden_layers} "
        f"layers ({c.first_layer}-{last}: {held}); chip "
        f"{c.shard_index} of {c.shards} per layer: {heads}experts "
        f"{c.first_expert}-{c.first_expert + c.experts_held - 1} of "
        f"{c.n_routed_experts} ({c.expert_matrices} matrices an expert); "
        f"of {c.vocab_shards} over the vocabulary: rows "
        f"0-{c.vocab_rows - 1} of {c.vocab_size}; sequence length "
        f"{self.seq_len}; no exchange")

  def attention_core_stats(self):
    """The run's ``stats["attention"]``: what the core of every attention
    layer (the MTP block's too) runs, as the attention module will ask
    for it when the step is traced: from the shapes and the backend, so
    it cannot vary by step. Latent attention has one kind of core, whose
    fields are the table; grouped-query attention has up to two,
    ``{"window": ..., "full": ...}``, each with its window, its heads
    and the score tiles (block x block, the forward's and the backward's
    grids together, a head and a sequence) that its kernels VISIT beside
    those of the causal half at the same tiling: the block skip as a
    number, equal for a full layer (``sequence.plan_tiles``)."""
    c = self._cfg
    t = self.seq_len
    off_tpu = jax.default_backend() != "tpu"

    def core(head_dim, window, layers):
      plan = sequence_lib.flash_plan(t, t, head_dim, min(ATTN_BLOCK, t),
                                     cpu_fallback=off_tpu, window=window)
      return plan, dict(dataclasses.asdict(plan), core_layers=layers)
    if c.attention == "mla":
      return core(c.qk_head_dim, None,
                  c.layers_held + c.num_nextn_predict_layers)[1]
    out = {}
    for kind, window in (("window", c.sliding_window), ("full", None)):
      layers = sum(w == window for w in c.attention_windows)
      if not layers:
        continue
      plan, out[kind] = core(c.head_dim, window, layers)
      out[kind].update(window=window, query_heads=c.num_attention_heads,
                       key_heads=c.num_key_value_heads)
      if plan.backward_kernel_passes:
        out[kind].update(
            tiles_visited=sequence_lib.plan_tiles(t, t, plan, window),
            tiles_causal=sequence_lib.plan_tiles(t, t, plan))
    return out

  def mhc_stats(self, dtype):
    """The run's static ``stats["mhc"]``: the streams, the sublayers
    behind a hyper-connection, the bytes of the streams one sublayer
    reads and what the mixing runs at this job's shapes
    (``mhc.mhc_stats``: the plan, from the shapes, the streams' and the
    coefficients' dtypes and the backend); what ``counter_blocks``
    adds to over the timed steps. None where the configuration has one
    stream."""
    c = self.cfg
    if not c.hc_mult:
      return None
    tokens = self.get_batch_size() * self.seq_len
    return dict({"streams": c.hc_mult, "hidden_size": c.hidden_size,
                 "sublayers": c.sublayers,
                 "sinkhorn_iters": c.hc_sinkhorn_iters, "eps": c.hc_eps,
                 "stream_bytes": tokens * c.hc_mult * c.hidden_size *
                 jnp.dtype(dtype).itemsize},
                **mhc.mhc_stats(tokens, c.hc_mult, c.hidden_size, dtype,
                                self.mhc_dtype))

  def counter_stats(self, rows):
    """``rows`` (steps, len(MOE_COUNTERS)) of ``moe_counters`` as the
    run's ``stats["moe"]``."""
    c = self.cfg
    layer_steps = rows.shape[0] * (c.moe_layers + c.num_nextn_predict_layers)
    moe = {"pairs_routed_here": float(rows[:, 0].mean()),
           "pairs_dropped": float(rows[:, 1].sum()),
           "load_max_over_mean": float(rows[:, 2].max()),
           "compact_share": float(rows[:, 3].sum()) / layer_steps,
           "steps": int(rows.shape[0])}
    if rows.shape[1] > 4:      # rows of the four counters before the layout's
      # The rows the grouped products' row tiles covered for a pair routed
      # here, every mixture layer of every step, and its log line.
      from kf_benchmarks_tpu.utils import log as log_util
      moe["rows_per_pair"] = float(rows[:, 4].sum() /
                                   max(rows[:, 0].sum(), 1))
      log_util.log_fn(
          f"moe layout: row tile {self._row_tile(c)}, rows per pair "
          f"{moe['rows_per_pair']:.4g} (every held expert's rows from a "
          "tile boundary where the tile is shorter than the round)")
    return moe

  def counter_blocks(self, rows):
    """What the timed steps add to the run's blocks: ``moe``
    (``counter_stats``) and, where there are streams, ``mhc``'s largest
    Sinkhorn residual of any step."""
    blocks = {"moe": self.counter_stats(rows)}
    if self.cfg.hc_mult:
      blocks["mhc"] = {
          "sinkhorn_residual": float(rows[:, len(MOE_COUNTERS)].max())}
    return blocks

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32):
    del nclass, phase_train, data_format
    on_tpu = jax.default_backend() == "tpu"
    # The Pallas kernels (flash attention, the grouped product) run on a
    # TPU only, and their outputs carry no vma type, which the step's
    # shard_map checker refuses (transformer_lm.py has the whole story).
    self.relax_shard_map_vma = on_tpu
    self.state_blocks(dtype)
    return MLAMoELM(cfg=self.cfg, dtype=dtype, param_dtype=param_dtype,
                    moe_impl="gmm" if on_tpu else "ragged_dot",
                    mhc_dtype=self.mhc_dtype)

  def _round_rows(self, c: LMConfig) -> int:
    """The sorted rows of one round of the routed path at this job's
    batch (what ``MoE`` asks ``held_experts_ffn`` for)."""
    return expert_lib.compact_rows(
        self.get_batch_size() * self.seq_len * c.num_experts_per_tok,
        c.experts_held, c.n_routed_experts)

  def _row_tile(self, c: LMConfig) -> int:
    """The grouped products' row tile at this job's batch, from which
    every held expert's rows start (``expert.row_tile``)."""
    return expert_lib.row_tile(
        self.get_batch_size() * self.seq_len * c.num_experts_per_tok,
        c.experts_held, c.n_routed_experts)

  def lm_head_stats(self, dtype):
    """The run's ``stats["lm_head"]``: the fused head's schedule at this
    job's shapes (``fused_loss.weight_grad_stats``; two losses where
    the configuration has an MTP module)."""
    c = self.cfg
    return fused_loss_lib.weight_grad_stats(
        self.get_batch_size(), self.seq_len, self.loss_chunk, c.vocab_rows,
        2 if c.num_nextn_predict_layers else 1, dtype)

  def rotary_stats(self, dtype):
    """The run's ``stats["rotary"]``: the rotary stage's call sites
    (``rotary_sites``) with what each runs at this job's shapes
    (``rotary.stage_stats``: the plan, from the shapes and the backend)."""
    return {name: rotary.stage_stats(self.get_batch_size(), self.seq_len,
                                     dtype=dtype, **site)
            for name, site in rotary_sites(self.cfg).items()}

  def mamba_stats(self, dtype):
    """The run's ``stats["mamba"]``: the state-space scan at this job's
    shapes (``ssd.scan_stats``: the plan, from the shapes and the
    backend); None where no layer held has one."""
    c = self.cfg
    if not c.mamba_layers:
      return None
    return ssd.scan_stats(
        self.get_batch_size(), self.seq_len, c.mamba_num_heads,
        c.mamba_head_dim, c.n_groups, c.ssm_state_size, c.chunk_size,
        c.mamba_layers, dtype)

  def logits_bytes(self, batch_shape, dtype) -> int:
    """The logits of the whole batch over the vocabulary rows held: two
    losses go through one head kernel (ops/fused_loss.py), and neither
    may hold them."""
    return (int(batch_shape[0]) * int(batch_shape[1]) * self.cfg.vocab_rows
            * jnp.dtype(dtype).itemsize)

  def get_input_shapes(self, subset):
    n = self.get_batch_size()
    return [[n, self.seq_len], [n, self.seq_len]]

  def get_input_data_types(self, subset):
    return [jnp.int32, jnp.int32]

  def get_synthetic_inputs(self, rng, nclass):
    del nclass
    tokens = jax.random.randint(
        rng, (self.get_batch_size(), self.seq_len), 0, self.cfg.vocab_rows,
        jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)

  # The f32 softmax temporaries live one chunk of the sequence at a
  # time: 512 positions, and never more than an eighth of the sequence.
  # The head kernel's products do NOT go chunk by chunk: they take the
  # chunks in groups of fused_loss.WEIGHT_GRAD_ROWS rows (2,048: four
  # chunks of one sequence, two of two), because each product of the
  # kernel's gradient reads and writes its float32 accumulator whole
  # and at a chunk's 512 rows that traffic, not the arithmetic, sets its
  # time (4 passes a step at 8,192 positions where a chunk a product
  # makes 16). What a group holds is bfloat16 rows, so this constant
  # alone bounds the float32.
  LOSS_CHUNK = 512

  @property
  def loss_chunk(self) -> int:
    return min(self.LOSS_CHUNK, max(1, self.seq_len // 8))

  def losses(self, heads, labels):
    """(main loss, MTP loss or None) through one head kernel."""
    with jax.named_scope("lm_head"):
      return fused_loss_lib.fused_softmax_xent_pair(
          heads.hidden, heads.kernel, labels, chunk_size=self.loss_chunk)

  def loss_function(self, build_network_result, labels):
    heads, _ = build_network_result.logits
    main, mtp = self.losses(heads, labels)
    return main if mtp is None else main + MTP_LOSS_WEIGHT * mtp

  def accuracy_function(self, build_network_result, labels):
    heads, _ = build_network_result.logits
    return fused_loss_lib.fused_top_k_accuracy(
        heads.hidden[0], heads.kernel, labels, chunk_size=self.loss_chunk)


def create_mla_moe_lm_model(params=None):
  return MLAMoELMModel(params=params)
