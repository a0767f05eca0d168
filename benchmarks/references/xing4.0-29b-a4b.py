"""Plain reference of Xing4.0-29B-A4B (``model_type: xing4_0``): the
forward pass, both losses and their gradients in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product otherwise runs in bfloat16 passes).

No scan over layers, no remat, no kernels, no sorting (a loop over the
experts held, each applied densely to every token under a one-hot
weight), materialised scores and logits, Sinkhorn-Knopp as its
iterations written out and differentiated by autodiff. It imports
NOTHING of ``kf_benchmarks_tpu``; it is loaded by file path, by
``named_checks/xing4.0-29b-a4b_reference_agrees.py`` on the chip and by
``tests/test_xing_lm.py`` on the CPU.

It follows the published description: config.json of
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B (the DeepSeek-V3
block, arXiv:2412.19437, with YaRN, arXiv:2309.00071, in DeepSeek-V3's
form) and the residual path of "mHC: Manifold-Constrained
Hyper-Connections" (DeepSeek-AI, December 2025) on Hyper-Connections
(Zhu et al., ICLR 2025). ``cfg`` is that config.json as a dict (any
dict with its keys: the tests pass tiny widths). Each token carries
``n = hc_mult`` streams X (n, C); around each sublayer F (attention with
``input_layernorm``, the feed-forward with ``post_attention_layernorm``):

    x^     = RMSNorm(vec(X))   (over n C, eps rms_norm_eps, no scale)
    h_pre  = sigmoid(alpha_pre  x^ phi_pre  + b_pre)
    h_post = 2 sigmoid(alpha_post x^ phi_post + b_post)
    H_res  = SinkhornKnopp(exp(clamp(alpha_res mat(x^ phi_res) + b_res,
                                     clamp_min, clamp_max)))
    y      = F(sum_i h_pre[i] X[i])
    X'[i]  = sum_j H_res[i, j] X[j] + h_post[i] y

Departures and choices, each deliberate (the configuration's file,
``benchmarks/configs/xing4.0-29b-a4b.json`` ``assumed``, has the source
of each):

1. THE SHARE. ``share = {"layers_held", "shards", "shard_index"}``: the
   layers given are computed; each mixture layer routes over all
   ``n_routed_experts`` and adds the outputs of the experts
   ``shard_index * E/shards .. + E/shards - 1`` only; attention computes
   the heads its parameters hold (a block of them: ``q_b_proj``,
   ``kv_b_proj`` and ``o_proj`` are given as that block's columns and
   rows); what the absent experts and heads would add is left out, and
   that partial result goes on. Embedding and head hold the first
   ``vocab_size / shards`` rows. The whole model is ``shards = 1`` with
   every head given.
2. FORCED CHOICES. ``chosen`` (optional, per mixture layer) replaces the
   top-k SELECTION by the given expert ids (a near-tie flips on
   rounding; the comparison is of the arithmetic).
3. The streams: the embedding copied into each; summed before the final
   ``norm`` and before the MTP module's ``hnorm``; the MTP block expands
   its own input into n streams and sums them before its ``norm``.
   ``phi`` is ONE (nC, 2n + n^2) matrix, its columns [pre | post | res]
   (H_res row-major), ``bias`` one vector in that order, ``alpha`` the
   three scales (pre, post, res). Each Sinkhorn iteration divides by the
   row sums, then by the column sums, ``hc_eps`` in each denominator.
4. The MTP module at position i joins the streams' sum after the last
   block at i with the embedding of token i+1, order [embedding, hidden],
   and predicts token i+2; the last position is left out of the MTP
   loss. Total loss = main + 0.3 x MTP (arXiv:2412.19437 section 2.2).
5. RoPE pairs dimension i with i + R/2 (``rotate_half``) at YaRN's
   frequencies; the scores' scale is (nope + rope)^-1/2 x (0.1 x
   mscale_all_dim x ln factor + 1)^2; cos and sin times mscale /
   mscale_all_dim (1 here). ``rope_scaling`` None: plain RoPE and scale.

PARAMETERS. ``from_program(tree, cfg, share)`` is the ONE mapping from the
program's parameter tree (``models/mla_moe_lm.py``; flax names, mixture
layers stacked on a leading axis under ``layers``) to this file's: the
glm reference's names, and per layer ``attn_hc`` and ``mlp_hc``, each
``{phi, bias, alpha}``. Matrices are (in, out), as the program stores
them. ``select_bias`` is router state, not a parameter.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

MTP_LOSS_WEIGHT = 0.3


def _highest(fn):
  def wrapped(*args, **kwargs):
    with jax.default_matmul_precision("highest"):
      return fn(*args, **kwargs)
  wrapped.__name__, wrapped.__doc__ = fn.__name__, fn.__doc__
  return wrapped


def held(cfg, share):
  """(first expert, experts held, vocabulary rows held)."""
  g = cfg["n_routed_experts"] // share["shards"]
  return share["shard_index"] * g, g, cfg["vocab_size"] // share["shards"]


def from_program(tree, cfg, share):
  """The program's parameter tree in this file's names (see above)."""
  del share
  def hc(p):
    return {"phi": p["phi"], "bias": p["bias"], "alpha": p["alpha"]}
  def attn(p):
    a = p["self_attn"]
    return {
        "input_layernorm": p["input_layernorm"]["scale"],
        "post_attention_layernorm": p["post_attention_layernorm"]["scale"],
        "attn_hc": hc(p["attn_hc"]), "mlp_hc": hc(p["mlp_hc"]),
        "q_a_proj": a["q_a_proj"]["kernel"],
        "q_a_layernorm": a["q_a_layernorm"]["scale"],
        "q_b_proj": a["q_b_proj"]["kernel"],
        "kv_a_proj_with_mqa": a["kv_a_proj_with_mqa"]["kernel"],
        "kv_a_layernorm": a["kv_a_layernorm"]["scale"],
        "kv_b_proj": a["kv_b_proj"]["kernel"],
        "o_proj": a["o_proj"]["kernel"]}
  def swiglu(p):
    return {k: p[k]["kernel"] for k in ("gate_proj", "up_proj",
                                        "down_proj")}
  def dense(p):
    return dict(attn(p), mlp=swiglu(p["mlp"]))
  def mixture(p):
    m = p["mlp"]
    return dict(attn(p), mlp={
        "router": m["router"], "experts_gate": m["experts_gate"],
        "experts_up": m["experts_up"], "experts_down": m["experts_down"],
        "shared": swiglu(m["shared_experts"])})
  layers = []
  i = 0
  while f"dense_{i}" in tree:
    layers.append(dense(tree[f"dense_{i}"]))
    i += 1
  if "layers" in tree:     # stacked on a leading layer axis
    depth = tree["layers"]["input_layernorm"]["scale"].shape[0]
    for j in range(depth):
      layers.append(mixture(jax.tree.map(lambda x: x[j], tree["layers"])))
  out = {"embed": tree["embed_tokens"]["embedding"],
         "lm_head": tree["lm_head"], "norm": tree["norm"]["scale"],
         "layers": layers}
  if cfg["num_nextn_predict_layers"]:
    out["mtp"] = {"enorm": tree["mtp_enorm"]["scale"],
                  "hnorm": tree["mtp_hnorm"]["scale"],
                  "eh_proj": tree["mtp_eh_proj"]["kernel"],
                  "block": mixture(tree["mtp_block"]),
                  "norm": tree["mtp_norm"]["scale"]}
  return out


def rms_norm(x, scale, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mscale(factor, m):
  """YaRN's attention temperature (DeepSeek-V3's ``yarn_get_mscale``)."""
  return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(cfg):
  """The rotary pairs' inverse frequencies: plain RoPE's, or YaRN's blend
  of them with those divided by its factor over the pairs between the
  ones that turn ``beta_fast`` and ``beta_slow`` times in the original
  context."""
  r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
  i = np.arange(r // 2, dtype=np.float64)
  base = theta ** (-2.0 * i / r)
  sc = cfg.get("rope_scaling")
  if not sc:
    return jnp.asarray(base, jnp.float32)
  orig = sc["original_max_position_embeddings"]
  pair = lambda turns: r * math.log(orig / (turns * 2 * math.pi)) / (
      2 * math.log(theta))
  low = max(math.floor(pair(sc["beta_fast"])), 0)
  high = min(math.ceil(pair(sc["beta_slow"])), r - 1)
  if low == high:
    high += 0.001
  m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
  return jnp.asarray((1 - m) * base / sc["factor"] + m * base, jnp.float32)


def softmax_scale(cfg):
  scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
  sc = cfg.get("rope_scaling")
  if sc and sc.get("mscale_all_dim"):
    scale *= mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
  return scale


def rope(cfg, x):
  """x (B, T, H, R): rotate pairs (i, i + R/2) by position."""
  ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq(
      cfg)[None]
  sc = cfg.get("rope_scaling")
  amp = (mscale(sc["factor"], sc.get("mscale", 1)) /
         mscale(sc["factor"], sc.get("mscale_all_dim", 0)) if sc else 1.0)
  cos = amp * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
  sin = amp * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
  r = x.shape[-1]
  rotated = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
  return x * cos + rotated * sin


@_highest
def attention(cfg, p, x):
  """Latent attention over x (B, T, D) for the heads ``p`` holds, causal,
  materialised scores; v at its own head size."""
  b, t, _ = x.shape
  nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
  h = p["q_b_proj"].shape[1] // (nope + rot)
  eps = cfg["rms_norm_eps"]
  q = rms_norm(x @ p["q_a_proj"], p["q_a_layernorm"], eps) @ p["q_b_proj"]
  q = q.reshape(b, t, h, nope + rot)
  kv_a = x @ p["kv_a_proj_with_mqa"]
  latent, k_rot = kv_a[..., :cfg["kv_lora_rank"]], kv_a[...,
                                                        cfg["kv_lora_rank"]:]
  kv = rms_norm(latent, p["kv_a_layernorm"], eps) @ p["kv_b_proj"]
  kv = kv.reshape(b, t, h, nope + vd)
  q = jnp.concatenate([q[..., :nope], rope(cfg, q[..., nope:])], -1)
  k_rot = rope(cfg, k_rot[:, :, None, :])
  k = jnp.concatenate([kv[..., :nope],
                       jnp.broadcast_to(k_rot, (b, t, h, rot))], -1)
  v = kv[..., nope:]
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(cfg)
  causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
  probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), -1)
  out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * vd)
  return out @ p["o_proj"]


def swiglu(p, x):
  return (jax.nn.silu(x @ p["gate_proj"]) * (x @ p["up_proj"])) @ p[
      "down_proj"]


@_highest
def route(cfg, router, select_bias, x, chosen=None):
  """(weights (N, k), idx (N, k), scores (N, E)) for tokens x (N, D)."""
  scores = jax.nn.sigmoid(x @ router)
  if chosen is None:
    _, chosen = jax.lax.top_k(scores + select_bias,
                              cfg["num_experts_per_tok"])
  weights = jnp.take_along_axis(scores, chosen, -1)
  if cfg["norm_topk_prob"]:
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
  return weights * cfg["routed_scaling_factor"], chosen, scores


@_highest
def mixture(cfg, share, p, select_bias, x, chosen=None):
  """The mixture feed-forward over x (B, T, D): the held experts' part of
  the routed sum, plus the shared expert. Returns (y, scores, idx)."""
  b, t, d = x.shape
  flat = x.reshape(b * t, d)
  first, count, _ = held(cfg, share)
  weights, idx, scores = route(cfg, p["router"], select_bias, flat, chosen)
  y = jnp.zeros_like(flat)
  for j in range(count):   # dense: every token through every held expert
    w_token = jnp.sum(jnp.where(idx == first + j, weights, 0.0), -1)
    out = (jax.nn.silu(flat @ p["experts_gate"][j]) *
           (flat @ p["experts_up"][j])) @ p["experts_down"][j]
    y = y + w_token[:, None] * out
  y = y + swiglu(p["shared"], flat)
  return y.reshape(b, t, d), scores, idx


def sinkhorn(m, iters, eps):
  """``iters`` Sinkhorn-Knopp iterations over the last two axes of the
  positive matrices ``m``: rows, then columns."""
  for _ in range(iters):
    m = m / (jnp.sum(m, -1, keepdims=True) + eps)
    m = m / (jnp.sum(m, -2, keepdims=True) + eps)
  return m


@_highest
def hc_coefficients(cfg, p, streams):
  """(h_pre (.., n), h_post (.., n), H_res (.., n, n)) of one sublayer's
  hyper-connection from the streams (.., n, C)."""
  n = cfg["hc_mult"]
  x = streams.reshape(streams.shape[:-2] + (-1,))
  xhat = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) +
                           cfg["rms_norm_eps"])
  z = xhat @ p["phi"]
  alpha, bias = p["alpha"], p["bias"]
  h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
  h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
  logits = alpha[2] * z[..., 2 * n:] + bias[2 * n:]
  logits = jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                    cfg["mhc_h_res_clamp_max"])
  h_res = sinkhorn(jnp.exp(logits).reshape(logits.shape[:-1] + (n, n)),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
  return h_pre, h_post, h_res


@_highest
def hyper(cfg, p, streams, sublayer):
  """One sublayer behind its hyper-connection: the streams after it."""
  h_pre, h_post, h_res = hc_coefficients(cfg, p, streams)
  y = sublayer(jnp.einsum("btn,btnc->btc", h_pre, streams))
  return (jnp.einsum("btij,btjc->btic", h_res, streams) +
          h_post[..., None] * y[:, :, None, :])


@_highest
def block(cfg, share, p, streams, select_bias=None, chosen=None):
  """One decoder layer over the streams (B, T, n, C). A mixture layer has
  ``router`` in its ``mlp``. Returns (streams, scores or None, idx or
  None)."""
  eps = cfg["rms_norm_eps"]
  streams = hyper(cfg, p["attn_hc"], streams, lambda x: attention(
      cfg, p, rms_norm(x, p["input_layernorm"], eps)))
  routed = {}

  def feed(x):
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    if "router" in p["mlp"]:
      y, routed["scores"], routed["idx"] = mixture(
          cfg, share, p["mlp"], select_bias, h, chosen)
      return y
    return swiglu(p["mlp"], h)
  streams = hyper(cfg, p["mlp_hc"], streams, feed)
  return streams, routed.get("scores"), routed.get("idx")


def expand(x, n):
  return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1]))


@_highest
def mtp_input(cfg, p, emb, hidden_last):
  """What enters the MTP block, as its n streams: [RMSNorm(emb of token
  i+1); RMSNorm(streams' sum i)] projected back to D, expanded."""
  eps = cfg["rms_norm_eps"]
  nxt = jnp.roll(emb, -1, axis=1)
  h = jnp.concatenate([rms_norm(nxt, p["enorm"], eps),
                       rms_norm(hidden_last.sum(-2), p["hnorm"], eps)],
                      -1) @ p["eh_proj"]
  return expand(h, cfg["hc_mult"])


@_highest
def heads(cfg, params, hidden_last, hidden_mtp, labels):
  """(main loss, MTP loss or None, logits main, logits MTP or None) from
  the streams after the last block and after the MTP block (each summed
  first), logits materialised."""
  eps = cfg["rms_norm_eps"]
  def xent(h, scale, y, weight):
    logits = rms_norm(h.sum(-2), scale, eps) @ params["lm_head"]
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), y[..., None],
                             -1)[..., 0]
    return -jnp.sum(ll * weight) / jnp.sum(weight), logits
  b, t = labels.shape
  main, logits = xent(hidden_last, params["norm"], labels, jnp.ones((b, t)))
  if hidden_mtp is None:
    return main, None, logits, None
  not_last = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t)).astype(
      jnp.float32)
  mtp, logits_mtp = xent(hidden_mtp, params["mtp"]["norm"],
                         jnp.roll(labels, -1, axis=1), not_last)
  return main, mtp, logits, logits_mtp


@_highest
def forward(cfg, share, params, select_bias, tokens, labels, chosen=None):
  """The whole forward pass. ``select_bias`` and ``chosen`` are lists
  with one entry per mixture layer, the MTP block's last (``chosen``
  None: this reference's own top-k). Returns a dict: ``loss``,
  ``main_loss``, ``mtp_loss``, ``hidden`` (the streams into every
  layer), ``hidden_last``, ``hidden_mtp`` (streams), ``scores`` and
  ``idx`` (per mixture layer, MTP last), ``logits``, ``logits_mtp``."""
  emb = params["embed"][tokens]
  x = expand(emb, cfg["hc_mult"])
  hidden, scores, idx = [], [], []
  m = 0
  for p in params["layers"]:
    hidden.append(x)
    mix = "router" in p["mlp"]
    x, s, i = block(cfg, share, p, x, select_bias[m] if mix else None,
                    chosen[m] if mix and chosen is not None else None)
    if mix:
      scores.append(s)
      idx.append(i)
      m += 1
  hidden_mtp = None
  if "mtp" in params:
    h = mtp_input(cfg, params["mtp"], emb, x)
    hidden.append(h)
    hidden_mtp, s, i = block(cfg, share, params["mtp"]["block"], h,
                             select_bias[m],
                             chosen[m] if chosen is not None else None)
    scores.append(s)
    idx.append(i)
  main, mtp, logits, logits_mtp = heads(cfg, params, x, hidden_mtp, labels)
  loss = main if mtp is None else main + MTP_LOSS_WEIGHT * mtp
  return {"loss": loss, "main_loss": main, "mtp_loss": mtp,
          "hidden": hidden, "hidden_last": x, "hidden_mtp": hidden_mtp,
          "scores": scores, "idx": idx, "logits": logits,
          "logits_mtp": logits_mtp}


def loss_and_grads(cfg, share, program_tree, select_bias, tokens, labels,
                   chosen=None):
  """(forward's dict, gradients of ``loss``) with the gradients in the
  PROGRAM's tree (``from_program`` is differentiated through)."""
  def fn(tree):
    out = forward(cfg, share, from_program(tree, cfg, share), select_bias,
                  tokens, labels, chosen)
    return out["loss"], out
  (_, out), grads = jax.value_and_grad(fn, has_aux=True)(program_tree)
  return out, grads


def bias_from_program(batch_stats):
  """The program's ``batch_stats`` collection as ``select_bias`` lists:
  one (E,) vector per mixture layer, the MTP block's last."""
  out = []
  if "layers" in batch_stats:
    out += list(batch_stats["layers"]["mlp"]["select_bias"])
  if "mtp_block" in batch_stats:
    out.append(batch_stats["mtp_block"]["mlp"]["select_bias"])
  return out
