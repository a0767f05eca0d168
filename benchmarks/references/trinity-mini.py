"""Plain reference of Trinity-Mini (``model_type: afmoe``): the forward
pass, its loss and gradients in straightforward ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")`` (on a TPU a float32
product otherwise runs in bfloat16 passes).

No scan over layers, no kernels, no sorting (a loop over the experts
held, each applied densely to every token under a one-hot
weight), materialised band masks, scores and logits, K and V repeated
over their query heads. It imports NOTHING of ``kf_benchmarks_tpu``; it
is loaded by file path, by
``named_checks/trinity-mini_reference_agrees.py`` on the chip and by
``tests/test_afmoe_lm.py`` on the CPU. ``references/trinity-mini.md``
says what a reader of this directory's README would look for.

It follows the published config.json
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json);
what no key of it spells out is from the family's public modelling code
and is listed under ``assumed`` in ``benchmarks/configs/trinity-mini.json``.
``cfg`` is that config.json as a dict (any dict with its keys: the tests
pass tiny widths). x is (B, T, D); RMSNorm has a learned scale; no
biases anywhere.

* Embedding: ``h = embed[tokens] * sqrt(hidden_size)`` (``mup_enabled``).
* Layer l: ``h += post_attention_layernorm(attn_l(input_layernorm(h)))``;
  ``h += post_mlp_layernorm(mlp_l(pre_mlp_layernorm(h)))``.
* ``attn_l``: q (T, H, hd), k, v (T, G, hd), gate (T, H x hd) by four
  projections of x; RMSNorm over the hd of each head of q and of k (one
  scale of hd each). ``layer_types[l] == "sliding_attention"``: RoPE
  (``rotate_half``, all hd dimensions) on q and k, and query i sees key j
  iff ``0 <= i - j < sliding_window``. ``"full_attention"``: NO
  rotation, causal. Query head n reads key head ``n // (H / G)``; scores
  / sqrt(hd); ``out = o_proj(core.reshape(T, H x hd) * sigmoid(gate))``.
* ``mlp_l``, l < ``num_dense_layers``: SwiGLU of ``intermediate_size``.
  Else ``s = sigmoid(x @ router)`` over all ``num_experts``; the
  ``num_experts_per_tok`` experts are the top of ``s + expert_bias``;
  weights = the chosen experts' own s over their sum (+1e-20)
  (``route_norm``) times ``route_scale``; output = shared
  SwiGLU(``moe_intermediate_size`` x ``num_shared_experts``)(x) + the
  weighted SwiGLU_e(x) of the chosen experts HELD here.
* Final RMSNorm, an untied head, one cross-entropy (mean over B x T).

Departures, each deliberate:

1. THE SHARE. ``share = {"layers_held", "first_layer", "shards",
   "shard_index"}``: published layers ``first_layer .. first_layer +
   layers_held - 1`` are computed, each of the kind ``layer_types`` gives
   its own index and dense iff that index is below ``num_dense_layers``;
   the embedding feeds the first of them. Each mixture layer routes over
   all ``num_experts`` and adds the outputs of experts ``shard_index *
   E/shards .. + E/shards - 1`` only; what the absent experts would add
   is left out, and that partial result goes on to the next layer.
   Embedding and head hold the first ``vocab_size / shards`` rows of the
   vocabulary: ids, labels, logits and the loss are over that slice.
   ``first_layer`` 0, ``layers_held = num_hidden_layers`` and ``shards``
   1 is the whole model.
2. FORCED CHOICES. ``chosen`` (optional, per mixture layer) replaces the
   top-k SELECTION by the given expert ids; the weights are still this
   reference's own sigmoid scores at those ids. With random weights a
   near-tie in ``score + bias`` flips on rounding, and the comparison is
   of the arithmetic, not of tie-breaking; the share of tokens whose own
   choice differs is reported by the check.

PARAMETERS. ``from_program(tree, cfg, share)`` is the ONE mapping from the
program's parameter tree (``models/mla_moe_lm.py``; flax names, the
held dense layers ``dense_<i>``, the mixture layers ``layer_<i>``, or
stacked on a leading axis under ``layers`` where they are of one kind)
to this file's:

    embed (V, D); lm_head (D, V); norm (D,)
    layers: [ {input_layernorm, post_attention_layernorm,
               pre_mlp_layernorm, post_mlp_layernorm (D,),
               q_proj (D, H*hd), k_proj, v_proj (D, G*hd),
               gate_proj (D, H*hd), q_norm, k_norm (hd,), o_proj (H*hd, D),
               mlp: {gate_proj, up_proj, down_proj}               (dense)
                 or {router (D, E), experts_gate (G, D, F), experts_up,
                     experts_down (G, F, D),
                     shared: {gate_proj, up_proj, down_proj}} } ]  (mixture)

Matrices are (in, out), as the program stores them. ``select_bias`` (per
mixture layer) is the router's balance bias (``expert_bias``), which is
state and not a parameter.
"""

import jax
import jax.numpy as jnp


def _highest(fn):
  def wrapped(*args, **kwargs):
    with jax.default_matmul_precision("highest"):
      return fn(*args, **kwargs)
  wrapped.__name__, wrapped.__doc__ = fn.__name__, fn.__doc__
  return wrapped


def held(cfg, share):
  """(first expert, experts held, vocabulary rows held)."""
  g = cfg["num_experts"] // share["shards"]
  return share["shard_index"] * g, g, cfg["vocab_size"] // share["shards"]


def layer_kinds(cfg, share):
  """Per layer held: (window or None, is it a mixture layer)."""
  first = share.get("first_layer", 0)
  return [(cfg["sliding_window"]
           if cfg["layer_types"][i] == "sliding_attention" else None,
           i >= cfg["num_dense_layers"])
          for i in range(first, first + share["layers_held"])]


def from_program(tree, cfg, share):
  """The program's parameter tree in this file's names (see above)."""
  def attn(p):
    a = p["self_attn"]
    out = {name: p[name]["scale"] for name in (
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm")}
    out.update({name: a[name]["kernel"] for name in (
        "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")})
    out.update(q_norm=a["q_norm"]["scale"], k_norm=a["k_norm"]["scale"])
    return out
  def swiglu(p):
    return {k: p[k]["kernel"] for k in ("gate_proj", "up_proj",
                                        "down_proj")}
  def mixture(p):
    m = p["mlp"]
    return dict(attn(p), mlp={
        "router": m["router"], "experts_gate": m["experts_gate"],
        "experts_up": m["experts_up"], "experts_down": m["experts_down"],
        "shared": swiglu(m["shared_experts"])})
  layers = []
  i = 0
  while f"dense_{i}" in tree:
    layers.append(dict(attn(tree[f"dense_{i}"]),
                       mlp=swiglu(tree[f"dense_{i}"]["mlp"])))
    i += 1
  if "layers" in tree:     # one kind: stacked on a leading layer axis
    depth = tree["layers"]["input_layernorm"]["scale"].shape[0]
    for j in range(depth):
      layers.append(mixture(jax.tree.map(lambda x: x[j], tree["layers"])))
  j = 0
  while f"layer_{j}" in tree:   # two kinds: unrolled
    layers.append(mixture(tree[f"layer_{j}"]))
    j += 1
  kinds = layer_kinds(cfg, share)
  assert [mix for _, mix in kinds] == ["router" in p["mlp"] for p in layers]
  return {"embed": tree["embed_tokens"]["embedding"],
          "lm_head": tree["lm_head"], "norm": tree["norm"]["scale"],
          "layers": layers}


def rms_norm(x, scale, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
  """x (B, T, H, R): rotate pairs (i, i + R/2) by position."""
  r = x.shape[-1]
  inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
  ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
  cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
  sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
  rotated = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
  return x * cos + rotated * sin


@_highest
def attention(cfg, p, x, window=None, query_block=None):
  """Grouped-query gated attention over x (B, T, D), causal, in a layer
  of ``window`` (None: a full layer), materialised scores;
  ``query_block`` queries at a time (None: all), so that the scores of a
  long sequence fit (the one place this file recomputes anything)."""
  b, t, _ = x.shape
  h, g, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
              cfg["head_dim"])
  eps = cfg["rms_norm_eps"]
  q = rms_norm((x @ p["q_proj"]).reshape(b, t, h, hd), p["q_norm"], eps)
  k = rms_norm((x @ p["k_proj"]).reshape(b, t, g, hd), p["k_norm"], eps)
  v = (x @ p["v_proj"]).reshape(b, t, g, hd)
  if window is not None:
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
  k, v = (jnp.repeat(y, h // g, axis=2) for y in (k, v))
  def rows_of(q_rows, k, v, start):
    distance = (start + jnp.arange(q_rows.shape[1]))[:, None] - \
        jnp.arange(t)[None, :]
    seen = distance >= 0
    if window is not None:
      seen &= distance < window
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(
        jnp.float32(hd))
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
  if query_block:
    # A memory device only: differentiated, a block's scores are formed
    # again in the backward pass and not kept from the forward.
    rows_of = jax.checkpoint(rows_of, static_argnums=(3,))
  step = query_block or t
  outs = [rows_of(q[:, start:start + step], k, v, start)
          for start in range(0, t, step)]
  core = jnp.concatenate(outs, 1).reshape(b, t, h * hd)
  return (core * jax.nn.sigmoid(x @ p["gate_proj"])) @ p["o_proj"]


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
  if cfg["route_norm"]:
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
  return weights * cfg["route_scale"], chosen, scores


@_highest
def routed(cfg, share, p, select_bias, x, chosen=None):
  """The held experts' part of the routed sum over x (B, T, D), WITHOUT
  the shared expert. Returns (y, scores, idx)."""
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
  return y.reshape(b, t, d), scores, idx


@_highest
def mixture(cfg, share, p, select_bias, x, chosen=None):
  """The mixture feed-forward over x (B, T, D): the held experts' part of
  the routed sum, plus the shared expert. Returns (y, scores, idx)."""
  y, scores, idx = routed(cfg, share, p, select_bias, x, chosen)
  return y + swiglu(p["shared"], x), scores, idx


@_highest
def block(cfg, share, p, x, window=None, select_bias=None, chosen=None,
          query_block=None):
  """One decoder layer of ``window`` (``layer_kinds``). A mixture layer
  has ``router`` in its ``mlp``. Returns (y, scores or None, idx or
  None)."""
  eps = cfg["rms_norm_eps"]
  a = attention(cfg, p, rms_norm(x, p["input_layernorm"], eps), window,
                query_block)
  x = x + rms_norm(a, p["post_attention_layernorm"], eps)
  h = rms_norm(x, p["pre_mlp_layernorm"], eps)
  scores = idx = None
  if "router" in p["mlp"]:
    m, scores, idx = mixture(cfg, share, p["mlp"], select_bias, h, chosen)
  else:
    m = swiglu(p["mlp"], h)
  return x + rms_norm(m, p["post_mlp_layernorm"], eps), scores, idx


@_highest
def embed(cfg, params, tokens):
  return params["embed"][tokens] * jnp.sqrt(jnp.float32(cfg["hidden_size"]))


@_highest
def head(cfg, params, hidden_last, labels):
  """(loss, logits) from the last block's output, logits materialised."""
  logits = rms_norm(hidden_last, params["norm"],
                    cfg["rms_norm_eps"]) @ params["lm_head"]
  ll = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), labels[..., None],
                           -1)[..., 0]
  return -jnp.mean(ll), logits


@_highest
def forward(cfg, share, params, select_bias, tokens, labels, chosen=None,
            query_block=None):
  """The whole forward pass. ``select_bias`` and ``chosen`` are lists
  with one entry per mixture layer (``chosen`` None: this reference's own
  top-k). Returns a dict: ``loss``, ``hidden`` (the input of every
  layer), ``hidden_last``, ``scores`` and ``idx`` (per mixture layer),
  ``logits``."""
  x = embed(cfg, params, tokens)
  hidden, scores, idx = [], [], []
  m = 0
  for p, (window, mix) in zip(params["layers"], layer_kinds(cfg, share)):
    hidden.append(x)
    x, s, i = block(cfg, share, p, x, window,
                    select_bias[m] if mix else None,
                    chosen[m] if mix and chosen is not None else None,
                    query_block)
    if mix:
      scores.append(s)
      idx.append(i)
      m += 1
  loss, logits = head(cfg, params, x, labels)
  return {"loss": loss, "hidden": hidden, "hidden_last": x,
          "scores": scores, "idx": idx, "logits": logits}


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
  one (E,) vector per mixture layer."""
  out = []
  if "layers" in batch_stats:
    out += list(batch_stats["layers"]["mlp"]["select_bias"])
  j = 0
  while f"layer_{j}" in batch_stats:
    out.append(batch_stats[f"layer_{j}"]["mlp"]["select_bias"])
    j += 1
  return out
