"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``): the forward pass, its loss and gradients in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product
otherwise runs in bfloat16 passes).

No kernels, no chunks, no sorting: the state-space layer is the
SEQUENTIAL recurrence, one position at a time (``lax.scan`` over t, the
state a (heads, P, N) array, elementwise arithmetic only, so nothing of
it is a matrix product that a precision setting could change); the
experts are a loop over those held, each applied densely to every token
under a one-hot weight; band masks, scores and logits are materialised,
K and V repeated over their query heads. It imports NOTHING of
``kf_benchmarks_tpu``; it is loaded by file path, by
``named_checks/nemotron-3-nano-30b-a3b_reference_agrees.py`` on the chip
and by ``tests/test_nemotron_h_lm.py`` on the CPU.
``references/nemotron-3-nano-30b-a3b.md`` says what a reader of this
directory's README would look for.

It follows the published config.json
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, file
``config.json``)
and the two public descriptions its keys name by convention: Mamba-2
(arXiv:2405.21060) and Nemotron-H (arXiv:2504.03624); what neither
spells out is listed under ``assumed`` in
``benchmarks/configs/nemotron-3-nano-30b-a3b.json``. ``cfg`` is that
config.json as a dict (any dict with its keys: the tests pass tiny
widths). x is (B, T, D); RMSNorm (``norm_eps``) has a learned scale; no
biases but the convolution's.

* Layer l of kind ``hybrid_override_pattern[l]``: ``h += mixer_l(norm_l(h))``,
  ONE mixer a layer. After the last layer ``norm_f``, an untied head,
  one cross-entropy (mean over B x T). No embedding scale.
* ``M``, Mamba-2 (H heads of P, G groups, state N, K taps):
  ``[z | xBC | dt] = in_proj(u)`` (widths H P | H P + 2 G N | H);
  ``xBC <- silu(conv(xBC))``, each channel over its own last K positions
  plus a bias; ``[x | B | C] = xBC``; ``dt <- softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head, h reading group h // (H / G):
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y <- RMSNorm_groups(y * silu(z))`` (the gate BEFORE the norm, the
  norm over each of the G groups of H P / G channels, one scale);
  ``out_proj(y)``.
* ``*``, attention: q (T, heads, hd), k, v (T, kv heads, hd) by three
  projections; NO rotation, no head norm, no gate; causal; query head n
  reads key head ``n // (heads / kv heads)``; scores / sqrt(hd);
  ``o_proj``.
* ``E``, mixture: ``s = sigmoid(x @ router)`` over all
  ``n_routed_experts``; the ``num_experts_per_tok`` experts are the top
  of ``s + bias``; weights = the chosen experts' own s over their sum
  (+1e-20) times ``routed_scaling_factor``; output =
  ``down_s(relu(up_s(x))^2)`` (the shared expert) + the weighted
  ``down_e(relu(up_e(x))^2)`` of the chosen experts HELD here.

Departures, each deliberate:

1. THE SHARE. ``share = {"layers_held", "first_layer", "shards",
   "shard_index", "vocab_shards"}``: published layers ``first_layer ..
   first_layer + layers_held - 1`` are computed, each of the kind the
   pattern gives its own index; the embedding feeds the first of them.
   Each mixture layer routes over all ``n_routed_experts`` and adds the
   outputs of experts ``shard_index * E/shards .. + E/shards - 1`` only;
   what the absent experts would add is left out, and that partial
   result goes on to the next layer. Embedding and head hold the first
   ``vocab_size / vocab_shards`` rows of the vocabulary
   (``vocab_shards`` absent: ``shards``): ids, labels, logits and the
   loss are over that slice. ``first_layer`` 0, ``layers_held =
   num_hidden_layers`` and both counts 1 is the whole model.
2. FORCED CHOICES. ``chosen`` (optional, per mixture layer) replaces the
   top-k SELECTION by the given expert ids; the weights are still this
   reference's own sigmoid scores at those ids. With random weights a
   near-tie in ``score + bias`` flips on rounding, and the comparison is
   of the arithmetic, not of tie-breaking.
3. BLOCKS, memory devices only, which the mathematics does not see:
   ``scan_block`` positions of the recurrence and ``query_block``
   queries of the scores are differentiated a block at a time, the block
   formed again in the backward pass (8,192 saved states of 64 heads are
   17 GB; 8,192 x 8,192 scores of 32 heads 8.6 GB).

PARAMETERS. ``from_program(tree, cfg, share)`` is the ONE mapping from the
program's parameter tree (``models/mla_moe_lm.py``; flax names, the held
layers ``layer_<i>``, each a ``norm`` and a ``mixer``) to this file's:

    embed (V, D); lm_head (D, V); norm_f (D,)
    layers: [ {norm (D,),
               in_proj (D, 2 H P + 2 G N + H), conv_kernel (K, H P + 2 G N),
               conv_bias, A_log, D, dt_bias (H,), gate_norm (H P,),
               out_proj (H P, D)}                                  (M)
           or {norm, q_proj (D, heads*hd), k_proj, v_proj (D, kv*hd),
               o_proj (heads*hd, D)}                               (*)
           or {norm, router (D, E), experts_up (G, D, F),
               experts_down (G, F, D), shared: {up_proj, down_proj}} ]  (E)

Matrices are (in, out), as the program stores them. ``select_bias`` (per
mixture layer) is the router's selection bias
(``e_score_correction_bias``), which is state and not a parameter.
"""

import jax
import jax.numpy as jnp

MAMBA, MIXTURE, ATTENTION = "M", "E", "*"


def _highest(fn):
  def wrapped(*args, **kwargs):
    with jax.default_matmul_precision("highest"):
      return fn(*args, **kwargs)
  wrapped.__name__, wrapped.__doc__ = fn.__name__, fn.__doc__
  return wrapped


def held(cfg, share):
  """(first expert, experts held, vocabulary rows held)."""
  g = cfg["n_routed_experts"] // share["shards"]
  return (share["shard_index"] * g, g,
          cfg["vocab_size"] // share.get("vocab_shards", share["shards"]))


def layer_kinds(cfg, share):
  """The kind of each layer held: a string over ``M``, ``E``, ``*``."""
  first = share.get("first_layer", 0)
  return cfg["hybrid_override_pattern"][first:first + share["layers_held"]]


def from_program(tree, cfg, share):
  """The program's parameter tree in this file's names (see above)."""
  def layer(p, kind):
    m = p["mixer"]
    out = {"norm": p["norm"]["scale"]}
    if kind == MAMBA:
      out.update(in_proj=m["in_proj"]["kernel"],
                 conv_kernel=m["conv1d"]["kernel"],
                 conv_bias=m["conv1d"]["bias"], A_log=m["A_log"], D=m["D"],
                 dt_bias=m["dt_bias"], gate_norm=m["norm"]["scale"],
                 out_proj=m["out_proj"]["kernel"])
    elif kind == ATTENTION:
      out.update({name: m[name]["kernel"] for name in (
          "q_proj", "k_proj", "v_proj", "o_proj")})
    else:
      out.update(router=m["router"], experts_up=m["experts_up"],
                 experts_down=m["experts_down"],
                 shared={k: m["shared_experts"][k]["kernel"]
                         for k in ("up_proj", "down_proj")})
    return out
  kinds = layer_kinds(cfg, share)
  return {"embed": tree["embed_tokens"]["embedding"],
          "lm_head": tree["lm_head"], "norm_f": tree["norm_f"]["scale"],
          "layers": [layer(tree[f"layer_{i}"], kind)
                     for i, kind in enumerate(kinds)]}


def rms_norm(x, scale, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def relu2(x):
  return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, dt, a, b, c, scan_block=None):
  """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T``, ``y_t = S_t c_t``,
  one position at a time from S = 0: y (B, T, H, P) for x (B, T, H, P),
  dt (B, T, H), a (H,), b and c (B, T, H, N) (already one a head).
  ``scan_block``: the positions differentiated at a time (None: all)."""
  batch, t, heads, p = x.shape

  def step(s, at):
    x_t, dt_t, b_t, c_t = at
    s = (jnp.exp(dt_t * a)[..., None, None] * s +
         (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
    return s, jnp.sum(s * c_t[..., None, :], -1)

  def run(s, block):
    return jax.lax.scan(step, s, block)
  state = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
  by_time = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
  if scan_block is None or scan_block >= t:
    y = run(state, by_time)[1]
  else:
    assert t % scan_block == 0, (t, scan_block)
    blocks = tuple(v.reshape((t // scan_block, scan_block) + v.shape[1:])
                   for v in by_time)
    y = jax.lax.scan(jax.checkpoint(run), state, blocks)[1]
    y = y.reshape((t,) + y.shape[2:])
  return jnp.moveaxis(y, 0, 1)


def conv(xbc, kernel, bias):
  """Causal depthwise convolution: channel ch at position t sees
  ``xbc[t - K + 1 .. t, ch]`` (zeros before the sequence)."""
  k, t = kernel.shape[0], xbc.shape[1]
  padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
  return bias + sum(padded[:, j:j + t] * kernel[j] for j in range(k))


@_highest
def mamba(cfg, p, u, scan_block=None):
  """The Mamba-2 mixer over u (B, T, D)."""
  b, t, _ = u.shape
  h, hp, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                 cfg["n_groups"], cfg["ssm_state_size"])
  inner, bc = h * hp, g * n
  zxbcdt = u @ p["in_proj"]
  z = zxbcdt[..., :inner]
  xbc = jax.nn.silu(conv(zxbcdt[..., inner:2 * inner + 2 * bc],
                         p["conv_kernel"], p["conv_bias"]))
  dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * bc:] + p["dt_bias"])
  x = xbc[..., :inner].reshape(b, t, h, hp)
  per_head = lambda v: jnp.repeat(v.reshape(b, t, g, n), h // g, axis=2)
  y = recurrence(x, dt, -jnp.exp(p["A_log"]),
                 per_head(xbc[..., inner:inner + bc]),
                 per_head(xbc[..., inner + bc:]), scan_block)
  y = (y + p["D"][:, None] * x).reshape(b, t, inner) * jax.nn.silu(z)
  grouped = y.reshape(b, t, g, inner // g)
  y = (grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, -1, keepdims=True)
                               + cfg["norm_eps"])).reshape(b, t, inner)
  return (y * p["gate_norm"]) @ p["out_proj"]


@_highest
def attention(cfg, p, x, query_block=None):
  """Grouped-query attention over x (B, T, D), causal, materialised
  scores; ``query_block`` queries at a time (None: all)."""
  b, t, _ = x.shape
  h, g, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
              cfg["head_dim"])
  q = (x @ p["q_proj"]).reshape(b, t, h, hd)
  k, v = (jnp.repeat((x @ p[name]).reshape(b, t, g, hd), h // g, axis=2)
          for name in ("k_proj", "v_proj"))

  def rows_of(q_rows, k, v, start):
    seen = (start + jnp.arange(q_rows.shape[1]))[:, None] >= \
        jnp.arange(t)[None, :]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(
        jnp.float32(hd))
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                           -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
  if query_block:
    rows_of = jax.checkpoint(rows_of, static_argnums=(3,))
  step = query_block or t
  outs = [rows_of(q[:, start:start + step], k, v, start)
          for start in range(0, t, step)]
  return jnp.concatenate(outs, 1).reshape(b, t, h * hd) @ p["o_proj"]


def mlp(p, x):
  return relu2(x @ p["up_proj"]) @ p["down_proj"]


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
    y = y + w_token[:, None] * (
        relu2(flat @ p["experts_up"][j]) @ p["experts_down"][j])
  return y.reshape(b, t, d), scores, idx


@_highest
def mixture(cfg, share, p, select_bias, x, chosen=None):
  """The mixture mixer over x (B, T, D): the held experts' part of the
  routed sum, plus the shared expert. Returns (y, scores, idx)."""
  y, scores, idx = routed(cfg, share, p, select_bias, x, chosen)
  return y + mlp(p["shared"], x), scores, idx


@_highest
def block(cfg, share, p, x, kind, select_bias=None, chosen=None,
          query_block=None, scan_block=None):
  """One layer of ``kind``. Returns (y, scores or None, idx or None)."""
  h = rms_norm(x, p["norm"], cfg["norm_eps"])
  scores = idx = None
  if kind == MAMBA:
    m = mamba(cfg, p, h, scan_block)
  elif kind == ATTENTION:
    m = attention(cfg, p, h, query_block)
  else:
    m, scores, idx = mixture(cfg, share, p, select_bias, h, chosen)
  return x + m, scores, idx


@_highest
def embed(cfg, params, tokens):
  del cfg
  return params["embed"][tokens]


@_highest
def head(cfg, params, hidden_last, labels):
  """(loss, logits) from the last block's output, logits materialised."""
  logits = rms_norm(hidden_last, params["norm_f"],
                    cfg["norm_eps"]) @ params["lm_head"]
  ll = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), labels[..., None],
                           -1)[..., 0]
  return -jnp.mean(ll), logits


@_highest
def forward(cfg, share, params, select_bias, tokens, labels, chosen=None,
            query_block=None, scan_block=None):
  """The whole forward pass. ``select_bias`` and ``chosen`` are lists
  with one entry per mixture layer (``chosen`` None: this reference's own
  top-k). Returns a dict: ``loss``, ``hidden`` (the input of every
  layer), ``hidden_last``, ``scores`` and ``idx`` (per mixture layer),
  ``logits``."""
  x = embed(cfg, params, tokens)
  hidden, scores, idx = [], [], []
  m = 0
  for p, kind in zip(params["layers"], layer_kinds(cfg, share)):
    hidden.append(x)
    mix = kind == MIXTURE
    x, s, i = block(cfg, share, p, x, kind, select_bias[m] if mix else None,
                    chosen[m] if mix and chosen is not None else None,
                    query_block, scan_block)
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
  one (E,) vector per mixture layer, in layer order."""
  names = sorted((k for k in batch_stats if k.startswith("layer_")),
                 key=lambda k: int(k[len("layer_"):]))
  return [batch_stats[k]["mixer"]["select_bias"] for k in names]
