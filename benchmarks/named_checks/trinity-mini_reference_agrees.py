"""``correct`` for the trinity-mini cells: what the timed path produced,
at the timed sizes and the published widths, against the plain reference
(``benchmarks/references/trinity-mini.py``: float32, ``highest``, no
kernels, materialised band masks) at the same parameters. Built like
``glm-4.7-flash_reference_agrees.py``, whose docstring has the method in
full; what differs is said here.

After the window has closed and the memory peak has been read:

1. The trained parameters and the router state are copied to the host,
   and Adam's two moments and its count for the leaves in
   ``GRAD_LEAVES``.
2. The trained state and the timed batch go ONCE MORE through the
   window's own step program (``run.bench.timed_step``). Read back: the
   step's loss; the new first moment, from which the gradient the step
   program itself formed is ``g = (mu' - b1 mu) / (1 - b1)``; the new
   parameters; and from the router state (``batch_stats``) every token's
   chosen experts and the router's input and scores of the first 1,024
   tokens of every mixture layer. The state is then dropped to make
   room.
3. The program's own module (bfloat16 compute, the model's own loss
   code) runs the batch forward once with its sown values switched on:
   each layer's input and choices, the last hidden state, the loss: a
   SECOND object built like the timed one. Its attention module alone
   (the first window layer's, with that layer's parameters) also runs
   twice on that layer's input, once as it is and once with ONE position
   scaled by 64: what that position's key and value add to the queries
   around the far edge of its window.
4. The reference runs the one sequence with its scores in blocks of
   ``QUERY_BLOCK`` queries (8,192 x 8,192 scores of 32 heads are 8.6 GB
   in float32; a block's are recomputed in the backward pass, which the
   reference's mathematics does not see). The router alone on the timed
   program's router input; layer by layer on the second object's input
   of that layer and that object's choices; the attention of the first
   window layer on the same two inputs as in 3; end to end from the
   token ids twice, the choices forced: forward under the second
   object's choices, forward and backward (the chain rule over its own
   layer functions, one layer's activations at a time) under the TIMED
   program's, and from that gradient and the moments read back in 1
   Adam's update of each leaf.

Every number compared goes into ``run.compared`` with its limit. Each
limit stands between what this configuration reads over its seeds on the
chip and what a planted fault reads there
(``experiments/lm_precision_control.py --fault ...``; PERF.md section 6,
PR 32, has every reading beside its limit). From the TIMED program:
``pairs_dropped`` (0), ``router_scores_err``, ``choice_mismatch_share``,
``step_loss_err``, ``grad_err.<leaf>`` (one routed expert's three
matrices, the router, ``q_proj``, ``k_proj`` and ``gate_proj`` of the
first mixture layer, a window layer, which is the deepest below the
loss; ``q_proj`` of the full layer; the head's slice) and
``param_change_err``, as the glm check defines them. From the second
object: ``layer_output_err``, ``hidden_last_err``, ``loss_err``, and

* ``window_edge_err``: with W the PUBLISHED window and j the scaled
  position, the change of the attention module's output at queries
  j + W - 2, j + W - 1 (the last that sees key j) and j + W (the first
  that does not), program against reference, relative norm over the
  three rows. The reference's third row is exactly 0 and its second is
  not: a program whose window is one key longer or shorter has one of
  the three rows wholly wrong and reads 0.5 or more; bfloat16 reads a
  few percent.
"""

from __future__ import annotations

import importlib.util
import os

# Each limit between the largest reading of the program over its seeds
# on the chip and the smallest reading of a planted fault that it has to
# see (my chip runs, PR 32; PERF.md section 6 has every reading).
LIMITS = {
    "pairs_dropped": 0,
    "router_scores_err": 2e-4,
    "choice_mismatch_share": 1e-3,
    "layer_output_err": 2e-2,
    "window_edge_err": 2e-1,
    "hidden_last_err": 3e-2,
    "loss_err": 3e-4,
    "step_loss_err": 3e-4,
    "grad_err.lm_head": 3e-2,
    "grad_err.full_q_proj": 6e-2,
    "grad_err.q_proj": 6e-2,
    "grad_err.k_proj": 6e-2,
    "grad_err.gate_proj": 6e-2,
    "grad_err.router": 4e-2,
    "grad_err.expert_gate": 5e-2,
    "grad_err.expert_up": 5e-2,
    "grad_err.expert_down": 5e-2,
    "param_change_err": 1e-1,
}

# (name, path in the program's tree, index into the leaf, path in the
# reference's gradient of that layer). ``layer_0`` is the first mixture
# layer held (published layer 2, a window layer), ``layer_1`` the full
# layer.
GRAD_LEAVES = (
    ("expert_gate", ("layer_0", "mlp", "experts_gate"), (0,),
     ("mlp", "experts_gate")),
    ("expert_up", ("layer_0", "mlp", "experts_up"), (0,),
     ("mlp", "experts_up")),
    ("expert_down", ("layer_0", "mlp", "experts_down"), (0,),
     ("mlp", "experts_down")),
    ("router", ("layer_0", "mlp", "router"), (), ("mlp", "router")),
    ("q_proj", ("layer_0", "self_attn", "q_proj", "kernel"), (), ("q_proj",)),
    ("k_proj", ("layer_0", "self_attn", "k_proj", "kernel"), (), ("k_proj",)),
    ("gate_proj", ("layer_0", "self_attn", "gate_proj", "kernel"), (),
     ("gate_proj",)),
    ("full_q_proj", ("layer_1", "self_attn", "q_proj", "kernel"), (),
     ("q_proj",)),
    ("lm_head", ("lm_head",), (), None),
)
FIRST_MIXTURE = "layer_0"
QUERY_BLOCK = 1024
EDGE_SCALE = 64.0


def _reference():
  path = os.path.join(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))), "references", "trinity-mini.py")
  spec = importlib.util.spec_from_file_location("_trinity_reference", path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


def _get(tree, path):
  for key in path:
    tree = tree[key]
  return tree


def _adam_state(opt_state):
  """Adam's own state (``mu``, ``nu``, ``count``) out of the optimizer's."""
  import jax
  has_mu = lambda s: hasattr(s, "mu")
  return next(s for s in jax.tree.leaves(opt_state, is_leaf=has_mu)
              if has_mu(s))


def _rel(got, want):
  import jax.numpy as jnp
  got, want = got.astype(jnp.float32), want.astype(jnp.float32)
  return float(jnp.linalg.norm((got - want).ravel()) /
               jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30))


def check(run, devices):
  import jax
  import jax.numpy as jnp
  import numpy as np
  del devices
  ref = _reference()
  bench = run.bench
  model = bench.model
  c = model.cfg
  # The reference reads the PUBLISHED configuration; the share says what
  # of it is held.
  cfg = dict(run.config)
  cfg.update(run.config.get("published", {}))
  share = {"layers_held": c.layers_held, "first_layer": c.first_layer,
           "shards": c.shards, "shard_index": c.shard_index}
  kinds = ref.layer_kinds(cfg, share)
  mixture_names = [f"layer_{i}" for i in range(c.moe_layers)]
  layer_names = [f"dense_{i}" for i in range(c.dense_layers)] + mixture_names
  first_mix = c.dense_layers
  values = {"pairs_dropped": (run.stats.get("moe") or {}).get(
      "pairs_dropped", float("nan"))}

  # 1. To the host: parameters, router state, Adam's state of the leaves.
  state = run.stats["state"]
  row0 = lambda tree: jax.tree.map(lambda x: np.asarray(x[0]), tree)
  params = row0(state.params)
  stats = row0(state.batch_stats)
  pick = lambda tree, path, index: np.asarray(
      _get(tree, path)[(0,) + index], np.float32)
  leaves = lambda tree: {name: pick(tree, path, index)
                         for name, path, index, _ in GRAD_LEAVES}
  adam = _adam_state(state.opt_state)
  mu_old, nu_old = leaves(adam.mu), leaves(adam.nu)
  count = int(np.asarray(adam.count).reshape(-1)[0])
  p_old = leaves(state.params)
  images, labels = bench.timed_batch

  # 2. Once more through the window's own step program.
  new_state, metrics = bench.timed_step(state, images, labels)
  step_loss = float(metrics["base_loss"])
  b1, b2, eps = (float(bench.params.adam_beta1),
                 float(bench.params.adam_beta2),
                 float(bench.params.adam_epsilon))
  mu_new = leaves(_adam_state(new_state.opt_state).mu)
  grads = {name: (mu_new[name] - b1 * mu_old[name]) / (1 - b1)
           for name in mu_new}
  p_new = leaves(new_state.params)
  stepped = row0(new_state.batch_stats)
  of_mixtures = lambda tree, key: [tree[name]["mlp"][key]
                                   for name in mixture_names]
  chosen = [x.astype(np.int32) for x in of_mixtures(stepped, "chosen")]
  probe_in = of_mixtures(stepped, "router_probe_in")
  probe_scores = of_mixtures(stepped, "router_probe_scores")
  tokens, labels = np.asarray(images), np.asarray(labels)
  # Room for the reference: the state, the batch and the step program's
  # own reservation go.
  run.stats["state"] = bench.timed_step = bench.timed_batch = None
  del state, new_state, adam, metrics, images, stepped
  jax.clear_caches()

  # 3. The second object: the program's own forward, sown values on.
  module = model.make_module(None, True, dtype=bench.compute_dtype,
                             param_dtype=bench.param_dtype)

  @jax.jit
  def program(p, s, tok, lab):
    (heads, _), sown = module.apply({"params": p, "batch_stats": s}, tok,
                                    mutable=["intermediates"])
    return model.losses(heads, lab)[0], sown["intermediates"]
  tree = jax.tree.map(jnp.asarray, params)
  loss, sown = program(tree, stats, tokens, labels)
  f32 = lambda x: jnp.asarray(x, jnp.float32)
  layer_in = [sown[name]["hidden_in"][0] for name in layer_names]
  layer_out = layer_in[1:] + [sown["hidden_last"][0]]
  n = tokens.shape[1]
  per_seq = lambda x, b: x.reshape((tokens.shape[0], n) + x.shape[1:])[b]
  sown_chosen = [sown[name]["mlp"]["topk_idx"][0] for name in mixture_names]

  # ... and its attention module alone around the far edge of a window.
  from kf_benchmarks_tpu.models import mla_moe_lm
  at = layer_names.index(FIRST_MIXTURE)
  window_ref = kinds[at][0]
  edge = None
  if window_ref is not None and window_ref + 2 < n:
    j = (n - window_ref) // 2
    rows = np.asarray([j + window_ref - 2, j + window_ref - 1,
                       j + window_ref])
    attend = mla_moe_lm.GQAttention(window=c.windows[at], **module.options())
    h = layer_in[at][:1]
    h_scaled = h.at[:, j].multiply(EDGE_SCALE)
    own = jax.jit(lambda p, x: attend.apply({"params": p}, x))
    p_attn = tree[FIRST_MIXTURE]["self_attn"]
    edge = (f32(own(p_attn, h_scaled))[:, rows] -
            f32(own(p_attn, h))[:, rows], h, h_scaled, rows)

  # 4. The reference. First the router alone, on the timed program's
  # own router input.
  p_ref = ref.from_program(tree, cfg, share)
  bias = [jnp.asarray(b) for b in ref.bias_from_program(stats)]
  blocks = p_ref["layers"]
  route = jax.jit(lambda w, b, x: ref.route(cfg, w, b, x))
  scores_err, mismatched, probed = 0.0, 0, 0
  for m, p in enumerate(blocks[first_mix:]):
    _, own, s = route(p["mlp"]["router"], bias[m], f32(probe_in[m]))
    scores_err = max(scores_err,
                     float(jnp.max(jnp.abs(probe_scores[m] - s))))
    theirs = chosen[m][:own.shape[0]]
    mismatched += int(jnp.sum(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), -1)))
    probed += own.shape[0]
  values.update(router_scores_err=scores_err,
                choice_mismatch_share=mismatched / max(probed, 1))

  if edge is not None:
    attention = jax.jit(lambda p, x: ref.attention(
        cfg, p, x, window_ref, QUERY_BLOCK))
    got, h, h_scaled, rows = edge
    want = (attention(blocks[at], f32(h_scaled))[:, rows] -
            attention(blocks[at], f32(h))[:, rows])
    values["window_edge_err"] = _rel(got, want)

  # Each layer on the second object's input of it, one sequence at a
  # time. A layer is its own jit: the window is static.
  def layer_fn(i):
    window = kinds[i][0]
    return lambda p, x, b, ch: ref.block(cfg, share, p, x, window, b, ch,
                                         QUERY_BLOCK)[0]
  block = [jax.jit(layer_fn(i)) for i in range(len(blocks))]
  layer_err = 0.0
  for b in range(tokens.shape[0]):
    for i, p in enumerate(blocks):
      m = i - first_mix
      out = block[i](p, f32(layer_in[i][b:b + 1]),
                     bias[m] if m >= 0 else None,
                     per_seq(sown_chosen[m], b) if m >= 0 else None)
      layer_err = max(layer_err, _rel(f32(layer_out[i][b:b + 1]), out))
  values["layer_output_err"] = layer_err

  # End to end, forward and backward by the chain rule over the
  # reference's own layer functions, the timed program's choices forced.
  # A block of queries is recomputed in the backward pass, so that one
  # block's scores are held at a time.
  block_bwd = [jax.jit(lambda cot, p, h, b, ch, fn=layer_fn(i): jax.vjp(
      lambda p, h: fn(p, h, b, ch), p, h)[1](cot))
               for i in range(len(blocks))]
  head_fn = lambda p, h, y: ref.head(cfg, p, h, y)[0]
  head_fwd = jax.jit(head_fn)
  head_bwd = jax.jit(lambda cot, p, h, y: jax.vjp(
      lambda p, h: head_fn(p, h, y), p, h)[1](cot))
  embed = jax.jit(lambda p, tok: ref.embed(cfg, p, tok))
  g_ref = {name: 0.0 for name, *_ in GRAD_LEAVES}
  batch = tokens.shape[0]
  head_p = {"lm_head": p_ref["lm_head"], "norm": p_ref["norm"]}

  def forward(b, choices):
    """Sequence b from its token ids under ``choices``: each layer's
    input, the last hidden state and the loss."""
    tok, lab = tokens[b:b + 1], labels[b:b + 1]
    xs, x = [], embed({"embed": p_ref["embed"]}, tok)
    for i, p in enumerate(blocks):
      xs.append(x)
      m = i - first_mix
      x = block[i](p, x, bias[m] if m >= 0 else None,
                   per_seq(choices[m], b) if m >= 0 else None)
    return xs, x, float(head_fwd(head_p, x, lab))

  # The second object's numbers under ITS choices (the two programs'
  # choices differ on the few tokens whose 8th and 9th scores tie within
  # bfloat16 rounding of the router's input): forward alone.
  sown_loss = hidden_err = 0.0
  for b in range(batch):
    _, x, l = forward(b, sown_chosen)
    sown_loss += l / batch
    hidden_err = max(hidden_err,
                     _rel(f32(sown["hidden_last"][0][b:b + 1]), x))
  # The timed program's under its own: forward and backward.
  want_loss = 0.0
  for b in range(batch):
    xs, x, l = forward(b, chosen)
    want_loss += l / batch
    g_head, g = head_bwd(jnp.float32(1.0 / batch), head_p, x,
                         labels[b:b + 1])
    g_ref["lm_head"] += g_head["lm_head"]
    for i in range(len(blocks) - 1, first_mix - 1, -1):
      m = i - first_mix
      g_p, g = block_bwd[i](g, blocks[i], xs[i], bias[m],
                            per_seq(chosen[m], b))
      for name, path, index, in_ref in GRAD_LEAVES:
        if path[0] == layer_names[i]:
          g_ref[name] += _get(g_p, in_ref)[index or ...]
      del g_p
  values.update(
      hidden_last_err=hidden_err,
      loss_err=abs(float(loss) - sown_loss) / sown_loss,
      step_loss_err=abs(step_loss - want_loss) / want_loss)
  # Adam's update of each leaf from the reference's gradient and the
  # state read back before the step (optax.scale_by_adam's form), at the
  # rate the configuration states.
  lr = float(run.kwargs["init_learning_rate"])
  change_err = 0.0
  for name, *_ in GRAD_LEAVES:
    g = np.asarray(g_ref[name], np.float32)
    values[f"grad_err.{name}"] = _rel(jnp.asarray(grads[name]), g)
    mu_hat = (b1 * mu_old[name] + (1 - b1) * g) / (1 - b1 ** (count + 1))
    nu_hat = (b2 * nu_old[name] + (1 - b2) * g * g) / (
        1 - b2 ** (count + 1))
    update = -lr * mu_hat / (np.sqrt(nu_hat) + eps)
    change_err = max(change_err, _rel(jnp.asarray(p_new[name] - p_old[name]),
                                      jnp.asarray(update)))
  values["param_change_err"] = change_err

  failures = []
  for name, value in values.items():
    limit = LIMITS[name]
    run.compared[name] = {"value": float(value), "limit": limit}
    if not value <= limit:       # a nan fails
      failures.append(f"{name} = {value:.3g}, over its limit {limit} "
                      "(reference: benchmarks/references/trinity-mini.py)")
  return failures
