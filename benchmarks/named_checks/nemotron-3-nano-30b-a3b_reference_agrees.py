"""``correct`` for the nemotron-3-nano-30b-a3b cells: what the timed path
produced, at the timed sizes and the published widths, against the plain
reference (``benchmarks/references/nemotron-3-nano-30b-a3b.py``: float32,
``highest``, no kernels, the state-space layers as the SEQUENTIAL
recurrence) at the same parameters. Built like
``trinity-mini_reference_agrees.py`` and
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
   SECOND object built like the timed one. Its Mamba mixer alone (the
   first layer's, with that layer's parameters) also runs twice on that
   layer's normed input, once as it is and once with ONE position scaled
   by 64: what that position adds to the positions after it.
4. The reference runs the one sequence with its recurrence in blocks of
   ``SCAN_BLOCK`` positions and its scores in blocks of ``QUERY_BLOCK``
   queries (a block is formed again in the backward pass, which the
   reference's mathematics does not see). The router alone on the timed
   program's router input; layer by layer (every kind) on the second
   object's input of that layer and that object's choices; the first
   Mamba mixer on the same two inputs as in 3; end to end from the token
   ids twice, the choices forced: forward under the second object's
   choices, forward and backward (the chain rule over its own layer
   functions, one layer's activations at a time) under the TIMED
   program's, and from that gradient and the moments read back in 1
   Adam's update of each leaf.

Every number compared goes into ``run.compared`` with its limit. Each
limit stands between what this configuration reads over its seeds on the
chip and what a planted fault reads there
(``experiments/lm_precision_control.py --fault ...``; PERF.md section 6,
PR 39, has every reading beside its limit). From the TIMED program:
``pairs_dropped`` (0), ``router_scores_err``, ``choice_mismatch_share``,
``step_loss_err``, ``grad_err.<leaf>`` (the FIRST Mamba layer's seven
kinds of leaf, which lie deepest below the loss: ``in_proj``,
``out_proj``, ``conv1d``, ``A_log``, ``dt_bias``, ``D`` and the gated
norm's scale; ``q_proj`` and ``k_proj`` of the attention layer; the first
mixture layer's router and one routed expert's ``up`` and ``down``; the
head's slice) and ``param_change_err``, as the glm check defines them.
From the second object: ``layer_output_err`` (the largest over the nine
layers, every kind), ``hidden_last_err``, ``loss_err``, and

* ``scan_carry_err``: with L the PUBLISHED chunk and j the scaled
  position (a quarter into a chunk in the middle of the sequence), the
  change of the mixer's output at ``CARRY_ROWS`` positions from j + 1
  on (the same chunk), from j + L on (the next chunk) and from j + 3 L
  on (three chunks on), program against reference (``carry_err``): each
  ROW's relative error, the MEDIAN of them over the rows inside the
  scaled position's chunk and over the rows beyond it (all that those
  see of position j came through the carried state), and the larger of
  the two medians. A program that loses the carried state reads 1 beyond
  the chunk, one that passes it a single link 0.5, one that mis-decays
  it 0.5 or more; bfloat16 products read 0.5-1%. Why rows and a median,
  and not one norm (my chip runs and the sandbox's bfloat16, PR 39): the
  scaled position's state is rank one, so a row's change is, group by
  group, a multiple of ``C_t . B_j``, and where that sum over 128 terms
  of +-10 comes out near 0 (0.14 in float32, -0.16 with bfloat16
  operands, at one row of one group in a third of the seeds) the row is
  half wrong in ANY bfloat16 program: one such row read 0.18 over a
  group of eight. And a row's error counts against at least
  ``CARRY_FLOOR`` of the row's own output: three chunks on the change
  has all but died at some seeds (a norm of 0.38 against 35 inside the
  chunk), and bfloat16's rounding of the OUTPUT alone is 0.18 of that.
"""

from __future__ import annotations

import importlib.util
import os

# Each limit between the largest reading of the program over its seeds
# on the chip and the smallest reading of a planted fault that it has to
# see (my chip runs, PR 39; PERF.md section 6 has every reading).
LIMITS = {
    "pairs_dropped": 0,
    "router_scores_err": 2e-4,
    "choice_mismatch_share": 1e-3,
    "layer_output_err": 2e-2,
    "scan_carry_err": 1e-1,
    "hidden_last_err": 3e-2,
    "loss_err": 3e-4,
    "step_loss_err": 3e-4,
    "grad_err.lm_head": 3e-2,
    "grad_err.q_proj": 5e-2,
    "grad_err.k_proj": 5e-2,
    "grad_err.router": 4e-2,
    "grad_err.expert_up": 4e-2,
    "grad_err.expert_down": 4e-2,
    "grad_err.in_proj": 4e-2,
    "grad_err.out_proj": 4e-2,
    "grad_err.conv1d": 4e-2,
    "grad_err.A_log": 6e-2,
    "grad_err.dt_bias": 6e-2,
    "grad_err.D": 5e-2,
    "grad_err.gate_norm": 4e-2,
    # Between the seeds' 0.046-0.059 and the 1 of a state left unchanged,
    # with the more room above the readings: these are no rounding of the
    # ARITHMETIC but of the float32 parameters themselves (A_log up to
    # 4.2 and dt_bias around -4 take steps of 2.2e-6, five to ten units
    # in their last place; the other cells' leaves are all near 0.02).
    "param_change_err": 2.5e-1,
}

MAMBA, MIXTURE, ATTENTION = "M", "E", "*"
# (name, the kind of layer whose FIRST held instance has the leaf, path
# below that layer in the program's tree, index into the leaf, path in
# the reference's gradient of that layer; kind None: the head).
GRAD_LEAVES = (
    ("in_proj", MAMBA, ("mixer", "in_proj", "kernel"), (), ("in_proj",)),
    ("out_proj", MAMBA, ("mixer", "out_proj", "kernel"), (), ("out_proj",)),
    ("conv1d", MAMBA, ("mixer", "conv1d", "kernel"), (), ("conv_kernel",)),
    ("A_log", MAMBA, ("mixer", "A_log"), (), ("A_log",)),
    ("dt_bias", MAMBA, ("mixer", "dt_bias"), (), ("dt_bias",)),
    ("D", MAMBA, ("mixer", "D"), (), ("D",)),
    ("gate_norm", MAMBA, ("mixer", "norm", "scale"), (), ("gate_norm",)),
    ("q_proj", ATTENTION, ("mixer", "q_proj", "kernel"), (), ("q_proj",)),
    ("k_proj", ATTENTION, ("mixer", "k_proj", "kernel"), (), ("k_proj",)),
    ("router", MIXTURE, ("mixer", "router"), (), ("router",)),
    ("expert_up", MIXTURE, ("mixer", "experts_up"), (0,), ("experts_up",)),
    ("expert_down", MIXTURE, ("mixer", "experts_down"), (0,),
     ("experts_down",)),
    ("lm_head", None, ("lm_head",), (), None),
)
QUERY_BLOCK = 1024
SCAN_BLOCK = 128
CARRY_SCALE = 64.0
CARRY_ROWS = 8
CARRY_FLOOR = 0.05


def _reference():
  path = os.path.join(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))), "references", "nemotron-3-nano-30b-a3b.py")
  spec = importlib.util.spec_from_file_location("_nemotron_reference", path)
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


def carry_rows(j, chunk, n):
  """The three groups of positions ``scan_carry_err`` reads around the
  scaled position j: after it in its own chunk, in the next chunk, three
  chunks on (None: the sequence is too short for them)."""
  starts = (j + 1, j + chunk, j + 3 * chunk)
  rows = min(CARRY_ROWS, chunk - j % chunk - 1)
  if rows < 1 or starts[-1] + rows > n:
    return None
  return [list(range(s, s + rows)) for s in starts]


def carry_err(got, want, base, groups):
  """``scan_carry_err`` from the change of the mixer's output at the rows
  read, ``got`` the program's and ``want`` the reference's (1, R, D),
  ``base`` the reference's output there without the scaled position, and
  ``groups`` the (first, end) row ranges medians are taken over."""
  import jax.numpy as jnp
  norm = lambda x: jnp.linalg.norm(x.astype(jnp.float32), axis=-1)
  err = norm(got - want) / jnp.maximum(norm(want), CARRY_FLOOR * norm(base))
  return max(float(jnp.median(err[:, lo:hi])) for lo, hi in groups)


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
           "shards": c.shards, "shard_index": c.shard_index,
           "vocab_shards": c.vocab_shards}
  kinds = ref.layer_kinds(cfg, share)
  layer_names = [f"layer_{i}" for i in range(len(kinds))]
  mixture_names = [name for name, kind in zip(layer_names, kinds)
                   if kind == MIXTURE]
  # The m-th mixture layer's place among the layers, and back.
  mixture_at = {i: m for m, i in enumerate(
      i for i, kind in enumerate(kinds) if kind == MIXTURE)}
  first_of = {kind: kinds.index(kind) for kind in set(kinds)}
  leaves_of = [(name, ((layer_names[first_of[kind]],) if kind else ()) + path,
                index, first_of.get(kind), in_ref)
               for name, kind, path, index, in_ref in GRAD_LEAVES
               if kind is None or kind in first_of]
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
                         for name, path, index, _, _ in leaves_of}
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
  of_mixtures = lambda tree, key: [tree[name]["mixer"][key]
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
  sown_chosen = [sown[name]["mixer"]["topk_idx"][0] for name in mixture_names]
  p_ref = ref.from_program(tree, cfg, share)
  blocks = p_ref["layers"]

  # ... and its first Mamba mixer alone, on that layer's normed input as
  # it is and with ONE position scaled.
  from kf_benchmarks_tpu.models import mla_moe_lm
  carry = None
  at = first_of.get(MAMBA)
  rows = None if at is None else carry_rows(
      (n // cfg["chunk_size"] // 2) * cfg["chunk_size"] +
      cfg["chunk_size"] // 4, cfg["chunk_size"], n)
  if rows is not None:
    j = rows[0][0] - 1
    flat_rows = np.asarray(sum(rows, []))
    mixer = mla_moe_lm.Mamba2Mixer(**module.options())
    h = ref.rms_norm(f32(layer_in[at][:1]), blocks[at]["norm"],
                     cfg["norm_eps"]).astype(layer_in[at].dtype)
    h_scaled = h.at[:, j].multiply(CARRY_SCALE)
    own = jax.jit(lambda p, x: mixer.apply({"params": p}, x))
    p_mixer = tree[layer_names[at]]["mixer"]
    carry = (f32(own(p_mixer, h_scaled))[:, flat_rows] -
             f32(own(p_mixer, h))[:, flat_rows], h, h_scaled, flat_rows)

  # 4. The reference. First the router alone, on the timed program's
  # own router input.
  bias = [jnp.asarray(b) for b in ref.bias_from_program(stats)]
  route = jax.jit(lambda w, b, x: ref.route(cfg, w, b, x))
  scores_err, mismatched, probed = 0.0, 0, 0
  for i, m in mixture_at.items():
    _, own, s = route(blocks[i]["router"], bias[m], f32(probe_in[m]))
    scores_err = max(scores_err,
                     float(jnp.max(jnp.abs(probe_scores[m] - s))))
    theirs = chosen[m][:own.shape[0]]
    mismatched += int(jnp.sum(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), -1)))
    probed += own.shape[0]
  values.update(router_scores_err=scores_err,
                choice_mismatch_share=mismatched / max(probed, 1))

  if carry is not None:
    mamba = jax.jit(lambda p, x: ref.mamba(cfg, p, x, SCAN_BLOCK))
    got, h, h_scaled, flat_rows = carry
    base = mamba(blocks[at], f32(h))[:, flat_rows]
    want = mamba(blocks[at], f32(h_scaled))[:, flat_rows] - base
    size = len(rows[0])
    values["scan_carry_err"] = carry_err(
        got, want, base, [(0, size), (size, len(flat_rows))])

  # Each layer on the second object's input of it, one sequence at a
  # time. ONE jit a kind of layer: the kind is static.
  def layer_fn(kind):
    return lambda p, x, b, ch: ref.block(cfg, share, p, x, kind, b, ch,
                                         QUERY_BLOCK, SCAN_BLOCK)[0]
  block = {kind: jax.jit(layer_fn(kind)) for kind in set(kinds)}

  def inputs(i, choices, b):
    """The selection bias and the choices of layer i (None for a layer
    that routes nothing)."""
    m = mixture_at.get(i)
    return (None, None) if m is None else (bias[m], per_seq(choices[m], b))
  layer_err = 0.0
  for b in range(tokens.shape[0]):
    for i, p in enumerate(blocks):
      out = block[kinds[i]](p, f32(layer_in[i][b:b + 1]),
                            *inputs(i, sown_chosen, b))
      layer_err = max(layer_err, _rel(f32(layer_out[i][b:b + 1]), out))
  values["layer_output_err"] = layer_err

  # End to end, forward and backward by the chain rule over the
  # reference's own layer functions, the timed program's choices forced.
  # A block of positions or queries is formed again in the backward pass,
  # so that one block's states or scores are held at a time.
  block_bwd = {kind: jax.jit(lambda cot, p, h, b, ch, fn=layer_fn(kind):
                             jax.vjp(lambda p, h: fn(p, h, b, ch), p, h)[1](
                                 cot)) for kind in set(kinds)}
  head_fn = lambda p, h, y: ref.head(cfg, p, h, y)[0]
  head_fwd = jax.jit(head_fn)
  head_bwd = jax.jit(lambda cot, p, h, y: jax.vjp(
      lambda p, h: head_fn(p, h, y), p, h)[1](cot))
  embed = jax.jit(lambda p, tok: ref.embed(cfg, p, tok))
  g_ref = {name: 0.0 for name, *_ in leaves_of}
  batch = tokens.shape[0]
  head_p = {"lm_head": p_ref["lm_head"], "norm_f": p_ref["norm_f"]}

  def forward(b, choices):
    """Sequence b from its token ids under ``choices``: each layer's
    input, the last hidden state and the loss."""
    tok, lab = tokens[b:b + 1], labels[b:b + 1]
    xs, x = [], embed({"embed": p_ref["embed"]}, tok)
    for i, p in enumerate(blocks):
      xs.append(x)
      x = block[kinds[i]](p, x, *inputs(i, choices, b))
    return xs, x, float(head_fwd(head_p, x, lab))

  # The second object's numbers under ITS choices (the two programs'
  # choices differ on the few tokens whose 6th and 7th scores tie within
  # bfloat16 rounding of the router's input): forward alone.
  sown_loss = hidden_err = 0.0
  for b in range(batch):
    _, x, l = forward(b, sown_chosen)
    sown_loss += l / batch
    hidden_err = max(hidden_err,
                     _rel(f32(sown["hidden_last"][0][b:b + 1]), x))
  # The timed program's under its own: forward and backward, down to the
  # first layer (the first Mamba layer's leaves lie there).
  want_loss = 0.0
  for b in range(batch):
    xs, x, l = forward(b, chosen)
    want_loss += l / batch
    g_head, g = head_bwd(jnp.float32(1.0 / batch), head_p, x,
                         labels[b:b + 1])
    g_ref["lm_head"] += g_head["lm_head"]
    for i in range(len(blocks) - 1, -1, -1):
      g_p, g = block_bwd[kinds[i]](g, blocks[i], xs[i],
                                   *inputs(i, chosen, b))
      for name, _, index, layer, in_ref in leaves_of:
        if layer == i:
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
  for name, *_ in leaves_of:
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
                      "(reference: benchmarks/references/"
                      "nemotron-3-nano-30b-a3b.py)")
  return failures
