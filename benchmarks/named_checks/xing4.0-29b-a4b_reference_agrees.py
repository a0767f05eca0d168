"""``correct`` for the xing4.0-29b-a4b cells: what the timed path
produced, at the timed sizes and the published widths, against the plain
reference (``benchmarks/references/xing4.0-29b-a4b.py``: float32,
``highest``, no kernels, Sinkhorn-Knopp as its 20 iterations) at the same
parameters and the same share (4 of 32 heads, 8 of 64 experts, 1/8 of
the vocabulary).

The steps are glm-4.7-flash's (``glm-4.7-flash_reference_agrees.py`` has
them at length): (1) the trained state to the host; (2) the trained
state and the timed batch ONCE MORE through the window's own step
program, read back: its loss, its gradient of each leaf in
``GRAD_LEAVES`` from Adam's first moment, the parameters' change, every
token's chosen experts, the router's input and scores of 1,024 tokens a
mixture layer, and, new here, the hyper-connections' probe of every
sublayer (the streams of the first 1,024 tokens and their h_pre, h_post
and H_res, which the program leaves in ``batch_stats``) and the largest
Sinkhorn residual of the step; (3) a SECOND object built like the timed
one runs the batch forward with its sown values on: each layer's input
streams, each sublayer's input and output, the last streams and both
losses; (4) the reference, on the timed program's probes, layer by layer
on the second object's inputs, and end to end from the token ids,
forward and backward by the chain rule over its own layer functions.

Every number compared goes into ``run.compared`` with its limit. Each
limit stands between what this configuration reads over its seeds on
the chip and what a planted fault reads there
(``experiments/lm_precision_control.py --fault ...``; PERF.md section
6, PR 42, has every reading beside its limit). From the TIMED program:

* ``pairs_dropped`` (limit 0), ``router_scores_err``,
  ``choice_mismatch_share``, ``step_loss_err``, ``grad_err.<leaf>``,
  ``param_change_err``: as glm's, with these leaves: one routed expert's
  three matrices, the router, ``q_b_proj`` and ``kv_b_proj`` of the
  first mixture layer (its 4 heads), the first mixture layer's two
  hyper-connections' ``phi``, the MTP module's ``eh_proj`` and the
  head's slice.
* ``hc_pre_err``, ``hc_post_err``, ``hc_res_err``: largest |program -
  reference| of an h_pre, h_post or H_res entry over the probed tokens of
  every sublayer, the same streams in. The program's coefficient path is
  float32 at HIGHEST precision: the two differ by float32 rounding of a
  14,336-long sum; the path in bfloat16 (the lower-precision control) by
  2^-9 of a value near 0.5 and more; one Sinkhorn iteration, an identity
  H_res or h_post without its factor 2 by a tenth and more.
* ``sinkhorn_residual``: the step's largest |row or column sum - 1| of
  any H_res (the program's counter, ``stats["mhc"]``): a few 1e-6 after
  20 iterations, 0.1 and more after one.

From the second object:

* ``attention_output_err``: largest relative norm error of the attention
  sublayer's output given its own input (the pre-mixed streams, through
  ``input_layernorm``), every layer: bfloat16 products with float32
  accumulation. Plain RoPE and scale in place of YaRN's read far above.
* ``layer_output_err``, ``hidden_last_err``, ``main_loss_err``,
  ``mtp_loss_err``: as glm's, on the streams.
"""

from __future__ import annotations

import importlib.util
import os

# Each limit between the largest reading of the program over 8 seeds on
# the chip and the smallest reading of a planted fault that it has to
# see (my chip runs, PR 42, call 1: two runs of the cell and
# experiments/lm_check_sweep.py's six seeds and five faults; PERF.md
# section 6 has every fault's reading beside each).
LIMITS = {
    "pairs_dropped": 0,
    "router_scores_err": 2e-4,       # 6.9e-7; glm's bfloat16 router 3.2e-3
    "choice_mismatch_share": 1e-3,   # 0
    "hc_pre_err": 1e-4,              # 1.9e-6; coefficients bfloat16 7.4e-4
    "hc_post_err": 1e-4,             # 3.6e-6; bfloat16 1.3e-3, no 2: 0.87
    "hc_res_err": 1e-4,              # 1.5e-6; bfloat16 6.3e-3, 1 iteration
                                     # 0.22, identity 0.98
    # 1.0e-4 at its worst (one sublayer of one seed; most read 1.3e-6):
    # the residual of 20 iterations has a long tail over random 4 x 4
    # logits (200,000 of them: 99.99% under 6.2e-4, none over 2.6e-3,
    # none over 1e-2); one iteration 0.42.
    "sinkhorn_residual": 1e-2,
    "attention_output_err": 3e-2,    # 0.0066; plain RoPE and scale 0.45
    "layer_output_err": 2e-2,        # 0.0076; 1 iteration 0.27
    "hidden_last_err": 3e-2,         # 0.0118; 1 iteration 0.084
    "main_loss_err": 3e-4,           # 4.5e-5; coefficients bfloat16 3.3e-4
    "mtp_loss_err": 3e-4,            # 3.4e-5; identity H_res 3.7e-4
    "step_loss_err": 3e-4,           # 4.1e-5; 1 iteration 4.4e-4
    "grad_err.lm_head": 3e-2,        # 0.0117; identity H_res 0.041
    "grad_err.eh_proj": 3e-2,        # 0.0114; identity H_res 0.031
    "grad_err.q_b_proj": 6e-2,       # 0.026; identity H_res 0.088
    "grad_err.kv_b_proj": 6e-2,      # 0.019; identity H_res 0.091
    "grad_err.router": 8e-2,         # 0.031 (0.015-0.031); bfloat16 0.139
    "grad_err.expert_gate": 5e-2,    # 0.019; coefficients bfloat16 0.073
    "grad_err.expert_up": 5e-2,      # 0.018; bfloat16 0.069
    "grad_err.expert_down": 5e-2,    # 0.018; bfloat16 0.070
    "grad_err.attn_hc_phi": 1e-1,    # 0.037 (0.014-0.037); 1 iteration 0.18
    "grad_err.mlp_hc_phi": 5e-2,     # 0.012; 1 iteration 0.115
    # Between the seeds' readings (0.025-0.054) and the 1 of a state left
    # unchanged, with the more room above the readings.
    "param_change_err": 3e-1,
}

# (name, path in the program's tree, index into the stacked leaf).
GRAD_LEAVES = (
    ("expert_gate", ("layers", "mlp", "experts_gate"), (0, 0)),
    ("expert_up", ("layers", "mlp", "experts_up"), (0, 0)),
    ("expert_down", ("layers", "mlp", "experts_down"), (0, 0)),
    ("router", ("layers", "mlp", "router"), (0,)),
    ("kv_b_proj", ("layers", "self_attn", "kv_b_proj", "kernel"), (0,)),
    ("q_b_proj", ("layers", "self_attn", "q_b_proj", "kernel"), (0,)),
    ("attn_hc_phi", ("layers", "attn_hc", "phi"), (0,)),
    ("mlp_hc_phi", ("layers", "mlp_hc", "phi"), (0,)),
    ("eh_proj", ("mtp_eh_proj", "kernel"), ()),
    ("lm_head", ("lm_head",), ()),
)
# Where the reference's gradient of a leaf of the first mixture layer is.
LAYER_LEAVES = {
    "expert_gate": lambda g: g["mlp"]["experts_gate"][0],
    "expert_up": lambda g: g["mlp"]["experts_up"][0],
    "expert_down": lambda g: g["mlp"]["experts_down"][0],
    "router": lambda g: g["mlp"]["router"],
    "kv_b_proj": lambda g: g["kv_b_proj"],
    "q_b_proj": lambda g: g["q_b_proj"],
    "attn_hc_phi": lambda g: g["attn_hc"]["phi"],
    "mlp_hc_phi": lambda g: g["mlp_hc"]["phi"],
}
SUBLAYERS = ("attn_hc", "mlp_hc")


def _reference():
  path = os.path.join(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))), "references", "xing4.0-29b-a4b.py")
  spec = importlib.util.spec_from_file_location("_xing40_reference", path)
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


def _layers(tree, group, key, dense):
  """``tree[...][group][key]`` of every layer in the reference's order:
  the dense layers, the scanned stack's, then the MTP block's."""
  out = [tree[f"dense_{i}"][group][key] for i in range(dense)]
  return out + list(tree["layers"][group][key]) + [
      tree["mtp_block"][group][key]]


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
  cfg = dict(run.config)                 # the values held here
  cfg["n_routed_experts"] = c.n_routed_experts   # the router's width
  cfg["vocab_size"] = c.vocab_size
  share = {"layers_held": c.layers_held, "shards": c.shards,
           "shard_index": c.shard_index}
  dense = c.dense_layers
  values = {"pairs_dropped": (run.stats.get("moe") or {}).get(
      "pairs_dropped", float("nan")),
            "sinkhorn_residual": (run.stats.get("mhc") or {}).get(
                "sinkhorn_residual", float("nan"))}

  # 1. To the host: parameters, router state, Adam's state of the leaves.
  state = run.stats["state"]
  row0 = lambda tree: jax.tree.map(lambda x: np.asarray(x[0]), tree)
  params = row0(state.params)
  stats = row0(state.batch_stats)
  pick = lambda tree, path, index: np.asarray(
      _get(tree, path)[(0,) + index], np.float32)
  leaves = lambda tree: {name: pick(tree, path, index)
                         for name, path, index in GRAD_LEAVES}
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
  mixtures = lambda key: list(stepped["layers"]["mlp"][key]) + [
      stepped["mtp_block"]["mlp"][key]]
  chosen = [x.astype(np.int32) for x in mixtures("chosen")]
  probe_in = mixtures("router_probe_in")
  probe_scores = mixtures("router_probe_scores")
  hc_probe = {(hc, key): _layers(stepped, hc, key, dense)
              for hc in SUBLAYERS
              for key in ("probe_streams", "probe_pre", "probe_post",
                          "probe_res")}
  tokens, labels = np.asarray(images), np.asarray(labels)
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
    return model.losses(heads, lab), sown["intermediates"]
  tree = jax.tree.map(jnp.asarray, params)
  (main, mtp), sown = program(tree, stats, tokens, labels)
  f32 = lambda x: jnp.asarray(x, jnp.float32)
  first = lambda x: x[0]
  layer_in = ([first(sown[f"dense_{i}"]["hidden_in"]) for i in range(dense)]
              + list(first(sown["layers"]["hidden_in"])) +
              [first(sown["mtp_block"]["hidden_in"])])
  layer_out = layer_in[1:c.layers_held] + [first(sown["hidden_last"]),
                                           first(sown["hidden_mtp"])]
  def every_layer(key):
    """A sown value of each layer, in the reference's order."""
    return ([first(sown[f"dense_{i}"][key]) for i in range(dense)] +
            list(first(sown["layers"][key])) +
            [first(sown["mtp_block"][key])])
  attn_in, attn_out = every_layer("attn_hc_in"), every_layer("attn_hc_out")
  n = tokens.shape[1]
  per_seq = lambda x, b: x.reshape((tokens.shape[0], n) + x.shape[1:])[b]
  sown_chosen = list(first(sown["layers"]["mlp"]["topk_idx"])) + [
      first(sown["mtp_block"]["mlp"]["topk_idx"])]

  # 4. The reference. The router and the hyper-connections alone, on the
  # timed program's own inputs of them.
  p_ref = ref.from_program(tree, cfg, share)
  bias = [jnp.asarray(b) for b in ref.bias_from_program(stats)]
  blocks = p_ref["layers"] + [p_ref["mtp"]["block"]]
  route = jax.jit(lambda w, b, x: ref.route(cfg, w, b, x))
  scores_err, mismatched, probed = 0.0, 0, 0
  for m, p in enumerate(blocks[dense:]):
    _, own, s = route(p["mlp"]["router"], bias[m], f32(probe_in[m]))
    scores_err = max(scores_err,
                     float(jnp.max(jnp.abs(probe_scores[m] - s))))
    theirs = chosen[m][:own.shape[0]]
    mismatched += int(jnp.sum(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), -1)))
    probed += own.shape[0]
  values.update(router_scores_err=scores_err,
                choice_mismatch_share=mismatched / max(probed, 1))
  coefficients = jax.jit(lambda p, x: ref.hc_coefficients(cfg, p, x))
  hc_err = {"pre": 0.0, "post": 0.0, "res": 0.0}
  for i, p in enumerate(blocks):
    for hc in SUBLAYERS:
      want = coefficients(p[hc], f32(hc_probe[(hc, "probe_streams")][i]))
      for key, w in zip(("pre", "post", "res"), want):
        got = f32(hc_probe[(hc, f"probe_{key}")][i])
        hc_err[key] = max(hc_err[key], float(jnp.max(jnp.abs(got - w))))
  values.update({f"hc_{key}_err": err for key, err in hc_err.items()})

  # Each layer, and its attention sublayer, on the second object's input
  # of it, one sequence at a time.
  block = jax.jit(lambda p, x, b, ch: ref.block(cfg, share, p, x, b, ch))
  attend = jax.jit(lambda p, h: ref.attention(
      cfg, p, ref.rms_norm(h, p["input_layernorm"], cfg["rms_norm_eps"])))
  layer_err = attention_err = 0.0
  for b in range(tokens.shape[0]):
    for i, p in enumerate(blocks):
      m = i - dense
      out = block(p, f32(layer_in[i][b:b + 1]),
                  bias[m] if m >= 0 else None,
                  per_seq(sown_chosen[m], b) if m >= 0 else None)[0]
      layer_err = max(layer_err, _rel(f32(layer_out[i][b:b + 1]), out))
      attention_err = max(attention_err, _rel(
          f32(attn_out[i][b:b + 1]), attend(p, f32(attn_in[i][b:b + 1]))))
  values.update(layer_output_err=layer_err,
                attention_output_err=attention_err)

  # End to end, forward and backward by the chain rule over the
  # reference's own layer functions, the timed program's choices forced.
  losses = lambda p, h, hm, y: ref.heads(cfg, p, h, hm, y)[:2]
  heads_fwd = jax.jit(losses)
  heads_bwd = jax.jit(lambda cot, p, h, hm, y: jax.vjp(
      lambda p, h, hm: losses(p, h, hm, y), p, h, hm)[1](cot))
  mtp_in_fn = lambda p, e, h: ref.mtp_input(cfg, p, e, h)
  mtp_in_fwd = jax.jit(mtp_in_fn)
  mtp_in_bwd = jax.jit(lambda cot, *args: jax.vjp(mtp_in_fn, *args)[1](cot))
  block_bwd = jax.jit(lambda cot, p, h, b, ch: jax.vjp(
      lambda p, h: ref.block(cfg, share, p, h, b, ch)[0], p, h)[1](cot))
  g_ref = {name: 0.0 for name, _, _ in GRAD_LEAVES}
  batch = tokens.shape[0]
  head_p = {"lm_head": p_ref["lm_head"], "norm": p_ref["norm"],
            "mtp": {"norm": p_ref["mtp"]["norm"]}}
  mtp_p = {key: p_ref["mtp"][key] for key in ("enorm", "hnorm", "eh_proj")}

  def forward(b, choices):
    """Sequence b from its token ids under ``choices``: each layer's
    input streams, the MTP block's, both last streams, both losses."""
    tok, lab = tokens[b:b + 1], labels[b:b + 1]
    emb = p_ref["embed"][tok]
    x = ref.expand(emb, cfg["hc_mult"])
    xs = []
    for i, p in enumerate(p_ref["layers"]):
      xs.append(x)
      m = i - dense
      x = block(p, x, bias[m] if m >= 0 else None,
                per_seq(choices[m], b) if m >= 0 else None)[0]
    mtp_in = mtp_in_fwd(mtp_p, emb, x)
    h_mtp = block(p_ref["mtp"]["block"], mtp_in, bias[-1],
                  per_seq(choices[-1], b))[0]
    l_main, l_mtp = heads_fwd(head_p, x, h_mtp, lab)
    return emb, xs, x, mtp_in, h_mtp, float(l_main), float(l_mtp)

  sown_main = sown_mtp = hidden_err = 0.0
  for b in range(batch):
    _, _, x, _, _, l_main, l_mtp = forward(b, sown_chosen)
    sown_main += l_main / batch
    sown_mtp += l_mtp / batch
    hidden_err = max(hidden_err,
                     _rel(f32(first(sown["hidden_last"])[b:b + 1]), x))
  want_loss = 0.0
  for b in range(batch):
    emb, xs, x, mtp_in, h_mtp, l_main, l_mtp = forward(b, chosen)
    want_loss += (l_main + ref.MTP_LOSS_WEIGHT * l_mtp) / batch
    lab, ch_mtp = labels[b:b + 1], per_seq(chosen[-1], b)
    g_head, g_x, g_hm = heads_bwd(
        (jnp.float32(1.0 / batch),
         jnp.float32(ref.MTP_LOSS_WEIGHT / batch)), head_p, x, h_mtp, lab)
    _, g_mtp_in = block_bwd(g_hm, p_ref["mtp"]["block"], mtp_in, bias[-1],
                            ch_mtp)
    g_mtp, _, g_x2 = mtp_in_bwd(g_mtp_in, mtp_p, emb, x)
    g = g_x + g_x2
    g_ref["lm_head"] += g_head["lm_head"]
    g_ref["eh_proj"] += g_mtp["eh_proj"]
    for i in range(len(p_ref["layers"]) - 1, dense - 1, -1):
      m = i - dense
      g_p, g = block_bwd(g, p_ref["layers"][i], xs[i], bias[m],
                         per_seq(chosen[m], b))
      if i == dense:
        for name, at in LAYER_LEAVES.items():
          g_ref[name] += at(g_p)
      del g_p
  values.update(
      hidden_last_err=hidden_err,
      main_loss_err=abs(float(main) - sown_main) / sown_main,
      mtp_loss_err=abs(float(mtp) - sown_mtp) / sown_mtp,
      step_loss_err=abs(step_loss - want_loss) / want_loss)
  # Adam's update of each leaf from the reference's gradient and the
  # state read back before the step, at the configuration's rate.
  lr = float(run.kwargs["init_learning_rate"])
  change_err = 0.0
  for name, _, _ in GRAD_LEAVES:
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
                      "(reference: benchmarks/references/xing4.0-29b-a4b.py)")
  return failures
