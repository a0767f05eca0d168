"""mla_moe_lm (models/mla_moe_lm.py, parallel/expert.py's dropless
routing, ops/fused_loss.fused_softmax_xent_pair) against the plain
reference ``benchmarks/references/glm-4.7-flash.py``: tiny sizes,
seeded random weights, float32, on the CPU.

TOLERANCE. Program and reference are both float32 here and differ in
the ORDER of sums alone (the scanned loss chunks, the sorted grouped
product against a dense loop over experts, the flash path's reference
form, the CPU backend's threads): float32 rounding (6e-8) carried
through four blocks at unit gain (the weights are scaled up so that every
branch matters). Six seeds read up to 4e-5 of a tensor's largest
magnitude on the router's scores and 8e-5 on a gradient leaf, moving
with the machine's thread count; the limit ``RTOL`` is 3e-4 of the
largest magnitude of the tensor compared. The lower-precision control
(the router in bfloat16, 8 bits of mantissa) reads 1e-2 on the scores
and, its choices differing, 0.2 and up on the gradients: it has to FAIL
the limit, and does (``test_lower_precision_control_fails``).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import mla_moe_lm as lm
from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.parallel import expert as expert_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-4


def _load_reference():
  path = os.path.join(REPO, "benchmarks", "references", "glm-4.7-flash.py")
  spec = importlib.util.spec_from_file_location("_glm_reference", path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


ref = _load_reference()


def tiny(layers_held=3, shards=4, shard_index=1, **changes):
  return dataclasses.replace(
      lm.load_lm_config("tiny", layers_held, shards, shard_index), **changes)


def ref_cfg(cfg):
  """The LMConfig as the config.json dict the reference reads, and the
  share."""
  d = dataclasses.asdict(cfg)
  share = {"layers_held": d.pop("layers_held"), "shards": d.pop("shards"),
           "shard_index": d.pop("shard_index")}
  return d, share


def close(got, want, what, rtol=RTOL):
  got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
  scale = max(np.abs(want).max(), 1e-30)
  err = np.abs(got - want).max() / scale
  assert err <= rtol, f"{what}: {err:.3g} of its scale, limit {rtol}"


def trees_close(got, want, what, rtol=RTOL):
  flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
  flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
  assert set(flat_got) == set(flat_want)
  for path, leaf in flat_want.items():
    close(flat_got[path], leaf, f"{what} {jax.tree_util.keystr(path)}", rtol)


def setup(cfg, seed=0, batch=2, seq=16, **module_kwargs):
  module = lm.MLAMoELM(cfg=cfg, **module_kwargs)
  tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              cfg.vocab_rows)
  variables = module.init({"params": jax.random.PRNGKey(seed + 1)}, tokens)
  # Initialised at 0.02 the routed part is far below the residual; the
  # comparison wants every branch to matter.
  params = jax.tree.map(lambda x: x * 8 if x.ndim > 1 else x,
                        variables["params"])
  bias = jax.tree.map(
      lambda x: 0.05 * jax.random.normal(jax.random.PRNGKey(7), x.shape),
      variables.get("batch_stats", {}))
  return module, params, bias, tokens, jnp.roll(tokens, -1, axis=1)


def program(module, cfg, params, batch_stats, tokens, labels):
  """(loss, (main, mtp, intermediates)) and gradients, through the
  module and the model's own loss code."""
  model = lm.MLAMoELMModel()
  model.cfg = cfg

  def fn(p):
    (heads, _), mods = module.apply(
        {"params": p, "batch_stats": batch_stats}, tokens,
        mutable=["intermediates"])
    main, mtp = model.losses(heads, labels)
    loss = model.loss_function(
        model_lib.BuildNetworkResult(logits=(heads, None)), labels)
    return loss, (main, mtp, mods["intermediates"])
  return jax.jit(jax.value_and_grad(fn, has_aux=True))(params)


# -- C1: layers, module, model against the reference --------------------------

@pytest.mark.parametrize("case", ["dense_layer", "mixture_layer",
                                  "mtp_module", "whole_model"])
def test_against_reference(case):
  if case in ("dense_layer", "mixture_layer"):
    cfg = tiny()
    mixture = case == "mixture_layer"
    block = lm.Block(cfg=cfg, mixture=mixture)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.hidden_size))
    variables = block.init({"params": jax.random.PRNGKey(4)}, x)
    params = jax.tree.map(lambda p: p * 8 if p.ndim > 1 else p,
                          variables["params"])
    stats = jax.tree.map(
        lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
        variables.get("batch_stats", {}))
    d, share = ref_cfg(cfg)
    key = "layer_0" if mixture else "dense_0"
    bias = stats["mlp"]["select_bias"] if mixture else None

    def prog(p, x):
      return jnp.sum(jnp.sin(block.apply(
          {"params": p, "batch_stats": stats}, x)[0]))

    def plain(p, x):
      layer = ref.from_program(
          {key: p, "embed_tokens": {"embedding": 0}, "lm_head": 0,
           "norm": {"scale": 0}}, dict(d, num_nextn_predict_layers=0),
          share)["layers"][0]
      return jnp.sum(jnp.sin(ref.block(d, share, layer, x, bias)[0]))
    close(block.apply({"params": params, "batch_stats": stats}, x)[0],
          ref.block(d, share, ref.from_program(
              {key: params, "embed_tokens": {"embedding": 0}, "lm_head": 0,
               "norm": {"scale": 0}}, dict(d, num_nextn_predict_layers=0),
              share)["layers"][0], x, bias)[0], "layer output")
    trees_close(jax.grad(prog, argnums=(0, 1))(params, x),
                jax.grad(plain, argnums=(0, 1))(params, x), "gradient")
    return
  cfg = tiny(layers_held=1 if case == "mtp_module" else 3)
  module, params, stats, tokens, labels = setup(cfg)
  d, share = ref_cfg(cfg)
  (loss, (main, mtp, mids)), grads = program(module, cfg, params, stats,
                                             tokens, labels)
  want, want_grads = ref.loss_and_grads(
      d, share, params, ref.bias_from_program(stats), tokens, labels)
  close(loss, want["loss"], "loss")
  close(main, want["main_loss"], "main loss")
  close(mtp, want["mtp_loss"], "MTP loss")
  close(mids["hidden_last"][0], want["hidden_last"], "last hidden state")
  close(mids["hidden_mtp"][0], want["hidden_mtp"], "MTP hidden state")
  close(mids["mtp_block"]["mlp"]["router_scores"][0], want["scores"][-1],
        "MTP router scores")
  assert np.array_equal(mids["mtp_block"]["mlp"]["topk_idx"][0],
                        want["idx"][-1])
  if case == "whole_model":
    for layer in range(cfg.moe_layers):
      close(mids["layers"]["hidden_in"][0][layer], want["hidden"][1 + layer],
            f"input of mixture layer {layer}")
      close(mids["layers"]["mlp"]["router_scores"][0][layer],
            want["scores"][layer], f"router scores of layer {layer}")
  trees_close(grads, want_grads, "gradient")


# -- C2: the shares add up to the uncut layer ---------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
  whole = tiny(shards=1, shard_index=0)
  d, share = ref_cfg(whole)
  moe = lm.MoE(cfg=whole)
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, whole.hidden_size))
  variables = moe.init({"params": jax.random.PRNGKey(4)}, x)
  params = jax.tree.map(lambda p: p * 8, variables["params"])
  stats = variables["batch_stats"]
  layer = ref.from_program(
      {"layer_0": {"mlp": params, "self_attn": {k: {"kernel": 0, "scale": 0}
                                                for k in (
          "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
          "kv_a_layernorm", "kv_b_proj", "o_proj")},
                   "input_layernorm": {"scale": 0},
                   "post_attention_layernorm": {"scale": 0}},
       "embed_tokens": {"embedding": 0}, "lm_head": 0, "norm": {"scale": 0}},
      dict(d, num_nextn_predict_layers=0), share)["layers"][0]["mlp"]
  uncut, _, _ = ref.mixture(d, share, layer, stats["select_bias"], x)
  shared = ref.swiglu(layer["shared"], x.reshape(-1, whole.hidden_size)
                      ).reshape(x.shape)
  total = jnp.zeros_like(x)
  for index in range(4):
    cfg = tiny(shards=4, shard_index=index)
    rows = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
    part = dict(params, **{k: params[k][rows] for k in (
        "experts_gate", "experts_up", "experts_down")})
    total += lm.MoE(cfg=cfg).apply(
        {"params": part, "batch_stats": stats}, x) - shared
  # The four routed parts, and what every chip computes alike counted once.
  close(total + shared, uncut, "sum of the shares")


def test_vocabulary_slices_concatenate_to_the_uncut_logits():
  whole = tiny(layers_held=1, shards=1, shard_index=0,
               num_nextn_predict_layers=0)
  module, params, stats, tokens, labels = setup(whole)
  tokens = tokens % (whole.vocab_size // 4)     # ids every slice's test has
  d, share = ref_cfg(whole)
  want = ref.forward(d, share, ref.from_program(params, d, share), [],
                     tokens, labels)["logits"]
  slices = []
  for index in range(4):
    cfg = tiny(layers_held=1, shards=4, shard_index=index,
               num_nextn_predict_layers=0)
    rows = slice(index * cfg.vocab_rows, (index + 1) * cfg.vocab_rows)
    part = dict(params,
                embed_tokens={"embedding": params["embed_tokens"][
                    "embedding"][:cfg.vocab_rows]},
                lm_head=params["lm_head"][:, rows])
    (heads, _) = lm.MLAMoELM(cfg=cfg).apply({"params": part}, tokens)
    slices.append(heads.hidden[0] @ heads.kernel)
  close(jnp.concatenate(slices, -1), want, "concatenated logits")


# -- C3: dropless under imbalance ---------------------------------------------

def _forced(cfg, experts, seq=16, **module_kwargs):
  """A MoE layer whose selection bias forces every token onto
  ``experts``."""
  moe = lm.MoE(cfg=cfg, **module_kwargs)
  x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, cfg.hidden_size))
  variables = moe.init({"params": jax.random.PRNGKey(4)}, x)
  params = jax.tree.map(lambda p: p * 8, variables["params"])
  bias = jnp.zeros((cfg.n_routed_experts,))
  if experts:
    bias = bias.at[jnp.asarray(experts)].set(9.0)
  stats = dict(variables["batch_stats"], select_bias=bias)
  return moe, params, stats, x


def _as_ref(p):
  """A MoE layer's parameters as the reference's ``mixture`` reads them."""
  return {"router": p["router"], "experts_gate": p["experts_gate"],
          "experts_up": p["experts_up"], "experts_down": p["experts_down"],
          "shared": {k: p["shared_experts"][k]["kernel"]
                     for k in ("gate_proj", "up_proj", "down_proj")}}


def _layer_against_reference(cfg, moe, params, stats, x):
  """Output and gradients (parameters and input) of a MoE layer against
  ``ref.mixture``; returns what the layer left in ``batch_stats``."""
  d, share = ref_cfg(cfg)
  out, updates = moe.apply({"params": params, "batch_stats": stats}, x,
                           mutable=["batch_stats"])
  close(out, ref.mixture(d, share, _as_ref(params), stats["select_bias"],
                         x)[0], "output")
  prog = lambda p, x: jnp.sum(jnp.sin(moe.apply(
      {"params": p, "batch_stats": stats}, x)))
  plain = lambda p, x: jnp.sum(jnp.sin(ref.mixture(
      d, share, _as_ref(p), stats["select_bias"], x)[0]))
  trees_close(jax.jit(jax.grad(prog, argnums=(0, 1)))(params, x),
              jax.jit(jax.grad(plain, argnums=(0, 1)))(params, x), "gradient")
  return updates["batch_stats"]


# 1,024 tokens x 2 choices over 4 of 16 experts held: the rule itself
# gives rounds of 1,024 of the 2,048 sorted rows (at the 32-token cases
# the 512-row tile makes one round of all the pairs, and no loop is
# built). Held here: experts 4..7.
ROUND_REGIMES = {
    # regime: (experts forced, pairs held here or None, rounds)
    "no_pair_here": ([0, 15], 0, 1),
    "under_one_round": ([], None, 1),
    "exactly_one_round": ([4, 0], 1024, 1),      # the boundary is compact
    "over_one_round": ([4], None, 2),            # the second round partial
    "every_pair_here": ([4, 6], 2048, 2),
}


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("regime", list(ROUND_REGIMES))
def test_rounds_of_the_routed_path_against_reference(regime, impl):
  experts, want_pairs, rounds = ROUND_REGIMES[regime]
  cfg = tiny()
  assert expert_lib.compact_rows(1024 * cfg.num_experts_per_tok,
                                 cfg.experts_held, cfg.n_routed_experts) == 1024
  moe, params, stats, x = _forced(cfg, experts, seq=512, moe_impl=impl)
  new = _layer_against_reference(cfg, moe, params, stats, x)
  held = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
  pairs_here = float(jnp.sum(new["load"][held]))
  if want_pairs is not None:
    assert pairs_here == want_pairs
  assert -(-max(pairs_here, 1) // 1024) == rounds
  assert float(new["pairs_computed"]) == pairs_here
  assert float(new["compact"]) == (rounds == 1)


@pytest.mark.parametrize("pairs, held, experts, tile, want", [
    (32768, 8, 64, None, 8192),     # the benchmark's cell: a quarter
    (32768, 64, 64, None, 32768),   # every expert held: all the pairs
    (32768, 32, 64, None, 32768),   # half of them held: all the pairs
    (32768, 1, 64, None, 1024),
    (2048, 4, 16, None, 1024),
    (1000, 1, 16, None, 512),       # 125 rounds up to one row tile
    (1000, 5, 16, None, 1000),      # ... and never above all the pairs
    (64, 4, 16, None, 64),          # the tiny cases: one round
    (96, 1, 16, 8, 16),             # 12 rounds up to two tiles of 8
    (100, 3, 7, 8, 88),             # 85.7.. -> 86 -> 88
])
def test_compact_rows(pairs, held, experts, tile, want):
  tiles = {} if tile is None else {"tile": tile}
  rows = expert_lib.compact_rows(pairs, held, experts, **tiles)
  assert rows == want and rows <= pairs
  assert rows == pairs or rows % tiles.get("tile", 512) == 0


def _eqns_in(jaxpr):
  """Every equation of a jaxpr, its sub-jaxprs' too."""
  for eqn in jaxpr.eqns:
    yield eqn
    for param in eqn.params.values():
      for sub in (param if isinstance(param, (tuple, list)) else (param,)):
        sub = getattr(sub, "jaxpr", sub)
        if hasattr(sub, "eqns"):
          yield from _eqns_in(sub)


def _avals_in(jaxpr):
  """(shape, dtype) of every value a jaxpr computes."""
  for eqn in _eqns_in(jaxpr):
    for var in eqn.outvars:
      yield (tuple(getattr(var.aval, "shape", ())),
             getattr(var.aval, "dtype", None))


def _shapes_in(jaxpr):
  return (shape for shape, _ in _avals_in(jaxpr))


def test_routed_path_holds_no_pairs_by_width_array():
  # Forward and backward work on rounds of 1,024 rows: no array of
  # N x k = 2,048 rows times a model width (32, or the experts' 16)
  # exists; index vectors of N x k and token-side (N, 32) are fine.
  cfg = tiny()
  pairs, widths = 2048, (cfg.hidden_size, cfg.moe_intermediate_size)
  moe, params, stats, x = _forced(cfg, [], seq=512)

  def offenders(module):
    fn = lambda p, x: jnp.sum(jnp.sin(module.apply(
        {"params": p, "batch_stats": stats}, x)))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 1)))(params, x)
    return sorted({s for s in _shapes_in(jaxpr.jaxpr)
                   if len(s) >= 2 and s[0] == pairs and s[-1] in widths})
  assert offenders(moe) == []
  # The control: with every expert held the rule gives one round of all
  # N x k rows, and the same search finds them.
  whole = tiny(shards=1, shard_index=0)
  moe, params, stats, x = _forced(whole, [], seq=512)
  assert (pairs, cfg.hidden_size) in offenders(moe)


def test_combine_gathers_the_products_own_rows():
  # A bfloat16 layer, 1,024 tokens x 2 choices over 2 of 16 experts held:
  # rounds of 512 rows (so a round's rows and the tokens differ in
  # number). The combine reads the round as the products stored it: no
  # copy with a zero row appended (513 rows), no float32 copy of the
  # round in the forward, and every gather from an array of the round's
  # shape (the combine's k, and the k of ``_rows_of_tokens``' backward)
  # reads bfloat16. (The interpreted kernel stores bfloat16 as the TPU's
  # does; XLA's grouped product on a CPU computes float32 and casts.)
  cfg = tiny(shards=8, shard_index=1)
  k, rows, width = cfg.num_experts_per_tok, 512, cfg.hidden_size
  assert expert_lib.compact_rows(1024 * k, cfg.experts_held,
                                 cfg.n_routed_experts) == rows
  moe, params, stats, x = _forced(cfg, [], seq=512, dtype=jnp.bfloat16,
                                  moe_impl="gmm_interpret")
  x = x.astype(jnp.bfloat16)
  fn = lambda p, x: jnp.sum(jnp.sin(moe.apply(
      {"params": p, "batch_stats": stats}, x).astype(jnp.float32)))
  forward = jax.make_jaxpr(fn)(params, x).jaxpr
  both = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 1)))(params,
                                                                x).jaxpr
  assert not [s for s in _shapes_in(both) if s and s[0] == rows + 1]
  assert (rows, width) in set(_shapes_in(forward))      # the search finds it
  assert ((rows, width), jnp.float32) not in set(_avals_in(forward))
  tables = [eqn.invars[0].aval for eqn in _eqns_in(both)
            if eqn.primitive.name == "gather"
            and eqn.invars[0].aval.shape == (rows, width)]
  assert len(tables) >= 2 * k
  assert {t.dtype for t in tables} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_combine_against_dense_one_hot_reference(k, dtype):
  # ``_rows_to_tokens`` alone, forward and both gradients, against a
  # dense one-hot sum in float64: 16 tokens choose k of 16 experts, 4..11
  # held, two rounds forced. The forward multiplies and sums in float32
  # whatever the rows' type, so it is held to float32's rounding; the
  # rows' gradient is stored in their type.
  n, e, g, first, d = 16, 16, 8, 4, 8
  rng = np.random.RandomState(k)
  idx = np.stack([rng.permutation(e)[:k] for _ in range(n)])
  idx[0] = np.arange(first, first + k)                  # every pair held
  idx[1] = np.asarray([0, 1, 2, 3, 12, 13, 14, 15])[:k]   # none held
  plan, held = expert_lib.sort_pairs(jnp.asarray(idx, jnp.int32), first, g)
  pairs_here = int(plan.ends[-1])
  rows = pairs_here // 2 + 1           # two rounds, the second not full
  held, inv = np.asarray(held), np.asarray(plan.inv).reshape(n, k)
  assert held[0].all() and not held[1].any()
  assert (held & (inv >= rows)).any()          # a held pair of round 1
  assert (~held & (inv < 2 * rows)).any()      # an absent one's row in it
  pair_w = jnp.where(held, jnp.asarray(rng.uniform(0.1, 1, (n, k)),
                                       jnp.float32), 0)
  g_out = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
  order = np.concatenate([np.asarray(plan.order), np.zeros(2 * rows, int)])
  for start in (0, rows):
    ys = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    tok = jnp.asarray(order[start:start + rows] // k, jnp.int32)
    slot = np.where((inv >= start) & (inv < start + rows), inv - start, rows)
    y, pull = jax.vjp(lambda ys, w: expert_lib._rows_to_tokens(
        ys, w, tok, jnp.asarray(slot, jnp.int32), jnp.int32(start), plan),
                      ys, pair_w)
    d_ys, d_w = pull(g_out)
    assert y.dtype == jnp.float32 and d_ys.dtype == dtype
    one_hot = (slot[:, :, None] == np.arange(rows)).astype(np.float64)
    ys64, w64 = np.asarray(ys, np.float64), np.asarray(pair_w, np.float64)
    close(y, np.einsum("tjr,tj,rd->td", one_hot, w64, ys64), "y", 1e-6)
    close(d_ys, np.einsum("tjr,tj,td->rd", one_hot, w64, g_out), "d ys",
          1e-6 if dtype == jnp.float32 else 4e-3)
    close(d_w, np.einsum("tjr,td,rd->tj", one_hot, g_out, ys64), "d pair_w",
          1e-6)
    if start == 0:
      assert not np.asarray(y)[1].any()        # the token with no pair


def test_every_token_on_held_experts_loses_none():
  cfg = tiny()
  experts = [cfg.first_expert, cfg.first_expert + 2]
  moe, params, stats, x = _forced(cfg, experts)
  new = _layer_against_reference(cfg, moe, params, stats, x)
  tokens = x.shape[0] * x.shape[1]
  assert float(new["pairs_computed"]) == 2 * tokens   # every pair, all here
  assert float(new["load"][experts[0]]) == tokens
  # 64 pairs are under one row tile: the round is all of them, and the
  # one round counts as compact (there is no other tier to take).
  assert float(new["compact"]) == 1


def test_every_token_on_absent_experts_leaves_the_shared_expert():
  cfg = tiny()
  moe, params, stats, x = _forced(cfg, [0, 15])    # held: 4..7
  out, updates = moe.apply({"params": params, "batch_stats": stats}, x,
                           mutable=["batch_stats"])
  assert float(updates["batch_stats"]["pairs_computed"]) == 0
  shared = lm.SwiGLU(cfg=cfg, width=cfg.moe_intermediate_size).apply(
      {"params": params["shared_experts"]}, x)
  assert np.array_equal(np.asarray(out), np.asarray(shared))


@pytest.mark.parametrize("fault, dropped", [
    (None, 0),
    ("buffer_cut_short", 3),      # a capacity: the last rows never run
    ("group_size_short", 2),      # sizes that disagree with the sort
])
def test_drop_count_is_taken_from_the_rows_the_products_run(fault, dropped):
  # 12 pairs over 3 held experts (key 3 = an absent expert's pair).
  key = jnp.asarray([0, 2, 1, 3, 2, 0, 1, 1, 3, 2, 0, 2], jnp.int32)
  sizes = jnp.asarray([3, 3, 4], jnp.int32)
  pairs_here = 10
  live = jnp.arange(12) < pairs_here
  if fault == "buffer_cut_short":
    live = jnp.arange(12) < pairs_here - 3
  if fault == "group_size_short":
    sizes = sizes.at[1].add(-1)   # expert 1's last row falls to expert 2
  computed = int(expert_lib.pairs_inside_groups(jnp.sort(key), sizes, live))
  assert pairs_here - computed == dropped


@pytest.mark.parametrize("rows", [16, 24])   # 64 pairs: 4 rounds; 2 and 16
def test_drop_count_of_one_round_forced_on_pairs_that_need_two(rows):
  # A wrong predicate -- the first round alone on a step whose pairs do
  # not fit it -- reads the pairs left out as dropped, so it cannot pass
  # the benchmark's check (``pairs_dropped`` must be 0).
  n, k, g, d, f = 32, 2, 3, 8, 4
  keys = jax.random.split(jax.random.PRNGKey(0), 5)
  idx = jnp.stack([jnp.arange(n) % g + 2, jnp.full((n,), 9)], -1)  # held 2..4
  plan, held = expert_lib.sort_pairs(idx, 2, g)
  pairs_here = int(plan.ends[-1])
  assert pairs_here == n > rows and bool(jnp.all(held[:, 0] & ~held[:, 1]))
  x = jax.random.normal(keys[0], (n, d))
  w = [jax.random.normal(key, shape) for key, shape in zip(
      keys[1:], [(g, d, f), (g, d, f), (g, f, d)])]
  pair_w = jnp.where(held, 0.5, 0.0)
  one = lambda i: expert_lib.experts_round(i, x, pair_w, *w, plan, rows,
                                           "ragged_dot")
  (y0, computed0, _), (y1, computed1, _) = one(0), one(1)
  assert pairs_here - int(computed0) == pairs_here - rows
  assert int(computed0) + int(computed1) == pairs_here
  # ... and the two rounds together are the layer.
  y, counts = expert_lib.held_experts_ffn(x, jnp.full((n, k), 0.5), idx, *w,
                                          first_expert=2, rows=rows)
  close(y, y0 + y1, "sum of the rounds")
  assert int(counts["pairs_computed"]) == pairs_here
  assert int(counts["compact"]) == 0


# Choices of 48 tokens x 2 over 12 experts, 2..5 held, for the layout
# whose every held expert's rows start on a row tile of 8: (a regime, the
# round's rows). Rounds of 64 rows hold every held pair and its padding
# but in ``second_round``, whose rounds of 16 rows are two tiles.
LAYOUT_REGIMES = {
    "uneven_loads": 64,
    "one_empty_expert": 64,
    "group_one_tile": 64,
    "second_round": 16,
}


def _layout_choices(regime, n=48, k=2, e=12, first=2, tile=8, seed=0):
  """(N, k) choices drawn with uneven popularity over the experts; held
  expert ``first + 1`` chosen by nobody in ``one_empty_expert``, held
  expert ``first`` by exactly one tile of tokens in ``group_one_tile``."""
  rng = np.random.RandomState(seed)
  p = rng.dirichlet(np.full(e, 0.7))
  if regime == "one_empty_expert":
    p[first + 1] = 0
  if regime == "group_one_tile":
    p[first] = 0
  idx = np.stack([rng.choice(e, k, replace=False, p=p / p.sum())
                  for _ in range(n)])
  if regime == "group_one_tile":
    idx[:tile, 0] = first
  return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("regime", list(LAYOUT_REGIMES))
def test_tile_aligned_layout_against_the_packed_one(regime, impl):
  # The same layer with every held expert's rows from a multiple of the
  # row tile and with the groups packed end to end: y bit for bit (each
  # output row is the same products over the same contraction tiles), the
  # five gradients to float32's last places (a weight gradient sums an
  # expert's rows over other tile boundaries), no pair dropped, every
  # group handed to the products from a tile boundary, and megablox's own
  # visits what ``rows_in_tiles`` counts: one a tile for one expert.
  from jax.experimental.pallas.ops.tpu.megablox.gmm import (
      make_group_metadata)
  n, k, g, first, d, f, tile = 48, 2, 4, 2, 16, 8, 8
  rows = LAYOUT_REGIMES[regime]
  idx = _layout_choices(regime)
  keys = jax.random.split(jax.random.PRNGKey(1), 6)
  x = jax.random.normal(keys[0], (n, d))
  weights = jax.nn.softmax(jax.random.normal(keys[1], (n, k)), -1)
  w = [0.3 * jax.random.normal(key, shape) for key, shape in zip(
      keys[2:], [(g, d, f), (g, d, f), (g, f, d)])]

  def layer(tile_):
    def fn(x, weights, *w):
      y, counts = expert_lib.held_experts_ffn(
          x, weights, idx, *w, first_expert=first, impl=impl, rows=rows,
          tile=tile_)
      return jnp.sum(jnp.sin(y)), (y, counts)
    return jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        x, weights, *w)
  (_, (y, counts)), grads = layer(tile)
  (_, (y_packed, packed)), want = layer(None)
  assert np.array_equal(np.asarray(y), np.asarray(y_packed))
  for name, got, ref_ in zip(("x", "weights", "gate", "up", "down"), grads,
                             want):
    close(got, ref_, f"{impl}: gradient of {name}", rtol=1e-5)
  pairs_here = int(counts["pairs_here"])
  assert pairs_here == int(packed["pairs_here"]) > 0
  assert int(counts["pairs_computed"]) == pairs_here       # none dropped
  plan, _ = expert_lib.sort_pairs(idx, first, g, tile)
  ends = np.asarray(plan.ends)
  loads = np.diff(ends, prepend=0)
  assert not (ends % tile).any()
  if regime == "one_empty_expert":
    assert loads[1] == 0
  if regime == "group_one_tile":
    assert loads[0] == tile
  # Padding rows hold no pair: the absent key, an index past the pairs.
  key, order = np.asarray(plan.key), np.asarray(plan.order)
  assert (key[order >= n * k] == g).all()
  assert (key < g).sum() == pairs_here
  rounds = -(-ends[-1] // rows)
  assert (rounds > 1) == (regime == "second_round") == (
      int(counts["compact"]) == 0)
  visited = 0
  for i in range(rounds):
    inside = np.clip(ends - i * rows, 0, rows)
    assert not (inside % tile).any()       # a group starts on a tile
    sizes = jnp.asarray(np.diff(inside, prepend=0), jnp.int32)
    _, tiles = make_group_metadata(
        group_sizes=sizes, m=rows, tm=tile, start_group=jnp.int32(0),
        num_nonzero_groups=g, visit_empty_groups=False)
    assert int(tiles) == int(np.sum(-(-np.asarray(sizes) // tile)))
    visited += int(tiles)
  assert int(counts["rows_in_tiles"]) == visited * tile


@pytest.mark.parametrize("pairs, held, experts, want", [
    (4096 * 4, 8, 64, 256),      # xing: 256 rows an expert
    (8192 * 8, 16, 128, 256),    # trinity-mini: 512
    (8192 * 4, 8, 64, 256),      # glm-4.7-flash: 512
    (8192 * 6, 8, 128, 256),     # nemotron: 384
    (32768 * 4, 8, 64, 512),     # 2,048 rows an expert: four tiles of 512
    (2048, 4, 16, 1024),         # the tiny preset at 1,024 tokens: the round
    (64, 4, 16, 64),             # ... at 32 tokens: all the pairs
])
def test_row_tile(pairs, held, experts, want):
  assert expert_lib.row_tile(pairs, held, experts) == want
  rows = expert_lib.compact_rows(pairs, held, experts)
  assert rows % want == 0
  assert expert_lib.gmm_tiling(want, 2048, 1024)[0] == min(want, 512)


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
def test_grouped_product_implementations_agree(impl):
  # The TPU kernel (interpreted) and XLA's grouped product, forward and
  # backward, on a buffer whose tail is not in use.
  rows, d, f, g = 64, 16, 8, 4
  key = jax.random.split(jax.random.PRNGKey(0), 3)
  lhs = jax.random.normal(key[0], (rows, d))
  rhs = jax.random.normal(key[1], (g, d, f))
  sizes = jnp.asarray([10, 0, 25, 6], jnp.int32)
  live = jnp.arange(rows) < 41
  fn = lambda a, b: jnp.sum(jnp.sin(expert_lib.grouped_matmul(
      a, b, sizes, live, impl)))
  bounds = np.cumsum([0, 10, 0, 25, 6])
  def plain(a, b):
    out = jnp.zeros((rows, f))
    for j in range(g):
      rows_j = (jnp.arange(rows) >= bounds[j]) & (jnp.arange(rows) <
                                                  bounds[j + 1])
      out = out + jnp.where(rows_j[:, None], a @ b[j], 0)
    return jnp.sum(jnp.sin(out))
  close(fn(lhs, rhs), plain(lhs, rhs), "value")
  trees_close(jax.grad(fn, argnums=(0, 1))(lhs, rhs),
              jax.grad(plain, argnums=(0, 1))(lhs, rhs), "gradient")


# -- C4: the selection bias ---------------------------------------------------

def test_bias_changes_the_choice_and_never_the_weights():
  cfg = tiny()
  x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.hidden_size))
  router = jax.random.normal(jax.random.PRNGKey(4),
                             (cfg.hidden_size, cfg.n_routed_experts))
  bias = jnp.zeros((cfg.n_routed_experts,)).at[3].set(5.0)
  args = (cfg.num_experts_per_tok, cfg.routed_scaling_factor)
  w0, idx0, scores = expert_lib.route_topk(x, router, 0 * bias, *args)
  w1, idx1, _ = expert_lib.route_topk(x, router, bias, *args)
  assert not np.array_equal(idx0, idx1) and bool(jnp.all(
      jnp.any(idx1 == 3, -1)))
  for w, idx in ((w0, idx0), (w1, idx1)):
    own = jnp.take_along_axis(scores, idx, -1)     # no bias in the weights
    close(w, own / own.sum(-1, keepdims=True) * args[1], "weights")
  # ... and no gradient reaches it.
  g = jax.grad(lambda b: jnp.sum(expert_lib.route_topk(
      x, router, b, *args)[0]))(bias)
  assert not np.any(np.asarray(g))


def test_one_step_moves_the_bias_by_its_own_rule(tmp_path):
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  params = params_lib.make_params(
      model="mla_moe_lm", lm_config="tiny", seq_len=16, batch_size=2,
      lm_layer_shards=4, lm_layer_shard_index=1, device="cpu",
      optimizer="adam", weight_decay=0.01, num_batches=1,
      num_warmup_batches=0, display_every=1, tf_random_seed=5)
  stats = benchmark.BenchmarkCNN(benchmark.setup(params)).run()
  state = stats["state"]
  flat = dict(jax.tree_util.tree_flatten_with_path(state.batch_stats)[0])
  biases = {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat.items()
            if "select_bias" in jax.tree_util.keystr(k)}
  loads = {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat.items()
           if k[-1].key == "load"}
  assert len(biases) == 2      # the scanned mixture layers, the MTP block
  for key, bias in biases.items():
    load = loads[key.replace("select_bias", "load")]
    want = lm.BIAS_UPDATE_SPEED * np.sign(
        load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(bias, want, rtol=0, atol=1e-9)
  # The optimizer does not own it: its state mirrors the parameters.
  mu = state.opt_state[0].mu
  assert jax.tree.structure(mu) == jax.tree.structure(state.params)
  assert "select_bias" not in str(jax.tree.structure(state.params))
  moe = stats["moe"]
  assert moe["pairs_dropped"] == 0 and moe["experts_held"] == 4
  assert moe["vocab_rows"] == 512 and moe["pairs_routed_here"] > 0
  assert moe["load_max_over_mean"] >= 1


@pytest.fixture(scope="module")
def two_step_stats():
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  params = params_lib.make_params(
      model="mla_moe_lm", lm_config="tiny", seq_len=16, batch_size=2,
      lm_layer_shards=4, lm_layer_shard_index=1, device="cpu",
      optimizer="adam", num_batches=2, num_warmup_batches=0,
      display_every=1, tf_random_seed=5)
  return benchmark.BenchmarkCNN(benchmark.setup(params)).run()


def test_tier_counter_reaches_the_stats(two_step_stats):
  moe = two_step_stats["moe"]
  # 2 x 16 tokens x 2 choices: under one row tile, so one round of all
  # 64 pairs in each of the 3 mixture layers of each of the 2 steps.
  assert moe["steps"] == 2 and moe["buffer_rows"] == 64
  assert moe["compact_share"] == 1.0 and moe["pairs_dropped"] == 0


def test_combine_states_itself_in_the_stats(two_step_stats):
  # k = 2 gathers of 2 x 16 rows from the one round's 64 float32 rows of
  # width 32 (a CPU run's module is float32).
  assert two_step_stats["moe"]["combine"] == {
      "gathers": 2, "rows_gathered": 64, "table_dtype": "float32",
      "table_bytes": 64 * 32 * 4}


def test_lm_head_states_itself_in_the_stats(two_step_stats):
  # 2 x 16 positions in chunks of 2 (an eighth of the sequence), main and
  # MTP loss: a group is a quarter of the sequence, 2 chunks of 4 rows a
  # loss, so FOUR products of the kernel's gradient a loss a step, both
  # losses' float32 rows (a CPU run's module is float32) over the 512
  # vocabulary rows held.
  assert two_step_stats["lm_head"] == {
      "chunk": 2, "rows_per_weight_grad_product": 8,
      "weight_grad_passes": 4, "dlogits_bytes_held": 2 * 8 * 512 * 4,
      "losses": 2}


def test_lm_head_of_the_glm_cell():
  # What the benchmark's cell states (2 x 4096 positions, 19,360
  # vocabulary rows held, main and MTP loss, bfloat16): chunks of 512
  # positions are 1,024 rows a loss, so two chunks a group: products over
  # 2,048 rows, 4 a loss into the one float32 accumulator a step where a
  # chunk a product made 8 a loss.
  from kf_benchmarks_tpu import params as params_lib
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", seq_len=4096, batch_size=2, lm_layers_held=5,
      lm_layer_shards=8, device="cpu"))
  assert model.lm_head_stats(jnp.bfloat16) == {
      "chunk": 512, "rows_per_weight_grad_product": 2048,
      "weight_grad_passes": 4, "dlogits_bytes_held": 2 * 2048 * 19360 * 2,
      "losses": 2}


def test_attention_core_states_itself_in_the_stats(two_step_stats):
  # Off the TPU no kernel runs (materialised scores), in the 3 layers
  # held and the MTP block; the keys are the ones a TPU run fills.
  assert two_step_stats["attention"] == {
      "core_layers": 4, "backward_kernel_passes": 0, "block": 0,
      "block_q": 0, "block_kv": 0, "block_kv_dkv": 0, "dq_partials": 0}


def test_rotary_stage_states_itself_in_the_stats(two_step_stats):
  # Latent attention's two sites, in the 3 layers held and the MTP block:
  # q's whole head with its trailing rope dimensions rotated, and the one
  # rotary key the heads share; no head norm; off the TPU the plain form.
  rotary = two_step_stats["rotary"]
  cfg = lm.load_lm_config("tiny")
  assert sorted(rotary) == ["k_rot", "q"]
  assert rotary["q"]["layers"] == rotary["k_rot"]["layers"] == 4
  assert (rotary["q"]["heads"], rotary["q"]["head_dim"],
          rotary["q"]["rot_dims"]) == (
              cfg.num_attention_heads, cfg.qk_head_dim, cfg.qk_rope_head_dim)
  assert (rotary["k_rot"]["heads"], rotary["k_rot"]["head_dim"],
          rotary["k_rot"]["rot_dims"]) == (
              1, cfg.qk_rope_head_dim, cfg.qk_rope_head_dim)
  assert {v["implementation"] for v in rotary.values()} == {"xla"}
  assert not any(v["normed"] or v["block_rows"] or v["block_heads"]
                 for v in rotary.values())


def test_rotary_stage_of_the_glm_cell_on_a_tpu(monkeypatch):
  # What the benchmark's cell states (2 x 4096 tokens in bfloat16, 20
  # heads of 192 + 64, 5 layers and the MTP block): q goes through the
  # kernel whole, 512 positions of 5 heads a block (1,280 lanes a
  # position), its trailing 64 dimensions rotated; the shared rotary key, one head of 64,
  # is narrower than the lanes and stays plain ``jnp``.
  from kf_benchmarks_tpu import params as params_lib
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", seq_len=4096, batch_size=2, lm_layers_held=5,
      lm_layer_shards=8, device="cpu"))
  model.set_batch_size(2)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert model.rotary_stats(jnp.bfloat16) == {
      "q": {"calls_per_layer": 1, "layers": 6, "rot_dims": 64, "heads": 20,
            "head_dim": 256, "normed": False, "implementation": "pallas",
            "block_rows": 512, "block_heads": 5,
            "bytes_read_and_written_per_call": 2 * 2 * 4096 * 20 * 256 * 2,
            "residual_bytes_per_layer": 0},
      "k_rot": {"calls_per_layer": 1, "layers": 6, "rot_dims": 64,
                "heads": 1, "head_dim": 64, "normed": False,
                "implementation": "xla", "block_rows": 0, "block_heads": 0,
                "bytes_read_and_written_per_call": 2 * 2 * 4096 * 64 * 2,
                "residual_bytes_per_layer": 0}}


def test_attention_core_of_the_glm_cell_on_a_tpu(monkeypatch):
  # What the benchmark's cell states (2 x 4096 tokens, head size 256, 5
  # layers and the MTP block): ONE backward kernel pass a layer on scores
  # of 512 x 512, the forward fetching 1,024 queries and keys a grid
  # step, 1,024 keys held across the backward's sweep, four partial dq.
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", seq_len=4096, batch_size=2, lm_layers_held=5,
      lm_layer_shards=8, device="cpu"))
  lines = []
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with tracing.session(tracing.RunTrace(log_fn=lines.append)):
    model.state_blocks(jnp.bfloat16)      # the build states the core
  assert model.cfg.layers_held == 5
  assert [ln for ln in lines if ln.startswith("attention core: ")] == [
      "attention core: 6 layer(s), backward in 1 kernel pass a layer; "
      "scores in blocks of 512, the forward fetching 1024 queries x 1024 "
      "keys a grid step, 1024 keys held across a backward sweep, 4 "
      "partial dq summed outside the kernel"]
  assert model.attention_core_stats() == {
      "core_layers": 6, "backward_kernel_passes": 1, "block": 512,
      "block_q": 1024, "block_kv": 1024, "block_kv_dkv": 1024,
      "dq_partials": 4}
  monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
  assert model.attention_core_stats()["backward_kernel_passes"] == 0


def test_learning_rate_is_the_familys_warm_up_unless_the_job_states_one():
  from kf_benchmarks_tpu import learning_rate
  from kf_benchmarks_tpu import params as params_lib
  model = lm.MLAMoELMModel()
  rate = lambda step: float(model.get_learning_rate(step, 2))
  assert rate(0) == pytest.approx(2.2e-4 / 2000)
  assert rate(999) == pytest.approx(1.1e-4)
  assert rate(1999) == rate(50_000) == pytest.approx(2.2e-4)
  # The benchmark's configuration states a constant (it reuses one batch).
  p = params_lib.make_params(model="mla_moe_lm", lm_config="tiny",
                             device="cpu", init_learning_rate=2.2e-6)
  fn = learning_rate.make_learning_rate_fn(p, model, 2, 1000)
  assert float(fn(0)) == float(fn(30)) == pytest.approx(2.2e-6)


# -- C5: scanned and unrolled, remat on and off -------------------------------

def _unrolled(params, stats, depth):
  def unstack(tree):
    out = {k: v for k, v in tree.items() if k != "layers"}
    for j in range(depth):
      out[f"layer_{j}"] = jax.tree.map(lambda x: x[j], tree["layers"])
    return out
  return unstack(params), unstack(stats)


def _step_counters(module, cfg, params, stats, tokens):
  """``MOE_COUNTERS`` of one forward pass, as the step computes them."""
  _, updates = module.apply({"params": params, "batch_stats": stats}, tokens,
                            mutable=["batch_stats"])
  counters = np.asarray(lm.moe_counters(updates["batch_stats"], cfg))
  assert counters.shape == (len(lm.MOE_COUNTERS),)
  return dict(zip(lm.MOE_COUNTERS, counters))


def test_scanned_and_unrolled_stacks_agree():
  cfg = tiny()
  module, params, stats, tokens, labels = setup(cfg)
  (loss, (main, mtp, _)), grads = program(module, cfg, params, stats,
                                          tokens, labels)
  loop = lm.MLAMoELM(cfg=cfg, scan_layers=False)
  p2, s2 = _unrolled(params, stats, cfg.moe_layers)
  (loss2, (main2, mtp2, _)), grads2 = program(loop, cfg, p2, s2, tokens,
                                              labels)
  close(loss2, loss, "loss")
  close(mtp2, mtp, "MTP loss")
  trees_close(grads2, _unrolled(grads, {"layers": {}}, cfg.moe_layers)[0],
              "gradient")
  counters = _step_counters(module, cfg, params, stats, tokens)
  assert counters == _step_counters(loop, cfg, p2, s2, tokens)
  # Every mixture layer (two in the stack, the MTP block) ran one round.
  assert counters["compact_layers"] == 3 and counters["pairs_dropped"] == 0


def test_remat_on_and_off_agree():
  # The loss bit for bit (remat leaves the forward pass alone). The
  # gradients to a few float32 roundings, not bit for bit: XLA's CPU
  # backend fuses the recomputed forward into the backward operations
  # differently with and without remat (seen under jit and op by op).
  cfg = tiny()
  module, params, stats, tokens, labels = setup(cfg)
  (loss, _), grads = program(module, cfg, params, stats, tokens, labels)
  plain = lm.MLAMoELM(cfg=cfg, remat=False)
  (loss2, _), grads2 = program(plain, cfg, params, stats, tokens, labels)
  assert np.array_equal(np.asarray(loss), np.asarray(loss2))
  trees_close(grads2, grads, "gradient", rtol=2e-6)
  counters = _step_counters(module, cfg, params, stats, tokens)
  assert counters == _step_counters(plain, cfg, params, stats, tokens)
  assert counters["compact_layers"] == 3


# -- C6: the comparison bites -------------------------------------------------

def test_lower_precision_control_fails():
  cfg = tiny()
  module, params, stats, tokens, labels = setup(
      cfg, router_dtype=jnp.bfloat16)
  d, share = ref_cfg(cfg)
  (loss, (main, mtp, mids)), grads = program(module, cfg, params, stats,
                                             tokens, labels)
  want, want_grads = ref.loss_and_grads(
      d, share, params, ref.bias_from_program(stats), tokens, labels)
  with pytest.raises(AssertionError, match="router scores"):
    close(mids["layers"]["mlp"]["router_scores"][0][0], want["scores"][0],
          "router scores")
  with pytest.raises(AssertionError):
    trees_close(grads, want_grads, "gradient")


# -- the configuration, the share, the flags ----------------------------------

def test_published_configuration_and_its_share():
  cfg = lm.load_lm_config("glm-4.7-flash", 5, 8, 0)
  assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == (
      2048, 47, 154880)
  assert (cfg.dense_layers, cfg.moe_layers) == (1, 4)
  assert (cfg.first_expert, cfg.experts_held, cfg.vocab_rows) == (
      0, 8, 19360)
  assert cfg.qk_head_dim == cfg.v_head_dim == 256
  # The cut model's parameters, counted from the built tree (abstract).
  module = lm.MLAMoELM(cfg=cfg)
  shapes = jax.eval_shape(lambda: module.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree.leaves(tree))
  p = shapes["params"]
  assert count(p["dense_0"]) == 84_677_888
  assert count(p["layers"]) == 4 * 106_829_056
  assert count(p["mtp_block"]) + count(p["mtp_eh_proj"]) + 3 * 2048 == \
      115_223_808
  assert count(p) == 706_518_528


@pytest.mark.parametrize("kwargs, message", [
    (dict(lm_config="nope"), "no file"),
    (dict(lm_layers_held=48), "the model has 47 layers"),
    (dict(lm_layer_shards=7), "does not divide"),
    (dict(lm_layer_shards=8, lm_layer_shard_index=8), "shard_index"),
])
def test_bad_share_is_refused(kwargs, message):
  args = dict(lm_config="glm-4.7-flash", lm_layers_held=None,
              lm_layer_shards=1, lm_layer_shard_index=0)
  args.update(kwargs)
  with pytest.raises(ValueError, match=message):
    lm.load_lm_config(args["lm_config"], args["lm_layers_held"],
                      args["lm_layer_shards"], args["lm_layer_shard_index"])


def test_unimplemented_config_value_is_refused(tmp_path, monkeypatch):
  import json
  with open(os.path.join(lm.CONFIG_DIR, "tiny.json")) as f:
    raw = json.load(f)
  raw["topk_method"] = "greedy"
  with open(tmp_path / "other.json", "w") as f:
    json.dump(raw, f)
  monkeypatch.setattr(lm, "CONFIG_DIR", str(tmp_path))
  with pytest.raises(ValueError, match="topk_method='greedy' is not"):
    lm.load_lm_config("other")


# -- what the model does not compose with yet is refused ----------------------

@pytest.mark.parametrize("kwargs, message", [
    (dict(model="transformer_lm", seq_len=128), "--seq_len is read by"),
    (dict(model="trivial", lm_config="tiny"), "--lm_config is read by"),
    (dict(model="resnet50", lm_layer_shards=8), "--lm_layer_shards is read"),
    (dict(model="trivial", lm_layers_held=2), "--lm_layers_held is read"),
    (dict(model="trivial", lm_layer_shard_index=0),
     "--lm_layer_shard_index is read"),
    (dict(num_devices=2), "runs on one device"),
    (dict(shard_optimizer_state=True), "--shard_params / --shard_optim"),
    (dict(packed_sequences=True), "transformer_lm input form"),
    (dict(forward_only=True), "trains only"),
    (dict(eval=True), "trains only"),
    (dict(num_grad_accum=2), "once per MICROBATCH"),
    (dict(steps_per_dispatch=4), "not stacked through the chunked"),
])
def test_validation_refuses(kwargs, message):
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  args = dict(model="mla_moe_lm", device="cpu", batch_size=2)
  args.update(kwargs)
  with pytest.raises(validation.ParamError, match=message):
    validation.validate_cross_flags(params_lib.make_params(**args))


def test_flags_stay_out_of_other_models_fingerprints():
  # None by default, and None drops out of the config fingerprint
  # (analysis/baseline.py): the tuned table and the compile ledger of
  # every other model key as before this model's flags existed.
  from kf_benchmarks_tpu import params as params_lib
  p = params_lib.make_params(model="trivial", device="cpu")
  assert [getattr(p, f) for f in (
      "seq_len", "lm_config", "lm_layers_held", "lm_layer_shards",
      "lm_layer_shard_index")] == [None] * 5


# -- two losses through one head kernel ---------------------------------------

def test_pair_loss_matches_two_plain_losses_and_holds_no_btv():
  from kf_benchmarks_tpu.ops import fused_loss
  b, t, d, v = 2, 32, 16, 40
  keys = jax.random.split(jax.random.PRNGKey(0), 4)
  h_main, h_mtp = (jax.random.normal(k, (b, t, d)) for k in keys[:2])
  kernel = jax.random.normal(keys[2], (d, v))
  labels = jax.random.randint(keys[3], (b, t), 0, v)

  def plain(h_main, h_mtp, kernel):
    def nll(h, y):
      logp = jax.nn.log_softmax(h @ kernel, -1)
      return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
    return (jnp.mean(nll(h_main, labels)),
            jnp.mean(nll(h_mtp, jnp.roll(labels, -1, 1))[:, :-1]))

  def fused(h_main, h_mtp, kernel):
    return fused_loss.fused_softmax_xent_pair((h_main, h_mtp), kernel,
                                              labels, chunk_size=8)
  for got, want in zip(fused(h_main, h_mtp, kernel),
                       plain(h_main, h_mtp, kernel)):
    close(got, want, "loss")
  total = lambda fn: lambda *a: fn(*a)[0] + 0.3 * fn(*a)[1]
  trees_close(jax.grad(total(fused), argnums=(0, 1, 2))(h_main, h_mtp,
                                                        kernel),
              jax.grad(total(plain), argnums=(0, 1, 2))(h_main, h_mtp,
                                                        kernel), "gradient")
  # One chunk of logits at a time, forward and backward: nothing of the
  # (B, T, V) size is ever held.
  hlo = jax.jit(jax.grad(total(fused), argnums=(0, 1, 2))).lower(
      h_main, h_mtp, kernel).compile().as_text()
  assert f"f32[{b},{t},{v}]" not in hlo and f"f32[{b},8,{v}]" in hlo
  # Without MTP hidden states it is the one-loss function.
  main, mtp = fused_loss.fused_softmax_xent_pair((h_main, None), kernel,
                                                 labels, chunk_size=8)
  assert mtp is None
  close(main, plain(h_main, h_mtp, kernel)[0], "main loss alone")
