"""mla_moe_lm's ``xing4_0`` family (models/mla_moe_lm.py: ``hc_mult``
residual streams under manifold-constrained hyper-connections, YaRN,
latent attention over a block of the heads, v at its own head size)
against the plain reference ``benchmarks/references/xing4.0-29b-a4b.py``:
tiny sizes, seeded random weights, float32, on the CPU.

TOLERANCE. Program and reference are both float32 here and differ in
the ORDER of sums alone (the scanned loss chunks, the sorted grouped
product against a dense loop over experts, the norm's statistic applied
after the coefficients' product rather than before it, the CPU backend's
threads): float32 rounding carried through three blocks of four streams
at unit gain (weights scaled up so that every branch matters, the
coefficients' token-dependent scales at 1 so that every coefficient
moves with its token). ``RTOL`` = 3e-4 of the largest magnitude of the
tensor compared, the glm family's limit (tests/test_mla_moe_lm.py), with
the same reasons. The lower-precision control (the coefficient path in
bfloat16, 8 bits of mantissa) reads 1e-3 and more on the coefficients:
it has to FAIL the limit, and does (``test_control_fails``).
"""

import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import mla_moe_lm as lm
from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.ops import mhc
from kf_benchmarks_tpu.ops import rotary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-4
PRESET = "tiny-xing"


def _load_reference():
  path = os.path.join(REPO, "benchmarks", "references", "xing4.0-29b-a4b.py")
  spec = importlib.util.spec_from_file_location("_xing_reference", path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


ref = _load_reference()


def tiny(layers_held=3, shards=4, shard_index=1, head_shards=2, **changes):
  return dataclasses.replace(
      lm.load_lm_config(PRESET, layers_held, shards, shard_index,
                        head_shards=head_shards), **changes)


def ref_cfg(cfg):
  """The configuration's file as the config.json dict the reference
  reads (its own keys: ``rope_scaling`` and the ``hc_*`` ones), with the
  LMConfig's changes, and the share."""
  _, raw = lm.read_config_file(PRESET)
  d = dict(raw, routed_scaling_factor=cfg.routed_scaling_factor,
           hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
           num_nextn_predict_layers=cfg.num_nextn_predict_layers)
  if not cfg.yarn_factor:
    d["rope_scaling"] = None
  share = {"layers_held": cfg.layers_held, "shards": cfg.shards,
           "shard_index": cfg.shard_index}
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


def lively(params):
  """Weights scaled up so that every branch matters, and every
  hyper-connection's token-dependent scales at 1."""
  def one(path, x):
    name = path[-1].key
    if name == "alpha":
      return jnp.ones_like(x)
    return x * 8 if x.ndim > 1 and name != "phi" else x
  return jax.tree_util.tree_map_with_path(one, params)


def setup(cfg, seed=0, batch=2, seq=16, **module_kwargs):
  module = lm.MLAMoELM(cfg=cfg, **module_kwargs)
  tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              cfg.vocab_rows)
  variables = module.init({"params": jax.random.PRNGKey(seed + 1)}, tokens)
  params = lively(variables["params"])
  stats = jax.tree_util.tree_map_with_path(
      lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               x.shape)
      if path[-1].key == "select_bias" else x, variables["batch_stats"])
  return module, params, stats, tokens, jnp.roll(tokens, -1, axis=1)


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


def streams_of(cfg, seed=3, batch=2, seq=16):
  return jax.random.normal(jax.random.PRNGKey(seed),
                           (batch, seq, cfg.hc_mult, cfg.hidden_size))


def one_layer(cfg, mixture, x):
  block = lm.Block(cfg=cfg, mixture=mixture)
  variables = block.init({"params": jax.random.PRNGKey(4)}, x)
  params = lively(variables["params"])
  stats = dict(variables["batch_stats"])
  if mixture:
    stats["mlp"] = dict(stats["mlp"], select_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(7), stats["mlp"]["select_bias"].shape))
  return block, params, stats


def ref_layer(cfg, key, params):
  d, share = ref_cfg(cfg)
  return ref.from_program(
      {key: params, "embed_tokens": {"embedding": 0}, "lm_head": 0,
       "norm": {"scale": 0}}, dict(d, num_nextn_predict_layers=0),
      share)["layers"][0]


# -- the layers, the module and the model against the reference ---------------

@pytest.mark.parametrize("case", ["dense_layer", "mixture_layer",
                                  "whole_model", "no_mtp"])
def test_against_reference(case):
  if case in ("dense_layer", "mixture_layer"):
    cfg = tiny()
    mixture = case == "mixture_layer"
    x = streams_of(cfg)
    block, params, stats = one_layer(cfg, mixture, x)
    d, share = ref_cfg(cfg)
    key = "layers" if mixture else "dense_0"
    bias = stats["mlp"]["select_bias"] if mixture else None
    wrap = (lambda p: jax.tree.map(lambda a: a[None], p)) if mixture else (
        lambda p: p)

    def prog(p, x):
      return jnp.sum(jnp.sin(block.apply(
          {"params": p, "batch_stats": stats}, x)[0]))

    def plain(p, x):
      return jnp.sum(jnp.sin(ref.block(d, share, ref_layer(
          cfg, key, wrap(p)), x, bias)[0]))
    close(block.apply({"params": params, "batch_stats": stats}, x)[0],
          ref.block(d, share, ref_layer(cfg, key, wrap(params)), x,
                    bias)[0], "layer output")
    trees_close(jax.grad(prog, argnums=(0, 1))(params, x),
                jax.grad(plain, argnums=(0, 1))(params, x), "gradient")
    return
  cfg = tiny(num_nextn_predict_layers=0 if case == "no_mtp" else 1)
  module, params, stats, tokens, labels = setup(cfg)
  d, share = ref_cfg(cfg)
  (loss, (main, mtp, mids)), grads = program(module, cfg, params, stats,
                                             tokens, labels)
  want, want_grads = ref.loss_and_grads(
      d, share, params, ref.bias_from_program(stats), tokens, labels)
  close(loss, want["loss"], "loss")
  close(main, want["main_loss"], "main loss")
  close(mids["hidden_last"][0], want["hidden_last"], "last streams")
  for layer in range(cfg.moe_layers):
    close(mids["layers"]["hidden_in"][0][layer], want["hidden"][1 + layer],
          f"streams into mixture layer {layer}")
  if case == "whole_model":
    close(mtp, want["mtp_loss"], "MTP loss")
    close(mids["hidden_mtp"][0], want["hidden_mtp"], "MTP streams")
  trees_close(grads, want_grads, "gradient")


def test_coefficients_and_probe_against_reference():
  """What the step leaves for the check: the probe's streams and their
  three coefficients, and the largest Sinkhorn residual."""
  cfg = tiny()
  x = streams_of(cfg)
  block, params, stats = one_layer(cfg, True, x)
  _, mutated = block.apply({"params": params, "batch_stats": stats}, x,
                           mutable=["batch_stats"])
  d, _ = ref_cfg(cfg)
  layer = ref_layer(cfg, "layers", jax.tree.map(lambda a: a[None], params))
  hc = mutated["batch_stats"]["attn_hc"]
  np.testing.assert_array_equal(hc["probe_streams"], x.reshape(32, 4, 32))
  pre, post, res = ref.hc_coefficients(d, layer["attn_hc"],
                                       hc["probe_streams"])
  close(hc["probe_pre"], pre, "h_pre")
  close(hc["probe_post"], post, "h_post")
  close(hc["probe_res"], res, "H_res")
  assert 0 < float(hc["sinkhorn_residual"]) < 1e-4
  assert float(jnp.max(jnp.abs(jnp.sum(res, -2) - 1))) < 1e-5


# -- Sinkhorn-Knopp -----------------------------------------------------------

def test_sinkhorn_rows_and_columns_sum_to_one():
  # Logits of the spread the residual mix starts from (its static bias,
  # ``HC_RES_BIAS_STD``).
  logits = jax.random.normal(jax.random.PRNGKey(0), (64, 4, 4))
  m = mhc.sinkhorn(logits, 20, 1e-6)
  assert float(jnp.min(m)) > 0
  # The last step divides by the column sums: they are 1 within eps; the
  # rows within what 20 iterations leave.
  assert float(jnp.max(jnp.abs(jnp.sum(m, -2) - 1))) <= 1e-6 + 1e-6
  assert float(mhc.sinkhorn_residual(m)) < 1e-4
  # One iteration leaves rows far from 1: the check's fault reads there.
  assert float(mhc.sinkhorn_residual(mhc.sinkhorn(logits, 1, 1e-6))) > 1e-2


def test_sinkhorn_gradient_against_finite_differences():
  """Autodiff through the 20 iterations against central differences, in
  float64 (truncation h^2 = 1e-12, rounding 1e-16 / h = 1e-10)."""
  with jax.enable_x64(True):
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 4), jnp.float64)
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 4), jnp.float64)
    f = jax.jit(lambda z: jnp.sum(w * mhc.sinkhorn(z, 20, 1e-6)))
    grad = np.asarray(jax.grad(f)(logits))
    h = 1e-6
    numeric = np.zeros((4, 4))
    for i in range(4):
      for j in range(4):
        e = np.zeros((4, 4))
        e[i, j] = h
        numeric[i, j] = (float(f(logits + e)) - float(f(logits - e))) / (2 * h)
  np.testing.assert_allclose(grad, numeric, rtol=1e-6, atol=1e-9)


# -- YaRN ---------------------------------------------------------------------

def test_yarn_tables_against_the_formula():
  cfg = lm.load_lm_config("xing4.0-29b-a4b", 5, 8, 0, 1, head_shards=8)
  r, theta, y = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn
  low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) /
                   (2 * math.log(theta)))
  high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) /
                   (2 * math.log(theta)))
  want = []
  for i in range(r // 2):
    m = 1 - min(max((i - low) / (high - low), 0.0), 1.0)
    base = theta ** (-2 * i / r)
    want.append((1 - m) * base / 64 + m * base)
  got = rotary.yarn_inv_freq(r, theta, y)
  np.testing.assert_allclose(got, want, rtol=2e-6)
  np.testing.assert_allclose(ref.inv_freq(lm.read_config_file(
      "xing4.0-29b-a4b")[1]), want, rtol=2e-6)
  # The pairs below ``low`` keep RoPE's frequency, those above ``high``
  # are divided by the factor.
  assert (low, high) == (10, 23)
  assert got[low] == pytest.approx(theta ** (-2 * low / r), rel=1e-6)
  assert got[high] == pytest.approx(theta ** (-2 * high / r) / 64, rel=1e-6)
  # The scores' scale: (192)^-1/2 (0.1 ln 64 + 1)^2, folded into q.
  assert cfg.softmax_scale == pytest.approx(
      192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
  assert round(cfg.softmax_scale, 5) == 0.14468
  assert not lm.exact_in_any_dtype(cfg.softmax_scale)
  # The stage's tables: cos and signed sin at those frequencies over the
  # trailing 64 dimensions of q's 192, the rest passing through.
  tabs = rotary.stage_tables((1, 32, 4, 192), 64, theta, False,
                             jnp.float32, y)
  ang = np.arange(32)[:, None] * np.asarray(want)[None]
  np.testing.assert_allclose(tabs.cos[:, 128:160], np.cos(ang), atol=2e-6)
  np.testing.assert_allclose(tabs.sin[:, 160:], np.sin(ang), atol=2e-6)
  np.testing.assert_allclose(tabs.sin[:, 128:160], -np.sin(ang), atol=2e-6)
  assert float(jnp.max(jnp.abs(tabs.cos[:, :128] - 1))) == 0.0


def test_plain_rope_tables_are_unchanged_without_yarn():
  plain = rotary.tables(64, 64, 1e6, 128)
  again = rotary.tables(64, 64, 1e6, 128, None)
  np.testing.assert_array_equal(plain.cos, again.cos)
  np.testing.assert_array_equal(plain.sin, again.sin)


# -- the shares add up to the uncut layer -------------------------------------

def test_head_and_expert_shares_add_up_to_the_uncut_layer():
  """One mixture layer's two sublayers, uncut, against the sum of the
  four head shares' attention and the four expert shares' routed parts,
  the shared expert and everything held whole counted once: the layer's
  output through the uncut reference."""
  whole = tiny(shards=1, shard_index=0, head_shards=1)
  d, share = ref_cfg(whole)
  x = streams_of(whole)
  block, params, stats = one_layer(whole, True, x)
  uncut = ref.block(d, share, ref_layer(whole, "layers", jax.tree.map(
      lambda a: a[None], params)), x, stats["mlp"]["select_bias"])[0]
  hc = lambda name, x: lm.HyperConnection(cfg=whole).apply(
      {"params": params[name], "batch_stats": stats[name]}, x)
  h, h_pre, h_post, h_res, *_ = hc("attn_hc", x)
  normed = lm.RMSNorm(whole.rms_norm_eps).apply(
      {"params": params["input_layernorm"]}, h)
  attn = params["self_attn"]
  heads = whole.num_attention_heads
  y = 0.0
  for index in range(4):
    cfg = tiny(shards=4, shard_index=index, head_shards=4)
    cols = lambda w, per: w.reshape(w.shape[0], heads, per)[
        :, index:index + 1].reshape(w.shape[0], -1)
    part = dict(attn, q_b_proj={"kernel": cols(
        attn["q_b_proj"]["kernel"], cfg.qk_head_dim)}, kv_b_proj={
            "kernel": cols(attn["kv_b_proj"]["kernel"],
                           cfg.qk_nope_head_dim + cfg.v_head_dim)},
                o_proj={"kernel": attn["o_proj"]["kernel"].reshape(
                    heads, cfg.v_head_dim, -1)[index]})
    y = y + lm.MLAttention(cfg=cfg).apply({"params": part}, normed)
  x = mhc.residual_mix(x, h_res, h_post, y)
  h, h_pre, h_post, h_res, *_ = hc("mlp_hc", x)
  normed = lm.RMSNorm(whole.rms_norm_eps).apply(
      {"params": params["post_attention_layernorm"]}, h)
  mlp, mstats = params["mlp"], {"select_bias": stats["mlp"]["select_bias"]}
  layer = ref_layer(whole, "layers", jax.tree.map(lambda a: a[None], params))
  shared = ref.swiglu(layer["mlp"]["shared"], normed.reshape(
      -1, whole.hidden_size)).reshape(normed.shape)
  routed = 0.0
  for index in range(4):
    cfg = tiny(shards=4, shard_index=index, head_shards=1)
    rows = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
    part = dict(mlp, **{k: mlp[k][rows] for k in (
        "experts_gate", "experts_up", "experts_down")})
    routed = routed + lm.MoE(cfg=cfg).apply(
        {"params": part, "batch_stats": dict(
            mstats, **{k: v for k, v in stats["mlp"].items()
                       if k != "select_bias"})}, normed) - shared
  close(mhc.residual_mix(x, h_res, h_post, routed + shared), uncut,
        "the layer from the sum of the shares")


# -- the published configuration and its share --------------------------------

def test_published_configuration_and_its_parameters():
  cfg = lm.load_lm_config("xing4.0-29b-a4b", 5, 8, 0, 1, head_shards=8)
  assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == (
      3584, 40, 131072)
  assert (cfg.dense_layers, cfg.moe_layers, cfg.hc_mult) == (1, 4, 4)
  assert (cfg.first_head, cfg.heads_held) == (0, 4)
  assert (cfg.first_expert, cfg.experts_held, cfg.vocab_rows) == (
      0, 8, 16384)
  assert (cfg.qk_head_dim, cfg.v_head_dim) == (192, 128)
  module = lm.MLAMoELM(cfg=cfg)
  shapes = jax.eval_shape(lambda: module.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree.leaves(tree))
  p = shapes["params"]
  hc = 2 * (4 * 3584 * 24 + 24 + 3)
  assert count(p["dense_0"]["self_attn"]) == 7_767_296
  assert count(p["dense_0"]["attn_hc"]) + count(p["dense_0"]["mlp_hc"]) == hc
  assert count(p["dense_0"]) == 107_553_078
  assert count(p["layers"]) == 4 * 107_782_454
  assert count(p["mtp_block"]) + count(p["mtp_eh_proj"]) + 3 * 3584 == \
      133_483_318
  assert count(p["embed_tokens"]) + count(p["lm_head"]) == 117_440_512
  assert count(p) == 789_610_308
  # The probe and the residual ride ``batch_stats``: 12 sublayers.
  assert cfg.sublayers == 12


@pytest.mark.parametrize("change, message", [
    (dict(rope_scaling=None), "rope_scaling=None is not implemented"),
    (dict(rope_scaling={"type": "linear", "factor": 4}),
     "implements YaRN alone"),
    (dict(hc_mult=1), "hc_mult=1"),
    (dict(scoring_func="softmax"), "scoring_func='softmax' is not"),
])
def test_unimplemented_config_value_is_refused(tmp_path, monkeypatch, change,
                                               message):
  with open(os.path.join(lm.CONFIG_DIR, PRESET + ".json")) as f:
    raw = json.load(f)
  raw.update(change)
  with open(tmp_path / "other.json", "w") as f:
    json.dump(raw, f)
  monkeypatch.setattr(lm, "CONFIG_DIR", str(tmp_path))
  with pytest.raises(ValueError, match=message):
    lm.load_lm_config("other")


def test_streams_in_another_family_are_refused(tmp_path, monkeypatch):
  with open(os.path.join(lm.CONFIG_DIR, "tiny.json")) as f:
    raw = json.load(f)
  raw["hc_mult"] = 4
  with open(tmp_path / "other.json", "w") as f:
    json.dump(raw, f)
  monkeypatch.setattr(lm, "CONFIG_DIR", str(tmp_path))
  with pytest.raises(ValueError, match="one residual stream"):
    lm.load_lm_config("other")


@pytest.mark.parametrize("args, message", [
    (("xing4.0-29b-a4b", 5, 8, 0, 1, None, 3), "does not divide"),
    (("xing4.0-29b-a4b", 5, 8, 4, 1, None, 4), "holds no heads"),
    (("trinity-mini", 5, 8, 0, 1, None, 8), "latent attention alone"),
])
def test_bad_head_share_is_refused(args, message):
  with pytest.raises(ValueError, match=message):
    lm.load_lm_config(*args)


@pytest.mark.parametrize("kwargs, message", [
    (dict(lm_config="xing4.0-29b-a4b", lm_head_shards=5), "does not divide"),
    (dict(lm_config="trinity-mini", lm_head_shards=2),
     "latent attention alone"),
    (dict(model="trivial", lm_head_shards=2), "--lm_head_shards is read"),
])
def test_validation_refuses(kwargs, message):
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  args = dict(model="mla_moe_lm", device="cpu", batch_size=2)
  args.update(kwargs)
  with pytest.raises(validation.ParamError, match=message):
    validation.validate_cross_flags(params_lib.make_params(**args))


# -- the controls fail --------------------------------------------------------

def _coefficients(cfg, **module_kwargs):
  x = streams_of(cfg)
  module = lm.HyperConnection(cfg=cfg, **module_kwargs)
  variables = module.init({"params": jax.random.PRNGKey(4)}, x)
  params = lively(variables["params"])
  return module.apply({"params": params, "batch_stats": variables[
      "batch_stats"]}, x), x, params


def test_control_fails():
  """The coefficient path in bfloat16 (the lower-precision control) and
  one Sinkhorn iteration fail the limit; float32 passes it."""
  cfg = tiny()
  d, _ = ref_cfg(cfg)
  (_, pre, post, res, *_), x, params = _coefficients(cfg)
  want = ref.hc_coefficients(d, params, x)
  for got, exp, what in zip((pre, post, res), want, ("pre", "post", "res")):
    close(got, exp, what)
  (_, pre, post, res, *_), _, _ = _coefficients(cfg, mhc_dtype=jnp.bfloat16)
  with pytest.raises(AssertionError):
    close(res, want[2], "res in bfloat16")
  (_, _, _, res, *_), _, _ = _coefficients(dataclasses.replace(
      cfg, hc_sinkhorn_iters=1))
  with pytest.raises(AssertionError):
    close(res, want[2], "res after one iteration")


# -- what the run states of itself --------------------------------------------

@pytest.fixture(scope="module")
def two_step_stats():
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util
  lines = []
  orig, log_util.log_fn = log_util.log_fn, lambda msg: lines.append(str(msg))
  try:
    params = params_lib.make_params(
        model="mla_moe_lm", lm_config=PRESET, seq_len=16, batch_size=2,
        lm_first_layer_held=0, lm_layers_held=3, lm_layer_shards=4,
        lm_layer_shard_index=1, lm_head_shards=2, device="cpu",
        optimizer="adam", num_batches=2, num_warmup_batches=0,
        display_every=1, tf_random_seed=5)
    stats = benchmark.BenchmarkCNN(benchmark.setup(params)).run()
  finally:
    log_util.log_fn = orig
  return stats, lines


def test_streams_and_share_reach_the_stats(two_step_stats):
  stats, lines = two_step_stats
  mhc = stats["mhc"]
  assert (mhc["streams"], mhc["sublayers"], mhc["sinkhorn_iters"]) == (
      4, 8, 20)
  assert 0 < mhc["sinkhorn_residual"] < 1e-3
  assert stats["moe"]["experts_held"] == 4 and stats["moe"]["steps"] == 2
  share = [l for l in lines if l.startswith("mla_moe_lm share: ")]
  assert len(share) == 1
  assert "chip 1 of 4 per layer: heads 2-3 of 4 (of 2 over the heads); " \
      "experts 4-7 of 16" in share[0]
  assert len([l for l in lines if l.startswith("mhc: 4 residual streams")]) \
      == 1
  # q's head of 6 + 4 rotated at YaRN's frequencies, 2 heads held.
  assert stats["rotary"]["q"]["heads"] == 2
