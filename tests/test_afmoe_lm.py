"""The grouped-query window/full decoder of ``models/mla_moe_lm.py``
(``model_type: afmoe``, the Trinity family) against the plain reference
``benchmarks/references/trinity-mini.py``: the ``tiny-afmoe`` preset (4
query heads over 2 key heads of size 8, a window of 24 under sequences
of 32, layers [window, window, full, window, window], 1 dense + 4
mixture layers, 16 experts at top-4), seeded random weights, float32, on
the CPU; the share tied to the uncut model; the built tree's parameter
count at the published widths (abstract shapes).

TOLERANCE. As tests/test_mla_moe_lm.py: program and reference are both
float32 here and differ in the ORDER of sums alone; ``RTOL`` is 3e-4 of
the largest magnitude of the tensor compared. The controls (a window one
key off, RoPE in the full layer, the gate or the post-norms left out,
the router in bfloat16) each have to FAIL it.
"""

import dataclasses
import importlib.util
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import mla_moe_lm as lm
from kf_benchmarks_tpu.models import model as model_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-4
SEQ = 32


def _load(path, label):
  spec = importlib.util.spec_from_file_location(label, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


ref = _load(os.path.join(REPO, "benchmarks", "references",
                         "trinity-mini.py"), "_trinity_reference")


def published(name="tiny-afmoe"):
  """``lm_configs/<name>.json`` as the reference reads it: the family's
  own key names."""
  import json
  with open(os.path.join(lm.CONFIG_DIR, name + ".json")) as f:
    return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def tiny(layers_held=5, shards=4, shard_index=1, first_layer=0, **changes):
  return dataclasses.replace(
      lm.load_lm_config("tiny-afmoe", layers_held, shards, shard_index,
                        first_layer), **changes)


def share_of(cfg):
  return {"layers_held": cfg.layers_held, "first_layer": cfg.first_layer,
          "shards": cfg.shards, "shard_index": cfg.shard_index}


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


def setup(cfg, seed=0, batch=2, seq=SEQ, **module_kwargs):
  module = lm.MLAMoELM(cfg=cfg, **module_kwargs)
  tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              cfg.vocab_rows)
  variables = module.init({"params": jax.random.PRNGKey(seed + 1)}, tokens)
  # Initialised at 0.02 every branch is far below the residual (which muP
  # multiplies by sqrt(32)); the comparison wants each to matter. Norm
  # scales move off 1 so that a norm left out, or put elsewhere, shows.
  def scaled(path, x):
    name = jax.tree_util.keystr(path)
    if not name.endswith("['scale']"):
      return x * 8
    return x * (1 + 0.3 * jax.random.normal(jax.random.PRNGKey(
        zlib.crc32(name.encode())), x.shape))
  params = jax.tree_util.tree_map_with_path(scaled, variables["params"])
  bias = jax.tree.map(
      lambda x: 0.05 * jax.random.normal(jax.random.PRNGKey(7), x.shape),
      variables.get("batch_stats", {}))
  return module, params, bias, tokens, jnp.roll(tokens, -1, axis=1)


def program(module, cfg, params, batch_stats, tokens, labels):
  """(loss, intermediates) and gradients, through the module and the
  model's own loss code."""
  model = lm.MLAMoELMModel()
  model.cfg = cfg

  def fn(p):
    (heads, _), mods = module.apply(
        {"params": p, "batch_stats": batch_stats}, tokens,
        mutable=["intermediates"])
    loss = model.loss_function(
        model_lib.BuildNetworkResult(logits=(heads, None)), labels)
    return loss, mods["intermediates"]
  return jax.jit(jax.value_and_grad(fn, has_aux=True))(params)


def reference(cfg, params, stats, tokens, labels, d=None):
  return ref.loss_and_grads(d or published(), share_of(cfg), params,
                            ref.bias_from_program(stats), tokens, labels)


# -- the decoder against the reference ----------------------------------------

@pytest.mark.parametrize("first_layer, layers_held", [
    (0, 5),    # the whole preset: dense window, window, full, window, window
    (1, 4),    # a stage that starts at a mixture layer
    (2, 2),    # [full, window]
])
def test_whole_model_against_reference(first_layer, layers_held):
  cfg = tiny(layers_held=layers_held, first_layer=first_layer)
  module, params, stats, tokens, labels = setup(cfg)
  (loss, mids), grads = program(module, cfg, params, stats, tokens, labels)
  want, want_grads = reference(cfg, params, stats, tokens, labels)
  close(loss, want["loss"], "loss")
  close(mids["hidden_last"][0], want["hidden_last"], "last hidden state")
  assert len(want["scores"]) == cfg.moe_layers
  for layer in range(cfg.moe_layers):
    mid = mids[f"layer_{layer}"]
    close(mid["hidden_in"][0], want["hidden"][cfg.dense_layers + layer],
          f"input of mixture layer {layer}")
    close(mid["mlp"]["router_scores"][0], want["scores"][layer],
          f"router scores of layer {layer}")
    assert np.array_equal(mid["mlp"]["topk_idx"][0], want["idx"][layer])
  trees_close(grads, want_grads, "gradient")


def test_the_stack_is_the_configurations_own_layer_types():
  cfg = tiny()
  assert cfg.windows == (24, 24, None, 24, 24)
  assert (cfg.dense_layers, cfg.moe_layers) == (1, 4)
  assert tiny(layers_held=3, first_layer=2).windows == (None, 24, 24)
  assert tiny(layers_held=3, first_layer=2).dense_layers == 0
  # Mixture layers of ONE kind are scanned, as the latent-attention
  # family's are; of two kinds they are unrolled.
  module, params, *_ = setup(tiny(layers_held=2, first_layer=3))
  assert "layers" in params and "layer_0" not in params
  module, params, *_ = setup(cfg)
  assert sorted(params) == ["dense_0", "embed_tokens", "layer_0", "layer_1",
                            "layer_2", "layer_3", "lm_head", "norm"]
  assert sorted(params["layer_0"]["self_attn"]) == [
      "gate_proj", "k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
  assert params["layer_0"]["self_attn"]["k_proj"]["kernel"].shape == (32, 16)
  assert params["layer_0"]["self_attn"]["q_norm"]["scale"].shape == (8,)


def test_scanned_stack_of_one_kind_agrees_with_reference():
  cfg = tiny(layers_held=2, first_layer=3)     # [window, window]
  module, params, stats, tokens, labels = setup(cfg)
  (loss, _), grads = program(module, cfg, params, stats, tokens, labels)
  want, want_grads = reference(cfg, params, stats, tokens, labels)
  close(loss, want["loss"], "loss")
  trees_close(grads, want_grads, "gradient")


def test_remat_on_and_off_agree():
  cfg = tiny()
  module, params, stats, tokens, labels = setup(cfg)
  (loss, _), grads = program(module, cfg, params, stats, tokens, labels)
  plain = lm.MLAMoELM(cfg=cfg, remat=False)
  (loss2, _), grads2 = program(plain, cfg, params, stats, tokens, labels)
  close(loss2, loss, "loss")
  trees_close(grads2, grads, "gradient")


# -- what a block keeps for its backward pass ----------------------------------

def _loss_of_params(cfg, **setup_kwargs):
  """(the training loss as a function of the parameters, the parameters)
  of ``setup``'s stack, router state updated as a step updates it."""
  module, params, stats, tokens, labels = setup(cfg, **setup_kwargs)
  model = lm.MLAMoELMModel()
  model.cfg = cfg

  def loss(p):
    (heads, _), _ = module.apply({"params": p, "batch_stats": stats}, tokens,
                                 mutable=["batch_stats"])
    return model.loss_function(
        model_lib.BuildNetworkResult(logits=(heads, None)), labels)
  return loss, params


def _routed_loops_in_the_gradient(cfg, seq=128):
  """How many rounds loops of the routed path (``expert._while_pairs_left``)
  the COMPILED gradient of the stack holds, under the model's own remat
  setting. 2 x 128 tokens x 4 choices are 1,024 pairs in rounds of 512
  rows (2 of 16 experts held), so the size has the loop; the grouped
  products sit in its body, so a loop more is a pass of them more. What
  remat's repeated forward costs is XLA's decision (it merges a repeated
  operation with its first copy, and never a loop), hence the compiled
  text and not the jaxpr."""
  loss, params = _loss_of_params(cfg, seq=seq)
  text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
  return sum(" while(" in line and "/moe_route/" in line
             for line in text.splitlines())


@pytest.mark.parametrize("post_norms", [True, False])
def test_routed_path_runs_once_forward_under_either_norm_placement(
    post_norms):
  # A post-norm block's backward pass reads the feed-forward's output
  # (``post_mlp_layernorm``'s input); a pre-norm block's does not. Either
  # way a mixture layer runs the rounds loop twice, forward and backward:
  # under nn.remat the post-norm block ran it a third time to have that
  # output again (PERF.md section 6, PR 35).
  cfg = tiny(shards=8, post_norms=post_norms)
  assert cfg.windows == (24, 24, None, 24, 24) and cfg.moe_layers == 4
  assert _routed_loops_in_the_gradient(cfg) == 2 * cfg.moe_layers


@pytest.mark.parametrize("first_layer, layers_held", [(0, 5), (2, 2)])
def test_unrolled_layers_without_remat_change_no_bit(first_layer, layers_held,
                                                     monkeypatch):
  # Same operations, other residuals: the unrolled layers under nn.remat
  # (the rule before PR 35) give the loss and every gradient to the last
  # bit. Operation by operation (no jit): in one compiled program the
  # last digit is also XLA's choice of fusions, which follows what is
  # kept.
  cfg = tiny(layers_held=layers_held, first_layer=first_layer)
  loss, params = _loss_of_params(cfg)
  assert "layer_0" in params and "layers" not in params

  def loss_and_gradients():
    with jax.disable_jit():
      return jax.tree.leaves(jax.value_and_grad(loss)(params))
  plain = loss_and_gradients()
  monkeypatch.setattr(lm, "Block", lm.nn.remat(lm.Block, prevent_cse=False))
  rematted = loss_and_gradients()
  assert len(plain) == len(rematted) > 1
  for a, b in zip(plain, rematted):
    assert np.array_equal(a, b)


# Each control is ONE departure from the published layer, planted in the
# program from outside (experiments/lm_precision_control.py plants the
# same in the benchmark's cell); the comparison that passes above has to
# see it.
@pytest.mark.parametrize("control", [
    "window_2047", "window_2049", "rope_in_full", "no_gate", "no_post_norms",
    "router_bf16"])
def test_control_fails(control, monkeypatch):
  _controls().plant(control, lambda obj, name, value: monkeypatch.setattr(
      obj, name, value, raising=False))
  cfg = tiny()
  assert cfg.sliding_window == {"window_2047": 23, "window_2049": 25}.get(
      control, 24)
  kwargs = ({"router_dtype": jnp.bfloat16} if control == "router_bf16"
            else {})       # (planted in the model's make_module)
  module, params, stats, tokens, labels = setup(cfg, **kwargs)
  (loss, mids), grads = program(module, cfg, params, stats, tokens, labels)
  want, want_grads = reference(cfg, params, stats, tokens, labels)
  with pytest.raises(AssertionError):
    close(mids["hidden_last"][0], want["hidden_last"], "last hidden state")
  with pytest.raises(AssertionError):
    trees_close(grads, want_grads, "gradient")
  if control == "router_bf16":
    with pytest.raises(AssertionError, match="router scores"):
      close(mids["layer_0"]["mlp"]["router_scores"][0], want["scores"][0],
            "router scores")


def _controls():
  return _load(os.path.join(REPO, "experiments", "lm_precision_control.py"),
               "_lm_controls")


# -- the share adds up to the model -------------------------------------------

def _mixture_as_ref(p):
  return {"router": p["router"], "experts_gate": p["experts_gate"],
          "experts_up": p["experts_up"], "experts_down": p["experts_down"],
          "shared": {k: p["shared_experts"][k]["kernel"]
                     for k in ("gate_proj", "up_proj", "down_proj")}}


def test_expert_shares_add_up_to_the_uncut_layer():
  """The routed parts of all 8 shares, and the shared expert counted
  once, are the uncut reference's mixture layer."""
  d = published()
  whole = tiny(shards=1, shard_index=0)
  moe = lm.MoE(cfg=whole)
  x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, whole.hidden_size))
  variables = moe.init({"params": jax.random.PRNGKey(4)}, x)
  params = jax.tree.map(lambda p: p * 8, variables["params"])
  stats = variables["batch_stats"]
  uncut, _, _ = ref.mixture(d, share_of(whole), _mixture_as_ref(params),
                            stats["select_bias"], x)
  shared = ref.swiglu(_mixture_as_ref(params)["shared"], x)
  total = jnp.zeros_like(x)
  for index in range(8):
    cfg = tiny(shards=8, shard_index=index)
    assert cfg.experts_held == 2
    rows = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
    part = dict(params, **{k: params[k][rows] for k in (
        "experts_gate", "experts_up", "experts_down")})
    mine = lm.MoE(cfg=cfg).apply({"params": part, "batch_stats": stats}, x)
    # ... and each share is the reference's own routed part of it.
    close(mine - shared, ref.routed(d, share_of(cfg), _mixture_as_ref(part),
                                    stats["select_bias"], x)[0],
          f"routed part of share {index}")
    total += mine - shared
  close(total + shared, uncut, "sum of the shares")


def test_vocabulary_slices_concatenate_to_the_uncut_logits():
  d = published()
  # (The dense layer alone: a share of it cuts nothing but the vocabulary.)
  whole = tiny(layers_held=1, shards=1, shard_index=0)
  module, params, stats, tokens, labels = setup(whole)
  tokens = tokens % (whole.vocab_size // 8)     # ids every slice has
  share = share_of(whole)
  want = ref.forward(d, share, ref.from_program(params, d, share), [],
                     tokens, labels)["logits"]
  slices = []
  for index in range(8):
    cfg = tiny(layers_held=1, shards=8, shard_index=index)
    rows = slice(index * cfg.vocab_rows, (index + 1) * cfg.vocab_rows)
    part = dict(params,
                embed_tokens={"embedding": params["embed_tokens"][
                    "embedding"][:cfg.vocab_rows]},
                lm_head=params["lm_head"][:, rows])
    (heads, _) = lm.MLAMoELM(cfg=cfg).apply({"params": part}, tokens)
    slices.append(heads.hidden[0] @ heads.kernel)
  close(jnp.concatenate(slices, -1), want, "concatenated logits")


# -- the configuration, the share, the stats ----------------------------------

def test_published_configuration_and_its_share():
  cfg = lm.load_lm_config("trinity-mini", 5, 8, 0, 1)
  assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == (
      2048, 32, 200192)
  assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
          cfg.sliding_window) == (32, 4, 128, 2048)
  assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
          cfg.routed_scaling_factor, cfg.norm_topk_prob,
          cfg.first_k_dense_replace) == (128, 8, 2.826, True, 2)
  assert cfg.embed_scale == 2048 ** 0.5 and cfg.post_norms
  assert cfg.windows == (2048, 2048, None, 2048, 2048)
  assert (cfg.dense_layers, cfg.moe_layers) == (1, 4)
  assert (cfg.first_expert, cfg.experts_held, cfg.vocab_rows) == (
      0, 16, 25024)
  # The cut model's parameters, counted from the built tree (abstract).
  module = lm.MLAMoELM(cfg=cfg)
  shapes = jax.eval_shape(lambda: module.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree.leaves(tree))
  p = shapes["params"]
  assert count(p["dense_0"]["self_attn"]) == 27_263_232
  assert count(p["dense_0"]) == 65_020_160
  for i in range(4):
    assert count(p[f"layer_{i}"]) == 134_488_320
  assert count(p["layer_0"]["mlp"]) - count(
      p["layer_0"]["mlp"]["shared_experts"]) == 262_144 + 16 * 6_291_456
  assert count(p["embed_tokens"]) == count(p["lm_head"]) == 51_249_152
  assert count(p) == 705_473_792
  # ... and the whole model by the same tree: 26.12 B.
  whole = lm.MLAMoELM(cfg=lm.load_lm_config("trinity-mini"))
  shapes = jax.eval_shape(lambda: whole.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
  assert count(shapes["params"]) == 26_123_970_560


@pytest.mark.parametrize("change, message", [
    ({"score_func": "softmax"}, "score_func='softmax' is not"),
    ({"mup_enabled": False}, "mup_enabled=False is not"),
    ({"num_key_value_heads": 3}, "do not divide"),
    ({"layer_types": ["sliding_attention"] * 4}, "not one entry a layer"),
    ({"layer_types": ["chunked_attention"] * 5}, "chunked_attention"),
    ({"model_type": "other"}, "not a family this decoder builds"),
])
def test_unimplemented_config_value_is_refused(tmp_path, monkeypatch, change,
                                               message):
  import json
  raw = dict(published(), **change)
  with open(tmp_path / "other.json", "w") as f:
    json.dump(raw, f)
  monkeypatch.setattr(lm, "CONFIG_DIR", str(tmp_path))
  with pytest.raises(ValueError, match=message):
    lm.load_lm_config("other")


@pytest.mark.parametrize("first, held", [(5, 1), (3, 3), (0, 6)])
def test_stage_outside_the_model_is_refused(first, held):
  with pytest.raises(ValueError, match="the model has 5 layers"):
    lm.load_lm_config("tiny-afmoe", held, 1, 0, first)


@pytest.fixture(scope="module")
def two_step_stats():
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  params = params_lib.make_params(
      model="mla_moe_lm", lm_config="tiny-afmoe", seq_len=SEQ, batch_size=2,
      lm_layer_shards=4, lm_layer_shard_index=1, lm_first_layer_held=1,
      lm_layers_held=4, device="cpu", optimizer="adam", num_batches=2,
      num_warmup_batches=0, display_every=1, tf_random_seed=5)
  return benchmark.BenchmarkCNN(benchmark.setup(params)).run()


def test_counters_and_cores_reach_the_stats(two_step_stats):
  moe = two_step_stats["moe"]
  # 2 x 32 tokens x 4 choices: under one row tile, so one round of all
  # 256 pairs in each of the 4 mixture layers of each of the 2 steps.
  assert moe["steps"] == 2 and moe["buffer_rows"] == 256
  assert moe["compact_share"] == 1.0 and moe["pairs_dropped"] == 0
  assert moe["experts_held"] == 4 and moe["vocab_rows"] == 512
  assert moe["combine"]["gathers"] == 4
  assert moe["combine"]["rows_gathered"] == moe["buffer_rows"]
  # Off the TPU no kernel runs; layers 1-4 are [window, full, window,
  # window]; the tiles are a kernel's, so a CPU run states none.
  att = two_step_stats["attention"]
  assert sorted(att) == ["full", "window"]
  assert att["window"]["core_layers"] == 3 and att["full"]["core_layers"] == 1
  assert att["window"]["window"] == 24 and att["full"]["window"] is None
  assert (att["full"]["query_heads"], att["full"]["key_heads"]) == (4, 2)
  assert att["window"]["backward_kernel_passes"] == 0
  assert "tiles_visited" not in att["window"]


def test_rotary_stage_reaches_the_stats(two_step_stats):
  # Layers 1-4 are [window, full, window, window], 4 query heads over 2
  # key heads of 8: a window layer rotates all 8 dimensions of q and of
  # k, the full layer none, every site under its head norm; off the TPU
  # the plain form (no kernel, no block of rows); 2 x 32 positions of
  # float32 in and out a call, the input kept for the backward pass.
  rotary = two_step_stats["rotary"]
  assert sorted(rotary) == ["full_k", "full_q", "window_k", "window_q"]
  assert rotary["window_q"] == {
      "calls_per_layer": 1, "layers": 3, "rot_dims": 8, "heads": 4,
      "head_dim": 8, "normed": True, "implementation": "xla",
      "block_rows": 0, "block_heads": 0,
      "bytes_read_and_written_per_call": 2 * 2 * SEQ * 4 * 8 * 4,
      "residual_bytes_per_layer": 2 * SEQ * 4 * 8 * 4}
  assert [(k, v["layers"], v["rot_dims"], v["heads"])
          for k, v in sorted(rotary.items())] == [
              ("full_k", 1, 0, 2), ("full_q", 1, 0, 4),
              ("window_k", 3, 8, 2), ("window_q", 3, 8, 4)]


def test_rotary_stage_of_the_trinity_cell_on_a_tpu(monkeypatch):
  # What the benchmark's cell states (1 x 8192 tokens in bfloat16, 32
  # query heads over 4 key heads of 128, 4 window layers and 1 full): the
  # kernel at every site, over blocks of 1,024 positions of 8 of q's
  # heads (4 MB in and out together) or of k's 4; and ONE log line,
  # whatever the build's second module asks.
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", lm_config="trinity-mini", seq_len=8192,
      batch_size=1, lm_layers_held=5, lm_first_layer_held=1,
      lm_layer_shards=8, device="cpu"))
  model.set_batch_size(1)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  stats = model.rotary_stats(jnp.bfloat16)
  assert [(k, v["layers"], v["rot_dims"], v["heads"], v["implementation"],
           v["block_rows"], v["block_heads"])
          for k, v in sorted(stats.items())] == [
              ("full_k", 1, 0, 4, "pallas", 1024, 4),
              ("full_q", 1, 0, 32, "pallas", 1024, 8),
              ("window_k", 4, 128, 4, "pallas", 1024, 4),
              ("window_q", 4, 128, 32, "pallas", 1024, 8)]
  assert stats["window_q"]["bytes_read_and_written_per_call"] == 2 * 2 ** 26
  assert stats["window_q"]["residual_bytes_per_layer"] == 2 ** 26
  lines = []
  with tracing.session(tracing.RunTrace(log_fn=lines.append)) as trace:
    model.state_blocks(jnp.bfloat16)
    model.state_blocks(jnp.bfloat16)      # the evaluation module's build
    assert trace.static("rotary") == stats
  said = [ln for ln in lines if ln.startswith("attention rotary: ")]
  assert len(said) == 1
  assert said[0].startswith(
      "attention rotary: window_q in 4 layer(s): 32 head(s) of 128, 128 "
      "rotated, normed, pallas over 1024 positions of 8 heads a block, "
      "134217728 bytes read and written a call, 67108864 kept a layer; "
      "window_k in 4 ")
  assert "full_q in 1 layer(s): 32 head(s) of 128, 0 rotated" in said[0]


def test_lm_head_reaches_the_stats(two_step_stats):
  # 2 x 32 positions in chunks of 4, one loss (no MTP module): groups of
  # a quarter of the sequence, 2 chunks of 8 float32 rows over the 512
  # vocabulary rows held.
  assert two_step_stats["lm_head"] == {
      "chunk": 4, "rows_per_weight_grad_product": 16,
      "weight_grad_passes": 4, "dlogits_bytes_held": 16 * 512 * 4,
      "losses": 1}


def test_lm_head_of_the_trinity_cell(monkeypatch):
  # What the benchmark's cell states (1 x 8192 positions, 25,024
  # vocabulary rows held, one loss, bfloat16): four chunks of 512 rows a
  # group, so the kernel's gradient is 4 products over 2,048 rows where
  # a chunk a product made 16; and the one log line says so.
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", lm_config="trinity-mini", seq_len=8192,
      batch_size=1, lm_layers_held=5, lm_first_layer_held=1,
      lm_layer_shards=8, device="cpu"))
  model.set_batch_size(1)   # what the run's set-up does with --batch_size
  assert model.lm_head_stats(jnp.bfloat16) == {
      "chunk": 512, "rows_per_weight_grad_product": 2048,
      "weight_grad_passes": 4, "dlogits_bytes_held": 2048 * 25024 * 2,
      "losses": 1}
  lines = []
  with tracing.session(tracing.RunTrace(log_fn=lines.append)):
    model.state_blocks(jnp.bfloat16)
  assert [ln for ln in lines if ln.startswith("lm head: ")] == [
      "lm head: 1 loss(es), float32 softmax over 512 positions at a time; "
      "the kernel's gradient from products over 2048 rows a loss, 4 passes "
      "over its float32 accumulator a step, 102498304 bytes of dlogits "
      "held"]


def test_attention_cores_of_the_trinity_cell_on_a_tpu(monkeypatch):
  # What the benchmark's cell states (1 x 8192 tokens, head size 128,
  # published layers 1-5): 4 window layers and 1 full, each with ONE
  # backward kernel pass a layer, and the window layers' kernels visit
  # fewer score tiles than the causal half at their own tiling.
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  from kf_benchmarks_tpu.parallel import sequence
  model = lm.MLAMoELMModel(params_lib.make_params(
      model="mla_moe_lm", lm_config="trinity-mini", seq_len=8192,
      batch_size=1, lm_layers_held=5, lm_first_layer_held=1,
      lm_layer_shards=8, device="cpu"))
  lines = []
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with tracing.session(tracing.RunTrace(log_fn=lines.append)):
    model.state_blocks(jnp.bfloat16)
  assert model.cfg.windows == (2048, 2048, None, 2048, 2048)
  att = model.attention_core_stats()
  assert att["window"]["core_layers"] == 4 and att["full"]["core_layers"] == 1
  full, window = att["full"], att["window"]
  assert full["tiles_visited"] == full["tiles_causal"]
  assert (full["block"], full["block_q"], full["block_kv_dkv"],
          full["dq_partials"]) == (512, 1024, 2048, 4)
  plan = sequence.flash_plan(8192, 8192, 128, 512, window=2048)
  assert {k: window[k] for k in dataclasses.asdict(plan)} == \
      dataclasses.asdict(plan)
  assert 0.44 < window["tiles_visited"] / window["tiles_causal"] < 0.75
  assert sum(ln.startswith("attention core (") for ln in lines) == 2
  assert any("layers (1-5: 1 dense, 4 mixture" in ln for ln in lines)
  window_line = next(ln for ln in lines
                     if ln.startswith("attention core (window)"))
  assert "window 2048, 32 query heads over 4 key heads" in window_line
  assert "causal score tiles visited" in window_line


def test_the_stage_flag_is_this_models_and_stays_out_of_fingerprints():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  with pytest.raises(validation.ParamError,
                     match="--lm_first_layer_held is read by"):
    validation.validate_cross_flags(params_lib.make_params(
        model="trivial", device="cpu", lm_first_layer_held=1))
  assert params_lib.make_params(
      model="trivial", device="cpu").lm_first_layer_held is None
