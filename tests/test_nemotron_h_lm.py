"""The one-mixer decoder of ``models/mla_moe_lm.py`` (``model_type:
nemotron_h``: Mamba-2 state-space mixers, two-matrix relu^2 experts,
grouped-query attention with no gate, norm or rotation) against the
plain reference ``benchmarks/references/nemotron-3-nano-30b-a3b.py``:
the ``tiny-nemotron-h`` preset (the published model's first nine layers'
kinds ``MEMEM*EME``; 8 Mamba heads of 4 over 2 groups, state 16, chunks
of 8 under sequences of 32; 4 query heads over 2 key heads of 8; 16
experts at top-4 beside a shared one), seeded random weights, float32,
on the CPU; the chunked scan (``ops/ssd.py``) against the SEQUENTIAL
recurrence; the shares tied to the uncut model; the built tree's
parameter count at the published widths (abstract shapes).

TOLERANCE. As tests/test_afmoe_lm.py: program and reference are both
float32 here and differ in the ORDER of sums alone (the chunked scan
sums a chunk's positions as products, the reference one position at a
time); ``RTOL`` is 3e-4 of the largest magnitude of the tensor compared.
The controls (the scan's running sums in bfloat16, no state carried
between chunks, the gate after the norm, the experts' other form, the
router in bfloat16) each have to FAIL it.
"""

import dataclasses
import importlib.util
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.models import mla_moe_lm as lm
from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.ops import ssd
from kf_benchmarks_tpu.parallel import expert as expert_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-4
SEQ = 32
PRESET = "tiny-nemotron-h"


def _load(path, label):
  spec = importlib.util.spec_from_file_location(label, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


ref = _load(os.path.join(REPO, "benchmarks", "references",
                         "nemotron-3-nano-30b-a3b.py"), "_nemotron_reference")


def published(name=PRESET):
  """``lm_configs/<name>.json`` as the reference reads it."""
  with open(os.path.join(lm.CONFIG_DIR, name + ".json")) as f:
    return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def tiny(layers_held=9, shards=4, shard_index=1, first_layer=0,
         vocab_shards=2, **changes):
  return dataclasses.replace(
      lm.load_lm_config(PRESET, layers_held, shards, shard_index,
                        first_layer, vocab_shards), **changes)


def share_of(cfg):
  return {"layers_held": cfg.layers_held, "first_layer": cfg.first_layer,
          "shards": cfg.shards, "shard_index": cfg.shard_index,
          "vocab_shards": cfg.vocab_shards}


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
  # Initialised at 0.02 every branch is far below the residual; the
  # comparison wants each to matter. Norm scales, D and the convolution's
  # taps move off their initial values so that one left out, or put
  # elsewhere, shows; A_log and dt_bias stay what the family starts from
  # (decays from 0.999 to 0.002 a position: states that live for a
  # chunk, and states that live for the sequence).
  def scaled(path, x):
    name = jax.tree_util.keystr(path)
    if name.endswith("['A_log']") or name.endswith("['dt_bias']") or \
        "['conv1d']" in name:
      return x
    if not (name.endswith("['scale']") or name.endswith("['D']")):
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
    (0, 9),    # the preset: M E M E M * E M E
    (1, 5),    # a stage that starts at a mixture layer: E M E M *
    (5, 4),    # ... and one that starts at the attention layer: * E M E
])
def test_whole_model_against_reference(first_layer, layers_held):
  cfg = tiny(layers_held=layers_held, first_layer=first_layer)
  module, params, stats, tokens, labels = setup(cfg)
  (loss, mids), grads = program(module, cfg, params, stats, tokens, labels)
  want, want_grads = reference(cfg, params, stats, tokens, labels)
  close(loss, want["loss"], "loss")
  close(mids["hidden_last"][0], want["hidden_last"], "last hidden state")
  assert len(want["scores"]) == cfg.moe_layers
  mixtures = [i for i, kind in enumerate(cfg.kinds) if kind == lm.MIXTURE]
  for layer in range(cfg.layers_held):
    close(mids[f"layer_{layer}"]["hidden_in"][0], want["hidden"][layer],
          f"input of layer {layer} ({cfg.kinds[layer]})")
  for m, layer in enumerate(mixtures):
    mid = mids[f"layer_{layer}"]["mixer"]
    close(mid["router_scores"][0], want["scores"][m],
          f"router scores of layer {layer}")
    assert np.array_equal(np.sort(mid["topk_idx"][0], -1),
                          np.sort(want["idx"][m], -1))
  # Every leaf's gradient, the Mamba mixers' seven kinds of leaf among
  # them.
  trees_close(grads, want_grads, "gradient")
  logits = ref.forward(published(), share_of(cfg), ref.from_program(
      params, published(), share_of(cfg)), ref.bias_from_program(stats),
                       tokens, labels)["logits"]
  (heads, _) = module.apply({"params": params, "batch_stats": stats}, tokens)
  close(heads.hidden[0] @ heads.kernel, logits, "logits")


def test_the_stack_is_the_configurations_own_pattern():
  cfg = tiny()
  assert cfg.kinds == "MEMEM*EME"
  assert (cfg.mamba_layers, cfg.moe_layers, cfg.dense_layers) == (4, 4, 0)
  assert cfg.attention_windows == (None,) and len(cfg.windows) == 9
  assert tiny(layers_held=3, first_layer=4).kinds == "M*E"
  module, params, stats, *_ = setup(cfg)
  assert sorted(params) == ["embed_tokens"] + [
      f"layer_{i}" for i in range(9)] + ["lm_head", "norm_f"]
  assert all(sorted(params[f"layer_{i}"]) == ["mixer", "norm"]
             for i in range(9))
  mamba, mixture, attention = (params[f"layer_{i}"]["mixer"]
                               for i in (0, 1, 5))
  assert sorted(mamba) == ["A_log", "D", "conv1d", "dt_bias", "in_proj",
                           "norm", "out_proj"]
  # [z | xBC | dt] = 32 | 32 + 2 x 2 x 16 | 8.
  assert mamba["in_proj"]["kernel"].shape == (32, 32 + 96 + 8)
  assert mamba["conv1d"]["kernel"].shape == (4, 96)
  assert mamba["norm"]["scale"].shape == (32,)
  # Two matrices an expert, no gate; neither has the attention one, nor
  # head norms.
  assert sorted(mixture) == ["experts_down", "experts_up", "router",
                             "shared_experts"]
  assert sorted(mixture["shared_experts"]) == ["down_proj", "up_proj"]
  assert mixture["shared_experts"]["up_proj"]["kernel"].shape == (32, 40)
  assert sorted(attention) == ["k_proj", "o_proj", "q_proj", "v_proj"]
  # The router's state is the mixture layers' alone.
  assert sorted(stats) == ["layer_1", "layer_3", "layer_6", "layer_8"]


def test_initial_values_are_the_familys():
  cfg = tiny()
  module = lm.MLAMoELM(cfg=cfg)
  params = module.init({"params": jax.random.PRNGKey(2)},
                       jnp.zeros((1, SEQ), jnp.int32))["params"]
  mamba = params["layer_0"]["mixer"]
  assert np.allclose(mamba["A_log"], np.log(np.arange(1, 9)))
  assert np.array_equal(mamba["D"], np.ones(8))
  step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
  assert (step >= cfg.time_step_min * 0.999).all() and (
      step <= cfg.time_step_max * 1.001).all()
  assert np.abs(mamba["conv1d"]["kernel"]).max() <= 0.5
  # ``rescale_prenorm_residual``: out_proj starts smaller by sqrt(9).
  assert np.std(mamba["out_proj"]["kernel"]) == pytest.approx(
      0.02 / 3, rel=0.15)
  assert np.std(mamba["in_proj"]["kernel"]) == pytest.approx(0.02, rel=0.1)


# -- the chunked scan against the sequential recurrence -----------------------

def _scan_inputs(seq, seed=0, batch=2, heads=8, p=4, groups=2, n=16):
  k = jax.random.split(jax.random.PRNGKey(seed), 5)
  return (jax.random.normal(k[0], (batch, seq, heads, p)),
          jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2),
          -jnp.exp(jax.random.normal(k[2], (heads,))),
          jax.random.normal(k[3], (batch, seq, groups, n)),
          jax.random.normal(k[4], (batch, seq, groups, n)))


def _sequential(x, dt, a, b, c, scan_block=None):
  per_head = lambda v: jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)
  return ref.recurrence(x, dt, a, per_head(b), per_head(c), scan_block)


@pytest.mark.parametrize("seq, chunk", [
    (8, 8),      # one chunk: no state is carried
    (32, 8),     # four links
    (40, 5),     # eight, of a chunk that is no power of two
    (24, 24),
])
def test_chunked_scan_is_the_sequential_recurrence(seq, chunk):
  args = _scan_inputs(seq)
  close(ssd.ssd_scan(*args, chunk), _sequential(*args), "scan")
  cost = lambda fn: lambda *v: jnp.sum(jnp.sin(fn(*v)))
  got = jax.grad(cost(lambda *v: ssd.ssd_scan(*v, chunk)),
                 argnums=range(5))(*args)
  want = jax.grad(cost(_sequential), argnums=range(5))(*args)
  for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
    close(g, w, f"gradient of {name}")


def test_the_references_blocks_change_nothing():
  args = _scan_inputs(32)
  close(_sequential(*args, scan_block=8), _sequential(*args), "blocks",
        rtol=1e-6)


@pytest.mark.parametrize("seq, chunk", [(30, 8), (7, 8), (129, 128)])
def test_a_length_the_scan_cannot_take_is_refused(seq, chunk):
  # The program takes whole chunks only: refused with the reason, by the
  # scan itself and, before any trace, by validation.py (below).
  with pytest.raises(ValueError, match="takes whole chunks"):
    ssd.ssd_scan(*_scan_inputs(seq), chunk)
  assert "no multiple of chunk_size" in ssd.refusal(seq, chunk)
  assert ssd.refusal(seq - seq % chunk + chunk, chunk) is None


def test_scan_state_decays_and_carries():
  # ONE position's input, scaled: what it adds to later positions of its
  # own chunk, of the next and three chunks on is the reference's (the
  # benchmark's check holds the same on the chip, ``scan_carry_err``).
  args = _scan_inputs(48)
  x = args[0]
  bumped = (x.at[:, 5].multiply(64.0),) + args[1:]
  rows = np.asarray([6, 7, 8 + 2, 8 * 4 + 1])
  got = ssd.ssd_scan(*bumped, 8)[:, rows] - ssd.ssd_scan(*args, 8)[:, rows]
  want = _sequential(*bumped)[:, rows] - _sequential(*args)[:, rows]
  assert np.abs(want[:, -1]).max() > 1e-3 * np.abs(want[:, 0]).max()
  close(got, want, "what position 5 adds later")
  # ... and nothing earlier.
  assert np.array_equal(ssd.ssd_scan(*bumped, 8)[:, :5],
                        ssd.ssd_scan(*args, 8)[:, :5])


def test_convolution_is_causal_and_depthwise():
  k = jax.random.split(jax.random.PRNGKey(1), 3)
  x = jax.random.normal(k[0], (2, 12, 6))
  kernel, bias = jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,))
  y = ssd.causal_conv(x, kernel, bias)
  close(y, ref.conv(x, kernel, bias), "convolution")
  # Position t is unchanged when t + 1 changes; a channel sees its own
  # last four positions and no other channel.
  later = ssd.causal_conv(x.at[:, 7].add(1.0), kernel, bias)
  assert np.array_equal(later[:, :7], y[:, :7])
  assert not np.array_equal(later[:, 7:11], y[:, 7:11])
  assert np.array_equal(later[:, 11:], y[:, 11:])
  other = ssd.causal_conv(x.at[:, :, 2].add(1.0), kernel, bias)
  assert np.array_equal(np.delete(other, 2, axis=2), np.delete(y, 2, axis=2))
  want = bias[0] + sum(kernel[j, 0] * x[0, 5 - 3 + j, 0] for j in range(4))
  assert float(y[0, 5, 0]) == pytest.approx(float(want), rel=1e-5)


def test_mamba_mixer_is_rematerialised_from_in_proj():
  # What the mixer keeps for its backward pass is its input's projection
  # (and what the projections' own gradients read): no (chunk x chunk)
  # decay table, no carried state.
  cfg = tiny(layers_held=1)
  mixer = lm.Mamba2Mixer(cfg=cfg)
  u = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, cfg.hidden_size))
  params = mixer.init({"params": jax.random.PRNGKey(1)}, u)["params"]
  _, kept = jax.vjp(lambda p, u: mixer.apply({"params": p}, u), params, u)
  shapes = sorted({tuple(x.shape) for x in jax.tree.leaves(kept)
                   if hasattr(x, "shape") and x.ndim >= 3})
  assert shapes == [(2, SEQ, 32), (2, SEQ, 136)]


# Each control is ONE departure from the published layer, planted in the
# program from outside (experiments/lm_precision_control.py plants the
# same in the benchmark's cell); the comparison that passes above has to
# see it.
@pytest.mark.parametrize("control", [
    "scan_bf16", "no_chunk_carry", "gate_after_norm", "experts_gated",
    "router_bf16"])
def test_control_fails(control, monkeypatch):
  _controls().plant(control, lambda obj, name, value: monkeypatch.setattr(
      obj, name, value, raising=False))
  cfg = tiny()
  assert cfg.expert_activation == ("self_gated" if control == "experts_gated"
                                   else "relu2")
  kwargs = {"scan_bf16": {"scan_dtype": jnp.bfloat16},
            "router_bf16": {"router_dtype": jnp.bfloat16}}.get(control, {})
  module, params, stats, tokens, labels = setup(cfg, **kwargs)
  (loss, mids), grads = program(module, cfg, params, stats, tokens, labels)
  want, want_grads = reference(cfg, params, stats, tokens, labels)
  with pytest.raises(AssertionError):
    close(mids["hidden_last"][0], want["hidden_last"], "last hidden state")
  with pytest.raises(AssertionError):
    trees_close(grads, want_grads, "gradient")
  if control != "router_bf16":
    # The first Mamba layer's own output already shows a fault of the
    # mixer; the first mixture layer's one of the experts.
    seen_at = 2 if control == "experts_gated" else 1
    with pytest.raises(AssertionError):
      close(mids[f"layer_{seen_at}"]["hidden_in"][0],
            want["hidden"][seen_at], "output of the first such layer")


def _controls():
  return _load(os.path.join(REPO, "experiments", "lm_precision_control.py"),
               "_lm_controls")


# -- the share adds up to the model -------------------------------------------

def _mixture_as_ref(p):
  return {"router": p["router"], "experts_up": p["experts_up"],
          "experts_down": p["experts_down"],
          "shared": {k: p["shared_experts"][k]["kernel"]
                     for k in ("up_proj", "down_proj")}}


def test_expert_shares_add_up_to_the_uncut_layer():
  """The routed parts of all 16 shares (one expert each, as the
  deployment's 16 chips hold 8 of 128), and the shared expert counted
  once, are the uncut reference's mixture layer."""
  d = published()
  whole = tiny(shards=1, shard_index=0, vocab_shards=1)
  moe = lm.MoE(cfg=whole)
  x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, whole.hidden_size))
  variables = moe.init({"params": jax.random.PRNGKey(4)}, x)
  params = jax.tree.map(lambda p: p * 8, variables["params"])
  stats = variables["batch_stats"]
  uncut, _, _ = ref.mixture(d, share_of(whole), _mixture_as_ref(params),
                            stats["select_bias"], x)
  shared = ref.mlp(_mixture_as_ref(params)["shared"], x)
  total = jnp.zeros_like(x)
  for index in range(16):
    # (The vocabulary lies over 8 of the 16: a chip past them holds
    # experts and no rows, which the model refuses to build; the mixture
    # layer alone has no vocabulary.)
    cfg = dataclasses.replace(tiny(shards=16, shard_index=0, vocab_shards=8),
                              shard_index=index)
    assert cfg.experts_held == 1 and cfg.first_expert == index
    rows = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
    part = dict(params, **{k: params[k][rows] for k in (
        "experts_up", "experts_down")})
    mine = lm.MoE(cfg=cfg).apply({"params": part, "batch_stats": stats}, x)
    close(mine - shared, ref.routed(d, share_of(cfg), _mixture_as_ref(part),
                                    stats["select_bias"], x)[0],
          f"routed part of share {index}")
    total += mine - shared
  close(total + shared, uncut, "sum of the shares")


def test_vocabulary_has_a_share_of_its_own():
  d = published()
  # Experts over 4 chips, the vocabulary over 2 of them: the slices of
  # the two concatenate to the uncut logits (the first Mamba layer alone:
  # a share of it cuts nothing but the vocabulary).
  whole = tiny(layers_held=1, shards=1, shard_index=0, vocab_shards=1)
  module, params, stats, tokens, labels = setup(whole)
  tokens = tokens % (whole.vocab_size // 2)
  share = share_of(whole)
  want = ref.forward(d, share, ref.from_program(params, d, share), [],
                     tokens, labels)["logits"]
  slices = []
  for index in range(2):
    cfg = tiny(layers_held=1, shards=4, shard_index=index, vocab_shards=2)
    assert (cfg.vocab_rows, cfg.experts_held) == (1024, 4)
    rows = slice(index * cfg.vocab_rows, (index + 1) * cfg.vocab_rows)
    part = dict(params,
                embed_tokens={"embedding": params["embed_tokens"][
                    "embedding"][:cfg.vocab_rows]},
                lm_head=params["lm_head"][:, rows])
    (heads, _) = lm.MLAMoELM(cfg=cfg).apply({"params": part}, tokens)
    slices.append(heads.hidden[0] @ heads.kernel)
  close(jnp.concatenate(slices, -1), want, "concatenated logits")
  # By default the vocabulary is divided like a layer, as the two
  # families before had it.
  assert lm.load_lm_config(PRESET, 9, 4, 1).vocab_rows == 512
  assert lm.load_lm_config("tiny-afmoe", 5, 4, 1).vocab_rows == 512


@pytest.mark.parametrize("args, message", [
    (dict(vocab_shards=3), "--lm_vocab_shards=3 does not divide vocab_size"),
    (dict(vocab_shards=0), "--lm_vocab_shards=0 does not divide"),
    (dict(shards=4, shard_index=2, vocab_shards=2),
     "holds no rows of a vocabulary divided over --lm_vocab_shards=2"),
    (dict(shards=3), "--lm_layer_shards=3 does not divide n_routed_experts"),
    (dict(shards=4, shard_index=4), "shard_index=4 of"),
])
def test_share_outside_the_model_is_refused(args, message):
  with pytest.raises(ValueError, match=message):
    tiny(**args)


# -- the configuration, the share, the stats ----------------------------------

def _count(tree):
  return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_published_configuration_and_its_share():
  cfg = lm.load_lm_config("nemotron-3-nano-30b-a3b", 9, 16, 0, 0, 8)
  assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == (
      2688, 52, 131072)
  assert len(cfg.layer_pattern) == 52 and cfg.kinds == "MEMEM*EME"
  assert (cfg.layer_pattern.count("M"), cfg.layer_pattern.count("E"),
          cfg.layer_pattern.count("*")) == (23, 23, 6)
  assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
          cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size) == (
              64, 64, 8, 128, 4, 128)
  assert (cfg.num_attention_heads, cfg.num_key_value_heads,
          cfg.head_dim) == (32, 2, 128)
  assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
          cfg.routed_scaling_factor, cfg.norm_topk_prob,
          cfg.moe_intermediate_size, cfg.shared_width) == (
              128, 6, 2.5, True, 1856, 3712)
  assert (cfg.expert_matrices, cfg.expert_activation) == (2, "relu2")
  assert not (cfg.attention_gate or cfg.head_norms or cfg.post_norms)
  assert cfg.embed_scale == 1.0 and cfg.rms_norm_eps == 1e-5
  assert (cfg.first_expert, cfg.experts_held, cfg.vocab_rows) == (
      0, 8, 16384)
  # The cut model's parameters, counted from the built tree (abstract:
  # no memory), part by part at the published widths. The router's 128
  # selection-bias values a mixture layer are the published model's
  # parameters and this program's STATE (``batch_stats``): counted here,
  # owned by no optimizer.
  module = lm.MLAMoELM(cfg=cfg)
  shapes = jax.eval_shape(lambda: module.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 128), jnp.int32)))
  p, state = shapes["params"], shapes["batch_stats"]
  bias = lambda i: _count(state[f"layer_{i}"]["mixer"]["select_bias"])
  for i in (0, 2, 4, 7):
    assert _count(p[f"layer_{i}"]) == 38_744_896
    assert _count(p[f"layer_{i}"]["mixer"]["in_proj"]) == 2688 * 10304
    assert _count(p[f"layer_{i}"]["mixer"]["conv1d"]) == 6144 * 4 + 6144
  for i in (1, 3, 6, 8):
    assert bias(i) == 128
    mixer = p[f"layer_{i}"]["mixer"]
    routed = _count(mixer["experts_up"]) + _count(mixer["experts_down"])
    assert routed == 8 * 9_977_856
    assert _count(p[f"layer_{i}"]) - routed + bias(i) == 20_302_592
  assert _count(p["layer_5"]) == 23_399_040
  assert _count(p["embed_tokens"]) == _count(p["lm_head"]) == 16384 * 2688
  assert _count(p["norm_f"]) == 2688
  assert _count(p) + sum(bias(i) for i in (1, 3, 6, 8)) == 666_963_456
  # ... and the whole model by the same tree: 31.578 B.
  whole = lm.MLAMoELM(cfg=lm.load_lm_config("nemotron-3-nano-30b-a3b"))
  shapes = jax.eval_shape(lambda: whole.init(
      {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 128), jnp.int32)))
  assert _count(shapes["params"]) + 23 * 128 == 31_577_940_288


@pytest.mark.parametrize("change, message", [
    ({"n_group": 2}, "n_group=2 is not"),
    ({"topk_group": 2}, "topk_group=2 is not"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=True is not"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias=True is not"),
    ({"attention_bias": True}, "attention_bias=True is not"),
    ({"mlp_bias": True}, "mlp_bias=True is not"),
    ({"use_conv_bias": False}, "use_conv_bias=False is not"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act='silu' is not"),
    ({"sliding_window": 64}, "sliding_window=64 is not"),
    ({"hybrid_override_pattern": "MEMEM*EM"}, "not one character a layer"),
    ({"hybrid_override_pattern": "MEMEM-EME"}, r"names \['-'\]"),
    ({"n_groups": 3}, "3 groups do not divide 8 Mamba heads"),
    ({"num_key_value_heads": 3}, "do not divide"),
])
def test_unimplemented_config_value_is_refused(tmp_path, monkeypatch, change,
                                               message):
  raw = dict(published(), **change)
  with open(tmp_path / "other.json", "w") as f:
    json.dump(raw, f)
  monkeypatch.setattr(lm, "CONFIG_DIR", str(tmp_path))
  with pytest.raises(ValueError, match=message):
    lm.load_lm_config("other")


def test_flags_are_validated():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  make = lambda **kw: params_lib.make_params(
      model="mla_moe_lm", lm_config=PRESET, device="cpu", **kw)
  ok = make(seq_len=32, lm_layer_shards=4, lm_vocab_shards=2)
  validation.validate_cross_flags(ok)
  assert ok.lm_vocab_shards == 2
  # A sequence the scan cannot take, with the scan's own reason, before
  # anything is traced; the other families take any length.
  with pytest.raises(validation.ParamError, match="takes whole chunks: a "
                     "sequence of 30 positions is no multiple of "
                     "chunk_size=8"):
    validation.validate_cross_flags(make(seq_len=30))
  validation.validate_cross_flags(params_lib.make_params(
      model="mla_moe_lm", lm_config="tiny-afmoe", device="cpu", seq_len=30))
  with pytest.raises(validation.ParamError,
                     match="--lm_vocab_shards is read by"):
    validation.validate_cross_flags(params_lib.make_params(
        model="trivial", device="cpu", lm_vocab_shards=2))
  assert params_lib.make_params(
      model="trivial", device="cpu").lm_vocab_shards is None
  # The share itself is held where the configuration is loaded.
  model = lm.MLAMoELMModel(make(seq_len=32, lm_layer_shards=4,
                                lm_vocab_shards=3))
  with pytest.raises(ValueError, match="--lm_vocab_shards=3 does not divide"):
    model.cfg  # pylint: disable=pointless-statement


@pytest.fixture(scope="module")
def two_step_stats():
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import log as log_util
  lines = []
  orig, log_util.log_fn = log_util.log_fn, lambda msg: lines.append(str(msg))
  try:
    params = params_lib.make_params(
        model="mla_moe_lm", lm_config=PRESET, seq_len=SEQ, batch_size=2,
        lm_layer_shards=4, lm_layer_shard_index=1, lm_vocab_shards=2,
        device="cpu", optimizer="adam", num_batches=2, num_warmup_batches=0,
        display_every=1, tf_random_seed=5)
    stats = benchmark.BenchmarkCNN(benchmark.setup(params)).run()
  finally:
    log_util.log_fn = orig
  return stats, lines


def test_scan_and_share_reach_the_stats(two_step_stats):
  stats, lines = two_step_stats
  assert stats["mamba"] == {
      "layers": 4, "heads": 8, "head_dim": 4, "groups": 2, "state": 16,
      "chunk": 8, "chunks_per_sequence": 4, "implementation": "xla",
      "kernel_share": 0.0, "carried_state_bytes_per_layer": 2 * 4 * 8 * 4 * 16 * 4,
      "residual_bytes_per_layer": 2 * SEQ * 136 * 4}
  moe = stats["moe"]
  assert moe["expert_matrices"] == 2 and moe["experts_held"] == 4
  assert moe["vocab_rows"] == 1024 and moe["steps"] == 2
  assert moe["compact_share"] == 1.0 and moe["pairs_dropped"] == 0
  # One attention layer, full, no kernel off the TPU; no rotary stage at
  # all (no head norms, nothing rotated).
  assert sorted(stats["attention"]) == ["full"]
  assert stats["attention"]["full"]["core_layers"] == 1
  assert (stats["attention"]["full"]["query_heads"],
          stats["attention"]["full"]["key_heads"]) == (4, 2)
  assert not stats.get("rotary")
  scan = [l for l in lines if l.startswith("mamba scan: ")]
  assert len(scan) == 1 and "4 chunks of 8 positions" in scan[0]
  assert "; xla (kernel share 0.0), " in scan[0]
  share = [l for l in lines if l.startswith("mla_moe_lm share: ")]
  assert len(share) == 1
  assert ("4 Mamba-2, 4 mixture, 1 attention, one mixer a layer" in share[0]
          and "chip 1 of 4 per layer: experts 4-7 of 16 (2 matrices an "
          "expert); of 2 over the vocabulary: rows 0-1023 of 2048"
          in share[0])
  assert not [l for l in lines if l.startswith("attention rotary")]


def test_the_other_families_state_three_matrices():
  cfg = lm.load_lm_config("tiny-afmoe", 5, 4, 1)
  assert (cfg.expert_matrices, cfg.expert_activation, cfg.kinds) == (
      3, "silu", "")
  assert cfg.attention_windows == cfg.windows
  assert lm.load_lm_config("tiny", None, 4, 1).expert_matrices == 3


# -- the routed path in the two-matrix form -----------------------------------

def _routed_inputs(n=64, d=256, f=192, e=8, g=4, k=2, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 5)
  x = jax.random.normal(keys[0], (n, d))
  idx = jnp.argsort(jax.random.normal(keys[1], (n, e)), -1)[:, :k].astype(
      jnp.int32)
  weights = jax.nn.softmax(jax.random.normal(keys[2], (n, k)), -1)
  return (x, weights, idx, 0.1 * jax.random.normal(keys[3], (g, d, f)),
          0.1 * jax.random.normal(keys[4], (g, f, d)))


def test_gmm_tiling_of_a_width_no_size_divides():
  # 1,856 = 29 x 64: the size whose last tile is masked least; the widths
  # of the cells before are tiled as they were, the rows by the layout's
  # row tile (256 in each cell: ``expert.row_tile``).
  assert expert_lib.row_tile(8192 * 6, 8, 128) == 256
  assert expert_lib.gmm_tiling(256, 2688, 1856) == (256, 896, 384)
  assert expert_lib.gmm_tiling(256, 1856, 2688) == (256, 384, 896)
  assert expert_lib.gmm_tiling(256, 2048, 1536) == (256, 1024, 768)
  assert expert_lib.gmm_tiling(256, 1536, 2048) == (256, 768, 1024)
  assert expert_lib.gmm_tiling(256, 2048, 1024) == (256, 1024, 512)
  assert expert_lib.gmm_tiling(256, 1024, 2048) == (256, 1024, 512)
  assert expert_lib.gmm_tiling(64, 48, 20) == (64, 48, 20)
  assert expert_lib.gmm_tiling(128, 256, 192) == (128, 256, 128)


@pytest.mark.parametrize("rows", [None, 32])
def test_two_matrix_experts_through_the_kernel_with_a_masked_tile(rows):
  # The TPU kernel, interpreted, at a width no tile divides (192: tiles
  # of 128, the second half masked, as 1,856 in tiles of 384), in one
  # round and in several: the plain dense loop's values and gradients.
  x, weights, idx, w_up, w_down = _routed_inputs()
  first = 2

  def routed(impl):
    def fn(x, weights, w_up, w_down):
      y, counts = expert_lib.held_experts_ffn(
          x, weights, idx, None, w_up, w_down, first, impl=impl, rows=rows,
          activation="relu2")
      return jnp.sum(jnp.sin(y)), (y, counts)
    return jax.value_and_grad(fn, argnums=(0, 1, 2, 3), has_aux=True)(
        x, weights, w_up, w_down)

  def dense(x, weights, w_up, w_down):
    y = jnp.zeros_like(x)
    for j in range(w_up.shape[0]):
      w_token = jnp.sum(jnp.where(idx == first + j, weights, 0.0), -1)
      y = y + w_token[:, None] * (
          jnp.square(jax.nn.relu(x @ w_up[j])) @ w_down[j])
    return jnp.sum(jnp.sin(y)), y
  (_, want_y), want = jax.value_and_grad(
      dense, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, w_up, w_down)
  for impl in ("ragged_dot", "gmm_interpret"):
    (_, (y, counts)), grads = routed(impl)
    close(y, want_y, f"{impl}: y", rtol=1e-5)
    assert int(counts["pairs_here"]) == int(counts["pairs_computed"]) > 0
    assert int(counts["compact"]) == (rows is None)
    for name, g, w in zip(("x", "weights", "up", "down"), grads, want):
      close(g, w, f"{impl}: gradient of {name}", rtol=1e-5)
