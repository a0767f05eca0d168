"""DeepSpeech2 (speech) and NCF (recommendation) model tests
(ref: models/experimental/deepspeech.py, official_ncf_model.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import benchmark, params as params_lib
from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.models.deepspeech import DeepSpeechDecoder
from kf_benchmarks_tpu.models.model import BuildNetworkResult


def _small_ds2():
  model = model_config.get_model_config("deepspeech2", "librispeech")
  model.set_batch_size(2)
  model.max_time_steps = 64
  model.max_label_length = 8
  model.rnn_hidden_size = 32
  model.num_rnn_layers = 2
  return model


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_ds2_forward_and_ctc_loss():
  model = _small_ds2()
  rng = jax.random.PRNGKey(0)
  spec, labels = model.get_synthetic_inputs(rng, 29)
  module = model.make_module(nclass=29, phase_train=True)
  variables = module.init({"params": rng, "dropout": rng}, spec)
  (logits, _), _ = module.apply(variables, spec, mutable=["batch_stats"])
  # conv stride 2 twice on time: 64 -> 16 frames; vocab 29
  assert logits.shape == (2, 16, 29)
  loss = model.loss_function(BuildNetworkResult(logits=(logits, None)),
                             labels)
  assert np.isfinite(float(loss))


def test_ds2_gru_variant():
  model = _small_ds2()
  model.rnn_type = "gru"
  model.is_bidirectional = False
  rng = jax.random.PRNGKey(0)
  spec, _ = model.get_synthetic_inputs(rng, 29)
  module = model.make_module(nclass=29, phase_train=False)
  variables = module.init({"params": rng}, spec)
  (logits, _), _ = module.apply(variables, spec, mutable=["batch_stats"])
  assert logits.shape == (2, 16, 29)


def test_ds2_decoder():
  d = DeepSpeechDecoder()
  assert d.wer("the cat sat", "the cat sat") == 0
  assert d.wer("the cat", "the bat") == 1
  assert d.cer("abc", "abd") == 1
  # greedy decode: collapse repeats, drop blanks (index 28)
  probs = np.zeros((5, 29))
  probs[0, 1] = probs[1, 1] = 1    # 'a' twice -> one 'a'
  probs[2, 28] = 1                 # blank
  probs[3, 2] = probs[4, 2] = 1    # 'b'
  assert d.decode_logits(probs) == "ab"
  assert d.decode([1, 2, 28, 3]) == "abc"


def test_ds2_postprocess_wer_cer():
  model = _small_ds2()
  n_frames, vocab = 10, 29
  probs = np.zeros((2, n_frames, vocab), np.float32)
  probs[:, :, 28] = 1.0  # all blanks -> empty predictions
  labels = np.full((2, 4), 1, np.int32)  # "aaaa"
  results = model.postprocess({"deepspeech2_prob": probs,
                               "deepspeech2_label": labels})
  assert results["CER"] == pytest.approx(1.0)  # all chars wrong
  assert results["WER"] == pytest.approx(1.0)


def test_ncf_forward_loss_accuracy():
  model = model_config.get_model_config("ncf", "imagenet")
  model.set_batch_size(32)
  rng = jax.random.PRNGKey(0)
  feats, labels = model.get_synthetic_inputs(rng, 2)
  assert feats.shape == (32, 2) and feats.dtype == jnp.int32
  module = model.make_module(nclass=2, phase_train=True)
  variables = module.init({"params": rng}, feats)
  (logits, _), _ = module.apply(variables, feats, mutable=["batch_stats"])
  assert logits.shape == (32, 1)
  result = BuildNetworkResult(logits=(logits, None))
  loss = model.loss_function(result, labels)
  assert np.isfinite(float(loss))
  acc = model.accuracy_function(result, labels)
  assert 0.0 <= float(acc["top_1_accuracy"]) <= 1.0


def test_ncf_trains_through_driver():
  """NCF end-to-end through the DP driver: non-image features work in
  the shared loop (ref CLI: --model=ncf --optimizer=adam)."""
  p = params_lib.make_params(
      model="ncf", data_name="imagenet", batch_size=32, num_batches=4,
      num_warmup_batches=1, device="cpu", num_devices=2,
      variable_update="replicated", optimizer="adam", weight_decay=0,
      display_every=2)
  bench = benchmark.BenchmarkCNN(p)
  stats = bench.run()
  assert np.isfinite(stats["last_average_loss"])
