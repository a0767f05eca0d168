"""The xing4.0-29b-a4b cell's own pieces, without a chip: the operation
and parameter counts against the numbers the files state, the
configuration's file against the published one, the accepted metrics the
cell joins, the four new readers on a made-up traced run (scope present,
scope absent, never over 100 at the necessary time), the configuration
and cell admitted by the repository's own rules in a temporary tree, and
the reference check itself, run end to end at the ``tiny-xing`` preset
on the CPU (where it has to pass, and fail under each control)."""

import functools
import importlib.util
import json
import os

import pytest

from bench_testlib import REPO, admit, copy_tree, holding, read_json, reports
from benchmarks import harness
from benchmarks import lm_flops
from benchmarks import mhc_flops as flops_lib
from benchmarks import spec
from trace_testlib import (BWD, FWD, LM_STEP_OPS, PEAK, US, _fusion,
                           _kernel, _read, _run, _trace)

H = holding("xing")
CONFIG, CELL, CHECK = H.CONFIG, H.CELL, H.CHECK
JOINED, NEW = H.JOINED, H.NEW
PUBLISHED = os.path.join(REPO, "kf_benchmarks_tpu", "models", "lm_configs",
                         CONFIG + ".json")
BANDWIDTH = 819e9


# -- the counts against the numbers the files state ---------------------------

def test_forward_operations_are_the_number_the_cell_states():
  cell = spec.load_cell(REPO, CELL)
  config = cell["config_data"]
  per_token = flops_lib.forward_flops_per_token(
      config, cell["tokens_per_sample"])
  assert per_token == 784_793_600
  assert cell["forward_flops_per_sample"] == per_token
  assert config["sample_unit"] == "tokens"
  # 9.64 TFLOP a training step of 4,096 tokens.
  assert 3 * per_token * 4096 == pytest.approx(9.644e12, rel=1e-3)
  assert flops_lib.attention_projection_params(config) == 7_766_016
  assert flops_lib.hc_params(config) == 14_336 * 24 + 27
  # By part (millions): the dense layer, a mixture layer, the MTP module
  # (a mixture layer and eh_proj), the two heads.
  hyper = 2 * 2 * 14_336 * 24
  attention = 2 * 7_766_016 + 2 * 4 * 320 * 2048 + hyper
  assert attention + 2 * 3 * 3584 * 9216 == pytest.approx(220.3e6, rel=1e-3)
  mixture = attention + 2 * (3584 * 64 + 1.5 * 3 * 3584 * 1024)
  assert mixture == pytest.approx(55.6e6, rel=1e-3)
  assert mixture + 2 * 2 * 3584 * 3584 == pytest.approx(107.0e6, rel=1e-3)
  assert 2 * 2 * 3584 * 16384 == pytest.approx(234.9e6, rel=1e-3)


def test_parameters_are_the_number_the_configuration_states():
  config = spec.load_config(REPO, CONFIG)
  assert flops_lib.parameters(config) == config["parameters"] == 789_610_308
  # 16 bytes a parameter: 75% of the 15.75 GiB the runtime gives.
  assert 16 * config["parameters"] / (15.75 * 2 ** 30) == pytest.approx(
      0.747, abs=2e-3)
  # The whole model by the same count, from the published keys.
  whole = dict(config, **config["published"])
  whole["published"] = config["published"]
  assert flops_lib.parameters(whole) == 30_276_192_678
  # Attention held whole (the other language-model cells' convention):
  # 913.5 M, 86% of the 15.75 GiB, too little left for four streams.
  heads_whole = dict(config, num_attention_heads=32)
  assert flops_lib.parameters(heads_whole) == pytest.approx(913.5e6,
                                                            rel=1e-3)


def test_mhc_necessary_and_two_size_core_counts():
  config = spec.load_config(REPO, CONFIG)
  ops, bytes_ = flops_lib.mhc_necessary(config, 4096)
  assert flops_lib.sublayers(config) == 12
  # (2n + 2) + (3n + 3) rows of 3,584 at 2 bytes a sublayer and token.
  assert bytes_ == 25 * 3584 * 2 * 4096 * 12 == pytest.approx(8.81e9,
                                                              rel=1e-3)
  assert ops == 3 * 2 * 14_336 * 24 * 4096 * 12
  # Bound by the bytes: 10.8 ms at the chip's 819 GB/s.
  assert bytes_ / BANDWIDTH > 10 * ops / PEAK
  # The core: one forward and one fused backward launch a layer, q/k 192
  # and v 128; one head size for all three would count more.
  launches = {"splash_mha_fwd_residuals": 6.0,
              "splash_mha_dkv_no_residuals": 6.0}
  flops, moved = flops_lib.attention_core_executed(config, 4096, 1, launches)
  pairs = 4096 ** 2 * 4
  assert flops == pairs * 6 * ((192 + 128) + (3 * 192 + 2 * 128))
  assert moved == 4096 * 4 * 2 * (6 * (2 * 192 + 2 * 128) +
                                  6 * (4 * 192 + 4 * 128))
  same = dict(config, v_head_dim=192)
  assert flops_lib.attention_core_executed(same, 4096, 1, launches)[0] == (
      lm_flops.attention_core_executed(same, 4096, 1, launches)[0])


def test_configuration_file_holds_the_published_keys():
  with open(PUBLISHED, encoding="utf-8") as f:
    published = {k: v for k, v in json.load(f).items()
                 if not k.startswith("_")}
  config = spec.load_config(REPO, CONFIG)
  for key, value in published.items():
    if key in config["reduced"]:
      assert config["published"][key] == value and config[key] != value
    else:
      assert config[key] == value, key
  assert config["reduced"] == [
      "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
      "num_attention_heads", "num_key_value_heads", "vocab_size"]
  assert config["deployment"]["chips_per_layer"] == 8
  assert (config["num_hidden_layers"], config["first_k_dense_replace"],
          config["n_routed_experts"], config["num_attention_heads"],
          config["vocab_size"]) == (5, 1, 8, 4, 16384)
  # The layers held are published layers 1-5: one dense, four mixture.
  params = config["params"]
  first, held = params["lm_first_layer_held"], params["lm_layers_held"]
  assert (first, held) == (1, config["num_hidden_layers"])
  from kf_benchmarks_tpu.models import mla_moe_lm
  cfg = mla_moe_lm.load_lm_config(
      params["lm_config"], held, params["lm_layer_shards"],
      params["lm_layer_shard_index"], first,
      head_shards=params["lm_head_shards"])
  assert (cfg.dense_layers, cfg.moe_layers) == (
      config["first_k_dense_replace"], 4)
  assert (cfg.heads_held, cfg.experts_held, cfg.vocab_rows) == (
      config["num_attention_heads"], config["n_routed_experts"],
      config["vocab_size"])
  for key in ("assumed", "held", "parameters_derivation"):
    assert config[key]


# -- the cell's metrics -------------------------------------------------------

@pytest.mark.parametrize("name", JOINED + NEW)
def test_the_cell_reports_the_metric(name):
  reports(REPO, CELL, [name])


def test_the_cell_reads_no_metric_of_the_other_families_keys_or_scopes():
  H.reads_no_metric_of_the_other_families(REPO)


MHC = "mhc/"
ATTN = "jvp(mla_attention)/jvp(attention_core)"


def xing_step_ops():
  """One step of the device, microseconds from its start, of a decoder
  with hyper-connections around its sublayers and latent attention,
  named as a trace of the program names them."""
  return [
      _fusion("fusion.1", 0, 4, FWD + MHC + "dot_general"),
      _fusion("fusion.2", 4, 6, FWD + MHC + "reduce_sum"),
      _kernel("splash_mha_fwd_residuals.3", 6, 16, FWD + ATTN),
      _fusion("fusion.4", 16, 20, FWD + "jvp(mla_attention)/dot_general"),
      _fusion("fusion.5", 20, 26, BWD + "rematted_computation/" + MHC +
              "add"),
      _kernel("splash_mha_dkv_no_residuals.6", 26, 46,
              "jit(s)/transpose(jvp(forward))/m/transpose(jvp(mla_attention))"
              "/transpose(jvp(attention_core))"),
      _fusion("fusion.7", 46, 60, BWD + "transpose(jvp(" + MHC[:-1] +
              "))/mul"),
      _fusion("fusion.8", 60, 95, "jit(s)/optimizer_apply/add"),
  ]


STATS = {"mhc": {"sinkhorn_residual": 3.5e-6, "streams": 4},
         "moe": {"pairs_routed_here": 4 * 4096.0, "compact_share": 1.0}}


def test_new_readers_on_a_made_up_trace(tmp_path, monkeypatch):
  _trace(tmp_path, monkeypatch, xing_step_ops())
  run = _run(CONFIG, 4096, 1, STATS)
  # The mixing: 4 + 2 forward, 6 formed again, 14 backward.
  assert _read("mhc_ms", run) == pytest.approx(26e-3)
  ops, bytes_ = flops_lib.mhc_necessary(run.config, 4096)
  assert _read("mhc_roofline", run) == pytest.approx(
      100 * max(ops / PEAK, bytes_ / BANDWIDTH) / (26 * US))
  assert _read("mhc_sinkhorn_residual", run) == 3.5e-6
  flops, moved = flops_lib.attention_core_executed(
      run.config, 4096, 1, {"splash_mha_fwd_residuals": 1.0,
                            "splash_mha_dkv_no_residuals": 1.0})
  assert _read("attention_core_2size_roofline", run) == pytest.approx(
      100 * max(flops / PEAK, moved / BANDWIDTH) / (30 * US))
  assert _read("attention_core_ms", run) == pytest.approx(30e-3)
  assert _read("mla_attention_ms", run) == pytest.approx(34e-3)


def test_new_readers_read_nothing_where_there_is_nothing(tmp_path,
                                                         monkeypatch):
  # An untraced run; then a traced run of a program WITHOUT the scope (the
  # parent of this PR); then a program with one stream: None, no raise.
  untraced = _run(CONFIG, 4096, 1, STATS, traced=False)
  assert all(_read(name, untraced) is None
             for name in NEW if name != "mhc_sinkhorn_residual")
  _trace(tmp_path, monkeypatch, LM_STEP_OPS)
  other = _run(CONFIG, 4096, 1, {"moe": STATS["moe"]})
  for name in ("mhc_ms", "mhc_roofline", "mhc_sinkhorn_residual"):
    assert _read(name, other) is None
  assert _read("mhc_sinkhorn_residual", _run(CONFIG, 4096, 1, None)) is None


def test_mhc_roofline_reads_100_at_the_necessary_time_and_never_over(
    tmp_path, monkeypatch):
  config = spec.load_config(REPO, CONFIG)
  tokens = 16
  ops, bytes_ = flops_lib.mhc_necessary(config, tokens)
  least_us = max(ops / PEAK, bytes_ / BANDWIDTH) / US
  assert 20 < least_us < 60
  run = _run(CONFIG, tokens, 1, STATS)
  for times, where in ((1, "least"), (1.25, "slower")):
    took = times * least_us
    os.makedirs(tmp_path / where)
    _trace(tmp_path / where, monkeypatch, [
        _fusion("fusion.1", 0, took, FWD + MHC + "add"),
        _fusion("fusion.2", took, 99, "jit(s)/optimizer_apply/add")])
    assert _read("mhc_roofline", run) == pytest.approx(100.0 / times,
                                                       rel=1e-4)


def test_configuration_and_cell_are_admitted_in_a_temporary_tree(tmp_path):
  root = copy_tree(str(tmp_path))
  admit(root)
  H.cell_and_configuration_are_there(root)


# -- the reference check, end to end at the tiny preset -----------------------

def _controls():
  spec_ = importlib.util.spec_from_file_location(
      "_lm_controls", os.path.join(REPO, "experiments",
                                   "lm_precision_control.py"))
  module = importlib.util.module_from_spec(spec_)
  spec_.loader.exec_module(module)
  return module


def _tiny_run(monkeypatch, fault=None):
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  if fault:
    _controls().plant(fault, functools.partial(monkeypatch.setattr,
                                               raising=False))
  kwargs = dict(
      model="mla_moe_lm", lm_config="tiny-xing", seq_len=32,
      batch_size=2, lm_first_layer_held=0, lm_layers_held=3,
      lm_layer_shards=4, lm_layer_shard_index=1, lm_head_shards=2,
      device="cpu", optimizer="adam", init_learning_rate=1e-4,
      weight_decay=0.0, num_batches=3, num_warmup_batches=1,
      display_every=1, tf_random_seed=11)
  bench = benchmark.BenchmarkCNN(benchmark.setup(
      params_lib.make_params(**kwargs)))
  stats = bench.run()
  config = read_json(os.path.join(
      REPO, "kf_benchmarks_tpu", "models", "lm_configs", "tiny-xing.json"))
  return harness.Run(cell={"name": "tiny", "config_data": config},
                     device={}, peaks={}, kwargs=kwargs, timed_steps=3,
                     t0=0.0, stats=stats, bench=bench)


def test_reference_check_passes_on_the_program(monkeypatch):
  run = _tiny_run(monkeypatch)
  check = spec.load_check(REPO, CHECK)
  assert check.check(run, None) == []
  compared = run.compared
  assert compared["pairs_dropped"] == {"value": 0.0, "limit": 0}
  assert set(compared) == set(check.LIMITS)
  # float32 on the CPU: everything far inside the chip's limits.
  assert compared["hc_res_err"]["value"] < 1e-5
  assert compared["attention_output_err"]["value"] < 1e-4
  assert compared["grad_err.attn_hc_phi"]["value"] < 1e-3
  assert compared["sinkhorn_residual"]["value"] < 1e-4
  assert run.stats["state"] is None      # the state made room


# Each planted fault, and the numbers of the check that have to see it
# (PERF.md section 6 has the chip's readings of the same).
@pytest.mark.parametrize("fault, seen_by", [
    ("mhc_bf16", ["hc_pre_err", "hc_post_err", "hc_res_err"]),
    ("sinkhorn_1", ["hc_res_err", "sinkhorn_residual"]),
    ("h_res_identity", ["hc_res_err", "layer_output_err"]),
    ("h_post_no_2", ["hc_post_err", "layer_output_err"]),
    # (At the preset's widths and 0.02 weights every score is near 0, so
    # the softmax barely sees YaRN's scale: q's gradient does, which the
    # scale multiplies. The chip's readings at the published widths are
    # in PERF.md section 6.)
    ("no_yarn", ["grad_err.q_b_proj"]),
    ("state_unchanged", ["param_change_err"]),
])
def test_reference_check_sees_a_planted_fault(monkeypatch, fault, seen_by):
  run = _tiny_run(monkeypatch, fault)
  check = spec.load_check(REPO, CHECK)
  failures = check.check(run, None)
  for name in seen_by:
    assert any(f.startswith(name + " ") for f in failures), (name, failures)
