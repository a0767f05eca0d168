"""The nemotron-3-nano-30b-a3b cell's own pieces, without a chip: the
operation and parameter counts against the numbers the files state, the
configuration's file against the published one, the accepted metrics the
cell joins, the four new readers on a made-up traced run (scope present,
scope absent, never over 100 at the necessary time), the configuration
and cell admitted by the repository's own rules in a temporary tree, and
the reference check itself, run end to end at the ``tiny-nemotron-h``
preset on the CPU (where it has to pass, and fail under each control)."""

import functools
import importlib.util
import json
import os
import shutil

import pytest

from bench_testlib import REPO, read_json
from benchmarks import harness
from benchmarks import nemotron_h_flops as flops_lib
from benchmarks import spec
from test_bench_lm_readers import (BWD, FWD, PEAK, ROUND, US, _fusion,
                                   _kernel, _launches, _read, _run, _trace)
from test_bench_spec import admit, metric_rules, reported_where_named

CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nemotron-3-nano-30b-a3b-train-seq8192-bs1-1chip"
CHECK = "nemotron-3-nano-30b-a3b_reference_agrees"
PUBLISHED = os.path.join(REPO, "kf_benchmarks_tpu", "models", "lm_configs",
                         CONFIG + ".json")
# The accepted metrics whose readers find something in this cell: each
# entry's ``workloads`` names the cell (among whichever others).
JOINED = ["moe_route_ms", "moe_experts_ms", "lm_head_ms",
          "moe_load_max_over_mean", "moe_compact_share",
          "train_loss_step_16", "optimizer_ms.lm", "hbm_peak_in_use_gib",
          "hbm_peak_reserved_gib", "gqa_attention_ms",
          "attention_core_full_ms", "attention_core_full_roofline"]
NEW = ["mamba_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline",
       "moe_experts_2mat_roofline"]
BANDWIDTH = 819e9


# -- the counts against the numbers the files state ---------------------------

def test_forward_operations_are_the_number_the_cell_states():
  cell = spec.load_cell(REPO, CELL)
  config = cell["config_data"]
  per_token = flops_lib.forward_flops_per_token(
      config, cell["tokens_per_sample"])
  assert per_token == 715_042_816
  assert cell["forward_flops_per_sample"] == per_token
  assert config["sample_unit"] == "tokens"
  # 17.6 TFLOP a training step of 8,192 tokens.
  assert 3 * per_token * 8192 == pytest.approx(17.573e12, rel=1e-4)
  assert flops_lib.mamba_projection_params(config) == 38_707_200
  assert flops_lib.scan_flops_per_token(config) == (
      2 * 128 * 8 * 64.5 + 2 * 64 * 64 * 64.5 + 2 * 2 * 64 * 64 * 128 +
      2 * 64 * 64 * 128 / 128) == 2_765_824
  assert flops_lib.attention_projection_params(config) == 23_396_352
  assert flops_lib.attention_core_flops_per_token(
      config, 8192) == 2 * 32 * 256 * 4096.5
  assert [flops_lib.layers_of(config, kind) for kind in "ME*"] == [4, 4, 1]
  # The four Mamba mixers are 45% of the counted work.
  mamba = 4 * (2 * 38_707_200 + 2_765_824)
  assert mamba / per_token == pytest.approx(0.4485, abs=1e-3)


def test_parameters_are_the_number_the_configuration_states():
  config = spec.load_config(REPO, CONFIG)
  assert flops_lib.parameters(config) == config["parameters"] == 666_963_456
  # 16 bytes a parameter: 63% of the 15.75 GiB the runtime gives.
  assert 16 * config["parameters"] / (15.75 * 2 ** 30) == pytest.approx(
      0.631, abs=2e-3)
  # The whole model by the same count, from the published keys.
  whole = dict(config, **config["published"])
  whole["published"] = config["published"]
  assert flops_lib.parameters(whole) == 31_577_940_288
  # 8 chips a layer (16 experts held, an eighth of the vocabulary): the
  # cut the issue weighed first does not fit.
  eight = dict(config, n_routed_experts=16)
  assert flops_lib.parameters(eight) == 986_254_848


def test_scan_necessary_and_two_matrix_counts():
  config = spec.load_config(REPO, CONFIG)
  ops, bytes_ = flops_lib.ssd_scan_necessary(config, 8192)
  assert ops == 3 * 2_765_824 * 8192 * 4
  # x and y 4,096 and B and C 1,024 each at 2 bytes, dt 64 at 4, twice
  # (the values and their gradients).
  assert bytes_ == 2 * ((4096 + 4096 + 2048) * 2 + 64 * 4) * 8192 * 4
  # Bound by the bytes, narrowly: 1.66 ms against 1.38 ms of operations.
  assert 1.15 < (bytes_ / BANDWIDTH) / (ops / PEAK) < 1.25
  # 3,072 pairs a mixture layer (6 x 8,192 x 8 / 128), four layers, two
  # launches a pass: 24 gmm and 8 tgmm a step are four passes.
  executed = flops_lib.moe_experts_executed(config, 4 * 3072, 24, 8, True)
  weights = 4 * 8 * 2 * 2688 * 1856
  assert executed == (
      4 * 2 * 2 * 4 * 3072 * 2688 * 1856,
      4 * 2 * 4 * 3072 * (2688 + 1856) * 2 + 3 * weights * 2 + weights * 2)
  assert flops_lib.moe_experts_passes(4, 24, 8, True) == 4
  assert flops_lib.moe_experts_passes(4, 32, 8, True) == 5
  # Not the pattern: a three-matrix expert's launches (36 + 12 over four
  # layers would be 1.5 rounds a layer), a fused kernel, no backward.
  assert flops_lib.moe_experts_passes(4, 36, 12, True) is None
  assert flops_lib.moe_experts_passes(4, 20, 8, True) is None
  assert flops_lib.moe_experts_passes(4, 8, 8, True) is None
  assert flops_lib.moe_experts_passes(4, 24, 0, True) is None
  # Two rounds in some steps: the mean a layer is over one.
  assert flops_lib.moe_experts_passes(4, 27, 9, False) == 4
  assert flops_lib.moe_experts_passes(4, 27, 9, True) is None


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
  assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size"]
  assert config["deployment"]["chips_per_layer"] == 16
  assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
          config["n_routed_experts"], config["vocab_size"]) == (
              9, "MEMEM*EME", 8, 16384)
  # The layers held are published layers 0-8, kinds and all; the experts
  # lie over 16 chips and the vocabulary over 8 of them.
  params = config["params"]
  first, held = params["lm_first_layer_held"], params["lm_layers_held"]
  assert (first, held) == (0, config["num_hidden_layers"])
  assert config["hybrid_override_pattern"] == published[
      "hybrid_override_pattern"][first:first + held]
  assert (published["n_routed_experts"] // params["lm_layer_shards"] ==
          config["n_routed_experts"])
  assert (published["vocab_size"] // params["lm_vocab_shards"] ==
          config["vocab_size"])
  assert (params["lm_layer_shards"], params["lm_vocab_shards"]) == (16, 8)
  # The program reads the same command.
  from kf_benchmarks_tpu.models import mla_moe_lm
  cfg = mla_moe_lm.load_lm_config(
      params["lm_config"], held, params["lm_layer_shards"],
      params["lm_layer_shard_index"], first, params["lm_vocab_shards"])
  assert (cfg.kinds, cfg.experts_held, cfg.vocab_rows) == (
      config["hybrid_override_pattern"], 8, 16384)


# -- the cell's metrics -------------------------------------------------------

@pytest.mark.parametrize("name", JOINED + NEW)
def test_the_cell_reports_the_metric(name):
  # The cell is among those the entry names, whoever else is: the next
  # decoder cell joins the same way and fails nothing here.
  metric_rules(REPO, "per_layer", name)
  reported_where_named(REPO, name, expected=[CELL])


def test_the_cell_reads_no_metric_of_the_other_families_keys_or_scopes():
  mine = spec.cell_metrics(REPO, "per_layer", CELL)
  # ``mla_attention`` and a window are no scope of this decoder, and
  # ``moe_experts_roofline`` counts three products a pair.
  assert not {"mla_attention_ms", "attention_core_ms",
              "attention_core_roofline", "attention_core_window_ms",
              "attention_core_window_roofline",
              "attention_tiles_visited_share", "moe_experts_roofline"} & set(
                  mine)
  generic = [m["name"] for m in spec.load_benchmark(REPO)["per_layer"]
             if "workloads" not in m]
  assert set(generic) <= set(mine)
  # The new entries stand at the END of the list, in this order, and are
  # this cell's alone.
  listed = spec.load_benchmark(REPO)["per_layer"]
  assert [m["name"] for m in listed[-len(NEW):]] == NEW
  assert all(m["workloads"] == [CELL] for m in listed[-len(NEW):])
  assert {m["layer"] for m in listed[-len(NEW):]} == {"state_space",
                                                      "kernels"}


def nemotron_step_ops():
  """One step of the device, microseconds from its start, of a decoder
  with one Mamba mixer, one attention layer and four mixture layers (two
  grouped products a pass and a layer), named as a trace of the program
  names them: the inside of the mixer that its backward pass forms again
  under ``checkpoint`` / ``rematted_computation``."""
  n = 2 * 4
  mamba = "mamba_mixer/"
  again = BWD + "mamba_mixer/rematted_computation/"
  pullback = BWD + ROUND + "jit(_round_pullback)/"
  return [
      _fusion("fusion.1", 0, 4, FWD + mamba + "dot_general"),
      _fusion("fusion.2", 4, 6, FWD + mamba + "checkpoint/mamba_conv/add"),
      _fusion("fusion.3", 6, 16,
              FWD + mamba + "checkpoint/ssd_scan/dot_general"),
      _kernel("splash_mha_fwd_residuals.4", 16, 20,
              FWD + "gqa_attention/attention_core_full"),
      *_launches("gmm", 100, 20, 24, FWD + ROUND + "moe_experts/jit(gmm)",
                 n),
      *_launches("gmm", 300, 24, 28, pullback +
                 "jvp(jit(experts_round))/moe_experts/jit(gmm)", n),
      *_launches("gmm", 400, 28, 32, pullback +
                 "transpose(jvp(jit(experts_round)))/moe_experts/jit(gmm)",
                 n),
      *_launches("tgmm", 500, 32, 36, pullback +
                 "transpose(jvp(jit(experts_round)))/moe_experts/jit(tgmm)",
                 n),
      _kernel("splash_mha_dkv_no_residuals.9", 36, 46,
              BWD + "gqa_attention/attention_core_full"),
      _fusion("fusion.10", 46, 56, again + "ssd_scan/dot_general"),
      _fusion("fusion.11", 56, 76,
              BWD + "transpose(jvp(mamba_mixer))/transpose(jvp(checkpoint))/"
              "transpose(jvp(ssd_scan))/dot_general"),
      _fusion("fusion.12", 76, 80,
              BWD + "transpose(jvp(mamba_mixer))/dot_general"),
      _fusion("fusion.13", 80, 95, "jit(s)/optimizer_apply/add"),
  ]


STATS = {"moe": {"pairs_routed_here": 4 * 3072.0, "compact_share": 1.0,
                 "expert_matrices": 2}}


def test_new_readers_on_a_made_up_trace(tmp_path, monkeypatch):
  _trace(tmp_path, monkeypatch, nemotron_step_ops())
  run = _run(CONFIG, 8192, 1, STATS)
  # Everything of the mixer: 4 + 2 + 10 forward, 10 formed again, 20 + 4
  # backward; the scan inside it 10 + 10 + 20.
  assert _read("mamba_mixer_ms", run) == pytest.approx(50e-3)
  assert _read("ssd_scan_ms", run) == pytest.approx(40e-3)
  ops, bytes_ = flops_lib.ssd_scan_necessary(run.config, 8192)
  least = max(ops / PEAK, bytes_ / BANDWIDTH)
  assert _read("ssd_scan_roofline", run) == pytest.approx(
      100 * least / (40 * US))
  flops, moved = flops_lib.moe_experts_executed(run.config, 4 * 3072, 24, 8,
                                                True)
  assert _read("moe_experts_2mat_roofline", run) == pytest.approx(
      100 * max(flops / PEAK, moved / BANDWIDTH) / (16 * US))
  # The joined readers find this decoder's scopes as they stand.
  assert _read("gqa_attention_ms", run) == pytest.approx(14e-3)
  assert _read("attention_core_full_ms", run) == pytest.approx(14e-3)
  assert _read("moe_experts_ms", run) == pytest.approx(16e-3)
  assert _read("attention_core_full_roofline", run) > 0


def test_new_readers_read_nothing_where_there_is_nothing(tmp_path,
                                                         monkeypatch):
  # An untraced run; then a traced run of a program WITHOUT the scopes
  # (the parent of the PR that added them): None, and nothing raises.
  untraced = _run(CONFIG, 8192, 1, STATS, traced=False)
  assert all(_read(name, untraced) is None for name in NEW)
  from test_bench_lm_readers import LM_STEP_OPS
  _trace(tmp_path, monkeypatch, LM_STEP_OPS)
  other = _run(CONFIG, 8192, 1, STATS)
  for name in ("mamba_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline"):
    assert _read(name, other) is None
  # That trace HAS grouped products, three a pass: not this metric's
  # pattern at this configuration's four layers, nor a program's whose
  # experts are three matrices, nor one that states no form.
  assert _read("moe_experts_2mat_roofline", other) is None
  for stats in ({"moe": dict(STATS["moe"], expert_matrices=3)},
                {"moe": {"pairs_routed_here": 1.0}}, {}, None):
    assert _read("moe_experts_2mat_roofline",
                 _run(CONFIG, 8192, 1, stats)) is None


def test_scan_roofline_reads_100_at_the_necessary_time_and_never_over(
    tmp_path, monkeypatch):
  # A scan that took exactly the least time the chip could take for the
  # necessary work reads 100; any time over it reads under. (A step of
  # 128 tokens: the made-up trace's steps are 100 microseconds.)
  config = spec.load_config(REPO, CONFIG)
  tokens = 128
  ops, bytes_ = flops_lib.ssd_scan_necessary(config, tokens)
  least_us = max(ops / PEAK, bytes_ / BANDWIDTH) / US
  assert 20 < least_us < 30
  run = _run(CONFIG, tokens, 1, STATS)
  for times, where in ((1, "least"), (3, "slower")):
    took = times * least_us
    os.makedirs(tmp_path / where)
    _trace(tmp_path / where, monkeypatch, [
        _fusion("fusion.1", 0, took,
                FWD + "mamba_mixer/checkpoint/ssd_scan/dot_general"),
        _fusion("fusion.2", took, 95, "jit(s)/optimizer_apply/add")])
    assert _read("ssd_scan_roofline", run) == pytest.approx(
        100.0 / times, rel=1e-4)


def test_configuration_and_cell_are_admitted_in_a_temporary_tree(tmp_path):
  # The repository's own rules (tests/benchmarks/test_bench_spec.admit)
  # on a copy of the tree: every configuration, cell, metric and file,
  # the new ones among them.
  root = str(tmp_path)
  shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
  shutil.copytree(os.path.join(REPO, "benchmarks"),
                  os.path.join(root, "benchmarks"),
                  ignore=shutil.ignore_patterns("__pycache__"))
  admit(root)
  bench = spec.load_benchmark(root)
  assert [w["name"] for w in bench["workloads"]][-1] == CELL
  assert [c["name"] for c in bench["configs"]][-1] == CONFIG
  assert len(bench["workloads"]) == 6
  assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
  cell = spec.load_cell(root, CELL)
  kwargs = harness.job_kwargs(cell, seed=2 ** 31 + 5, seconds=10)
  assert kwargs["lm_vocab_shards"] == 8 and kwargs["seq_len"] == 8192
  assert kwargs["num_batches"] == 34
  assert spec.check_names(cell) == [CHECK]


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
  # 64 positions in chunks of 8: the scaled position of ``scan_carry_err``
  # has its three chunks on inside the sequence.
  kwargs = dict(
      model="mla_moe_lm", lm_config="tiny-nemotron-h", seq_len=64,
      batch_size=1, lm_layer_shards=4, lm_layer_shard_index=1,
      lm_vocab_shards=2, device="cpu", optimizer="adam",
      init_learning_rate=1e-4, weight_decay=0.0, num_batches=3,
      num_warmup_batches=1, display_every=1, tf_random_seed=11)
  bench = benchmark.BenchmarkCNN(benchmark.setup(
      params_lib.make_params(**kwargs)))
  stats = bench.run()
  config = read_json(os.path.join(
      REPO, "kf_benchmarks_tpu", "models", "lm_configs",
      "tiny-nemotron-h.json"))
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
  assert all(v["value"] <= v["limit"] for v in compared.values())
  assert compared["grad_err.expert_down"]["value"] < 1e-3
  assert compared["grad_err.A_log"]["value"] < 1e-5
  assert compared["grad_err.dt_bias"]["value"] < 1e-5
  assert compared["scan_carry_err"]["value"] < 1e-5
  assert compared["param_change_err"]["value"] < 1e-2
  assert run.stats["state"] is None      # the state made room
  # The rows the carry is read at: after the scaled position in its own
  # chunk, in the next, three chunks on.
  assert check.carry_rows(4096 + 32, 128, 8192) == [
      list(range(4129, 4137)), list(range(4256, 4264)),
      list(range(4512, 4520))]
  assert check.carry_rows(34, 8, 64) == [
      [35, 36, 37, 38, 39], [42, 43, 44, 45, 46], [58, 59, 60, 61, 62]]
  assert check.carry_rows(10, 8, 32) is None


def test_carry_err_is_a_median_over_rows_with_a_floor():
  # What the chip taught (PERF.md section 6, PR 39): one row of a group
  # half wrong (a near-zero ``C_t . B_j`` under bfloat16 operands) and a
  # change that has died three chunks on must both leave the reading
  # where a sound program's is; a carry lost, or passed one link only,
  # must not.
  import jax
  import jax.numpy as jnp
  check = spec.load_check(REPO, CHECK)
  keys = jax.random.split(jax.random.PRNGKey(0), 3)
  base = jax.random.normal(keys[0], (1, 24, 64))
  want = 1.3 * jax.random.normal(keys[1], (1, 24, 64))
  noise = 0.01 * jax.random.normal(keys[2], (1, 24, 64))
  groups = [(0, 8), (8, 24)]
  clean = check.carry_err(want + noise, want, base, groups)
  assert 0.005 < clean < 0.01
  flipped = (want + noise).at[:, 5].multiply(0.5)
  assert check.carry_err(flipped, want, base, groups) == pytest.approx(
      clean, rel=0.2)
  # The change three chunks on a hundredth of the output: the noise
  # counts against CARRY_FLOOR of the row's own output, not against it.
  died = want.at[:, 16:].multiply(0.01 / 1.3)
  reading = check.carry_err(died + noise, died, base, groups)
  assert reading < 0.01 / check.CARRY_FLOOR * 0.6 and reading < 0.15
  lost = (want + noise).at[:, 8:].set(0.0)
  assert check.carry_err(lost, want, base, groups) == pytest.approx(1.0)
  one_link = (want + noise).at[:, 16:].set(0.0)
  assert check.carry_err(one_link, want, base, groups) == pytest.approx(
      0.5, abs=0.01)


# Each planted fault, and the numbers of the check that have to see it
# (the lower-precision control of the state-space layers first; PERF.md
# section 6 has the chip's readings of the same).
@pytest.mark.parametrize("fault, seen_by", [
    # (At the preset's chunks of 8 positions a bfloat16 running sum is a
    # hundred times nearer than at the cell's 128: it is seen by what it
    # reads beside the clean program's 1e-6, not by the chip's limits.)
    ("scan_bf16", []),
    ("no_chunk_carry", ["scan_carry_err", "grad_err.A_log",
                        "grad_err.dt_bias"]),
    ("gate_after_norm", ["layer_output_err", "grad_err.gate_norm"]),
    ("experts_gated", ["layer_output_err", "grad_err.expert_up"]),
    ("router_bf16", ["router_scores_err"]),
    ("state_unchanged", ["param_change_err"]),
    ("half_batch", ["grad_err.lm_head", "grad_err.expert_down",
                    "grad_err.in_proj", "grad_err.k_proj"]),
    ("no_scaling", ["grad_err.expert_up", "grad_err.router"]),
])
def test_reference_check_sees_a_planted_fault(monkeypatch, fault, seen_by):
  run = _tiny_run(monkeypatch, fault)
  check = spec.load_check(REPO, CHECK)
  failures = check.check(run, None)
  for name in seen_by:
    assert any(f.startswith(name + " ") for f in failures), (name, failures)
  if fault == "state_unchanged":
    assert run.compared["param_change_err"]["value"] == pytest.approx(1.0)
  if fault == "scan_bf16":
    assert run.compared["scan_carry_err"]["value"] > 1e-2
    assert run.compared["grad_err.A_log"]["value"] > 1e-3
    assert run.compared["grad_err.dt_bias"]["value"] > 1e-3
  if fault == "no_chunk_carry":
    assert run.compared["scan_carry_err"]["value"] == pytest.approx(
        1.0, abs=1e-3)
