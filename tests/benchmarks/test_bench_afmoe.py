"""The trinity-mini cell's own pieces, without a chip: the operation
counts against the numbers the files state, the configuration's file
against the published one, the accepted metrics the cell joins, and the
reference check itself, run end to end at the
``tiny-afmoe`` preset on the CPU (where it has to pass, and fail under
each control)."""

import functools
import importlib.util
import json
import os

import pytest

from bench_testlib import REPO, read_json
from benchmarks import afmoe_flops
from benchmarks import harness
from benchmarks import lm_flops
from benchmarks import spec
from test_bench_spec import metric_rules, reported_where_named

CELL = "trinity-mini-train-seq8192-bs1-1chip"
CHECK = "trinity-mini_reference_agrees"
PUBLISHED = os.path.join(REPO, "kf_benchmarks_tpu", "models", "lm_configs",
                         "trinity-mini.json")
# The accepted metrics whose readers find something in this cell: each
# entry's ``workloads`` names the cell (among whichever others).
JOINED = ["moe_route_ms", "moe_experts_ms", "lm_head_ms",
          "moe_load_max_over_mean", "train_loss_step_16", "optimizer_ms.lm",
          "hbm_peak_in_use_gib", "hbm_peak_reserved_gib",
          "moe_experts_roofline", "moe_compact_share"]   # the last two: PR 34


def _run(stats=None, **kwargs):
  return harness.Run(cell={"name": "x", "config_data": {}}, device={},
                     peaks={}, kwargs={}, timed_steps=20, t0=0.0,
                     stats=stats, **kwargs)


def test_forward_operations_are_the_number_the_cell_states():
  cell = spec.load_cell(REPO, CELL)
  config = cell["config_data"]
  per_token = afmoe_flops.forward_flops_per_token(
      config, cell["tokens_per_sample"])
  assert per_token == 737_951_744
  assert cell["forward_flops_per_sample"] == per_token
  assert config["sample_unit"] == "tokens"
  # 18.1 TFLOP a training step of 8,192 tokens.
  assert 3 * per_token * 8192 == pytest.approx(18.136e12, rel=1e-4)
  assert afmoe_flops.attention_projection_params(config) == 27_262_976
  # 0.44 of the causal pairs lie in the band.
  assert afmoe_flops.band_pairs(8192, 2048) == 14_681_088
  assert afmoe_flops.band_pairs(8192, None) == 8192 * 8193 // 2
  assert afmoe_flops.band_pairs(8192, 2048) / afmoe_flops.band_pairs(
      8192, None) == pytest.approx(0.4375, abs=1e-3)
  assert afmoe_flops.attention_core_flops_per_token(
      config, 8192, afmoe_flops.FULL) == 2 * 32 * 256 * 4096.5
  assert afmoe_flops.attention_core_flops_per_token(
      config, 8192, afmoe_flops.WINDOW) == 2 * 32 * 256 * 1792.125
  assert (afmoe_flops.layers_of(config, afmoe_flops.WINDOW),
          afmoe_flops.layers_of(config, afmoe_flops.FULL)) == (4, 1)


def test_executed_counts():
  config = spec.load_config(REPO, "trinity-mini")
  peaks = spec.load_peaks(REPO)["TPU v5 lite"]
  # 8,192 pairs a mixture layer, four layers, and the cell's launches a
  # step (48 gmm, 12 tgmm): five passes, through the one function both
  # families share, which reads this family's key names.
  flops, bytes_ = lm_flops.moe_experts_executed(config, 4 * 8192, 48, 12)
  assert flops == 5 * 3 * 2 * 4 * 8192 * 2048 * 1024
  weights = 4 * 16 * 3 * 2048 * 1024
  assert bytes_ == 15 * 4 * 8192 * 3072 * 2 + 4 * weights * 2 + weights * 2
  # At 512 rows an expert the products sit near the chip's ridge: the
  # operations take 10.5 ms of its peak, the bytes 8.6 ms of its bandwidth.
  assert 1.1 < (flops / peaks["bf16_flops_per_s"]) / (
      bytes_ / peaks["hbm_bytes_per_s"]) < 1.3
  for kind, layers, pairs in ((afmoe_flops.WINDOW, 4, 14_681_088),
                              (afmoe_flops.FULL, 1, 33_558_528)):
    # One forward and one fused backward launch a layer, as the cell's
    # trace shows: 7 products of the pairs inside the band, at head size
    # 128.
    launches = {"splash_mha_fwd_residuals": layers,
                "splash_mha_dkv_no_residuals": layers, "reduce": 9}
    flops, bytes_ = afmoe_flops.attention_core_executed(
        config, 8192, 1, kind, launches)
    assert flops == 7 * 2 * pairs * 128 * 32 * layers
    # K and V at the 4 key heads, q and its like at the 32.
    tensor = lambda heads: 8192 * heads * 128 * 2
    assert bytes_ == (6 * tensor(32) + 6 * tensor(4)) * layers
    assert flops / peaks["bf16_flops_per_s"] > bytes_ / peaks[
        "hbm_bytes_per_s"]
    # A forward that ran twice a layer would be 9, counted where it runs.
    twice = dict(launches, splash_mha_fwd_residuals=2 * layers)
    assert afmoe_flops.attention_core_executed(
        config, 8192, 1, kind, twice)[0] == 9 * 2 * pairs * 128 * 32 * layers


def test_configuration_file_holds_the_published_keys():
  with open(PUBLISHED, encoding="utf-8") as f:
    published = {k: v for k, v in json.load(f).items()
                 if not k.startswith("_")}
  config = spec.load_config(REPO, "trinity-mini")
  for key, value in published.items():
    if key in config["reduced"]:
      assert config["published"][key] == value and config[key] != value
    else:
      assert config[key] == value, key
  assert config["deployment"]["chips_per_layer"] == 8
  assert (config["num_hidden_layers"], config["num_dense_layers"],
          config["num_experts"], config["vocab_size"]) == (5, 1, 16, 25024)
  # The layers held are published layers 1-5, kinds and all.
  params = config["params"]
  first, held = params["lm_first_layer_held"], params["lm_layers_held"]
  assert (first, held) == (1, config["num_hidden_layers"])
  assert config["layer_types"] == published["layer_types"][
      first:first + held]
  assert config["num_dense_layers"] == published["num_dense_layers"] - first
  assert (published["num_experts"] // params["lm_layer_shards"] ==
          config["num_experts"])
  assert (published["vocab_size"] // params["lm_layer_shards"] ==
          config["vocab_size"])
  assert config["parameters"] == 705_473_792


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_an_accepted_metric(name):
  # The cell is among those the entry names, whoever else is: the next
  # decoder cell joins the same way and fails nothing here.
  metric_rules(REPO, "per_layer", name)
  reported_where_named(REPO, name, expected=[CELL])


def test_the_cell_reads_no_metric_of_the_other_familys_keys_or_scopes():
  mine = spec.cell_metrics(REPO, "per_layer", CELL)
  # ``mla_attention`` / ``attention_core`` are no scope of this decoder,
  # and ``lm_flops.attention_core_executed`` reads the other family's
  # head sizes.
  assert not {"mla_attention_ms", "attention_core_ms",
              "attention_core_roofline"} & set(mine)
  # Every metric that names no cell is read in the new cell too.
  generic = [m["name"] for m in spec.load_benchmark(REPO)["per_layer"]
             if "workloads" not in m]
  assert set(generic) <= set(mine)


@pytest.mark.parametrize("name, stats, want", [
    ("moe_load_max_over_mean", {"moe": {"load_max_over_mean": 1.19}}, 1.19),
    ("hbm_peak_in_use_gib",
     {"device_memory": {"peak_bytes_in_use": 13 * 2 ** 30}}, 13.0),
    ("hbm_peak_reserved_gib",
     {"device_memory": {"peak_bytes_reserved": 2 ** 30}}, 1.0),
])
def test_a_joined_counter_reads_what_this_decoder_leaves(name, stats, want):
  assert spec.load_metric(REPO, "per_layer", name).read(_run(stats)) == want


def test_the_program_states_the_block_skip(monkeypatch):
  # stats["attention"] as the program's model states it for the cell's
  # shapes on a TPU: of the tiles a causal mask visits (forward and
  # backward grids, by area) the band's tables visit under 0.75; the full
  # layer visits them all (``attention_tiles_visited_share`` reads it).
  import jax
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.models import mla_moe_lm
  config = spec.load_config(REPO, "trinity-mini")
  traffic = read_json(os.path.join(
      REPO, "benchmarks", "traffic", "train-seq8192-bs1-1chip.json"))
  kwargs = dict(config["params"], **traffic["params"])
  kwargs["device"] = "cpu"
  model = mla_moe_lm.MLAMoELMModel(params_lib.make_params(**kwargs))
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert model.cfg.first_layer == 1
  stats = model.attention_core_stats()
  window, full = stats["window"], stats["full"]
  assert 0.44 < window["tiles_visited"] / window["tiles_causal"] < 0.75
  assert full["tiles_visited"] == full["tiles_causal"] > 0
  assert spec.load_metric(
      REPO, "per_layer", "attention_tiles_visited_share").read(
          _run({"attention": stats})) == (
              window["tiles_visited"] / window["tiles_causal"])


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
      model="mla_moe_lm", lm_config="tiny-afmoe", seq_len=32, batch_size=1,
      lm_layer_shards=4, lm_layer_shard_index=1, device="cpu",
      optimizer="adam", init_learning_rate=1e-4, weight_decay=0.0,
      num_batches=3, num_warmup_batches=1, display_every=1,
      tf_random_seed=11)
  bench = benchmark.BenchmarkCNN(benchmark.setup(
      params_lib.make_params(**kwargs)))
  stats = bench.run()
  config = read_json(os.path.join(
      REPO, "kf_benchmarks_tpu", "models", "lm_configs", "tiny-afmoe.json"))
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
  assert compared["window_edge_err"]["value"] < 1e-3
  assert compared["param_change_err"]["value"] < 1e-2
  assert run.stats["state"] is None      # the state made room


# Each planted fault, and the numbers of the check that have to see it
# (the lower-precision control first; PERF.md section 6 has the chip's
# readings of the same).
@pytest.mark.parametrize("fault, seen_by", [
    ("router_bf16", ["router_scores_err"]),
    ("window_2047", ["window_edge_err"]),
    ("window_2049", ["window_edge_err"]),
    ("rope_in_full", ["layer_output_err", "grad_err.full_q_proj"]),
    ("no_gate", ["layer_output_err", "grad_err.gate_proj"]),
    ("no_post_norms", ["layer_output_err", "hidden_last_err"]),
    ("state_unchanged", ["param_change_err"]),
    ("half_batch", ["grad_err.lm_head", "grad_err.expert_down",
                    "grad_err.router", "grad_err.k_proj"]),
    ("no_scaling", ["grad_err.expert_gate", "grad_err.router"]),
])
def test_reference_check_sees_a_planted_fault(monkeypatch, fault, seen_by):
  run = _tiny_run(monkeypatch, fault)
  check = spec.load_check(REPO, CHECK)
  failures = check.check(run, None)
  for name in seen_by:
    assert any(f.startswith(name + " ") for f in failures), (name, failures)
  if fault == "state_unchanged":
    assert run.compared["param_change_err"]["value"] == pytest.approx(1.0)
