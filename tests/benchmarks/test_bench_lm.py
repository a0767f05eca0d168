"""The glm-4.7-flash cell's own pieces, without a chip: the operation
counts against the numbers the files state, the scope reader's parsing,
the configuration's file against the published one, and the reference
check itself, run end to end at the tiny preset on the CPU (where it has
to pass, and fail under the lower-precision control)."""

import json
import os

import pytest

from bench_testlib import REPO, read_json
from benchmarks import harness
from benchmarks import lm_flops
from benchmarks import lm_scopes
from benchmarks import spec

CELL = "glm-4.7-flash-train-seq4096-bs2-1chip"
PUBLISHED = os.path.join(REPO, "kf_benchmarks_tpu", "models", "lm_configs",
                         "glm-4.7-flash.json")


def test_forward_operations_are_the_number_the_cell_states():
  cell = spec.load_cell(REPO, CELL)
  per_token = lm_flops.forward_flops_per_token(
      cell["config_data"], cell["tokens_per_sample"])
  assert per_token == 956_825_600
  # Per unit of the configuration's sample_unit, which is tokens.
  assert cell["forward_flops_per_sample"] == per_token
  assert cell["config_data"]["sample_unit"] == "tokens"
  # 23.5 TFLOP a training step of 8,192 tokens.
  assert 3 * per_token * 8192 == pytest.approx(23.515e12, rel=1e-4)
  config = cell["config_data"]
  assert lm_flops.attention_projection_params(config) == 21_757_952
  assert lm_flops.attention_core_flops_per_token(config, 4096) == 41_943_040


def test_executed_counts_and_the_roofline_share():
  config = spec.load_config(REPO, "glm-4.7-flash")
  peaks = spec.load_peaks(REPO)["TPU v5 lite"]
  # 45 gmm and 15 tgmm launches a step (the cell's trace): four passes.
  flops, bytes_ = lm_flops.moe_experts_executed(config, 5 * 4096, 45, 15)
  assert flops == 4 * 3 * 2 * 5 * 4096 * 2048 * 1536
  weights = 5 * 8 * 3 * 2048 * 1536
  assert bytes_ == (4 * 3 * 5 * 4096 * (2048 + 1536) * 2 +
                    3 * weights * 2 + weights * 2)
  assert flops / peaks["bf16_flops_per_s"] > bytes_ / peaks[
      "hbm_bytes_per_s"]
  # All of a second's peak in a second is 100%.
  assert lm_flops.roofline_share(197e12, 0, 1.0, peaks) == 100.0
  assert lm_flops.roofline_share(0, 819e9, 2.0, peaks) == 50.0


# The attention core's products by the kernels launched a step over the
# cell's six layers: every forward twice and the fused backward (9 a
# layer); the two backward kernels of PR 30's parent (11); what the
# cell's trace shows since PR 30 (the two unrolled layers' forward once).
@pytest.mark.parametrize("launches, products", [
    ({"splash_mha_fwd_residuals": 12, "splash_mha_dkv_no_residuals": 6},
     6 * 9),
    ({"splash_mha_fwd_residuals": 12, "splash_mha_dkv": 6,
      "splash_mha_dq": 6}, 6 * 11),
    ({"splash_mha_fwd_residuals": 10, "splash_mha_dkv_no_residuals": 6,
      "fusion": 40}, 50),
    ({"splash_mha_fwd_no_residuals": 6}, 6 * 2),          # forward only
])
def test_attention_core_products_follow_the_launches(launches, products):
  config = spec.load_config(REPO, "glm-4.7-flash")
  flops, bytes_ = lm_flops.attention_core_executed(config, 4096, 2, launches)
  assert flops == products * 4096 ** 2 * 256 * 20 * 2
  fwd, dkv, dq = lm_flops.splash_launches(launches)
  assert lm_flops.splash_products(fwd, dkv, dq) == products
  assert bytes_ == (4 * fwd + 8 * dkv + 7 * dq) * 4096 * 20 * 256 * 2 * 2


# The grouped products' passes by the launches, for both families' key
# names: the glm cell's four, the trinity-mini cell's five (its post-norm
# keeps the forward that remat repeats), a backward that recomputes
# nothing (three).
@pytest.mark.parametrize("config_name, layers, gmm, tgmm, passes", [
    ("glm-4.7-flash", 5, 45, 15, 4),
    ("trinity-mini", 4, 48, 12, 5),
    ("trinity-mini", 4, 24, 12, 3),
    ("trinity-mini", 4, 96, 24, 5),      # every layer took two rounds
    # One layer took a second round in one step of twenty: the mean a
    # step is a multiple of nothing, and the passes are what they were.
    ("trinity-mini", 4, 48.6, 12.15, 5),
])
def test_moe_experts_passes_follow_the_launches(config_name, layers, gmm,
                                                tgmm, passes):
  c = spec.load_config(REPO, config_name)
  experts = c.get("n_routed_experts", c.get("num_experts"))
  d, f = c["hidden_size"], c["moe_intermediate_size"]
  flops, bytes_ = lm_flops.moe_experts_executed(c, 1000.0, gmm, tgmm)
  assert flops == passes * 3 * 2 * 1000.0 * d * f
  weights = layers * experts * 3 * d * f
  assert bytes_ == (passes * 3 * 1000.0 * (d + f) * 2 +
                    (passes - 1) * weights * 2 + weights * 2)


# Launches that are not the pattern the count of passes stands on leave
# the share silent and not wrong (4 mixture layers: 12 ``tgmm`` a step at
# one round a layer).
@pytest.mark.parametrize("gmm, tgmm, one_round", [
    (12, 12, True),      # the forward kernel renamed: the backward's alone
    (0, 12, True),       # no ``gmm`` at all
    (46, 12, True),      # one pass runs other rounds than another
    (32, 8, True),       # gate and up fused: two products a pass
    (32, 8, False),      # ... the same with some layer in a second round
    (96, 24, True),      # two rounds a layer where the counter says one
    (48, 0, True),
])
def test_moe_experts_read_nothing_from_another_pattern(gmm, tgmm, one_round):
  c = spec.load_config(REPO, "trinity-mini")
  assert lm_flops.moe_experts_passes(4, gmm, tgmm, one_round) is None
  assert lm_flops.moe_experts_executed(c, 1000.0, gmm, tgmm,
                                       one_round) is None
  # The cell's own launches are the pattern, with one round and without.
  assert lm_flops.moe_experts_passes(4, 48, 12, True) == 5
  assert lm_flops.moe_experts_passes(4, 96, 24, False) == 5


def test_configuration_file_holds_the_published_keys():
  with open(PUBLISHED, encoding="utf-8") as f:
    published = {k: v for k, v in json.load(f).items()
                 if not k.startswith("_")}
  config = spec.load_config(REPO, "glm-4.7-flash")
  for key, value in published.items():
    if key in config["reduced"]:
      assert config["published"][key] == value and config[key] != value
    else:
      assert config[key] == value, key
  assert config["deployment"]["chips_per_layer"] == 8
  assert (config["num_hidden_layers"], config["n_routed_experts"],
          config["vocab_size"]) == (5, 8, 19360)
  params = config["params"]
  assert params["lm_layers_held"] == config["num_hidden_layers"]
  assert (published["n_routed_experts"] // params["lm_layer_shards"] ==
          config["n_routed_experts"])


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(forward)/layers/while/body/checkpoint/"
     "jvp(mla_attention)/jvp(attention_core)/dot_general",
     {"forward", "mla_attention", "attention_core"}),
    ("jit(step)/transpose(jvp(forward))/transpose(jvp(moe_route))/"
     "transpose(jvp(moe_experts))/gmm", {"forward", "moe_route",
                                         "moe_experts"}),
    ("jit(step)/optimizer_apply/add", {"optimizer_apply"}),
])
def test_scope_components(op_name, want):
  assert want <= lm_scopes.components(op_name)
  assert "lm_head" not in lm_scopes.components(op_name)


def test_readers_find_nothing_in_a_program_without_the_scopes():
  # The parent of the PR that added them: no counter, no traced file.
  run = harness.Run(cell={"name": "x", "config_data": {}}, device={},
                    peaks={}, kwargs={}, timed_steps=20, t0=0.0, stats={})
  for name in ("mla_attention_ms", "moe_route_ms", "moe_experts_ms",
               "lm_head_ms", "moe_load_max_over_mean",
               "moe_experts_roofline", "attention_core_roofline",
               "optimizer_ms.lm", "hbm_peak_in_use_gib",
               "hbm_peak_reserved_gib"):
    assert spec.load_metric(REPO, "per_layer", name).read(run) is None


def test_memory_terms_and_the_optimizer_under_its_split_name():
  run = harness.Run(cell={"name": "x", "config_data": {}}, device={},
                    peaks={}, kwargs={}, timed_steps=20, t0=0.0,
                    stats={"device_memory": {
                        "peak_bytes_in_use": 3 * 2 ** 30,
                        "peak_bytes_reserved": 2 ** 29,
                        "bytes_limit": 2 ** 34}})
  read = lambda name: spec.load_metric(REPO, "per_layer", name).read(run)
  assert read("hbm_peak_in_use_gib") == 3.0
  assert read("hbm_peak_reserved_gib") == 0.5
  # The parent metric's reader and fields whole, but for what a cell
  # needs: this model's update is operations of its own on one chip.
  split = spec.load_metric(REPO, "per_layer", "optimizer_ms.lm")
  parent = spec.load_metric(REPO, "per_layer", "optimizer_ms")
  assert split.NEEDS == {} and parent.NEEDS == {"chips": 2}
  assert (split.LAYER, split.UNIT, split.MOVES, split.SOURCE) == (
      parent.LAYER, parent.UNIT, parent.MOVES, parent.SOURCE)
  assert CELL in spec._entry(spec.load_benchmark(REPO)["per_layer"],
                             "hbm_peak_in_use_gib", "metric")["workloads"]


def _controls():
  """``experiments/lm_precision_control.py``, which plants the faults."""
  import importlib.util
  spec_ = importlib.util.spec_from_file_location(
      "_lm_controls", os.path.join(REPO, "experiments",
                                   "lm_precision_control.py"))
  module = importlib.util.module_from_spec(spec_)
  spec_.loader.exec_module(module)
  return module


def _tiny_run(monkeypatch, fault=None):
  import functools
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  if fault:
    _controls().plant(fault, functools.partial(monkeypatch.setattr,
                                               raising=False))
  kwargs = dict(
      model="mla_moe_lm", lm_config="tiny", seq_len=32, batch_size=2,
      lm_layer_shards=4, lm_layer_shard_index=1, device="cpu",
      optimizer="adam", init_learning_rate=1e-4, weight_decay=0.0,
      num_batches=3, num_warmup_batches=1, display_every=1,
      tf_random_seed=11)
  bench = benchmark.BenchmarkCNN(benchmark.setup(
      params_lib.make_params(**kwargs)))
  stats = bench.run()
  config = read_json(os.path.join(
      REPO, "kf_benchmarks_tpu", "models", "lm_configs", "tiny.json"))
  return harness.Run(cell={"name": "tiny", "config_data": config},
                     device={}, peaks={}, kwargs=kwargs, timed_steps=3,
                     t0=0.0, stats=stats, bench=bench)


def test_reference_check_passes_on_the_program(monkeypatch):
  run = _tiny_run(monkeypatch)
  check = spec.load_check(REPO, "glm-4.7-flash_reference_agrees")
  assert check.check(run, None) == []
  compared = run.compared
  assert compared["pairs_dropped"] == {"value": 0.0, "limit": 0}
  assert set(compared) == set(check.LIMITS)
  # float32 on the CPU: everything far inside the chip's limits.
  assert all(v["value"] <= v["limit"] for v in compared.values())
  assert compared["grad_err.expert_down"]["value"] < 1e-3
  assert compared["param_change_err"]["value"] < 1e-2
  assert run.stats["state"] is None      # the state made room


# Each planted fault, and the numbers of the check that have to see it
# (the lower-precision control first; PERF.md section 6 has the chip's
# readings of the same five).
@pytest.mark.parametrize("fault, seen_by", [
    ("router_bf16", ["router_scores_err"]),
    ("state_unchanged", ["param_change_err"]),
    ("half_batch", ["grad_err.lm_head", "grad_err.expert_down",
                    "grad_err.router", "grad_err.kv_b_proj"]),
    ("no_mtp", ["step_loss_err", "grad_err.eh_proj"]),
    # (at the tiny widths the routed part is a small share of a layer's
    # output, so layer_output_err is held to a multiple of its sound
    # reading, below; at the published widths it passes its limit.)
    ("no_scaling", ["grad_err.expert_gate", "grad_err.router"]),
])
def test_reference_check_sees_a_planted_fault(monkeypatch, fault, seen_by):
  run = _tiny_run(monkeypatch, fault)
  check = spec.load_check(REPO, "glm-4.7-flash_reference_agrees")
  failures = check.check(run, None)
  for name in seen_by:
    assert any(f.startswith(name + " ") for f in failures), (name, failures)
  if fault == "state_unchanged":
    assert run.compared["param_change_err"]["value"] == pytest.approx(1.0)
  if fault == "no_scaling":   # float32, sound: 4e-8
    assert run.compared["layer_output_err"]["value"] > 1e-3
