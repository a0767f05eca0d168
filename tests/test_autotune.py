"""Contract-driven autotuner (kf_benchmarks_tpu/analysis/autotune.py).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: cost-model monotonicity (buffer bytes / collective
    count / dispatch amortization), static-prune bounds, tuned-knob
    fingerprint behaviour (each knob changes the run-store key; the
    table path and store plumbing do not), table schema validation.
  * seeded search: an injected tracer plants an over-HBM candidate and
    a counting measure_fn proves pruned configs are NEVER executed;
    the same injected pair run twice produces a byte-identical table
    (same seed + same contracts => same JSON).
  * e2e on the 8-device CPU mesh: the real prune -> rank -> probe
    pipeline on two model families, with the measured tuned throughput
    >= the same run's own measured default (the derived no-regression
    bar); the warm pass precompiles a config's shapes and a follow-up
    run's compile ledger reads cache_hit on what it re-compiles.
"""

import json
import os

import jax.numpy as jnp
import pytest

from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.analysis import autotune, baseline
from kf_benchmarks_tpu.analysis.contracts import (Collective,
                                                  ProgramContract)

BASE = dict(model="trivial", batch_size=4, device="cpu", num_devices=8)


def _contract(n_coll=2, elems=1024, temp=1000, flops=1e9, aux=None):
  colls = [Collective(kind="all-reduce", dtype="f32", elems=elems,
                      scalar=False, in_loop=False, replica_groups="")
           for _ in range(n_coll)]
  merged_aux = {"flops": flops}
  merged_aux.update(aux or {})
  return ProgramContract(
      config={}, program="train_step", collectives=colls,
      host_transfers=[], custom_call_targets=[],
      optimizer_apply_present=True, optimizer_apply_in_loop=False,
      donated_buffers=1, largest_tensor_bytes=temp,
      largest_tensor_type="f32[x]", temp_bytes=temp, aux=merged_aux)


# -- fingerprints: tuned knobs key runs apart, plumbing does not --------------

# One legal non-default value per tuned knob (reduce_bucket_mb needs its
# FSDP consumer; attn_block needs the LM family).
_KNOB_CASES = {
    "steps_per_dispatch": (dict(BASE), 4),
    "num_grad_accum": (dict(BASE), 2),
    "reduce_bucket_mb": (dict(BASE, optimizer="momentum",
                              shard_optimizer_state=True,
                              shard_params=True), 8),
    "input_prefetch_depth": (dict(BASE), 3),
    "attn_block": (dict(BASE, model="transformer_lm", batch_size=8),
                   256),
    # The string-valued knob: gspmd only applies to the sharded
    # families (cross-flag matrix), so the case rides a sharded base.
    "partitioner": (dict(BASE, shard_optimizer_state=True), "gspmd"),
}


def test_knob_registry_covers_every_case():
  assert set(_KNOB_CASES) == set(baseline.TUNED_KNOBS)


@pytest.mark.parametrize("knob", sorted(baseline.TUNED_KNOBS))
def test_each_tuned_knob_changes_the_run_fingerprint(knob):
  kw, value = _KNOB_CASES[knob]
  default_key = baseline.config_fingerprint_key(
      params_lib.make_params(**kw)._asdict())
  tuned_key = baseline.config_fingerprint_key(
      params_lib.make_params(**kw, **{knob: value})._asdict())
  assert tuned_key != default_key, (
      f"--{knob} is a tuned knob but does not change the run-store/"
      "ledger fingerprint: tuned and default histories would mix")
  # ... while the TABLE key strips exactly the tuned knobs, so the
  # tuned run looks its own entry up under the default's key.
  assert baseline.base_fingerprint_key(
      params_lib.make_params(**kw, **{knob: value})._asdict()) == \
      baseline.base_fingerprint_key(
          params_lib.make_params(**kw)._asdict())


def test_cli_and_library_param_paths_share_a_fingerprint():
  """The CLI parser materializes float flags as 0.0 where make_params
  keeps a registry-literal 0 (Python-equal, canonical-JSON-different);
  the fingerprint canonicalizes integral floats so one config keys the
  same from both paths -- the tuned-table lookup (and the compile
  ledger) must not split on parser provenance."""
  assert baseline.config_fingerprint_key({"a": 0.0}) == \
      baseline.config_fingerprint_key({"a": 0})
  assert baseline.config_fingerprint_key({"a": 2.0}) == \
      baseline.config_fingerprint_key({"a": 2})
  assert baseline.config_fingerprint_key({"a": 2.5}) != \
      baseline.config_fingerprint_key({"a": 2})
  # Bools stay typed (True must not collapse onto 1).
  assert baseline.config_fingerprint_key({"a": True}) != \
      baseline.config_fingerprint_key({"a": 1})
  # The concrete incident: the CLI float rendering of the LR-decay
  # defaults vs the make_params literals.
  mk = params_lib.make_params(**BASE)._asdict()
  cli_like = dict(mk, learning_rate_decay_factor=0.0,
                  minimum_learning_rate=0.0, num_epochs_per_decay=0.0,
                  num_learning_rate_warmup_epochs=0.0)
  assert baseline.base_fingerprint_key(cli_like) == \
      baseline.base_fingerprint_key(mk)


def test_plumbing_paths_do_not_change_the_fingerprint(tmp_path):
  plain = baseline.config_fingerprint_key(
      params_lib.make_params(**BASE)._asdict())
  plumbed = baseline.config_fingerprint_key(
      params_lib.make_params(
          **BASE, autotuned_config=str(tmp_path / "t.json"),
          run_store_dir=str(tmp_path))._asdict())
  assert plumbed == plain


# -- cost model: monotone in the contract inventory ---------------------------

def test_cost_monotone_in_collective_count():
  lo = autotune.candidate_cost(_contract(n_coll=2), {})
  hi = autotune.candidate_cost(_contract(n_coll=6), {})
  assert hi > lo


def test_cost_monotone_in_collective_bytes():
  lo = autotune.candidate_cost(_contract(elems=1024), {})
  hi = autotune.candidate_cost(_contract(elems=1 << 20), {})
  assert hi > lo


def test_cost_monotone_in_buffer_bytes():
  lo = autotune.candidate_cost(_contract(temp=1000), {})
  hi = autotune.candidate_cost(_contract(temp=10**9), {})
  assert hi > lo


def test_cost_decreases_with_dispatch_amortization():
  c = _contract()
  assert autotune.candidate_cost(c, {"steps_per_dispatch": 8}) < \
      autotune.candidate_cost(c, {"steps_per_dispatch": 1})


def test_prune_reasons_bounds():
  ok = _contract(temp=1000)
  assert not autotune.prune_reasons(ok, hbm_budget_bytes=10**9)
  over = _contract(temp=2 * 10**9)
  reasons = autotune.prune_reasons(over, hbm_budget_bytes=10**9)
  assert reasons and "HBM budget" in reasons[0]
  chatty = _contract(n_coll=9)
  assert autotune.prune_reasons(chatty, max_collectives=8)
  bucketed = _contract(aux={"fsdp_step_gathers": 99})
  assert autotune.prune_reasons(bucketed, max_step_buckets=64)


# -- seeded search: pruned candidates never execute ---------------------------

def _seeded_tracer(overrides, program):
  """The injected oracle: accum-4 candidates trace to an over-HBM
  contract, everything else is small."""
  assert program == "train_step"
  # The static projection never carries the non-program knobs.
  assert "steps_per_dispatch" not in overrides
  assert "input_prefetch_depth" not in overrides
  accum = int(overrides.get("num_grad_accum") or 1)
  return _contract(temp=10**13 if accum == 4 else 1000)


def _deterministic_measure(merged):
  return 100.0 + 3.0 * int(merged.get("steps_per_dispatch") or 1) \
      - 1.0 * int(merged.get("num_grad_accum") or 1)


def test_statically_pruned_candidates_are_never_executed():
  executed = []

  def counting_measure(merged):
    executed.append(dict(merged))
    return _deterministic_measure(merged)

  key, entry = autotune.autotune_config(
      dict(BASE), tracer=_seeded_tracer, measure_fn=counting_measure,
      hbm_budget_bytes=10**9, log=lambda s: None)
  # The default grid: spd x accum = 12 candidates; the 4 accum-4 ones
  # are the seeded over-HBM class and must all be pruned...
  assert entry["candidates"] == 12
  assert entry["pruned"] == 4
  assert entry["invalid"] == 0
  # ... and NONE of them ever reached the measure stage (the
  # 0-executions-of-pruned-configs contract).
  assert executed, "nothing was probed at all"
  assert all(int(m.get("num_grad_accum") or 1) != 4 for m in executed)
  # The winner's recorded throughput is >= the same run's own default
  # measurement, by construction.
  assert entry["tuned_images_per_sec"] >= entry["default_images_per_sec"]
  assert key == baseline.base_fingerprint_key(
      params_lib.make_params(**BASE)._asdict())


def test_pruned_default_runs_no_probes():
  def always_over(overrides, program):
    return _contract(temp=10**13)

  def must_not_run(merged):
    raise AssertionError("a pruned config was executed")

  _, entry = autotune.autotune_config(
      dict(BASE), tracer=always_over, measure_fn=must_not_run,
      hbm_budget_bytes=10**9, log=lambda s: None)
  assert entry["probed"] == 0 and entry["pruned"] == entry["candidates"]
  assert entry["tuned"] == entry["default"]


def test_search_is_deterministic_byte_identical(tmp_path):
  paths = []
  for i in (0, 1):
    table = autotune.autotune_configs(
        [dict(BASE)], seed=7, max_candidates=6,
        tracer=_seeded_tracer, measure_fn=_deterministic_measure,
        hbm_budget_bytes=10**9, log=lambda s: None,
        out=str(tmp_path / f"t{i}.json"))
    paths.append(tmp_path / f"t{i}.json")
    # max_candidates subsamples the grid (seeded) but keeps the
    # incumbent default.
    assert table["entries"]
    entry = next(iter(table["entries"].values()))
    assert entry["candidates"] == 6
  assert paths[0].read_bytes() == paths[1].read_bytes()


# -- table schema validation (the --audit tuned-table leg) --------------------

def _one_entry_table():
  table = autotune.autotune_configs(
      [dict(BASE)], tracer=_seeded_tracer,
      measure_fn=_deterministic_measure, hbm_budget_bytes=10**9,
      log=lambda s: None)
  return table


def test_validate_table_clean_and_rederives():
  problems, warnings = autotune.validate_table(_one_entry_table())
  assert problems == []
  assert warnings == []


def test_validate_table_catches_unknown_knob():
  table = _one_entry_table()
  entry = next(iter(table["entries"].values()))
  entry["tuned"]["not_a_knob"] = 3
  problems, _ = autotune.validate_table(table)
  assert any("knob registry" in p for p in problems)


def test_validate_table_catches_measured_regression():
  table = _one_entry_table()
  entry = next(iter(table["entries"].values()))
  entry["tuned_images_per_sec"] = entry["default_images_per_sec"] - 1
  problems, _ = autotune.validate_table(table)
  assert any("measured regression" in p for p in problems)


def test_validate_table_flags_stale_jax_as_warning():
  table = _one_entry_table()
  entry = next(iter(table["entries"].values()))
  entry["jax_version"] = "0.0.1"
  problems, warnings = autotune.validate_table(table)
  assert problems == []
  assert any("stale" in w for w in warnings)


def test_validate_table_catches_fingerprint_drift():
  table = _one_entry_table()
  (key, entry), = table["entries"].items()
  table["entries"] = {"0" * 16: entry}
  problems, _ = autotune.validate_table(table)
  assert any("re-derive" in p for p in problems)


# -- startup application ------------------------------------------------------

def _write_seeded_table(tmp_path):
  table = _one_entry_table()
  path = str(tmp_path / "tuned_configs.json")
  autotune.write_table(table, path)
  (key, entry), = table["entries"].items()
  return path, key, entry


def test_apply_tuned_config_replaces_knobs_with_provenance(tmp_path):
  path, key, entry = _write_seeded_table(tmp_path)
  lines = []
  p = params_lib.make_params(**BASE, autotuned_config=path)
  applied, prov = autotune.apply_tuned_config(p, log_fn=lines.append)
  assert applied.steps_per_dispatch == \
      entry["tuned"]["steps_per_dispatch"]
  assert len(lines) == 1 and key[:16] in lines[0] and path in lines[0]
  # The provenance payload the stats/bench JSON carries -- returned by
  # the application itself (threaded through, not re-read) and
  # re-derivable by the fallback lookup.
  assert prov == {"path": path, "entry": key}
  assert autotune.tuned_provenance(p) == prov


def test_apply_tuned_config_no_entry_keeps_flags(tmp_path):
  path, _, _ = _write_seeded_table(tmp_path)
  lines = []
  p = params_lib.make_params(**dict(BASE, batch_size=16),
                             autotuned_config=path)
  applied, prov = autotune.apply_tuned_config(p, log_fn=lines.append)
  assert applied.steps_per_dispatch == 1
  assert len(lines) == 1 and "no entry" in lines[0]
  assert prov == {"path": path, "entry": None}
  assert autotune.tuned_provenance(p) == prov


def test_apply_tuned_config_missing_table_raises(tmp_path):
  p = params_lib.make_params(
      **BASE, autotuned_config=str(tmp_path / "absent.json"))
  with pytest.raises(validation.ParamError):
    autotune.apply_tuned_config(p, log_fn=lambda s: None)


def test_autotuned_config_rejected_for_eval():
  with pytest.raises(validation.ParamError):
    validation.validate_cross_flags(params_lib.make_params(
        **BASE, eval=True, autotuned_config="t.json"))


def test_flatten_stats_carries_tuned_provenance():
  from kf_benchmarks_tpu import metrics as metrics_lib
  flat = metrics_lib.flatten_stats(
      {"tuned_config": {"path": "p.json", "entry": "abcd"}})
  assert flat == {"tuned_config_path": "p.json",
                  "tuned_config_entry": "abcd"}


# -- the --attn_block knob ----------------------------------------------------

def test_attn_block_requires_the_lm_family():
  with pytest.raises(validation.ParamError):
    validation.validate_cross_flags(
        params_lib.make_params(**BASE, attn_block=256))


def test_attn_block_must_divide_seq_len():
  with pytest.raises(validation.ParamError):
    validation.validate_cross_flags(params_lib.make_params(
        model="transformer_lm", batch_size=8, attn_block=384))


def test_attn_block_drives_both_tilings():
  from kf_benchmarks_tpu.models import transformer_lm
  p = params_lib.make_params(model="transformer_lm", batch_size=8,
                             attn_block=256)
  model = transformer_lm.create_transformer_lm_model(p)
  module = model.make_module(nclass=0, phase_train=True,
                             dtype=jnp.float32,
                             param_dtype=jnp.float32)
  assert module.attn_block == 256 and module.attn_q_block == 256


# -- e2e: the real pipeline on the 8-device CPU mesh --------------------------

@pytest.mark.slow
@pytest.mark.parametrize("model", ["trivial", "lenet"])
def test_autotune_e2e_tuned_meets_the_measured_default_bar(model):
  """Acceptance: real trace + real probes for two model families; the
  emitted entry's measured tuned throughput >= the same run's own
  measured default (the bar is derived from this run's measurements,
  never a constant). Slow-tiered: ~25 s/family of real compiles+probes
  -- the tier-1 wall budget is already at its edge; the fast tier
  keeps the dry-run CLI e2e and the seeded/injected pipeline tests."""
  key, entry = autotune.autotune_config(
      {"model": model, "batch_size": 2},
      axes={"steps_per_dispatch": (1, 2)}, top_k=1,
      probe_dispatches=1, log=lambda s: None)
  assert entry["probed"] >= 2
  assert entry["pruned"] == 0
  assert entry["tuned_images_per_sec"] >= entry["default_images_per_sec"]
  problems, warnings = autotune.validate_table(
      {"schema_version": 1, "entries": {key: entry}})
  assert problems == [] and warnings == []


def test_dry_run_cli_writes_a_valid_table(tmp_path):
  """`analysis autotune --dry-run`: static stages only (candidates
  compile, nothing executes), CPU-only, and the written table
  validates -- the CI rehearsal the audit budget admits."""
  from kf_benchmarks_tpu.analysis import __main__ as analysis_main
  out = str(tmp_path / "dry.json")
  rc = analysis_main.main(["autotune", "--models", "trivial",
                           "--batch_size", "4", "--dry-run",
                           "--out", out])
  assert rc == 0
  table = autotune.load_table(out)
  entry = next(iter(table["entries"].values()))
  assert entry["dry_run"] is True and entry["probed"] == 0
  assert entry["tuned_images_per_sec"] is None


def test_num_batches_resolution_never_mutates_params():
  """The premise the warm-pass key convention rests on: a job that
  leaves --num_batches unset keys with the field ABSENT (the runtime
  resolves the count into an attribute, never back into params), so
  warm() must not inject a value either."""
  from kf_benchmarks_tpu import benchmark
  bench = benchmark.BenchmarkCNN(params_lib.make_params(**BASE))
  assert bench.params.num_batches is None
  assert bench.num_batches == 100  # the reference default, attribute-only


@pytest.mark.slow
def test_warm_precompiles_and_follow_up_run_reads_cache_hit(tmp_path):
  """Acceptance: the warm pass compiles every predicted shape into the
  persistent cache under the runtime's own fingerprint keys; a
  follow-up run of the same config reads cache_hit on every shape it
  re-compiles -- from the compilation cache's own hit events
  (tracing.RunTrace.on_event), so the warm pass must have lowered the
  very program the runtime dispatches (contracts.lower_step_program).
  Slow-tiered with the measured e2e above (full compile passes + a real
  training run; the wall budget is the constraint, not the 60 s
  per-test rule)."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import tracing as tracing_lib
  td = str(tmp_path)
  cfg = dict(model="trivial", batch_size=4, device="cpu",
             num_devices=8, steps_per_dispatch=2, num_batches=6,
             num_warmup_batches=2)
  # The cache goes where the one rule says (benchmark.
  # configure_compile_cache): off by default on the CPU, so the warm
  # pass and the follow-up run both name it -- never <train_dir>/
  # xla_cache.
  cache = str(tmp_path / "cache")
  with pytest.raises(ValueError, match="no persistent XLA cache"):
    autotune.warm(td, configs=[cfg], log=lambda s: None)
  summary = autotune.warm(td, configs=[cfg], cache_dir=cache,
                          log=lambda s: None)
  assert summary["cache_dir"] == cache
  # steps_per_dispatch=2 predicts both the chunk and the single-step
  # program; both land in the ledger and the cache dir is populated.
  assert {prog for _, prog in summary["warmed"]} == \
      {"train_step", "train_chunk"}
  assert os.listdir(summary["cache_dir"])
  ledger = tracing_lib.read_ledger(td)
  assert tracing_lib.ledger_programs(ledger) == \
      {"train_step", "train_chunk"}
  # Warming twice is idempotent: everything reads already-warm.
  again = autotune.warm(td, configs=[cfg], cache_dir=cache,
                        log=lambda s: None)
  assert not again["warmed"] and len(again["skipped"]) == 2

  p = params_lib.make_params(**cfg, train_dir=td,
                             compilation_cache_dir=cache)
  try:
    benchmark.BenchmarkCNN(p).run()
  finally:
    benchmark.configure_compile_cache("cpu")  # process-global: off again
  after = tracing_lib.read_ledger(td)
  recompiled = {key: row for key, row in after["entries"].items()
                if "cache_hit" in row}
  assert recompiled, "the follow-up run ledgered no compile episodes"
  assert all(row["cache_hit"] for row in recompiled.values()), after
  # ... and the run's episodes landed on keys the warm pass seeded.
  warmed_keys = {key for key, _ in summary["warmed"]}
  assert set(recompiled) <= warmed_keys
