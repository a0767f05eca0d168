"""--partitioner=gspmd|manual: the compiler-partitioned twin of the
sharded training families and the tensor-parallel serving leg
(ISSUE 17). The manual path hand-places every collective
(ops/sharded.py reduce-scatter/all-gather, ops/overlap.py buckets --
the reference's hand-picked reduction algorithms, ref:
batch_allreduce.py:300-317 and variable_mgr.py:175-243); the gspmd
path lowers the SAME step function through plain ``jit`` +
``NamedSharding`` and lets XLA's SPMD partitioner choose the exchange
(train_step.py _gspmd_wrap). The twin referee
(analysis/audit.py rule_partitioner_twin) diffs the two programs'
collective inventories; THIS file pins the math: per-step f32 losses
bit-identical between partitioners on the 8-device CPU mesh.

Layers, reference-style (SURVEY 7.1):
  * pure-unit: the --partitioner cross-flag validation matrix (gspmd
    covers sharded families + TP serving only; gossip/async-PS/
    independent/staged/hand-spec'd reducers stay manual, each with its
    reason) and the LMSpec model_shards laws.
  * fingerprint: ``partitioner`` is program-shaping (twin runs key
    apart in the run store / compile ledger) yet strips out of the
    tuned-table base key; the table validator admits exactly
    {manual, gspmd, null} for the one string-valued knob.
  * numerical equivalence: losses BIT-IDENTICAL manual-vs-gspmd --
    plain sharded, --steps_per_dispatch=8, --num_grad_accum=2
    (tier 1), FSDP and the 4x2 model-axis mesh (slow tier).
  * serving TP oracle: exact-mode TP decode == the TP full forward,
    bit for bit (same op graph, same shardings); TP vs DENSE agrees to
    psum-reassociation rounding (measured ~2e-6 -- the documented
    tolerance, round-15 wd lesson); the engine end-to-end emits
    token-identical greedy output dense-vs-TP.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kf_benchmarks_tpu import benchmark
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.analysis import autotune, baseline
from kf_benchmarks_tpu.serving import decode as decode_lib
from kf_benchmarks_tpu.serving import engine as engine_lib
from kf_benchmarks_tpu.utils import log as log_util

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: [\d.]+ \+/- [\d.]+ \(jitter = [\d.]+\)\t(.*)$")


def _run_and_scrape(**overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=6, num_warmup_batches=0,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=8, optimizer="momentum",
                    shard_optimizer_state=True)
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def _loss_columns(logs):
  return [(m.group(1), m.group(2)) for l in logs
          if (m := STEP_RE.match(l))]


def _assert_twin_bit_identical(**overrides):
  """The tentpole law: the SAME config under --partitioner=manual and
  --partitioner=gspmd logs bit-identical per-step loss columns (f32
  scalars printed full-precision through the reference step-line
  format -- string equality IS bit equality)."""
  logs_m, _ = _run_and_scrape(**overrides)
  logs_g, _ = _run_and_scrape(partitioner="gspmd", **overrides)
  cols_m, cols_g = _loss_columns(logs_m), _loss_columns(logs_g)
  assert cols_m, "manual arm logged no step lines"
  assert cols_m == cols_g, (
      "gspmd twin diverged from the manual program:\n"
      f"manual: {cols_m}\ngspmd:  {cols_g}")


# -- pure-unit: the cross-flag validation matrix ------------------------------

def _validate(**kw):
  validation.validate_cross_flags(
      params_lib.make_params(model="trivial", partitioner="gspmd", **kw))


def test_gspmd_requires_a_sharded_family():
  with pytest.raises(validation.ParamError, match="sharded training"):
    _validate()


def test_gspmd_accepts_the_sharded_families():
  for extra in (dict(shard_optimizer_state=True),
                dict(shard_optimizer_state=True, shard_params=True),
                dict(serving_model_shards=2, num_devices=8)):
    _validate(**extra)


@pytest.mark.parametrize("extra,reason", [
    # Bare combos on purpose: most also fall out of the sharded
    # matrix, but a bare --partitioner=gspmd + mode deserves the
    # SPECIFIC gspmd reason (validation.py), which is what matches.
    (dict(staged_vars=True), "staged_vars"),
    (dict(variable_update="independent"), "independent"),
    (dict(variable_update="kungfu", kungfu_option="sma"), "gossip"),
    (dict(variable_update="parameter_server", cross_replica_sync=False),
     "async"),
    (dict(hierarchical_copy=True), "hierarchical"),
], ids=["staged", "independent", "gossip", "async_ps", "hierarchical"])
def test_gspmd_rejects_semantic_hand_placements(extra, reason):
  """Modes whose collectives ARE the semantics (not partitioning
  choices) stay manual-only, each with its specific reason."""
  with pytest.raises(validation.ParamError, match=reason):
    _validate(**extra)


def test_model_shards_divisibility_rejected():
  with pytest.raises(validation.ParamError, match="head count"):
    validation.validate_cross_flags(
        params_lib.make_params(model="trivial", serving_model_shards=3))


# -- fingerprint: program-shaping knob, tuned-table string value --------------

def test_partitioner_is_program_shaping():
  """Twin runs must never mix in the regression gate or the compile
  ledger: the flag keys the config fingerprint (same pin style as
  tests/test_autotune.py's per-knob checks)."""
  base = dict(model="trivial", batch_size=4, optimizer="momentum",
              shard_optimizer_state=True)
  k_m = baseline.config_fingerprint_key(
      params_lib.make_params(**base)._asdict())
  k_g = baseline.config_fingerprint_key(
      params_lib.make_params(partitioner="gspmd", **base)._asdict())
  assert k_m != k_g


def test_partitioner_strips_out_of_the_tuned_base_key():
  """The autotuner's table key must be shared by a tuned and a default
  run of one base config -- partitioner is in TUNED_KNOBS, so the twin
  pair collapses onto one table entry."""
  assert "partitioner" in baseline.TUNED_KNOBS
  base = dict(model="trivial", batch_size=4, optimizer="momentum",
              shard_optimizer_state=True)
  b_m = baseline.base_fingerprint_key(
      params_lib.make_params(**base)._asdict(), "train_step")
  b_g = baseline.base_fingerprint_key(
      params_lib.make_params(partitioner="gspmd", **base)._asdict(),
      "train_step")
  assert b_m == b_g


def test_autotuner_searches_partitioner_on_sharded_bases():
  sharded = params_lib.make_params(model="trivial", batch_size=4,
                                   optimizer="momentum",
                                   shard_optimizer_state=True)
  plain = params_lib.make_params(model="trivial", batch_size=4,
                                 optimizer="momentum")
  assert autotune.default_axes(sharded).get("partitioner") == \
      (None, "gspmd")
  assert "partitioner" not in autotune.default_axes(plain)


def test_table_validator_admits_the_string_knob():
  def table_with(tuned):
    return {"schema_version": autotune.TABLE_SCHEMA_VERSION,
            "entries": {"k" * 16: {"tuned": tuned}}}

  ok, _ = autotune.validate_table(table_with({"partitioner": "gspmd"}),
                                  rederive=False)
  assert not ok
  bad, _ = autotune.validate_table(table_with({"partitioner": "zorg"}),
                                   rederive=False)
  assert any("partitioner" in p for p in bad)


# -- numerical equivalence: bit-identical losses ------------------------------

@pytest.mark.slow
def test_twin_bit_identical_plain_sharded():
  _assert_twin_bit_identical()


@pytest.mark.slow
def test_twin_bit_identical_k_dispatch():
  _assert_twin_bit_identical(steps_per_dispatch=8, num_batches=8)


@pytest.mark.slow
def test_twin_bit_identical_grad_accum():
  _assert_twin_bit_identical(num_grad_accum=2)


@pytest.mark.slow
def test_twin_bit_identical_fsdp():
  _assert_twin_bit_identical(shard_params=True)


@pytest.mark.slow
def test_twin_bit_identical_model_axis_4x2():
  _assert_twin_bit_identical(mesh_shape="4x2")


@pytest.mark.slow
def test_twin_bit_identical_fsdp_accum():
  _assert_twin_bit_identical(shard_params=True, num_grad_accum=2)


# -- serving TP: spec laws + the sharded oracle -------------------------------

TINY = dict(vocab=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_len=16, attn_block=8)


def test_model_shards_spec_laws():
  with pytest.raises(ValueError, match=">= 2"):
    decode_lib.LMSpec(**{**TINY, "model_shards": 1})
  with pytest.raises(ValueError, match="divide"):
    decode_lib.LMSpec(**{**TINY, "model_shards": 3})
  with pytest.raises(ValueError, match="quantize"):
    decode_lib.LMSpec(**{**TINY, "model_shards": 2, "quantize": "int8"})


def test_tp_config_carries_model_shards():
  spec = decode_lib.LMSpec(**{**TINY, "model_shards": 2})
  assert spec.config()["model_shards"] == 2
  assert decode_lib.LMSpec(**TINY).config()["model_shards"] is None


@pytest.fixture(scope="module")
def tp_setup():
  """One tiny LM + its 2-way model mesh, shared by the TP oracle
  tests. Weights come from the UNSHARDED init so the dense twin is the
  same f32 tree bit for bit."""
  spec = decode_lib.LMSpec(**{**TINY, "decode_exact": True,
                              "model_shards": 2})
  dense = decode_lib.LMSpec(**{**TINY, "decode_exact": True})
  variables = decode_lib.init_variables(dense, seed=0)
  tokens = jax.random.randint(jax.random.PRNGKey(7),
                              (2, spec.max_len), 0, spec.vocab,
                              jnp.int32)
  return spec, dense, variables, tokens


def _tp_full_logits(spec, variables, tokens):
  mesh = decode_lib.serving_mesh(spec)
  var_sh = decode_lib._variables_shardings(spec, mesh)
  rep = NamedSharding(mesh, P())
  module = decode_lib.forward_module(spec, fused_head=False)
  fn = jax.jit(lambda v, t: module.apply(v, t)[0],
               in_shardings=(var_sh, rep), out_shardings=rep)
  return fn(jax.device_put(variables, var_sh),
            jax.device_put(tokens, rep))


def _tp_decode_all(spec, variables, tokens):
  mesh = decode_lib.serving_mesh(spec)
  var_sh = decode_lib._variables_shardings(spec, mesh)
  rep = NamedSharding(mesh, P())
  kvsh = decode_lib._kv_sharding(spec, mesh, 3, 5)
  module = decode_lib.decode_module(spec)
  step = jax.jit(module.apply,
                 in_shardings=(var_sh, rep, kvsh, kvsh, rep),
                 out_shardings=(rep, (kvsh, kvsh)))
  svars = jax.device_put(variables, var_sh)
  b, t = tokens.shape
  cache = decode_lib.init_cache(spec, b)
  ck = jax.device_put(cache.k, kvsh)
  cv = jax.device_put(cache.v, kvsh)
  rows = []
  for p in range(t):
    pos = jax.device_put(jnp.full((b,), p, jnp.int32), rep)
    logits, (ck, cv) = step(svars,
                            jax.device_put(tokens[:, p], rep),
                            ck, cv, pos)
    rows.append(logits[:, 0])
  return jnp.stack(rows, axis=1)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_tp_decode_bit_identical_to_tp_full_forward(tp_setup):
  """The sharded oracle: under the SAME model sharding, exact-mode
  incremental decode == the full forward bit for bit at every prefix
  (gemm shapes: B >= 2, contractions <= 256 -- the same boundary the
  dense oracle records)."""
  spec, _dense, variables, tokens = tp_setup
  np.testing.assert_array_equal(
      np.asarray(_tp_decode_all(spec, variables, tokens)),
      np.asarray(_tp_full_logits(spec, variables, tokens)))


def test_tp_matches_dense_to_psum_rounding(tp_setup):
  """TP vs DENSE is NOT bitwise: the row-parallel matmuls finish with
  a 2-way psum whose reassociation reorders the K-sum (measured
  max |delta| ~2e-6 on this spec). The documented tolerance, NOT a
  bug -- same class as the round-15 wd reassociation lesson."""
  spec, dense, variables, tokens = tp_setup
  module = decode_lib.forward_module(dense, fused_head=False)
  full_dense = jax.jit(lambda v, t: module.apply(v, t)[0])(variables,
                                                           tokens)
  np.testing.assert_allclose(
      np.asarray(_tp_full_logits(spec, variables, tokens)),
      np.asarray(full_dense), rtol=1e-4, atol=1e-5)


def _engine_tokens(model_shards):
  spec = decode_lib.LMSpec(**{**TINY, "decode_exact": True,
                              **({"model_shards": model_shards}
                                 if model_shards else {})})
  cfg = engine_lib.EngineConfig(spec=spec, bucket_ladder=(1, 2, 4),
                                batching="continuous",
                                max_new_tokens=4)
  eng = engine_lib.ServingEngine(cfg, seed=0)
  rng = np.random.default_rng(0)
  for i in range(5):
    prompt = rng.integers(1, TINY["vocab"],
                          size=rng.integers(2, 10)).astype(np.int32)
    eng.submit(engine_lib.Request(rid=i, prompt=prompt, tenant="t"))
  return {r.rid: list(r.tokens or []) for r in eng.drain()}


@pytest.mark.slow
def test_tp_engine_token_identical_to_dense():
  """End to end through the continuous-batching engine: greedy argmax
  output is token-identical dense-vs-TP (argmax absorbs the psum
  rounding by construction on this workload)."""
  assert _engine_tokens(0) == _engine_tokens(2)
