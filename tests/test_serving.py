"""Serving path (kf_benchmarks_tpu/serving/): KV-cache decode oracle,
continuous-batching engine, admission control, bounded executables.

Layers, reference-style (SURVEY 7.1):
  * numerical-equivalence: the KV-cache ORACLE -- exact-mode
    incremental decode produces f32 per-token logits BIT-IDENTICAL to
    the full-sequence forward at every prefix length, for the blockwise
    (tiled) path and the flash path's CPU reference, scan and loop
    layer modes; the fast 1-row production schedule agrees to float
    rounding. (Bit-identity holds where XLA:CPU's GEMM is k-block-free
    -- contractions <= 256 deep, measured; test dims sit inside that.)
  * prefill equivalence: the packed prefill program installs the same
    ring-buffer contents and first token the incremental path builds.
  * engine e2e: requests through the continuous-batching engine equal
    the engine-free greedy reference; mixed-length replay compiles
    <= len(bucket ladder) decode programs (the bounded-executable pin).
  * admission: queue-depth rejection, TTFT-deadline expiry, tenant
    token budgets -- first-class results + serving/* metrics.
  * auditor: the serving_decode golden matches, and each seeded
    violation fires exactly the serving rule (mutation self-test).
"""

import copy
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import metrics as metrics_lib
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.analysis import audit, baseline, contracts
from kf_benchmarks_tpu.data import packing
from kf_benchmarks_tpu.serving import decode as decode_lib
from kf_benchmarks_tpu.serving import engine as engine_lib

TINY = dict(vocab=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_len=16, attn_block=8)


def tiny_spec(**kw):
  return decode_lib.LMSpec(**{**TINY, **kw})


@pytest.fixture(scope="module")
def tiny_setup():
  """One initialized tiny LM shared by the oracle tests (attention
  impl/layer-mode variants reuse the same variables -- the param tree
  is impl-independent by construction)."""
  spec = tiny_spec(decode_exact=True)
  variables = decode_lib.init_variables(spec, seed=0)
  rng = jax.random.PRNGKey(7)
  tokens = jax.random.randint(rng, (2, spec.max_len), 0, spec.vocab,
                              jnp.int32)
  return spec, variables, tokens


def _full_logits(spec, variables, tokens):
  module = decode_lib.forward_module(spec, fused_head=False)
  logits, _ = jax.jit(module.apply)(variables, tokens)
  return logits


def _decode_all(spec, variables, tokens):
  """Teacher-forced incremental decode over every position; returns the
  (B, T, V) stack of per-token logits."""
  module = decode_lib.decode_module(spec)
  step = jax.jit(module.apply)
  b, t = tokens.shape
  cache = decode_lib.init_cache(spec, b)
  ck, cv = cache.k, cache.v
  rows = []
  for p in range(t):
    pos = jnp.full((b,), p, jnp.int32)
    logits, (ck, cv) = step(variables, tokens[:, p], ck, cv, pos)
    rows.append(logits[:, 0])
  return jnp.stack(rows, axis=1)


@pytest.mark.parametrize("impl", ["tiled", "flash"])
def test_decode_bit_identical_to_full_forward(tiny_setup, impl):
  """The KV-cache correctness oracle: exact-mode incremental decode ==
  the full-sequence forward, bit for bit, at EVERY prefix length."""
  spec, variables, tokens = tiny_setup
  spec = decode_lib.LMSpec(**{**TINY, "attn_impl": impl,
                              "decode_exact": True})
  full = _full_logits(spec, variables, tokens)
  inc = _decode_all(spec, variables, tokens)
  assert full.dtype == jnp.float32
  np.testing.assert_array_equal(np.asarray(inc), np.asarray(full))


def test_decode_bit_identical_loop_layers(tiny_setup):
  """Same oracle through the unrolled per-layer path (block_i params),
  so the two layer modes cannot drift."""
  _spec, _, _ = tiny_setup
  spec = tiny_spec(scan_layers=False, decode_exact=True)
  variables = decode_lib.init_variables(spec, seed=1)
  # Batch >= 2: XLA:CPU's M=1 gemv accumulates differently from gemm
  # rows, so the bitwise contract binds at gemm shapes (B >= 2) --
  # same boundary the module docstring records.
  tokens = jax.random.randint(jax.random.PRNGKey(3),
                              (2, spec.max_len), 0, spec.vocab, jnp.int32)
  np.testing.assert_array_equal(
      np.asarray(_decode_all(spec, variables, tokens)),
      np.asarray(_full_logits(spec, variables, tokens)))


def test_decode_fast_mode_matches_to_rounding(tiny_setup):
  """The production 1-row schedule: same results to float rounding
  (XLA schedules the (1, T) contraction differently -- measured ~2e-6;
  the exact mode exists precisely because this is NOT bitwise)."""
  spec, variables, tokens = tiny_setup
  fast = decode_lib.LMSpec(**{**TINY, "decode_exact": False})
  full = _full_logits(spec, variables, tokens)
  inc = _decode_all(fast, variables, tokens)
  np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                             rtol=1e-4, atol=1e-5)


def test_stale_ring_contents_are_invisible(tiny_setup):
  """Garbage in cache slots past ``pos`` (stale ring contents / a
  packed neighbor's K/V) must not perturb the decode output AT ALL --
  the masked-contribution-is-exactly-zero contract."""
  spec, variables, tokens = tiny_setup
  module = decode_lib.decode_module(spec)
  step = jax.jit(module.apply)
  b = tokens.shape[0]
  cache = decode_lib.init_cache(spec, b)
  ck, cv = cache.k, cache.v
  for p in range(4):
    pos = jnp.full((b,), p, jnp.int32)
    clean, (ck2, cv2) = step(variables, tokens[:, p], ck, cv, pos)
    dirty, _ = step(variables, tokens[:, p],
                    ck.at[:, :, p + 1:].set(1e9),
                    cv.at[:, :, p + 1:].set(-1e9), pos)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    ck, cv = ck2, cv2


# -- packed prefill -----------------------------------------------------------

def test_pack_prompts_layout_and_placements():
  prompts = [np.arange(1, 6, dtype=np.int32),       # 5 tokens
             np.arange(10, 19, dtype=np.int32),     # 9 tokens
             np.arange(30, 33, dtype=np.int32)]     # 3 tokens
  images, placements = packing.pack_prompts(prompts, seq_len=16,
                                            batch_size=2)
  assert images.shape == (2, 3, 16)
  assert placements == [(0, 0), (0, 5), (1, 0)]
  row0 = images[0]
  # tokens / 1-based segment ids / per-document positions, padding 0.
  np.testing.assert_array_equal(row0[0, :5], prompts[0])
  np.testing.assert_array_equal(row0[0, 5:14], prompts[1])
  np.testing.assert_array_equal(row0[1, :14], [1] * 5 + [2] * 9)
  np.testing.assert_array_equal(row0[2, 5:14], np.arange(9))
  assert row0[1, 14:].sum() == 0
  # overflow: a third long prompt with full rows stays unplaced
  _, pl = packing.pack_prompts([np.ones(16, np.int32)] * 3, 16, 2)
  assert pl == [(0, 0), (1, 0), None]


def test_packed_prefill_matches_incremental_decode(tiny_setup):
  """The prefill program's installed caches, positions, and first
  sampled tokens equal what stepping the decode path over each prompt
  builds -- so continuous batching can mix prefilled and decoded slots
  freely.

  Equality structure: positions and greedy tokens (the engine's actual
  consumer) are identical; the caches agree to float rounding. A prompt
  at a nonzero row offset sees the online softmax's K/V block
  boundaries shifted relative to its tokens, and under jax 0.9.0's
  XLA:CPU a prompt at offset 0 no longer rebuilds the incremental cache
  to the bit either: the prefill's projections are products over
  4 x 16 rows and the decode step's over 2, the backend picks its
  kernel by shape, and already the FIRST layer's keys differ by up to
  9.5e-7 (a drift of the backend, not a defect: 36 prompts over init
  seeds 0-11, every first token equal, PR 29). One bar for both, read
  from those 36: the largest difference is 2.50e-6 on values up to 3.5
  (seed 2, second layer), and at rtol 1e-5 the largest atol any prompt
  needs is 7.5e-7."""
  spec, variables, _ = tiny_setup
  prompts = [np.array([3, 1, 4, 1, 5], np.int32),
             np.array([9, 2, 6, 5, 3, 5, 8, 9, 7], np.int32),
             np.array([2, 7, 1], np.int32)]
  bucket = 4
  images, placements = packing.pack_prompts(prompts, spec.max_len,
                                            bucket)
  assert all(p is not None for p in placements)
  rows = np.zeros((bucket,), np.int32)
  offsets = np.zeros((bucket,), np.int32)
  last_pos = np.zeros((bucket,), np.int32)
  lengths = np.zeros((bucket,), np.int32)
  slots = np.full((bucket,), bucket, np.int32)
  for i, (prm, (row, off)) in enumerate(zip(prompts, placements)):
    rows[i], offsets[i] = row, off
    lengths[i] = prm.size
    last_pos[i] = off + prm.size - 1
    slots[i] = i
  cache = decode_lib.init_cache(spec, bucket)
  prefill = jax.jit(decode_lib.prefill_fn(spec))
  first, ek, ev = prefill(
      variables, jnp.asarray(images), jnp.asarray(rows),
      jnp.asarray(last_pos), jnp.asarray(offsets))
  cache = decode_lib.install_prefill(cache, ek, ev, first,
                                     jnp.asarray(lengths),
                                     jnp.asarray(slots))
  ck, cv, pos, tok = cache.k, cache.v, cache.pos, cache.tok

  step = jax.jit(decode_lib.decode_fn(spec))
  for i, prm in enumerate(prompts):
    # Teacher-forced incremental build of the same prompt, at bucket 2
    # with an idle second slot (B >= 2 keeps XLA on the gemm path --
    # its M=1 gemv accumulates differently, the bitwise boundary).
    c1 = decode_lib.init_cache(spec, 2)
    k1, v1, p1 = c1.k, c1.v, c1.pos
    nxt = None
    for p, t in enumerate(prm):
      nxt, k1, v1, p1 = step(variables, k1, v1, p1,
                             jnp.asarray([int(t), 0], jnp.int32),
                             jnp.asarray([True, False]))
    n = prm.size
    assert int(pos[i]) == n == int(p1[0])
    assert int(tok[i]) == int(first[i]) == int(nxt[0])
    check = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6)
    check(np.asarray(ck[:, i, :n]), np.asarray(k1[:, 0, :n]))
    check(np.asarray(cv[:, i, :n]), np.asarray(v1[:, 0, :n]))


# -- engine e2e ---------------------------------------------------------------

def _tiny_engine(ladder=(1, 2, 4), batching="continuous", **cfg_kw):
  spec = cfg_kw.pop("spec", tiny_spec(decode_exact=True))
  cfg = engine_lib.EngineConfig(spec=spec, bucket_ladder=ladder,
                                batching=batching, max_new_tokens=3,
                                **cfg_kw)
  return engine_lib.ServingEngine(cfg, seed=0)


def _prompts(n, rng=None, lo=2, hi=10):
  rng = rng or np.random.default_rng(0)
  return [rng.integers(0, 97, size=int(rng.integers(lo, hi)),
                       dtype=np.int32) for _ in range(n)]


@pytest.mark.parametrize("batching", [
    "continuous",
    # The static arm re-pays the module compiles; slow tier (wall
    # margin) -- its admission semantics stay tier-1 via the
    # static-drains test's sibling assertions.
    pytest.param("static", marks=pytest.mark.slow),
])
def test_engine_matches_engine_free_reference(batching):
  eng = _tiny_engine(batching=batching)
  prompts = _prompts(5)
  for i, prm in enumerate(prompts):
    assert eng.submit(engine_lib.Request(rid=i, prompt=prm))
  results = eng.drain()
  assert [r.status for r in results] == ["ok"] * 5
  for r, prm in zip(results, prompts):
    _, ref = decode_lib.reference_generate(eng.spec, eng.variables,
                                           prm, 3)
    assert r.tokens == ref, f"rid {r.rid}"
    assert r.ttft_s is not None and r.total_s >= r.ttft_s >= 0


def test_engine_bounded_compiles_on_mixed_length_replay():
  """The <=-bucket-count compile pin: a replay of mixed-length requests
  arriving in waves (bucket growth included) records at most
  len(ladder) decode compiles -- and the same for prefill -- in the
  compile ledger."""
  trace = tracing.RunTrace(path=None)
  tracing.activate(trace)
  try:
    eng = _tiny_engine(ladder=(1, 2, 4))
    rng = np.random.default_rng(1)
    rid = 0
    for wave in (1, 3, 4, 2):  # growth 1 -> 4, then reuse
      for prm in _prompts(wave, rng):
        assert eng.submit(engine_lib.Request(rid=rid, prompt=prm))
        rid += 1
      results = eng.drain()
    assert all(r.status == "ok" for r in results)
    entries = trace.compile_ledger()["entries"]
    by_program = {}
    for e in entries:
      by_program.setdefault(e["program"], set()).add(e["key"])
    assert 1 <= len(by_program["serving_decode"]) <= 3   # len(ladder)
    assert 1 <= len(by_program["serving_prefill"]) <= 3
    # ... and re-draining the same buckets compiled nothing new.
    assert len(entries) == sum(len(v) for v in by_program.values())
  finally:
    tracing.deactivate()


@pytest.mark.slow  # ~11 s: four drains + a full ladder warm
def test_engine_bucket_growth_and_warm():
  eng = _tiny_engine(ladder=(1, 2, 4))
  assert engine_lib.bucket_for(3, (1, 2, 4)) == 4
  assert engine_lib.bucket_for(9, (1, 2, 4)) == 4  # capped at top
  assert eng.submit(engine_lib.Request(rid=0, prompt=_prompts(1)[0]))
  eng.drain()
  assert eng._bucket == 1
  for i, prm in enumerate(_prompts(3), start=1):
    eng.submit(engine_lib.Request(rid=i, prompt=prm))
  eng.drain()
  assert eng._bucket == 4
  # warm() precompiles the remaining ladder shapes idempotently.
  fresh = _tiny_engine(ladder=(1, 2))
  assert fresh.warm() == 4          # 2 buckets x (decode + prefill)
  assert fresh.warm() == 0


@pytest.mark.slow  # ~6 s: two engines x three requests
def test_static_drains_before_admitting():
  """Batch-and-drain semantics: a static engine never prefills while
  slots are active; the continuous engine does (in-flight refill)."""
  observed = {}

  def instrument(eng, name):
    orig = eng._prefill_wave
    observed[name] = []

    def wrapped(wave):
      observed[name].append(eng._active_count())
      return orig(wave)

    eng._prefill_wave = wrapped

  for batching in ("static", "continuous"):
    eng = _tiny_engine(ladder=(2,), batching=batching)
    instrument(eng, batching)
    prompts = _prompts(3)
    # First request finishes after 1 token; its slot frees mid-wave.
    eng.submit(engine_lib.Request(rid=0, prompt=prompts[0],
                                  max_new_tokens=1))
    eng.submit(engine_lib.Request(rid=1, prompt=prompts[1],
                                  max_new_tokens=6))
    eng.submit(engine_lib.Request(rid=2, prompt=prompts[2],
                                  max_new_tokens=2))
    results = eng.drain()
    assert all(r.status == "ok" for r in results)
  assert all(a == 0 for a in observed["static"])
  assert any(a > 0 for a in observed["continuous"])


# -- admission control --------------------------------------------------------

def test_queue_depth_rejection():
  eng = _tiny_engine(max_queue_depth=2)
  prompts = _prompts(4)
  oks = [eng.submit(engine_lib.Request(rid=i, prompt=p))
         for i, p in enumerate(prompts)]
  assert oks == [True, True, False, False]
  results = eng.drain()
  by_rid = {r.rid: r for r in results}
  assert by_rid[2].status == "rejected"
  assert by_rid[2].shed_reason == "queue_depth"
  assert by_rid[0].status == "ok"
  stats = eng.stats()
  assert stats["serving/shed"] == 2
  assert stats["serving/shed_fraction"] == pytest.approx(0.5)


def test_ttft_deadline_expiry():
  """Deadline shedding is evaluated at coalesce time on the engine's
  own clock -- a fake clock makes it deterministic."""
  now = [0.0]
  eng = engine_lib.ServingEngine(
      engine_lib.EngineConfig(spec=tiny_spec(), bucket_ladder=(2,),
                              max_new_tokens=2, ttft_slo_s=0.5),
      seed=0, time_fn=lambda: now[0], sleep_fn=lambda s: None)
  eng.submit(engine_lib.Request(rid=0, prompt=_prompts(1)[0]))
  eng.submit(engine_lib.Request(rid=1, prompt=_prompts(1)[0],
                                deadline_s=10.0))
  now[0] = 1.0  # past the 0.5 s default SLO, inside rid 1's own
  results = eng.drain()
  by_rid = {r.rid: r for r in results}
  assert by_rid[0].status == "expired"
  assert by_rid[0].shed_reason == "ttft_deadline"
  assert by_rid[1].status == "ok"


def test_tenant_token_budget():
  eng = _tiny_engine(tenant_tokens_per_s=10.0, tenant_burst_s=1.0)
  prompt = np.ones(8, np.int32)
  # 8 prompt + 3 generated = 11 tokens > the 10-token burst bucket.
  assert not eng.submit(engine_lib.Request(rid=0, prompt=prompt,
                                           tenant="a"))
  small = np.ones(4, np.int32)  # 7 tokens: fits a fresh bucket
  assert eng.submit(engine_lib.Request(rid=1, prompt=small, tenant="a"))
  # ... tenant a's bucket is down to ~3 tokens; 7 more won't fit
  # (refill at 10 tokens/s over the microseconds between submits is
  # negligible), while tenant b's fresh bucket admits.
  assert not eng.submit(engine_lib.Request(rid=2, prompt=small,
                                           tenant="a"))
  assert eng.submit(engine_lib.Request(rid=3, prompt=small, tenant="b"))
  results = eng.drain()
  statuses = {r.rid: r.status for r in results}
  assert statuses == {0: "rejected", 1: "ok", 2: "rejected", 3: "ok"}


def test_prompt_too_long_is_shed_not_raised():
  eng = _tiny_engine()
  assert not eng.submit(engine_lib.Request(
      rid=0, prompt=np.ones(eng.spec.max_len + 1, np.int32)))
  assert not eng.submit(engine_lib.Request(
      rid=1, prompt=np.zeros((0,), np.int32)))
  r0, r1 = eng.drain()
  assert (r0.status, r0.shed_reason) == ("rejected", "prompt_too_long")
  assert (r1.status, r1.shed_reason) == ("rejected", "empty_prompt")


def test_exact_decode_attention_survives_ring_wrap():
  """Past the ring's capacity (pos >= T) the exact oracle schedule must
  degrade to the SAME trailing-window semantics as the fast path (all
  slots valid), not a causal mask pinned at pos % T that attends one
  key (the review-caught wrap bug)."""
  from kf_benchmarks_tpu.parallel import sequence as seq
  b, t, h, d = 2, 8, 2, 4
  rng = jax.random.PRNGKey(0)
  q = jax.random.normal(rng, (b, 1, h, d), jnp.float32)
  k = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d), jnp.float32)
  v = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, d), jnp.float32)
  for p in (t - 1, t, t + 5):
    pos = jnp.full((b,), p, jnp.int32)
    exact = seq.decode_attention(q, k, v, pos, block=4, impl="tiled",
                                 exact=True)
    fast = seq.decode_attention(q, k, v, pos, block=4, impl="tiled",
                                exact=False)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(fast),
                               rtol=1e-5, atol=1e-6)


# -- observability joins ------------------------------------------------------

def test_metrics_registry_spans_and_healthz():
  registry = metrics_lib.MetricRegistry()
  metrics_lib.activate(registry)
  trace = tracing.RunTrace(path="unused.json")  # retain spans, no write
  trace.path = None
  tracing.activate(trace)
  try:
    eng = _tiny_engine()
    server = eng.serve_metrics(0, registry)
    try:
      for i, prm in enumerate(_prompts(3)):
        eng.submit(engine_lib.Request(rid=i, prompt=prm))
      eng.drain()
      snap = registry.snapshot()
      assert snap["serving/requests"] == 3
      assert snap["serving/completed"] == 3
      assert snap["serving/ttft_p99"] > 0
      assert 0 < snap["serving/batch_fill_fraction"] <= 1
      assert not metrics_lib.validate_prometheus_text(registry.render())
      with urllib.request.urlopen(
          f"http://127.0.0.1:{server.port}/healthz") as resp:
        payload = json.loads(resp.read())
      assert payload["status"] == "ok"
      assert payload["serving"]["state"] == "drained"
      assert payload["serving"]["completed"] == 3
      with urllib.request.urlopen(
          f"http://127.0.0.1:{server.port}/metrics") as resp:
        body = resp.read().decode()
      assert "kf_serving_completed" in body
    finally:
      server.close()
    # Request spans + samples landed on the run-trace timeline.
    names = {(s["sub"], s["name"]) for s in trace._spans}
    assert ("serving", "prefill") in names
    assert ("serving", "decode_step") in names
    assert ("serving", "request") in names
    pct = trace.percentiles()
    assert pct["serving/ttft"]["n"] == 3
    assert pct["serving/token_latency"]["n"] >= 1
  finally:
    tracing.deactivate()
    metrics_lib.deactivate()


@pytest.mark.slow  # ~5 s: engine replay on top of the workload check
def test_replay_workload_is_deterministic():
  spec = tiny_spec()
  w1 = engine_lib.poisson_workload(6, 100.0, spec, seed=4)
  w2 = engine_lib.poisson_workload(6, 100.0, spec, seed=4)
  assert [t for t, _ in w1] == [t for t, _ in w2]
  for (_, a), (_, b) in zip(w1, w2):
    np.testing.assert_array_equal(a.prompt, b.prompt)
  eng = _tiny_engine()
  results = eng.replay(w1)
  assert all(r.status == "ok" for r in results)
  assert eng.stats()["serving/tokens_per_sec"] > 0


# -- AOT signature validation (aot.py satellite) ------------------------------

def test_aot_signature_sidecar_and_bucket_error(tmp_path):
  from kf_benchmarks_tpu import aot
  from kf_benchmarks_tpu.models import model_config
  model = model_config.get_model_config("trivial", "imagenet")
  model.set_batch_size(4)
  module = model.make_module(nclass=1001, phase_train=False)
  rng = jax.random.PRNGKey(0)
  images = jnp.zeros(tuple(model.get_input_shapes("eval")[0]),
                     jnp.float32)
  variables = module.init({"params": rng, "dropout": rng}, images)
  path = str(tmp_path / "trivial_bs4.bin")
  aot.export_forward(model, variables, 4, path, fingerprint="fp-abc")
  sig = aot.read_signature(path)
  assert sig["batch_size"] == 4 and sig["fingerprint"] == "fp-abc"
  # valid expectation loads; mismatch names signature + bucket list
  fn = aot.load_forward(path, expect_batch=4)
  assert fn(images).shape[0] == 4
  model.set_batch_size(2)
  path2 = str(tmp_path / "trivial_bs2.bin")
  aot.export_forward(model, variables, 2, path2, fingerprint="fp-abc")
  with pytest.raises(ValueError) as err:
    aot.load_forward(path, expect_batch=16)
  msg = str(err.value)
  assert "batch 4" in msg and "16" in msg
  assert "[2, 4]" in msg  # the available bucket list (both siblings)
  assert "fp-abc" in msg


# -- auditor: serving golden + rule self-tests --------------------------------

@pytest.fixture(scope="module")
def serving_contract():
  return contracts.trace_serving_contract(
      dict(contracts.SERVING_GOLDEN_CONFIGS["serving_decode"]))


def test_serving_golden_matches_and_passes_rules(serving_contract):
  assert not baseline.check_against_golden("serving_decode",
                                           serving_contract)
  assert not audit.audit_contract(serving_contract, tracer=None)


def test_serving_contract_shape(serving_contract):
  c = serving_contract
  assert c.program == "serving_decode"
  assert c.donated_buffers > 0              # the ring updates in place
  assert not c.host_transfers
  assert c.aux["decode_batch"] in c.aux["bucket_ladder"]
  # The largest array is (at most) one KV ring buffer -- in particular
  # nowhere near a (B, T, V) logits tensor.
  assert c.largest_tensor_bytes <= c.aux["kv_ring_bytes"]
  assert c.aux["kv_ring_bytes"] < c.aux["vocab_logits_bytes"]


SERVING_MUTATIONS = [
    ("off-ladder bucket",
     lambda c: c.aux.update(decode_batch=5)),
    ("lost cache donation",
     lambda c: setattr(c, "donated_buffers", 0)),
    ("materialized (B,T,V) logits",
     lambda c: setattr(c, "largest_tensor_bytes",
                       c.aux["vocab_logits_bytes"])),
    ("oversized temp leak",
     lambda c: setattr(c, "largest_tensor_bytes",
                       c.aux["kv_ring_bytes"] + 1)),
]


@pytest.mark.parametrize("seed,mutate", SERVING_MUTATIONS,
                         ids=[m[0] for m in SERVING_MUTATIONS])
def test_serving_mutation_fires_exactly_the_serving_rule(
    serving_contract, seed, mutate):
  contract = copy.deepcopy(serving_contract)
  assert not audit.audit_contract(contract, tracer=None)
  mutate(contract)
  fired = {v.rule for v in audit.audit_contract(contract, tracer=None)}
  assert fired == {"serving-bounded-decode"}, (seed, fired)
