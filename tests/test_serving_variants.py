"""Decode-cost variants (ISSUE 16; serving/decode.py + serving/engine.py):
INT8 weight-only decode, paged KV cache, speculative decoding.

Layers, reference-style (SURVEY 7.1):
  * spec validation: every invalid variant combination fails in
    LMSpec.__post_init__ / validation.validate_cross_flags with the
    named flag, and variant-off specs fingerprint byte-identically to
    pre-variant history (None-valued config entries drop).
  * numerical-equivalence: paged decode_attention reconstructs the
    dense ring BIT-EXACTLY at gemm shapes (the same XLA:CPU envelope
    as the dense oracle); INT8 greedy decode agrees with the f32 arm
    (bars read from a dozen init seeds, bounded max logit delta); the
    speculative verify
    program's chunked argmax equals the full forward's argmax bitwise.
  * allocator invariants: pages are never double-freed, a drained
    engine returns every page, pool exhaustion sheds/requeues through
    the existing admission path instead of raising.
  * engine e2e: paged == dense tokens; speculative == plain greedy
    (token identity, per request, vs reference_generate AND vs the
    plain engine on the SAME workload); all three legs composed ==
    the INT8-only arm; the compile ledger stays bounded by the ladder
    (decode + prefill + verify families).
  * auditor: the three variant goldens match; each seeded regression
    fires exactly its owning rule (a dense-slab regression in the
    paged program fires serving-paged-kv, nothing else).
  * aot: the signature sidecar records quantize mode + page geometry
    and load_forward fails with the sidecar DIFF, not an XLA error.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import quantization
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu.analysis import audit, baseline, contracts
from kf_benchmarks_tpu.data.packing import pack_prompts
from kf_benchmarks_tpu.parallel import sequence
from kf_benchmarks_tpu.serving import decode as decode_lib
from kf_benchmarks_tpu.serving import engine as engine_lib

TINY = dict(vocab=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_len=32, attn_block=8)


def tiny_spec(**kw):
  return decode_lib.LMSpec(**{**TINY, **kw})


@pytest.fixture(scope="module")
def tiny_vars():
  return decode_lib.init_variables(tiny_spec(), seed=0)


def _run_engine(spec, variables, requests, max_new=6, ladder=(1, 2, 4),
                **cfg_kw):
  cfg = engine_lib.EngineConfig(spec=spec, bucket_ladder=ladder,
                                max_new_tokens=max_new, **cfg_kw)
  eng = engine_lib.ServingEngine(cfg, variables=variables, seed=0)
  for r in requests:
    eng.submit(dataclasses.replace(r))
  results = eng.drain()
  return eng, {r.rid: tuple(r.tokens) for r in results
               if r.status == "ok"}


def _workload_requests(spec, n=10, rate=50.0, seed=3, max_new=6):
  return [r for _, r in engine_lib.poisson_workload(
      n, rate, spec, seed=seed, max_new_tokens=max_new)]


# -- spec validation + fingerprint stability ----------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(quantize="fp4"), "quantize"),
    (dict(kv_page_size=7), "kv_page_size"),          # 7 does not divide 32
    (dict(speculative_k=1, draft_n_layers=1), "speculative_k"),
    (dict(speculative_k=3), "draft"),                # no draft spec
    (dict(speculative_k=3, draft_n_layers=2), "draft"),  # not < n_layers
    (dict(draft_n_layers=1), "inert"),               # draft without k
])
def test_spec_rejects_invalid_variants(kw, needle):
  with pytest.raises(ValueError, match=needle):
    tiny_spec(**kw)


def test_variant_off_fingerprint_is_byte_identical():
  """The variant fields are None-when-off in LMSpec.config(), and
  config_fingerprint_key drops None entries -- so every pre-variant
  golden, run-store record and ledger key survives this round
  unchanged."""
  cfg = tiny_spec().config()
  for key in ("quantize", "kv_page_size", "speculative_k",
              "draft_n_layers"):
    assert cfg[key] is None
  stripped = {k: v for k, v in cfg.items()
              if k not in ("quantize", "kv_page_size", "speculative_k",
                           "draft_n_layers")}
  assert (baseline.config_fingerprint_key({**cfg, "bucket": 4}, "sd") ==
          baseline.config_fingerprint_key({**stripped, "bucket": 4},
                                          "sd"))


def test_cross_flag_validation_names_the_flag():
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  base = dict(model="transformer_lm", device="cpu", num_devices=1)
  with pytest.raises(validation.ParamError,
                     match="serving_draft_layers"):
    validation.validate_cross_flags(
        params_lib.make_params(**base, serving_speculative_k=4))
  with pytest.raises(validation.ParamError, match="inert"):
    validation.validate_cross_flags(
        params_lib.make_params(**base, serving_draft_layers=2))
  with pytest.raises(validation.ParamError, match="divide"):
    validation.validate_cross_flags(
        params_lib.make_params(**base, serving_kv_page_size=100))
  # The valid combination passes the cross check.
  validation.validate_cross_flags(params_lib.make_params(
      **base, serving_quantize="int8", serving_kv_page_size=128,
      serving_speculative_k=4, serving_draft_layers=2))


# -- INT8 weight-only decode --------------------------------------------------

# Agreement bars for the random-init tiny model, read from init seeds
# 0-11 under jax 0.9.0's XLA:CPU (PR 29; the 0.99 these tests held before
# was one backend's reading of seed 0). A drift of the backend, not a
# defect: over the twelve seeds the dequantized forward's max logit
# delta is 0.042-0.069 on a logit scale of 3.3-4.3 (under 2.1%), and
# random-init argmax margins are thinner than that.
#   next-token agreement given the f32 prefix (decode.quantize_agreement):
#     46/48 to 48/48, lowest 0.9583 (seed 9), seed 0 0.9792;
#   whole-sequence zip through the engine, where one flip costs the rest
#     of its row: 53/60 to 60/60, lowest 0.8833 (seed 10), seed 0 0.9167.
INT8_GATE_AGREEMENT = 45 / 48
INT8_ENGINE_AGREEMENT = 51 / 60

def test_int8_prepare_idempotent_and_abstract_matches(tiny_vars):
  qspec = tiny_spec(quantize="int8")
  qvars = decode_lib.prepare_variables(qspec, tiny_vars)
  assert quantization.has_quantized_leaves(qvars)
  assert decode_lib.prepare_variables(qspec, qvars) is qvars
  real = jax.tree.map(lambda x: (x.shape, str(x.dtype)), qvars)
  ab = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                    decode_lib.abstract_variables(qspec))
  assert real == ab


def test_int8_greedy_agreement_and_logit_delta(tiny_vars):
  """INT8 against the f32 arm over a seeded replay: whole-sequence
  greedy agreement at or above INT8_ENGINE_AGREEMENT, and the
  dequantized forward's max logit delta small relative to the logit
  scale."""
  spec = tiny_spec()
  qspec = tiny_spec(quantize="int8")
  reqs = _workload_requests(spec, n=10)
  _, plain = _run_engine(spec, tiny_vars, reqs)
  _, quant = _run_engine(qspec, tiny_vars, reqs)
  assert set(quant) == set(plain)
  total = agree = 0
  for rid in plain:
    for a, b in zip(plain[rid], quant[rid]):
      total += 1
      agree += int(a == b)
  assert total >= 40
  assert agree / total >= INT8_ENGINE_AGREEMENT, (
      f"INT8 greedy agreement {agree}/{total}")
  # Logit delta: full forward, dequantized weights vs originals.
  qvars = decode_lib.prepare_variables(qspec, tiny_vars)
  fvars = quantization.dequantize_variables(qvars, qspec.param_dtype)
  module = decode_lib.forward_module(spec, fused_head=False)
  tokens = jnp.asarray(
      np.random.RandomState(0).randint(0, spec.vocab,
                                       (2, spec.max_len)), jnp.int32)
  ref, _ = jax.jit(module.apply)(tiny_vars, tokens)
  got, _ = jax.jit(module.apply)(fvars, tokens)
  delta = float(jnp.max(jnp.abs(got - ref)))
  scale = float(jnp.max(jnp.abs(ref)))
  assert delta <= 0.05 * max(scale, 1.0), (delta, scale)


def test_quantize_agreement_gate_primitive(tiny_vars):
  """decode.quantize_agreement -- the serve/fall-back decision the
  bench path enforces (--serving_quantize=int8): prefix-conditioned
  next-token agreement (teacher-forced on the f32 arm's rows, so one
  early flip can't poison the rest of the sequence), plus the max
  logit delta of the dequantized forward. The program's bar
  (QUANTIZE_AGREEMENT_BAR, 0.99) is the gate's and is not this test's
  to move: a random-init tiny model sits on it (seed 0 reads 47/48 and
  is turned away, seeds 1 and 4-8 read 48/48 and are admitted -- the
  razor-thin-margin case the gate exists to catch, PERF.md round 19),
  so the test holds the measurement to INT8_GATE_AGREEMENT and the
  decision to the measurement."""
  qspec = tiny_spec(quantize="int8")
  rng = np.random.default_rng(0)
  prompts = [rng.integers(0, qspec.vocab, size=int(rng.integers(2, 10)))
             for _ in range(8)]
  gate = decode_lib.quantize_agreement(qspec, tiny_vars, prompts,
                                       max_new_tokens=6)
  assert set(gate) == {"agreement", "total", "max_logit_delta",
                       "logit_scale", "passed"}
  assert gate["total"] >= 30
  assert gate["agreement"] >= INT8_GATE_AGREEMENT
  assert gate["passed"] is (
      gate["agreement"] >= decode_lib.QUANTIZE_AGREEMENT_BAR)
  assert gate["max_logit_delta"] <= 0.05 * max(gate["logit_scale"], 1.0)
  with pytest.raises(ValueError, match="quantized spec"):
    decode_lib.quantize_agreement(tiny_spec(), tiny_vars, prompts, 4)


# -- paged KV cache -----------------------------------------------------------

def test_paged_attention_bit_identical_to_dense_at_gemm_shapes():
  """Page-table reconstruction == the dense ring, bitwise, for both
  the exact path and the fast gather schedule -- at the gemm shapes
  where XLA:CPU is k-block-free (PERF.md round 18)."""
  rng = np.random.RandomState(0)
  B, H, Dh, page, npages = 2, 4, 8, 8, 4
  T = page * npages
  kpool = jnp.asarray(rng.randn(1 + B * npages, page, H, Dh),
                      jnp.float32)
  vpool = jnp.asarray(rng.randn(1 + B * npages, page, H, Dh),
                      jnp.float32)
  tbl = jnp.arange(1, 1 + B * npages, dtype=jnp.int32).reshape(B, npages)
  q = jnp.asarray(rng.randn(B, 1, H, Dh), jnp.float32)
  pos = jnp.asarray([13, 27], jnp.int32)
  kd = kpool[tbl].reshape(B, T, H, Dh)
  vd = vpool[tbl].reshape(B, T, H, Dh)
  dense = sequence.decode_attention(q, kd, vd, pos, block=page,
                                    impl="tiled")
  paged = sequence.decode_attention(q, kpool, vpool, pos, block=page,
                                    impl="tiled", page_table=tbl)
  dense_exact = sequence.decode_attention(q, kd, vd, pos, block=page,
                                          impl="tiled", exact=True,
                                          q_block=page)
  paged_exact = sequence.decode_attention(q, kpool, vpool, pos,
                                          block=page, impl="tiled",
                                          exact=True, page_table=tbl,
                                          q_block=page)
  # Each paged schedule is bit-identical to ITS dense counterpart (the
  # exact path orders the reduction differently from the fast tiled
  # one, so the two schedules only agree to float rounding).
  assert bool(jnp.all(dense == paged))
  assert bool(jnp.all(dense_exact == paged_exact))


def test_paged_pool_strictly_under_dense_slab():
  """The concurrency win paging exists for: the pool is sized by
  expected occupancy (KV_POOL_FRACTION), strictly under one dense
  slab's page count for every multi-slot bucket -- so the same HBM
  budget admits MORE concurrent sessions than the dense ring."""
  spec = tiny_spec(kv_page_size=8)
  pps = spec.pages_per_slot
  for bucket in (2, 4, 8):
    dense_pages = bucket * pps
    assert decode_lib.kv_pool_pages(spec, bucket) < dense_pages
  # A single slot always fits outright (pps pages + the scratch page).
  assert decode_lib.kv_pool_pages(spec, 1) >= pps + 1


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_paged_engine_matches_dense_and_reference(tiny_vars):
  spec = tiny_spec()
  pspec = tiny_spec(kv_page_size=8)
  reqs = _workload_requests(spec, n=10)
  _, dense = _run_engine(spec, tiny_vars, reqs)
  engp, paged = _run_engine(pspec, tiny_vars, reqs)
  assert paged == dense
  assert engp._kv_pages_peak > 0
  by_rid = {r.rid: r for r in reqs}
  for rid, toks in list(paged.items())[:3]:
    _, ref = decode_lib.reference_generate(spec, tiny_vars,
                                           by_rid[rid].prompt, 6)
    assert list(toks) == ref


def test_page_allocator_no_double_free_and_full_return(tiny_vars):
  """After a drain every allocated page is back on the free list
  exactly once, and every live table row is zeroed (scratch)."""
  pspec = tiny_spec(kv_page_size=8)
  eng, ok = _run_engine(pspec, tiny_vars,
                        _workload_requests(pspec, n=12))
  assert ok
  free = eng._free_pages
  assert len(free) == len(set(free)), "double-freed page"
  pool = int(eng._cache.k.shape[1]) if eng._cache is not None else None
  if pool is not None:
    # Page 0 is the scratch page (never allocated, never freed).
    assert sorted(free) == list(range(1, pool))
    assert not eng._table_np.any(), "stale page-table rows after drain"


def test_page_pool_exhaustion_sheds_via_admission_not_raise(tiny_vars):
  """The pool holds ~half a bucket's worth of pages; a wave of
  max-length prompts cannot all prefill at once. The overflow goes
  back through the admission path (requeue/shed) -- never an
  exception -- and every admitted request still completes correctly."""
  pspec = tiny_spec(kv_page_size=8)
  rng = np.random.default_rng(0)
  # Long prompts: each needs the full pages_per_slot allocation.
  prompts = [rng.integers(0, pspec.vocab, size=24, dtype=np.int32)
             for _ in range(8)]
  reqs = [engine_lib.Request(rid=i, prompt=p)
          for i, p in enumerate(prompts)]
  eng, paged = _run_engine(pspec, tiny_vars, reqs, ladder=(8,))
  spec = tiny_spec()
  reqs2 = [engine_lib.Request(rid=i, prompt=p)
           for i, p in enumerate(prompts)]
  _, dense = _run_engine(spec, tiny_vars, reqs2, ladder=(8,))
  assert paged == dense  # same completions, same tokens
  free = eng._free_pages
  assert len(free) == len(set(free))


# -- speculative decoding -----------------------------------------------------

def test_verify_fn_equals_full_forward_argmax(tiny_vars):
  spec = tiny_spec()
  preds = jax.jit(decode_lib.verify_fn(spec))(
      tiny_vars,
      jnp.asarray(np.random.RandomState(1).randint(
          0, spec.vocab, (2, spec.max_len)), jnp.int32))
  module = decode_lib.forward_module(spec, fused_head=False)
  logits, _ = jax.jit(module.apply)(
      tiny_vars,
      jnp.asarray(np.random.RandomState(1).randint(
          0, spec.vocab, (2, spec.max_len)), jnp.int32))
  ref = jnp.argmax(logits, axis=-1).astype(jnp.int32)
  assert bool(jnp.all(preds == ref))
  assert spec.max_len % decode_lib.verify_chunk(spec) == 0


def test_truncate_variables_slices_scanned_blocks(tiny_vars):
  sspec = tiny_spec(speculative_k=3, draft_n_layers=1)
  draft = decode_lib.draft_spec(sspec)
  assert draft.n_layers == 1 and draft.speculative_k == 0
  dvars = decode_lib.truncate_variables(sspec, tiny_vars)
  full = jax.tree.leaves(tiny_vars["params"]["blocks"])
  cut = jax.tree.leaves(dvars["params"]["blocks"])
  for f, c in zip(full, cut):
    assert c.shape == (1,) + f.shape[1:]
    assert bool(jnp.all(c == f[:1]))


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_speculative_token_identical_to_plain_greedy(tiny_vars):
  """THE speculative invariant: greedy speculative output is provably
  token-identical to plain greedy decode -- per request, against both
  the engine-free reference and the plain engine on the SAME workload
  (generated from the speculative spec, whose admission cap is
  tighter, so both arms serve identical requests)."""
  sspec = tiny_spec(speculative_k=3, draft_n_layers=1)
  spec = tiny_spec()
  reqs = _workload_requests(sspec, n=10)
  _, plain = _run_engine(spec, tiny_vars, reqs)
  engs, specd = _run_engine(sspec, tiny_vars, reqs)
  assert set(specd) == set(plain)
  for rid in specd:
    assert specd[rid] == plain[rid], f"speculative diverged on {rid}"
  by_rid = {r.rid: r for r in reqs}
  for rid, toks in list(specd.items())[:3]:
    _, ref = decode_lib.reference_generate(spec, tiny_vars,
                                           by_rid[rid].prompt, 6)
    assert list(toks) == ref
  # Accounting: every acceptance is a draft proposal the target agreed
  # with; rounds ran; the accept-length histogram was sampled.
  assert engs._spec_rounds > 0
  assert 0 <= engs._accepted_tokens <= engs._draft_tokens
  st = engs.stats()
  assert st["serving/spec_rounds"] == engs._spec_rounds
  assert st["serving/accept_len_p50"] is not None


def test_speculative_accepts_when_draft_agrees(tiny_vars):
  """A draft that always agrees with the target (all-zero weights:
  argmax ties resolve to token 0 for both) accepts nearly every
  proposal -- each verify round emits more than one token, which is
  the whole speculative win."""
  sspec = tiny_spec(speculative_k=3, draft_n_layers=1)
  zeros = jax.tree.map(jnp.zeros_like, tiny_vars)
  reqs = _workload_requests(sspec, n=6)
  engs, out = _run_engine(sspec, zeros, reqs)
  assert out
  emitted = sum(len(t) for t in out.values())
  assert engs._accepted_tokens > 0
  assert emitted / max(engs._spec_rounds, 1) > 1.2, (
      emitted, engs._spec_rounds)
  for toks in out.values():
    assert all(t == 0 for t in toks)


def test_speculative_oversized_prompt_sheds_not_raises(tiny_vars):
  sspec = tiny_spec(speculative_k=3, draft_n_layers=1)
  cfg = engine_lib.EngineConfig(spec=sspec, bucket_ladder=(1, 2, 4),
                                max_new_tokens=6)
  eng = engine_lib.ServingEngine(cfg, variables=tiny_vars, seed=0)
  # prompt_len + max_new + k must fit max_len for the verify rows.
  too_long = np.zeros((sspec.max_len - 6, ), np.int32)
  assert not eng.submit(engine_lib.Request(rid=0, prompt=too_long))
  results = eng.drain()
  assert [r.status for r in results] == ["rejected"]
  assert results[0].shed_reason == "prompt_too_long"


# -- composition + bounded compiles -------------------------------------------

# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_all_three_legs_composed_match_int8_arm(tiny_vars):
  cspec = tiny_spec(quantize="int8", kv_page_size=8, speculative_k=3,
                    draft_n_layers=1)
  qspec = tiny_spec(quantize="int8")
  reqs = _workload_requests(cspec, n=8)
  _, quant = _run_engine(qspec, tiny_vars, reqs)
  _, comp = _run_engine(cspec, tiny_vars, reqs)
  assert comp == quant


def test_speculative_compile_ledger_bounded_by_ladder(tiny_vars):
  """Decode + prefill + verify are each a per-bucket family: the
  ledger stays <= 3 * len(ladder) compiles on a mixed replay."""
  trace = tracing.RunTrace(path=None)
  tracing.activate(trace)
  try:
    sspec = tiny_spec(speculative_k=3, draft_n_layers=1)
    reqs = _workload_requests(sspec, n=12, rate=200.0)
    _run_engine(sspec, tiny_vars, reqs, ladder=(1, 2, 4))
    ledger = trace.compile_ledger()
    assert ledger.get("shapes", 0) <= 3 * 3
  finally:
    tracing.deactivate()


def test_engine_stats_variant_keys_none_when_off(tiny_vars):
  spec = tiny_spec()
  eng, _ = _run_engine(spec, tiny_vars, _workload_requests(spec, n=3))
  st = eng.stats()
  for key in ("serving/kv_pages_in_use", "serving/kv_page_fraction",
              "serving/spec_rounds", "serving/draft_tokens",
              "serving/accepted_tokens", "serving/accept_len_p50"):
    assert st[key] is None, key


# -- auditor: variant goldens + one-owner mutation self-tests -----------------

@pytest.fixture(scope="module")
def paged_contract():
  return contracts.trace_serving_contract(
      dict(contracts.SERVING_GOLDEN_CONFIGS["serving_decode_paged"]))


@pytest.fixture(scope="module")
def verify_contract():
  return contracts.trace_serving_contract(
      dict(contracts.SERVING_GOLDEN_CONFIGS["serving_verify"]))


def test_variant_goldens_exist_and_match(paged_contract, verify_contract):
  assert not baseline.check_against_golden("serving_decode_paged",
                                           paged_contract)
  assert not baseline.check_against_golden("serving_verify",
                                           verify_contract)
  int8 = contracts.trace_serving_contract(
      dict(contracts.SERVING_GOLDEN_CONFIGS["serving_decode_int8"]))
  assert not baseline.check_against_golden("serving_decode_int8", int8)
  assert not audit.audit_contract(int8, tracer=None)


def test_paged_contract_shape(paged_contract):
  c = paged_contract
  assert c.program == "serving_decode"
  assert c.donated_buffers > 0
  assert c.aux["kv_pool_bytes"] < c.aux["kv_ring_bytes"]
  assert c.largest_tensor_bytes < c.aux["kv_ring_bytes"]
  assert not audit.audit_contract(c, tracer=None)


def test_verify_contract_shape(verify_contract):
  c = verify_contract
  assert c.program == "serving_verify"
  assert not c.host_transfers
  # The chunked argmax keeps every live buffer under the (B, T, V)
  # logits tensor; the chunk slice is the legitimate ceiling.
  assert c.aux["verify_logits_bytes"] < c.aux["vocab_logits_bytes"]
  assert c.largest_tensor_bytes < c.aux["vocab_logits_bytes"]
  assert not audit.audit_contract(c, tracer=None)


PAGED_MUTATIONS = [
    ("dense-slab regression (buffer at the slab ceiling)",
     lambda c: setattr(c, "largest_tensor_bytes",
                       c.aux["kv_ring_bytes"])),
    ("pool grown to the dense slab",
     lambda c: c.aux.update(kv_pool_bytes=c.aux["kv_ring_bytes"])),
]


@pytest.mark.parametrize("seed,mutate", PAGED_MUTATIONS,
                         ids=[m[0] for m in PAGED_MUTATIONS])
def test_paged_mutation_fires_exactly_the_paged_rule(
    paged_contract, seed, mutate):
  contract = copy.deepcopy(paged_contract)
  assert not audit.audit_contract(contract, tracer=None)
  mutate(contract)
  fired = {v.rule for v in audit.audit_contract(contract, tracer=None)}
  assert fired == {"serving-paged-kv"}, (seed, fired)


VERIFY_MUTATIONS = [
    ("materialized full (B,T,V) logits",
     lambda c: setattr(c, "largest_tensor_bytes",
                       c.aux["vocab_logits_bytes"])),
    ("off-ladder verify bucket",
     lambda c: c.aux.update(decode_batch=5)),
]


@pytest.mark.parametrize("seed,mutate", VERIFY_MUTATIONS,
                         ids=[m[0] for m in VERIFY_MUTATIONS])
def test_verify_mutation_fires_exactly_the_verify_rule(
    verify_contract, seed, mutate):
  contract = copy.deepcopy(verify_contract)
  assert not audit.audit_contract(contract, tracer=None)
  mutate(contract)
  fired = {v.rule for v in audit.audit_contract(contract, tracer=None)}
  assert fired == {"serving-verify-bounded"}, (seed, fired)


# -- aot sidecar: serving-mode diff -------------------------------------------

class _TinyModel:
  """Just enough of the model zoo surface for export_forward."""

  def set_batch_size(self, bs):
    self.bs = bs

  def get_input_shapes(self, phase):
    return [(self.bs, 8, 8, 3)]

  def make_module(self, **kw):
    import flax.linen as nn

    class M(nn.Module):

      @nn.compact
      def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(4, name="head")(x), {}

    return M()


def _export(tmp_path, name, **kw):
  from kf_benchmarks_tpu import aot
  model = _TinyModel()
  model.set_batch_size(2)
  module = model.make_module()
  variables = module.init(jax.random.PRNGKey(0),
                          jnp.zeros((2, 8, 8, 3), jnp.float32))
  path = str(tmp_path / name)
  aot.export_forward(model, variables, 2, path, nclass=4, **kw)
  return path


def test_aot_sidecar_records_mode_and_diffs_on_load(tmp_path):
  from kf_benchmarks_tpu import aot
  qpath = _export(tmp_path, "int8.bin", quantize=True, kv_page_size=8)
  sig = aot.read_signature(qpath)
  assert sig["quantize_mode"] == "int8"
  assert sig["kv_page_size"] == 8
  # A bf16 engine loading the INT8 export fails with the sidecar diff
  # BEFORE deserialization, naming both sides.
  with pytest.raises(ValueError, match="quantize_mode") as err:
    aot.load_forward(qpath, expect_quantize=None, expect_kv_page_size=8)
  assert "sidecar='int8'" in str(err.value)
  assert "requested=None" in str(err.value)
  with pytest.raises(ValueError, match="kv_page_size"):
    aot.load_forward(qpath, expect_quantize="int8",
                     expect_kv_page_size=None)
  # The matching mode loads and serves.
  fn = aot.load_forward(qpath, expect_quantize="int8",
                        expect_kv_page_size=8)
  out = fn(jnp.zeros((2, 8, 8, 3), jnp.float32))
  assert out.shape == (2, 4)


def test_aot_presidecar_artifact_skips_mode_check(tmp_path):
  import os
  from kf_benchmarks_tpu import aot
  path = _export(tmp_path, "plain.bin")
  sig = aot.read_signature(path)
  assert sig["quantize_mode"] is None and sig["kv_page_size"] is None
  os.remove(aot.signature_path(path))
  # No sidecar -> mode expectations are unverifiable; stays loadable.
  fn = aot.load_forward(path, expect_quantize="int8")
  assert fn(jnp.zeros((2, 8, 8, 3), jnp.float32)).shape == (2, 4)
