"""Program-contract auditor (kf_benchmarks_tpu/analysis/).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: HLO extraction on hand-built dumps (no jax needed for
    the parser), and an end-to-end seeded program -- an extra psum
    injected inside a scan body -- that the extractor must place
    in-loop and the rule engine must reject.
  * golden configs: every earned contract (one-collective accum,
    no-collective-in-loop, no-(B,T,V)-buffer LM, health-no-extra-
    collective, bf16-wire flag) verified by tracing each golden config
    on the 8-device mesh, passing the full rule set, and matching the
    checked-in golden fingerprint field-for-field.
  * mutation self-tests: each seeded violation is caught by EXACTLY
    the intended rule, so the auditor cannot rot into a
    pass-everything stub.
"""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P

from kf_benchmarks_tpu.analysis import audit, baseline, contracts
from kf_benchmarks_tpu.analysis.contracts import Collective
from kf_benchmarks_tpu.parallel.mesh import REPLICA_AXIS


@pytest.fixture(scope="module")
def tracer():
  """Memoized config -> ProgramContract tracer shared by the module
  (each golden compiles once per pytest session)."""
  return audit.make_memo_tracer()


# -- pure-unit: the HLO parser ------------------------------------------------

_FAKE_HLO = """\
HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }

%region_0 { ... }
ENTRY %main {
  %ar0 = f32[] all-reduce(f32[] %loss), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region_0, metadata={op_name="jit(step)/pmean"}
  %ar1 = bf16[4096,1001]{1,0} all-reduce(bf16[4096,1001]{1,0} %g), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region_0, metadata={op_name="jit(step)/grads"}
  %ar2 = f32[1024]{0} all-reduce-start(f32[1024]{0} %h), replica_groups={{0,1,2,3},{4,5,6,7}}, metadata={op_name="jit(step)/while/body/hook"}
  %cc = f32[8]{0} custom-call(f32[8]{0} %x), custom_call_target="TopK"
  %u = f32[16]{0} add(f32[16]{0} %a, f32[16]{0} %b), metadata={op_name="jit(step)/optimizer_apply/add"}
}
"""


def test_extract_contract_parses_hand_built_hlo():
  c = contracts.extract_contract(_FAKE_HLO, config={"model": "fake"})
  kinds = [(x.kind, x.dtype, x.scalar, x.in_loop) for x in c.collectives]
  assert ("all-reduce", "f32", True, False) in kinds
  assert ("all-reduce", "bf16", False, False) in kinds
  assert ("all-reduce", "f32", False, True) in kinds  # the -start in-loop
  assert len(c.collectives) == 3
  grads = c.gradient_collectives()
  assert {g.dtype for g in grads} == {"bf16", "f32"}
  assert c.donated_buffers == 2
  assert c.optimizer_apply_present and not c.optimizer_apply_in_loop
  assert "TopK" in c.custom_call_targets
  assert not c.host_transfers
  # 4096*1001 bf16 is the biggest array in the dump.
  assert c.largest_tensor_type == "bf16[4096,1001]"
  assert c.largest_tensor_bytes == 4096 * 1001 * 2
  # Partial replica groups survive extraction (the full-mesh rule
  # keys on them).
  assert any(x.replica_groups == "{{0,1,2,3},{4,5,6,7}}"
             for x in c.collectives)


def test_requested_wire_parser():
  txt = ('x = "stablehlo.all_reduce"(%1) ({\n^bb0: ...\n})'
         ' : (tensor<4101097xbf16>) -> tensor<4101097xbf16>\n'
         'y = "stablehlo.all_reduce"(%2) ({\n})'
         ' : (tensor<f32>) -> tensor<f32>\n')
  wires = contracts.requested_all_reduce_wires(txt)
  assert ("bf16", 4101097) in wires and ("f32", 1) in wires


# -- pure-unit: seeded program with an extra in-scan psum ---------------------

def test_injected_in_scan_psum_is_placed_in_loop_and_rejected():
  """The end-to-end seed: a step-shaped program with a pmean inside a
  lax.scan body. The extractor must place the collective in-loop, and
  the rule engine must reject it."""
  if len(jax.devices()) < 8:
    pytest.skip("needs the 8-device virtual CPU mesh")
  mesh = Mesh(np.array(jax.devices()[:8]), (REPLICA_AXIS,))

  def body(x):
    def step(carry, _):
      # The seeded violation: a collective inside the scan body.
      return carry + jax.lax.pmean(x.sum(), REPLICA_AXIS), None
    out, _ = jax.lax.scan(step, jnp.float32(0), None, length=4)
    return jax.lax.pmean(out, REPLICA_AXIS)

  fn = jax.jit(jax.shard_map(body, mesh=mesh,
                             in_specs=(P(REPLICA_AXIS),), out_specs=P()))
  hlo = fn.lower(jnp.zeros((8, 4))).compile().as_text()
  contract = contracts.extract_contract(hlo, config={})
  assert contract.in_loop_collectives(), "extractor missed the in-scan psum"
  violations = audit.audit_contract(
      contract, rules={"no-collective-in-loop":
                       audit.rule_no_collective_in_loop})
  assert [v.rule for v in violations] == ["no-collective-in-loop"]


# -- golden configs: the earned contracts hold across the lattice -------------

@pytest.mark.parametrize("name", list(contracts.GOLDEN_CONFIGS))
def test_golden_config_passes_all_rules(name, tracer):
  contract = tracer(contracts.GOLDEN_CONFIGS[name], "train_step")
  violations = audit.audit_contract(contract, tracer)
  assert not violations, [v.as_dict() for v in violations]


@pytest.mark.parametrize("name", list(contracts.GOLDEN_CONFIGS))
def test_golden_config_matches_checked_in_golden(name, tracer):
  contract = tracer(contracts.GOLDEN_CONFIGS[name], "train_step")
  diffs = baseline.check_against_golden(name, contract)
  assert not diffs, (
      "traced contract drifted from tests/golden_contracts/"
      f"{name}.json: {diffs} -- if intentional, regenerate via "
      "`python -m kf_benchmarks_tpu.analysis audit --write-goldens`")


def test_earned_contract_shapes(tracer):
  """The five earned contracts, spelled out against the traced goldens
  (redundant with the rules on purpose: if a rule rots, this still
  pins the shape)."""
  accum = tracer(contracts.GOLDEN_CONFIGS["accum4_packed"], "train_step")
  assert len(accum.gradient_collectives()) == 1
  assert not accum.in_loop_collectives()
  lm = tracer(contracts.GOLDEN_CONFIGS["lm_base"], "train_step")
  assert lm.largest_tensor_bytes < lm.aux["btv_bytes"]
  assert not lm.in_loop_collectives()
  bf16 = tracer(contracts.GOLDEN_CONFIGS["packed_bf16_wire"], "train_step")
  assert bf16.aux["requested_grad_wires"] == ["bf16"]
  assert accum.aux["requested_grad_wires"] == ["f32"]
  health = tracer(contracts.GOLDEN_CONFIGS["health"], "train_step")
  base = tracer(contracts.GOLDEN_CONFIGS["base"], "train_step")
  n = lambda c: sum(1 for x in c.collectives if x.kind == "all-reduce")
  assert n(health) <= n(base)


# -- mutation self-tests: each seed caught by EXACTLY the intended rule -------

def _add_collective(contract, **kw):
  spec = dict(kind="all-reduce", dtype="f32", elems=1 << 20, scalar=False,
              in_loop=False, replica_groups="")
  spec.update(kw)
  contract.collectives.append(Collective(**spec))


MUTATIONS = [
    ("extra_in_loop_psum", "base",
     lambda c: _add_collective(c, in_loop=True),
     "no-collective-in-loop"),
    ("extra_grad_collective_under_accum", "accum4_packed",
     lambda c: _add_collective(c),
     "accum-one-collective"),
    ("psum_inside_microbatch_scan", "accum4_packed",
     lambda c: _add_collective(c, in_loop=True),
     "accum-one-collective"),
    ("leaked_f32_wire", "packed_bf16_wire",
     lambda c: c.aux.update(requested_grad_wires=["bf16", "f32"]),
     "wire-dtype"),
    ("silent_bf16_downcast", "base",
     lambda c: c.aux.update(requested_grad_wires=["bf16"]),
     "wire-dtype"),
    ("materialized_btv_logits", "lm_base",
     lambda c: setattr(c, "largest_tensor_bytes", c.aux["btv_bytes"]),
     "no-btv-buffer"),
    # Two scalars: the health vector REPLACED two scalar loss pmeans,
    # so the health-on program legitimately runs one collective below
    # the stats-off twin; two extras break the <= bound unambiguously.
    ("health_extra_collective", "health",
     lambda c: (_add_collective(c, scalar=True, elems=1),
                _add_collective(c, scalar=True, elems=1)),
     "health-no-extra-collective"),
    ("lost_donation", "base",
     lambda c: setattr(c, "donated_buffers", 0),
     "state-donated"),
    ("optimizer_apply_in_scan", "base",
     lambda c: setattr(c, "optimizer_apply_in_loop", True),
     "single-optimizer-apply"),
    ("optimizer_apply_missing", "base",
     lambda c: setattr(c, "optimizer_apply_present", False),
     "single-optimizer-apply"),
    ("host_transfer_in_step", "base",
     lambda c: c.host_transfers.append("outfeed"),
     "no-host-transfer"),
    # ISSUE 25 seeds: every gradient leaf is reduced exactly once, in
    # the exchange scope's all-reduce OR on the factor data plane. The
    # classifier kernel of ``base`` takes the factor plane; all-reducing
    # it as well is the double reduction the step's one predicate rules
    # out (an unscoped extra all-reduce trips nothing on ``base``, see
    # traced_device_side_reduction: the scope is what this rule reads).
    ("factor_leaf_reduced_twice", "base",
     lambda c: _add_collective(c, elems=4096 * 1001, in_exchange=True),
     "gradient-reduced-once"),
    # ... and a claimed leaf whose gathers are gone is reduced by
    # neither plane: each replica would apply its local product.
    ("factor_gathers_lost", "base",
     lambda c: c.collectives.__setitem__(
         slice(None), [x for x in c.collectives
                       if x.kind != "all-gather"]),
     "gradient-reduced-once"),
    # The claim lost while the gathers stay: the all-reduced elements
    # no longer add up to the tree.
    ("factor_claim_lost", "base",
     lambda c: c.aux.update(factor_elems=0),
     "gradient-reduced-once"),
    ("partial_replica_groups", "base",
     lambda c: _add_collective(c, elems=1 << 20,
                               replica_groups="{{0,1,2,3},{4,5,6,7}}"),
     "full-mesh-replica-groups"),
    # PR 6 seeds. Replacing the scatter with a full all-reduce is the
    # exact regression --shard_optimizer_state exists to rule out: the
    # replicated exchange returns, and with it the 2(n-1)/n wire.
    ("full_all_reduce_instead_of_reduce_scatter", "sharded_base",
     lambda c: (c.collectives.__setitem__(
         slice(None),
         [x for x in c.collectives if x.kind != "reduce-scatter"]),
                _add_collective(c)),
     "sharded-collectives"),
    ("partial_reduce_scatter_groups", "sharded_base",
     lambda c: _add_collective(c, kind="reduce-scatter",
                               replica_groups="{{0,1,2,3},{4,5,6,7}}"),
     "sharded-collectives"),
    # Opt state silently re-replicated: per-device bytes jump from
    # ~|state|/n back to |state| (n x the shard) -- the ZeRO memory
    # claim is the thing being audited, not the collective mix.
    ("replicated_opt_state_leak", "sharded_base",
     lambda c: c.aux.update(
         opt_state_bytes_per_device=(
             c.aux["opt_state_bytes_per_device"] * c.aux["num_devices"])),
     "sharded-opt-bytes"),
    # PR 8 seeds. The packed vector pmean REPLACED two scalar loss
    # pmeans (one fewer all-reduce than the unpacked twin), so two
    # scalar extras break the kind-count bound unambiguously; scalars
    # stay out of gradient traffic, so only the count check fires.
    ("packed_extra_metric_collectives", "lm_packed",
     lambda c: (_add_collective(c, scalar=True, elems=1),
                _add_collective(c, scalar=True, elems=1)),
     "packed-no-overhead"),
    # A new GRADIENT collective lands exactly at the twin's all-reduce
    # count (17 + 1 == 18), so only the gradient-count half bites --
    # the packed path must not touch the gradient exchange.
    ("packed_gradient_exchange_drift", "lm_packed",
     lambda c: _add_collective(c),
     "packed-no-overhead"),
    # Losing the (B, T, V) bound aux silently unbinds rule_no_btv_buffer
    # on the packed program; the packed rule pins the aux's presence.
    ("packed_btv_aux_lost", "lm_packed",
     lambda c: c.aux.pop("btv_bytes"),
     "packed-no-overhead"),
    # PR 9 seed. A device-side reduction smuggled into the traced step
    # is the exact regression the host-only tracing contract rules
    # out: the trace-on fingerprint stops matching the trace-off twin.
    # (A top-level full-mesh-group-free f32 all-reduce trips no other
    # rule on the replicated base program, so exactly the twin rule
    # fires.)
    ("traced_device_side_reduction", "traced",
     lambda c: _add_collective(c),
     "trace-twin"),
    # PR 10 seeds (--shard_params). A single all-gather re-assembling
    # the whole parameter tree is params leaking back to replicated
    # residency -- the exact buffer FSDP exists to never materialize.
    # (Full-mesh groups, so sharded-collectives stays quiet; only the
    # residency rule may fire.)
    ("fsdp_full_tree_gather", "fsdp_base",
     lambda c: _add_collective(
         c, kind="all-gather",
         elems=c.aux["fsdp_param_full_bytes"] // 4 + 1,
         replica_groups="{{0,1,2,3,4,5,6,7}}"),
     "fsdp-residency"),
    # The round-11 trailing re-gather returns: extra bucket-sized
    # all-gathers beyond the planned step buckets mean the steady
    # state re-assembles params it should leave sharded.
    ("fsdp_trailing_regather_leak", "fsdp_base",
     lambda c: _add_collective(
         c, kind="all-gather", elems=4096,
         replica_groups="{{0,1,2,3,4,5,6,7}}"),
     "fsdp-residency"),
    # The scanned LM's per-block gather hoisted out of the scan body:
    # the whole layer stack would re-assemble at once.
    ("fsdp_block_gather_left_the_loop", "fsdp_lm",
     lambda c: c.collectives.__setitem__(
         slice(None), [x for x in c.collectives
                       if not (x.kind == "all-gather" and x.in_loop)]),
     "fsdp-residency"),
    # ISSUE 17 seeds. The twin referee is the ONE owner of gspmd
    # program shapes (every manual-shape rule stands down on
    # partitioner=gspmd contracts): a gradient collective seeded into
    # the microbatch scan on the gspmd side fires exactly the
    # referee's in-loop bug leg -- accum-one-collective and
    # no-collective-in-loop are gspmd-guarded off, so nothing else may
    # bite.
    ("gspmd_in_loop_gradient_collective", "gspmd_accum",
     lambda c: _add_collective(c, in_loop=True),
     "partitioner-twin"),
    # GSPMD re-materializing a buffer the manual program keeps
    # sharded: the largest-live-buffer > 2x-manual bound is the
    # referee's memory leg (the legitimate divergence classes stay
    # inside 2x by construction on the goldens).
    ("gspmd_buffer_blowup", "gspmd_sharded_base",
     lambda c: setattr(c, "largest_tensor_bytes",
                       c.largest_tensor_bytes * 20),
     "partitioner-twin"),
]


def test_audit_clean_on_4x2_model_axis_config(tracer):
  """A real model axis (M=2) must audit clean end-to-end: the metric
  pmeans legitimately span 4-wide batch groups (model peers hold
  identical scalars), which rule_full_mesh_replica_groups admits for
  sharded configs, and the opt-bytes twin drops --mesh_shape."""
  contract = tracer(dict(model="trivial", batch_size=4,
                         optimizer="momentum",
                         shard_optimizer_state=True, mesh_shape="4x2"),
                    "train_step")
  violations = audit.audit_contract(contract, tracer)
  assert not violations, [v.as_dict() for v in violations]
  sizes = {tuple(audit._group_sizes(c.replica_groups))
           for c in contract.collectives
           if c.kind == "all-reduce" and c.replica_groups}
  assert (4, 4) in sizes  # the batch-axis scalar pmeans, 2 groups of 4


def test_sharded_opt_bytes_twin_drops_mesh_shape():
  """The replicated twin of rule_sharded_opt_bytes must drop
  --mesh_shape along with --shard_optimizer_state: a model axis > 1 is
  only valid WITH sharded state (validation.py), so a twin keeping it
  would crash the audit of any documented 4x2 config."""
  contract = contracts.extract_contract(
      _FAKE_HLO, config=dict(model="trivial", optimizer="momentum",
                             shard_optimizer_state=True,
                             mesh_shape="4x2"))
  contract.aux.update(opt_state_bytes_per_device=100_000, num_devices=8)
  seen = []

  def stub_tracer(cfg, program):
    seen.append(dict(cfg))
    twin = contracts.extract_contract(_FAKE_HLO, config=dict(cfg))
    twin.aux["opt_state_bytes_per_device"] = 800_000
    return twin

  assert not audit.rule_sharded_opt_bytes(contract, stub_tracer)
  assert seen and "mesh_shape" not in seen[0]
  assert "shard_optimizer_state" not in seen[0]
  # And the bound itself still bites on the same twin.
  contract.aux["opt_state_bytes_per_device"] = 800_000
  assert audit.rule_sharded_opt_bytes(contract, stub_tracer)


@pytest.mark.parametrize("seed,config,mutate,expected",
                         MUTATIONS, ids=[m[0] for m in MUTATIONS])
def test_mutation_caught_by_exactly_the_intended_rule(
    seed, config, mutate, expected, tracer):
  contract = copy.deepcopy(tracer(contracts.GOLDEN_CONFIGS[config],
                                  "train_step"))
  # Clean before the seed...
  assert not audit.audit_contract(contract, tracer)
  mutate(contract)
  violations = audit.audit_contract(contract, tracer)
  fired = {v.rule for v in violations}
  assert fired == {expected}, (
      f"seed {seed!r}: expected exactly {{{expected!r}}}, got "
      f"{sorted(fired)}: {[v.as_dict() for v in violations]}")


# -- baseline: field-level golden diffs ---------------------------------------

def test_golden_diff_names_the_field(tracer):
  contract = tracer(contracts.GOLDEN_CONFIGS["base"], "train_step")
  fp = baseline.contract_fingerprint(contract)
  golden = json.loads(json.dumps(fp))  # deep copy
  golden["state_donated"] = False
  golden["collectives"][0]["count"] += 1
  diffs = baseline.diff_fingerprints(golden, fp)
  fields = {f for f, _, _ in diffs}
  assert "state_donated" in fields
  assert any(f.startswith("collectives[") and f.endswith(".count")
             for f in fields)
  assert len(diffs) == 2, diffs


def test_missing_golden_is_a_diff(tmp_path, monkeypatch):
  monkeypatch.setattr(baseline, "GOLDEN_DIR", str(tmp_path))
  contract = contracts.extract_contract(_FAKE_HLO, config={})
  diffs = baseline.check_against_golden("nope", contract)
  assert diffs and diffs[0][0] == "<golden file>"
  # write + re-check closes the loop
  baseline.write_golden("nope", contract)
  assert not baseline.check_against_golden("nope", contract)
