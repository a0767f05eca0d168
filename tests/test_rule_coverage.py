"""Rule-coverage meta-audit (ISSUE 20 satellites 1 and 3).

  * meta-test: every rule id registered in the RULES dicts of
    analysis/lint.py and analysis/audit.py appears as a quoted literal
    in at least one tests/test_*.py -- a rule nobody ever observed
    firing is a rule whose seeded-violation test was forgotten. The
    ids are read from the source ASTs, so adding a rule without a test
    fails HERE, not in review.
  * seeded one-owner conflict: a second OWNERSHIP row claiming an
    already-owned property makes ``rule_one_owner`` fail naming BOTH
    rules and the contested property (and the unmodified table is
    conflict-free on the same shapes).
  * seeded metrics-twin divergence: a metrics-on program whose
    metrics-off twin is structurally different fires the host-only
    rule (previously the one registered rule with no observing test --
    exactly the rot the meta-test exists to stop).
"""

import ast
import os

import pytest

from kf_benchmarks_tpu.analysis import audit, contracts
from kf_benchmarks_tpu.analysis.contracts import Collective

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.join(REPO, "tests")
RULE_SOURCES = ("kf_benchmarks_tpu/analysis/lint.py",
                "kf_benchmarks_tpu/analysis/audit.py")


def _registered_rule_ids(rel):
  """The string keys of the module's ``RULES`` dict, from the AST
  (handles both ``RULES = {...}`` and ``RULES: Dict[...] = {...}``)."""
  tree = ast.parse(open(os.path.join(REPO, rel)).read())
  for node in ast.walk(tree):
    if isinstance(node, ast.Assign) and len(node.targets) == 1:
      target = node.targets[0]
    elif isinstance(node, ast.AnnAssign):
      target = node.target
    else:
      continue
    if (isinstance(target, ast.Name) and target.id == "RULES"
        and isinstance(node.value, ast.Dict)):
      keys = [k.value for k in node.value.keys
              if isinstance(k, ast.Constant)]
      assert len(keys) == len(node.value.keys), f"non-literal key in {rel}"
      return keys
  raise AssertionError(f"no RULES dict found in {rel}")


def test_every_registered_rule_has_an_observing_test():
  quoted_anywhere = {}
  test_files = sorted(f for f in os.listdir(TESTS_DIR)
                      if f.startswith("test_") and f.endswith(".py"))
  texts = {f: open(os.path.join(TESTS_DIR, f)).read() for f in test_files}
  missing = []
  for rel in RULE_SOURCES:
    ids = _registered_rule_ids(rel)
    assert ids, rel
    for rid in ids:
      hits = [f for f, text in texts.items()
              if f'"{rid}"' in text or f"'{rid}'" in text]
      quoted_anywhere[rid] = hits
      if not hits:
        missing.append(f"{rel}: rule '{rid}' is registered but no "
                       "tests/test_*.py quotes it")
  assert not missing, "\n".join(missing)
  # Sanity: the extraction really sees both registries.
  assert "version-gate-comment" in quoted_anywhere  # lint.py
  assert "trace-twin" in quoted_anywhere         # audit.py


# -- seeded one-owner conflict (satellite 1) ----------------------------------

def _contract(program="train_step", config=None, aux=None,
              collectives=()):
  c = contracts.ProgramContract(
      config=dict(config or {}), program=program,
      collectives=list(collectives), host_transfers=[],
      custom_call_targets=[], optimizer_apply_present=True,
      optimizer_apply_in_loop=False, donated_buffers=1,
      largest_tensor_bytes=0, largest_tensor_type="", temp_bytes=None)
  c.aux.update(aux or {})
  return c


def test_one_owner_clean_on_the_untouched_table():
  # The real OWNERSHIP table: a plain decode program is owned by
  # serving-bounded-decode alone on both its properties.
  assert audit.rule_one_owner(_contract(program="serving_decode"),
                              tracer=None) == []
  assert audit.rule_one_owner(_contract(program="train_step"),
                              tracer=None) == []


def test_one_owner_conflict_names_both_rules(monkeypatch):
  conflicted = audit.OWNERSHIP + [
      ("state-donated", "decode-buffer-bound",
       lambda c: c.program == "serving_decode"),
  ]
  monkeypatch.setattr(audit, "OWNERSHIP", conflicted)
  msgs = audit.rule_one_owner(_contract(program="serving_decode"),
                              tracer=None)
  assert len(msgs) == 1
  assert "decode-buffer-bound" in msgs[0]
  assert "serving-bounded-decode" in msgs[0]
  assert "state-donated" in msgs[0]
  # ...while a shape the bad row does not bind keeps passing.
  assert audit.rule_one_owner(_contract(program="train_step"),
                              tracer=None) == []


def test_one_owner_runs_as_a_registered_rule(monkeypatch):
  """The conflict surfaces through the ordinary audit driver (it is a
  RULES entry, not a separate pass)."""
  assert audit.RULES["one-owner"] is audit.rule_one_owner
  monkeypatch.setattr(audit, "OWNERSHIP", audit.OWNERSHIP + [
      ("state-donated", "decode-buffer-bound",
       lambda c: c.program == "serving_decode")])
  violations = audit.audit_contract(_contract(program="serving_decode"))
  assert any(v.rule == "one-owner" for v in violations)


# -- seeded metrics-twin divergence (satellite 3) -----------------------------

def _ar(scalar=False):
  return Collective(kind="all-reduce", dtype="f32", elems=1 << 10,
                    scalar=scalar, in_loop=False, replica_groups="")


def test_metrics_twin_fires_on_structural_divergence():
  on = _contract(config={"model": "x", "metrics_port": 9090},
                 collectives=[_ar(), _ar(scalar=True)])

  def tracer(cfg, program="train_step"):
    assert "metrics_port" not in cfg
    return _contract(config=cfg, collectives=[_ar(scalar=True)])

  msgs = audit.RULES["metrics-twin"](on, tracer)
  assert msgs and any("host-only" in m for m in msgs)


def test_metrics_twin_clean_when_twins_agree():
  on = _contract(config={"model": "x", "metrics_port": 9090},
                 collectives=[_ar()])

  def tracer(cfg, program="train_step"):
    return _contract(config=cfg, collectives=[_ar()])

  assert audit.rule_metrics_twin(on, tracer) == []
  # No metrics config at all: the rule stands down without a trace.
  off = _contract(config={"model": "x"}, collectives=[_ar()])
  assert audit.rule_metrics_twin(off, tracer=None) == []
