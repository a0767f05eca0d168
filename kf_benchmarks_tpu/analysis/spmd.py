"""Static SPMD divergence analyzer (ISSUE 20): deadlock-freedom checks
for the multi-host leg, run before any 2-process job touches hardware.

The classic multi-host failure mode is a cross-rank collective mismatch:
one rank issues an all-gather the others never reach and the job hangs
silently. The reference had exactly this class of bug in its
KungFu exit path (SURVEY 2.9, tf_cnn_benchmarks.py:58-60 barrier). The
existing audit checks collective *inventories* (unordered multisets);
two programs with identical inventories can still deadlock each other
when their *schedules* -- the rendezvous order -- differ. This pass has
two device-free legs (the third leg, the rank-divergence lint, is an
AST pass in analysis/lint.py):

* **Ordered schedules** (:func:`schedule_drift`): every golden contract
  now pins its ``collective_schedule`` (contracts.Collective
  .schedule_entry rows in compiled-dump definition order). When the
  schedule drifts while the inventory still matches, the audit fails
  with the exact regen command -- an inventory-equal reorder is
  precisely the silent class the old golden diff missed.
* **Cross-world-size agreement** (:func:`world_size_verdict`): every
  sharded golden config is traced at world sizes {2, 4, 8} on the
  virtual CPU mesh (checkpoint._reshard re-addresses the (n, k) shard
  stacks at ANY n', so these are all reachable elastic-rescale sizes)
  and the schedules must be identical modulo replica-group arity and
  commutation of scalar control reductions (:func:`schedule_diffs`:
  the tensor exchange chain compares as a strict sequence, scalar
  metric pmeans as a multiset -- their textual position floats).
  Divergences classify like audit.rule_partitioner_twin's referee:
  ``benign_arity`` (same sequence, groups differ only in width --
  the expected shape), ``documented`` (a gspmd-partitioned program:
  GSPMD legally re-plans the exchange per topology, sharding
  thresholds and divisibility change with n -- tabled, not failed),
  ``bug`` (a manual program whose rendezvous order changed with the
  world size -- the deadlock class; the only failing verdict).

Static tracing only: every trace goes through the audit's memoized
tracer (jit().lower().compile(); nothing executes). The serving
tensor-parallel twin is out of scope here -- its model mesh is pinned
by head-count divisibility (serving_decode_tp, M | n_heads), not by the
elastic world size _reshard ranges over.
"""

from __future__ import annotations

import os
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Tuple

from kf_benchmarks_tpu.analysis.contracts import (
    GOLDEN_CONFIGS, N_REPLICAS, ProgramContract)

# The exact command the schedule-drift failure names (an intentional
# program change regenerates the pinned schedules the same way every
# other golden field regenerates).
REGEN_COMMAND = "python -m kf_benchmarks_tpu.analysis audit --write-goldens"

# The elastic world sizes the agreement leg traces (all reachable:
# checkpoint._reshard re-addresses zero-padded row-major shard stacks
# at any n', and sharded_rescale's golden already pins n=4).
WORLD_SIZES = (2, 4, 8)


def schedule_key(entry: Dict[str, Any]) -> Tuple[str, str, str, str]:
  """The arity-free identity of one schedule row: everything two ranks
  must agree on for the collective to rendezvous. Group sizes are
  excluded -- they widen with the world size by construction -- and the
  index is the row's list position."""
  return (entry["kind"], entry["dtype"], entry["rank"],
          entry["placement"])


def normalize_schedule(schedule: List[Dict[str, Any]]
                       ) -> List[Tuple[str, str, str, str]]:
  """A schedule modulo replica-group arity (see :func:`schedule_key`)."""
  return [schedule_key(e) for e in schedule]


def schedule_diffs(ref: List[Dict[str, Any]],
                   other: List[Dict[str, Any]]) -> List[str]:
  """Human-readable divergences of two schedules modulo group arity
  AND modulo commutation of scalar control reductions; empty when they
  agree.

  TENSOR collectives (the gradient/param exchange chain) compare as a
  strict sequence: they are data-dependent on each other, so their
  order IS the rendezvous order -- a reorder is the deadlock class.
  SCALAR collectives (loss/metric pmeans) compare as a multiset: a
  scalar reduction is data-independent of the exchange chain, so its
  HLO textual position legally floats with the topology (measured:
  sharded_base's loss pmean prints at position 0 for n=8 and position
  2 for n=2 around a bit-identical exchange) -- textual definition
  order is a DAG print order, not an execution order, for independent
  ops."""
  na, nb = normalize_schedule(ref), normalize_schedule(other)
  ta = [r for r in na if r[2] == "tensor"]
  tb = [r for r in nb if r[2] == "tensor"]
  sa = Counter(r for r in na if r[2] == "scalar")
  sb = Counter(r for r in nb if r[2] == "scalar")
  if ta == tb and sa == sb:
    return []
  out = []
  if ta != tb:
    if len(ta) != len(tb):
      out.append(f"tensor-collective sequence length {len(ta)} vs "
                 f"{len(tb)}")
    for i, (a, b) in enumerate(zip(ta, tb)):
      if a != b:
        out.append(f"first tensor-sequence divergence at position {i}: "
                   f"{'/'.join(a)} vs {'/'.join(b)}")
        break
    else:
      i = min(len(ta), len(tb))
      longer = ta if len(ta) > len(tb) else tb
      if i < len(longer):
        out.append(f"first tensor-sequence divergence at position {i}: "
                   f"trailing {'/'.join(longer[i])} on one side only")
  for row in sorted(set(sa) | set(sb)):
    if sa[row] != sb[row]:
      out.append(f"scalar collective {'/'.join(row)} count "
                 f"{sa[row]} vs {sb[row]}")
  return out


# -- leg (a): ordered-schedule drift vs the golden ----------------------------

def schedule_drift(name: str, contract: ProgramContract) -> List[str]:
  """Schedule drift the inventory diff cannot see: the golden's
  unordered collective inventory still matches, but the ORDERED
  ``collective_schedule`` differs (a reorder, or a same-row swap
  between loop bodies). Returns failure messages naming the exact
  regen command; empty when the schedule holds, when the golden is
  missing (the whole-file diff owns that), or when the inventory
  itself drifted (the field-level golden diff owns that)."""
  from kf_benchmarks_tpu.analysis import baseline

  if not os.path.exists(baseline.golden_path(name)):
    return []
  golden = baseline.load_golden(name)
  current = baseline.contract_fingerprint(contract)
  if golden.get("collectives") != current.get("collectives"):
    return []
  g_sched = golden.get("collective_schedule")
  if g_sched is None:
    return [f"golden '{name}' predates the collective_schedule field -- "
            f"regenerate the goldens: {REGEN_COMMAND}"]
  c_sched = current["collective_schedule"]
  if g_sched == c_sched:
    return []
  where = schedule_diffs(g_sched, c_sched) or ["group arity changed at "
                                               "a fixed topology"]
  for i, (g, c) in enumerate(zip(g_sched, c_sched)):
    if g != c:
      where.append(f"golden[{i}]={g} current[{i}]={c}")
      break
  return [("ordered collective schedule drifted while the inventory "
           f"matched ({'; '.join(where)}) -- an inventory-equal reorder "
           "can still deadlock ranks cross-host; if the change is "
           f"intentional, regenerate: {REGEN_COMMAND}")]


# -- leg (b): cross-world-size agreement --------------------------------------

def sharded_world_size_configs(
    configs: Optional[Dict[str, Dict[str, Any]]] = None
    ) -> Dict[str, Dict[str, Any]]:
  """The golden configs the agreement leg binds on: every sharded
  train config (--shard_optimizer_state; the elastic/multi-host
  family _reshard re-addresses)."""
  configs = GOLDEN_CONFIGS if configs is None else configs
  return {name: dict(cfg) for name, cfg in configs.items()
          if cfg.get("shard_optimizer_state")}


def world_size_verdict(name: str, overrides: Dict[str, Any],
                       tracer: Callable,
                       sizes: Tuple[int, ...] = WORLD_SIZES
                       ) -> Dict[str, Any]:
  """Trace ``overrides`` at every world size; compare the schedules
  modulo group arity against the config's own (golden) size; classify
  (see module docstring). ``tracer(overrides, program)`` is the
  audit's memoized tracer, so the golden size costs nothing extra."""
  own = int(overrides.get("num_devices", N_REPLICAS))
  all_sizes = sorted(set(int(s) for s in sizes) | {own})
  schedules: Dict[int, List[Dict[str, Any]]] = {}
  for s in all_sizes:
    cfg = dict(overrides)
    cfg["num_devices"] = s
    schedules[s] = tracer(cfg, "train_step").collective_schedule()
  ref = schedules[own]
  diffs: List[Dict[str, Any]] = []
  arity_differs = False
  for s in all_sizes:
    if s == own:
      continue
    d = schedule_diffs(ref, schedules[s])
    if d:
      diffs.append({"size": s, "diffs": d})
    elif ([e["group_sizes"] for e in ref] !=
          [e["group_sizes"] for e in schedules[s]]):
      arity_differs = True
  gspmd = overrides.get("partitioner") == "gspmd"
  note = ""
  if diffs and gspmd:
    classification = "documented"
    note = ("GSPMD re-plans the exchange per topology (sharding "
            "divisibility changes with n) -- the documented "
            "reassociation class; tabled, not failed")
  elif diffs:
    classification = "bug"
  elif arity_differs:
    classification = "benign_arity"
  else:
    classification = "agree"
  return {
      "config": name,
      "sizes": all_sizes,
      "golden_size": own,
      "schedule_lengths": {str(s): len(schedules[s]) for s in all_sizes},
      "classification": classification,
      "diffs": diffs,
      "note": note,
  }


def world_size_violations(verdict: Dict[str, Any]) -> List[str]:
  """The failing messages of one verdict: only the ``bug`` class --
  a manual program whose rendezvous order changed with the world size
  is the deadlock class no partitioner choice explains."""
  if verdict["classification"] != "bug":
    return []
  out = []
  for d in verdict["diffs"]:
    out.append(
        f"collective schedule at world size {d['size']} diverges from "
        f"the golden size {verdict['golden_size']} "
        f"({'; '.join(d['diffs'])}) -- ranks lowered at different "
        "world sizes would not rendezvous (the multi-host deadlock "
        "class); the manual partitioner's schedule must be invariant "
        "modulo group arity")
  return out


def audit_world_sizes(configs: Dict[str, Dict[str, Any]],
                      tracer: Callable,
                      sizes: Tuple[int, ...] = WORLD_SIZES
                      ) -> Dict[str, Any]:
  """Run the agreement leg over ``configs``; returns the report block
  the CLI embeds under ``spmd.world_size`` (per-config verdicts +
  the flat failing messages)."""
  verdicts, violations = {}, []
  for name, overrides in configs.items():
    verdict = world_size_verdict(name, overrides, tracer, sizes)
    verdicts[name] = verdict
    for msg in world_size_violations(verdict):
      violations.append({"config": name, "message": msg})
  return {"verdicts": verdicts, "violations": violations}
