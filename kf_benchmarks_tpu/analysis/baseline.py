"""Golden program contracts: serialize, load, diff.

``tests/golden_contracts/<name>.json`` pins the STRUCTURAL contract of
each golden config (collective inventory by kind/dtype/placement,
donation, optimizer-apply scope, host transfers) so a regression --
a duplicated pmean, a dtype drift, a collective sliding out of the
backward loop -- fails with a field-level diff instead of a silent
perf cliff on the serialized TPU chip.

Volatile statics (buffer sizes, custom-call targets, temp totals) stay
OUT of the goldens: they move with the XLA version, and the memory
contracts are enforced as rules (audit.rule_no_btv_buffer) against
bounds derived from the config, not pinned bytes.
"""

from __future__ import annotations

import hashlib
import json
import os
from collections import Counter
from typing import Any, Dict, List, Tuple

from kf_benchmarks_tpu.analysis.contracts import ProgramContract

GOLDEN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tests", "golden_contracts")


def contract_fingerprint(contract: ProgramContract) -> Dict[str, Any]:
  """The stable, golden-worthy subset of a contract."""
  inventory = Counter(
      (c.kind, c.dtype, "scalar" if c.scalar else "tensor",
       "in_loop" if c.in_loop else "top_level")
      for c in contract.collectives)
  return {
      "config": dict(contract.config),
      "program": contract.program,
      "collectives": _sorted_collectives(
          {"kind": k, "dtype": d, "rank": r, "placement": p, "count": n}
          for (k, d, r, p), n in inventory.items()),
      # The ORDERED schedule (ISSUE 20): definition-order rows with
      # group ARITY only (member ids are topology labels). Two ranks
      # whose programs agree on the inventory above but not on this
      # sequence can still deadlock each other -- analysis/spmd.py
      # fails schedule drift with the exact regen command.
      "collective_schedule": contract.collective_schedule(),
      "gradient_collectives": len(contract.gradient_collectives()),
      "in_loop_collectives": len(contract.in_loop_collectives()),
      "host_transfers": list(contract.host_transfers),
      "optimizer_apply_present": contract.optimizer_apply_present,
      "optimizer_apply_in_loop": contract.optimizer_apply_in_loop,
      "state_donated": contract.donated_buffers > 0,
      # Lowered-level gradient wire dtypes (the TPU wire; see
      # contracts.requested_all_reduce_wires).
      "requested_grad_wires": contract.aux.get("requested_grad_wires"),
      # Sharded-path collective wires (reduce-scatter/all-gather mix of
      # --shard_optimizer_state programs; None elsewhere).
      "requested_collective_wires": contract.aux.get(
          "requested_collective_wires"),
  }


def _sorted_collectives(entries):
  return sorted(entries, key=lambda e: json.dumps(e, sort_keys=True))


# Param fields that do NOT shape the compiled step program: artifact
# sinks, cadences, and host-side-only observability/launcher knobs.
# Excluded from the program-shape fingerprint so the compile ledger
# (tracing.py) -- and the persistent compile cache it is groundwork for
# (ROADMAP item 5) -- is not fragmented by paths and cadences that
# change every run. Fields that DO reach the traced program (model,
# batch, mesh, reducers, dtypes, accumulation, ...) all stay in.
PROGRAM_SHAPE_EXCLUDE = frozenset({
    "train_dir", "data_dir", "eval_dir", "benchmark_log_dir",
    "benchmark_test_id", "trace_file", "trace_events_file",
    "tfprof_file", "graph_file", "partitioned_graph_file_prefix",
    "aot_save_path", "aot_load_path", "backbone_model_path",
    "compilation_cache_dir",
    "use_chrome_trace_format", "display_every", "save_model_secs",
    "save_model_steps", "save_summaries_steps", "summary_verbosity",
    "max_ckpts_to_keep", "eval_interval_secs",
    "flight_recorder_window", "health_grad_norm_sigma",
    "stall_watchdog_factor", "fault_schedule",
    "elastic_check_every_n_steps", "sync_on_finish",
    "metrics_port", "run_store_dir",
    # The tuned-table PATH (--autotuned_config) is plumbing, not a
    # program shape: the knobs a table APPLIES are ordinary
    # program-shaping params (TUNED_KNOBS below) and land in the
    # fingerprint through their own fields, so a tuned run and a
    # default run can never share a fingerprint -- but WHICH file the
    # values came from must not fragment the key corpus.
    "autotuned_config",
})

# The program-shaping knobs the autotuner (analysis/autotune.py)
# searches. Deliberately NOT in PROGRAM_SHAPE_EXCLUDE: each one changes
# the compiled program or its dispatch schedule, so two runs that
# differ in a tuned knob must key differently in the run store /
# compile ledger (tests/test_autotune.py pins each knob's effect on
# config_fingerprint_key). The autotuner strips exactly this set (plus
# the run-length counters below) to derive the table key a tuned and a
# default run of the same base config share.
TUNED_KNOBS = (
    "steps_per_dispatch",
    "num_grad_accum",
    "reduce_bucket_mb",
    "input_prefetch_depth",
    "attn_block",
    # Round 20: who inserts the sharded step's collectives -- None/
    # "manual" (hand-written shard_map programs) or "gspmd" (plain jit
    # + NamedShardings, XLA SPMD chooses). The one string-valued knob:
    # the table validator admits {"manual","gspmd"} for it only.
    "partitioner",
)

# Run-length counters: in the full fingerprint (the LR schedule can
# embed the total step count as a program constant), but OUT of the
# tuned-table base key -- a table tuned at one sweep length must apply
# to production runs of any length.
_RUN_LENGTH_FIELDS = ("num_batches", "num_warmup_batches", "num_epochs")


def fingerprint_key(payload: Dict[str, Any]) -> str:
  """Short stable key of a canonical-JSON payload (sha256 hex, 16
  chars) -- the identity scheme the compile ledger shares with the
  golden fingerprints."""
  canon = json.dumps(payload, sort_keys=True, default=str)
  return hashlib.sha256(canon.encode("utf-8")).hexdigest()[:16]


def _canonical_value(v):
  """Numeric canonicalization for fingerprinting: an integral float
  keys as its int. The CLI parser materializes float flags as 0.0
  where ``make_params`` keeps a registry-literal 0 -- Python-equal,
  canonical-JSON-different -- and both shape the SAME program, so a
  CLI run and a library run of one config must share a fingerprint
  (found when the tuned-table lookup missed from the CLI; the same
  split silently fragmented the compile ledger). Bools pass through
  (they are typed consistently on both paths)."""
  if isinstance(v, float) and not isinstance(v, bool) and \
      v.is_integer():
    return int(v)
  return v


def config_fingerprint_key(config: Dict[str, Any],
                           program: str = "train_step") -> str:
  """The program-shape fingerprint key a compile episode is ledgered
  under (tracing.py note_compile): the param fields that shape the
  compiled program, plus the program name and the jax version (an XLA
  upgrade recompiles everything, so a persistent cache must key on
  it). Call it with the full ``params._asdict()`` (the ledger
  convention: two runs key equal iff every program-shaping field --
  explicit or default -- agrees); None values and the excluded
  host-side fields drop out first, and integral floats key as ints
  (see :func:`_canonical_value`)."""
  shape = {k: _canonical_value(v) for k, v in config.items()
           if v is not None and k not in PROGRAM_SHAPE_EXCLUDE}
  try:
    import jax
    jax_version = jax.__version__
  except Exception:  # pure-stdlib caller (lint harness)
    jax_version = ""
  return fingerprint_key({"config": shape, "program": program,
                          "jax": jax_version})


def base_fingerprint_key(config: Dict[str, Any],
                         program: str = "train_step") -> str:
  """The tuned-table key: :func:`config_fingerprint_key` of ``config``
  with the tuned knobs (TUNED_KNOBS) and the run-length counters
  stripped first -- the identity a default run, a tuned run, and the
  table entry that tuned it all share. Call it with the full
  ``params._asdict()`` at the MAKE_PARAMS level (before BenchmarkCNN's
  auto-resolutions -- e.g. the --health_stats auto bool -- mutate the
  dict): the table is consulted at startup, so its keys live on the
  pre-resolution config, unlike the compile ledger's resolved keys."""
  stripped = {k: v for k, v in config.items()
              if k not in TUNED_KNOBS and k not in _RUN_LENGTH_FIELDS}
  return config_fingerprint_key(stripped, program)


def diff_fingerprints(golden: Dict[str, Any], current: Dict[str, Any]
                      ) -> List[Tuple[str, Any, Any]]:
  """Field-level diff: [(field, golden_value, current_value), ...].

  Collective inventories diff per-entry so the report names the exact
  (kind, dtype, placement) row that changed count; the ordered
  collective_schedule diffs at the first divergent position (plus a
  length row) instead of dumping both full sequences."""
  diffs = []
  keys = sorted(set(golden) | set(current))
  for key in keys:
    g, c = golden.get(key), current.get(key)
    if key == "collective_schedule":
      g_rows, c_rows = list(g or []), list(c or [])
      if g_rows == c_rows:
        continue
      if len(g_rows) != len(c_rows):
        diffs.append(("collective_schedule.length",
                      len(g_rows), len(c_rows)))
      for i, (gr, cr) in enumerate(zip(g_rows, c_rows)):
        if gr != cr:
          diffs.append((f"collective_schedule[{i}]", gr, cr))
          break
    elif key == "collectives":
      g_rows = {json.dumps({k: v for k, v in e.items() if k != "count"},
                           sort_keys=True): e.get("count")
                for e in (g or [])}
      c_rows = {json.dumps({k: v for k, v in e.items() if k != "count"},
                           sort_keys=True): e.get("count")
                for e in (c or [])}
      for row in sorted(set(g_rows) | set(c_rows)):
        if g_rows.get(row) != c_rows.get(row):
          diffs.append((f"collectives[{row}].count",
                        g_rows.get(row), c_rows.get(row)))
    elif g != c:
      diffs.append((key, g, c))
  return diffs


def golden_path(name: str) -> str:
  return os.path.join(GOLDEN_DIR, f"{name}.json")


def load_golden(name: str) -> Dict[str, Any]:
  with open(golden_path(name), encoding="utf-8") as f:
    return json.load(f)


def write_golden(name: str, contract: ProgramContract) -> str:
  os.makedirs(GOLDEN_DIR, exist_ok=True)
  path = golden_path(name)
  with open(path, "w", encoding="utf-8") as f:
    json.dump(contract_fingerprint(contract), f, indent=2, sort_keys=True)
    f.write("\n")
  return path


def check_against_golden(name: str, contract: ProgramContract
                         ) -> List[Tuple[str, Any, Any]]:
  """Diff a traced contract against its checked-in golden; a missing
  golden is itself a (whole-file) diff."""
  path = golden_path(name)
  if not os.path.exists(path):
    return [("<golden file>", "missing", path)]
  return diff_fingerprints(load_golden(name), contract_fingerprint(contract))
