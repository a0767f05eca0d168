"""CLI: ``python -m kf_benchmarks_tpu.analysis
[lint|audit|autotune|warm|all]``.

``lint``/``audit`` are CPU-only and device-free: the audit lowers+
compiles step programs on an 8-virtual-device host mesh (same recipe
as tests/conftest.py) and never executes one; the lint is a pure AST
pass. The audit additionally validates any tuned-config table it finds
(the repo-root table, or ``--table``) against the knob registry --
the ``run_tests.py --audit`` tuned-table leg. Exit status is nonzero
on any lint violation, audit-rule violation, golden diff or
tuned-table problem (stale-jax-version entries are warnings only).

``autotune`` runs the contract-driven knob search (autotune.py:
prune -> rank -> probe) for the named models on the virtual CPU mesh
and writes a tuned-config table; ``--dry-run`` stops after the static
stages (candidates compile but never execute -- the CPU-only CI
rehearsal). ``warm`` precompiles every (tuned-table x ledger) shape of
a train_dir into the persistent XLA cache (placed by
benchmark.configure_compile_cache: JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache on the chip; a CPU rehearsal needs the env set).

    python -m kf_benchmarks_tpu.analysis              # lint + audit
    python -m kf_benchmarks_tpu.analysis lint
    python -m kf_benchmarks_tpu.analysis audit [--configs a,b] [--json F]
    python -m kf_benchmarks_tpu.analysis audit --write-goldens
    python -m kf_benchmarks_tpu.analysis autotune --models trivial,lenet \
        --batch_size 4 --out tuned_configs.json [--dry-run]
    python -m kf_benchmarks_tpu.analysis warm --train_dir D [--table T]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _force_virtual_cpu_mesh() -> None:
  """The conftest recipe (tests/conftest.py): XLA_FLAGS must carry the
  host-device count before the backend initializes; the platform is
  then selected through jax.config."""
  xla_flags = os.environ.get("XLA_FLAGS", "")
  if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
  import jax
  jax.config.update("jax_platforms", "cpu")


def run_lint(args) -> int:
  from kf_benchmarks_tpu.analysis import lint
  return lint.main(["--rules", args.rules] if args.rules else [])


def run_tuned_table_audit(args) -> int:
  """The tuned-table schema leg: validate every table in sight (the
  committed repo-root table plus --table) against the knob registry,
  re-derive every entry's fingerprint, flag stale-jax entries."""
  from kf_benchmarks_tpu.analysis import autotune

  paths = []
  if getattr(args, "table", None):
    paths.append(args.table)
  default = os.path.join(REPO_ROOT, autotune.TABLE_FILENAME)
  if os.path.exists(default) and default not in paths:
    paths.append(default)
  n_problems = n_warnings = 0
  for path in paths:
    try:
      table = autotune.load_table(path)
    except autotune.AutotuneError as e:
      print(f"TUNED-TABLE PROBLEM [{path}] {e}")
      n_problems += 1
      continue
    problems, warnings = autotune.validate_table(table)
    for p in problems:
      print(f"TUNED-TABLE PROBLEM [{path}] {p}")
    for w in warnings:
      print(f"tuned-table warning [{path}] {w}")
    n_problems += len(problems)
    n_warnings += len(warnings)
  print(f"tuned-table audit: {n_problems} problem(s), {n_warnings} "
        f"warning(s) across {len(paths)} table(s)")
  return 1 if n_problems else 0


def run_audit(args) -> int:
  _force_virtual_cpu_mesh()
  from kf_benchmarks_tpu.analysis import audit, baseline, contracts

  known = dict(contracts.GOLDEN_CONFIGS)
  known.update(contracts.SERVING_GOLDEN_CONFIGS)
  names = (args.configs.split(",") if args.configs else list(known))
  unknown = [n for n in names if n not in known]
  if unknown:
    print(f"unknown golden config(s): {unknown}; have {list(known)}")
    return 2

  train_names = [n for n in names if n in contracts.GOLDEN_CONFIGS]
  serving_names = [n for n in names
                   if n in contracts.SERVING_GOLDEN_CONFIGS]
  configs = {n: contracts.GOLDEN_CONFIGS[n] for n in train_names}
  tracer = audit.make_memo_tracer()
  report = audit.audit_configs(configs, tracer=tracer)

  # Serving-path contracts: traced through their own lowering recipe
  # (the engine's AOT decode program), audited by the same rule engine.
  serving_contracts = {}
  for name in serving_names:
    cfg = dict(contracts.SERVING_GOLDEN_CONFIGS[name])
    program = cfg.get("program", "serving_decode")
    contract = tracer(cfg, program)
    serving_contracts[name] = contract
    violations = audit.audit_contract(contract, tracer)
    report["configs"][name] = {
        "config": dict(contracts.SERVING_GOLDEN_CONFIGS[name]),
        "violations": [v.as_dict() for v in violations],
        "collectives": len(contract.collectives),
        "in_loop_collectives": len(contract.in_loop_collectives()),
        "gradient_collectives": len(contract.gradient_collectives()),
    }
    twin_cfg = audit._twin_manual_config(contract)
    if twin_cfg is not None:
      report["configs"][name]["partitioner_twin"] = (
          audit.partitioner_twin_verdict(
              contract, tracer(twin_cfg, contract.program)))
    report["violations"] += len(violations)

  diff_total = 0
  for name in names:
    contract = (serving_contracts[name] if name in serving_contracts
                else tracer(configs[name], "train_step"))
    if args.write_goldens:
      path = baseline.write_golden(name, contract)
      print(f"golden written: {path}")
      continue
    diffs = baseline.check_against_golden(name, contract)
    report["configs"][name]["golden_diffs"] = [
        {"field": f, "golden": g, "current": c} for f, g, c in diffs]
    diff_total += len(diffs)
    for f, g, c in diffs:
      print(f"GOLDEN DIFF [{name}] {f}: golden={g!r} current={c!r}")

  # Fourth audit family (ISSUE 20): the SPMD divergence analyzer
  # (analysis/spmd.py) -- ordered-schedule drift the inventory diff
  # cannot see, plus cross-world-size schedule agreement for every
  # sharded golden config ({2,4,8} on the same memoized tracer; only
  # the `bug` class fails, the gspmd twins table as `documented`).
  spmd_total = 0
  if not args.write_goldens:
    from kf_benchmarks_tpu.analysis import spmd
    drift = []
    for name in names:
      contract = (serving_contracts[name] if name in serving_contracts
                  else tracer(configs[name], "train_step"))
      for msg in spmd.schedule_drift(name, contract):
        drift.append({"config": name, "message": msg})
        print(f"SPMD SCHEDULE DRIFT [{name}] {msg}")
    ws = spmd.audit_world_sizes(
        spmd.sharded_world_size_configs(configs), tracer)
    for name, verdict in sorted(ws["verdicts"].items()):
      print(f"spmd world-size [{name}] sizes={verdict['sizes']}: "
            f"{verdict['classification']}")
    for v in ws["violations"]:
      print(f"SPMD DIVERGENCE [{v['config']}] {v['message']}")
    report["spmd"] = {"schedule_drift": drift, "world_size": ws}
    spmd_total = len(drift) + len(ws["violations"])
    print(f"spmd audit: {len(drift)} schedule drift(s), "
          f"{len(ws['violations'])} world-size divergence(s) across "
          f"{len(ws['verdicts'])} sharded config(s)")

  for name, entry in report["configs"].items():
    for v in entry["violations"]:
      print(f"CONTRACT VIOLATION [{name}] [{v['rule']}] {v['message']}")
    status = ("OK" if not entry["violations"]
              and not entry.get("golden_diffs") else "FAIL")
    print(f"audit [{name}]: {status} ({entry['collectives']} collectives, "
          f"{entry['gradient_collectives']} gradient, "
          f"{entry['in_loop_collectives']} in-loop)")

  if args.json:
    with open(args.json, "w", encoding="utf-8") as f:
      json.dump(report, f, indent=2, sort_keys=True)
    print(f"report written: {args.json}")
  print(f"program-contract audit: {report['violations']} violation(s), "
        f"{diff_total} golden diff(s) across {len(names)} config(s)")
  if args.write_goldens:
    # Regeneration mode's exit code reflects golden regeneration only:
    # the intentional-program-change scenario it exists for is exactly
    # when the tuned table's re-derivation leg fires (the table is
    # regenerated separately, with `analysis autotune` -- the ordinary
    # audit keeps failing until it is).
    return 1 if report["violations"] else 0
  rc_tables = run_tuned_table_audit(args)
  return 1 if (report["violations"] or diff_total or spmd_total
               or rc_tables) else 0


def run_autotune(args) -> int:
  if not args.tpu:
    _force_virtual_cpu_mesh()
  from kf_benchmarks_tpu.analysis import autotune

  models = [m for m in (args.models or "").split(",") if m]
  if not models:
    print("autotune: pass --models model[,model...]")
    return 2
  bases = []
  for model in models:
    base = {"model": model}
    if args.batch_size:
      base["batch_size"] = args.batch_size
    if args.tpu:
      # Explicit device so autotune_config's cpu/8-virtual-mesh
      # defaults never apply under --tpu: the probes must measure the
      # real backend (one chip, one process -- serialized), not a CPU
      # stand-in written into the table as the backend's tuning.
      base.update(device="tpu", num_devices=1)
    bases.append(base)
  table = autotune.autotune_configs(
      bases, out=args.out, seed=args.seed, top_k=args.top_k,
      max_candidates=args.max_candidates,
      probe_dispatches=args.probe_dispatches, dry_run=args.dry_run)
  problems, _ = autotune.validate_table(table)
  for p in problems:
    print(f"TUNED-TABLE PROBLEM {p}")
  return 1 if problems else 0


def run_warm(args) -> int:
  if not args.train_dir:
    print("warm: pass --train_dir (the compile ledger's home)")
    return 2
  if not args.tpu:
    _force_virtual_cpu_mesh()
  from kf_benchmarks_tpu.analysis import autotune

  summary = autotune.warm(args.train_dir, table_path=args.table)
  print(f"warm: {len(summary['warmed'])} shape(s) compiled, "
        f"{len(summary['skipped'])} already warm -> "
        f"{summary['cache_dir']}")
  return 0


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(
      prog="python -m kf_benchmarks_tpu.analysis", description=__doc__)
  parser.add_argument("mode", nargs="?", default="all",
                      choices=("all", "lint", "audit", "autotune",
                               "warm"))
  parser.add_argument("--configs", default=None,
                      help="comma-separated golden-config names "
                           "(default: all)")
  parser.add_argument("--rules", default=None,
                      help="comma-separated lint rule ids (default: all)")
  parser.add_argument("--json", default=None,
                      help="write the audit report as JSON to this path")
  parser.add_argument("--write-goldens", action="store_true",
                      help="(re)generate tests/golden_contracts/*.json "
                           "from the current tree instead of diffing")
  parser.add_argument("--models", default=None,
                      help="autotune: comma-separated model names")
  parser.add_argument("--batch_size", type=int, default=None,
                      help="autotune: per-device batch for every model "
                           "(default: each model's own)")
  parser.add_argument("--out", default=None,
                      help="autotune: tuned-table output path")
  parser.add_argument("--seed", type=int, default=0,
                      help="autotune: candidate-subsample seed")
  parser.add_argument("--top_k", type=int, default=3,
                      help="autotune: cost-ranked survivors to probe")
  parser.add_argument("--max_candidates", type=int, default=24,
                      help="autotune: seeded grid-subsample bound")
  parser.add_argument("--probe_dispatches", type=int, default=4,
                      help="autotune: differential probe window size")
  parser.add_argument("--dry-run", action="store_true", dest="dry_run",
                      help="autotune: static stages only (trace + "
                           "prune + cost rank); nothing executes -- "
                           "the CPU-only CI rehearsal")
  parser.add_argument("--table", default=None,
                      help="tuned-config table path (warm input / "
                           "audit target beyond the repo-root table)")
  parser.add_argument("--train_dir", default=None,
                      help="warm: the job's train_dir (the compile "
                           "ledger lives here)")
  parser.add_argument("--tpu", action="store_true",
                      help="autotune/warm: keep the process on the "
                           "real backend instead of forcing the "
                           "8-virtual-device CPU mesh (one process "
                           "holds the chip)")
  args = parser.parse_args(argv)
  if args.mode == "autotune":
    return run_autotune(args)
  if args.mode == "warm":
    return run_warm(args)
  rc = 0
  if args.mode in ("all", "lint"):
    rc |= run_lint(args)
  if args.mode in ("all", "audit"):
    rc |= run_audit(args)
  return rc


if __name__ == "__main__":
  sys.exit(main())
