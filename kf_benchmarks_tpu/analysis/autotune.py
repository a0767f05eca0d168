"""Contract-driven autotuner: search the program-shaping knobs with the
static analyzer as the oracle, confirm with cheap measured probes.

The reference ships one hand-tuned flag set per model (SURVEY 2's
per-model defaults), and this repo has already paid for constants that
encode one host's envelope (the round-6 1.5x-throughput-bar incident,
PERF.md). This module replaces both with a measured search per
(model, batch, mesh):

1. **Enumerate** a deterministic, seeded candidate grid over the tuned
   knobs (``analysis/baseline.TUNED_KNOBS``: --steps_per_dispatch,
   --num_grad_accum, --reduce_bucket_mb, --input_prefetch_depth,
   --attn_block), filtered through the ordinary cross-flag validation
   so the grid can never propose a combination the CLI would reject.
2. **Prune statically** -- every surviving candidate is traced (never
   executed) through ``contracts.trace_contract`` on the abstract mesh,
   and rejected when its contract violates the memory/collective
   bounds (largest live buffer vs the HBM budget, collective-count and
   step-bucket caps) before any probe runs. A pruned candidate is
   never measured (tests assert 0 executions).
3. **Rank** survivors with a deterministic cost model over the
   contract's flop/collective/buffer inventory plus the dispatch
   amortization term K divides (a placeholder until measured, see
   COST_DISPATCH_OVERHEAD_S).
4. **Probe** the top-k (plus the incumbent default, always) with short
   differential paired windows -- the dispatch_amortization_probe
   methodology: warm one dispatch, ``utils.sync.drain`` at every
   boundary, time an n-dispatch and a 2n-dispatch window and
   difference them so constant overheads cancel. Probes run in-process
   and strictly sequentially: one process holds the chip.

The winner is the measured argmax over a set that always contains the
default config, so the emitted table can never regress a base config
against its own measured bar -- the no-regression bar is the run's own
default measurement, never a constant.

Output: a versioned tuned-config table (``tuned_configs.json``), keyed
on ``analysis/baseline.base_fingerprint_key`` (the config fingerprint
sans the tuned knobs and run-length counters), which
``--autotuned_config=PATH`` applies at startup with a logged
provenance line and ``experiments/zoo_sweep.py --autotune`` produces
for the whole zoo.

On top of the same table, **ledger-informed warming**: :func:`warm`
cross-references the persisted compile ledger (tracing.py) with the
tuned table and precompiles every (config, program) shape a job will
need into the persistent XLA compilation cache -- first compiles are
paid in a controlled warm pass, not mid-run. The warm pass seeds the train_dir
compile ledger under the exact fingerprint keys the runtime computes,
so a follow-up run's ledger reads ``cache_hit`` on every warmed shape.

Not in the v1 knob space: the transformer remat/layer policy stays on
its env switches (KF_TRANSFORMER_LM_LAYERS) -- env knobs are invisible
to the params fingerprint, so tuning them here would fragment the
table identity; promote them to flags first.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from kf_benchmarks_tpu.analysis import baseline
from kf_benchmarks_tpu.analysis import contracts
from kf_benchmarks_tpu.analysis.baseline import TUNED_KNOBS

TABLE_SCHEMA_VERSION = 1
TABLE_FILENAME = "tuned_configs.json"

# Knobs that do not shape the compiled train_step program (host-side
# feed depth; the dispatch chunking wraps the SAME step in a scan):
# dropped from the static-trace key so candidates differing only in
# them share one memoized compile, and ranked purely by the cost
# model's dispatch term / confirmed by the measured probe.
NON_PROGRAM_KNOBS = ("steps_per_dispatch", "input_prefetch_depth")

# Static-prune defaults. The HBM budget is the v5e single-chip 16 GiB
# minus a 1 GiB runtime reserve -- a BOUND, not a tuning constant: a
# candidate whose traced contract already exceeds it would OOM before
# producing a throughput number at all (override per backend).
DEFAULT_HBM_BUDGET_BYTES = 15 * 2**30
DEFAULT_MAX_COLLECTIVES = 256
DEFAULT_MAX_STEP_BUCKETS = 64

# Cost-model constants. Deterministic and documented; the model only
# RANKS candidates (the measured probe confirms), so what matters is
# monotonicity -- more collective bytes, more collective dispatches,
# bigger live buffers, fewer amortized host dispatches all cost more.
COST_PEAK_FLOPS = 197e12          # v5e bf16 peak (observability.DEVICE_PEAKS)
COST_ICI_BYTES_PER_S = 4.5e10     # interconnect order of magnitude
COST_HBM_BYTES_PER_S = 8.0e11    # HBM stream order of magnitude
COST_COLLECTIVE_LATENCY_S = 1e-5  # per-collective issue latency
# Per-dispatch host overhead: a PLACEHOLDER, not measured on this
# machine (its re-measurement is ROADMAP A4). The one chip reading so
# far is the resnet50 smoke's dispatch_overhead_s ~0.002 s (PR 21).
COST_DISPATCH_OVERHEAD_S = 0.07


class AutotuneError(ValueError):
  """A tuned-config table problem (missing/invalid file, bad entry)."""


# -- candidate grid -----------------------------------------------------------

def default_axes(base_params) -> "collections.OrderedDict[str, tuple]":
  """The per-knob candidate values for a base config. ``None`` means
  the knob's own default; axes only appear when the base config can
  legally consume them (the cross-flag validation would reject the
  rest anyway -- this just keeps the grid small)."""
  axes: "collections.OrderedDict[str, tuple]" = collections.OrderedDict()
  axes["steps_per_dispatch"] = (1, 2, 4, 8)
  axes["num_grad_accum"] = (1, 2, 4)
  if bool(getattr(base_params, "shard_params", False)):
    axes["reduce_bucket_mb"] = (None, 1, 4, 16)
  if getattr(base_params, "model", None) == "transformer_lm":
    axes["attn_block"] = (None, 256, 512, 1024)
  if getattr(base_params, "data_dir", None) or \
      bool(getattr(base_params, "packed_sequences", False)):
    axes["input_prefetch_depth"] = (None, 2, 4)
  # The gspmd twin is only legal where the manual program shards
  # something (validation.py rejects it elsewhere) -- same families
  # the twin-referee audits.
  if bool(getattr(base_params, "shard_optimizer_state", False)) or \
      bool(getattr(base_params, "shard_params", False)):
    axes["partitioner"] = (None, "gspmd")
  return axes


def _canon(knobs: Dict[str, Any]) -> str:
  return json.dumps(knobs, sort_keys=True)


def merged_overrides(base: Dict[str, Any],
                     knobs: Dict[str, Any]) -> Dict[str, Any]:
  """Base overrides + candidate knob values; a ``None`` knob value
  means 'the flag default' and removes any base override of it."""
  out = dict(base)
  for k, v in knobs.items():
    if v is None:
      out.pop(k, None)
    else:
      out[k] = v
  return out


def enumerate_candidates(axes: Dict[str, tuple],
                         defaults: Dict[str, Any],
                         seed: int = 0,
                         max_candidates: int = 24
                         ) -> List[Dict[str, Any]]:
  """The deterministic candidate list: full cross product of ``axes``,
  seeded-subsampled to ``max_candidates``, with the incumbent default
  candidate always present and always first."""
  default_cand = collections.OrderedDict(
      (k, defaults.get(k)) for k in axes)
  seen = {_canon(default_cand)}
  grid: List[Dict[str, Any]] = []
  for combo in itertools.product(*(axes[k] for k in axes)):
    cand = collections.OrderedDict(zip(axes, combo))
    c = _canon(cand)
    if c in seen:
      continue
    seen.add(c)
    grid.append(cand)
  if len(grid) + 1 > max_candidates:
    rng = random.Random(seed)
    keep = sorted(rng.sample(range(len(grid)),
                             max(0, max_candidates - 1)))
    grid = [grid[i] for i in keep]
  return [default_cand] + grid


# -- static oracle: prune + rank ----------------------------------------------

def prune_reasons(contract, *,
                  hbm_budget_bytes: int = DEFAULT_HBM_BUDGET_BYTES,
                  max_collectives: int = DEFAULT_MAX_COLLECTIVES,
                  max_step_buckets: int = DEFAULT_MAX_STEP_BUCKETS
                  ) -> List[str]:
  """The memory/collective bounds a candidate's contract must satisfy
  BEFORE it may execute; reasons (empty = survives)."""
  out = []
  live = max(int(contract.temp_bytes or 0),
             int(contract.largest_tensor_bytes or 0))
  if hbm_budget_bytes and live > hbm_budget_bytes:
    out.append(f"largest live buffer {live} B exceeds the HBM budget "
               f"{hbm_budget_bytes} B")
  n = len(contract.collectives)
  if max_collectives and n > max_collectives:
    out.append(f"{n} collectives exceed the per-step cap "
               f"{max_collectives}")
  planned = contract.aux.get("fsdp_step_gathers")
  if planned is not None and max_step_buckets and \
      int(planned) > max_step_buckets:
    out.append(f"{planned} planned FSDP gather buckets exceed the cap "
               f"{max_step_buckets} (per-bucket dispatch latency "
               "would dominate what the in-loop gathers win)")
  return out


def _collective_bytes(c) -> int:
  return int(c.elems) * contracts._ITEMSIZE.get(c.dtype, 4)


def candidate_cost(contract, overrides: Dict[str, Any]) -> float:
  """Deterministic per-step cost estimate from the contract inventory.

  Monotone (tests pin it) in: collective bytes, collective count, live
  buffer bytes; decreasing in the dispatch amortization K. Ranks only
  -- the measured probe is the arbiter."""
  k = int(overrides.get("steps_per_dispatch") or 1)
  flops = float(contract.aux.get("flops") or 0.0)
  coll_bytes = sum(_collective_bytes(c) for c in contract.collectives)
  n_coll = len(contract.collectives)
  live = max(int(contract.temp_bytes or 0),
             int(contract.largest_tensor_bytes or 0))
  return (flops / COST_PEAK_FLOPS
          + coll_bytes / COST_ICI_BYTES_PER_S
          + n_coll * COST_COLLECTIVE_LATENCY_S
          + live / COST_HBM_BYTES_PER_S
          + COST_DISPATCH_OVERHEAD_S / max(k, 1))


def static_overrides(merged: Dict[str, Any]) -> Dict[str, Any]:
  """The candidate's program-shaping projection (NON_PROGRAM_KNOBS
  dropped): what the static oracle traces, and the memo key that lets
  candidates differing only in host-side knobs share one compile."""
  return {k: v for k, v in merged.items() if k not in NON_PROGRAM_KNOBS}


# -- measured probe -----------------------------------------------------------

def measure_candidate(overrides: Dict[str, Any],
                      probe_dispatches: int = 4) -> float:
  """Measured throughput (examples/sec) of one candidate via short
  differential paired windows (the dispatch_amortization_probe
  methodology): warm one dispatch, then time an n-window and a
  2n-window with ``utils.sync.drain`` at each boundary and difference
  them, so compile residue and constant per-window overheads cancel.
  Runs in-process (one process holds the chip)."""
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.utils import sync

  merged = dict(overrides)
  k = int(merged.get("steps_per_dispatch") or 1)
  # Probe-only run-length fields (outside the base key, baseline.py):
  # long enough that the runtime never clamps K down.
  merged.setdefault("num_batches", max(100, 3 * k * probe_dispatches))
  merged.setdefault("num_warmup_batches", 0)
  p = params_lib.make_params(**merged)
  bench = benchmark.BenchmarkCNN(p)
  init_state, train_step, _, broadcast_init, train_chunk = bench._build()
  rng = jax.random.PRNGKey(0)
  next_batch, stop = bench._input_iterator(rng, "train", chunk=k)
  try:
    batch = next_batch()
    in_shapes = bench.model.get_input_shapes("train")
    in_dtypes = bench.model.get_input_data_types("train")
    sample = jnp.zeros(tuple(in_shapes[0]), in_dtypes[0])
    state = init_state(rng, sample)
    state = state.replace(params=broadcast_init(state.params))
    fn = train_chunk if k > 1 else train_step
    state, metrics = fn(state, *batch)  # compile + warm
    sync.drain(metrics)

    def window(n: int) -> float:
      nonlocal state
      t0 = time.monotonic()
      m = metrics
      for _ in range(n):
        state, m = fn(state, *batch)
      sync.drain(m)
      return time.monotonic() - t0

    n = max(1, int(probe_dispatches))
    t_short = window(n)
    t_long = window(2 * n)
    wall = max(t_long - t_short, 1e-9)
    return n * k * bench.batch_size / wall
  finally:
    if stop is not None:
      stop()


# -- the search ---------------------------------------------------------------

def autotune_config(base: Dict[str, Any], *,
                    seed: int = 0,
                    axes: Optional[Dict[str, tuple]] = None,
                    hbm_budget_bytes: int = DEFAULT_HBM_BUDGET_BYTES,
                    max_collectives: int = DEFAULT_MAX_COLLECTIVES,
                    max_step_buckets: int = DEFAULT_MAX_STEP_BUCKETS,
                    top_k: int = 3,
                    max_candidates: int = 24,
                    probe_dispatches: int = 4,
                    tracer: Optional[Callable] = None,
                    measure_fn: Optional[Callable] = None,
                    dry_run: bool = False,
                    log: Callable[[str], None] = print
                    ) -> Tuple[str, Dict[str, Any]]:
  """Run the full prune -> rank -> probe pipeline for one base config;
  returns ``(table_key, entry)``.

  ``tracer(overrides, program) -> ProgramContract`` and
  ``measure_fn(merged_overrides) -> examples/sec`` are injectable so
  the unit tests drive seeded contracts and count probe executions;
  the defaults are the real oracle (``audit.make_memo_tracer``) and
  :func:`measure_candidate`. ``dry_run`` stops after the static stages
  (CPU-only: candidates compile but never execute) and records the
  cost-model favourite with no measured fields."""
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import validation
  from kf_benchmarks_tpu.analysis import audit

  base = dict(base)
  base.setdefault("device", "cpu")
  base.setdefault("num_devices", contracts.N_REPLICAS)
  base_params = params_lib.make_params(**base)
  base_dict = base_params._asdict()
  key = baseline.base_fingerprint_key(base_dict, "train_step")
  axes = collections.OrderedDict(axes if axes is not None
                                 else default_axes(base_params))
  defaults = {k: base_dict.get(k) for k in axes}
  candidates = enumerate_candidates(axes, defaults, seed=seed,
                                    max_candidates=max_candidates)
  tracer = tracer or audit.make_memo_tracer()
  measure_fn = measure_fn or measure_candidate

  n_invalid = n_pruned = 0
  survivors: List[Tuple[float, int, Dict[str, Any]]] = []
  default_cand = candidates[0]
  default_pruned = False
  for i, cand in enumerate(candidates):
    m = merged_overrides(base, cand)
    try:
      p = params_lib.make_params(**m)
      validation.validate_cross_flags(p)
      contract = tracer(static_overrides(m), "train_step")
    except (validation.ParamError, ValueError) as e:
      n_invalid += 1
      log(f"autotune[{base_params.model}]: candidate {_canon(cand)} "
          f"invalid: {e}")
      continue
    reasons = prune_reasons(contract,
                            hbm_budget_bytes=hbm_budget_bytes,
                            max_collectives=max_collectives,
                            max_step_buckets=max_step_buckets)
    if reasons:
      n_pruned += 1
      if i == 0:
        default_pruned = True
      log(f"autotune[{base_params.model}]: candidate {_canon(cand)} "
          f"pruned statically: {'; '.join(reasons)}")
      continue
    survivors.append((candidate_cost(contract, m), i, cand))

  survivors.sort(key=lambda t: (t[0], _canon(t[2])))
  entry: Dict[str, Any] = {
      "model": base_params.model,
      "program": "train_step",
      "base_config": {k: v for k, v in base.items()
                      if k not in TUNED_KNOBS},
      "default": dict(defaults),
      "candidates": len(candidates),
      "invalid": n_invalid,
      "pruned": n_pruned,
      "seed": seed,
      "dry_run": bool(dry_run),
      "jax_version": _jax_version(),
  }
  if default_pruned:
    # The incumbent itself violates the static bounds: nothing may
    # execute (the 0-executions contract covers the default too), so
    # the entry records the finding and keeps the flag values.
    log(f"autotune[{base_params.model}]: base config violates the "
        "static bounds; no probes run, table keeps the defaults")
    entry.update(tuned=dict(defaults), probed=0,
                 default_images_per_sec=None, tuned_images_per_sec=None,
                 note="base config pruned by the static oracle")
    return key, entry

  if dry_run:
    best = survivors[0][2] if survivors else default_cand
    entry.update(tuned=dict(best), probed=0,
                 default_images_per_sec=None,
                 tuned_images_per_sec=None)
    return key, entry

  # Probe set: the incumbent default ALWAYS, then the cost-ranked
  # top-k survivors. Every probed candidate passed the static oracle.
  probe: List[Dict[str, Any]] = [default_cand]
  seen = {_canon(default_cand)}
  for _, _, cand in survivors:
    if len(probe) >= top_k + 1:
      break
    c = _canon(cand)
    if c not in seen:
      seen.add(c)
      probe.append(cand)
  measured: List[Tuple[Dict[str, Any], float]] = []
  for cand in probe:
    ips = float(measure_fn(merged_overrides(base, cand)))
    measured.append((cand, ips))
    log(f"autotune[{base_params.model}]: probe {_canon(cand)} -> "
        f"{ips:.1f} examples/s")
  # Strict > with the default first: ties keep the incumbent, so the
  # winner's measured throughput is >= the default's by construction
  # (the no-regression bar is the run's own default measurement).
  best_cand, best_ips = measured[0]
  for cand, ips in measured[1:]:
    if ips > best_ips:
      best_cand, best_ips = cand, ips
  entry.update(tuned=dict(best_cand), probed=len(measured),
               default_images_per_sec=round(measured[0][1], 2),
               tuned_images_per_sec=round(best_ips, 2))
  return key, entry


def _jax_version() -> str:
  try:
    import jax
    return jax.__version__
  except Exception:  # pure-stdlib caller (table validation harness)
    return ""


def new_table(seed: int = 0) -> Dict[str, Any]:
  return {"schema_version": TABLE_SCHEMA_VERSION, "seed": seed,
          "jax_version": _jax_version(), "entries": {}}


def autotune_configs(bases: List[Dict[str, Any]], *,
                     out: Optional[str] = None,
                     seed: int = 0,
                     log: Callable[[str], None] = print,
                     **kwargs) -> Dict[str, Any]:
  """Search each base config; return (and optionally write) the table.
  Strictly sequential -- on TPU that IS the serialization rule."""
  table = new_table(seed)
  for base in bases:
    key, entry = autotune_config(dict(base), seed=seed, log=log,
                                 **kwargs)
    table["entries"][key] = entry
    log(f"autotune[{entry['model']}]: entry {key[:16]} tuned="
        f"{_canon(entry['tuned'])} default={entry['default_images_per_sec']} "
        f"tuned_ips={entry['tuned_images_per_sec']}")
  if out:
    write_table(table, out)
    log(f"tuned-config table written: {out} "
        f"({len(table['entries'])} entr{'y' if len(table['entries']) == 1 else 'ies'})")
  return table


# -- table I/O + validation ---------------------------------------------------

def write_table(table: Dict[str, Any], path: str) -> str:
  """Atomic, canonical write (sorted keys, stable indent): same seed +
  same contracts + same measurements => byte-identical file (the
  determinism contract tests pin)."""
  os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
  tmp = path + ".tmp"
  with open(tmp, "w", encoding="utf-8") as f:
    json.dump(table, f, indent=2, sort_keys=True)
    f.write("\n")
  os.replace(tmp, path)
  return path


def load_table(path: str) -> Dict[str, Any]:
  try:
    with open(path, encoding="utf-8") as f:
      table = json.load(f)
  except OSError as e:
    raise AutotuneError(f"tuned-config table unreadable: {path}: {e}")
  except ValueError as e:
    raise AutotuneError(f"tuned-config table is not valid JSON: "
                        f"{path}: {e}")
  if not isinstance(table, dict) or \
      not isinstance(table.get("entries"), dict):
    raise AutotuneError(f"tuned-config table has no entries object: "
                        f"{path}")
  return table


def validate_table(table: Dict[str, Any], *,
                   rederive: bool = True
                   ) -> Tuple[List[str], List[str]]:
  """(problems, warnings) for a tuned-config table -- the
  ``run_tests.py --audit`` tuned-table leg.

  Problems (audit-fatal): schema shape, knobs outside the registry
  (baseline.TUNED_KNOBS), non-integer knob values, a tuned measurement
  below the entry's own default measurement, and -- with ``rederive``
  -- an entry key that no longer re-derives from its stored base
  config (a program-shaping flag default changed underneath the table;
  regenerate with `python -m kf_benchmarks_tpu.analysis autotune`).
  Warnings (non-fatal): entries recorded under a different jax version
  (an XLA upgrade recompiles everything; the tuning may be stale)."""
  problems: List[str] = []
  warnings: List[str] = []
  ver = table.get("schema_version")
  if not isinstance(ver, int) or not 1 <= ver <= TABLE_SCHEMA_VERSION:
    problems.append(f"schema_version {ver!r} outside "
                    f"[1, {TABLE_SCHEMA_VERSION}]")
  entries = table.get("entries")
  if not isinstance(entries, dict):
    return problems + ["entries missing or not an object"], warnings
  current_jax = _jax_version()
  for key in sorted(entries):
    entry = entries[key]
    where = f"entry {key[:16]}"
    if not isinstance(entry, dict):
      problems.append(f"{where}: not an object")
      continue
    tuned = entry.get("tuned")
    if not isinstance(tuned, dict):
      problems.append(f"{where}: tuned knobs missing")
      tuned = {}
    for k, v in sorted(tuned.items()):
      if k not in TUNED_KNOBS:
        problems.append(f"{where}: tuned knob {k!r} is not in the "
                        f"knob registry {list(TUNED_KNOBS)}")
      elif k == "partitioner":
        # The one string-valued knob (see baseline.TUNED_KNOBS).
        if v is not None and v not in ("manual", "gspmd"):
          problems.append(f"{where}: tuned value partitioner={v!r} is "
                          "not 'manual', 'gspmd', or null")
      elif v is not None and (isinstance(v, bool)
                              or not isinstance(v, int)):
        problems.append(f"{where}: tuned value {k}={v!r} is not an "
                        "integer or null")
    d_ips = entry.get("default_images_per_sec")
    t_ips = entry.get("tuned_images_per_sec")
    if d_ips is not None and t_ips is not None and t_ips < d_ips:
      problems.append(
          f"{where}: tuned_images_per_sec {t_ips} < the entry's own "
          f"default measurement {d_ips} -- the search must never emit "
          "a measured regression over its own bar")
    if entry.get("jax_version") and current_jax and \
        entry["jax_version"] != current_jax:
      warnings.append(
          f"{where}: recorded under jax {entry['jax_version']} "
          f"(current {current_jax}); tuning may be stale -- "
          "regenerate after validating on the new runtime")
    if rederive:
      base_cfg = entry.get("base_config")
      if not isinstance(base_cfg, dict):
        problems.append(f"{where}: base_config missing")
        continue
      try:
        from kf_benchmarks_tpu import params as params_lib
        params = params_lib.make_params(**base_cfg)
        derived = baseline.base_fingerprint_key(
            params._asdict(), entry.get("program", "train_step"))
      except Exception as e:
        problems.append(f"{where}: base_config does not build: {e}")
        continue
      if derived != key:
        problems.append(
            f"{where}: fingerprint does not re-derive (got "
            f"{derived[:16]}): a program-shaping flag changed "
            "underneath the table -- regenerate it with `python -m "
            "kf_benchmarks_tpu.analysis autotune`")
  return problems, warnings


# -- startup application ------------------------------------------------------

def lookup_entry(path: str, params
                 ) -> Tuple[str, Optional[Dict[str, Any]]]:
  """(base_key, entry or None) for a resolved Params against the table
  at ``path``. Stable across application: the base key strips exactly
  the knobs the table sets, so a tuned run looks itself up under the
  same key as its default twin."""
  table = load_table(path)
  key = baseline.base_fingerprint_key(params._asdict(), "train_step")
  entry = table["entries"].get(key)
  return key, entry if isinstance(entry, dict) else None


def apply_tuned_config(params, log_fn: Callable[[str], None] = print):
  """Apply --autotuned_config at startup (benchmark.setup calls this
  before the runtime is constructed): look the run's base fingerprint
  up in the table and replace the tuned knobs, with one logged
  provenance line either way. Returns ``(params, provenance)`` --
  provenance is the ``{path, entry}`` payload the stats/run record
  carries (entry None when the table held no row), or None when the
  flag is unset; the caller threads it through so the recorded
  provenance can never disagree with what was actually applied."""
  path = getattr(params, "autotuned_config", None)
  if not path:
    return params, None
  from kf_benchmarks_tpu import validation
  if params.eval or params.forward_only:
    raise validation.ParamError(
        "--autotuned_config tunes the training step's program-shaping "
        "knobs (analysis/autotune.py); it cannot be combined with "
        "--eval or --forward_only")
  try:
    key, entry = lookup_entry(path, params)
  except AutotuneError as e:
    raise validation.ParamError(str(e))
  if entry is None:
    log_fn(f"autotuned config: no entry for base fingerprint "
           f"{key[:16]} in {path}; running with the flag values")
    return params, {"path": path, "entry": None}
  tuned = {k: v for k, v in (entry.get("tuned") or {}).items()
           if k in TUNED_KNOBS}
  params = params._replace(**tuned)
  applied = ", ".join(f"{k}={tuned[k]}" for k in sorted(tuned))
  log_fn(f"autotuned config: applied {applied} from {path} "
         f"(entry {key[:16]}, model {entry.get('model')}, "
         f"measured {entry.get('tuned_images_per_sec')} vs default "
         f"{entry.get('default_images_per_sec')} examples/s)")
  return params, {"path": path, "entry": key}


def tuned_provenance(params) -> Optional[Dict[str, Any]]:
  """The run-record provenance payload: table path + matched entry
  fingerprint (None when the table had no entry for this config), or
  None when --autotuned_config is unset. Best-effort -- a table that
  disappeared between setup and the stats build reports entry None
  rather than failing the run."""
  path = getattr(params, "autotuned_config", None)
  if not path:
    return None
  try:
    key, entry = lookup_entry(path, params)
  except AutotuneError:
    return {"path": path, "entry": None}
  return {"path": path, "entry": key if entry is not None else None}


# -- ledger-informed warming --------------------------------------------------

def warm(train_dir: str, *,
         table_path: Optional[str] = None,
         configs: Optional[List[Dict[str, Any]]] = None,
         cache_dir: Optional[str] = None,
         log: Callable[[str], None] = print) -> Dict[str, Any]:
  """Precompile every (config, program) shape a job will need into the
  persistent XLA compilation cache, ahead of a hardware window.

  Shapes come from the tuned table at ``table_path`` (default:
  ``train_dir/tuned_configs.json``; each entry's base config + tuned
  knobs) and/or explicit ``configs``; the persisted compile ledger
  (tracing.read_ledger) is cross-referenced so already-warm shapes are
  skipped and ledgered program labels beyond the config's own
  prediction are warmed too. Every compile is keyed exactly as the
  runtime keys it (config_fingerprint_key over the RESOLVED params)
  and written back to the train_dir ledger, so a follow-up run reads
  ``cache_hit`` on every warmed shape. Strictly sequential: on the
  chip this is the controlled place to pay first compiles. The cache
  directory follows benchmark.configure_compile_cache (``cache_dir``
  plays the flag)."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing as tracing_lib

  # The one cache rule (benchmark.configure_compile_cache), keyed on the
  # platform this process compiles for; ``cache_dir`` plays the
  # --compilation_cache_dir flag.
  cache_dir = benchmark.configure_compile_cache(
      benchmark.device_identity()["platform"], cache_dir)
  if not cache_dir:
    raise ValueError(
        "warm: no persistent XLA cache to fill -- set "
        "JAX_COMPILATION_CACHE_DIR (or pass cache_dir); CPU runs keep "
        "the cache off by default")
  log(f"warm: persistent XLA cache {cache_dir}")
  ledger = tracing_lib.read_ledger(train_dir)
  prior_keys = tracing_lib.ledger_keys(ledger)
  ledger_progs = tracing_lib.ledger_programs(ledger)
  cache_warm = False
  try:
    cache_warm = any(os.scandir(cache_dir))
  except OSError:
    cache_warm = False

  jobs: List[Dict[str, Any]] = [dict(c) for c in (configs or [])]
  path = table_path or os.path.join(train_dir, TABLE_FILENAME)
  if table_path or os.path.exists(path):
    table = load_table(path)
    for key in sorted(table["entries"]):
      entry = table["entries"][key]
      full = merged_overrides(dict(entry.get("base_config") or {}),
                              entry.get("tuned") or {})
      jobs.append(full)

  trace = tracing_lib.RunTrace(log_fn=log)
  warmed, skipped = [], []
  for full in jobs:
    # num_batches is NOT defaulted here: a job that leaves it unset
    # keys with the field ABSENT (the runtime resolves the count into
    # an attribute, never back into params), so injecting a value
    # would key a shape no real run ever looks up. Jobs that DO set
    # --num_batches must pass it in ``configs`` (the tuned table's
    # base configs strip run-length fields by design). The train_dir
    # IS mirrored: it is fingerprint-excluded itself, but its
    # PRESENCE feeds the --health_stats auto-resolution
    # (telemetry.py), which IS a program-shaping bool -- a warm pass
    # without it would key the health-off twin of the job's program.
    full.setdefault("train_dir", train_dir)
    bench = benchmark.BenchmarkCNN(params_lib.make_params(**full))
    spd = int(bench.params.steps_per_dispatch or 1)
    programs = ["train_step"]
    if spd > 1:
      programs.append("train_chunk")
    # Ledger labels beyond what this config can build here (eval_step,
    # or train_chunk at K=1) are reported, not silently covered.
    unbuildable = ledger_progs - set(programs)
    if unbuildable:
      log(f"warm: ledger names program(s) {sorted(unbuildable)} this "
          f"config cannot build (K={spd}); not warmed")
    for prog in programs:
      key = baseline.config_fingerprint_key(bench.params._asdict(),
                                            prog)
      if cache_warm and key in prior_keys:
        skipped.append((key, prog))
        log(f"warm: {bench.model.get_name()}/{prog} {key[:16]} "
            "already warm; skipped")
        continue
      t0 = time.monotonic()
      _, lowered = contracts.lower_step_program(bench, prog)
      lowered.compile()
      wall = time.monotonic() - t0
      trace.note_compile(key, prog, wall,
                         model=bench.model.get_name(),
                         num_devices=bench.num_devices,
                         warm_pass=True)
      warmed.append((key, prog))
      log(f"warm: compiled {bench.model.get_name()}/{prog} "
          f"{key[:16]} in {wall:.2f} s")
  out_path = trace.write_ledger(train_dir)
  return {"cache_dir": cache_dir, "warmed": warmed,
          "skipped": skipped, "ledger": out_path}
