"""Rule engine: check every earned program contract against a config.

Each rule encodes one guarantee a past PR earned and a test pinned for
the configs it happened to cover; here the same invariant is checked
for ANY config (the golden lattice in ``contracts.GOLDEN_CONFIGS``, or
whatever the CLI is pointed at), the way the reference leaned on
graph-mode structure checks before a session ever ran (SURVEY 2).

A rule is (id, applies(config) -> bool, check(contract, tracer) ->
[message]); ``audit_contract`` runs every applicable rule and returns
machine-readable violations. ``tracer`` lets paired rules trace a twin
config (health on vs off) through the same memoized path.

Mutation self-tests (tests/test_program_audit.py) seed violations --
an extra in-loop psum, a leaked f32 wire, a materialized (B, T, V)
buffer -- and assert exactly the intended rule fires, so this engine
cannot rot into a pass-everything stub.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from kf_benchmarks_tpu.analysis.contracts import ProgramContract


@dataclasses.dataclass
class Violation:
  rule: str
  message: str

  def as_dict(self):
    return {"rule": self.rule, "message": self.message}


def _cfg(contract: ProgramContract, name: str, default=None):
  return contract.config.get(name, default)


def _accum(contract) -> int:
  return int(_cfg(contract, "num_grad_accum", 1) or 1)


def _replicated_sync(contract) -> bool:
  vu = _cfg(contract, "variable_update", "replicated")
  sync = bool(_cfg(contract, "cross_replica_sync", True))
  return vu in ("replicated", "distributed_replicated", "parameter_server",
                "collective_all_reduce", "distributed_all_reduce") and sync


# -- the earned contracts -----------------------------------------------------

def rule_accum_one_collective(contract, tracer):
  """PR 2: --num_grad_accum pays ONE gradient reduction per step, never
  inside the microbatch scan; with a packing reducer the count is
  literally one."""
  if _accum(contract) <= 1:
    return []
  if _gspmd(contract):
    # GSPMD places the gradient exchange itself; the twin referee's
    # accum leg owns the in-loop check against the manual twin
    # (rule_partitioner_twin; one owner per seeded violation).
    return []
  out = []
  grads = contract.gradient_collectives()
  in_loop = [c for c in grads if c.in_loop]
  if in_loop:
    out.append(f"{len(in_loop)} gradient collective(s) inside the "
               "microbatch scan body -- reduction must be per STEP, "
               "not per microbatch")
  packed = (int(_cfg(contract, "agg_small_grads_max_bytes", 0) or 0) > 0
            or int(_cfg(contract, "gradient_repacking", 0) or 0) > 0)
  if packed and len(grads) != 1:
    out.append(f"expected exactly ONE packed gradient all-reduce per "
               f"accumulated step, found {len(grads)}")
  return out


def rule_no_collective_in_loop(contract, tracer):
  """No collective inside a loop body unless the module gathers there:
  the replicated family's exchange trails the backward pass, once per
  step (PR 3's in-backward hooks, the one mode that put it inside the
  backward scan, lost on the chip and went in PR 29).

  Manual TRAIN programs only: GSPMD decides collective placement
  itself (in-or-out of the scanned backward), so the twin referee
  owns that program shape (rule_partitioner_twin) -- and a tensor-
  parallel serving program's per-block reductions live inside the
  layer scan by construction (same owner)."""
  if _gspmd(contract) or contract.program not in ("train_step",
                                                  "train_chunk"):
    return []
  if not _replicated_sync(contract):
    # async-PS sequential apply / gossip schedules legitimately issue
    # collectives inside scans; the rule binds the replicated family.
    return []
  if _accum(contract) > 1:
    # The microbatch scan is rule_accum_one_collective's territory
    # (one owner per seeded violation, so mutation self-tests can
    # assert exactly one rule fires).
    return []
  if _cfg(contract, "shard_params", False):
    # Full FSDP's per-block gathers/scatters live inside the scan
    # body by DESIGN; rule_fsdp_residency owns that program shape
    # (one owner per seeded violation).
    return []
  in_loop = contract.in_loop_collectives()
  if in_loop:
    return [f"{len(in_loop)} collective(s) inside a scanned body -- a "
            "collective leaked into a while loop"]
  return []


def rule_no_btv_buffer(contract, tracer):
  """PR 2: the fused-head scanned LM materializes no (B, T, V) logits
  tensor anywhere in the compiled step."""
  btv = contract.aux.get("btv_bytes")
  if btv is None:
    return []
  if contract.largest_tensor_bytes >= btv:
    return [f"largest program buffer {contract.largest_tensor_type} "
            f"({contract.largest_tensor_bytes} B) >= the (B, T, V) "
            f"logits tensor ({btv} B) the fused head exists to avoid"]
  return []


def rule_trace_twin(contract, tracer):
  """PR 9: run tracing is HOST-ONLY. The trace-on step program
  (--trace_events_file set, tracing.py) must be STRUCTURALLY IDENTICAL
  to the trace-off twin -- full fingerprint identity (collective
  inventory, wires, donation, optimizer scope, host transfers), not
  just a collective-count bound: a device-side reduction, a host
  transfer or a lost donation smuggled in by instrumentation is exactly
  the regression this rule exists to catch."""
  if not _cfg(contract, "trace_events_file"):
    return []
  if tracer is None:
    return []
  from kf_benchmarks_tpu.analysis import baseline as baseline_lib
  twin_cfg = dict(contract.config)
  twin_cfg.pop("trace_events_file")
  twin = tracer(twin_cfg, contract.program)
  on = baseline_lib.contract_fingerprint(contract)
  off = baseline_lib.contract_fingerprint(twin)
  # The config field differs by construction (it carries the flag).
  on.pop("config", None)
  off.pop("config", None)
  return [
      f"trace-on program differs from the trace-off twin at {field}: "
      f"{off_v!r} (off) vs {on_v!r} (on) -- tracing must stay host-only"
      for field, off_v, on_v in baseline_lib.diff_fingerprints(off, on)]


def rule_metrics_twin(contract, tracer):
  """PR 11: the metrics fabric is HOST-ONLY. A step program traced
  with --metrics_port / --run_store_dir set (metrics.py registry,
  endpoint, run store) must be STRUCTURALLY IDENTICAL to the twin
  without them -- the rule_trace_twin contract, extended to the
  metrics session: device-side instrumentation smuggled in through the
  registry is exactly the regression this catches."""
  if not (_cfg(contract, "metrics_port") or
          _cfg(contract, "run_store_dir")):
    return []
  if tracer is None:
    return []
  from kf_benchmarks_tpu.analysis import baseline as baseline_lib
  twin_cfg = dict(contract.config)
  twin_cfg.pop("metrics_port", None)
  twin_cfg.pop("run_store_dir", None)
  twin = tracer(twin_cfg, contract.program)
  on = baseline_lib.contract_fingerprint(contract)
  off = baseline_lib.contract_fingerprint(twin)
  on.pop("config", None)
  off.pop("config", None)
  return [
      f"metrics-on program differs from the metrics-off twin at "
      f"{field}: {off_v!r} (off) vs {on_v!r} (on) -- the metrics "
      "fabric must stay host-only"
      for field, off_v, on_v in baseline_lib.diff_fingerprints(off, on)]


def rule_health_no_extra_collective(contract, tracer):
  """PR 4: the health-on step carries NO additional collective (the
  stats ride the loss pmean)."""
  if not contract.aux.get("health_stats"):
    return []
  if tracer is None:
    return []
  twin_cfg = dict(contract.config)
  twin_cfg["health_stats"] = False
  twin = tracer(twin_cfg, contract.program)
  n_on = sum(1 for c in contract.collectives if c.kind == "all-reduce")
  n_off = sum(1 for c in twin.collectives if c.kind == "all-reduce")
  if n_on > n_off:
    return [f"health stats added collectives: {n_on} all-reduces vs "
            f"{n_off} with stats off"]
  return []


def rule_wire_dtype(contract, tracer):
  """PR 3 satellite: gradients ride a bf16 wire iff the compact
  transfer engages (--use_fp16, or --compact_gradient_transfer_f32 on
  a packed path); pure-f32 training keeps an f32 wire."""
  grads = contract.gradient_collectives()
  if not grads:
    return []
  compact_16 = bool(_cfg(contract, "compact_gradient_transfer_f32")
                    or _cfg(contract, "use_fp16"))
  # The lowered-level wire (what the program REQUESTS -- the TPU wire)
  # when the tracer recorded it; the compiled dump's dtypes otherwise
  # (XLA:CPU legalizes 16-bit collectives to f32 while compiling).
  requested = contract.aux.get("requested_grad_wires")
  wire = set(requested) if requested else {c.dtype for c in grads}
  if compact_16 and "f32" in wire:
    return [f"16-bit wire expected but f32 gradient all-reduce(s) "
            f"found (wire dtypes: {sorted(wire)})"]
  if not compact_16 and wire != {"f32"}:
    return [f"f32 wire expected (no 16-bit compaction engaged) but "
            f"found wire dtypes {sorted(wire)}"]
  return []


def rule_gradient_reduced_once(contract, tracer):
  """ISSUE 25: under the plain replica mean every gradient leaf is
  reduced EXACTLY once, one of two ways: in the all-reduce of the
  step's ``exchange`` scope, or -- a dense kernel larger than its batch
  -- on the factor data plane, formed in the backward pass from two
  all-gathered factors and left out of the all-reduce
  (parallel/kungfu.py). So the all-reduced elements and the factor
  plane's add up to the tree (with the batch statistics, which sync
  under the same scope), and each factor layer shows its two gathers.
  A claimed leaf that is all-reduced as well, or one that is neither,
  breaks the sum. Binds where the step's plan says so
  (train_step.plan_step's exchange kind, in the contract's aux)."""
  if (contract.program != "train_step"
      or contract.aux.get("exchange") != "factored_mean"):
    return []
  aux = contract.aux
  exchange = [c for c in contract.collectives if c.in_exchange]
  reduced = sum(c.elems for c in exchange if c.kind == "all-reduce")
  want = (aux["gradient_elems"] - aux["factor_elems"]
          + aux["batch_stats_elems"])
  out = []
  if reduced != want:
    out.append(
        f"{reduced} elements all-reduced under the exchange scope, but "
        f"the gradient tree has {aux['gradient_elems']}, the factor "
        f"plane took {aux['factor_elems']} and the batch statistics "
        f"are {aux['batch_stats_elems']}: want {want} -- a leaf is "
        "reduced twice or not at all")
  gathers = sum(c.kind == "all-gather" for c in exchange)
  if gathers != 2 * aux["factor_layers"]:
    out.append(
        f"{gathers} all-gather(s) under the exchange scope for "
        f"{aux['factor_layers']} dense layer(s) on the factor plane: "
        "each gathers its two factors, nothing else gathers here")
  return out


def _sharded(contract) -> bool:
  return bool(_cfg(contract, "shard_optimizer_state", False))


def _gspmd(contract) -> bool:
  """True when the contract's program was partitioned by GSPMD
  (--partitioner=gspmd). The hand-written collective-shape rules
  (sharded exchange kinds, FSDP gather residency, replica-group
  shapes) encode the MANUAL shard_map program; GSPMD is free to pick
  a different-but-correct exchange, so those rules stand down and
  rule_partitioner_twin referees the divergence instead (one owner
  per seeded violation)."""
  return _cfg(contract, "partitioner") == "gspmd"


def _group_sizes(replica_groups: str):
  """Parse an HLO ``{{0,1},{2,3}}`` replica-groups string into the list
  of group sizes (empty when the attribute was absent)."""
  inner = replica_groups.strip().strip("{}")
  if not inner:
    return []
  return [len([t for t in grp.split(",") if t.strip() != ""])
          for grp in inner.split("},{")]


def rule_sharded_collectives(contract, tracer):
  """PR 6: a --shard_optimizer_state step meets its gradients in
  reduce-scatter and returns params by all-gather -- NO full-gradient
  all-reduce may remain (the ZeRO exchange, ops/sharded.py), each
  reduce-scatter group spans the 'batch' axis (B data replicas) and
  each all-gather group the whole mesh, and f32 training keeps f32
  wires on both. Binds only on the MANUAL partitioner's programs --
  GSPMD may legally choose a different exchange (see _gspmd)."""
  if not _sharded(contract) or _gspmd(contract):
    return []
  out = []
  rs = [c for c in contract.collectives
        if c.kind == "reduce-scatter" and not c.scalar]
  ag = [c for c in contract.collectives
        if c.kind == "all-gather" and not c.scalar]
  if not rs:
    out.append("no reduce-scatter in the sharded step program -- the "
               "gradient exchange fell back to something else")
  if not ag:
    out.append("no all-gather in the sharded step program -- updated "
               "params are not being re-assembled from the shards")
  grads = contract.gradient_collectives()
  if grads:
    out.append(f"{len(grads)} full-gradient all-reduce(s) in a sharded "
               "step -- the reduce-scatter path is being duplicated "
               "(or replaced) by the replicated exchange")
  n = contract.aux.get("num_devices")
  n_data = contract.aux.get("num_data_replicas") or n
  if n:
    bad_rs = [c for c in rs if c.replica_groups and
              set(_group_sizes(c.replica_groups)) != {n_data}]
    if bad_rs:
      out.append(
          f"{len(bad_rs)} reduce-scatter(s) with groups not spanning "
          f"the {n_data}-replica 'batch' axis (e.g. "
          f"{bad_rs[0].replica_groups}) -- the scattered mean would "
          "meet the wrong contribution set")
    bad_ag = [c for c in ag if c.replica_groups and
              set(_group_sizes(c.replica_groups)) != {n}]
    if bad_ag:
      out.append(
          f"{len(bad_ag)} all-gather(s) with groups not spanning the "
          f"full {n}-device mesh (e.g. {bad_ag[0].replica_groups}) -- "
          "devices would re-assemble partial parameter trees")
  compact_16 = bool(_cfg(contract, "compact_gradient_transfer_f32")
                    or _cfg(contract, "use_fp16"))
  wires = contract.aux.get("requested_collective_wires") or {}
  sharded_wires = set(wires.get("reduce-scatter", []) +
                      wires.get("all-gather", []))
  if not compact_16 and sharded_wires and sharded_wires != {"f32"}:
    out.append(f"f32 wire expected on the sharded exchange (no 16-bit "
               f"compaction engaged) but found {sorted(sharded_wires)}")
  return out


def rule_sharded_opt_bytes(contract, tracer):
  """PR 6: per-device optimizer-state bytes under
  --shard_optimizer_state are ~|state|/n of the replicated twin's (the
  ZeRO partitioning bound; slack covers the per-leaf zero pad and the
  per-shard scalar counts)."""
  if not _sharded(contract) or tracer is None:
    return []
  per_device = contract.aux.get("opt_state_bytes_per_device")
  n = contract.aux.get("num_devices")
  if per_device is None or not n:
    return []
  twin_cfg = dict(contract.config)
  twin_cfg.pop("shard_optimizer_state")
  # A model axis is only valid WITH sharded state (validation.py), so
  # the replicated twin must drop the mesh too -- the comparison is
  # against the same device count's 1-D replicated state either way.
  twin_cfg.pop("mesh_shape", None)
  # ... and --shard_params requires --shard_optimizer_state, so the
  # replicated twin drops it with the rest.
  twin_cfg.pop("shard_params", None)
  # ... and --partitioner=gspmd requires sharded state too (the twin
  # is the plain replicated program either way -- the ZeRO bound is
  # about the state bytes, not who inserted the collectives).
  twin_cfg.pop("partitioner", None)
  twin = tracer(twin_cfg, contract.program)
  full = twin.aux.get("opt_state_bytes_per_device")
  if full is None:
    return []
  bound = int(full / n * 1.05) + 4096
  if per_device > bound:
    return [f"per-device optimizer state {per_device} B exceeds the "
            f"ZeRO bound ~|state|/n = {full}/{n} B (+pad slack "
            f"{bound} B) -- state is leaking back to replicated"]
  return []


def _fsdp(contract) -> bool:
  return bool(_cfg(contract, "shard_params", False))


def _collective_bytes(c) -> int:
  from kf_benchmarks_tpu.analysis import contracts as contracts_lib
  return int(c.elems) * contracts_lib._ITEMSIZE.get(c.dtype, 4)


def rule_fsdp_residency(contract, tracer):
  """PR 10 (round 15): a --shard_params step never materializes the
  full parameter tree.

  Checks, against the traced aux (contracts.py): (a) scanned FSDP
  models carry their per-block all-gather INSIDE the scan while body;
  (b) the out-of-loop all-gather inventory never exceeds the planned
  step-bucket count -- a whole-tree re-assembly (the round-11 trailing
  gather) would show up as extra gathers here; (c) no single
  all-gather result reaches half the full parameter-tree bytes --
  every live re-assembled param buffer is bucket/block-sized. Under
  --num_grad_accum the in-compute gathers disengage by design (one
  whole-tree gather per step, train_step.py), so only the size bound
  binds there. Manual-partitioner programs only (see _gspmd) -- the
  gspmd twin's residency is refereed by rule_partitioner_twin's
  largest-live-buffer bound against this very program."""
  if not _fsdp(contract) or contract.program != "train_step" or \
      _gspmd(contract):
    return []
  out = []
  full_bytes = contract.aux.get("fsdp_param_full_bytes")
  ags = [c for c in contract.collectives
         if c.kind == "all-gather" and not c.scalar]
  in_loop = [c for c in ags if c.in_loop]
  out_loop = [c for c in ags if not c.in_loop]
  if contract.aux.get("fsdp_engaged", True):
    if contract.aux.get("fsdp_scan_prefixes") and not in_loop:
      out.append(
          "scanned FSDP model but no all-gather inside a scan while "
          "body -- the per-block parameter gather left the loop (full "
          "stack residency)")
    planned = contract.aux.get("fsdp_step_gathers")
    if planned is not None and len(out_loop) > planned:
      out.append(
          f"{len(out_loop)} all-gather(s) outside the scan bodies vs "
          f"{planned} planned step gather bucket(s) -- a full-tree "
          "re-assembly (the round-11 trailing gather) leaked back into "
          "the steady state")
  if full_bytes:
    # Per-gather residency bound: half the full tree, floored at the
    # largest PLANNED bucket result (a tree dominated by one layer --
    # trivial's 1001-way head -- legitimately gathers most of its
    # bytes in that layer's bucket; what must never appear is a gather
    # larger than any planned bucket, i.e. a whole-tree re-assembly).
    planned_max = contract.aux.get("fsdp_max_gather_bytes") or 0
    bound = max(full_bytes // 2, planned_max + 1)
    for where, group in (("in-loop", in_loop), ("step-level", out_loop)):
      big = [c for c in group if _collective_bytes(c) >= bound]
      if big:
        out.append(
            f"{len(big)} {where} all-gather(s) re-assemble "
            f"{_collective_bytes(big[0])} B >= the residency bound "
            f"{bound} B (full tree {full_bytes} B, largest planned "
            f"bucket {planned_max} B) -- params leaked back to "
            "replicated residency")
  return out


def rule_packed_no_overhead(contract, tracer):
  """PR 8 (round 13): --packed_sequences must not change the program
  class. The packed LM still carries no (B, T, V) logits buffer (the
  btv aux must be present so rule_no_btv_buffer binds -- segment
  masking must not have detoured through a dense-head path), and the
  packed step carries NO more collectives than its unpacked twin,
  kind-for-kind: segment masks are pointwise/tile-local and the
  token-weighted metric combine PACKS the loss pmeans into one vector
  (train_step.py), so any count increase is a leak."""
  if not _cfg(contract, "packed_sequences", False):
    return []
  out = []
  if contract.aux.get("btv_bytes") is None:
    out.append("packed transformer_lm contract carries no (B, T, V) "
               "bound aux -- the no-logits rule cannot bind on the "
               "packed program")
  if tracer is None:
    return out
  twin_cfg = dict(contract.config)
  twin_cfg.pop("packed_sequences")
  twin = tracer(twin_cfg, contract.program)

  def counts(c):
    by_kind: Dict[str, int] = {}
    for x in c.collectives:
      by_kind[x.kind] = by_kind.get(x.kind, 0) + 1
    return by_kind

  on, off = counts(contract), counts(twin)
  for kind in sorted(on):
    if on[kind] > off.get(kind, 0):
      out.append(
          f"packed step has {on[kind]} {kind}(s) vs {off.get(kind, 0)} "
          "unpacked -- packing added a collective (the weighted "
          "metric combine must ride ONE packed vector)")
  n_grad_on = len(contract.gradient_collectives())
  n_grad_off = len(twin.gradient_collectives())
  if n_grad_on != n_grad_off:
    out.append(
        f"packed step's gradient collective count {n_grad_on} != "
        f"unpacked twin's {n_grad_off} -- packing must not touch the "
        "gradient exchange")
  return out


def _twin_inventory(contract):
  """Collective inventory keyed on (kind, dtype, rank, placement):
  count, total wire bytes, and the replica-group sizes seen -- the
  rows the partitioner referee diffs between the twins."""
  rows: Dict[tuple, Dict[str, Any]] = {}
  for c in contract.collectives:
    key = (c.kind, c.dtype, "scalar" if c.scalar else "tensor",
           "in_loop" if c.in_loop else "top_level")
    row = rows.setdefault(key, {"count": 0, "bytes": 0, "groups": set()})
    row["count"] += 1
    row["bytes"] += _collective_bytes(c)
    if c.replica_groups:
      row["groups"].update(_group_sizes(c.replica_groups))
  return rows


def _twin_wire_bytes(inventory) -> int:
  """Total non-scalar wire bytes an inventory moves (scalar control
  reductions are noise at any partitioner's scale)."""
  return sum(row["bytes"] for (k, d, r, p), row in inventory.items()
             if r == "tensor")


def partitioner_twin_verdict(contract, twin) -> Dict[str, Any]:
  """ISSUE 17: the twin referee. Diff the gspmd contract against its
  manual twin -- collective inventory (kind/wire/elems/groups/in-loop
  placement) and largest live buffer -- and CLASSIFY the divergence:

  - ``equivalent``: identical inventory rows and buffer within 5%.
  - ``manual-wins`` / ``gspmd-wins``: the programs legitimately
    diverge (GSPMD chose a different exchange); the side moving fewer
    wire bytes (buffer as tiebreak) wins. Not a violation -- the diff
    table IS the deliverable (PERF.md reads it from the report).
  - ``bug``: a divergence no partitioner choice explains -- a host
    transfer only the gspmd side carries, donation lost, a gradient
    collective re-entering the microbatch scan, or the largest live
    buffer blowing past 2x the manual twin's. These violate.

  Returns the machine-readable verdict dict embedded in the audit
  report (classification, per-row diff, buffer ratio, bug messages)."""
  inv_g = _twin_inventory(contract)
  inv_m = _twin_inventory(twin)
  rows = []
  for key in sorted(set(inv_g) | set(inv_m), key=repr):
    g, m = inv_g.get(key), inv_m.get(key)
    if g == m:
      continue
    kind, dtype, rank, placement = key
    rows.append({
        "kind": kind, "dtype": dtype, "rank": rank,
        "placement": placement,
        "manual": {"count": m["count"], "bytes": m["bytes"],
                   "groups": sorted(m["groups"])} if m else None,
        "gspmd": {"count": g["count"], "bytes": g["bytes"],
                  "groups": sorted(g["groups"])} if g else None,
    })
  bytes_g, bytes_m = _twin_wire_bytes(inv_g), _twin_wire_bytes(inv_m)
  buf_g = contract.largest_tensor_bytes
  buf_m = twin.largest_tensor_bytes
  buf_ratio = (buf_g / buf_m) if buf_m else None

  bugs = []
  extra_host = [h for h in contract.host_transfers
                if h not in twin.host_transfers]
  if extra_host:
    bugs.append(f"gspmd-only host transfer(s) {extra_host} -- GSPMD "
                "smuggled a host round-trip into the step the manual "
                "program does without")
  if twin.donated_buffers > 0 and contract.donated_buffers == 0:
    bugs.append("manual twin donates its state but the gspmd program "
                "lost the aliasing -- HBM footprint doubles under "
                "GSPMD for no partitioning reason")
  if _accum(contract) > 1:
    grads_in_loop_g = [c for c in contract.gradient_collectives()
                       if c.in_loop]
    grads_in_loop_m = [c for c in twin.gradient_collectives()
                       if c.in_loop]
    if grads_in_loop_g and not grads_in_loop_m:
      bugs.append(
          f"{len(grads_in_loop_g)} gradient collective(s) inside the "
          "microbatch scan on the gspmd side only -- GSPMD moved the "
          "once-per-step reduction into the per-microbatch body")
  if buf_m and buf_g > 2 * buf_m:
    bugs.append(
        f"gspmd largest live buffer {contract.largest_tensor_type} "
        f"({buf_g} B) blows past 2x the manual twin's "
        f"{twin.largest_tensor_type} ({buf_m} B) -- GSPMD "
        "materialized something the manual program keeps sharded")

  if bugs:
    classification = "bug"
  elif not rows and (buf_ratio is None or 0.95 <= buf_ratio <= 1.05):
    classification = "equivalent"
  elif bytes_g < bytes_m or (bytes_g == bytes_m and buf_g < buf_m):
    classification = "gspmd-wins"
  elif bytes_m < bytes_g or (bytes_g == bytes_m and buf_m < buf_g):
    classification = "manual-wins"
  else:
    classification = "equivalent"
  return {
      "classification": classification,
      "inventory_diff": rows,
      "wire_bytes": {"manual": bytes_m, "gspmd": bytes_g},
      "largest_buffer": {"manual": buf_m, "gspmd": buf_g,
                         "ratio": buf_ratio},
      "bugs": bugs,
  }


def _twin_manual_config(contract) -> Optional[Dict[str, Any]]:
  """The manual twin's config for a gspmd-side contract, or None when
  the referee does not bind. Train programs: the config carries
  ``partitioner='gspmd'``; the twin drops the flag (manual is the
  default). Serving programs: the config carries ``model_shards``; the
  twin is the unsharded decode of the same spec."""
  if contract.program in ("train_step", "train_chunk"):
    if _cfg(contract, "partitioner") != "gspmd":
      return None
    twin_cfg = dict(contract.config)
    twin_cfg.pop("partitioner")
    return twin_cfg
  if contract.program in ("serving_decode", "serving_verify"):
    if not _cfg(contract, "model_shards"):
      return None
    twin_cfg = dict(contract.config)
    twin_cfg.pop("model_shards")
    return twin_cfg
  return None


def rule_partitioner_twin(contract, tracer):
  """ISSUE 17: the gspmd/manual twin referee. A --partitioner=gspmd
  step (or a model-sharded serving decode) is the SAME math lowered
  through GSPMD's propagation instead of the hand-written shard_map
  collectives; the referee traces the manual twin, diffs collective
  inventory + largest live buffer, and classifies
  (partitioner_twin_verdict). Only the ``bug`` class violates --
  equivalent/manual-wins/gspmd-wins are legitimate partitioner
  divergences the report tables for PERF.md."""
  twin_cfg = _twin_manual_config(contract)
  if twin_cfg is None or tracer is None:
    return []
  twin = tracer(twin_cfg, contract.program)
  verdict = partitioner_twin_verdict(contract, twin)
  return [f"gspmd/manual twin divergence classified as a BUG: {msg}"
          for msg in verdict["bugs"]]


def rule_serving_bounded_decode(contract, tracer):
  """Round 18: the serving decode step is a bounded-executable, cache-
  resident program. Binds only on ``serving_decode`` contracts
  (contracts.trace_serving_contract): (a) the decode batch is a
  bucket-ladder member -- the engine may only ever compile ladder
  shapes, which is what bounds the executable set (the e2e half of the
  same invariant pins ledger compiles <= len(ladder),
  tests/test_serving.py); (b) the ring-buffer caches are donated
  (updated in place -- losing the alias doubles serving HBM and breaks
  the AOT call convention); (c) no program buffer reaches the (B, T,
  V) logits tensor's size, and nothing exceeds one KV ring buffer (the
  largest legitimate array) -- a bigger temp is a shape-polymorphic
  materialization leaking into the per-token step."""
  if contract.program != "serving_decode":
    return []
  out = []
  ladder = contract.aux.get("bucket_ladder") or []
  bucket = contract.aux.get("decode_batch")
  if ladder and bucket not in ladder:
    out.append(f"decode batch {bucket} is not a bucket-ladder member "
               f"{ladder} -- an off-ladder shape breaks the bounded "
               "executable set")
  if contract.donated_buffers == 0:
    out.append("KV ring buffers not donated -- the decode step must "
               "update its cache in place (aliasing lost)")
  btv = contract.aux.get("vocab_logits_bytes")
  ring = contract.aux.get("kv_ring_bytes")
  if "kv_pool_bytes" in contract.aux:
    # Paged-KV decode: rule_serving_paged_kv owns the buffer bound for
    # this program shape (one owner per seeded violation) -- the
    # legitimate ceiling there is the page POOL, which must itself sit
    # strictly under the dense ring.
    return out
  # The ring is the largest LEGITIMATE array, so only buffers beyond
  # it are leaks; name the (B, T, V) materialization only when that
  # ceiling genuinely sits above the ring (a small-vocab spec can put
  # btv BELOW the ring -- there the ring bound alone binds, and the
  # ring itself must never fire a false logits violation).
  if ring and contract.largest_tensor_bytes > ring:
    if btv and btv > ring and contract.largest_tensor_bytes >= btv:
      out.append(f"largest decode buffer {contract.largest_tensor_type} "
                 f"({contract.largest_tensor_bytes} B) reaches the "
                 f"(B, T, V) logits tensor ({btv} B) -- the per-token "
                 "step materialized a full-sequence product")
    else:
      out.append(f"largest decode buffer {contract.largest_tensor_type} "
                 f"({contract.largest_tensor_bytes} B) exceeds one KV "
                 f"ring buffer ({ring} B), the largest legitimate "
                 "array in the decode step")
  elif btv and not ring and contract.largest_tensor_bytes >= btv:
    out.append(f"largest decode buffer {contract.largest_tensor_type} "
               f"({contract.largest_tensor_bytes} B) reaches the "
               f"(B, T, V) logits tensor ({btv} B) -- the per-token "
               "step materialized a full-sequence product")
  return out


def rule_serving_paged_kv(contract, tracer):
  """Round 19: the paged-KV decode step's memory bound. Binds on
  ``serving_decode`` contracts whose aux carries ``kv_pool_bytes`` --
  i.e. the spec set ``kv_page_size`` and the cache is a fixed-size
  block pool instead of the dense per-slot ring slab. Two legs: (a)
  the pool itself must sit strictly UNDER the dense ring ceiling
  (``kv_ring_bytes``) -- a pool that reaches the slab it replaces has
  lost paging's whole point (that bound is what lets the engine admit
  more concurrent sessions per HBM byte); (b) no live program buffer
  may reach the dense-slab ceiling either -- a buffer that does is a
  densification leak (e.g. the gather path materializing the
  per-slot (T_max,) view for every slot at once)."""
  if contract.program != "serving_decode":
    return []
  pool = contract.aux.get("kv_pool_bytes")
  if not pool:
    return []
  out = []
  ring = contract.aux.get("kv_ring_bytes")
  if ring and pool >= ring:
    out.append(f"paged KV pool ({pool} B) reaches the dense ring slab "
               f"it replaces ({ring} B) -- the pool must stay strictly "
               "under the dense ceiling or paging buys no concurrency")
  if ring and contract.largest_tensor_bytes >= ring:
    out.append(f"largest paged-decode buffer "
               f"{contract.largest_tensor_type} "
               f"({contract.largest_tensor_bytes} B) reaches the dense "
               f"KV slab ceiling ({ring} B) -- a live buffer at the "
               "slab size is a densification leak in the paged step")
  return out


def rule_serving_verify_bounded(contract, tracer):
  """Round 19: the speculative-decoding verify step scores all k draft
  proposals in ONE prefill-shaped call, with the logits argmax chunked
  (lax.scan over (B, chunk, V) slices). Binds on ``serving_verify``
  contracts: (a) the verify batch is a bucket-ladder member (same
  bounded-executable-set invariant as decode); (b) no program buffer
  reaches the full (B, T, V) logits tensor -- the chunked argmax
  exists precisely so verification never materializes what the fused
  head avoids; the (B, chunk, V) slice (``verify_logits_bytes``) is
  the legitimate ceiling."""
  if contract.program != "serving_verify":
    return []
  out = []
  ladder = contract.aux.get("bucket_ladder") or []
  bucket = contract.aux.get("decode_batch")
  if ladder and bucket not in ladder:
    out.append(f"verify batch {bucket} is not a bucket-ladder member "
               f"{ladder} -- an off-ladder shape breaks the bounded "
               "executable set")
  btv = contract.aux.get("vocab_logits_bytes")
  if btv and contract.largest_tensor_bytes >= btv:
    out.append(f"largest verify buffer {contract.largest_tensor_type} "
               f"({contract.largest_tensor_bytes} B) reaches the "
               f"(B, T, V) logits tensor ({btv} B) -- the chunked "
               "argmax must never materialize the full logits")
  return out


# -- program-shape invariants (every config) ----------------------------------

def rule_no_host_transfer(contract, tracer):
  """The step program must stay device-resident: any infeed/outfeed/
  send/recv would put a host round-trip in the step."""
  if contract.host_transfers:
    return [f"host-transfer ops in the step program: "
            f"{contract.host_transfers}"]
  return []


def rule_state_donated(contract, tracer):
  """TrainState is donated (donate_argnums=(0,)): losing the aliasing
  doubles the state's HBM footprint."""
  if contract.program == "serving_decode":
    # The serving step donates its KV ring, not a TrainState;
    # rule_serving_bounded_decode owns that program shape (one owner
    # per seeded violation).
    return []
  if contract.program == "serving_verify":
    # The verify step is a pure function of (variables, token rows) --
    # it owns no mutable state, so it donates nothing by design.
    return []
  if contract.donated_buffers == 0:
    return ["no input/output buffer aliasing -- the donated TrainState "
            "stopped aliasing (HBM footprint doubles)"]
  return []


def rule_single_optimizer_apply(contract, tracer):
  """Exactly one optimizer apply per step, outside every scan (async-PS
  sequential_apply is the documented exception and is excluded)."""
  vu = _cfg(contract, "variable_update", "replicated")
  if vu == "parameter_server" and not _cfg(contract, "cross_replica_sync",
                                           True):
    return []
  if contract.program != "train_step":
    return []  # the chunked program scans the WHOLE step by design
  out = []
  if not contract.optimizer_apply_present:
    out.append("optimizer_apply scope missing from the step program "
               "(train_step.py's named_scope)")
  elif contract.optimizer_apply_in_loop:
    out.append("optimizer apply inside a scanned body -- the update "
               "must run once per step, after any microbatch scan")
  return out


def rule_full_mesh_replica_groups(contract, tracer):
  """Replicated-family reductions span the full replica mesh as one
  group -- a split group means a silent partial reduction. On a 2-D
  sharded mesh with a model axis, the metric pmeans legitimately span
  the BATCH axis only (M groups of B devices; model-axis peers hold
  identical values), so groups of exactly num_data_replicas are also
  admitted there. Manual programs only: GSPMD derives its own group
  shapes from the sharding propagation (rule_partitioner_twin diffs
  them against the manual twin's)."""
  if not _replicated_sync(contract) or _gspmd(contract):
    return []
  n = contract.aux.get("num_devices")
  if not n:
    return []
  ok_sizes = {n}
  n_data = contract.aux.get("num_data_replicas")
  if _sharded(contract) and n_data:
    ok_sizes.add(n_data)
  want = "{{" + ",".join(str(i) for i in range(n)) + "}}"
  bad = [c for c in contract.collectives
         if c.kind == "all-reduce" and c.replica_groups
         and set(_group_sizes(c.replica_groups)) not in
         [{s} for s in ok_sizes]]
  if bad:
    alt = (f" or {n_data}-wide batch groups" if len(ok_sizes) > 1
           else "")
    return [f"{len(bad)} all-reduce(s) with partial replica groups "
            f"(want {want}{alt}, got e.g. {bad[0].replica_groups})"]
  return []


# -- one-owner meta-audit (ISSUE 20 satellite) --------------------------------

# The "one owner per seeded violation / per program shape" comments
# above, made checkable. Each row declares (owning rule, property,
# binds(contract)): the rule that owns checking `property` on contracts
# where `binds` holds. The stand-down comments in
# rule_accum_one_collective / rule_no_collective_in_loop /
# rule_fsdp_residency / rule_serving_bounded_decode /
# rule_state_donated are the prose versions of these predicates; this
# table is what rule_one_owner enforces, so a future rule (or a widened
# predicate) that silently double-claims a property fails the audit
# with BOTH rule names instead of making the mutation self-tests
# ambiguous about which rule must fire.
OWNERSHIP = [
    ("accum-one-collective", "in-scan-gradient-exchange",
     lambda c: c.program in ("train_step", "train_chunk")
     and not _gspmd(c) and _accum(c) > 1),
    ("no-collective-in-loop", "in-scan-gradient-exchange",
     lambda c: c.program in ("train_step", "train_chunk")
     and not _gspmd(c) and _accum(c) == 1 and _replicated_sync(c)
     and not _fsdp(c)),
    ("partitioner-twin", "in-scan-gradient-exchange",
     lambda c: c.program in ("train_step", "train_chunk")
     and _gspmd(c)),
    ("fsdp-residency", "param-gather-residency",
     lambda c: c.program == "train_step" and _fsdp(c)
     and not _gspmd(c)),
    ("partitioner-twin", "param-gather-residency",
     lambda c: c.program in ("train_step", "train_chunk")
     and _gspmd(c)),
    ("serving-bounded-decode", "decode-buffer-bound",
     lambda c: c.program == "serving_decode"
     and "kv_pool_bytes" not in c.aux),
    ("serving-paged-kv", "decode-buffer-bound",
     lambda c: c.program == "serving_decode"
     and "kv_pool_bytes" in c.aux),
    ("state-donated", "state-donation",
     lambda c: c.program not in ("serving_decode", "serving_verify")),
    ("serving-bounded-decode", "state-donation",
     lambda c: c.program == "serving_decode"),
]


def rule_one_owner(contract, tracer):
  """ISSUE 20 satellite: no golden program shape may have TWO rules
  claiming ownership of the same property (see OWNERSHIP). Runs as an
  ordinary rule so every audited contract is checked; a conflict names
  both rules and the contested property."""
  by_property: Dict[str, set] = {}
  for rule_id, prop, binds in OWNERSHIP:
    if binds(contract):
      by_property.setdefault(prop, set()).add(rule_id)
  out = []
  for prop, owners in sorted(by_property.items()):
    if len(owners) > 1:
      out.append(
          f"property '{prop}' is claimed by {len(owners)} rules on "
          f"this program shape: {sorted(owners)} -- exactly one rule "
          "may own a seeded violation (the mutation self-tests assert "
          "ONE rule fires); tighten the OWNERSHIP predicates")
  return out


# -- resume-time contract re-verification -------------------------------------

def check_resumed_state(state, mesh, sharded_state: bool) -> List[str]:
  """Host-side structural re-verification of a TrainState that was just
  rebuilt onto a (possibly different) mesh -- after an elastic rescale
  or a cross-topology checkpoint restore (benchmark.py calls this at
  both seams; the traced-program half of the same contract lives in the
  ``sharded_rescale`` golden).

  Cheap (shape/dtype reads only, no device work) and deliberately
  strict: a rescale that silently produced a wrong-topology state would
  train -- broadcast semantics make almost any leading dim "work" --
  and corrupt the run long after the seam. Returns problem strings
  (empty = contract holds)."""
  problems = []
  n = int(mesh.devices.size)

  def leading(tree, what):
    for leaf in _tree_leaves(tree):
      shape = tuple(getattr(leaf, "shape", ()))
      if not shape or shape[0] != n:
        problems.append(
            f"{what} leaf shape {shape} does not carry the {n}-row "
            "stacked leading dim of the rebuilt mesh")
        return

  leading(state.params, "params")
  leading(state.batch_stats, "batch_stats")
  if sharded_state:
    for leaf in _tree_leaves(state.opt_state):
      shape = tuple(getattr(leaf, "shape", ()))
      if not shape or shape[0] != n:
        problems.append(
            f"sharded opt_state leaf shape {shape} is not an (n, k) "
            f"shard stack for the {n}-device mesh -- the rescale left "
            "state at the old shard count")
        break
  else:
    leading(state.opt_state, "opt_state")
  if tuple(getattr(state.step, "shape", ())) != ():
    problems.append("step is not a replicated scalar after resume")
  return problems


def _tree_leaves(tree):
  try:
    import jax
    return jax.tree.leaves(tree)
  except Exception:
    return []


RULES: Dict[str, Callable] = {
    "trace-twin": rule_trace_twin,
    "metrics-twin": rule_metrics_twin,
    "accum-one-collective": rule_accum_one_collective,
    "no-collective-in-loop": rule_no_collective_in_loop,
    "no-btv-buffer": rule_no_btv_buffer,
    "health-no-extra-collective": rule_health_no_extra_collective,
    "wire-dtype": rule_wire_dtype,
    "gradient-reduced-once": rule_gradient_reduced_once,
    "partitioner-twin": rule_partitioner_twin,
    "sharded-collectives": rule_sharded_collectives,
    "sharded-opt-bytes": rule_sharded_opt_bytes,
    "fsdp-residency": rule_fsdp_residency,
    "packed-no-overhead": rule_packed_no_overhead,
    "serving-bounded-decode": rule_serving_bounded_decode,
    "serving-paged-kv": rule_serving_paged_kv,
    "serving-verify-bounded": rule_serving_verify_bounded,
    "no-host-transfer": rule_no_host_transfer,
    "state-donated": rule_state_donated,
    "single-optimizer-apply": rule_single_optimizer_apply,
    "full-mesh-replica-groups": rule_full_mesh_replica_groups,
    "one-owner": rule_one_owner,
}


def audit_contract(contract: ProgramContract,
                   tracer: Optional[Callable] = None,
                   rules: Optional[Dict[str, Callable]] = None
                   ) -> List[Violation]:
  """Run every rule over one contract; return machine-readable
  violations. ``tracer(overrides, program) -> ProgramContract`` serves
  the paired rules (health twin); None skips them."""
  out = []
  for rule_id, rule in (rules or RULES).items():
    for msg in rule(contract, tracer):
      out.append(Violation(rule=rule_id, message=msg))
  return out


def make_memo_tracer() -> Callable:
  """A memoizing ``tracer(overrides, program) -> ProgramContract`` so a
  config traced for the audit is not re-compiled for the golden diff
  (or for a paired rule's twin)."""
  from kf_benchmarks_tpu.analysis import contracts as contracts_lib
  memo: Dict[str, ProgramContract] = {}

  def tracer(overrides, program="train_step"):
    key = repr(sorted(overrides.items())) + program
    if key not in memo:
      if program.startswith("serving"):
        # Serving contracts lower through the engine's own AOT recipe
        # (LMSpec overrides), not make_params -- route them so paired
        # rules (the partitioner-twin referee) can trace serving twins
        # through the same memo.
        memo[key] = contracts_lib.trace_serving_contract(
            dict(overrides), program)
      else:
        memo[key] = contracts_lib.trace_contract(dict(overrides), program)
    return memo[key]

  return tracer


def audit_configs(configs: Dict[str, Dict[str, Any]],
                  tracer: Optional[Callable] = None) -> Dict[str, Any]:
  """Trace + audit each named config; returns the machine-readable
  report the CLI emits as JSON."""
  tracer = tracer or make_memo_tracer()
  report = {"configs": {}, "violations": 0}
  for name, overrides in configs.items():
    contract = tracer(dict(overrides), "train_step")
    violations = audit_contract(contract, tracer)
    report["configs"][name] = {
        "config": dict(overrides),
        "violations": [v.as_dict() for v in violations],
        "collectives": len(contract.collectives),
        "in_loop_collectives": len(contract.in_loop_collectives()),
        "gradient_collectives": len(contract.gradient_collectives()),
    }
    twin_cfg = _twin_manual_config(contract)
    if twin_cfg is not None:
      # The referee's full verdict rides the report (PERF.md's twin
      # inventory-diff table is generated from it); only the "bug"
      # class fed report["violations"] above.
      report["configs"][name]["partitioner_twin"] = (
          partitioner_twin_verdict(contract,
                                   tracer(twin_cfg, contract.program)))
    report["violations"] += len(violations)
  return report
