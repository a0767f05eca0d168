"""Program-contract extraction: trace a config's train step, never run it.

The single source of the HLO-scraping conventions the test suite pins
against (tests/test_grad_accum.py, tests/test_telemetry.py,
tests/test_fsdp.py):

* an *all-reduce definition* is an instruction-definition line matching
  :data:`ALL_REDUCE_DEF` (``-start`` covers async pairs);
* a collective is *in the backward loop* when its jax ``op_name``
  metadata places it inside a scanned (``while``) body -- the backward
  of a lax.scan/nn.scan lowers to a while loop, and a collective issued
  by an in-loop hook (FSDP's per-block gather) carries the loop in its
  op_name;
* *gradient traffic* is the non-scalar all-reduce
  (:data:`GRAD_MIN_ELEMS` guards the packed health/metric vectors);
  ``f32[]`` reductions are the step's metric pmeans.

On top of the shared helpers, :func:`trace_contract` builds a config's
step program exactly as the runtime does (``BenchmarkCNN._build``),
lowers it over the abstract 8-device mesh with ``jax.eval_shape`` +
``jit(...).lower(...)`` -- no train step ever executes, only XLA
compilation runs -- and extracts a :class:`ProgramContract` that
``audit`` checks and ``baseline`` diffs against goldens.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import OrderedDict
from typing import Any, Dict, List, Optional

# -- shared HLO-scraping helpers (the tests import these) ---------------------

# The result type is one array type or, for a variadic all-reduce, a
# parenthesized tuple of them.
ALL_REDUCE_DEF = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+all-reduce(-start)?\(")


def compile_for_audit(lowered):
  """Compile a lowered program for collective-structure inspection.

  XLA:CPU's all-reduce combiner is switched off: the dump then shows
  the collectives the PROGRAM places (one per bucket, inside or outside
  a scan body), not what one backend's combiner merges them into --
  the property every count/placement rule and HLO pin checks."""
  return lowered.compile(compiler_options={
      "xla_disable_hlo_passes": "cpu-all-reduce-combiner"})


# A non-scalar all-reduce below this element count is a packed
# metric/health vector (telemetry packs ~10 floats onto the loss pmean),
# not gradient traffic; every real gradient bucket is far larger.
GRAD_MIN_ELEMS = 128


def all_reduce_defs(hlo: str) -> List[str]:
  """All-reduce instruction definition lines of a compiled-HLO dump."""
  return [ln for ln in hlo.splitlines() if ALL_REDUCE_DEF.search(ln)]


_SCALAR_ALL_REDUCE = re.compile(r"=\s+\w+\[\]\s+all-reduce")


def grad_all_reduce_defs(hlo: str):
  """(all defs, gradient defs): gradient traffic is the non-scalar
  all-reduce; ``f32[]`` reductions are the step's metric pmeans.

  Intentionally LOOSER than :meth:`Collective.is_gradient_traffic`:
  no :data:`GRAD_MIN_ELEMS` floor, because the test pins that import
  this helper drive tiny toy models whose real gradient buckets can be
  under the floor, and their programs carry no packed health vector to
  exclude. The auditor's real-config predicate needs the floor; keep
  the two in mind if a pin ever mixes health stats with this helper."""
  defs = all_reduce_defs(hlo)
  grad = [ln for ln in defs if not _SCALAR_ALL_REDUCE.search(ln)]
  return defs, grad


# -- structured contract ------------------------------------------------------

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")
_COLLECTIVE_DEF = re.compile(
    r"=\s+(?P<type>[^\s].*?)\s+"
    r"(?P<kind>" + "|".join(_COLLECTIVE_KINDS) + r")(?P<start>-start)?\(")
_ARRAY_TYPE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)"
    r"\[([0-9,]*)\]")
_REPLICA_GROUPS = re.compile(r"replica_groups=(\{\{[0-9, ]*(?:\},\{[0-9, ]*)*\}\})")
_CUSTOM_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_ALIAS_ENTRY = re.compile(r"(?:may|must)-alias")
_HOST_TRANSFER_KINDS = ("infeed", "outfeed", " send(", " recv(",
                        "send-done", "recv-done")

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "c64": 8, "f64": 8,
             "s64": 8, "u64": 8, "c128": 16}

# A stablehlo collective's result type in LOWERED (pre-optimization)
# text: "... }) : (tensor<4101097xbf16>) -> tensor<4101097xbf16>". The
# wire dtype must be read here: XLA:CPU legalizes 16-bit collectives to
# f32 during compilation, so the COMPILED dump shows the backend's
# wire, not the program's requested one (which is what the TPU runs).
def _stablehlo_result_types(lowered_text: str, op: str):
  pat = re.compile(r'"stablehlo\.%s".*?-> tensor<([0-9a-z_]+)>' % op,
                   re.S)
  out = []
  for spec in pat.findall(lowered_text):
    parts = spec.split("x")
    dtype = parts[-1]
    elems = math.prod(int(d) for d in parts[:-1]) if len(parts) > 1 else 1
    out.append((dtype, elems))
  return out


def requested_all_reduce_wires(lowered_text: str):
  """[(dtype, elems), ...] of every all_reduce in a lowered module."""
  return _stablehlo_result_types(lowered_text, "all_reduce")


def requested_collective_wires(lowered_text: str):
  """{kind: sorted wire dtypes of non-scalar ops} at the LOWERED level
  for the sharded path's collective mix (reduce_scatter / all_gather /
  all_reduce) -- read here for the same reason as
  :func:`requested_all_reduce_wires`: XLA:CPU legalizes 16-bit
  collectives to f32 while compiling, so the compiled dump shows the
  backend's wire, not the program's requested (TPU) one."""
  out = {}
  for op in ("all_reduce", "reduce_scatter", "all_gather"):
    dtypes = sorted({dtype for dtype, elems
                     in _stablehlo_result_types(lowered_text, op)
                     if elems > 1})
    if dtypes:
      out[op.replace("_", "-")] = dtypes
  return out


def _array_bytes(dtype: str, dims: str) -> int:
  elems = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
  return elems * _ITEMSIZE[dtype]


@dataclasses.dataclass
class Collective:
  """One collective instruction of the compiled step program."""
  kind: str            # all-reduce | all-gather | reduce-scatter | ...
  dtype: str           # wire dtype of the (first) array operand
  elems: int           # element count (1 for scalars)
  scalar: bool
  in_loop: bool        # inside a scanned (while) body
  replica_groups: str  # "" when the kind has none (collective-permute)
  # Position in the compiled dump's definition order (the ORDERED
  # schedule the SPMD divergence pass compares; -1 for hand-built
  # Collectives that never went through extract_contract).
  index: int = -1
  # Issued under the step's ``exchange`` scope (op_name metadata): the
  # gradient exchange and the batch-statistics sync, apart from the
  # metric reductions (audit.rule_gradient_reduced_once).
  in_exchange: bool = False

  def is_gradient_traffic(self) -> bool:
    return (self.kind == "all-reduce" and not self.scalar
            and self.elems >= GRAD_MIN_ELEMS)

  def schedule_entry(self) -> Dict[str, Any]:
    """The golden-worthy row of the ordered collective schedule: every
    field two ranks must agree on for the programs to rendezvous
    (kind, wire dtype, scalar/tensor rank, loop placement), plus the
    replica-group SIZES (arity) -- group member ids are topology
    labels, not schedule structure -- and the position index."""
    inner = self.replica_groups.strip().strip("{}")
    sizes = ([len([t for t in grp.split(",") if t.strip() != ""])
              for grp in inner.split("},{")] if inner else [])
    return {
        "index": self.index, "kind": self.kind, "dtype": self.dtype,
        "rank": "scalar" if self.scalar else "tensor",
        "placement": "in_loop" if self.in_loop else "top_level",
        "group_sizes": sizes,
    }


@dataclasses.dataclass
class ProgramContract:
  """Structured statics of one compiled step program."""
  config: Dict[str, Any]          # the param overrides that produced it
  program: str                    # "train_step" | "train_chunk"
  collectives: List[Collective]
  host_transfers: List[str]       # infeed/outfeed/send/recv kinds found
  custom_call_targets: List[str]  # informational (backend-dependent)
  optimizer_apply_present: bool   # train_step.py's named_scope found
  optimizer_apply_in_loop: bool   # ... inside a while body
  donated_buffers: int            # input_output_alias entry count
  largest_tensor_bytes: int       # biggest single array in the program
  largest_tensor_type: str        # e.g. "f32[4096,1001]"
  temp_bytes: Optional[int]       # memory_analysis().temp_size_in_bytes
  aux: Dict[str, Any] = dataclasses.field(default_factory=dict)

  def gradient_collectives(self) -> List[Collective]:
    return [c for c in self.collectives if c.is_gradient_traffic()]

  def in_loop_collectives(self) -> List[Collective]:
    return [c for c in self.collectives if c.in_loop]

  def collective_schedule(self) -> List[Dict[str, Any]]:
    """The ORDERED collective schedule (ISSUE 20 leg a): one
    :meth:`Collective.schedule_entry` row per collective, in compiled-
    dump definition order. Two programs with identical unordered
    inventories can still deadlock each other cross-rank when their
    schedules differ -- the inventory is a multiset, the schedule is
    the rendezvous order; analysis/spmd.py compares these."""
    return [c.schedule_entry() for c in self.collectives]


def extract_contract(hlo: str, config: Optional[dict] = None,
                     program: str = "train_step",
                     temp_bytes: Optional[int] = None,
                     aux: Optional[dict] = None) -> ProgramContract:
  """Parse a compiled-HLO text dump into a :class:`ProgramContract`.

  Pure text analysis (no jax): tests feed hand-built programs through
  this to seed violations the audit rules must catch.
  """
  collectives = []
  host_transfers = []
  for ln in hlo.splitlines():
    m = _COLLECTIVE_DEF.search(ln)
    if m:
      arr = _ARRAY_TYPE.search(m.group("type"))
      dtype, dims = (arr.group(1), arr.group(2)) if arr else ("f32", "")
      elems = (math.prod(int(d) for d in dims.split(",") if d)
               if dims else 1)
      groups = _REPLICA_GROUPS.search(ln)
      op_name = ln.partition('op_name="')[2].partition('"')[0]
      collectives.append(Collective(
          kind=m.group("kind"), dtype=dtype, elems=elems,
          # Inside a scanned body by where it was TRACED (its op_name),
          # not by its operands: a top-level combined all-reduce whose
          # operands are a ``%while``'s outputs (a scanned stack's
          # gradients, one device) is not in the loop.
          scalar=not dims, in_loop="while" in (op_name or ln),
          replica_groups=groups.group(1).replace(" ", "") if groups
          else "", index=len(collectives),
          in_exchange="/exchange/" in ln.partition("op_name=")[2]))
    # Only the instruction text counts (op_name metadata may quote a
    # jax scope containing e.g. 'send' without the op being one).
    head = ln.split("metadata")[0]
    for kind in _HOST_TRANSFER_KINDS:
      if kind in head and "=" in head:
        host_transfers.append(kind.strip().strip("("))
  opt_lines = [ln for ln in hlo.splitlines() if "optimizer_apply" in ln]
  largest_bytes, largest_type = 0, ""
  for dtype, dims in _ARRAY_TYPE.findall(hlo):
    b = _array_bytes(dtype, dims)
    if b > largest_bytes:
      largest_bytes, largest_type = b, f"{dtype}[{dims}]"
  return ProgramContract(
      config=dict(config or {}), program=program,
      collectives=collectives, host_transfers=sorted(set(host_transfers)),
      custom_call_targets=sorted(set(_CUSTOM_CALL_TARGET.findall(hlo))),
      optimizer_apply_present=bool(opt_lines),
      optimizer_apply_in_loop=any("while" in ln for ln in opt_lines),
      donated_buffers=len(_ALIAS_ENTRY.findall(hlo)),
      largest_tensor_bytes=largest_bytes, largest_tensor_type=largest_type,
      temp_bytes=temp_bytes, aux=dict(aux or {}))


# -- config -> contract (trace, never execute) --------------------------------

N_REPLICAS = 8  # the abstract mesh every golden traces on (conftest's)


def lower_step_program(bench, program: str = "train_step"):
  """Lower (never execute) a built runtime's step program over abstract
  ``ShapeDtypeStruct`` inputs -- the one build+lower recipe shared by
  :func:`trace_contract` and the autotuner's warm pass (the warm pass
  compiles the result against the persistent XLA cache). The abstract
  state and batch carry the shardings the runtime gives the real ones:
  the persistent cache keys on them, so this lowering makes the entry a
  later run's dispatch finds (the cache's own hit events say so,
  tests/test_autotune.py). Returns ``(state_sds, lowered)``."""
  import jax
  from kf_benchmarks_tpu.parallel import mesh as mesh_lib
  fns = bench._build()
  init_state, train_step, train_chunk = fns[0], fns[1], fns[4]
  in_shapes = bench.model.get_input_shapes("train")
  in_dtypes = bench.model.get_input_data_types("train")
  sample = jax.ShapeDtypeStruct(tuple(in_shapes[0]), in_dtypes[0])
  state_sds = init_state.eval_shape(jax.random.PRNGKey(0), sample)
  chunk = program == "train_chunk"
  if chunk and train_chunk is None:
    raise ValueError("train_chunk requested but --steps_per_dispatch=1")
  n = bench.num_devices
  # Global batch follows the DATA-parallel width (model-axis peers of a
  # 2-D mesh re-compute the same shard; == n on 1-D meshes). A synthetic
  # resident chunk has a leading staged-steps axis of 1.
  n_data = int(getattr(bench, "num_data_replicas", n))
  lead = (1,) if chunk else ()
  sharding = (mesh_lib.chunk_batch_sharding(bench.mesh) if chunk
              else mesh_lib.batch_sharding(bench.mesh))
  gx, gy = (
      jax.ShapeDtypeStruct(lead + (shape[0] * n_data,) + tuple(shape[1:]),
                           dtype, sharding=sharding)
      for shape, dtype in zip(in_shapes[:2], in_dtypes[:2]))
  step_fn = train_chunk if chunk else train_step
  return state_sds, step_fn.lower(state_sds, gx, gy)


def trace_contract(overrides: Dict[str, Any],
                   program: str = "train_step") -> ProgramContract:
  """Build + lower + compile the step program for ``overrides``; extract.

  Mirrors the runtime exactly (``BenchmarkCNN._build``), but the state
  is ``jax.eval_shape``-abstract and inputs are ``ShapeDtypeStruct``s:
  nothing executes, only XLA compilation runs. Requires the 8-device
  CPU mesh (tests get it from conftest; the CLI sets XLA_FLAGS).
  """
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  from kf_benchmarks_tpu import train_step as train_step_lib

  kw = dict(device="cpu", num_devices=N_REPLICAS, num_batches=2)
  kw.update(overrides)
  p = params_lib.make_params(**kw)
  bench = benchmark.BenchmarkCNN(p)
  # The step states its factor-exchange counter to the active run trace
  # while it is traced; a session of this trace's own catches it.
  with tracing.session() as session:
    state_sds, lowered = lower_step_program(bench, program)
  in_shapes = bench.model.get_input_shapes("train")
  n = bench.num_devices
  n_data = int(getattr(bench, "num_data_replicas", n))
  compiled = compile_for_audit(lowered)
  # The step's own plan, from the training module as the step builder
  # makes it: which exchange the rules hold the program to, and FSDP's
  # template, prefixes and bucket bound.
  plan = train_step_lib.plan_step(
      bench.strategy, bench.params, bench.mesh, bench.model,
      module=bench.model.make_module(
          nclass=bench.dataset.num_classes, phase_train=True,
          data_format=bench.params.data_format,
          dtype=bench.compute_dtype, param_dtype=bench.param_dtype))

  aux: Dict[str, Any] = {
      "model": bench.model.get_name(),
      "exchange": plan.exchange.value,
      "num_devices": n,
      "num_data_replicas": n_data,
      "per_device_batch": int(in_shapes[0][0]),
      "health_stats": bool(bench.params.health_stats),
      # Gradient wire dtypes the PROGRAM requests (lowered level; the
      # compiled CPU dump legalizes 16-bit collectives to f32).
      "requested_grad_wires": sorted({
          dtype for dtype, elems in requested_all_reduce_wires(
              lowered.as_text())
          if elems >= GRAD_MIN_ELEMS}),
  }
  # audit.rule_gradient_reduced_once: the elements of one replica's
  # gradient tree and batch statistics (the leading dim of the abstract
  # state is the replica stack), and the dense kernels that took the
  # factor data plane of the mean gradient (parallel/kungfu.py).
  per_replica_elems = lambda tree: sum(
      int(math.prod(l.shape[1:])) for l in jax.tree_util.tree_leaves(tree))
  factor = session.static("factor_exchange") or {}
  aux["gradient_elems"] = per_replica_elems(state_sds.params)
  aux["batch_stats_elems"] = per_replica_elems(state_sds.batch_stats)
  aux["factor_layers"] = int(factor.get("layers", 0))
  aux["factor_elems"] = (int(factor.get("bytes_off_allreduce", 0))
                         // jnp.dtype(bench.param_dtype).itemsize)
  # --shard_optimizer_state contract inputs (audit.rule_sharded_*): the
  # requested reduce-scatter/all-gather wire dtypes, and the per-device
  # optimizer-state bytes read from the ABSTRACT state -- exactly what
  # each device will hold, one row of every (n, k) shard stack.
  if bool(getattr(bench.params, "shard_optimizer_state", False)):
    aux["sharded_state"] = True
    aux["requested_collective_wires"] = requested_collective_wires(
        lowered.as_text())
  # --shard_params contract inputs (audit.rule_fsdp_residency): the
  # full-tree parameter bytes (the residency denominator), the planned
  # step-level gather-bucket count (what the out-of-loop all-gather
  # inventory must not exceed), and the module-gathered scanned
  # prefixes (whose per-block gathers must sit INSIDE the scan body).
  if bool(getattr(bench.params, "shard_params", False)):
    from kf_benchmarks_tpu.ops import sharded as sharded_lib
    aux["fsdp_params"] = True
    template = plan.fsdp_template
    aux["fsdp_scan_prefixes"] = list(plan.fsdp_prefixes)
    aux["fsdp_param_full_bytes"] = sharded_lib.fsdp_param_bytes(template)
    buckets, _ = sharded_lib.fsdp_plan_buckets(
        template, plan.fsdp_bucket_bytes,
        exclude_prefixes=plan.fsdp_prefixes)
    aux["fsdp_step_gathers"] = len(buckets)
    # Exact planned bytes of the largest step-level gather RESULT
    # (bucket leaves re-assemble as n * ceil(size/n) elements each):
    # the per-gather residency bound rule_fsdp_residency admits --
    # models whose tree is dominated by ONE layer (trivial's 1001-way
    # head) legitimately gather more than half the tree in that
    # layer's bucket.
    t_flat = jax.tree_util.tree_leaves(template)
    def _gather_bytes(idxs):
      total = 0
      for i in idxs:
        leaf = t_flat[i]
        size = int(math.prod(leaf.shape)) if leaf.shape else 1
        total += n * (-(-size // n)) * jnp.dtype(leaf.dtype).itemsize
      return total
    aux["fsdp_max_gather_bytes"] = max(
        (_gather_bytes(b) for b in buckets), default=0)
    aux["fsdp_engaged"] = (
        plan.exchange is train_step_lib.Exchange.FSDP_IN_BACKWARD)
  # Shape/dtype-based, so the ONE accounting serves both the bench
  # JSON field (concrete arrays) and this abstract state.
  aux["opt_state_bytes_per_device"] = benchmark.opt_state_bytes_per_device(
      state_sds.opt_state)
  # The (B, T, V) bound the fused-head LM contract is checked against:
  # the bytes of the logits tensor the program must NOT materialize.
  if bench.model.get_name() == "transformer_lm":
    from kf_benchmarks_tpu.models import transformer_lm as lm
    itemsize = jnp.dtype(bench.compute_dtype).itemsize
    aux["btv_bytes"] = int(in_shapes[0][0]) * lm.SEQ_LEN * lm.VOCAB * itemsize
  elif bench.model.get_name() == "mla_moe_lm":
    # Two losses through one head kernel (ops/fused_loss.py): neither
    # may hold the logits of the whole batch over the rows held.
    itemsize = jnp.dtype(bench.compute_dtype).itemsize
    aux["btv_bytes"] = (int(in_shapes[0][0]) * int(in_shapes[0][1]) *
                        bench.model.cfg.vocab_rows * itemsize)

  # Static flop count (the cost-analysis surface the --tfprof_file dump
  # reads): the autotuner's cost model consumes it from the aux; absent
  # on backends without cost analysis. Not part of the golden
  # fingerprint (baseline.contract_fingerprint reads named aux keys).
  try:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
      cost = cost[0] if cost else {}
    flops = dict(cost or {}).get("flops")
    if flops is not None and math.isfinite(float(flops)):
      aux["flops"] = float(flops)
  except Exception:  # backend-dependent surface
    pass
  temp = None
  try:
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
  except Exception:  # backend without memory analysis
    temp = None
  return extract_contract(compiled.as_text(), config=dict(overrides),
                          program=program, temp_bytes=temp, aux=aux)


# -- the golden lattice -------------------------------------------------------

# Every earned program-level contract, sampled across the flag lattice.
# Keys are the golden names (tests/golden_contracts/<name>.json); values
# are make_params overrides on top of the cpu/8-device/trivial defaults.
GOLDEN_CONFIGS: "OrderedDict[str, Dict[str, Any]]" = OrderedDict([
    # The monolithic default program (the PERF.md envelope).
    ("base", dict(model="trivial", batch_size=4)),
    # PR 2: --num_grad_accum pays ONE packed gradient collective per
    # step, outside the microbatch scan (agg packing makes "one"
    # literal, as in tests/test_grad_accum.py).
    ("accum4_packed", dict(model="trivial", batch_size=4, num_grad_accum=4,
                           agg_small_grads_max_bytes=1 << 30,
                           agg_small_grads_max_group=1000)),
    # PR 3 satellite: the f32-training bf16 wire opt-in
    # (--compact_gradient_transfer_f32) on a packed reducer (the
    # small-gradient aggregation of accum4_packed, one step): the ONE
    # golden in which audit.rule_wire_dtype's 16-bit leg engages.
    ("packed_bf16_wire", dict(model="trivial", batch_size=4,
                              agg_small_grads_max_bytes=1 << 30,
                              agg_small_grads_max_group=1000,
                              compact_gradient_transfer_f32=True)),
    # PR 4: in-step health stats ride the loss pmean (no new collective).
    ("health", dict(model="trivial", batch_size=4, health_stats=True)),
    # PR 2: the scanned fused-head LM never materializes (B, T, V).
    ("lm_base", dict(model="transformer_lm", batch_size=8)),
    # PR 6: ZeRO sharded optimizer state on the named 2-D mesh
    # (--shard_optimizer_state resolves an 8x1 ('batch', 'model') mesh
    # here): gradients meet in reduce-scatter, params return by
    # all-gather, NO full-gradient all-reduce, per-device opt state
    # ~|state|/n (audit.rule_sharded_collectives / _opt_bytes).
    # (momentum, not the sgd default: sgd's only slot is a schedule
    # count, which would leave the ZeRO memory bound vacuous.)
    ("sharded_base", dict(model="trivial", batch_size=4,
                          optimizer="momentum",
                          shard_optimizer_state=True)),
    # PR 6: composition with --num_grad_accum -- the microbatch scan
    # still pays its reductions once per STEP, now as the scatter.
    ("sharded_accum", dict(model="trivial", batch_size=4,
                           num_grad_accum=4, optimizer="momentum",
                           shard_optimizer_state=True)),
    # PR 6: the scanned fused-head LM under sharded state -- the
    # (B, T, V) bound and the sharded collective mix must hold at once.
    ("lm_sharded", dict(model="transformer_lm", batch_size=8,
                        optimizer="momentum",
                        shard_optimizer_state=True)),
    # PR 8 (round 13): the packed-sequence LM program. Segment-aware
    # masks + the weighted chunked loss must keep the program class:
    # still no (B, T, V) logits buffer, and the token-weighted metric
    # combine PACKS the loss pmeans into one vector, so the packed
    # step carries no more collectives than lm_base
    # (audit.rule_packed_no_overhead).
    ("lm_packed", dict(model="transformer_lm", batch_size=8,
                       packed_sequences=True)),
    # PR 7: the elastic-rescale RESUME shape -- sharded_base after an
    # 8 -> 4 resize (the program benchmark.py rebuilds at the new mesh
    # and resumes into from the resliced checkpoint). Every sharded
    # rule re-checks at n=4: 4-wide scatter groups, full-4-device
    # gathers, no full-gradient all-reduce -- so a resumed run's
    # program shape is golden-pinned, not just the original's.
    ("sharded_rescale", dict(model="trivial", batch_size=4,
                             num_devices=4, optimizer="momentum",
                             shard_optimizer_state=True)),
    # PR 10 (round 15): full FSDP (--shard_params). The CNN shape:
    # params live as (n, k) shard stacks, every builder-layer bucket
    # re-assembles with ONE packed all-gather at the loss top whose
    # backward reduce-scatters the bucket cotangent, the optimizer
    # applies on the shard, and the round-11 trailing full-tree
    # all-gather is GONE (audit.rule_fsdp_residency: out-of-loop
    # gather count == planned bucket count, every gather < half the
    # full tree).
    ("fsdp_base", dict(model="trivial", batch_size=4,
                       optimizer="momentum",
                       shard_optimizer_state=True, shard_params=True)),
    # PR 10: the scanned fused-head LM under full FSDP -- the per-
    # block parameter gather sits INSIDE the nn.scan while body (under
    # remat: the backward re-gathers in the loop too), the scanned
    # stack never materializes whole, and the (B, T, V) bound plus the
    # sharded collective mix must hold at once.
    ("fsdp_lm", dict(model="transformer_lm", batch_size=8,
                     optimizer="momentum",
                     shard_optimizer_state=True, shard_params=True)),
    # PR 9 (round 14): the twin-trace rule's anchor. Run tracing
    # (--trace_events_file, tracing.py) is HOST-ONLY by contract: the
    # trace-on step program must be STRUCTURALLY IDENTICAL to the
    # trace-off one (audit.rule_trace_twin diffs the full fingerprint
    # against the twin without the flag -- the same paired-trace
    # pattern as rule_health_no_extra_collective, but exact identity
    # rather than a collective-count bound). The path is never opened
    # during tracing (the span session lives in the train LOOP, not
    # the step program).
    ("traced", dict(model="trivial", batch_size=4,
                    trace_events_file="trace_events.json")),
    # PR 11 (round 16): the metrics-twin rule's anchor. The metric
    # registry + live /metrics endpoint (--metrics_port, metrics.py)
    # and the run-record store (--run_store_dir) are HOST-ONLY by the
    # same contract as tracing: the metrics-on step program must be
    # STRUCTURALLY IDENTICAL to the metrics-off twin
    # (audit.rule_metrics_twin). No socket is bound during tracing --
    # the endpoint lives in the train LOOP, not the step program.
    ("metrics_on", dict(model="trivial", batch_size=4,
                        metrics_port=9309,
                        run_store_dir="run_store")),
    # ISSUE 17 (round 20): the GSPMD twin lattice. Each entry is an
    # existing sharded golden's config plus --partitioner=gspmd: the
    # SAME per-replica step function lowered under plain jit with
    # NamedSharding-annotated state on the same ('batch', 'model')
    # mesh, letting the XLA SPMD partitioner insert the collectives
    # the manual shard_map programs write by hand (train_step.py
    # _gspmd_wrap). The twin referee (audit.rule_partitioner_twin)
    # traces each one's manual twin (config minus the flag), diffs
    # collective inventory + largest live buffer, and classifies the
    # divergence -- only the "bug" class violates; the full verdict
    # rides the report for PERF.md's inventory-diff table. Losses are
    # bit-identical between the twins (tests/test_partitioner.py).
    ("gspmd_sharded_base", dict(model="trivial", batch_size=4,
                                optimizer="momentum",
                                shard_optimizer_state=True,
                                partitioner="gspmd")),
    ("gspmd_fsdp_base", dict(model="trivial", batch_size=4,
                             optimizer="momentum",
                             shard_optimizer_state=True,
                             shard_params=True,
                             partitioner="gspmd")),
    ("gspmd_lm_sharded", dict(model="transformer_lm", batch_size=8,
                              optimizer="momentum",
                              shard_optimizer_state=True,
                              partitioner="gspmd")),
    # The accum twin: the once-per-step gradient exchange must stay
    # OUT of the microbatch scan on the gspmd side too -- the
    # referee's in-loop-gradient bug leg binds here (and the mutation
    # self-test seeds exactly that regression).
    ("gspmd_accum", dict(model="trivial", batch_size=4,
                         optimizer="momentum",
                         shard_optimizer_state=True,
                         num_grad_accum=2,
                         partitioner="gspmd")),
    # PR 27: the config-driven latent-attention mixture-of-experts
    # decoder at its tiny preset (models/lm_configs/tiny.json), one
    # device: no host transfer, the state donated (the router state in
    # batch_stats included), ONE optimizer apply (the router bias moves
    # by its own rule outside it), and no (B, T, V) logits in either of
    # its two losses (audit.rule_no_btv_buffer).
    ("moe_lm", dict(model="mla_moe_lm", lm_config="tiny", seq_len=64,
                    batch_size=2, num_devices=1, optimizer="adam")),
])


# -- serving-path contracts (round 18) ----------------------------------------

# Serving goldens trace the ENGINE's decode-step program (never a train
# step): overrides are serving/decode.LMSpec fields plus the decode
# bucket. The production (fast 1-row attention) program at the zoo
# transformer_lm's real dims -- the shape the engine AOT-compiles per
# ladder bucket and the bounded-executable rule binds against
# (audit.rule_serving_bounded_decode).
SERVING_GOLDEN_CONFIGS: "OrderedDict[str, Dict[str, Any]]" = OrderedDict([
    ("serving_decode", dict(bucket=4)),
    # Decode-cost variants (ISSUE 16): each leg's program pinned at the
    # same bucket so a variant regression (e.g. dequantize hoisted out
    # of the step, or the paged gather collapsing back to a dense
    # slab) diffs against ITS OWN golden, not serving_decode's.
    ("serving_decode_int8", dict(bucket=4, quantize="int8")),
    ("serving_decode_paged", dict(bucket=4, kv_page_size=128)),
    # The speculative TARGET's verify program (prefill-shaped full
    # forward + chunked argmax; program="serving_verify" routes the
    # tracer to verify_lowering_args).
    ("serving_verify", dict(bucket=4, speculative_k=4,
                            draft_n_layers=2,
                            program="serving_verify")),
    # ISSUE 17 (round 20): the tensor-parallel decode twin -- the same
    # bucket-4 decode step lowered with Megatron-style NamedShardings
    # over a 2-device ('model',) mesh (decode.tp_shardings: KV cache
    # sharded on the head axis, attention/MLP kernels column/row-
    # parallel) and GSPMD inserting the block reductions. The twin
    # referee (audit.rule_partitioner_twin) diffs it against
    # serving_decode's program and classifies; the compiled HLO is the
    # per-partition module, so buffer bounds here are per-shard.
    ("serving_decode_tp", dict(bucket=4, model_shards=2)),
])


def trace_serving_contract(overrides: Dict[str, Any],
                           program: str = "serving_decode"
                           ) -> ProgramContract:
  """Lower + compile (never execute) a serving program for an LMSpec
  override dict; extract its contract.

  Mirrors the engine's AOT path exactly (serving/engine._decode_exe /
  _verify_exe: jit + donation + lower + compile over abstract
  ShapeDtypeStructs), so the golden pins the program the engine will
  actually cache per bucket. A ``program`` key in ``overrides`` routes
  the trace (``serving_decode`` -> the decode step,
  ``serving_verify`` -> the speculative verify forward) -- that is how
  the golden table encodes per-program entries."""
  import dataclasses as _dc

  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu.serving import decode as decode_lib
  from kf_benchmarks_tpu.serving import engine as engine_lib

  kw = dict(overrides)
  program = kw.pop("program", program)
  bucket = int(kw.pop("bucket", 4))
  field_names = {f.name for f in _dc.fields(decode_lib.LMSpec)}
  unknown = sorted(set(kw) - field_names)
  if unknown:
    raise ValueError(f"unknown LMSpec override(s) {unknown}; have "
                     f"{sorted(field_names)}")
  spec = decode_lib.LMSpec(**kw)
  # The engine's OWN lowering recipes (decode.decode_lowering_args /
  # verify_lowering_args are the single source), so this golden pins
  # the program the engine actually caches per bucket.
  if program == "serving_verify":
    fn, args, donate = decode_lib.verify_lowering_args(spec, bucket)
  else:
    fn, args, donate = decode_lib.decode_lowering_args(spec, bucket)
  compiled = compile_for_audit(
      decode_lib.aot_jit(spec, fn, program, bucket, donate).lower(*args))
  itemsize = jnp.dtype(spec.dtype).itemsize
  aux: Dict[str, Any] = {
      "bucket_ladder": list(engine_lib.DEFAULT_BUCKET_LADDER),
      "decode_batch": bucket,
      # One DENSE ring buffer's bytes (k or v; the largest LEGITIMATE
      # array in the dense decode program) -- the residency bound the
      # bounded-executable rule admits. Anything bigger is a leak
      # (e.g. a (B, T, V) logits buffer: vocab_logits_bytes below).
      # For paged programs this is the ceiling the pool must stay
      # strictly UNDER (rule serving-paged-kv).
      "kv_ring_bytes": (spec.n_layers * bucket * spec.max_len *
                        spec.n_heads * spec.head_dim * itemsize),
      "vocab_logits_bytes": bucket * spec.max_len * spec.vocab * itemsize,
  }
  if spec.kv_page_size:
    aux["kv_page_size"] = spec.kv_page_size
    aux["kv_pool_bytes"] = (
        spec.n_layers * decode_lib.kv_pool_pages(spec, bucket) *
        spec.kv_page_size * spec.n_heads * spec.head_dim * itemsize)
  if program == "serving_verify":
    # The verify program's own residency bound: its chunked argmax
    # head must keep every live logits buffer under the dense
    # (B, T, V) tensor (rule serving-verify-bounded).
    aux["verify_chunk"] = decode_lib.verify_chunk(spec)
    aux["verify_logits_bytes"] = (
        bucket * decode_lib.verify_chunk(spec) * spec.vocab * itemsize)
  temp = None
  try:
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
  except Exception:  # backend without memory analysis
    temp = None
  return extract_contract(compiled.as_text(), config=dict(overrides),
                          program=program, temp_bytes=temp, aux=aux)
