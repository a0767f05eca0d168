"""Request-driven serving engine: continuous batching + admission control.

Host-side half of the serving path (device programs: decode.py). The
reference never had a request path at all (its serving story is the
frozen forward-only loop, ref: benchmark_cnn.py:2405-2525); this engine
turns request ARRIVALS into device throughput:

* **Bounded executable set** -- decode/prefill programs exist only at
  bucket-ladder batch widths (default 1/4/16/64/256), AOT-compiled via
  ``jit(...).lower(...).compile()`` once per bucket and cached keyed on
  ``analysis/baseline.config_fingerprint_key``; every compile lands in
  the run-trace compile ledger, which is how the e2e test pins
  "<= len(ladder) decode compiles across a mixed-length replay"
  (tests/test_serving.py).
* **Continuous in-flight batching** -- freed slots refill from the
  queue every decode step (``batching='continuous'``); the A/B arm
  ``'static'`` is classic batch-and-drain: admit a wave, decode it to
  completion, only then admit again (experiments/serving_sweep.py
  measures the p99-TTFT gap between the two at fixed offered load).
* **SLO-aware admission** -- queue-depth rejection at submit,
  TTFT-deadline expiry at coalesce time, and a per-tenant token-bucket
  budget; rejected/expired requests are first-class results and
  ``serving/*`` metrics, never exceptions.
* **Decode-cost variants** (ISSUE 16; composable, all spec-driven) --
  ``spec.quantize='int8'`` serves per-channel INT8 weights dequantized
  inside the compiled step; ``spec.kv_page_size`` runs the paged KV
  pool with this engine as the page ALLOCATOR (pages granted for a
  request's whole lifetime at prefill, freed at completion, pool
  exhaustion requeues the wave remainder -- a shed path, never an
  exception); ``spec.speculative_k`` runs draft-propose/target-verify
  rounds where the engine's step loop drives the DRAFT model and the
  target is consulted once per round through a prefill-shaped verify
  program (greedy output stays token-identical to plain greedy decode
  -- every emitted token is the target verifier's own argmax).
* **Observability joins** -- request spans (enqueue -> coalesce ->
  prefill -> decode -> done) land on the active ``RunTrace`` timeline
  ("serving" lane); TTFT / per-token latency ride ``add_sample`` into
  the standard percentile machinery; counters/gauges go through the
  registered ``serving/*`` schema keys (metrics.py). Decode-step
  device time is attributed from completion-to-completion intervals
  (the token fetch is a value dependency).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from kf_benchmarks_tpu import metrics as metrics_lib
from kf_benchmarks_tpu import quantization
from kf_benchmarks_tpu import tracing as tracing_lib
from kf_benchmarks_tpu.serving import decode as decode_lib

DEFAULT_BUCKET_LADDER = (1, 4, 16, 64, 256)


def bucket_for(n: int, ladder: Sequence[int]) -> int:
  """Smallest ladder bucket >= n (the top bucket when n overflows)."""
  for b in ladder:
    if n <= b:
      return b
  return ladder[-1]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
  spec: decode_lib.LMSpec = dataclasses.field(
      default_factory=decode_lib.LMSpec)
  bucket_ladder: Tuple[int, ...] = DEFAULT_BUCKET_LADDER
  batching: str = "continuous"       # or "static" (batch-and-drain)
  max_new_tokens: int = 32           # default per-request cap
  max_queue_depth: int = 64          # submit-time rejection bound
  ttft_slo_s: Optional[float] = None  # default TTFT deadline (expiry)
  tenant_tokens_per_s: Optional[float] = None  # None = unmetered
  tenant_burst_s: float = 4.0        # token-bucket burst window
  # Error-budget burn-rate monitoring (metrics.SLOMonitor): objectives
  # are ttft_deadline (first token within its deadline) and
  # shed_fraction (request admitted at all); target = good fraction.
  slo_target: float = 0.99
  slo_fast_window_s: float = 15.0
  slo_slow_window_s: float = 60.0
  slo_burn_threshold: float = 2.0

  def __post_init__(self):
    ladder = tuple(sorted(set(int(b) for b in self.bucket_ladder)))
    if not ladder or ladder[0] < 1:
      raise ValueError(f"bucket ladder must be positive ints, got "
                       f"{self.bucket_ladder}")
    object.__setattr__(self, "bucket_ladder", ladder)
    if self.batching not in ("continuous", "static"):
      raise ValueError(f"batching must be 'continuous' or 'static', "
                       f"got {self.batching!r}")

  def fingerprint_config(self, bucket: int, program: str) -> dict:
    """The executable-cache / compile-ledger key payload: the served
    model's shape plus the one shape knob (the bucket)."""
    return {**self.spec.config(), "bucket": int(bucket),
            "serving_program": program}


@dataclasses.dataclass
class Request:
  rid: Any
  prompt: Any                         # 1-D int32 token array
  max_new_tokens: Optional[int] = None
  tenant: str = "default"
  deadline_s: Optional[float] = None  # TTFT deadline (engine default
                                      # applies when None)
  enqueue_t: Optional[float] = None   # stamped by submit()


@dataclasses.dataclass
class RequestResult:
  rid: Any
  tenant: str
  status: str                         # ok | rejected | expired
  tokens: List[int] = dataclasses.field(default_factory=list)
  ttft_s: Optional[float] = None
  total_s: Optional[float] = None
  shed_reason: Optional[str] = None


class ServingEngine:
  """One-process serving loop over the decode.py programs.

  Synchronous by design: callers drive it with ``submit`` + ``drain``
  (tests) or ``replay(workload)`` (bench/sweep -- wall-clock arrival
  offsets). TPU discipline: ONE engine per process, programs dispatched
  strictly serially, results awaited by value dependency.
  """

  def __init__(self, config: EngineConfig, variables=None,
               seed: int = 0, time_fn=time.monotonic,
               sleep_fn=time.sleep, draft_variables=None,
               recorder=None):
    self.cfg = config
    self.spec = config.spec
    self._time = time_fn
    self._sleep = sleep_fn
    raw = (variables if variables is not None
           else decode_lib.init_variables(self.spec, seed))
    self.variables = decode_lib.prepare_variables(self.spec, raw)
    # Speculative mode: the step loop (decode/prefill programs, the KV
    # cache) runs the DRAFT; the target owns only the verify program.
    # _step_spec/_step_vars are what every per-step codepath uses, so
    # the non-speculative engine is the degenerate draft == target.
    if self.spec.speculative_k:
      self._draft = decode_lib.draft_spec(self.spec)
      if draft_variables is None:
        # Self-drafting default: the draft is the target's own first
        # draft_n_layers (truncate_variables) -- a free draft whose
        # early-layer features track the target far better than a
        # random init ever would. Token identity holds for ANY draft;
        # only the acceptance rate (and so the speedup) depends on it.
        base = raw
        if quantization.has_quantized_leaves(base):
          base = quantization.dequantize_variables(base,
                                                   self.spec.param_dtype)
        draft_variables = decode_lib.truncate_variables(self.spec, base)
      self.draft_variables = decode_lib.prepare_variables(
          self._draft, draft_variables)
      self._step_spec = self._draft
      self._step_vars = self.draft_variables
    else:
      self._draft = None
      self.draft_variables = None
      self._step_spec = self.spec
      self._step_vars = self.variables
    self._queue: collections.deque = collections.deque()
    self._results: Dict[Any, RequestResult] = {}
    self._order: List[Any] = []
    self._bucket = 0
    self._cache: Optional[decode_lib.CacheState] = None
    self._slots: List[Optional[dict]] = []
    self._decode_exes: Dict[int, Any] = {}
    self._prefill_exes: Dict[int, Any] = {}
    self._verify_exes: Dict[int, Any] = {}
    # Paged-KV allocator state (spec.kv_page_size): the authoritative
    # per-slot page tables are HOST numpy (scheduler metadata, shipped
    # to each step as an argument); pool row 0 is the scratch page.
    self._pps = self._step_spec.pages_per_slot
    self._free_pages: List[int] = []
    self._table_np = (np.zeros((0, self._pps), np.int32)
                      if self._pps else None)
    self._kv_pages_peak = 0
    self._kv_fraction_peak = 0.0
    self._arrivals = 0
    self._shed = 0
    self._completed = 0
    self._decode_steps = 0
    self._tokens_out = 0
    self._fill_sum = 0.0
    self._queue_depth_sum = 0.0
    self._ticks = 0
    self._ttfts: List[float] = []
    self._token_lat: List[float] = []
    self._spec_rounds = 0
    self._draft_tokens = 0
    self._accepted_tokens = 0
    self._accept_lens: List[float] = []
    self._tenant_allowance: Dict[str, float] = {}
    self._tenant_last: Dict[str, float] = {}
    # Per-tenant observability (round 21): every tenant the engine has
    # seen gets its own TTFT/token-latency samples, token counts, and
    # shed-by-reason counters -- the labeled half of the serving/*
    # schema keys.
    self._tenant_ttfts: Dict[str, List[float]] = {}
    self._tenant_token_lat: Dict[str, List[float]] = {}
    self._tenant_tokens: Dict[str, int] = {}
    self._tenant_arrivals: Dict[str, int] = {}
    self._tenant_completed: Dict[str, int] = {}
    self._tenant_shed: Dict[Tuple[str, str], int] = {}
    # Burn-rate monitor over the two serving objectives; alert
    # episodes land on the flight recorder (when attached) and on
    # /healthz -- data, never exceptions, like the sheds themselves.
    self.slo = metrics_lib.SLOMonitor(
        objectives={"ttft_deadline": config.slo_target,
                    "shed_fraction": config.slo_target},
        fast_window_s=config.slo_fast_window_s,
        slow_window_s=config.slo_slow_window_s,
        burn_threshold=config.slo_burn_threshold,
        time_fn=time_fn, recorder=recorder)
    self._t_serve0: Optional[float] = None
    self._t_serve1: Optional[float] = None
    self._last_step_t: Optional[float] = None
    self.state = "idle"

  # -- admission --------------------------------------------------------------

  def submit(self, req: Request) -> bool:
    """Enqueue one request; returns False when admission shed it
    (queue depth / tenant budget) -- the shed is a RESULT, not an
    exception. A pre-stamped ``enqueue_t`` is honored (replay stamps
    the SCHEDULED arrival time, so TTFT and deadline expiry include
    any wait behind an in-flight decode step -- the coordinated-
    omission trap); direct callers get stamped here."""
    now = self._time()
    if req.enqueue_t is None:
      req.enqueue_t = now
    self._arrivals += 1
    tenant = req.tenant
    self._tenant_arrivals[tenant] = \
        self._tenant_arrivals.get(tenant, 0) + 1
    reg = metrics_lib.active()
    reg.inc("serving/requests")
    reg.inc("serving/requests", labels={"tenant": tenant})
    if len(self._queue) >= self.cfg.max_queue_depth:
      self._shed_request(req, "queue_depth")
      return False
    prompt_len = int(np.asarray(req.prompt).size)
    if prompt_len < 1:
      self._shed_request(req, "empty_prompt")
      return False
    if prompt_len > self.spec.max_len:
      self._shed_request(req, "prompt_too_long")
      return False
    if self.spec.speculative_k and (
        prompt_len + self._max_new(req) + self.spec.speculative_k
        > self.spec.max_len):
      # Verify rows are history ++ proposals laid out flat in a
      # (B, max_len) token batch -- no ring wrap exists for them, so
      # the whole lifetime must fit the context up front.
      self._shed_request(req, "prompt_too_long")
      return False
    tokens = prompt_len + self._max_new(req)
    if not self._tenant_admit(req.tenant, tokens, now):
      self._shed_request(req, "tenant_budget")
      return False
    tracing_lib.active().instant("serving", "enqueue", rid=str(req.rid),
                                 tenant=req.tenant)
    self._queue.append(req)
    return True

  def _max_new(self, req: Request) -> int:
    return int(req.max_new_tokens or self.cfg.max_new_tokens)

  def _deadline(self, req: Request) -> Optional[float]:
    return (req.deadline_s if req.deadline_s is not None
            else self.cfg.ttft_slo_s)

  def _tenant_admit(self, tenant: str, tokens: int, now: float) -> bool:
    rate = self.cfg.tenant_tokens_per_s
    if rate is None:
      return True
    burst = rate * self.cfg.tenant_burst_s
    allowance = self._tenant_allowance.get(tenant, burst)
    last = self._tenant_last.get(tenant, now)
    allowance = min(burst, allowance + (now - last) * rate)
    self._tenant_last[tenant] = now
    if tokens > allowance:
      self._tenant_allowance[tenant] = allowance
      return False
    self._tenant_allowance[tenant] = allowance - tokens
    return True

  def _shed_request(self, req: Request, reason: str,
                    status: str = "rejected") -> None:
    self._shed += 1
    tenant = req.tenant
    self._tenant_shed[(tenant, reason)] = \
        self._tenant_shed.get((tenant, reason), 0) + 1
    reg = metrics_lib.active()
    reg.inc("serving/shed")
    reg.inc("serving/shed", labels={"tenant": tenant,
                                    "shed_reason": reason})
    # A shed is a bad event on the shed-fraction objective; it also
    # burns the TTFT objective when the request carried a deadline (it
    # will never see a first token).
    self.slo.observe("shed_fraction", tenant, good=False)
    if self._deadline(req) is not None:
      self.slo.observe("ttft_deadline", tenant, good=False)
    self._publish_slo(tenant)
    tracing_lib.active().instant("serving", "shed", rid=str(req.rid),
                                 reason=reason)
    self._record(RequestResult(rid=req.rid, tenant=req.tenant,
                               status=status, shed_reason=reason))

  _SLO_BURN_KEYS = {
      "ttft_deadline": ("serving/slo_ttft_burn_fast",
                        "serving/slo_ttft_burn_slow"),
      "shed_fraction": ("serving/slo_shed_burn_fast",
                        "serving/slo_shed_burn_slow"),
  }

  def _publish_slo(self, tenant: str) -> None:
    """Publish this tenant's current burn rates as labeled gauges (the
    live half; stats() republishes the final values at drain)."""
    reg = metrics_lib.active()
    for objective, (fast_key, slow_key) in self._SLO_BURN_KEYS.items():
      burns = self.slo.burn(objective, tenant)
      if burns["fast"] is not None:
        reg.set(fast_key, burns["fast"], labels={"tenant": tenant})
      if burns["slow"] is not None:
        reg.set(slow_key, burns["slow"], labels={"tenant": tenant})

  def _note_first_token(self, req: Request, now: float) -> float:
    """First-token bookkeeping shared by the plain prefill path and
    the first speculative verify round: global + per-tenant TTFT
    samples, the labeled TTFT histogram, and the ttft_deadline SLO
    event (good iff the first token beat the request's deadline)."""
    ttft = now - req.enqueue_t
    tenant = req.tenant
    self._ttfts.append(ttft)
    self._tenant_ttfts.setdefault(tenant, []).append(ttft)
    tracing_lib.active().add_sample("serving/ttft", ttft)
    metrics_lib.active().observe("serving/ttft_s", ttft,
                                 labels={"tenant": tenant})
    deadline = self._deadline(req)
    if deadline is not None:
      self.slo.observe("ttft_deadline", tenant, good=ttft <= deadline)
      self._publish_slo(tenant)
    return ttft

  def _record(self, result: RequestResult) -> None:
    if result.rid not in self._results:
      self._order.append(result.rid)
    self._results[result.rid] = result

  # -- executable cache (the bounded set) -------------------------------------

  def _compile(self, kind: str, bucket: int, fn, abstract_args,
               donate, spec=None) -> Any:
    from kf_benchmarks_tpu.analysis import baseline as baseline_lib
    key = baseline_lib.config_fingerprint_key(
        self.cfg.fingerprint_config(bucket, kind), program=kind)
    t0 = time.monotonic()
    # The shared AOT recipe (decode.aot_jit): donation always, and the
    # tensor-parallel NamedShardings when spec.model_shards is set.
    compiled = decode_lib.aot_jit(spec or self._step_spec, fn, kind,
                                  bucket, donate).lower(
        *abstract_args).compile()
    tracing_lib.active().note_compile(key, kind,
                                      time.monotonic() - t0,
                                      bucket=bucket)
    return compiled

  def _decode_exe(self, bucket: int):
    if bucket not in self._decode_exes:
      fn, args, donate = decode_lib.decode_lowering_args(
          self._step_spec, bucket)
      self._decode_exes[bucket] = self._compile(
          "serving_decode", bucket, fn, args, donate=donate)
    return self._decode_exes[bucket]

  def _prefill_exe(self, bucket: int):
    # Keyed on the PACK bucket (the wave size), independent of the
    # decode bucket: a one-request refill wave pays a one-row packed
    # forward even while a wide decode batch is in flight.
    if bucket not in self._prefill_exes:
      import jax
      spec = self._step_spec
      var_sds = decode_lib.abstract_variables(spec)
      i32 = lambda: jax.ShapeDtypeStruct((bucket,), np.int32)
      args = (var_sds,
              jax.ShapeDtypeStruct((bucket, 3, spec.max_len), np.int32),
              i32(), i32(), i32())
      self._prefill_exes[bucket] = self._compile(
          "serving_prefill", bucket, decode_lib.prefill_fn(spec), args,
          donate=())
    return self._prefill_exes[bucket]

  def _verify_exe(self, bucket: int):
    # The speculative TARGET's one program: the full spec (not the
    # draft), undonated, keyed per decode bucket like the others --
    # the bounded-compile ledger e2e counts serving_verify as its own
    # <= len(ladder) family.
    if bucket not in self._verify_exes:
      fn, args, donate = decode_lib.verify_lowering_args(self.spec,
                                                         bucket)
      self._verify_exes[bucket] = self._compile(
          "serving_verify", bucket, fn, args, donate=donate,
          spec=self.spec)
    return self._verify_exes[bucket]

  def warm(self, buckets: Optional[Sequence[int]] = None) -> int:
    """Precompile the decode + prefill (+ verify, when speculative)
    executables for ``buckets`` (default: the whole ladder) BEFORE
    serving -- the `analysis warm` discipline applied to the request
    path, so the first wave's TTFT measures the system, not XLA.
    Returns the number of executables compiled."""
    n = 0
    for b in (buckets if buckets is not None else self.cfg.bucket_ladder):
      b = bucket_for(int(b), self.cfg.bucket_ladder)
      before = (len(self._decode_exes) + len(self._prefill_exes)
                + len(self._verify_exes))
      self._decode_exe(b)
      self._prefill_exe(b)
      if self.spec.speculative_k:
        self._verify_exe(b)
      n += (len(self._decode_exes) + len(self._prefill_exes)
            + len(self._verify_exes) - before)
    return n

  # -- the serving loop -------------------------------------------------------

  def _active_count(self) -> int:
    return sum(1 for s in self._slots if s is not None)

  def _ensure_bucket(self, target: int) -> None:
    want = bucket_for(target, self.cfg.bucket_ladder)
    if want <= self._bucket:
      return
    old_pool = (self._cache.k.shape[1] if self._cache is not None
                else 0)
    if self._cache is None:
      self._cache = decode_lib.init_cache(self._step_spec, want)
    else:
      self._cache = decode_lib.grow_cache(self._cache, self._step_spec,
                                          want)
    if self._pps:
      # Pool growth keeps old page ids valid (grow_cache copies the
      # pool prefix); only the NEW rows join the free list.
      new_pool = self._cache.k.shape[1]
      if old_pool == 0:
        self._free_pages = list(range(1, new_pool))
        self._table_np = np.zeros((want, self._pps), np.int32)
      else:
        self._free_pages.extend(range(old_pool, new_pool))
        grown = np.zeros((want, self._pps), np.int32)
        grown[:self._table_np.shape[0]] = self._table_np
        self._table_np = grown
    self._slots.extend([None] * (want - self._bucket))
    self._bucket = want
    metrics_lib.active().set("serving/decode_bucket", want)

  def _maybe_shrink(self) -> None:
    """Compact the decode batch DOWN the ladder when occupancy drops:
    a decode step costs ~O(bucket) host/device work, so dragging a
    wide bucket at low fill taxes every remaining token (measured on
    the CPU-mesh A/B). Active slots compact to the front; an empty
    engine drops its cache entirely so the next wave sizes itself.
    The ladder's spacing is the hysteresis -- a shrink only fires when
    occupancy fits a strictly lower bucket."""
    if self._bucket == 0:
      return
    active_idx = [i for i, s in enumerate(self._slots) if s is not None]
    if not active_idx:
      self._bucket = 0
      self._cache = None
      self._slots = []
      if self._pps:
        self._free_pages = []
        self._table_np = np.zeros((0, self._pps), np.int32)
      metrics_lib.active().set("serving/decode_bucket", 0)
      return
    target = bucket_for(len(active_idx), self.cfg.bucket_ladder)
    if target >= self._bucket:
      return
    import jax.numpy as jnp
    keep = jnp.asarray(
        active_idx + [0] * (target - len(active_idx)), jnp.int32)
    cache = self._cache
    if self._pps:
      # Paged shrink = page-pool compaction: live pages (in kept-slot
      # order) remap onto the head of the smaller pool; slot tables
      # rewrite to the new ids. Skip when the live set does not fit
      # the target pool (a long-session tail can exceed the smaller
      # pool's KV_POOL_FRACTION budget -- the ladder retries next
      # tick once completions free pages).
      new_pool = decode_lib.kv_pool_pages(self._step_spec, target)
      live = [int(pid) for i in active_idx
              for pid in self._table_np[i] if pid]
      if 1 + len(live) > new_pool:
        return
      old_of = np.zeros((new_pool,), np.int32)   # new id -> old id
      remap = {0: 0}
      for new_id, pid in enumerate(live, start=1):
        remap[pid] = new_id
        old_of[new_id] = pid
      gather = jnp.asarray(old_of)
      table = np.zeros((target, self._pps), np.int32)
      for row, i in enumerate(active_idx):
        table[row] = [remap.get(int(pid), 0) for pid in self._table_np[i]]
        # The slot's free-at-completion list must follow the remap, or
        # _complete would return the OLD ids to the new pool.
        self._slots[i]["pages"] = [remap[p]
                                   for p in self._slots[i]["pages"]]
      self._table_np = table
      self._free_pages = list(range(1 + len(live), new_pool))
      self._cache = decode_lib.CacheState(
          k=cache.k[:, gather], v=cache.v[:, gather],
          pos=cache.pos[keep], tok=cache.tok[keep])
    else:
      # Pad rows duplicate slot 0's cache; they carry active=False, so
      # their contents are never read and their writes land on the pad
      # row only.
      self._cache = decode_lib.CacheState(
          k=cache.k[:, keep], v=cache.v[:, keep],
          pos=cache.pos[keep], tok=cache.tok[keep])
    self._slots = ([self._slots[i] for i in active_idx]
                   + [None] * (target - len(active_idx)))
    self._bucket = target
    metrics_lib.active().set("serving/decode_bucket", target)

  def _coalesce(self, now: float) -> List[Request]:
    """Pop admitted work for this wave: expired requests shed here
    (deadline-based shedding), live ones admitted up to the ladder
    headroom left by in-flight slots."""
    headroom = self.cfg.bucket_ladder[-1] - self._active_count()
    wave: List[Request] = []
    while self._queue and len(wave) < headroom:
      req = self._queue.popleft()
      deadline = self._deadline(req)
      if deadline is not None and now - req.enqueue_t > deadline:
        self._shed_request(req, "ttft_deadline", status="expired")
        continue
      wave.append(req)
    return wave

  def _pages_needed(self, prompt_len: int, max_new: int) -> int:
    """Pages a request needs for its WHOLE lifetime (prompt + budget
    + speculative lookahead) -- allocated once at prefill, so decode
    never grows mid-flight. Capped at pages_per_slot: a fully
    allocated slot has the dense slab's ring semantics exactly
    (positions wrap inside its own pages)."""
    page = self._step_spec.kv_page_size
    need = prompt_len + max_new + self.spec.speculative_k
    return min(self._pps, -(-need // page))

  def _prefill_wave(self, wave: List[Request]) -> None:
    from kf_benchmarks_tpu.data import packing as packing_lib
    import jax.numpy as jnp
    self._ensure_bucket(self._active_count() + len(wave))
    free = [i for i, s in enumerate(self._slots) if s is None]
    # Pack bucket = the wave's own ladder size (rows <= prompts always
    # suffice: every prompt fits one row).
    pack_bucket = bucket_for(len(wave), self.cfg.bucket_ladder)
    prompts = [np.asarray(r.prompt, np.int32) for r in wave]
    packed_np, placements = packing_lib.pack_prompts(
        prompts, self.spec.max_len, pack_bucket)
    placed: List[Tuple[Request, np.ndarray, Tuple[int, int], int]] = []
    overflow: List[Request] = []
    avail_pages = len(self._free_pages) if self._pps else 0
    for req, prm, place in zip(wave, prompts, placements):
      need = (self._pages_needed(prm.size, self._max_new(req))
              if self._pps else 0)
      if (place is None or len(placed) >= min(len(free), pack_bucket)
          or need > avail_pages):
        # Pool exhaustion lands here too: the request requeues and
        # retries after in-flight completions free pages -- a shed
        # path (TTFT-deadline expiry at the next coalesce if an SLO
        # is set), never an exception. An EMPTY engine can never
        # exhaust (kv_pool_pages floors at pages_per_slot + 1 and an
        # idle engine resets to a fresh pool), so requeueing always
        # makes progress.
        overflow.append(req)
      else:
        avail_pages -= need
        placed.append((req, prm, place, need))
    # Requests that did not fit this wave's packed batch go back to
    # the queue HEAD in order (near-FIFO, like the packer's lookahead).
    for req in reversed(overflow):
      self._queue.appendleft(req)
    if not placed:
      return
    r = len(placed)
    rows = np.zeros((pack_bucket,), np.int32)
    offsets = np.zeros((pack_bucket,), np.int32)
    last_pos = np.zeros((pack_bucket,), np.int32)
    lengths = np.zeros((pack_bucket,), np.int32)
    slots = np.full((pack_bucket,), self._bucket, np.int32)  # pad drops
    page_lists: List[List[int]] = []
    if self._pps:
      pool = self._cache.k.shape[1]
      # Sentinel P on unallocated pages / pad rows: the install
      # scatter drops them (mode="drop"); the engine-side table keeps
      # 0 (the scratch page) there instead.
      sent = np.full((pack_bucket, self._pps), pool, np.int32)
    for i, (req, prm, (row, off), need) in enumerate(placed):
      rows[i], offsets[i] = row, off
      lengths[i] = prm.size
      last_pos[i] = off + prm.size - 1
      slots[i] = free[i]
      if self._pps:
        pages = [self._free_pages.pop() for _ in range(need)]
        page_lists.append(pages)
        self._table_np[free[i], :] = 0
        self._table_np[free[i], :need] = pages
        sent[i, :need] = pages
    if self._pps:
      in_use = self._cache.k.shape[1] - 1 - len(self._free_pages)
      self._kv_pages_peak = max(self._kv_pages_peak, in_use)
      self._kv_fraction_peak = max(
          self._kv_fraction_peak,
          in_use / max(self._cache.k.shape[1] - 1, 1))
      reg = metrics_lib.active()
      reg.set("serving/kv_pages_in_use", in_use)
      reg.set("serving/kv_page_fraction",
              in_use / max(self._cache.k.shape[1] - 1, 1))
    exe = self._prefill_exe(pack_bucket)
    trace = tracing_lib.active()
    with trace.span("serving", "prefill", requests=r,
                    bucket=pack_bucket):
      first, ek, ev = exe(*decode_lib.place_serving_args(
          self._step_spec, "serving_prefill", pack_bucket,
          (self._step_vars, jnp.asarray(packed_np), jnp.asarray(rows),
           jnp.asarray(last_pos), jnp.asarray(offsets))))
      if self._pps:
        self._cache = decode_lib.install_prefill_paged(
            self._cache, ek, ev, first, jnp.asarray(lengths),
            jnp.asarray(slots), jnp.asarray(sent))
      else:
        self._cache = decode_lib.install_prefill(
            self._cache, ek, ev, first, jnp.asarray(lengths),
            jnp.asarray(slots))
      first_np = np.asarray(first)  # value dependency = completion
    now = self._time()
    for i, (req, prm, _place, _need) in enumerate(placed):
      if self.spec.speculative_k:
        # Speculative: the prefill ran the DRAFT, so its first token
        # is a PROPOSAL, not an emission -- TTFT and the first real
        # token come from the first verify round.
        slot = {"req": req, "tokens": [], "history": prm.copy(),
                "props": [int(first_np[i])],
                "t_first": None, "ttft": None}
      else:
        ttft = self._note_first_token(req, now)
        slot = {"req": req, "tokens": [int(first_np[i])],
                "t_first": now, "ttft": ttft}
      if self._pps:
        slot["pages"] = page_lists[i]
      self._slots[free[i]] = slot
      if not self.spec.speculative_k:
        if len(slot["tokens"]) >= self._max_new(req):
          self._complete(free[i], now)
        self._tokens_out += 1

  def _run_decode_exe(self, active_np) -> np.ndarray:
    """One batched decode dispatch on the step model (the draft, when
    speculative); updates the cache in place and returns the sampled
    tokens. Shared by the plain decode step and the speculative
    draft-propose loop."""
    import jax.numpy as jnp
    exe = self._decode_exe(self._bucket)
    cache = self._cache
    if self._pps:
      args = (self._step_vars, cache.k, cache.v, cache.pos, cache.tok,
              jnp.asarray(self._table_np), jnp.asarray(active_np))
    else:
      args = (self._step_vars, cache.k, cache.v, cache.pos, cache.tok,
              jnp.asarray(active_np))
    nxt, k, v, pos = exe(*decode_lib.place_serving_args(
        self._step_spec, "serving_decode", self._bucket, args))
    nxt_np = np.asarray(nxt)  # value dependency = completion
    self._cache = decode_lib.CacheState(k=k, v=v, pos=pos,
                                        tok=jnp.asarray(nxt))
    self._decode_steps += 1
    reg = metrics_lib.active()
    reg.inc("serving/decode_steps")
    reg.inc("serving/decode_steps", labels={"bucket": str(self._bucket)})
    return nxt_np

  def _decode_step(self) -> None:
    bucket = self._bucket
    active_np = np.array([s is not None for s in self._slots], np.bool_)
    trace = tracing_lib.active()
    t0 = self._time()
    with trace.span("serving", "decode_step",
                    active=int(active_np.sum()), bucket=bucket):
      nxt_np = self._run_decode_exe(active_np)
    now = self._time()
    step_wall = now - t0
    self._last_step_t = now
    n_active = int(active_np.sum())
    self._fill_sum += n_active / max(bucket, 1)
    self._tokens_out += n_active
    trace.add_sample("serving/token_latency", step_wall)
    self._token_lat.append(step_wall)
    reg = metrics_lib.active()
    reg.observe("serving/token_latency_s", step_wall)
    reg.set("serving/active", n_active)
    for i, slot in enumerate(self._slots):
      if slot is None:
        continue
      slot["tokens"].append(int(nxt_np[i]))
      if len(slot["tokens"]) >= self._max_new(slot["req"]):
        self._complete(i, now)

  def _speculative_round(self) -> None:
    """One draft-propose / target-verify round.

    k-1 draft decode steps extend every active slot's proposal run
    (slots fresh from prefill already hold the draft's first proposal,
    so they offer k; slots continuing from a previous round offer
    k-1). ONE target verify dispatch then scores every slot's row =
    confirmed history ++ proposals, and the engine accepts the longest
    agreeing prefix plus the verifier's own next token (the bonus) --
    so every emitted token is the TARGET's greedy argmax and the
    output is token-identical to plain greedy decode, whatever the
    draft proposed.

    Acceptance is capped at len(proposals)-1 so the accepted prefix
    (whose K/V the draft wrote while proposing) plus the bonus
    position (overwritten by the next draft step) never leaves a
    confirmed position without draft K/V; the cap costs at most the
    bonus-vs-final-proposal token, which the bonus replaces 1:1."""
    import jax.numpy as jnp
    trace = tracing_lib.active()
    t0 = self._time()
    bucket = self._bucket
    active_np = np.array([s is not None for s in self._slots], np.bool_)
    n_active = int(active_np.sum())
    for _ in range(self.spec.speculative_k - 1):
      nxt_np = self._run_decode_exe(active_np)
      for i, slot in enumerate(self._slots):
        if slot is not None:
          slot["props"].append(int(nxt_np[i]))
    rows_np = np.zeros((bucket, self.spec.max_len), np.int32)
    for i, slot in enumerate(self._slots):
      if slot is None:
        continue
      row = np.concatenate(
          [slot["history"], np.asarray(slot["props"], np.int32)])
      rows_np[i, :row.size] = row
    exe = self._verify_exe(bucket)
    with trace.span("serving", "verify", active=n_active,
                    bucket=bucket):
      preds = np.asarray(exe(*decode_lib.place_serving_args(
          self.spec, "serving_verify", bucket,
          (self.variables, jnp.asarray(rows_np)))))
    now = self._time()
    self._spec_rounds += 1
    self._last_step_t = now
    self._fill_sum += n_active / max(bucket, 1)
    reg = metrics_lib.active()
    reg.inc("serving/spec_rounds")
    reg.set("serving/active", n_active)
    new_pos = np.array(self._cache.pos)
    new_tok = np.array(self._cache.tok)
    emitted_total = 0
    round_draft = round_accepted = 0
    for i, slot in enumerate(list(self._slots)):
      if slot is None:
        continue
      history, props = slot["history"], slot["props"]
      q0 = history.size
      # props[j] sits at row position q0+j; the target's greedy choice
      # FOR that position is preds[i, q0+j-1] (preds[t] predicts t+1).
      agree = 0
      while (agree < len(props)
             and props[agree] == preds[i, q0 + agree - 1]):
        agree += 1
      a = min(agree, len(props) - 1)
      bonus = int(preds[i, q0 + a - 1])
      emit = [int(x) for x in props[:a]] + [bonus]
      room = self._max_new(slot["req"]) - len(slot["tokens"])
      emit = emit[:room]
      round_draft += len(props)
      round_accepted += min(a, len(emit))
      self._accept_lens.append(float(min(a, len(emit))))
      trace.add_sample("serving/accept_len", float(min(a, len(emit))))
      reg.observe("serving/accept_len", float(min(a, len(emit))))
      slot["tokens"].extend(emit)
      slot["history"] = np.concatenate(
          [history, np.asarray(emit, np.int32)])
      slot["props"] = []
      # Rewind the draft cache onto the confirmed row: the new tok is
      # the last emitted token at position len(history')-1; the next
      # draft step writes its K/V there (overwriting whatever rejected
      # proposal K/V the draft had left).
      new_pos[i] = slot["history"].size - 1
      new_tok[i] = emit[-1]
      if slot["t_first"] is None:
        slot["t_first"] = now
        slot["ttft"] = self._note_first_token(slot["req"], now)
      emitted_total += len(emit)
      if len(slot["tokens"]) >= self._max_new(slot["req"]):
        self._complete(i, now)
    self._draft_tokens += round_draft
    self._accepted_tokens += round_accepted
    reg.inc("serving/draft_tokens", round_draft)
    reg.inc("serving/accepted_tokens", round_accepted)
    self._cache = decode_lib.CacheState(
        k=self._cache.k, v=self._cache.v,
        pos=jnp.asarray(new_pos), tok=jnp.asarray(new_tok))
    self._tokens_out += emitted_total
    per_tok = (now - t0) / max(emitted_total, 1)
    self._token_lat.append(per_tok)
    trace.add_sample("serving/token_latency", per_tok)
    reg.observe("serving/token_latency_s", per_tok)

  def _complete(self, slot_idx: int, now: float) -> None:
    slot = self._slots[slot_idx]
    self._slots[slot_idx] = None
    if self._pps:
      # Free the slot's pages and point its table row at the scratch
      # page: a freed slot's (inactive) decode writes land on scratch,
      # never on a page a later request owns.
      self._free_pages.extend(slot["pages"])
      self._table_np[slot_idx, :] = 0
    req = slot["req"]
    tenant = req.tenant
    self._completed += 1
    self._tenant_completed[tenant] = \
        self._tenant_completed.get(tenant, 0) + 1
    self._tenant_tokens[tenant] = \
        self._tenant_tokens.get(tenant, 0) + len(slot["tokens"])
    reg = metrics_lib.active()
    reg.inc("serving/completed")
    reg.inc("serving/completed", labels={"tenant": tenant})
    result = RequestResult(
        rid=req.rid, tenant=req.tenant, status="ok",
        tokens=list(slot["tokens"]), ttft_s=slot["ttft"],
        total_s=now - req.enqueue_t)
    # Per-tenant token latency: the request's own mean decode interval
    # (total wall after the first token over the tokens it bought) --
    # a per-REQUEST figure, so a tenant's percentiles reflect its own
    # requests rather than whichever batch it shared.
    if len(result.tokens) > 1 and result.ttft_s is not None:
      per_tok = (result.total_s - result.ttft_s) / (len(result.tokens)
                                                    - 1)
      self._tenant_token_lat.setdefault(tenant, []).append(per_tok)
      reg.observe("serving/token_latency_s", per_tok,
                  labels={"tenant": tenant})
    # A completion is a good event on the shed-fraction objective.
    self.slo.observe("shed_fraction", tenant, good=True)
    self._publish_slo(tenant)
    self._record(result)
    trace = tracing_lib.active()
    # Retrospective whole-request span: enqueue -> completion, on the
    # trace clock (requests were stamped with self._time; translate by
    # the shared monotonic origin only when the clocks coincide).
    trace.add_span("serving", "request", trace.now() - result.total_s,
                   result.total_s,
                   {"rid": str(req.rid), "status": "ok",
                    "ttft_s": round(result.ttft_s, 6),
                    "tokens": len(result.tokens)})

  def _tick(self) -> None:
    self._ticks += 1
    reg = metrics_lib.active()
    self._queue_depth_sum += len(self._queue)
    reg.set("serving/queue_depth", len(self._queue))
    self._maybe_shrink()
    now = self._time()
    admit = bool(self._queue) and (
        self.cfg.batching == "continuous" or self._active_count() == 0)
    if admit:
      wave = self._coalesce(now)
      if wave:
        self._prefill_wave(wave)
    if self._active_count():
      if self.spec.speculative_k:
        self._speculative_round()
      else:
        self._decode_step()

  def drain(self) -> List[RequestResult]:
    """Serve until queue and slots are empty; returns every result so
    far in submission order."""
    self.state = "running"
    if self._t_serve0 is None:
      self._t_serve0 = self._time()
    while self._queue or self._active_count():
      self._tick()
    self._t_serve1 = self._time()
    self.state = "drained"
    self._publish()
    return self.results()

  def replay(self, workload: Sequence[Tuple[float, Request]]
             ) -> List[RequestResult]:
    """Replay a seeded workload of (arrival_offset_s, request) pairs in
    wall time: requests become visible at their offsets, the loop
    decodes continuously in between (idle gaps sleep until the next
    arrival). The replayable-trace form bench.py --serving and
    experiments/serving_sweep.py drive."""
    self.state = "running"
    pending = collections.deque(
        sorted(workload, key=lambda pair: pair[0]))
    start = self._time()
    self._t_serve0 = start
    while pending or self._queue or self._active_count():
      now = self._time() - start
      while pending and pending[0][0] <= now:
        offset, req = pending.popleft()
        # The SCHEDULED arrival is the enqueue time: a request whose
        # offset fell while a decode step was in flight has already
        # been waiting, and its TTFT/deadline clock must say so.
        req.enqueue_t = start + offset
        self.submit(req)
      if not self._queue and not self._active_count() and pending:
        self._sleep(max(0.0, pending[0][0] - (self._time() - start)))
        continue
      self._tick()
    self._t_serve1 = self._time()
    self.state = "drained"
    self._publish()
    return self.results()

  def results(self) -> List[RequestResult]:
    return [self._results[rid] for rid in self._order]

  # -- reporting --------------------------------------------------------------

  def healthz(self) -> Dict[str, Any]:
    """Engine liveness for the /healthz endpoint (metrics.py
    MetricsServer healthz_fn). Status distinguishes "up" from "up but
    burning error budget": any firing SLO stream turns it
    "burning"."""
    slo = self.slo.state()
    return {
        "status": slo["status"] if slo["status"] != "ok" else "ok",
        "serving": {
            "state": self.state,
            "active": self._active_count(),
            "queue_depth": len(self._queue),
            "bucket": self._bucket,
            "completed": self._completed,
            "shed": self._shed,
            "decode_steps": self._decode_steps,
        },
        "slo": slo,
    }

  def serve_metrics(self, port: int, registry=None,
                    host: str = "127.0.0.1"):
    """Bind the live /metrics + /healthz endpoint for this engine."""
    return metrics_lib.MetricsServer(
        registry if registry is not None else metrics_lib.active(),
        port, host=host, healthz_fn=self.healthz)

  def stats(self) -> Dict[str, Any]:
    """Flat registered-key stats of the run so far (the bench.py
    --serving JSON payload; every key lives in metrics.SCHEMA)."""
    wall = None
    if self._t_serve0 is not None and self._t_serve1 is not None:
      wall = max(self._t_serve1 - self._t_serve0, 1e-9)
    pct = tracing_lib.percentile
    out = {
        "serving/requests": self._arrivals,
        "serving/completed": self._completed,
        "serving/shed": self._shed,
        "serving/shed_fraction": (self._shed / self._arrivals
                                  if self._arrivals else 0.0),
        "serving/decode_steps": self._decode_steps,
        "serving/decode_bucket": self._bucket,
        "serving/batch_fill_fraction": (
            self._fill_sum / self._decode_steps
            if self._decode_steps else None),
        "serving/queue_depth": (self._queue_depth_sum / self._ticks
                                if self._ticks else None),
        "serving/tokens_per_sec": (self._tokens_out / wall
                                   if wall else None),
        "serving/ttft_p50": pct(self._ttfts, 50),
        "serving/ttft_p90": pct(self._ttfts, 90),
        "serving/ttft_p99": pct(self._ttfts, 99),
        "serving/token_latency_p50": pct(self._token_lat, 50),
        "serving/token_latency_p90": pct(self._token_lat, 90),
        "serving/token_latency_p99": pct(self._token_lat, 99),
        # Variant stats: None when the variant is off (the publish
        # path drops None, so variant-off runs report exactly the
        # pre-variant key set).
        "serving/kv_pages_in_use": (self._kv_pages_peak
                                    if self._pps else None),
        "serving/kv_page_fraction": (self._kv_fraction_peak
                                     if self._pps else None),
        "serving/spec_rounds": (self._spec_rounds
                                if self.spec.speculative_k else None),
        "serving/draft_tokens": (self._draft_tokens
                                 if self.spec.speculative_k else None),
        "serving/accepted_tokens": (
            self._accepted_tokens if self.spec.speculative_k else None),
        "serving/accept_len_p50": (
            pct(self._accept_lens, 50)
            if self.spec.speculative_k else None),
        "serving/accept_len_p90": (
            pct(self._accept_lens, 90)
            if self.spec.speculative_k else None),
        "serving/accept_len_p99": (
            pct(self._accept_lens, 99)
            if self.spec.speculative_k else None),
        "serving/slo_alerts": float(len(self.slo.alerts)),
        # Aggregate burn = the worst tenant (the number an unlabeled
        # dashboard should alarm on); None before any SLO event.
        "serving/slo_ttft_burn_fast": self._agg_burn("ttft_deadline",
                                                     "fast"),
        "serving/slo_ttft_burn_slow": self._agg_burn("ttft_deadline",
                                                     "slow"),
        "serving/slo_shed_burn_fast": self._agg_burn("shed_fraction",
                                                     "fast"),
        "serving/slo_shed_burn_slow": self._agg_burn("shed_fraction",
                                                     "slow"),
        # Per-tenant block: flatten_stats expands it onto labeled keys
        # (name{tenant=...}; sheds additionally carry shed_reason).
        "serving_tenants": self.tenant_stats(),
    }
    return out

  def _agg_burn(self, objective: str, window: str) -> Optional[float]:
    burns = [self.slo.burn(objective, t)[window]
             for t in self._tenants_seen()]
    burns = [b for b in burns if b is not None]
    return max(burns) if burns else None

  def _tenants_seen(self) -> List[str]:
    seen = set(self._tenant_arrivals)
    seen.update(t for (t, _r) in self._tenant_shed)
    return sorted(seen)

  def tenant_stats(self) -> Dict[str, Dict[str, Any]]:
    """Per-tenant stats keyed on FULL registered metric names (so the
    flattened labeled keys stay inside the single-source schema)."""
    pct = tracing_lib.percentile
    wall = None
    if self._t_serve0 is not None and self._t_serve1 is not None:
      wall = max(self._t_serve1 - self._t_serve0, 1e-9)
    out: Dict[str, Dict[str, Any]] = {}
    for tenant in self._tenants_seen():
      ttfts = self._tenant_ttfts.get(tenant, [])
      lats = self._tenant_token_lat.get(tenant, [])
      sheds = {reason: n for (t, reason), n in
               sorted(self._tenant_shed.items()) if t == tenant}
      ttft_burn = self.slo.burn("ttft_deadline", tenant)
      shed_burn = self.slo.burn("shed_fraction", tenant)
      out[tenant] = {
          "serving/requests": self._tenant_arrivals.get(tenant, 0),
          "serving/completed": self._tenant_completed.get(tenant, 0),
          "serving/shed": sheds or None,
          "serving/tokens_per_sec": (
              self._tenant_tokens.get(tenant, 0) / wall
              if wall else None),
          "serving/ttft_p50": pct(ttfts, 50),
          "serving/ttft_p90": pct(ttfts, 90),
          "serving/ttft_p99": pct(ttfts, 99),
          "serving/token_latency_p50": pct(lats, 50),
          "serving/token_latency_p90": pct(lats, 90),
          "serving/token_latency_p99": pct(lats, 99),
          "serving/slo_ttft_burn_fast": ttft_burn["fast"],
          "serving/slo_ttft_burn_slow": ttft_burn["slow"],
          "serving/slo_shed_burn_fast": shed_burn["fast"],
          "serving/slo_shed_burn_slow": shed_burn["slow"],
      }
    return out

  def _publish(self) -> None:
    reg = metrics_lib.active()
    for key, value in metrics_lib.flatten_stats(self.stats()).items():
      base, labels = metrics_lib.parse_labeled_key(key)
      kind = metrics_lib.SCHEMA[base].kind
      if kind in ("counter", "histogram"):
        continue  # counters/histograms were published live
      reg.set(base, value, labels=labels or None)


# -- replayable workloads -----------------------------------------------------

def poisson_workload(n: int, rate_per_s: float, spec: decode_lib.LMSpec,
                     seed: int = 0, max_new_tokens: int = 16,
                     mean_prompt_fraction: float = 0.2,
                     tenants: Sequence[str] = ("default",)
                     ) -> List[Tuple[float, Request]]:
  """A seeded, replayable open-loop arrival trace: exponential
  inter-arrivals at ``rate_per_s``, lognormal prompt lengths
  (data/packing.py's document-length shape, scaled down so prompts +
  generation fit the ring), tenants round-robin. Same seed => same
  workload, the A/B and regression-comparison contract."""
  from kf_benchmarks_tpu.data import packing as packing_lib
  rng = np.random.default_rng(seed)
  # Speculative specs need prompt + max_new + k to fit the context
  # (verify rows never wrap), so the prompt cap shrinks by k.
  cap = max(1, spec.max_len - max_new_tokens - spec.speculative_k - 1)
  lengths = np.minimum(
      packing_lib.sample_document_lengths(
          rng, n, spec.max_len, mean_fraction=mean_prompt_fraction),
      cap)
  gaps = rng.exponential(1.0 / max(rate_per_s, 1e-9), size=n)
  t = np.cumsum(gaps)
  out = []
  for i in range(n):
    prompt = rng.integers(0, spec.vocab, size=int(lengths[i]),
                          dtype=np.int32)
    out.append((float(t[i]), Request(
        rid=i, prompt=prompt, max_new_tokens=max_new_tokens,
        tenant=tenants[i % len(tenants)])))
  return out
