"""Metrics fabric: unified metric registry, cross-run record store with
a regression sentinel, and a live ``/metrics`` endpoint.

TPU-native re-design of the reference's result-upload path: the
reference ships every run's numbers off-host -- tf_cnn_benchmarks'
BenchmarkLogger writes structured JSON metric/run files an uploader
ships to BigQuery (ref: benchmark_cnn.py:1594-1608 benchmark_log_dir
plumbing, logs the same ``average_examples_per_sec`` rows this module
registers), and the keras_benchmarks project uploads straight to
BigQuery (SURVEY §0 item 2) -- so results accumulate in a queryable
store. Here the same capability is host-local and dependency-free,
with three coupled pieces:

* **MetricRegistry** -- the typed schema (``SCHEMA``) is the single
  source of every metric key the framework emits: benchmark run stats,
  bench.py's one-line JSON, telemetry health keys, tracing latency
  percentiles and DeviceFeeder stats all render from keys registered
  here. The hazard lint (``analysis/lint.py`` rule
  ``metric-key-literal``) bans metric-key construction outside this
  schema; ``schema_audit`` cross-checks the registry against what the
  emitters actually produce.
* **Run-record store** -- every run appends ONE schema-versioned JSON
  line (config fingerprint from
  ``analysis/baseline.config_fingerprint_key``, git rev, jax version,
  platform, full metric snapshot) to an append-only JSONL store, with
  a query/merge API and a noise-aware (MAD-based) **regression
  sentinel** (``check_regression``). The first chip record per
  fingerprint auto-promotes to baseline.
* **Live endpoint** -- an opt-in stdlib HTTP thread
  (``--metrics_port``; port + rank under kfrun) serving ``/metrics``
  in Prometheus text exposition format straight from the registry and
  ``/healthz`` from watchdog + flight-recorder state. Host-side only:
  the metrics-on step program is structurally identical to the
  metrics-off golden (``analysis/audit.rule_metrics_twin``, the
  twin-trace pattern).

Pure stdlib and host-only. Loadable standalone by file path (the
``run_tests.py --audit`` metrics-schema leg does exactly that); when
path-loaded, the percentile math is taken from ``tracing.py`` loaded
the same way, so the quantile convention stays single-sourced without
importing the (jax-importing) package.
"""

from __future__ import annotations

import collections
import http.server
import json
import math
import os
import re
import socketserver
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

if __package__:
  from kf_benchmarks_tpu import tracing as _tracing
else:  # loaded by file path (run_tests.py --audit): stay stdlib-only
  import importlib.util as _ilu

  def _load_tracing():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tracing.py")
    spec = _ilu.spec_from_file_location("kf_metrics_tracing", path)
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

  _tracing = _load_tracing()


# -- schema -------------------------------------------------------------------

# Dimensional labels (round 21): the registered label-name universe. A
# SCHEMA entry declares which of these its series may carry
# (``MetricSpec.labels``); the registry rejects any other label name at
# publish time -- the runtime half of the single-source contract, with
# the metric-key-literal lint's label leg as the static half.
LABEL_NAMES = ("tenant", "bucket", "shed_reason")


class MetricSpec(NamedTuple):
  name: str
  kind: str    # "counter" | "gauge" | "histogram" | "info"
  unit: str
  help: str
  source: str  # producing subsystem
  # Label names (each in LABEL_NAMES) this metric's series may carry;
  # () = a plain single-series metric.
  labels: Tuple[str, ...] = ()
  # Regression-sentinel direction: True = bigger is healthier
  # (throughput), False = smaller is (latency, shed), None = the
  # sentinel never gates this key directly. schema_audit REQUIRES a
  # non-None direction on every percentile/throughput/burn gauge.
  higher_is_better: Optional[bool] = None


SCHEMA: "collections.OrderedDict[str, MetricSpec]" = \
    collections.OrderedDict()

# The in-step health vector's key order (telemetry.health_finalize
# builds it; telemetry.py re-exports this tuple -- the ONE copy).
HEALTH_KEYS = ("grad_norm", "update_ratio", "nonfinite_leaves",
               "loss_scale", "skipped")
# Run-end health summary keys (FlightRecorder.summary + watchdog).
HEALTH_SUMMARY_KEYS = ("records", "max_grad_norm", "nonfinite_steps",
                       "loss_scale_final", "anomaly_dumps",
                       "watchdog_stalls")


def health_key(name: str) -> str:
  """The ``health/<key>`` namespace -- the ONE place that prefix is
  constructed (flight-recorder rows, summary scalars and the registry
  all share it; the metric-key-literal lint bans building it
  elsewhere)."""
  return "health/" + name


def _register(name: str, kind: str, unit: str, help_: str, source: str,
              labels: Tuple[str, ...] = (),
              higher_is_better: Optional[bool] = None) -> str:
  if name in SCHEMA:
    raise ValueError(f"duplicate metric key: {name}")
  for lab in labels:
    if lab not in LABEL_NAMES:
      # Unregistered label names fail AT REGISTRATION, exactly like
      # unregistered keys fail at publish -- both are schema typos.
      raise ValueError(f"unregistered label name {lab!r} on {name!r}: "
                       f"LABEL_NAMES is {LABEL_NAMES}")
  SCHEMA[name] = MetricSpec(name, kind, unit, help_, source,
                            tuple(labels), higher_is_better)
  return name


def _gauge(name, unit, help_, source, labels=(), higher_is_better=None):
  return _register(name, "gauge", unit, help_, source, labels,
                   higher_is_better)


def _counter(name, unit, help_, source, labels=()):
  return _register(name, "counter", unit, help_, source, labels)


def _hist(name, unit, help_, source, labels=(), higher_is_better=None):
  return _register(name, "histogram", unit, help_, source, labels,
                   higher_is_better)


def _info(name, help_, source):
  return _register(name, "info", "", help_, source)


# Benchmark run stats (benchmark.py _benchmark_train / forward / eval).
_gauge("images_per_sec", "images/s",
       "Timed-loop throughput (the headline metric)", "benchmark",
       higher_is_better=True)
_gauge("average_wall_time", "s", "Mean wall time per step", "benchmark",
       higher_is_better=False)
_gauge("last_average_loss", "1", "Loss of the last completed step",
       "benchmark")
_counter("num_steps", "steps", "Timed steps completed", "benchmark")
_counter("num_chunks", "chunks", "Timed K-step dispatches completed",
         "benchmark")
_gauge("num_workers", "processes", "Cooperating worker processes",
       "benchmark")
_gauge("steps_per_dispatch", "steps", "K of the chunked dispatch",
       "benchmark")
_gauge("compile_s", "s",
       "Wall of the first dispatch (blocks on trace+compile)",
       "benchmark", higher_is_better=False)
_gauge("dispatch_overhead_s", "s",
       "Mean host time per timed dispatch call (jit call + RTT)",
       "benchmark", higher_is_better=False)
_gauge("grad_noise_scale", "1", "EMA-smoothed B_simple estimate",
       "benchmark")
_gauge("opt_state_bytes_per_device", "bytes",
       "Per-device optimizer-state HBM", "benchmark")
_gauge("param_bytes_per_device", "bytes", "Per-device parameter HBM",
       "benchmark")
_gauge("feed_stall_fraction", "1",
       "Fraction of the consume window blocked on the host feed",
       "feeder", higher_is_better=False)
_gauge("packing_efficiency", "1",
       "Real-token fraction of the packed (B, T) grid", "feeder",
       higher_is_better=True)
_gauge("eval_images_per_sec", "images/s", "Eval-loop throughput",
       "benchmark", higher_is_better=True)
_gauge("top_1_accuracy", "1", "Eval top-1 accuracy", "benchmark",
       higher_is_better=True)
_gauge("top_5_accuracy", "1", "Eval top-5 accuracy", "benchmark",
       higher_is_better=True)

# Live training-loop gauges (the /metrics endpoint's per-step surface).
_counter("step", "steps", "Last completed global step", "benchmark")
_gauge("loss", "1", "Loss at the last completed step", "benchmark")
_gauge("learning_rate", "1", "Learning rate at the last completed step",
       "benchmark")
_gauge("step_images_per_sec", "images/s",
       "Throughput over the last display window", "benchmark",
       higher_is_better=True)

# Telemetry (telemetry.py): in-step health vector + run-end summary,
# all under the health/ namespace (health_key).
_gauge("health/grad_norm", "1", "Global gradient norm (in-step)",
       "telemetry")
_gauge("health/update_ratio", "1",
       "Update/param norm ratio (in-step)", "telemetry")
_gauge("health/nonfinite_leaves", "leaves",
       "Non-finite gradient leaves (in-step)", "telemetry")
_gauge("health/loss_scale", "1", "Loss scale (in-step)", "telemetry")
_gauge("health/skipped", "1", "Step skipped by the loss-scale machine",
       "telemetry")
_counter("health/records", "records", "Flight-recorder rows retained",
         "telemetry")
_gauge("health/max_grad_norm", "1", "Max global grad norm seen",
       "telemetry")
_counter("health/nonfinite_steps", "steps",
         "Steps with a non-finite training signal", "telemetry")
_gauge("health/loss_scale_final", "1", "Final loss scale", "telemetry")
_counter("health/anomaly_dumps", "dumps",
         "Flight-recorder anomaly episodes dumped", "telemetry")
_counter("health/watchdog_stalls", "stalls",
         "Stall-watchdog diagnostic episodes", "telemetry")

# Tracing (tracing.py): streaming latency percentiles over
# tracing.SAMPLE_KEYS x tracing.QUANTILES (schema_audit cross-checks
# this block against those tuples so the two cannot drift) + the
# compile-ledger aggregates.
_gauge("chunk_wall_p50", "s", "Chunk wall p50", "tracing",
       higher_is_better=False)
_gauge("chunk_wall_p90", "s", "Chunk wall p90", "tracing",
       higher_is_better=False)
_gauge("chunk_wall_p99", "s", "Chunk wall p99", "tracing",
       higher_is_better=False)
_gauge("feed_wait_p50", "s", "Feed wait p50", "tracing",
       higher_is_better=False)
_gauge("feed_wait_p90", "s", "Feed wait p90", "tracing",
       higher_is_better=False)
_gauge("feed_wait_p99", "s", "Feed wait p99", "tracing",
       higher_is_better=False)
_gauge("checkpoint_save_p50", "s", "Checkpoint save p50", "tracing",
       higher_is_better=False)
_gauge("checkpoint_save_p90", "s", "Checkpoint save p90", "tracing",
       higher_is_better=False)
_gauge("checkpoint_save_p99", "s", "Checkpoint save p99", "tracing",
       higher_is_better=False)
# Cumulative-histogram twins of the tracing SAMPLE_KEYS (round 21):
# the percentile gauges above remain the run-stats surface; these give
# the /metrics exposition a true le-bucket histogram a scraper can
# aggregate across scrapes and ranks (feed_wait already had its
# feed_wait_s twin below -- this completes the set, which schema_audit
# now pins against tracing.SAMPLE_KEYS). The serving pair carries the
# tenant label.
_hist("chunk_wall_s", "s", "Chunk wall distribution", "tracing",
      higher_is_better=False)
_hist("checkpoint_save_s", "s", "Checkpoint save distribution",
      "tracing", higher_is_better=False)
_counter("compile_ledger/shapes", "programs",
         "Distinct program shapes compiled", "tracing")
_counter("compile_ledger/total_compile_s", "s",
         "Total compile wall seconds", "tracing")

# Serving engine (serving/engine.py): request-path counters/gauges plus
# the TTFT / per-token latency percentiles (the serving/* sample keys
# in tracing.SAMPLE_KEYS render onto the _p50/_p90/_p99 keys here, so
# the cross-check in schema_audit covers them like every other sampled
# latency).
_counter("serving/requests", "requests", "Requests submitted", "serving",
         labels=("tenant",))
_counter("serving/completed", "requests", "Requests served to completion",
         "serving", labels=("tenant",))
_counter("serving/shed", "requests",
         "Requests shed by admission control (rejected + expired)",
         "serving", labels=("tenant", "shed_reason"))
_counter("serving/decode_steps", "steps", "Decode steps dispatched",
         "serving", labels=("bucket",))
_gauge("serving/shed_fraction", "1", "Shed fraction of all arrivals",
       "serving", labels=("tenant",), higher_is_better=False)
_gauge("serving/queue_depth", "requests",
       "Admission queue depth (mean at tick time)", "serving",
       higher_is_better=False)
_gauge("serving/batch_fill_fraction", "1",
       "Mean active-slot fraction of the decode bucket", "serving",
       higher_is_better=True)
_gauge("serving/active", "requests", "In-flight requests decoding",
       "serving")
_gauge("serving/decode_bucket", "requests",
       "Current bucket-ladder decode batch width", "serving")
_gauge("serving/tokens_per_sec", "tokens/s",
       "Generated-token throughput over the serve window", "serving",
       labels=("tenant",), higher_is_better=True)
_gauge("serving/ttft_p50", "s", "Time-to-first-token p50", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/ttft_p90", "s", "Time-to-first-token p90", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/ttft_p99", "s", "Time-to-first-token p99", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/token_latency_p50", "s", "Per-token decode latency p50",
       "serving", labels=("tenant",), higher_is_better=False)
_gauge("serving/token_latency_p90", "s", "Per-token decode latency p90",
       "serving", labels=("tenant",), higher_is_better=False)
_gauge("serving/token_latency_p99", "s", "Per-token decode latency p99",
       "serving", labels=("tenant",), higher_is_better=False)
_hist("serving/ttft_s", "s", "Time-to-first-token distribution",
      "serving", labels=("tenant",), higher_is_better=False)
_hist("serving/token_latency_s", "s",
      "Per-token decode latency distribution", "serving",
      labels=("tenant",), higher_is_better=False)
_hist("serving/accept_len", "tokens",
      "Accepted speculative prefix length distribution", "serving",
      higher_is_better=True)
# Per-tenant SLO burn rates (round 21, SLOMonitor): error rate over
# error budget on a fast and a slow sliding window (the multi-window
# burn-rate alerting idiom); 1.0 = consuming exactly the budget,
# sustained >= the threshold on BOTH windows fires one alert episode.
_gauge("serving/slo_ttft_burn_fast", "x_budget",
       "TTFT-deadline objective burn rate (fast window)", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/slo_ttft_burn_slow", "x_budget",
       "TTFT-deadline objective burn rate (slow window)", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/slo_shed_burn_fast", "x_budget",
       "Shed-fraction objective burn rate (fast window)", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/slo_shed_burn_slow", "x_budget",
       "Shed-fraction objective burn rate (slow window)", "serving",
       labels=("tenant",), higher_is_better=False)
_gauge("serving/slo_alerts", "episodes",
       "SLO alert episodes currently firing", "serving",
       labels=("tenant",), higher_is_better=False)
# Decode-cost variants (ISSUE 16): paged-KV occupancy and speculative
# accept accounting. Variant-off engines report these as None, which
# the publish path drops.
_gauge("serving/kv_pages_in_use", "pages",
       "Peak KV pool pages allocated to live requests", "serving")
_gauge("serving/kv_page_fraction", "1",
       "Peak allocated fraction of the KV page pool", "serving")
_counter("serving/spec_rounds", "rounds",
         "Speculative draft-propose/target-verify rounds", "serving")
_counter("serving/draft_tokens", "tokens",
         "Draft-model proposal tokens offered to the verifier",
         "serving")
_counter("serving/accepted_tokens", "tokens",
         "Draft proposals accepted by the target verifier", "serving")
_gauge("serving/accept_len_p50", "tokens",
       "Accepted speculative prefix length p50", "serving",
       higher_is_better=True)
_gauge("serving/accept_len_p90", "tokens",
       "Accepted speculative prefix length p90", "serving",
       higher_is_better=True)
_gauge("serving/accept_len_p99", "tokens",
       "Accepted speculative prefix length p99", "serving",
       higher_is_better=True)

# DeviceFeeder (data/device_feed.py): run-end stats + live lanes.
_counter("fetches", "batches", "Batches delivered to the consumer",
         "feeder")
_gauge("consumer_wait_s", "s", "Total consumer blocked-wait time",
       "feeder", higher_is_better=False)
_gauge("window_s", "s", "Wall window spanning the fetches", "feeder")
_gauge("queue_depth", "batches", "Prefetch queue depth at last fetch",
       "feeder")
_gauge("queue_depth_mean", "batches", "Mean queue depth at fetch time",
       "feeder")
_gauge("queue_depth_max", "batches", "Max queue depth at fetch time",
       "feeder")
_gauge("prefetch_batches", "batches", "Configured prefetch depth",
       "feeder")
_hist("feed_wait_s", "s", "Per-fetch consumer blocked-wait", "feeder")

# bench.py's one-line JSON (fields not covered above).
_gauge("vs_baseline", "1",
       "Headline value over the reference's committed baseline",
       "bench", higher_is_better=True)
_info("mesh_shape", "Mesh topology the run executed on", "benchmark")
_info("run_id", "Run id shared with trace + flight recorder",
      "benchmark")
_info("git_rev", "Git revision the run was built from", "bench")
_info("platform", "Platform JAX reported (jax.devices()[0].platform)",
      "bench")
_info("device_kind", "device_kind JAX reported for the run's devices",
      "bench")
_gauge("device_count", "devices", "Devices JAX reported (len(jax.devices()))",
       "bench")
_info("metric", "Headline metric name", "bench")
_info("unit", "Headline metric unit", "bench")
# Round 20: which partitioner shaped the sharded step's collectives --
# "manual" (hand-written shard_map programs) or "gspmd" (plain jit +
# NamedShardings, XLA SPMD chooses the exchange). Provenance on the
# JSON line; the flag itself is program-shaping and keys the record's
# config fingerprint.
_info("partitioner", "Collective partitioner (manual | gspmd)", "bench")
# Tuned-config provenance (--autotuned_config, analysis/autotune.py):
# flatten_stats expands the nested stats/bench-JSON payload onto these,
# so the run-store snapshot records WHICH table row shaped a run (the
# tuned knobs themselves are program-shaping params and already key
# the record's config fingerprint).
_info("tuned_config_path", "Tuned-config table the run applied",
      "autotune")
_info("tuned_config_entry", "Matched tuned-table entry fingerprint",
      "autotune")

# Run-stats / bench-JSON keys that are bookkeeping, not metrics: the
# schema audit accepts them from the emitters without registration.
NON_METRIC_KEYS = frozenset({
    "state", "stopped_early", "restart_for_resize", "reshape_events",
    "aot_load_path", "value", "entries", "health",
    "latency_percentiles", "compile_ledger", "tuned_config",
    # Round 19: the serving bench's decode-variant identity block
    # ({quantize, paged_kv, speculative_k}) -- config provenance on the
    # JSON line, not a measurement; the same fields fold into the
    # record's fingerprint via the spec config.
    "decode_variant",
    # The int8 accuracy-gate evidence ({agreement, max_logit_delta,
    # passed}) behind a quantized serving line -- a measured decision
    # record, not a throughput metric.
    "quantize_gate",
    # Round 21: the serving bench's per-tenant block ({tenant:
    # {registered key: value, "serving/shed": {reason: n}, ...}}) --
    # flatten_stats expands it onto tenant-labeled registered keys for
    # the run-store snapshot; the nested form keeps the JSON line
    # readable per tenant.
    "serving_tenants",
    # PR 23: the run trace's always-on totals ({phase: {spans: {name:
    # {n, total_s, max_s}}, counters: {...}}}; tracing.span_totals) --
    # an open-ended table keyed by span name, read by the benchmark's
    # set-up and host-side metrics (benchmarks/spans.py), not a set of
    # registry keys.
    "span_totals",
    # PR 37: the timed loop's account of every iteration's host seconds
    # by span ({iterations, median_s, rows, stalls};
    # tracing.step_account) -- a table of rows read by the benchmark's
    # host_stalls_in_window / host_step_max_over_median / host_self_ms
    # (benchmarks/step_account.py), not a set of registry keys.
    "step_account",
    # PR 23: the scopes the step program names (train_step.STEP_SCOPES),
    # config provenance for the benchmark's trace reader.
    "step_scopes",
    # PR 25: the factor data plane's static counter ({layers,
    # bytes_off_allreduce, bytes_gathered}; parallel/kungfu.py
    # FactorExchange) -- computed from shapes when the step is traced:
    # a description of the program that ran, not a measurement.
    "factor_exchange",
    # PR 27: the expert layer's counters of models/mla_moe_lm.py (the
    # static share held, and pairs routed / dropped / imbalance over the
    # timed steps): a nested table read by the benchmark's readers
    # (benchmarks/layer_metrics/moe_*), None for every other model.
    "moe",
    # PR 27: the allocator's peaks and limit on the fullest device, a
    # nested table (benchmarks/layer_metrics/hbm_peak_in_use_gib.py);
    # None on a backend without memory statistics.
    "device_memory",
    # PR 30: the attention core as models/mla_moe_lm.py states it at the
    # build ({core_layers, backward_kernel_passes, block, block_q,
    # block_kv, block_kv_dkv, dq_partials}; parallel/sequence.flash_plan): a
    # description of the program that ran, None for every other model.
    "attention",
    # PR 36: the fused head's schedule as models/mla_moe_lm.py states it
    # at the build ({chunk, rows_per_weight_grad_product,
    # weight_grad_passes, dlogits_bytes_held, losses};
    # ops/fused_loss.weight_grad_stats): a description of the program
    # that ran, None for every other model.
    "lm_head",
    # PR 38: the rotary stage by call site as models/mla_moe_lm.py states
    # it at the build ({calls_per_layer, layers, rot_dims, heads,
    # head_dim, normed, implementation, block_rows, block_heads,
    # bytes_read_and_written_per_call, residual_bytes_per_layer} a site;
    # ops/rotary.stage_stats): a description of the program that ran,
    # None for every other model.
    "rotary",
    # PR 39: the state-space scan as models/mla_moe_lm.py states it at
    # the build ({layers, heads, head_dim, groups, state, chunk,
    # chunks_per_sequence, implementation, kernel_share (PR 40),
    # carried_state_bytes_per_layer, residual_bytes_per_layer};
    # ops/ssd.scan_stats): a description of
    # the program that ran, None for every other model.
    "mamba",
})

_PROM_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def prometheus_name(key: str) -> str:
  return "kf_" + _PROM_NAME_RE.sub("_", key)


# -- labeled keys -------------------------------------------------------------
#
# A labeled series flattens onto ONE string key -- Prometheus's own
# canonical form, ``name{a="x",b="y"}`` with label names sorted -- so
# run-store snapshots, registry storage and the exposition all share
# one encoding (and one parser).

_LABELED_KEY_RE = re.compile(r"^([^{}]+)\{(.*)\}$")
_LABEL_ITEM_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape_label(v: str) -> str:
  return v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")


def labeled_key(name: str, labels: Optional[Dict[str, Any]]) -> str:
  """Canonical flat key of a (metric, labels) series; the bare name
  when ``labels`` is empty."""
  if not labels:
    return name
  body = ",".join(f'{k}="{_escape_label(str(v))}"'
                  for k, v in sorted(labels.items()))
  return f"{name}{{{body}}}"


def parse_labeled_key(key: str) -> Tuple[str, Dict[str, str]]:
  """(base name, labels dict) of a flat key; plain keys give an empty
  dict. Raises ValueError on a malformed label block."""
  if "{" not in key:
    return key, {}
  m = _LABELED_KEY_RE.match(key)
  if not m:
    raise ValueError(f"malformed labeled metric key {key!r}")
  body = m.group(2)
  items = _LABEL_ITEM_RE.findall(body)
  rebuilt = ",".join(f'{k}="{v}"' for k, v in items)
  if rebuilt != body:
    raise ValueError(f"malformed labeled metric key {key!r}")
  return m.group(1), {k: _unescape_label(v) for k, v in items}


# -- registry -----------------------------------------------------------------

# Cumulative-histogram bucket boundaries (le is inclusive; +Inf is
# implicit as the overflow bin). Seconds-scale latencies by default; a
# token-count histogram (unit "tokens") gets integer-ish bounds.
HIST_BUCKETS_SECONDS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
HIST_BUCKETS_TOKENS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


def hist_buckets(spec: MetricSpec) -> Tuple[float, ...]:
  return (HIST_BUCKETS_TOKENS if spec.unit == "tokens"
          else HIST_BUCKETS_SECONDS)


class MetricRegistry:
  """Typed, thread-safe value store over the SCHEMA.

  Producers set/inc/observe REGISTERED keys only -- an unknown key
  raises, which is the runtime half of the single-source contract (the
  lint rule is the static half). Labeled series pass
  ``labels={name: value}`` with names declared on the key's SCHEMA
  entry -- an undeclared label name raises exactly like an
  unregistered key. Purely host-side: no jax, no device work, cheap
  enough to update per completed step.
  """

  def __init__(self):
    self._lock = threading.Lock()
    # Flat (possibly labeled) key -> value; histogram rows are
    # [count, sum, per-bin counts] over hist_buckets + the +Inf bin --
    # bounded memory by construction, no sample decimation needed.
    self._values: Dict[str, float] = {}
    self._info: Dict[str, str] = {}
    self._hists: Dict[str, list] = {}

  @staticmethod
  def _spec(name: str) -> MetricSpec:
    spec = SCHEMA.get(name)
    if spec is None:
      raise ValueError(
          f"unregistered metric key {name!r}: register it in "
          "kf_benchmarks_tpu/metrics.py SCHEMA (the single source of "
          "metric keys; see the metric-key-literal lint rule)")
    return spec

  @staticmethod
  def _key(spec: MetricSpec, labels: Optional[Dict[str, Any]]) -> str:
    if not labels:
      return spec.name
    for lab in labels:
      if lab not in spec.labels:
        raise ValueError(
            f"unregistered label name {lab!r} on metric "
            f"{spec.name!r}: its SCHEMA entry declares {spec.labels!r} "
            "(labels are single-sourced in metrics.py LABEL_NAMES / "
            "the registration)")
    return labeled_key(spec.name, labels)

  def set(self, name: str, value,
          labels: Optional[Dict[str, Any]] = None) -> None:
    spec = self._spec(name)
    key = self._key(spec, labels)
    with self._lock:
      if spec.kind == "info":
        if labels:
          raise ValueError(f"{name} is info-kind; it renders as a "
                           "kf_run_info label and takes no labels")
        self._info[key] = str(value)
      elif spec.kind == "histogram":
        raise ValueError(f"{name} is a histogram; use observe()")
      else:
        self._values[key] = float(value)

  def inc(self, name: str, delta: float = 1.0,
          labels: Optional[Dict[str, Any]] = None) -> None:
    spec = self._spec(name)
    if spec.kind != "counter":
      raise ValueError(f"{name} is a {spec.kind}; inc() is counter-only")
    key = self._key(spec, labels)
    with self._lock:
      self._values[key] = self._values.get(key, 0.0) + float(delta)

  def observe(self, name: str, value: float,
              labels: Optional[Dict[str, Any]] = None) -> None:
    spec = self._spec(name)
    if spec.kind != "histogram":
      raise ValueError(f"{name} is a {spec.kind}; observe() is "
                       "histogram-only")
    key = self._key(spec, labels)
    bounds = hist_buckets(spec)
    v = float(value)
    with self._lock:
      row = self._hists.setdefault(key, [0, 0.0,
                                         [0] * (len(bounds) + 1)])
      row[0] += 1
      row[1] += v
      i = 0
      while i < len(bounds) and v > bounds[i]:
        i += 1
      row[2][i] += 1

  def snapshot(self) -> Dict[str, Any]:
    """Flat {key: value} of every set scalar/info value (labeled series
    under their canonical ``name{...}`` keys); histograms surface as
    <key>/count and <key>/sum for the run record."""
    with self._lock:
      out: Dict[str, Any] = dict(self._values)
      out.update(self._info)
      hists = {k: (row[0], row[1]) for k, row in self._hists.items()}
    for k, (count, total) in hists.items():
      base, labels = parse_labeled_key(k)
      out[labeled_key(base + "/count", labels)] = count
      out[labeled_key(base + "/sum", labels)] = total
    return out

  def render(self) -> str:
    """Prometheus text exposition format (version 0.0.4), straight
    from the registry: labeled series group under one HELP/TYPE block
    per metric, histogram-kind metrics render as true cumulative
    histograms (``_bucket{le=...}`` + ``_sum`` + ``_count``), and
    info-kind values collapse into one ``kf_run_info`` labeled gauge
    (the Prometheus info-metric idiom)."""
    with self._lock:
      values = dict(self._values)
      info = dict(self._info)
      hists = {k: (row[0], row[1], list(row[2]))
               for k, row in self._hists.items()}
    lines: List[str] = []

    def _suffix(labels: Dict[str, str], extra: str = "") -> str:
      body = ",".join(f'{_PROM_NAME_RE.sub("_", k)}='
                      f'"{_escape_label(v)}"'
                      for k, v in sorted(labels.items()))
      if extra:
        body = f"{body},{extra}" if body else extra
      return "{%s}" % body if body else ""

    by_base: Dict[str, List[Tuple[Dict[str, str], float]]] = {}
    for key, value in values.items():
      base, labels = parse_labeled_key(key)
      by_base.setdefault(base, []).append((labels, value))
    for base in sorted(by_base):
      spec = SCHEMA[base]
      prom = prometheus_name(base)
      lines.append(f"# HELP {prom} {spec.help} [{spec.unit}]")
      lines.append(f"# TYPE {prom} {spec.kind}")
      for labels, value in sorted(by_base[base],
                                  key=lambda p: sorted(p[0].items())):
        lines.append(f"{prom}{_suffix(labels)} {_fmt_value(value)}")
    hist_by_base: Dict[str, List[Tuple[Dict[str, str], tuple]]] = {}
    for key, row in hists.items():
      base, labels = parse_labeled_key(key)
      hist_by_base.setdefault(base, []).append((labels, row))
    for base in sorted(hist_by_base):
      spec = SCHEMA[base]
      prom = prometheus_name(base)
      bounds = hist_buckets(spec)
      lines.append(f"# HELP {prom} {spec.help} [{spec.unit}]")
      lines.append(f"# TYPE {prom} histogram")
      for labels, (count, total, bins) in sorted(
          hist_by_base[base], key=lambda p: sorted(p[0].items())):
        running = 0
        for bound, n in zip(bounds, bins):
          running += n
          le = _suffix(labels, f'le="{_fmt_value(bound)}"')
          lines.append(f"{prom}_bucket{le} {running}")
        le = _suffix(labels, 'le="+Inf"')
        lines.append(f"{prom}_bucket{le} {count}")
        lines.append(f"{prom}_sum{_suffix(labels)} {_fmt_value(total)}")
        lines.append(f"{prom}_count{_suffix(labels)} {count}")
    if info:
      labels = ",".join(
          f'{_PROM_NAME_RE.sub("_", k)}="{_escape_label(v)}"'
          for k, v in sorted(info.items()))
      lines.append("# HELP kf_run_info Run identity labels")
      lines.append("# TYPE kf_run_info gauge")
      lines.append("kf_run_info{%s} 1" % labels)
    return "\n".join(lines) + "\n"


def _fmt_value(v: float) -> str:
  if math.isnan(v):
    return "NaN"
  if math.isinf(v):
    return "+Inf" if v > 0 else "-Inf"
  return format(float(v), ".10g")


def _escape_label(v: str) -> str:
  return str(v).replace("\\", "\\\\").replace('"', '\\"').replace(
      "\n", "\\n")


_PROM_LINE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? "
    r"(NaN|[+-]Inf|[-+0-9.eE]+)$")
_PROM_LABEL_ITEM_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prom_labels(body: Optional[str]):
  """{...} label body -> dict, or None on malformed body."""
  if not body:
    return {}
  inner = body[1:-1]
  items = _PROM_LABEL_ITEM_RE.findall(inner)
  if ",".join(f'{k}="{v}"' for k, v in items) != inner:
    return None
  return dict(items)


def validate_prometheus_text(text: str) -> List[str]:
  """Structural check of a Prometheus text-format payload; returns
  problem strings (empty = valid). Beyond line grammar this checks the
  cumulative-histogram contract promtool enforces: every
  ``<name>_bucket`` series needs an ``le`` label, each (family,
  labels) series needs a ``+Inf`` bucket with monotone non-decreasing
  cumulative counts, and ``<name>_count`` must equal the ``+Inf``
  bucket. The schema contract the endpoint tests and the
  metrics-schema audit pin."""
  problems = []
  # (family, frozen non-le labels) -> [(le, count)], and _count values.
  # Only families DECLARED "# TYPE <fam> histogram" get the histogram
  # suffix treatment -- a plain gauge whose name happens to end in
  # _bucket (serving/decode_bucket) must not trip the grammar.
  hist_families = set()
  buckets: Dict[Tuple[str, frozenset], List[Tuple[str, float]]] = {}
  counts: Dict[Tuple[str, frozenset], float] = {}
  for i, line in enumerate(text.splitlines()):
    if not line.strip():
      continue
    if line.startswith("# TYPE "):
      parts = line.split()
      if len(parts) != 4 or parts[3] not in (
          "counter", "gauge", "summary", "histogram", "untyped"):
        problems.append(f"line {i}: bad TYPE line {line!r}")
      elif parts[3] == "histogram":
        hist_families.add(parts[2])
      continue
    if line.startswith("#"):
      continue
    m = _PROM_LINE_RE.match(line)
    if not m:
      problems.append(f"line {i}: not a metric sample: {line!r}")
      continue
    name, body, value = m.group(1), m.group(2), m.group(3)
    labels = _parse_prom_labels(body)
    if labels is None:
      problems.append(f"line {i}: malformed label body: {line!r}")
      continue
    if name.endswith("_bucket") and name[:-len("_bucket")] in \
        hist_families:
      le = labels.pop("le", None)
      if le is None:
        problems.append(f"line {i}: _bucket sample without le label: "
                        f"{line!r}")
        continue
      series = (name[:-len("_bucket")], frozenset(labels.items()))
      buckets.setdefault(series, []).append((le, float(value)))
    elif name.endswith("_count") and name[:-len("_count")] in \
        hist_families:
      counts[(name[:-len("_count")], frozenset(labels.items()))] = \
          float(value)
  for series, rows in buckets.items():
    fam = series[0]
    les = [le for le, _ in rows]
    if "+Inf" not in les:
      problems.append(f"histogram {fam}: series missing +Inf bucket")
    vals = [n for _, n in rows]
    if any(b < a for a, b in zip(vals, vals[1:])):
      problems.append(f"histogram {fam}: bucket counts not cumulative "
                      f"monotone: {vals}")
    if "+Inf" in les and series in counts:
      inf = dict(rows)["+Inf"]
      if counts[series] != inf:
        problems.append(f"histogram {fam}: _count {counts[series]} != "
                        f"+Inf bucket {inf}")
  return problems


# -- stats flattening (run stats / bench JSON -> registered keys) -------------

def flatten_stats(stats: Dict[str, Any]) -> Dict[str, Any]:
  """One flat {registered key: value} view of a benchmark stats dict or
  a bench.py JSON record: nested health / latency_percentiles /
  compile_ledger containers expand onto their registered keys,
  bookkeeping keys (NON_METRIC_KEYS) and unset values drop out.
  The serving engine's per-tenant block (``serving_tenants``) expands
  onto labeled keys (``name{tenant="..."}``; shed counts additionally
  carry ``shed_reason``)."""
  out: Dict[str, Any] = {}
  for key, value in (stats or {}).items():
    if value is None:
      continue
    if key == "serving_tenants" and isinstance(value, dict):
      for tenant, block in value.items():
        if not isinstance(block, dict):
          continue
        for tk, tv in block.items():
          if tv is None:
            continue
          if tk == "serving/shed" and isinstance(tv, dict):
            for reason, n in tv.items():
              out[labeled_key("serving/shed",
                              {"tenant": tenant,
                               "shed_reason": reason})] = float(n)
            continue
          if tk in SCHEMA and isinstance(tv, (int, float)) \
              and not isinstance(tv, bool):
            out[labeled_key(tk, {"tenant": tenant})] = float(tv)
      continue
    if key == "health" and isinstance(value, dict):
      for hk, hv in value.items():
        name = health_key(hk)
        if name in SCHEMA and isinstance(hv, (int, float)):
          out[name] = float(hv)
      continue
    if key == "latency_percentiles" and isinstance(value, dict):
      for lk, lv in value.items():
        if lk in SCHEMA and lv is not None:
          out[lk] = float(lv)
      continue
    if key == "compile_ledger" and isinstance(value, dict):
      for ck in ("shapes", "total_compile_s"):
        if value.get(ck) is not None:
          out["compile_ledger/" + ck] = float(value[ck])
      continue
    if key == "tuned_config" and isinstance(value, dict):
      if value.get("path"):
        out["tuned_config_path"] = str(value["path"])
      if value.get("entry"):
        out["tuned_config_entry"] = str(value["entry"])
      continue
    spec = SCHEMA.get(key)
    if spec is None:
      continue
    if spec.kind == "info":
      out[key] = str(value)
    elif isinstance(value, bool):
      out[key] = float(value)
    elif isinstance(value, (int, float)):
      out[key] = float(value)
  return out


def publish_stats(registry, stats: Dict[str, Any]) -> None:
  """Render a stats dict into a registry (the run-end publication the
  /metrics endpoint serves after the loop completes)."""
  for key, value in flatten_stats(stats).items():
    base, labels = parse_labeled_key(key)
    if SCHEMA[base].kind == "histogram":
      continue
    registry.set(base, value, labels=labels or None)


# -- active-registry (the tracing.py pattern) ---------------------------------

class _NullRegistry:
  """No-op sink with the MetricRegistry surface, so deep producers
  (DeviceFeeder's consumer path) publish unconditionally."""

  def set(self, *a, **k) -> None:
    pass

  def inc(self, *a, **k) -> None:
    pass

  def observe(self, *a, **k) -> None:
    pass

  def snapshot(self) -> Dict[str, Any]:
    return {}

  def render(self) -> str:
    return "\n"


NULL_REGISTRY = _NullRegistry()
_active: Any = None


def activate(registry: MetricRegistry) -> MetricRegistry:
  global _active
  _active = registry
  return registry


def deactivate() -> None:
  global _active
  _active = None


def active():
  """The process's active MetricRegistry, or the no-op sink."""
  return _active if _active is not None else NULL_REGISTRY


# -- live endpoint ------------------------------------------------------------

def resolve_port(base_port: int, rank: int = 0) -> int:
  """Per-rank port under kfrun: rank r serves base + r (every worker of
  a single-host job gets its own scrape target)."""
  return int(base_port) + int(rank)


class _Handler(http.server.BaseHTTPRequestHandler):
  server_version = "kf-metrics/1"

  def do_GET(self):  # noqa: N802 (BaseHTTPRequestHandler API)
    path = self.path.split("?", 1)[0]
    if path == "/metrics":
      body = self.server.registry.render().encode("utf-8")
      ctype = "text/plain; version=0.0.4; charset=utf-8"
    elif path == "/healthz":
      try:
        payload = self.server.healthz_fn()
      except Exception as e:  # a health probe must answer, not raise
        payload = {"status": "error", "error": repr(e)}
      body = (json.dumps(payload) + "\n").encode("utf-8")
      ctype = "application/json"
    else:
      self.send_error(404, "unknown path (serving /metrics, /healthz)")
      return
    self.send_response(200)
    self.send_header("Content-Type", ctype)
    self.send_header("Content-Length", str(len(body)))
    self.end_headers()
    self.wfile.write(body)

  def log_message(self, *args) -> None:
    pass  # scrapes must never interleave into the run's stdout


class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
  daemon_threads = True
  # Scrape targets restart with the run; a lingering TIME_WAIT socket
  # must not fail the next run's bind.
  allow_reuse_address = True


class MetricsServer:
  """Opt-in scrape endpoint on a daemon thread.

  Binds eagerly (a bad port fails fast at session start, not at first
  scrape); ``port=0`` binds an ephemeral port -- ``self.port`` is
  always the real bound port. Host-side only by construction: the
  handler reads the registry under its lock and never touches jax.
  """

  def __init__(self, registry, port: int, host: str = "127.0.0.1",
               healthz_fn: Optional[Callable[[], Dict[str, Any]]] = None):
    self._httpd = _Server((host, int(port)), _Handler)
    self._httpd.registry = registry
    self._httpd.healthz_fn = healthz_fn or (lambda: {"status": "ok"})
    self.host = host
    self.port = int(self._httpd.server_address[1])
    self._thread = threading.Thread(
        target=self._httpd.serve_forever, name="kf-metrics-endpoint",
        daemon=True)
    self._thread.start()

  def close(self) -> None:
    self._httpd.shutdown()
    self._httpd.server_close()
    self._thread.join(timeout=5.0)


# -- SLO burn-rate monitor ----------------------------------------------------

SLO_OBJECTIVES = ("ttft_deadline", "shed_fraction")


class SLOMonitor:
  """Multi-window error-budget burn-rate monitor (the Google SRE
  alerting shape): per (objective, tenant) stream of good/bad events,
  burn = bad_fraction / error_budget over a fast and a slow sliding
  window, and an alert fires only when BOTH windows burn at or above
  the threshold -- fast alone is noise, slow alone is stale.

  Alerts are DATA, never exceptions (the serving shed discipline):
  edge-triggered episode records (one ``firing``, one ``resolved``)
  append to ``self.alerts`` and, when a flight recorder is attached,
  ride its row stream via ``note_event`` so the post-run report and
  the live ``/healthz`` agree. Host-only, stdlib-only, fake-clock
  testable via ``time_fn``.
  """

  def __init__(self, objectives: Optional[Dict[str, float]] = None,
               fast_window_s: float = 15.0, slow_window_s: float = 60.0,
               burn_threshold: float = 2.0,
               time_fn: Callable[[], float] = time.monotonic,
               recorder=None):
    objectives = dict(objectives if objectives is not None
                      else {o: 0.99 for o in SLO_OBJECTIVES})
    for obj, target in objectives.items():
      if obj not in SLO_OBJECTIVES:
        raise ValueError(f"unknown SLO objective {obj!r}: "
                         f"SLO_OBJECTIVES is {SLO_OBJECTIVES}")
      if not 0.0 < float(target) < 1.0:
        raise ValueError(f"SLO target for {obj!r} must be in (0, 1), "
                         f"got {target!r}")
    self.objectives = {k: float(v) for k, v in objectives.items()}
    self.fast_window_s = float(fast_window_s)
    self.slow_window_s = float(slow_window_s)
    self.burn_threshold = float(burn_threshold)
    self._time = time_fn
    self._recorder = recorder
    self._lock = threading.Lock()
    # (objective, tenant) -> deque[(t, good)] pruned past slow window.
    self._events: Dict[Tuple[str, str], "collections.deque"] = {}
    self._firing: Dict[Tuple[str, str], bool] = {}
    self.alerts: List[Dict[str, Any]] = []

  def observe(self, objective: str, tenant: str, good: bool,
              t: Optional[float] = None) -> None:
    if objective not in self.objectives:
      raise ValueError(f"unknown SLO objective {objective!r}: this "
                       f"monitor tracks {sorted(self.objectives)}")
    t = self._time() if t is None else float(t)
    key = (objective, str(tenant))
    with self._lock:
      q = self._events.setdefault(key, collections.deque())
      q.append((t, bool(good)))
      self._prune(q, t)
      self._evaluate(key, t)

  def _prune(self, q, t: float) -> None:
    horizon = t - self.slow_window_s
    while q and q[0][0] < horizon:
      q.popleft()

  def burn(self, objective: str, tenant: str,
           t: Optional[float] = None) -> Dict[str, Optional[float]]:
    """{"fast": burn, "slow": burn}; None where the window is empty."""
    t = self._time() if t is None else float(t)
    budget = max(1.0 - self.objectives[objective], 1e-9)
    with self._lock:
      q = self._events.get((objective, str(tenant))) or ()
      rows = list(q)
    out: Dict[str, Optional[float]] = {}
    for name, win in (("fast", self.fast_window_s),
                      ("slow", self.slow_window_s)):
      inside = [good for (et, good) in rows if et >= t - win]
      if not inside:
        out[name] = None
      else:
        bad = sum(1 for good in inside if not good)
        out[name] = (bad / len(inside)) / budget
    return out

  def _evaluate(self, key: Tuple[str, str], t: float) -> None:
    # Caller holds the lock via observe(); burn() re-takes it, so
    # compute inline over the already-pruned deque.
    objective, tenant = key
    budget = max(1.0 - self.objectives[objective], 1e-9)
    rows = list(self._events.get(key) or ())
    burns = {}
    for name, win in (("fast", self.fast_window_s),
                      ("slow", self.slow_window_s)):
      inside = [good for (et, good) in rows if et >= t - win]
      burns[name] = None if not inside else \
          (sum(1 for g in inside if not g) / len(inside)) / budget
    hot = (burns["fast"] is not None and burns["slow"] is not None
           and burns["fast"] >= self.burn_threshold
           and burns["slow"] >= self.burn_threshold)
    was = self._firing.get(key, False)
    if hot == was:
      return
    self._firing[key] = hot
    rec = {
        "slo_alert": objective,
        "tenant": tenant,
        "state": "firing" if hot else "resolved",
        "burn_fast": burns["fast"],
        "burn_slow": burns["slow"],
        "threshold": self.burn_threshold,
        "budget": budget,
        "t": t,
    }
    self.alerts.append(rec)
    if self._recorder is not None:
      self._recorder.note_event(dict(rec))

  def firing(self, t: Optional[float] = None) -> List[Tuple[str, str]]:
    """Currently-firing (objective, tenant) streams. Re-evaluates every
    stream at ``t`` first, so a quiet recovery (no new events) still
    clears -- the probe IS the evaluation tick."""
    t = self._time() if t is None else float(t)
    with self._lock:
      for key, q in self._events.items():
        self._prune(q, t)
        self._evaluate(key, t)
      return sorted(k for k, hot in self._firing.items() if hot)

  def state(self, t: Optional[float] = None) -> Dict[str, Any]:
    """The /healthz payload: per-objective per-tenant burn rates plus
    the episode count; status "burning" iff any stream fires."""
    t = self._time() if t is None else float(t)
    hot = self.firing(t)
    objectives: Dict[str, Any] = {}
    with self._lock:
      keys = sorted(self._events)
    for objective, tenant in keys:
      burns = self.burn(objective, tenant, t)
      objectives.setdefault(objective, {})[tenant] = {
          "burn_fast": burns["fast"],
          "burn_slow": burns["slow"],
          "firing": (objective, tenant) in hot,
      }
    return {
        "status": "burning" if hot else "ok",
        "threshold": self.burn_threshold,
        "objectives": objectives,
        "alerts": len(self.alerts),
    }


# -- run-record store ---------------------------------------------------------

RECORD_SCHEMA_VERSION = 1
STORE_FILENAME = "run_store.jsonl"


def run_record(*, metric: str, value: float, unit: str,
               fingerprint: str, run_id: str, platform: str,
               git_rev: Optional[str] = None,
               jax_version: Optional[str] = None,
               snapshot: Optional[Dict[str, Any]] = None,
               t_wall: Optional[float] = None) -> Dict[str, Any]:
  """One schema-versioned run record. ``fingerprint`` is the program
  identity (analysis/baseline.config_fingerprint_key) the sentinel
  compares within; ``platform`` is the platform JAX reported for the
  run's devices; ``snapshot`` is the flat registered metric view
  (flatten_stats / MetricRegistry.snapshot)."""
  return {
      "schema_version": RECORD_SCHEMA_VERSION,
      "t_wall": round(float(time.time() if t_wall is None else t_wall),
                      3),
      "run_id": str(run_id),
      "fingerprint": str(fingerprint),
      "metric": str(metric),
      "value": float(value),
      "unit": str(unit),
      "platform": str(platform),
      "baseline": False,
      "git_rev": git_rev,
      "jax_version": jax_version,
      "snapshot": dict(snapshot or {}),
  }


def validate_record(rec) -> List[str]:
  """Problem strings (empty = valid) for one store record -- the
  schema-version contract the metrics-schema audit re-checks over the
  whole store."""
  problems = []
  if not isinstance(rec, dict):
    return ["record is not an object"]
  ver = rec.get("schema_version")
  if not isinstance(ver, int) or not 1 <= ver <= RECORD_SCHEMA_VERSION:
    problems.append(f"schema_version {ver!r} outside "
                    f"[1, {RECORD_SCHEMA_VERSION}]")
  for field in ("run_id", "fingerprint", "metric", "unit", "platform"):
    v = rec.get(field)
    if not isinstance(v, str) or not v:
      problems.append(f"{field} missing or not a non-empty string")
  v = rec.get("value")
  if not isinstance(v, (int, float)) or isinstance(v, bool) or \
      not math.isfinite(v):
    problems.append(f"value {v!r} is not a finite number")
  if not isinstance(rec.get("t_wall"), (int, float)):
    problems.append("t_wall missing or not a number")
  if not isinstance(rec.get("baseline"), bool):
    problems.append("baseline missing or not a bool")
  snap = rec.get("snapshot")
  if not isinstance(snap, dict):
    problems.append("snapshot missing or not an object")
  else:
    for k, sv in snap.items():
      try:
        base, labels = parse_labeled_key(k)
      except ValueError:
        problems.append(f"snapshot key {k!r} is a malformed labeled key")
        continue
      base = base.split("/count")[0].split("/sum")[0]
      spec = SCHEMA.get(base)
      if spec is None:
        problems.append(f"snapshot key {k!r} not in the metric schema")
        continue
      bad = [lab for lab in labels if lab not in spec.labels]
      if bad:
        problems.append(f"snapshot key {k!r} carries undeclared label "
                        f"names {bad} (declared: {list(spec.labels)})")
      elif not isinstance(sv, (int, float, str)):
        problems.append(f"snapshot value for {k!r} is {type(sv).__name__}")
  return problems


class RunStore:
  """Append-only JSONL store of run records.

  One line per run; torn/foreign lines are skipped on read (the store
  rides ordinary filesystems and a crashed writer must not poison the
  history). ``append`` validates and auto-promotes the first real-chip
  record of a fingerprint to baseline.
  """

  def __init__(self, store_dir: str, filename: str = STORE_FILENAME):
    self.dir = str(store_dir)
    self.path = os.path.join(self.dir, filename)

  def records(self) -> List[Dict[str, Any]]:
    out = []
    try:
      with open(self.path, encoding="utf-8") as f:
        for line in f:
          line = line.strip()
          if not line:
            continue
          try:
            rec = json.loads(line)
          except ValueError:
            continue
          if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    except OSError:
      pass
    return out

  def query(self, fingerprint: Optional[str] = None,
            metric: Optional[str] = None) -> List[Dict[str, Any]]:
    rows = self.records()
    if fingerprint is not None:
      rows = [r for r in rows if r.get("fingerprint") == fingerprint]
    if metric is not None:
      rows = [r for r in rows if r.get("metric") == metric]
    rows.sort(key=lambda r: r.get("t_wall", 0.0))
    return rows

  def has_run(self, run_id: str, metric: str) -> bool:
    return any(r.get("run_id") == run_id and r.get("metric") == metric
               for r in self.records())

  def append(self, rec: Dict[str, Any]) -> Dict[str, Any]:
    problems = validate_record(rec)
    if problems:
      raise ValueError("invalid run record: " + "; ".join(problems))
    if rec["platform"] == "tpu" and not rec["baseline"]:
      # Baseline self-promotion: the FIRST chip record per fingerprint
      # becomes the baseline. CPU runs are never eligible.
      prior = [r for r in self.records()
               if r.get("fingerprint") == rec["fingerprint"]
               and r.get("baseline")]
      if not prior:
        rec = dict(rec, baseline=True)
    os.makedirs(self.dir, exist_ok=True)
    with open(self.path, "a", encoding="utf-8") as f:
      f.write(json.dumps(rec, sort_keys=True) + "\n")
    return rec

  @staticmethod
  def merge(paths: List[str]) -> List[Dict[str, Any]]:
    """Union of several store files, deduped on (run_id, metric,
    t_wall) -- the cross-host merge for stores synced from more than
    one machine."""
    seen = set()
    out = []
    for path in paths:
      for rec in RunStore(os.path.dirname(path) or ".",
                          os.path.basename(path)).records():
        key = (rec.get("run_id"), rec.get("metric"), rec.get("t_wall"))
        if key in seen:
          continue
        seen.add(key)
        out.append(rec)
    out.sort(key=lambda r: r.get("t_wall", 0.0))
    return out


# -- regression sentinel ------------------------------------------------------

# Consistent MAD->sigma factor for normal noise.
MAD_SIGMA = 1.4826
# Defaults tuned to the acceptance bar: a seeded 20% throughput drop
# flags against any realistic history, +-5% run-to-run noise stays
# quiet (uniform +-5% noise has MAD ~2.5%, so the MAD leg of the bar
# sits at ~15%; a noise-free history floors the bar at rel_floor).
SENTINEL_WINDOW = 8
SENTINEL_MAD_FACTOR = 4.0
SENTINEL_REL_FLOOR = 0.08
SENTINEL_MIN_HISTORY = 3


def check_regression(history: List[Dict[str, Any]],
                     fresh: Dict[str, Any],
                     window: int = SENTINEL_WINDOW,
                     mad_factor: float = SENTINEL_MAD_FACTOR,
                     rel_floor: float = SENTINEL_REL_FLOOR,
                     min_history: int = SENTINEL_MIN_HISTORY,
                     higher_is_better: bool = True) -> Dict[str, Any]:
  """Compare ``fresh`` against the trailing median of comparable
  history with a noise-aware bar.

  Comparable = same fingerprint (which covers --device) and same
  metric name, excluding the fresh run itself. The bar is
  ``max(mad_factor * 1.4826 * MAD, rel_floor * |median|)``: the MAD leg
  adapts to the config's measured run-to-run noise, the relative floor
  keeps a noise-free history from flagging epsilon jitter.
  """
  rows = [r for r in history
          if r.get("fingerprint") == fresh.get("fingerprint")
          and r.get("metric") == fresh.get("metric")
          and r.get("run_id") != fresh.get("run_id")]
  rows.sort(key=lambda r: r.get("t_wall", 0.0))
  tail = rows[-max(1, int(window)):]
  value = float(fresh.get("value", float("nan")))
  base = {
      "metric": fresh.get("metric"),
      "fingerprint": fresh.get("fingerprint"),
      "value": value,
      "n": len(tail),
      "window": int(window),
  }
  if len(tail) < min_history:
    return dict(base, status="no_history", median=None, bar=None)
  vals = [float(r["value"]) for r in tail]
  med = _tracing.percentile(vals, 50)
  mad = _tracing.percentile([abs(v - med) for v in vals], 50)
  bar = max(mad_factor * MAD_SIGMA * mad, rel_floor * abs(med))
  delta = (med - value) if higher_is_better else (value - med)
  status = "regression" if delta > bar else "ok"
  return dict(base, status=status, median=med, bar=bar)


def verdict_line(verdict: Dict[str, Any]) -> str:
  """One whole self-identifying verdict line (the scrape-guard
  discipline: never interleaves inside the bench JSON line)."""
  metric = verdict.get("metric")
  fp = (verdict.get("fingerprint") or "")[:16]
  if verdict["status"] == "no_history":
    return (f"regression check: NO HISTORY for {metric} "
            f"(fingerprint {fp}, {verdict['n']} comparable record(s)); "
            "recorded as history for future runs")
  word = "REGRESSION" if verdict["status"] == "regression" else "OK"
  return ("regression check: %s %s value=%.3f median=%.3f bar=%.3f "
          "(n=%d, fingerprint %s)" % (
              word, metric, verdict["value"], verdict["median"],
              verdict["bar"], verdict["n"], fp))


# Direction fallback for keys whose SCHEMA entry predates (or lacks)
# higher_is_better -- substring heuristics, first match wins.
_DIRECTION_HINTS = (
    ("per_sec", True),
    ("accuracy", True),
    ("ttft", False),
    ("latency", False),
    ("shed", False),
    ("wall", False),
)


def metric_direction(name: str) -> bool:
  """higher_is_better for a (possibly labeled) metric key: the SCHEMA
  field when set, else a name heuristic, else True (the pre-label
  sentinel default, so old throughput records keep their polarity)."""
  base, _ = parse_labeled_key(name)
  base = base.split("/count")[0].split("/sum")[0]
  spec = SCHEMA.get(base)
  if spec is not None and spec.higher_is_better is not None:
    return spec.higher_is_better
  for needle, better in _DIRECTION_HINTS:
    if needle in base:
      return better
  return True


def snapshot_check(history: List[Dict[str, Any]],
                   fresh: Dict[str, Any],
                   key: str) -> Optional[Dict[str, Any]]:
  """Direction-aware sentinel over a SNAPSHOT key instead of the
  headline metric: synthesizes per-key rows from the stored snapshots
  and runs check_regression with the key's SCHEMA direction. Returns
  None when the fresh record has no such snapshot key (the variant is
  off)."""
  if key not in (fresh.get("snapshot") or {}):
    return None

  def _row(rec):
    snap = rec.get("snapshot") or {}
    if key not in snap or not isinstance(snap[key], (int, float)):
      return None
    return {
        "fingerprint": rec.get("fingerprint"),
        "metric": key,
        "run_id": rec.get("run_id"),
        "t_wall": rec.get("t_wall", 0.0),
        "value": float(snap[key]),
    }

  hist_rows = [r for r in map(_row, history) if r is not None]
  fresh_row = _row(fresh)
  return check_regression(hist_rows, fresh_row,
                          higher_is_better=metric_direction(key))


# -- bench identity -----------------------------------------------------------

def bench_params_kwargs() -> Dict[str, Any]:
  """The canonical headline-bench config (bench.py's make_params call)
  -- ONE copy, so every consumer computes the same config
  fingerprint. (num_batches/num_warmup_batches None = the reference
  defaults, 100 and 10.)"""
  return dict(
      model="resnet50",
      batch_size=256,
      num_batches=None,
      num_warmup_batches=None,
      device="tpu",
      num_devices=1,
      variable_update="replicated",
      use_fp16=True,
      optimizer="momentum",
      display_every=10,
      health_stats=True,
  )


def bench_fingerprint(params=None) -> str:
  """Config fingerprint of the headline bench (program name "bench").

  ``params`` is the RESOLVED Params when the caller has them (bench.py
  after setup -- so a tuned-table application keys the record under
  the knobs it actually ran with, never the canonical defaults; the
  run store must not mix tuned and default runs under one
  fingerprint)."""
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.analysis import baseline as baseline_lib
  if params is None:
    params = params_lib.make_params(**bench_params_kwargs())
  return baseline_lib.config_fingerprint_key(params._asdict(), "bench")


def git_revision(repo_dir: Optional[str] = None) -> Optional[str]:
  """Short git revision of ``repo_dir`` (default: this repo), or None
  when git/metadata is unavailable -- a missing rev must never fail a
  bench run."""
  import subprocess
  cwd = repo_dir or os.path.dirname(os.path.dirname(
      os.path.abspath(__file__)))
  try:
    out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                         capture_output=True, text=True, cwd=cwd,
                         timeout=10)
  except (OSError, subprocess.SubprocessError):
    return None
  rev = (out.stdout or "").strip()
  return rev if out.returncode == 0 and rev else None


# -- schema audit (the run_tests.py --audit leg) ------------------------------

def _ast_emitted_keys(path: str) -> List[Tuple[str, int]]:
  """Literal keys of the metric-emitting dicts in a source file: any
  dict literal that carries an ``images_per_sec`` key (the benchmark
  stats dicts) or both ``metric`` and ``value`` (the bench JSON
  record), plus ``record["..."]``-style subscript assignments onto
  such a dict's name."""
  import ast
  try:
    tree = ast.parse(open(path, encoding="utf-8").read())
  except (OSError, SyntaxError):
    return []
  out = []
  for node in ast.walk(tree):
    if not isinstance(node, ast.Dict):
      continue
    keys = [k.value for k in node.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)]
    if "images_per_sec" in keys or {"metric", "value"} <= set(keys):
      out.extend((k, node.lineno) for k in keys)
  for node in ast.walk(tree):
    if (isinstance(node, ast.Assign) and len(node.targets) == 1
        and isinstance(node.targets[0], ast.Subscript)
        and isinstance(node.targets[0].value, ast.Name)
        and node.targets[0].value.id == "record"
        and isinstance(node.targets[0].slice, ast.Constant)
        and isinstance(node.targets[0].slice.value, str)):
      out.append((node.targets[0].slice.value, node.lineno))
  return out


def schema_audit(repo_dir: str) -> List[str]:
  """The metrics-schema audit: registry keys vs what the emitters
  actually produce, plus store-record validity. Pure host-side, no
  device work (the ``run_tests.py --audit`` budget). Returns problem
  strings (empty = clean)."""
  problems: List[str] = []
  # 1. Schema self-consistency: prometheus names must stay distinct
  # after sanitization (two keys mapping to one exposition name would
  # silently merge on the endpoint).
  prom_names: Dict[str, str] = {}
  for name in SCHEMA:
    prom = prometheus_name(name)
    if prom in prom_names:
      problems.append(f"schema: {name!r} and {prom_names[prom]!r} both "
                      f"render as {prom}")
    prom_names[prom] = name
  # 2. Health namespace coverage: every key telemetry can emit is
  # registered.
  for k in HEALTH_KEYS + HEALTH_SUMMARY_KEYS:
    if health_key(k) not in SCHEMA:
      problems.append(f"schema: telemetry key {health_key(k)!r} is not "
                      "registered")
  # 3. Tracing coverage: every SAMPLE_KEYS x QUANTILES percentile field
  # and the ledger aggregates are registered (the registration block is
  # literal for the lint; this is its staleness check) -- and every
  # sample stream also has a cumulative-histogram twin (key or key_s)
  # so the exposition carries the full distribution, not just
  # precomputed quantile gauges.
  for key in _tracing.SAMPLE_KEYS:
    for q in _tracing.QUANTILES:
      name = f"{key}_p{q}"
      if name not in SCHEMA:
        problems.append(f"schema: tracing percentile field {name!r} is "
                        "not registered")
    twins = [key, key + "_s"]
    if not any(SCHEMA.get(t) is not None and SCHEMA[t].kind == "histogram"
               for t in twins):
      problems.append(f"schema: tracing sample key {key!r} has no "
                      f"histogram-kind twin (looked for {twins})")
  # 3b. Label + direction validity: declared labels come from
  # LABEL_NAMES, higher_is_better is a tri-state bool, and direction
  # is REQUIRED on every key the sentinel or the fleet report can
  # judge (percentile gauges, throughputs, shed/burn rates).
  _needs_direction = re.compile(r"_p(50|90|99)$")
  for name, spec in SCHEMA.items():
    for lab in spec.labels:
      if lab not in LABEL_NAMES:
        problems.append(f"schema: {name!r} declares label {lab!r} "
                        f"outside LABEL_NAMES {LABEL_NAMES}")
    if spec.higher_is_better not in (True, False, None):
      problems.append(f"schema: {name!r} higher_is_better must be "
                      "True/False/None")
    if spec.kind == "gauge" and spec.higher_is_better is None and (
        _needs_direction.search(name) or "per_sec" in name
        or "shed_fraction" in name or "_burn_" in name):
      problems.append(f"schema: sentinel-judged gauge {name!r} has no "
                      "higher_is_better direction")
  # 4. Emitters: every literal key of the benchmark stats dicts and the
  # bench JSON record is registered or explicitly non-metric.
  for rel in ("kf_benchmarks_tpu/benchmark.py", "bench.py"):
    for key, lineno in _ast_emitted_keys(os.path.join(repo_dir, rel)):
      if key not in SCHEMA and key not in NON_METRIC_KEYS:
        problems.append(
            f"{rel}:{lineno}: emitted metric key {key!r} is neither "
            "registered in metrics.SCHEMA nor in NON_METRIC_KEYS")
  # 5. Run store (when present): every record validates against the
  # current schema version.
  store = RunStore(repo_dir)
  for i, rec in enumerate(store.records()):
    for p in validate_record(rec):
      problems.append(f"{store.path}: record {i}: {p}")
  # 6. Exposition self-check: a fully-populated registry -- every key,
  # and a labeled series for every key that declares labels -- renders
  # valid Prometheus text including the cumulative-histogram grammar.
  reg = MetricRegistry()
  for name, spec in SCHEMA.items():
    labeled = {spec.labels[0]: "t0"} if spec.labels else None
    if spec.kind == "info":
      reg.set(name, "x")
    elif spec.kind == "histogram":
      reg.observe(name, 0.5)
      if labeled:
        reg.observe(name, 0.5, labels=labeled)
    elif spec.kind == "counter":
      reg.inc(name)
      if labeled:
        reg.inc(name, labels=labeled)
    else:
      reg.set(name, 1.5)
      if labeled:
        reg.set(name, 1.5, labels=labeled)
  problems.extend("prometheus render: " + p
                  for p in validate_prometheus_text(reg.render()))
  return problems


# -- fleet report (the BigQuery-dashboard replacement) ------------------------

def fleet_rows(records: List[Dict[str, Any]],
               fingerprint: Optional[str] = None,
               metric: Optional[str] = None,
               platform: Optional[str] = None) -> List[Dict[str, Any]]:
  """Group store records into per-(fingerprint, metric) trend rows with
  a direction-aware verdict on the LATEST record vs its own trailing
  history. ``fingerprint`` is a prefix filter (verdict lines only print
  16 chars)."""
  rows = []
  for rec in records:
    if validate_record(rec):
      continue
    if fingerprint and not rec["fingerprint"].startswith(fingerprint):
      continue
    if metric and rec["metric"] != metric:
      continue
    if platform and rec["platform"] != platform:
      continue
    rows.append(rec)
  groups: Dict[Tuple[str, str], List[Dict[str, Any]]] = {}
  for rec in rows:
    groups.setdefault((rec["fingerprint"], rec["metric"]),
                      []).append(rec)
  out = []
  for (fp, met), rs in sorted(groups.items()):
    rs.sort(key=lambda r: r.get("t_wall", 0.0))
    values = [float(r["value"]) for r in rs]
    direction = metric_direction(met)
    verdict = check_regression(rs[:-1], rs[-1],
                               higher_is_better=direction)
    out.append({
        "fingerprint": fp,
        "metric": met,
        "unit": rs[-1].get("unit"),
        "platform": rs[-1].get("platform"),
        "n": len(rs),
        "values": values,
        "first": values[0],
        "last": values[-1],
        "median": _tracing.percentile(values, 50),
        "direction": direction,
        "verdict": verdict["status"],
        "records": rs,
    })
  return out


def format_fleet_report(rows: List[Dict[str, Any]]) -> str:
  """Aligned per-fingerprint trend table; the text half of the report
  CLI. Empty input explains itself instead of printing a bare
  header."""
  if not rows:
    return ("fleet report: no matching run records. Any bench.py run "
            "(or a --run_store_dir training run) populates the "
            "store.\n")
  header = ("FINGERPRINT", "METRIC", "N", "FIRST", "LAST", "MEDIAN",
            "BETTER", "VERDICT", "FLAGS")
  table = [header]
  for r in rows:
    flags = [str(r["platform"])] if r["platform"] else []
    table.append((
        r["fingerprint"][:16],
        r["metric"],
        str(r["n"]),
        "%.3f" % r["first"],
        "%.3f" % r["last"],
        "%.3f" % r["median"],
        "higher" if r["direction"] else "lower",
        r["verdict"],
        ",".join(flags),
    ))
  widths = [max(len(row[i]) for row in table)
            for i in range(len(header))]
  lines = ["  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
           for row in table]
  lines.append("fleet report: %d trend row(s) over %d record(s)" % (
      len(rows), sum(r["n"] for r in rows)))
  return "\n".join(lines) + "\n"


def _svg_sparkline(series: List[float], w: int = 220,
                   h: int = 48) -> str:
  """Self-contained inline-SVG sparkline (no JS, no external assets --
  the report file must open from an airgapped artifact store)."""
  pad = 4.0
  if not series:
    return f'<svg width="{w}" height="{h}"></svg>'
  lo, hi = min(series), max(series)
  span = (hi - lo) or 1.0

  def _xy(i, v):
    x = pad + (w - 2 * pad) * (i / max(1, len(series) - 1))
    y = pad + (h - 2 * pad) * (1.0 - (v - lo) / span)
    return f"{x:.1f},{y:.1f}"

  if len(series) == 1:
    x, y = _xy(0, series[0]).split(",")
    body = f'<circle cx="{x}" cy="{y}" r="3" fill="#36c"/>'
  else:
    pts = " ".join(_xy(i, v) for i, v in enumerate(series))
    body = (f'<polyline points="{pts}" fill="none" stroke="#36c" '
            'stroke-width="1.5"/>')
  return (f'<svg width="{w}" height="{h}" '
          f'viewBox="0 0 {w} {h}">{body}</svg>')


_SERVING_CURVE_KEYS = ("serving/ttft_p50", "serving/ttft_p90",
                       "serving/ttft_p99")
_CURVE_COLORS = ("#2a9d5c", "#e0a426", "#d0453e")


def fleet_report_html(rows: List[Dict[str, Any]]) -> str:
  """One self-contained HTML timeline: a sparkline per trend row and
  serving TTFT percentile curves where the snapshots carry them."""
  import html as _html

  def _row_html(r):
    cells = [
        f"<td><code>{_html.escape(r['fingerprint'][:16])}</code></td>",
        f"<td>{_html.escape(r['metric'])}</td>",
        f"<td>{r['n']}</td>",
        f"<td>{r['last']:.3f} {_html.escape(str(r['unit'] or ''))}</td>",
        f"<td>{'higher' if r['direction'] else 'lower'}</td>",
        f"<td class=\"v-{_html.escape(r['verdict'])}\">"
        f"{_html.escape(r['verdict'])}</td>",
        f"<td>{_svg_sparkline(r['values'])}</td>",
    ]
    curves = []
    for key, color in zip(_SERVING_CURVE_KEYS, _CURVE_COLORS):
      series = [float(rec["snapshot"][key]) for rec in r["records"]
                if isinstance((rec.get("snapshot") or {}).get(key),
                              (int, float))]
      if series:
        curves.append(
            _svg_sparkline(series).replace("#36c", color))
    cells.append("<td>" + "".join(curves) + "</td>")
    return "<tr>" + "".join(cells) + "</tr>"

  head = ("<tr><th>fingerprint</th><th>metric</th><th>n</th>"
          "<th>last</th><th>better</th><th>verdict</th>"
          "<th>trend</th><th>serving ttft p50/p90/p99</th></tr>")
  if rows:
    body = ("<h2>Trends</h2><table>" + head
            + "".join(_row_html(r) for r in rows) + "</table>")
  else:
    body = ("<p>No matching run records. Any <code>bench.py</code> "
            "run populates the store.</p>")
  return (
      "<!doctype html><html><head><meta charset=\"utf-8\">"
      "<title>kf_benchmarks_tpu fleet report</title><style>"
      "body{font-family:sans-serif;margin:24px}"
      "table{border-collapse:collapse}"
      "td,th{border:1px solid #ccc;padding:4px 8px;text-align:left}"
      ".v-regression{color:#b00;font-weight:bold}"
      ".v-ok{color:#080}.v-no_history{color:#888}"
      "</style></head><body><h1>kf_benchmarks_tpu fleet report</h1>"
      + body + "</body></html>\n")


# -- CLI ----------------------------------------------------------------------

def main(argv=None) -> int:
  import argparse
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  parser = argparse.ArgumentParser(
      prog="python -m kf_benchmarks_tpu.metrics",
      description="run-record store tools: audit the metric schema, "
                  "render the cross-run fleet report")
  sub = parser.add_subparsers(dest="cmd", required=True)
  p_audit = sub.add_parser("audit", help="metrics-schema audit")
  p_audit.add_argument("--repo", default=repo)
  p_rep = sub.add_parser(
      "report", help="per-fingerprint trend table from the run store")
  p_rep.add_argument("--repo", default=repo)
  p_rep.add_argument("--run_store_dir", default=None,
                     help="store directory (default: the repo root)")
  p_rep.add_argument("--html", default=None, metavar="OUT",
                     help="also write a self-contained HTML timeline")
  p_rep.add_argument("--fingerprint", default=None,
                     help="fingerprint prefix filter")
  p_rep.add_argument("--metric", default=None)
  p_rep.add_argument("--platform", default=None)
  args = parser.parse_args(argv)
  if args.cmd == "report":
    store = RunStore(args.run_store_dir or args.repo)
    rows = fleet_rows(store.records(),
                      fingerprint=args.fingerprint,
                      metric=args.metric,
                      platform=args.platform)
    print(format_fleet_report(rows), end="")
    if args.html:
      with open(args.html, "w") as f:
        f.write(fleet_report_html(rows))
      print(f"fleet report: wrote {args.html}")
    return 0
  problems = schema_audit(args.repo)
  for p in problems:
    print(p)
  print(f"metrics-schema audit: {len(problems)} problem(s)")
  return 1 if problems else 0


if __name__ == "__main__":
  raise SystemExit(main())
