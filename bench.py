#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic ImageNet images/sec on one chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...,
"platform", "device_kind", "device_count"} with the device as JAX
reports it. Without a TPU it prints "not measured", exits nonzero and
runs nothing: there is no CPU fallback.

Baseline anchor: the reference's best committed single-GPU number --
ResNet-50, synthetic ImageNet, batch 200, RTX 3090, 416.43 images/sec
(BASELINE.md, slurm-2810608-200.out). vs_baseline = ours / 416.43.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMAGES_PER_SEC = 416.43


def main(argv=None):
  import argparse
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument(
      "--run_store_dir",
      default=os.path.dirname(os.path.abspath(__file__)),
      help="directory of the append-only run-record store "
           "(metrics.py RunStore); defaults to the repo root")
  parser.add_argument(
      "--check-regression", action="store_true",
      dest="check_regression",
      help="compare this run against the trailing median of "
           "same-fingerprint history in the run store (noise-aware "
           "MAD bar, metrics.py check_regression); prints a verdict "
           "line to stderr and exits nonzero on a regression")
  parser.add_argument(
      "--autotuned_config", default=None,
      help="tuned-config table to apply at startup "
           "(analysis/autotune.py; benchmark.setup logs the "
           "provenance line). The applied knobs are program-shaping "
           "params, so the run-store fingerprint below keys the tuned "
           "run apart from default history automatically")
  parser.add_argument(
      "--serving", action="store_true",
      help="run the serving-path bench instead of the training-step "
           "headline: a seeded request replay through the "
           "continuous-batching engine (kf_benchmarks_tpu/serving/), "
           "emitting ONE JSON line (tokens/s, TTFT + per-token "
           "percentiles, shed fraction) appended to the same run "
           "store")
  parser.add_argument("--serving_requests", type=int, default=None,
                      help="serving: replayed request count (default "
                           "128)")
  parser.add_argument("--serving_rate", type=float, default=None,
                      help="serving: offered load, requests/s "
                           "(default 16)")
  parser.add_argument("--serving_tenants", type=int, default=None,
                      help="serving: distinct tenants round-robined "
                           "through the replay (default 1); >1 joins "
                           "the workload fingerprint and lands "
                           "per-tenant percentiles in the run store")
  parser.add_argument("--serving_bucket_ladder", default=None,
                      help="serving: --serving_bucket_ladder params "
                           "flag passthrough")
  parser.add_argument("--serving_batching", default=None,
                      help="serving: continuous | static")
  parser.add_argument("--serving_quantize", default=None,
                      choices=("int8",),
                      help="serving: INT8 weight-only decode "
                           "(--serving_quantize params passthrough)")
  parser.add_argument("--serving_kv_page_size", type=int, default=None,
                      help="serving: paged KV cache block size "
                           "(must divide the spec's max_len)")
  parser.add_argument("--serving_speculative_k", type=int, default=None,
                      help="serving: speculative decoding draft length "
                           "(>= 2; requires --serving_draft_layers)")
  parser.add_argument("--serving_draft_layers", type=int, default=None,
                      help="serving: draft model depth for speculative "
                           "decoding (< the spec's n_layers)")
  parser.add_argument("--serving_model_shards", type=int, default=None,
                      help="serving: tensor-parallel shard count for "
                           "the decode/prefill/verify executables "
                           "(>= 2, must divide the spec's head count; "
                           "--serving_model_shards params passthrough)")
  parser.add_argument("--partitioner", default=None,
                      choices=("manual", "gspmd"),
                      help="training bench: who inserts the sharded "
                           "step's collectives (--partitioner params "
                           "passthrough; program-shaping, so the run "
                           "keys apart from default history)")
  parser.add_argument("--metrics_port", type=int, default=None,
                      help="serving: bind the live /metrics + /healthz "
                           "endpoint for the duration of the replay")
  args = parser.parse_args(argv)

  from kf_benchmarks_tpu import metrics as metrics_lib
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu.utils import log as log_util

  # Keep the bench quiet: route step logs to stderr so stdout carries
  # only the JSON line (benchmark.log_fn late-binds to log_util.log_fn).
  log_util.log_fn = lambda s: print(s, file=sys.stderr, flush=True)

  # The one backend init of this process, in-process (a chip belongs to
  # one process at a time; no child probes it first). No chip, no
  # number: the bench never runs on another platform.
  device = benchmark.device_identity()
  if device["platform"] != "tpu":
    print("not measured: bench.py needs a TPU and JAX found platform="
          f"{device['platform']} (device_kind={device['kind']}, "
          f"{device['count']} device(s)); there is no CPU fallback",
          flush=True)
    return 1
  if args.serving:
    return run_serving_bench(args, device)
  # The canonical bench config lives in metrics.bench_params_kwargs --
  # ONE copy, so every consumer computes the same config fingerprint.
  # (num_batches=None -> the reference default, 100, the baseline
  # logs' config; health_stats explicit opt-in -- the bench has no
  # train_dir, so auto would stay off and the one-line JSON would lose
  # its run-health aggregate; use_fp16 means bfloat16 compute on TPU.)
  bench_kwargs = metrics_lib.bench_params_kwargs()
  if args.autotuned_config:
    bench_kwargs["autotuned_config"] = args.autotuned_config
  if args.partitioner:
    bench_kwargs["partitioner"] = args.partitioner
  params = params_lib.make_params(**bench_kwargs)
  # setup() applies --autotuned_config (with the provenance line), so
  # the params this process fingerprints below are the APPLIED ones.
  params = benchmark.setup(params)
  bench = benchmark.BenchmarkCNN(params)
  stats = bench.run()
  value = stats["images_per_sec"]
  metric = "resnet50_synthetic_images_per_sec"
  # compile_s: wall time of the first dispatch (blocks on trace +
  # compile); dispatch_overhead_s: mean host time per timed dispatch
  # call (what --steps_per_dispatch amortizes).
  compile_s = stats.get("compile_s")
  dispatch_s = stats.get("dispatch_overhead_s")
  record = {
      "metric": metric,
      "value": round(value, 2),
      "unit": "images/sec",
      "vs_baseline": round(value / BASELINE_IMAGES_PER_SEC, 3),
      "compile_s": round(compile_s, 3) if compile_s is not None else None,
      "dispatch_overhead_s": (round(dispatch_s, 6)
                              if dispatch_s is not None else None),
      # Mesh topology ("8" = 1-D replica mesh, "BxM" = the named 2-D
      # mesh) + per-device optimizer-state HBM -- the pair that A/Bs
      # --shard_optimizer_state runs (~|state|/n expected) against
      # replicated ones (~|state|).
      "mesh_shape": stats.get("mesh_shape"),
      "opt_state_bytes_per_device": stats.get("opt_state_bytes_per_device"),
      # Per-device parameter HBM next to the optimizer-state field:
      # the pair A/Bs --shard_params (FSDP, ~|params|/n expected)
      # against replicated-param runs (~|params|).
      "param_bytes_per_device": stats.get("param_bytes_per_device"),
      # Input-pipeline health (PR 8): fraction of the loop wall spent
      # blocked on the host feed. None here -- the resnet bench runs
      # the resident synthetic batch, which has no feeder -- but the
      # field rides every bench line so packed/real-data trajectories
      # record it uniformly.
      "feed_stall_fraction": stats.get("feed_stall_fraction"),
      # Who inserted the sharded step's collectives (ISSUE 17):
      # "manual" = the hand-written shard_map programs (the default,
      # also when the flag is unset), "gspmd" = plain jit +
      # NamedShardings with the XLA SPMD partitioner choosing the
      # exchange. Program-shaping (the flag is in the fingerprint), so
      # twin runs never mix in the regression gate.
      "partitioner": params.partitioner or "manual",
  }
  # Streaming latency percentiles + compile ledger (tracing.py): the
  # SLO-telemetry and compile-cache groundwork fields (ROADMAP items 2
  # and 5). Seconds, like compile_s; None when the run produced no
  # samples of a key (e.g. feed_wait on the resident synthetic batch,
  # which has no feeder).
  lat = stats.get("latency_percentiles") or {}

  def _r6(v):
    return round(v, 6) if v is not None else None

  record["latency_percentiles"] = {
      "chunk_wall_p50": _r6(lat.get("chunk_wall_p50")),
      "chunk_wall_p90": _r6(lat.get("chunk_wall_p90")),
      "chunk_wall_p99": _r6(lat.get("chunk_wall_p99")),
      "feed_wait_p99": _r6(lat.get("feed_wait_p99")),
  }
  ledger = stats.get("compile_ledger") or {}
  record["compile_ledger"] = {
      "shapes": ledger.get("shapes", 0),
      "total_compile_s": ledger.get("total_compile_s"),
  }
  # Tuned-config provenance (--autotuned_config): {path, entry} when a
  # table was applied (entry None when it held no row for this
  # config), null otherwise -- so a bench line always says whether a
  # tuned table shaped it.
  record["tuned_config"] = stats.get("tuned_config")
  # Run-health summary (telemetry.py): the line records whether the
  # run was HEALTHY, not just fast -- a throughput number next to
  # nonfinite_steps > 0 or a watchdog stall is a different story than
  # the same number from a clean run. Absent (None) when --health_stats
  # resolved off.
  health = stats.get("health")
  if health:
    mgn = health.get("max_grad_norm")
    record["health"] = {
        "max_grad_norm": round(mgn, 4) if mgn is not None else None,
        "nonfinite_steps": health.get("nonfinite_steps"),
        "loss_scale_final": health.get("loss_scale_final"),
        "watchdog_stalls": health.get("watchdog_stalls"),
    }
  # Run attribution: the git revision the run was built from and the
  # device as JAX reports it (platform, device_kind, device count).
  record["git_rev"] = metrics_lib.git_revision()
  record["platform"] = device["platform"]
  record["device_kind"] = device["kind"]
  record["device_count"] = device["count"]
  print(json.dumps(record), flush=True)
  return record_and_check(record, args.run_store_dir,
                          args.check_regression,
                          run_id=stats.get("run_id"),
                          # Fingerprint of the RESOLVED params: a tuned
                          # run keys apart from default history (the
                          # tuned knobs are program-shaping), so
                          # --check-regression compares like with like.
                          fingerprint=metrics_lib.bench_fingerprint(
                              params=params))


def run_serving_bench(args, device) -> int:
  """The serving-path bench: replay a seeded request trace of the zoo
  transformer_lm through the continuous-batching engine and print ONE
  JSON line. ``device`` is benchmark.device_identity() (a TPU: main()
  has already refused anything else)."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import metrics as metrics_lib
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu import tracing
  from kf_benchmarks_tpu import validation
  from kf_benchmarks_tpu.analysis import baseline as baseline_lib
  from kf_benchmarks_tpu.serving import (
      EngineConfig, LMSpec, ServingEngine, poisson_workload)

  params = params_lib.make_params(
      model="transformer_lm", device="tpu",
      # The serving 'model' mesh draws whole devices, so a TP bench
      # claims exactly model_shards of them (dense stays single-device).
      num_devices=max(1, args.serving_model_shards or 1),
      serving_bucket_ladder=args.serving_bucket_ladder,
      serving_batching=args.serving_batching,
      serving_quantize=args.serving_quantize,
      serving_kv_page_size=args.serving_kv_page_size,
      serving_speculative_k=args.serving_speculative_k,
      serving_draft_layers=args.serving_draft_layers,
      serving_model_shards=args.serving_model_shards)
  # Cross-flag contract (validation.py): an inconsistent variant combo
  # (speculative without a draft, a non-dividing page size) fails at
  # parse time with the named flag, not mid-serve inside LMSpec.
  validation.validate_cross_flags(params)
  # The serving bench does not go through benchmark.setup(): place the
  # compile cache by the same rule before the first trace.
  cache_dir = benchmark.configure_compile_cache(params.device)
  if cache_dir:
    print(f"XLA compilation cache: {cache_dir}", file=sys.stderr,
          flush=True)
  p = params
  # Decode-cost variants (serving/decode.py LMSpec): None-when-off so a
  # variant-free run's spec config -- and therefore its run-store
  # fingerprint -- is byte-identical to pre-variant history.
  variant_kw = {}
  if p.serving_quantize:
    variant_kw["quantize"] = p.serving_quantize
  if p.serving_kv_page_size:
    variant_kw["kv_page_size"] = p.serving_kv_page_size
  if p.serving_speculative_k:
    variant_kw["speculative_k"] = p.serving_speculative_k
    variant_kw["draft_n_layers"] = p.serving_draft_layers
  if p.serving_model_shards:
    variant_kw["model_shards"] = p.serving_model_shards
  spec = LMSpec(**variant_kw)
  n_req, rate, max_new = 128, 16.0, 32
  # Flag unset = the engine's own default ladder (the params.py help's
  # contract), so a default bench run fingerprints identically to any
  # other default-engine consumer.
  ladder_kw = ({"bucket_ladder":
                validation.parse_bucket_ladder(p.serving_bucket_ladder)}
               if p.serving_bucket_ladder else {})
  cfg = EngineConfig(
      spec=spec, **ladder_kw,
      batching=p.serving_batching or "continuous",
      max_new_tokens=p.serving_max_new_tokens or max_new,
      max_queue_depth=p.serving_queue_depth or 64,
      ttft_slo_s=(p.serving_ttft_slo_ms / 1e3
                  if p.serving_ttft_slo_ms is not None else None),
      tenant_tokens_per_s=p.serving_tenant_tokens_per_s)
  n_req = args.serving_requests or n_req
  rate = args.serving_rate or rate
  n_tenants = max(1, args.serving_tenants or 1)
  tenants = (tuple(f"tenant{i}" for i in range(n_tenants))
             if n_tenants > 1 else ("default",))
  workload = poisson_workload(n_req, rate, spec, seed=0,
                              max_new_tokens=cfg.max_new_tokens,
                              tenants=tenants)

  # INT8 accuracy gate (ISSUE 16a): before serving a quantized spec,
  # measure prefix-conditioned greedy agreement vs the f32 weights on a
  # probe slice of the SAME seeded workload. Below the bar the bench
  # falls back to the dense arm and says so -- a quantized line never
  # enters the run store without its measured accuracy evidence.
  quantize_gate = None
  if spec.quantize:
    import dataclasses
    from kf_benchmarks_tpu.serving import decode as decode_lib
    probe = [req.prompt for _, req in workload[:8]]
    raw = decode_lib.init_variables(spec, seed=0)
    quantize_gate = decode_lib.quantize_agreement(
        spec, raw, probe, max_new_tokens=min(8, cfg.max_new_tokens))
    if not quantize_gate["passed"]:
      print(
          f"serving bench: int8 gate FAILED (agreement "
          f"{quantize_gate['agreement']:.4f} < "
          f"{decode_lib.QUANTIZE_AGREEMENT_BAR}) -- serving the dense "
          "arm instead", file=sys.stderr, flush=True)
      spec = dataclasses.replace(spec, quantize=None)
      cfg = dataclasses.replace(cfg, spec=spec)

  trace = tracing.RunTrace(path=None)
  tracing.activate(trace)
  registry = metrics_lib.activate(metrics_lib.MetricRegistry())
  engine = ServingEngine(cfg, seed=0)
  server = None
  if args.metrics_port is not None:
    server = engine.serve_metrics(args.metrics_port, registry)
    print(f"serving /metrics + /healthz on 127.0.0.1:{server.port}",
          file=sys.stderr, flush=True)
  n_warm = engine.warm()  # TTFT must measure the system, not XLA
  print(f"serving bench: {n_warm} executable(s) warmed across ladder "
        f"{cfg.bucket_ladder}", file=sys.stderr, flush=True)
  engine.replay(workload)
  stats = engine.stats()
  if server is not None:
    server.close()

  metric = "serving_tokens_per_sec"
  value = stats.get("serving/tokens_per_sec") or 0.0
  ledger = trace.compile_ledger()
  record = {
      "metric": metric,
      "value": round(value, 2),
      "unit": "tokens/sec",
      "compile_ledger": {"shapes": ledger.get("shapes", 0),
                         "total_compile_s": ledger.get("total_compile_s")},
      # Which decode-cost variants shaped this line (ISSUE 16): the
      # same fields ride spec.config() into the fingerprint below, so
      # variant runs never mix with dense/bf16 history.
      "decode_variant": {"quantize": spec.quantize,
                         "paged_kv": spec.kv_page_size or None,
                         "speculative_k": spec.speculative_k or None,
                         "model_shards": spec.model_shards or None},
  }
  if quantize_gate is not None:
    # The measured accuracy evidence behind the int8 decision: if the
    # gate failed, decode_variant.quantize above is already None (the
    # served arm fell back to dense) and this block says why.
    record["quantize_gate"] = {
        "agreement": round(quantize_gate["agreement"], 6),
        "max_logit_delta": round(quantize_gate["max_logit_delta"], 6),
        "passed": quantize_gate["passed"]}
  # Every serving/* stat is a registered schema key; Nones (an empty
  # replay) drop so the JSON line stays dense. The per-tenant block
  # prunes the same way per tenant.
  record.update({k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in stats.items()
                 if v is not None and k != "serving_tenants"})
  tenant_block = {
      t: {k: (round(v, 6) if isinstance(v, float) else v)
          for k, v in block.items() if v is not None}
      for t, block in (stats.get("serving_tenants") or {}).items()}
  if tenant_block:
    record["serving_tenants"] = tenant_block
  record["git_rev"] = metrics_lib.git_revision()
  record["platform"] = device["platform"]
  record["device_kind"] = device["kind"]
  record["device_count"] = device["count"]
  print(json.dumps(record), flush=True)
  # Multi-tenant replays key apart from single-tenant history; the
  # default (tenants=1) workload desc stays byte-identical to the
  # pre-tenant fingerprint.
  workload_desc = {"requests": n_req, "rate": rate}
  if n_tenants > 1:
    workload_desc["tenants"] = n_tenants
  fingerprint = baseline_lib.config_fingerprint_key(
      {**params._asdict(),
       "serving_spec": spec.config(),
       "serving_workload": workload_desc},
      "serving_bench")
  rc = record_and_check(record, args.run_store_dir,
                        args.check_regression, run_id=trace.run_id,
                        fingerprint=fingerprint,
                        extra_keys=("serving/ttft_p99",
                                    "serving/shed_fraction"))
  tracing.deactivate()
  metrics_lib.deactivate()
  return rc


def record_and_check(record, store_dir, check_regression,
                     run_id=None, fingerprint=None,
                     extra_keys=()) -> int:
  """Append this run's record to the run store; under
  --check-regression, judge it against the trailing same-fingerprint
  median and return the process exit code (nonzero = regression).
  Every verdict reads its polarity from the metric schema
  (metrics.metric_direction), so a lower-is-better headline (TTFT,
  shed fraction) regresses on INCREASE; ``extra_keys`` adds snapshot
  keys gated the same way, one verdict line each (the serving bench
  gates TTFT p99 + shed fraction alongside tokens/s). Split from
  main() so the sentinel leg is unit-testable on synthetic records
  without running the benchmark."""
  from kf_benchmarks_tpu import metrics as metrics_lib
  from kf_benchmarks_tpu import tracing
  import jax

  store = metrics_lib.RunStore(store_dir)
  try:
    rec = metrics_lib.run_record(
        metric=record["metric"], value=record["value"],
        unit=record["unit"],
        fingerprint=fingerprint or metrics_lib.bench_fingerprint(),
        # The RUN'S id (stats carry the trace session's), so the store
        # record joins its trace/flight-recorder artifacts; minted only
        # when the caller has none (synthetic-record tests).
        run_id=run_id or tracing.resolve_run_id(),
        platform=record["platform"],
        git_rev=record.get("git_rev"),
        jax_version=jax.__version__,
        snapshot=metrics_lib.flatten_stats(record))
    # History is read BEFORE the append so the fresh run never judges
    # itself; the append itself runs unconditionally (the store is the
    # bench trajectory's memory, sentinel on or off).
    history = store.records()
    rec = store.append(rec)
    if rec.get("baseline"):
      print("run store: first real-chip record for fingerprint "
            f"{rec['fingerprint'][:16]} promoted to baseline",
            file=sys.stderr, flush=True)
  except (OSError, ValueError) as e:
    print(f"run store append failed (non-fatal): {e}",
          file=sys.stderr, flush=True)
    return 0
  if not check_regression:
    return 0
  verdict = metrics_lib.check_regression(
      history, rec,
      higher_is_better=metrics_lib.metric_direction(rec["metric"]))
  print(metrics_lib.verdict_line(verdict), file=sys.stderr, flush=True)
  rc = 1 if verdict["status"] == "regression" else 0
  for key in extra_keys:
    extra = metrics_lib.snapshot_check(history, rec, key)
    if extra is None:
      continue
    print(metrics_lib.verdict_line(extra), file=sys.stderr, flush=True)
    if extra["status"] == "regression":
      rc = 1
  return rc


if __name__ == "__main__":
  sys.exit(main())
