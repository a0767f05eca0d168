"""Cross-flag validation rules.

The reference scatters ~35 cross-flag checks through BenchmarkCNN.__init__
(ref: benchmark_cnn.py:1268-1352); here they are standalone, unit-testable
validators run before the runtime is constructed (SURVEY 7.1).
"""

from __future__ import annotations


# Largest world for async-PS's sequential_apply path (stateful
# optimizers): each step costs n optimizer applications (lax.scan) plus an
# all-gather of n full gradient trees, so the mode is bounded to sizes
# where that stays tractable (see validate_cross_flags and PERF.md).
ASYNC_PS_SEQUENTIAL_MAX_DEVICES = 32


class ParamError(ValueError):
  pass


def parse_mesh_shape(mesh_shape: str):
  """'BxM' -> (B, M), both positive ints (ParamError otherwise). Pure
  (no jax): callable from validation and from the mesh builder."""
  parts = str(mesh_shape).lower().split("x")
  try:
    dims = [int(v) for v in parts]
  except ValueError:
    dims = []
  if len(dims) != 2 or any(d < 1 for d in dims):
    raise ParamError(
        f"--mesh_shape={mesh_shape!r}: expected 'BxM' with positive "
        "integer batch and model axis sizes (e.g. 8x1, 4x2)")
  return dims[0], dims[1]


def parse_bucket_ladder(ladder: str):
  """'1,4,16,64' -> (1, 4, 16, 64): strictly ascending positive ints
  (ParamError otherwise). Pure (no jax): callable from validation and
  from bench.py / the serving sweep when they build an EngineConfig."""
  parts = [s.strip() for s in str(ladder).split(",") if s.strip()]
  try:
    buckets = tuple(int(v) for v in parts)
  except ValueError:
    buckets = ()
  if not buckets or any(b < 1 for b in buckets) or \
      list(buckets) != sorted(set(buckets)):
    raise ParamError(
        f"--serving_bucket_ladder={ladder!r}: expected strictly "
        "ascending positive integers (e.g. '1,4,16,64'); the ladder "
        "bounds the serving engine's executable set")
  return buckets


# Flags with NO cross-flag constraint, each with the reason -- the
# explicit no-validation marker the hazard lint requires (analysis/
# lint.py rule 'flag-validation'): every flag in the params registry
# must either appear in validate_cross_flags below or carry an entry
# here, so a new flag cannot silently skip validation. A flag that
# appears in BOTH is a stale marker and fails the lint.
NO_CROSS_FLAG_VALIDATION = {
    # Optimizer hyperparameters: numerically free knobs; the per-spec
    # bounds in the flags registry are the whole contract.
    "adam_beta1": "free hyperparameter (registry bounds only)",
    "adam_beta2": "free hyperparameter (registry bounds only)",
    "adam_epsilon": "free hyperparameter (registry bounds only)",
    "momentum": "free hyperparameter (registry bounds only)",
    "rmsprop_decay": "free hyperparameter (registry bounds only)",
    "rmsprop_epsilon": "free hyperparameter (registry bounds only)",
    "rmsprop_momentum": "free hyperparameter (registry bounds only)",
    "weight_decay": "free hyperparameter (registry bounds only)",
    "gradient_clip": "free hyperparameter; None disables",
    "fp16_loss_scale": "numeric knob; engagement gated by use_fp16 "
                       "checks above",
    "fp16_inc_loss_scale_every_n": "numeric knob of the auto-loss-scale "
                                   "machine; engagement validated via "
                                   "fp16_enable_auto_loss_scale",
    "single_l2_loss_op": "numerically identical formulation toggle "
                         "(train_step.l2_loss)",
    # Display / logging / artifact sinks: consumed as-is by the
    # observability layer; any path works, nothing to cross-check.
    "display_every": "display cadence only",
    "print_training_accuracy": "adds metric columns only",
    "benchmark_log_dir": "artifact sink path",
    "compilation_cache_dir": "cache directory path; any writable path "
                             "works with every mode (benchmark.py "
                             "derives <train_dir>/xla_cache when unset)",
    "benchmark_test_id": "artifact metadata string",
    "eval_dir": "artifact sink path",
    "eval_interval_secs": "eval-loop cadence only",
    "save_summaries_steps": "summary cadence only",
    # (summary_verbosity left this list when --shard_params began
    # cross-checking the tier-2 histogram surface.)
    "loss_type_to_report": "display column selector",
    "use_chrome_trace_format": "output-format toggle of the "
                               "--trace_events_file exporter (tracing.py:"
                               " Chrome trace-event JSON when true, raw "
                               "span JSONL when false); reference CLIs "
                               "also pass it with --trace_file, where it "
                               "stays inert (jax.profiler owns that "
                               "format), so no hard cross-check",
    "max_ckpts_to_keep": "checkpoint GC depth",
    "tf_random_seed": "seed value; any int is valid",
    "num_warmup_batches": "None = runtime default (benchmark.py:_run)",
    # Input pipeline knobs: consumed by data/ preprocessing with safe
    # fallbacks; no cross-flag interaction. (data_dir and
    # use_synthetic_gpu_images left this list when --packed_sequences
    # began cross-checking them.)
    "data_name": "dataset selector; inferred from data_dir when unset",
    "batch_group_size": "host pipeline batching depth",
    "distortions": "preprocessing toggle",
    "resize_method": "preprocessing method selector",
    "input_preprocessor": "preprocessor selector (datasets resolve it)",
    "input_preprocessing_parallelism": "host thread count",
    "datasets_num_private_threads": "host thread count",
    "datasets_prefetch_buffer_size": "feeder prefetch depth",
    "input_prefetch_depth": "explicit feeder prefetch depth override "
                            "(benchmark.feeder_prefetch); any depth "
                            ">= 1 is valid with every input path",
    "datasets_repeat_cached_sample": "pipeline toggle",
    "datasets_use_caching": "pipeline toggle",
    # Telemetry knobs (PR 4): numeric thresholds with registry bounds;
    # engagement is validated through health_stats above.
    "health_grad_norm_sigma": "anomaly threshold (registry bounds only)",
    "flight_recorder_window": "ring size (registry bounds only)",
    "elastic_check_every_n_steps": "resize-poll cadence only",
    # Cluster wiring: free-form host lists/ids consumed by cluster.py;
    # the modes that REQUIRE them are validated via job_name above.
    "ps_hosts": "cluster wiring string (cluster.py)",
    "task_index": "cluster wiring index (cluster.py)",
    "process_index": "cluster wiring index (cluster.py)",
    "sync_on_finish": "accepted for reference CLI parity; drain() is "
                      "unconditional at run end",
    # Model-private paths, host pools and sinks with no cross-flag
    # interaction.
    "backbone_model_path": "SSD backbone restore path; model-private",
    "num_intra_threads": "host thread pool size",
    "partitioned_graph_file_prefix": "inert TF graph-dump knob "
                                     "(reference parity)",
    "agg_small_grads_max_group": "reducer group bound; engagement "
                                 "validated via agg_small_grads_max_bytes",
}


def eval_during_training_enabled(params) -> bool:
  """Any of the four mid-training eval schedules set
  (ref: benchmark_cnn.py:1317-1327)."""
  return any(map(bool, [
      params.eval_during_training_every_n_steps,
      params.eval_during_training_every_n_epochs,
      params.eval_during_training_at_specified_steps,
      params.eval_during_training_at_specified_epochs,
  ]))


def validate_cross_flags(params) -> None:
  """Raise ParamError on inconsistent flag combinations."""
  p = params
  if p.eval:
    if p.forward_only:
      raise ParamError("--eval is incompatible with --forward_only "
                       "(ref :1269-1270)")
    if p.job_name:
      raise ParamError("--job_name is unsupported with --eval (ref :1273)")
  if p.num_batches is not None and p.num_epochs is not None:
    raise ParamError("At most one of --num_batches and --num_epochs may be "
                     "set (ref :1300-1303)")
  # Serving-engine knobs (bench.py --serving / serving_sweep --engine):
  # value-validated here so a bad ladder or policy fails at parse time,
  # not mid-serve. serving_max_new_tokens / serving_queue_depth /
  # serving_ttft_slo_ms / serving_tenant_tokens_per_s carry their whole
  # contract in the registry bounds (lower_bound), nothing to cross.
  if getattr(p, "serving_bucket_ladder", None):
    parse_bucket_ladder(p.serving_bucket_ladder)
  batching = getattr(p, "serving_batching", None)
  if batching is not None and batching not in ("continuous", "static"):
    raise ParamError(
        f"--serving_batching={batching!r}: expected 'continuous' "
        "(in-flight batching) or 'static' (batch-and-drain)")
  # Decode-cost variants (ISSUE 16). serving_quantize carries its
  # whole contract in the registry enum; the two below cross flags.
  page = getattr(p, "serving_kv_page_size", None)
  if page is not None:
    # The serving context length defaults to the zoo transformer_lm's
    # SEQ_LEN (serving/decode.py LMSpec.max_len); LMSpec.__post_init__
    # re-validates against the per-spec max_len when a caller
    # overrides it.
    from kf_benchmarks_tpu.models import transformer_lm as _lm
    if _lm.SEQ_LEN % page:
      raise ParamError(
          f"--serving_kv_page_size={page} must divide the serving "
          f"context length ({_lm.SEQ_LEN}): partial pages would break "
          "the page-table <-> ring position bijection "
          "(serving/decode.py)")
  spec_k = getattr(p, "serving_speculative_k", None)
  draft_layers = getattr(p, "serving_draft_layers", None)
  if spec_k is not None and draft_layers is None:
    raise ParamError(
        f"--serving_speculative_k={spec_k} requires a draft spec: set "
        "--serving_draft_layers (< the served model's layer count; "
        "serving/decode.py draft_spec)")
  if draft_layers is not None and spec_k is None:
    raise ParamError(
        f"--serving_draft_layers={draft_layers} is inert without "
        "--serving_speculative_k (the draft only runs inside "
        "speculative rounds)")
  if p.num_batches is not None and p.num_batches <= 0:
    raise ParamError("--num_batches must be positive")
  if (getattr(p, "steps_per_dispatch", 1) or 1) > 1:
    # Chunked dispatch wraps the TRAIN step in a device-resident scan
    # (train_step.py); eval/forward-only loops dispatch a stateless
    # forward per step and are not chunked (yet).
    if p.eval:
      raise ParamError("--steps_per_dispatch > 1 applies to training "
                       "only; it cannot be combined with --eval")
    if p.forward_only:
      raise ParamError("--steps_per_dispatch > 1 applies to training "
                       "only; it cannot be combined with --forward_only")
  if (getattr(p, "num_grad_accum", 1) or 1) > 1:
    m = p.num_grad_accum
    # Microbatching wraps the TRAIN step's forward/backward in a scan
    # (train_step.py); the modes below either have no gradient to
    # accumulate or consume gradients in a shape the scan cannot feed.
    if p.eval:
      raise ParamError("--num_grad_accum > 1 applies to training only; "
                       "it cannot be combined with --eval")
    if p.forward_only:
      raise ParamError("--num_grad_accum > 1 applies to training only; "
                       "it cannot be combined with --forward_only")
    if p.batch_size and p.batch_size % m:
      raise ParamError(
          f"--num_grad_accum={m} must divide --batch_size="
          f"{p.batch_size}: the step splits each per-device batch into "
          "M equal microbatches (a ragged tail microbatch would change "
          "the gradient weighting silently)")
    if p.staged_vars:
      raise ParamError(
          "--num_grad_accum > 1 cannot be combined with --staged_vars: "
          "staged reads hand the forward one-step-stale weights from a "
          "single staging slot per step (variable_mgr.py:246-274); "
          "microbatches would all read the same stale copy while the "
          "accumulated update lands once, making the effective "
          "staleness M-dependent in a way the reference semantics "
          "never defined")
    if (p.variable_update == "parameter_server"
        and not p.cross_replica_sync):
      raise ParamError(
          "--num_grad_accum > 1 cannot be combined with async "
          "parameter_server (--cross_replica_sync=false): the "
          "sequential-apply path consumes each replica's UNAVERAGED "
          "per-batch gradient (train_step.py sequential_apply); an "
          "accumulated mean-of-microbatches gradient would silently "
          "change what each of its n optimizer applications sees. Use "
          "a synchronous --variable_update with accumulation")
    if p.adaptive_batch_size:
      raise ParamError(
          "--num_grad_accum > 1 cannot be combined with "
          "--adaptive_batch_size: the policy re-picks the per-device "
          "batch mid-run and cannot guarantee divisibility by M")
  if getattr(p, "packed_sequences", False):
    # Packing re-shapes the LM input (tokens -> the (B, 3, T) packed
    # stack) and re-weights losses by real-token count; only the
    # segment-aware transformer_lm family consumes that form.
    if p.model != "transformer_lm":
      raise ParamError(
          "--packed_sequences is a transformer_lm input form (segment-"
          f"aware attention + weighted LM loss); got --model={p.model}. "
          "The CNN/speech/recsys families have no variable-length "
          "sequence axis to pack")
    if p.eval or p.forward_only:
      raise ParamError(
          "--packed_sequences applies to training only (the packed "
          "stream feeds the train loop); it cannot be combined with "
          "--eval or --forward_only")
    if p.data_dir and not p.use_synthetic_gpu_images:
      raise ParamError(
          "--packed_sequences draws documents from its seeded "
          "synthetic length distribution (data/packing.py); packing a "
          "real --data_dir corpus is not wired yet -- drop --data_dir "
          "or add --use_synthetic_gpu_images")
    # --elastic / --adaptive_batch_size compose: every reshape reopens
    # the input stream (benchmark._open_input), and the packer is
    # re-instantiated at the new row count/incarnation seed.
  if getattr(p, "autotuned_config", None) and (p.eval or p.forward_only):
    # The tuned table tunes the TRAINING step's program-shaping knobs
    # (--steps_per_dispatch and friends, analysis/autotune.py); applying
    # it to eval/forward-only would silently set training-only flags
    # (the round-1 ineffective-flag defect class, same rule as
    # --trace_events_file). benchmark.setup() re-checks before applying
    # so the failure names this flag, not the knob it would have set.
    raise ParamError(
        "--autotuned_config tunes the training step's program-shaping "
        "knobs (analysis/autotune.py); it cannot be combined with "
        "--eval or --forward_only")
  if getattr(p, "attn_block", None):
    if p.model != "transformer_lm":
      raise ParamError(
          "--attn_block sizes the transformer_lm attention tiling "
          f"(parallel/sequence.py); got --model={p.model}. The CNN/"
          "speech/recsys families have no attention blocks to tile")
    # Lazy import (the models package imports jax/flax; every caller of
    # cross-flag validation has them, but module import must stay light).
    from kf_benchmarks_tpu.models import transformer_lm as _lm
    if _lm.SEQ_LEN % p.attn_block:
      raise ParamError(
          f"--attn_block={p.attn_block} must divide the transformer_lm "
          f"sequence length {_lm.SEQ_LEN} (blockwise_attention tiles "
          "the K/V axis in whole blocks)")
  lm_flags = [f for f in ("seq_len", "lm_config", "lm_layers_held",
                          "lm_first_layer_held", "lm_layer_shards",
                          "lm_layer_shard_index", "lm_vocab_shards")
              if getattr(p, f, None) is not None]
  if p.model != "mla_moe_lm":
    if lm_flags:
      raise ParamError(
          f"--{lm_flags[0]} is read by --model=mla_moe_lm alone (the "
          "decoder whose sizes come from a configuration); got "
          f"--model={p.model}, whose sequence length and sizes are "
          "constants of its module")
  else:
    # What the share of a deployment does not compose with YET: each of
    # these would need the expert layer's exchange, a sharded layout of
    # the stacked experts, or an input form the module does not read.
    if p.num_devices > 1:
      raise ParamError(
          "--model=mla_moe_lm runs on one device: its expert layer runs "
          "without the exchange, and the router's selection bias is "
          "updated from this chip's tokens alone (more devices would "
          f"average it like batch-norm statistics); got --num_devices="
          f"{p.num_devices}")
    if getattr(p, "shard_params", False) or getattr(
        p, "shard_optimizer_state", False):
      raise ParamError(
          "--model=mla_moe_lm cannot be combined with --shard_params / "
          "--shard_optimizer_state: the flat-shard layout has no rule "
          "for the stacked experts or the router state in batch_stats")
    if getattr(p, "packed_sequences", False):
      raise ParamError(
          "--model=mla_moe_lm cannot be combined with --packed_sequences: "
          "neither of its attention modules nor its fused losses take "
          "segment ids yet")
    if p.eval or p.forward_only or getattr(p, "aot_save_path", None):
      raise ParamError(
          "--model=mla_moe_lm trains only: --eval, --forward_only and "
          "serving export are not wired for the router state, a "
          "multi-token-prediction head, or a cache that holds window and "
          "full layers side by side")
    if (getattr(p, "num_grad_accum", 1) or 1) > 1:
      raise ParamError(
          "--model=mla_moe_lm cannot be combined with --num_grad_accum > "
          "1: the router's bias would move once per MICROBATCH, which is "
          "not the rule of arXiv:2412.19437 section 2.1.2, and no test "
          "covers it")
    if (getattr(p, "steps_per_dispatch", 1) or 1) > 1:
      raise ParamError(
          "--model=mla_moe_lm cannot be combined with "
          "--steps_per_dispatch > 1: its per-step counters are not "
          "stacked through the chunked program yet")
    # A sequence the state-space scan cannot take is refused here, with
    # the scan's own reason, not at the first trace of the step (lazy
    # import, as above; a configuration that cannot be read is refused
    # where it is loaded).
    from kf_benchmarks_tpu.models import mla_moe_lm as _moe_lm
    from kf_benchmarks_tpu.ops import ssd as _ssd
    try:
      _, raw = _moe_lm.read_config_file(
          getattr(p, "lm_config", None) or _moe_lm.DEFAULT_CONFIG)
    except ValueError:
      raw = {}
    if _moe_lm.MAMBA in raw.get("hybrid_override_pattern", ""):
      why = _ssd.refusal(getattr(p, "seq_len", None) or
                         _moe_lm.DEFAULT_SEQ_LEN, raw["chunk_size"])
      if why:
        raise ParamError(f"--seq_len with --lm_config={p.lm_config}: {why}")
  mesh_shape = getattr(p, "mesh_shape", None)
  sharded = bool(getattr(p, "shard_optimizer_state", False))
  if mesh_shape:
    b, m = parse_mesh_shape(mesh_shape)
    if b * m != p.num_devices:
      raise ParamError(
          f"--mesh_shape={mesh_shape} spans {b * m} devices but "
          f"--num_devices={p.num_devices}: the named 2-D mesh must "
          "cover exactly the requested devices")
    if m > 1 and not sharded:
      raise ParamError(
          f"--mesh_shape={mesh_shape}: a model axis > 1 requires "
          "--shard_optimizer_state -- without it the core step has no "
          "consumer for the axis and would silently duplicate every "
          "forward/backward M times")
  if sharded:
    # --shard_optimizer_state exclusion matrix. The sharded step
    # replaces the strategy's gradient pass with reduce-scatter +
    # all-gather and applies the optimizer on 1/n flat state shards
    # (ops/sharded.py); modes below either own gradient aggregation
    # themselves, need per-replica gradient trees the scatter never
    # materializes, or read full-tree state the shards no longer hold.
    if p.eval or p.forward_only:
      raise ParamError(
          "--shard_optimizer_state applies to training only (there is "
          "no optimizer state to shard in --eval/--forward_only)")
    if p.variable_update not in ("replicated", "parameter_server"):
      raise ParamError(
          "--shard_optimizer_state requires --variable_update="
          f"replicated or parameter_server (got {p.variable_update!r}): "
          "independent/gossip modes keep per-replica diverged state "
          "with no global reduction to scatter, and the distributed_* "
          "modes' multi-process worlds are not wired to the sharded "
          "checkpoint layout yet")
    if not p.cross_replica_sync:
      raise ParamError(
          "--shard_optimizer_state cannot be combined with async "
          "parameter_server (--cross_replica_sync=false): the "
          "sequential-apply path serializes each replica's UNAVERAGED "
          "gradient through one shared full state copy "
          "(train_step.py); sharded state has no such copy")
    if p.job_name or (p.worker_hosts or []) or (p.num_processes or 1) > 1:
      raise ParamError(
          "--shard_optimizer_state is single-process for now: the "
          "checkpoint path saves the sharded optimizer state from "
          "locally-addressable rows (checkpoint.py), which a "
          "multi-host mesh cannot do chief-only without a cross-host "
          "gather leg")
    if p.optimizer == "lars":
      raise ParamError(
          "--shard_optimizer_state cannot be combined with "
          "--optimizer=lars: the LARS trust ratio needs per-LAYER "
          "param/update norms, and the flat 1/n shard cuts across "
          "layer boundaries. Every other stock optimizer updates "
          "elementwise, so the shard apply stays exact")
    if p.staged_vars:
      raise ParamError(
          "--shard_optimizer_state cannot be combined with "
          "--staged_vars: staged reads keep a second full weight copy "
          "per device (variable_mgr.py:246-274), the exact footprint "
          "sharded state exists to retire")
    if p.variable_consistency == "relaxed":
      raise ParamError(
          "--shard_optimizer_state cannot be combined with "
          "--variable_consistency=relaxed: the deferred-gradient bank "
          "stores a full gradient tree per device "
          "(train_step.py buffers); banking shards instead would "
          "change the staleness semantics silently")
    if p.adaptive_batch_size or p.track_grad_noise_scale:
      raise ParamError(
          "--shard_optimizer_state cannot be combined with "
          "--adaptive_batch_size/--track_grad_noise_scale: the "
          "noise-scale estimator contrasts PRE-reduction per-replica "
          "gradients with their replica mean (elastic.py), and the "
          "scattered reduction never materializes the replica mean")
    for flag, name in ((p.all_reduce_spec, "--all_reduce_spec"),
                       (p.gradient_repacking, "--gradient_repacking"),
                       (p.agg_small_grads_max_bytes > 0,
                        "--agg_small_grads_max_bytes"),
                       (p.hierarchical_copy, "--hierarchical_copy")):
      if flag:
        raise ParamError(
            f"--shard_optimizer_state cannot be combined with {name}: "
            "each reducer owns the reduction granularity (ref: "
            "batch_allreduce.py:300-317 selects one algorithm); the "
            "sharded path's reduction IS the per-leaf reduce-scatter")
    # --elastic composes since the cross-mesh rescale landed: a resize
    # re-slices the saved (n, k) shard stack onto the new topology
    # (checkpoint.py _reshard), preserving the model-axis width -- a
    # target the model axis does not divide is rejected at poll time,
    # not here (the target is only known mid-run).
    if p.health_stats:
      raise ParamError(
          "--health_stats cannot be combined with "
          "--shard_optimizer_state: the in-step stats read the full "
          "per-step update tree (telemetry.py health_partials), and "
          "the sharded apply only materializes this device's 1/n "
          "update shard. Drop the flag (auto-off with a note)")
  if getattr(p, "shard_params", False):
    # --shard_params (full FSDP): params join the optimizer state on
    # the (n, k) shard layout and re-assemble inside the compute
    # (train_step.py + ops/sharded.py). Requiring
    # --shard_optimizer_state makes the whole sharded exclusion matrix
    # above binding here too -- elementwise-optimizer family only (no
    # LARS), synchronous replicated/parameter_server only (no
    # async-PS, no independent/gossip), no staged vars / relaxed
    # consistency / packed reducers, single-process.
    if not sharded:
      raise ParamError(
          "--shard_params requires --shard_optimizer_state: the FSDP "
          "forward rides the sharded family's scatter/apply machinery "
          "(reduce-scatter mean, 1/n shard apply, the (n, k) "
          "checkpoint layout), and params-sharded-but-state-replicated "
          "would re-create exactly the per-device footprint ZeRO "
          "removes. Add --shard_optimizer_state (which also brings its "
          "exclusion matrix: elementwise optimizers only, synchronous "
          "replicated/parameter_server only, no --staged_vars)")
    if (p.summary_verbosity or 0) >= 2:
      raise ParamError(
          "--summary_verbosity >= 2 cannot be combined with "
          "--shard_params: the tier-2 parameter histograms read the "
          "replica-0 FULL parameter tree (observability.py "
          "write_histograms), which the FSDP layout stores as 1/n "
          "flat shards -- the histograms would silently describe one "
          "shard. Use verbosity 1 (scalars) or drop --shard_params "
          "for histogram debugging")
  if getattr(p, "partitioner", None) == "gspmd":
    # --partitioner=gspmd cross-flag matrix. The compiler-partitioned
    # twin (train_step.py) covers programs whose collectives are
    # PARTITIONING choices -- the sharded training families
    # (--shard_optimizer_state [+ --shard_params]) and the serving
    # decode leg (--serving_model_shards). Modes whose collectives ARE
    # the semantics stay manual-only and are rejected here with the
    # reason; note most also fall out of the sharded matrix above, but
    # a bare --partitioner=gspmd with one of them set deserves the
    # specific message, not the generic requires-sharded one.
    if p.staged_vars:
      raise ParamError(
          "--partitioner=gspmd cannot be combined with --staged_vars: "
          "the staging double-buffer is a hand-placed staleness "
          "pattern (variable_mgr.py:246-274), not a partitioning "
          "choice -- there is nothing for GSPMD to re-place")
    if p.variable_update == "independent":
      raise ParamError(
          "--partitioner=gspmd cannot be combined with "
          "--variable_update=independent: independent replicas run NO "
          "collectives at all; a partitioner twin would have an empty "
          "inventory to referee")
    if p.variable_update == "kungfu" and p.kungfu_option != "sync_sgd":
      raise ParamError(
          "--partitioner=gspmd cannot be combined with the gossip "
          f"modes (--kungfu_option={p.kungfu_option}): pair-averaging "
          "ppermutes and SMA weight pmeans are semantic hand "
          "placements (parallel/strategies.py), not compiler-"
          "placeable data movement")
    if (p.variable_update == "parameter_server"
        and not p.cross_replica_sync):
      raise ParamError(
          "--partitioner=gspmd cannot be combined with async "
          "parameter_server (--cross_replica_sync=false): the "
          "sequential-apply scan consumes per-replica UNAVERAGED "
          "gradients in replica order -- the collective order IS the "
          "semantics there")
    if p.hierarchical_copy or p.all_reduce_spec:
      raise ParamError(
          "--partitioner=gspmd cannot be combined with "
          "--hierarchical_copy/--all_reduce_spec: the hierarchical/"
          "spec'd reducers hand-pick the reduction algorithm (ref: "
          "batch_allreduce.py:300-317), which is exactly the choice "
          "gspmd delegates to the compiler")
    if not bool(getattr(p, "shard_optimizer_state", False)) and \
        not getattr(p, "serving_model_shards", None):
      raise ParamError(
          "--partitioner=gspmd covers the sharded training families "
          "(--shard_optimizer_state [+ --shard_params]) and the "
          "tensor-parallel serving leg (--serving_model_shards): the "
          "replicated 1-D program has no NamedSharding-annotated "
          "state for GSPMD to partition (train_step.py)")
  shards_tp = getattr(p, "serving_model_shards", None)
  if shards_tp:
    # Tensor-parallel serving (serving/decode.py model_shardings): the
    # head axis of the attention KV cache and the sharded weight
    # matrices split M ways, so M must divide both the head count and
    # the device pool the serving mesh draws from.
    from kf_benchmarks_tpu.models import transformer_lm as _lm
    if _lm.N_HEADS % shards_tp:
      raise ParamError(
          f"--serving_model_shards={shards_tp} must divide the served "
          f"LM's head count ({_lm.N_HEADS}): the KV cache and "
          "attention projections shard on the head axis "
          "(serving/decode.py model_shardings)")
    if p.num_devices % shards_tp:
      raise ParamError(
          f"--serving_model_shards={shards_tp} must divide "
          f"--num_devices={p.num_devices}: the serving 'model' mesh "
          "draws whole devices")
  if getattr(p, "fault_schedule", None):
    # Malformed schedules fail at startup, not at the named step: a
    # fault harness that silently skips its fault proves nothing.
    from kf_benchmarks_tpu import faults
    try:
      entries = faults.parse_schedule(p.fault_schedule)
    except faults.FaultScheduleError as e:
      raise ParamError(str(e))
    if any(f.kind == "corrupt_ckpt" for f in entries) and not p.train_dir:
      raise ParamError(
          "--fault_schedule=corrupt_ckpt@... requires --train_dir: "
          "there is no checkpoint to corrupt without one")
    if any(f.kind in ("kill", "sigterm") for f in entries) \
        and not p.train_dir:
      raise ParamError(
          "--fault_schedule kill/sigterm entries require --train_dir: "
          "the one-shot-across-generations marker lives there "
          "(faults.py) -- without it every relaunched generation "
          "re-kills itself at the same step, and there is no "
          "checkpoint to rejoin from anyway")
    if any(f.kind == "drop_msg" for f in entries) and not p.elastic:
      raise ParamError(
          "--fault_schedule=drop_msg@... requires --elastic: the fault "
          "suppresses a coordination-service poll, and without elastic "
          "polling there is no message to drop -- the injection would "
          "log success while testing nothing")
    if any(f.kind == "heartbeat_delay" for f in entries) and (
        not p.stall_watchdog_factor or
        not (p.train_dir or p.health_stats)):
      raise ParamError(
          "--fault_schedule=heartbeat_delay@... requires a live stall "
          "watchdog to starve: --stall_watchdog_factor > 0 plus a "
          "telemetry session (--train_dir, or explicit --health_stats) "
          "-- otherwise the injected silence is observed by nothing")
    if p.eval or p.forward_only:
      raise ParamError(
          "--fault_schedule applies to training runs only (the faults "
          "fire at train-dispatch boundaries); it cannot be combined "
          "with --eval or --forward_only")
  if (p.adaptive_batch_size and
      p.adaptive_batch_min > p.adaptive_batch_max):
    raise ParamError(
        f"--adaptive_batch_min={p.adaptive_batch_min} exceeds "
        f"--adaptive_batch_max={p.adaptive_batch_max}: the adaptive "
        "policy's search interval is empty")
  if p.num_epochs is not None and p.num_epochs <= 0:
    raise ParamError("--num_epochs must be positive")
  if p.num_eval_batches is not None and p.num_eval_epochs is not None:
    raise ParamError("At most one of --num_eval_batches and "
                     "--num_eval_epochs may be set (ref "
                     "get_num_batches_and_epochs, :782-800)")
  if p.num_eval_batches is not None and p.num_eval_batches <= 0:
    raise ParamError("--num_eval_batches must be positive")
  if p.num_eval_epochs is not None and p.num_eval_epochs <= 0:
    raise ParamError("--num_eval_epochs must be positive")
  if p.coordinator_address and ":" not in p.coordinator_address:
    raise ParamError("--coordinator_address must be host:port "
                     f"(got {p.coordinator_address!r})")
  if p.forward_only and p.variable_update in ("distributed_replicated",
                                              "distributed_all_reduce",
                                              "collective_all_reduce"):
    raise ParamError(f"--forward_only cannot be used with "
                     f"--variable_update={p.variable_update} (ref :1306-1310)")
  if p.variable_update in ("horovod", "kungfu"):
    # The reference requires one GPU per process for external DP runtimes
    # (ref :1287-1297). On TPU the SPMD program owns every local chip, so we
    # relax the device-count rule but keep the job_name exclusion.
    if p.job_name:
      raise ParamError(f"--job_name is incompatible with "
                       f"--variable_update={p.variable_update} "
                       f"(ref :1293-1297)")
  if p.variable_update == "distributed_replicated":
    if not p.job_name:
      raise ParamError("distributed_replicated requires --job_name "
                       "(ref :1311-1314)")
    if not p.cross_replica_sync:
      raise ParamError("distributed_replicated requires "
                       "--cross_replica_sync=true (ref :1315-1318)")
  if p.variable_update == "distributed_all_reduce" and not p.all_reduce_spec:
    raise ParamError("distributed_all_reduce requires --all_reduce_spec "
                     "(ref :1319-1321)")
  if p.fp16_vars and not p.use_fp16:
    raise ParamError("--fp16_vars requires --use_fp16 (ref :1330-1331)")
  if p.fp16_vars and p.gradient_repacking:
    raise ParamError("--fp16_vars cannot be used with --gradient_repacking "
                     "(ref :1284-1285)")
  if p.fp16_enable_auto_loss_scale and not p.use_fp16:
    raise ParamError("--fp16_enable_auto_loss_scale requires --use_fp16 "
                     "(ref :1334-1336)")
  if (p.variable_update == "parameter_server" and not p.cross_replica_sync
      and p.optimizer != "sgd"
      and p.num_devices > ASYNC_PS_SEQUENTIAL_MAX_DEVICES):
    # Async PS + stateful optimizer serializes every replica's gradient
    # through the shared optimizer state: O(n) optimizer applications per
    # step and an O(n * |grads|) all-gather (train_step.py
    # sequential_apply). Faithful to the PS semantics but a CORRECTNESS
    # mode -- at pod scale the scan alone would dominate the step and the
    # gather may not fit HBM, so large worlds are rejected up front
    # (VERDICT r3 weak #4). SGD is exempt: N sequential applications
    # collapse exactly into one summed update.
    raise ParamError(
        "async parameter_server (--cross_replica_sync=false) with a "
        f"stateful optimizer ({p.optimizer}) applies num_devices "
        "optimizer updates sequentially through shared state each step; "
        f"capped at {ASYNC_PS_SEQUENTIAL_MAX_DEVICES} devices. Use "
        "--optimizer=sgd (exact single-update collapse) or a "
        "synchronous --variable_update at this scale")
  if p.staged_vars and p.variable_update != "parameter_server":
    raise ParamError("--staged_vars is only supported with "
                     "--variable_update=parameter_server (ref :1478-1479)")
  if p.staged_vars and p.fp16_enable_auto_loss_scale:
    raise ParamError("Automatic loss scaling is not supported with "
                     "--staged_vars (ref :1304-1305)")
  if p.staged_vars and eval_during_training_enabled(p):
    raise ParamError("--eval_during_training_* is not compatible with "
                     "--staged_vars (ref :1335-1336)")
  if p.variable_consistency == "relaxed" and p.variable_update not in (
      "replicated", "distributed_replicated", "parameter_server",
      "collective_all_reduce", "distributed_all_reduce"):
    raise ParamError(
        "--variable_consistency=relaxed requires a replicated-family "
        "--variable_update (the deferral lives in the batched all-reduce, "
        "ref: batch_allreduce.py:32-153; independent/kungfu/horovod "
        "reduce outside it)")
  if (p.use_fp16 and p.fp16_enable_auto_loss_scale and
      p.variable_update not in ("parameter_server", "replicated",
                                "independent", "kungfu")):
    # Ref restricts auto loss scaling to ps/replicated/independent
    # (ref :1299-1303); kungfu is additionally allowed here because the
    # SPMD state machine makes the finite-decision replica-uniform via
    # pmin (train_step.py), which the reference's chief-only check could
    # not do for externally-reduced modes.
    raise ParamError("Automatic loss scaling is not supported with "
                     f"--variable_update={p.variable_update} (ref :1299-1303)")
  if p.hierarchical_copy and p.num_devices <= 1:
    raise ParamError("--hierarchical_copy requires more than one device "
                     "(ref :1310-1311)")
  if bool(p.learning_rate_decay_factor) != bool(p.num_epochs_per_decay):
    raise ParamError("--learning_rate_decay_factor and "
                     "--num_epochs_per_decay must be set together "
                     "(ref :1271-1277)")
  if p.learning_rate_decay_factor and p.init_learning_rate is None:
    raise ParamError("LR decay flags require --init_learning_rate "
                     "(ref :1271-1277)")
  if p.minimum_learning_rate and not (p.learning_rate_decay_factor and
                                      p.num_epochs_per_decay and
                                      p.init_learning_rate is not None):
    raise ParamError("--minimum_learning_rate requires "
                     "--init_learning_rate, --learning_rate_decay_factor "
                     "and --num_epochs_per_decay (ref :445-449, :1143-1146)")
  if p.piecewise_learning_rate_schedule and p.init_learning_rate is not None:
    raise ParamError("--piecewise_learning_rate_schedule cannot be combined "
                     "with --init_learning_rate (ref :1104-1120)")
  if (p.piecewise_learning_rate_schedule and
      (p.learning_rate_decay_factor or p.num_learning_rate_warmup_epochs)):
    raise ParamError("--piecewise_learning_rate_schedule cannot be combined "
                     "with decay/warmup flags (ref :1116-1120)")
  edt_flags = [p.eval_during_training_every_n_steps,
               p.eval_during_training_every_n_epochs,
               p.eval_during_training_at_specified_steps,
               p.eval_during_training_at_specified_epochs]
  if sum(map(bool, edt_flags)) > 1:
    raise ParamError("At most one --eval_during_training_* flag may be "
                     "specified (ref :1316-1325)")
  if eval_during_training_enabled(p):
    if p.eval:
      raise ParamError("eval-during-training flags are incompatible with "
                       "--eval (ref :1329-1330)")
    if p.forward_only:
      raise ParamError("eval-during-training flags are incompatible with "
                       "--forward_only (ref :1331-1332)")
    if p.job_name:
      raise ParamError("--eval_during_training_* is not supported in "
                       "distributed ps/controller mode (ref :1333-1334)")
  if p.stop_at_top_1_accuracy and not eval_during_training_enabled(p):
    # The reference allows it only with eval-during-training (ref :1339-1340).
    raise ParamError("--stop_at_top_1_accuracy requires eval-during-training "
                     "(ref :1339-1340)")
  if p.save_model_secs and p.save_model_steps:
    raise ParamError("At most one of --save_model_secs and "
                     "--save_model_steps may be set (ref :1341-1344)")
  if p.forward_only and p.job_name == "controller":
    raise ParamError("--forward_only is incompatible with controller jobs")
  if p.device == "cpu" and p.data_format == "NCHW":
    raise ParamError("NCHW is not supported on cpu device (ref :1323-1326)")
  if p.controller_host:
    raise ParamError(
        "--controller_host: the controller role has no TPU analog -- "
        "distributed_all_reduce's single-session graph maps to the flat "
        "SPMD program every worker runs (SURVEY 5.8; ref :576)")
  if getattr(p, "debugger", None):
    raise ParamError("--debugger: tfdbg has no TPU analog "
                     "(ref :370-377); use --trace_file / --tfprof_file "
                     "for profiling and --graph_file for program dumps")
  trt_mode = (getattr(p, "trt_mode", "") or "").upper()
  if trt_mode and trt_mode not in ("FP32", "FP16", "INT8"):
    raise ParamError(f"--trt_mode: unknown mode {p.trt_mode!r}; the "
                     "serving-export precisions are FP32, FP16, INT8 "
                     "(ref :615-620)")
  if trt_mode and not getattr(p, "aot_save_path", None):
    raise ParamError("--trt_mode sets the precision of the frozen "
                     "serving export and requires --forward_only with "
                     "--aot_save_path (the TRT conversion analog, ref "
                     ":615-620, :2466-2486)")
  if getattr(p, "trace_events_file", None) and (p.eval or p.forward_only):
    # The span timeline instruments the TRAINING loop's wall-clock
    # boundaries (feed, dispatch, compile, checkpoint, elastic seams);
    # the eval/forward-only drivers carry none of them, and silently
    # accepting the flag there would log success while tracing nothing
    # (the round-1 ineffective-flag defect class).
    raise ParamError(
        "--trace_events_file instruments training runs only (the span "
        "timeline covers the train loop's feed/dispatch/compile/"
        "checkpoint/elastic boundaries, tracing.py); it cannot be "
        "combined with --eval or --forward_only. The jax.profiler "
        "--trace_file capture works in every mode")
  if getattr(p, "metrics_port", None) and (p.eval or p.forward_only):
    # The live endpoint serves the TRAIN loop's registry session
    # (benchmark.py binds it around _train_loop); accepting the flag in
    # eval/forward-only would bind nothing and log success while
    # serving nothing (the round-1 ineffective-flag defect class, same
    # rule as --trace_events_file above).
    raise ParamError(
        "--metrics_port serves the training loop's metric registry "
        "(metrics.py); it cannot be combined with --eval or "
        "--forward_only")
  if getattr(p, "run_store_dir", None) and (p.eval or p.forward_only):
    raise ParamError(
        "--run_store_dir appends the TRAINING run's record to the "
        "run store (metrics.py RunStore, written at train-loop end); "
        "it cannot be combined with --eval or --forward_only. The "
        "bench/serving records come from bench.py, which owns its own "
        "store path")
  if p.aot_load_path and not p.forward_only:
    raise ParamError("--aot_load_path requires --forward_only (the "
                     "frozen artifact has no training program; ref: "
                     "TRT serving path, benchmark_cnn.py:2405-2525)")
  if p.aot_save_path and not p.forward_only:
    raise ParamError("--aot_save_path requires --forward_only (the "
                     "export freezes the inference program, the analog "
                     "of the reference's forward-only graph freeze; ref: "
                     "benchmark_cnn.py:2405-2525)")
  if p.aot_load_path and p.aot_save_path:
    raise ParamError("At most one of --aot_load_path and --aot_save_path "
                     "may be set")
  if not p.use_xla_compile:
    raise ParamError(
        "--use_xla_compile=false is unsupported: every step function is "
        "jitted -- XLA compilation IS the TPU execution model (the "
        "reference's per-tower xla.compile toggle, ref :413-416, has no "
        "non-XLA fallback here)")
  if not p.use_datasets:
    raise ParamError(
        "--use_datasets=false is unsupported: the framework has a single "
        "host input pipeline (the reference's legacy RecordInput path, "
        "ref :215-217/:601-617, has no TPU analog)")
  if p.gradient_repacking and p.all_reduce_spec:
    raise ParamError(
        "--gradient_repacking cannot be combined with --all_reduce_spec "
        "(repacking re-splits the full gradient vector; the spec planner "
        "owns packing on the spec path -- ref: batch_allreduce.py:300-317)")
  if p.gradient_repacking and p.agg_small_grads_max_bytes > 0:
    raise ParamError(
        "--gradient_repacking cannot be combined with "
        "--agg_small_grads_max_bytes (both re-shape reduction granularity)")
  if p.hierarchical_copy and p.all_reduce_spec:
    raise ParamError(
        "--hierarchical_copy cannot be combined with --all_reduce_spec "
        "(use the 'hier' algorithm inside the spec instead; "
        "ref :507-513 vs :532-553)")
  if getattr(p, "compact_gradient_transfer_f32", False):
    if not p.compact_gradient_transfer:
      raise ParamError(
          "--compact_gradient_transfer_f32 requires "
          "--compact_gradient_transfer: it widens WHEN the 16-bit wire "
          "format engages (f32 training too), it cannot engage a "
          "compaction that is switched off")
    if not (p.use_fp16 or p.all_reduce_spec or p.gradient_repacking
            or p.agg_small_grads_max_bytes > 0 or p.hierarchical_copy):
      raise ParamError(
          "--compact_gradient_transfer_f32 has no effect without a "
          "reduction path that repacks the wire: the default per-leaf "
          "pmean never re-encodes gradients (ops/allreduce.py "
          "build_reducer returns None). Select a packed path -- "
          "--all_reduce_spec, "
          "--gradient_repacking, --agg_small_grads_max_bytes or "
          "--hierarchical_copy -- or drop the flag (a silent no-op "
          "that logs a halved-bytes note would misrecord the run)")
  if getattr(p, "reduce_bucket_mb", None) and \
      not getattr(p, "shard_params", False):
    raise ParamError(
        "--reduce_bucket_mb bounds FSDP's gather buckets and requires "
        "--shard_params; the post-hoc reducers' granularity levers are "
        "--gradient_repacking / --agg_small_grads_max_bytes / "
        "--all_reduce_spec")
  if getattr(p, "health_stats", None):
    # Explicit --health_stats (unset = auto-resolve, telemetry.py): the
    # in-step stats read the APPLIED gradient tree and are only global
    # values when that tree is replica-identical -- i.e. when the
    # strategy reduces gradients replica-synchronously. Modes below
    # would silently report replica-LOCAL norms as global health.
    if p.eval or p.forward_only:
      raise ParamError(
          "--health_stats applies to training only (the stats are "
          "computed from the step's gradient tree); it cannot be "
          "combined with --eval or --forward_only")
    if p.variable_update == "independent":
      raise ParamError(
          "--health_stats requires replica-synchronous gradient "
          "reduction: --variable_update=independent never reduces, so "
          "each replica's 'global' grad norm would be its own local "
          "one. Drop the flag (auto-off) or use a replicated-family "
          "mode")
    if p.variable_update == "kungfu" and p.kungfu_option != "sync_sgd":
      raise ParamError(
          "--health_stats cannot be combined with --kungfu_option="
          f"{p.kungfu_option}: gossip/model-averaging modes keep "
          "per-replica gradient trees (parallel/strategies.py); only "
          "sync_sgd reduces replica-synchronously")
    if p.variable_update == "parameter_server" and not p.cross_replica_sync:
      raise ParamError(
          "--health_stats cannot be combined with async "
          "parameter_server (--cross_replica_sync=false): the "
          "sequential-apply path consumes each replica's UNAVERAGED "
          "gradient (train_step.py sequential_apply), so no replica-"
          "identical reduced tree exists for the stats to read")
  if p.hierarchical_copy and p.gradient_repacking:
    raise ParamError(
        "--hierarchical_copy cannot be combined with --gradient_repacking "
        "(ref: batch_allreduce.py:300-317 selects one algorithm)")
  if p.hierarchical_copy and p.agg_small_grads_max_bytes > 0:
    raise ParamError(
        "--hierarchical_copy cannot be combined with "
        "--agg_small_grads_max_bytes "
        "(ref: batch_allreduce.py:300-317 selects one algorithm)")
