"""Parameter corpus + Params object + cross-flag validation.

TPU-native re-design of the reference's flag corpus and Params plumbing
(ref: benchmark_cnn.py:114-636 for the corpus, :953-1034 for Params /
make_params / make_params_from_flags / validation). GPU-specific knobs
(winograd env vars, TensorRT, MKL, NCCL specs) map to their TPU analogs:
XLA flag plumbing, AOT compilation, ICI collectives. Names are kept close
to the reference so users of the reference CLI can switch with minimal
churn; `num_gpus` is accepted as an alias for `num_devices`.
"""

from __future__ import annotations

import collections
from typing import Any, Dict

from kf_benchmarks_tpu import flags

# ---------------------------------------------------------------------------
# Flag corpus (ref: benchmark_cnn.py:114-636)
# ---------------------------------------------------------------------------

flags.DEFINE_string("model", "trivial",
                    "Name of the model to run (ref :116-118).")
flags.DEFINE_integer("batch_size", 0, "Per-device batch size (0 = model "
                     "default; ref :130-133).", lower_bound=0)
flags.DEFINE_integer("batch_group_size", 1,
                     "Number of batches the input feeder keeps in flight "
                     "ahead of the step loop (ref :134-136; wired to the "
                     "DeviceFeeder prefetch depth).", lower_bound=1)
flags.DEFINE_integer("steps_per_dispatch", 1,
                     "Device-resident multi-step training: compile K "
                     "train steps into one lax.scan program so host "
                     "dispatch and metric fetches are paid "
                     "once per K steps (the TPU-native analog of the "
                     "reference's in-graph loops / amortized sess.run "
                     "fetches, ref: benchmark_cnn.py:786-884 step "
                     "semantics). 1 = one dispatch per step. Per-step "
                     "losses are unchanged; wall-clock timing is honest "
                     "at chunk granularity (utils/pipeline.py).",
                     lower_bound=1)
flags.DEFINE_integer("num_grad_accum", 1,
                     "Gradient accumulation: split each per-device batch "
                     "into M microbatches scanned inside the train step, "
                     "accumulating gradients in f32 before ONE gradient "
                     "collective and ONE optimizer apply (Megatron-style "
                     "microbatching, Shoeybi et al. 2019 -- no reference "
                     "analog; its per-GPU towers never exceeded memory). "
                     "Backward-pass activation residuals shrink to "
                     "batch/M; per-device batch size must be divisible by "
                     "M (validation.py). Batch-norm models note: BN "
                     "statistics are computed per MICROBATCH (batch/M "
                     "samples) and the running-stats EMA advances M times "
                     "per step -- standard microbatching semantics, NOT "
                     "numerically equivalent to M=1 for BN models (a "
                     "run-time note is logged). Composes with "
                     "--steps_per_dispatch (dispatch chunking outside, "
                     "microbatching inside). 1 = the monolithic step.",
                     lower_bound=1)
flags.DEFINE_boolean("packed_sequences", False,
                     "Variable-length sequence packing for "
                     "transformer_lm (the standard LM-pretraining "
                     "input form; no reference analog -- its inputs "
                     "are fixed-shape images): a deterministic "
                     "host-side first-fit bin-packer (data/packing.py) "
                     "draws variable-length documents from a seeded "
                     "length distribution and packs them into (B, T) "
                     "rows with segment ids + per-document positions; "
                     "segment-aware masks run through BOTH attention "
                     "implementations (block-level cross-segment tile "
                     "skip, parallel/sequence.py), the chunked fused "
                     "loss weighs real tokens only (ops/fused_loss.py) "
                     "and step metrics combine token-weighted "
                     "(train_step.py). Batches stream through the "
                     "DeviceFeeder (prefetch overlap measured via "
                     "feed_stall_fraction). transformer_lm training "
                     "only; composes with --steps_per_dispatch/"
                     "--num_grad_accum; exclusions in validation.py.")
flags.DEFINE_integer("input_prefetch_depth", None,
                     "Host->device prefetch depth of the DeviceFeeder "
                     "in batches (the StagingArea/MultiDeviceIterator "
                     "buffer depth analog, ref: benchmark_cnn.py:"
                     "2572-2600, preprocessing.py:368-399). None = "
                     "derived: max(--datasets_prefetch_buffer_size, "
                     "--batch_group_size), the historical default. "
                     "The measured consumer-side knob: "
                     "feed_stall_fraction in the benchmark stats / "
                     "bench JSON shows whether the depth hides host "
                     "preprocessing behind device compute.",
                     lower_bound=1)
flags.DEFINE_string("autotuned_config", None,
                    "Path to a tuned-config table "
                    "(analysis/autotune.py; train_dir/tuned_configs.json "
                    "from `python -m kf_benchmarks_tpu.analysis autotune` "
                    "or `experiments/zoo_sweep.py --autotune`). At "
                    "startup the entry matching this run's base "
                    "fingerprint (analysis/baseline.base_fingerprint_key "
                    "-- the config sans the tuned knobs) is applied over "
                    "the flag values of --steps_per_dispatch, "
                    "--num_grad_accum, --reduce_bucket_mb, "
                    "--input_prefetch_depth and --attn_block, with a "
                    "logged provenance line; no matching entry logs a "
                    "note and runs with the flag values. Replaces the "
                    "reference's per-model hand-tuned flag defaults "
                    "(SURVEY 2) with a measured, per-host table. "
                    "Training runs only (validation.py).")
flags.DEFINE_integer("attn_block", None,
                     "Attention K/V block size of the transformer_lm "
                     "family's tiled/flash attention (parallel/"
                     "sequence.py blockwise_attention; the q-block is "
                     "matched to it). None = the model default "
                     "(models/transformer_lm.ATTN_BLOCK). Must divide "
                     "the model's sequence length (validation.py); a "
                     "program-shaping knob the autotuner searches "
                     "(analysis/autotune.py TUNED_KNOBS).", lower_bound=8)
flags.DEFINE_integer("seq_len", None,
                     "Sequence length of a job's batches, in tokens: it "
                     "is traffic, so a flag of the job and not a constant "
                     "of the model. Read by mla_moe_lm (None = 4096); "
                     "transformer_lm keeps its module constant "
                     "(validation.py).", lower_bound=2)
flags.DEFINE_string("lm_config", None,
                    "mla_moe_lm: name of the published configuration, a "
                    "data file models/lm_configs/<name>.json holding the "
                    "model's public config.json verbatim; every size "
                    "comes from it. None = glm-4.7-flash.")
flags.DEFINE_integer("lm_layers_held", None,
                     "mla_moe_lm: how many of the configuration's layers "
                     "this job holds, from --lm_first_layer_held on (the "
                     "others lie on further chips, as pipeline stages). "
                     "None = all that follow it.",
                     lower_bound=1)
flags.DEFINE_integer("lm_first_layer_held", None,
                     "mla_moe_lm: the published index of the first layer "
                     "this job holds, for a pipeline stage that does not "
                     "start at the model's first layer (its layers' kinds "
                     "and whether each is dense come from the "
                     "configuration at their own indices; the embedding "
                     "still feeds the first layer held). None = 0.",
                     lower_bound=0)
flags.DEFINE_integer("lm_layer_shards", None,
                     "mla_moe_lm: over how many chips each layer is "
                     "divided (expert parallelism with a vocabulary-"
                     "parallel embedding and head). This job holds one "
                     "share: n_routed_experts / n contiguous experts and "
                     "vocab_size / n rows (--lm_vocab_shards gives the "
                     "vocabulary a number of its own); attention, the "
                     "state-space mixers, shared expert, "
                     "router and norms whole. It runs WITHOUT the "
                     "exchange: the absent experts' part is left out. "
                     "None = 1 (like every mla_moe_lm flag, None by "
                     "default so that it stays out of the other models' "
                     "config fingerprints, analysis/baseline.py).",
                     lower_bound=1)
flags.DEFINE_integer("lm_layer_shard_index", None,
                     "mla_moe_lm: which of the --lm_layer_shards shares "
                     "of a layer this job holds. None = 0.", lower_bound=0)
flags.DEFINE_integer("lm_vocab_shards", None,
                     "mla_moe_lm: over how many chips the VOCABULARY is "
                     "divided (embedding, head, token ids, labels and the "
                     "loss are over vocab_size / n rows), where that is "
                     "not the number that divides a layer: a deployment "
                     "whose experts lie over 16 chips may keep the "
                     "vocabulary over 8 of them. None = "
                     "--lm_layer_shards.", lower_bound=1)
flags.DEFINE_integer("num_batches", None,
                     "Number of timed batches to run (ref :137-139).")
flags.DEFINE_float("num_epochs", None,
                   "Number of epochs to run (mutually exclusive with "
                   "num_batches; ref :140-144).")
flags.DEFINE_integer("num_warmup_batches", None,
                     "Number of warmup batches before timing (ref :145-146).")
flags.DEFINE_integer("num_devices", 1,
                     "Number of accelerator devices to use per process "
                     "(ref num_gpus :122-123).", lower_bound=1)
flags.DEFINE_enum("device", "tpu", ("tpu", "cpu", "gpu"),
                  "Device to run compute on (ref :179-181; TPU added per "
                  "north star).")
flags.DEFINE_enum("data_format", "NHWC", ("NHWC", "NCHW"),
                  "Tensor layout. NHWC is the TPU-native layout (the "
                  "reference defaults to NCHW for cuDNN, ref :182-185).")
flags.DEFINE_boolean("eval", False, "Run evaluation instead of training "
                     "(ref :119).")
flags.DEFINE_integer("eval_interval_secs", 0,
                     "How often eval polls for new checkpoints (ref :147-151).")
flags.DEFINE_integer("num_eval_batches", None,
                     "Number of eval batches (ref :152-155).")
flags.DEFINE_float("num_eval_epochs", None,
                   "Number of eval epochs (ref :156-160).")
flags.DEFINE_integer("eval_during_training_every_n_steps", None,
                     "Mid-training eval cadence in steps (ref :161-166).")
flags.DEFINE_float("eval_during_training_every_n_epochs", None,
                   "Mid-training eval cadence in epochs (ref :140-143).")
flags.DEFINE_list("eval_during_training_at_specified_steps", [],
                  "Explicit training steps after which to run eval "
                  "(ref :144-147).")
flags.DEFINE_list("eval_during_training_at_specified_epochs", [],
                  "Explicit training epochs after which to run eval "
                  "(ref :148-152).")
flags.DEFINE_float("stop_at_top_1_accuracy", None,
                   "Stop training early once this top-1 is reached "
                   "(ref :167-172).")
flags.DEFINE_boolean("forward_only", False,
                     "Only run forward pass (ref :124-126).")
flags.DEFINE_boolean("print_training_accuracy", False,
                     "Compute and print top-1/top-5 during training "
                     "(ref :127-129).")
flags.DEFINE_integer("display_every", 10,
                     "Print step stats every N steps (ref :173-175).",
                     lower_bound=1)
flags.DEFINE_string("data_dir", None,
                    "Path to dataset; synthetic data if empty (ref :186-190).")
flags.DEFINE_string("data_name", None,
                    "Dataset name, sniffed from data_dir if empty "
                    "(ref :191-194).")
flags.DEFINE_boolean("distortions", False,
                     "Enable full image distortions (ref :199-202; reference "
                     "default True, flipped off here: synthetic-first).")
flags.DEFINE_boolean("use_fp16", False,
                     "Use reduced precision activations/gradients. On TPU "
                     "this means bfloat16 (ref use_fp16 :464-470).")
flags.DEFINE_float("fp16_loss_scale", None,
                   "Loss scale; None = model default. bfloat16 does not "
                   "need loss scaling so TPU default is 1 (ref :471-480).")
flags.DEFINE_boolean("fp16_vars", False,
                     "Keep variables in reduced precision too (ref :481-485).")
flags.DEFINE_boolean("fp16_enable_auto_loss_scale", False,
                     "Auto loss-scaling state machine (ref :486-490).")
flags.DEFINE_integer("fp16_inc_loss_scale_every_n", 1000,
                     "Double loss scale after N clean steps (ref :491-495).")
flags.DEFINE_string("mesh_shape", None,
                    "Named 2-D device mesh 'BxM' (e.g. 8x1, 4x2): B = "
                    "'batch' axis (data parallelism; global batch = B x "
                    "per-device batch), M = 'model' axis (state-sharding "
                    "/ tensor dimension; the composed LM trainer refines "
                    "it into seq x tensor, parallel/transformer.py). "
                    "B*M must equal --num_devices; M > 1 requires "
                    "--shard_optimizer_state (its only consumer in the "
                    "core step). Unset = the 1-D replica mesh "
                    "(--shard_optimizer_state alone resolves to Nx1). "
                    "The GSPMD named-mesh idiom (Xu et al. 2021).")
flags.DEFINE_boolean("shard_optimizer_state", False,
                     "ZeRO-shard optimizer state over the whole "
                     "('batch', 'model') mesh (Rajbhandari et al.): "
                     "gradients meet in a reduce-scatter of the batch "
                     "mean (bit-identical to the replicated pmean at "
                     "f32), the optimizer applies on each device's 1/n "
                     "flat state shard only, and updated params "
                     "all-gather for the next forward -- per-device "
                     "optimizer HBM drops to ~|state|/n and gradient "
                     "wire bytes to (B-1)/B + (n-1)/n of |grads| (the "
                     "TPU analog of the reference's central variable "
                     "placement, variable_mgr.py:201-243; ops/"
                     "sharded.py). Synchronous replicated/"
                     "parameter_server family only; composes with "
                     "--steps_per_dispatch and --num_grad_accum; "
                     "exclusions in validation.py.")
flags.DEFINE_boolean("shard_params", False,
                     "Full FSDP (ZeRO-3, Rajbhandari et al.): params "
                     "live as 1/n flat shards between steps (the same "
                     "(n, k) stacked layout as the sharded optimizer "
                     "state; per-layer rows for scanned stacks) and "
                     "the step re-assembles them per builder-layer "
                     "bucket / per scanned transformer block INSIDE "
                     "the forward/backward with one packed all-gather "
                     "each (ops/sharded.py gather_params; the bucket "
                     "bound is --reduce_bucket_mb, default 4 MiB), so "
                     "peak param residency is one bucket/block and "
                     "steady-state per-device param HBM is |params|/n "
                     "-- the full tree never materializes and the "
                     "sharded path's trailing all-gather is gone. "
                     "Gradients arrive reduce-scattered by the gather "
                     "hooks' backward (bit-identical per element to "
                     "the post-hoc scatter at f32). Requires "
                     "--shard_optimizer_state (elementwise-optimizer "
                     "family, same exclusions; validation.py); under "
                     "--num_grad_accum the in-compute gathers "
                     "disengage (one whole-tree gather and one scatter "
                     "of the accumulated tree per step).")
flags.DEFINE_enum("partitioner", None, ("manual", "gspmd"),
                  "Who places the collectives in the sharded training "
                  "step. 'manual' (the None default) = the hand-placed "
                  "shard_map programs (ops/sharded.py; every golden "
                  "contract pins them byte-identically). "
                  "'gspmd' = the SAME step body lowered under plain "
                  "jit with NamedSharding-annotated state/batch on the "
                  "same ('batch', 'model') mesh, letting the XLA SPMD "
                  "partitioner insert/re-place the collectives (Xu et "
                  "al. 2021); losses stay bit-identical at f32 and the "
                  "analysis/audit.py twin-referee rule classifies every "
                  "inventory divergence. Sharded families "
                  "(--shard_optimizer_state [+ --shard_params]) and "
                  "serving only -- the gossip/async-PS/independent/"
                  "staged/hierarchical modes are semantic hand "
                  "placements (validation.py). Program-shaping: a "
                  "tuned knob (analysis/baseline.TUNED_KNOBS), so "
                  "gspmd runs never mix with manual run-store history. "
                  "None default keeps non-sharded fingerprints "
                  "untouched (fingerprints drop None fields).")
flags.DEFINE_enum("variable_update", "replicated",
                  ("independent", "parameter_server", "replicated",
                   "distributed_replicated", "distributed_all_reduce",
                   "collective_all_reduce", "horovod", "kungfu"),
                  "Parallelism strategy (ref :523-531).")
flags.DEFINE_enum("kungfu_option", "sync_sgd",
                  ("sync_sgd", "async_sgd", "sma"),
                  "KungFu optimizer wrapper. The reference enum advertises "
                  "'ada_sgd' but dispatches on 'sma' (quirk, ref :530 vs "
                  ":1199); we expose the reachable set.")
flags.DEFINE_string("all_reduce_spec", None,
                    "All-reduce algorithm spec, BNF alg#shards:limit:... "
                    "(ref :532-553). TPU algs: psum, rsag (reduce-scatter + "
                    "all-gather), hierarchical; size-ranged hybrids kept.")
flags.DEFINE_integer("agg_small_grads_max_bytes", 0,
                     "Pack gradients smaller than this into one tensor "
                     "before the all-reduce (ref :554-557; 0 = off).")
flags.DEFINE_integer("agg_small_grads_max_group", 10,
                     "Max number of small gradients per pack (ref :558-560).")
flags.DEFINE_integer("gradient_repacking", 0,
                     "Re-split the concatenated gradient vector into this "
                     "many evenly-sized chunks for reduction (ref "
                     ":499-502; 0 = off; exclusive with --all_reduce_spec).",
                     lower_bound=0)
flags.DEFINE_boolean("compact_gradient_transfer", True,
                     "Compact gradients to a 16-bit wire format (bf16) for "
                     "the all-reduce when --use_fp16 is on (ref :503-506).")
flags.DEFINE_boolean("compact_gradient_transfer_f32", False,
                     "Engage the 16-bit (bf16) all-reduce wire format for "
                     "f32 training too -- the reference compacted only "
                     "fp16 gradients (ref: batch_allreduce.py:96-103); "
                     "this is the explicit f32 opt-in (halves reduction "
                     "bytes; a precision note is logged -- NOT "
                     "bit-identical to the f32 wire). Requires "
                     "--compact_gradient_transfer AND a reduction path "
                     "that repacks the wire (a packed reducer flag); the "
                     "default per-leaf pmean has nothing to compact "
                     "(validation.py).")
flags.DEFINE_integer("reduce_bucket_mb", None,
                     "Bound in MiB of one FSDP gather bucket under "
                     "--shard_params (default 4): parameter leaves group "
                     "at builder-layer granularity and merge into "
                     "buckets of at most this size, one packed "
                     "all-gather (and, going back, one reduce-scatter) "
                     "per bucket (ops/sharded.py fsdp_plan_buckets).",
                     lower_bound=1)
flags.DEFINE_boolean("hierarchical_copy", False,
                     "Two-level reduction: grouped psum within contiguous "
                     "device groups, then across them (ref :507-513).")
flags.DEFINE_enum("optimizer", "sgd", ("sgd", "momentum", "rmsprop", "adam",
                                       "lars"),
                  "Optimizer (ref :414-417; lars added: standard for "
                  "large-batch ResNet on TPU).")
flags.DEFINE_float("init_learning_rate", None,
                   "Initial LR; None = model default (ref :418-422).")
flags.DEFINE_string("piecewise_learning_rate_schedule", None,
                    "Schedule 'LR0;E1;LR1;...;En;LRn' (ref :423-429).")
flags.DEFINE_float("num_epochs_per_decay", 0,
                   "Epochs between LR decays (ref :430-434).")
flags.DEFINE_float("learning_rate_decay_factor", 0,
                   "Exponential decay factor (ref :435-440).")
flags.DEFINE_float("num_learning_rate_warmup_epochs", 0,
                   "Linear LR warmup epochs (ref :441-444).")
flags.DEFINE_float("minimum_learning_rate", 0,
                   "LR floor (requires decay flags; ref :445-449).")
flags.DEFINE_float("momentum", 0.9, "Momentum (ref :450).")
flags.DEFINE_float("rmsprop_decay", 0.9, "RMSProp decay (ref :451-452).")
flags.DEFINE_float("rmsprop_momentum", 0.9, "RMSProp momentum (ref :453-454).")
flags.DEFINE_float("rmsprop_epsilon", 1.0, "RMSProp epsilon (ref :455-456).")
flags.DEFINE_float("adam_beta1", 0.9, "Adam beta1 (ref :457-458).")
flags.DEFINE_float("adam_beta2", 0.999, "Adam beta2 (ref :459-460).")
flags.DEFINE_float("adam_epsilon", 1e-8, "Adam epsilon (ref :461-462).")
flags.DEFINE_float("weight_decay", 4e-5, "L2 weight decay (ref :496-498).")
flags.DEFINE_boolean("single_l2_loss_op", False,
                     "Compute L2 loss on concatenated weights instead of "
                     "per-tensor (ref :499-502 single_l2_loss_op).")
flags.DEFINE_float("gradient_clip", None, "Gradient clip magnitude "
                   "(ref :412-413).")
flags.DEFINE_boolean("use_xla_compile", True,
                     "jit the whole step function. Must stay true: XLA "
                     "compilation IS the TPU execution model; false is "
                     "rejected in validation (ref xla_compile :413-416).")
flags.DEFINE_boolean("sync_on_finish", False,
                     "Barrier across workers at exit (ref :567-569; KungFu "
                     "run_barrier analog, ref tf_cnn_benchmarks.py:58-60).")
flags.DEFINE_boolean("track_grad_noise_scale", False,
                     "Measure the gradient noise scale in the train step "
                     "(per-replica vs replica-mean gradients) and report "
                     "the EMA-smoothed B_simple -- the statistic KungFu's "
                     "adaptation policies monitor (SURVEY 2.9 north star).")
flags.DEFINE_boolean("elastic", False,
                     "Enable elastic resize: watch the coordination "
                     "service (KFCOORD_* env) for target-size changes and "
                     "re-jit over the new device mesh, carrying state via "
                     "checkpointed rescale (KungFu resize_cluster analog).")
flags.DEFINE_integer("elastic_check_every_n_steps", 10,
                     "How often the train loop polls for elastic resize / "
                     "adaptive-batch decisions.", lower_bound=1)
flags.DEFINE_string("fault_schedule", None,
                    "Deterministic fault injection (faults.py): "
                    "comma-separated <kind>@<step>[:rank=R][:secs=S] "
                    "entries with kind in kill | sigterm | "
                    "heartbeat_delay | drop_msg | corrupt_ckpt; each "
                    "fires ONCE at the dispatch boundary after the "
                    "named step (one-shot across checkpoint-restart "
                    "generations via train_dir markers). The "
                    "reproducible-preemption harness behind the "
                    "kill/rejoin tests; no reference analog.")
flags.DEFINE_boolean("adaptive_batch_size", False,
                     "Adapt the per-device batch size to the measured "
                     "gradient noise scale (implies "
                     "track_grad_noise_scale; KungFu adaptive batch "
                     "policy analog).")
flags.DEFINE_integer("adaptive_batch_min", 1,
                     "Lower bound for the adaptive per-device batch size.",
                     lower_bound=1)
flags.DEFINE_integer("adaptive_batch_max", 1024,
                     "Upper bound for the adaptive per-device batch size.",
                     lower_bound=1)
flags.DEFINE_boolean("cross_replica_sync", True,
                     "Synchronous data-parallel updates (ref :520-522).")
flags.DEFINE_enum("variable_consistency", "strong", ("strong", "relaxed"),
                  "relaxed applies one-step-stale gradients (double-"
                  "buffered in the step carry; ref :242, "
                  "batch_allreduce.py:353-388 deferred StagingArea "
                  "gradients).")
flags.DEFINE_boolean("staged_vars", False,
                     "Forward/backward read one-step-stale weights while "
                     "updates land on the live ones (ref :406, "
                     "variable_mgr.py:246-274 StagedVariableGetter).")
flags.DEFINE_string("train_dir", None,
                    "Checkpoint/summary directory (ref :585-588).")
flags.DEFINE_string("compilation_cache_dir", None,
                    "Persistent XLA compilation-cache directory "
                    "(benchmark.configure_compile_cache, applied "
                    "before the first trace): a program shape "
                    "compiles once, later runs deserialize it. "
                    "Ignored when JAX_COMPILATION_CACHE_DIR is set "
                    "(the env places the cache; the program sets no "
                    "other). Unset = <checkout>/.jax_cache for "
                    "--device=tpu runs, off for CPU runs; never a "
                    "path under --train_dir. The compile ledger's "
                    "cache_hit column (tracing.py) says which "
                    "episodes the cache answered, from the cache's "
                    "own hit events (jax.monitoring).")
flags.DEFINE_boolean("health_stats", None,
                     "In-step training-health stats (telemetry.py): the "
                     "train step additionally returns a compact f32 "
                     "vector (global grad norm, update/param norm ratio, "
                     "non-finite leaf count, loss scale + skip flag) "
                     "computed inside the compiled program and packed "
                     "into the existing loss pmean, so it adds NO extra "
                     "collective (pinned in tests/test_telemetry.py); "
                     "feeds the flight recorder and stall watchdog. "
                     "Unset = auto: on for training runs that reduce "
                     "gradients replica-synchronously (replicated family "
                     "/ kungfu sync_sgd) AND have a telemetry sink "
                     "(--train_dir or --benchmark_log_dir); off with a "
                     "note for per-replica/gossip/async modes, off "
                     "quietly for sink-less runs (the readout rides the "
                     "step's tail, so it is not free). No reference "
                     "analog -- its observability is post-hoc only "
                     "(SURVEY 5.1/9; ref: benchmark_cnn.py:585-620 "
                     "summaries/benchmark logs).")
flags.DEFINE_float("health_grad_norm_sigma", 6.0,
                   "Flight-recorder anomaly threshold: a step whose "
                   "global grad norm exceeds the trailing window's mean "
                   "by this many standard deviations dumps the window "
                   "(telemetry.py).", lower_bound=0.1)
flags.DEFINE_integer("flight_recorder_window", 64,
                     "Per-step records the flight recorder retains (and "
                     "continuously rewrites to train_dir/"
                     "flight_recorder.jsonl); the post-mortem window "
                     "dumped on anomaly/signal/exit (telemetry.py).",
                     lower_bound=4)
flags.DEFINE_float("stall_watchdog_factor", 10.0,
                   "Mid-run stall threshold: silence beyond this factor "
                   "times the trailing mean chunk wall emits a watchdog "
                   "diagnostic (never a kill: it diagnoses, the "
                   "operator decides). 0 disables the "
                   "watchdog thread; the first compile is always exempt "
                   "(patient, log-only) (telemetry.py).", lower_bound=0)
flags.DEFINE_integer("metrics_port", None,
                     "Serve a live scrape endpoint from the metric "
                     "registry (metrics.py) on this port: /metrics in "
                     "Prometheus text format, /healthz from watchdog + "
                     "flight-recorder state. Under kfrun each rank "
                     "binds port + rank, so every worker of a "
                     "single-host job gets its own scrape target. "
                     "Host-side only: the metrics-on step program is "
                     "structurally identical to the metrics-off golden "
                     "(analysis/audit.rule_metrics_twin). Unset = no "
                     "socket is ever bound. Training runs only "
                     "(validation.py). No reference analog -- its "
                     "results ship post-hoc (BenchmarkLogger / BigQuery "
                     "upload, ref: benchmark_cnn.py:1594-1608).",
                     lower_bound=1, upper_bound=65535)
flags.DEFINE_string("run_store_dir", None,
                    "Append one schema-versioned run record (config "
                    "fingerprint, git rev, jax version, platform, full "
                    "metric snapshot) to the append-only JSONL run "
                    "store in this directory at run end (metrics.py "
                    "RunStore; rank 0 only) -- the cross-run history "
                    "the regression sentinel (bench.py "
                    "--check-regression) compares against. Unset = no "
                    "record for training runs; bench.py defaults its "
                    "own store next to the BENCH_*.json trajectory. "
                    "Training runs only (validation.py).")
flags.DEFINE_integer("summary_verbosity", 0,
                     "0-3: none / scalars / grad histograms / everything "
                     "(ref :589-593).", lower_bound=0, upper_bound=3)
flags.DEFINE_integer("save_summaries_steps", 0,
                     "Summary cadence, 0 = off (ref :594-597).")
flags.DEFINE_integer("save_model_secs", 0,
                     "Checkpoint cadence in seconds (ref :598-601).")
flags.DEFINE_integer("save_model_steps", 0,
                     "Checkpoint cadence in steps (ref :602-605).")
flags.DEFINE_integer("max_ckpts_to_keep", 5,
                     "Max checkpoints kept (ref :606-608).")
flags.DEFINE_string("trace_file", None,
                    "Profiler trace output path (ref :270-275; jax.profiler "
                    "trace dir on TPU).")
flags.DEFINE_string("trace_events_file", None,
                    "Whole-run host-side span timeline (tracing.py; the "
                    "run-wide successor of the reference's one-step "
                    "timeline, ref :806-817): DeviceFeeder fetches/waits, "
                    "dispatch issue + per-chunk device completion, "
                    "compile episodes, checkpoint save/restore, eval, "
                    "elastic reseams and fault injections, exported as "
                    "Chrome trace-event JSON (loads in Perfetto / "
                    "chrome://tracing; pid=rank, tid=subsystem; "
                    "--use_chrome_trace_format=false writes the raw span "
                    "JSONL instead). Host-only: the step program and "
                    "per-step losses are bit-identical trace-on vs off "
                    "(auditor twin rule). Per-rank files under kfrun, "
                    "rank 0 merges at exit. Independent of the "
                    "jax.profiler --trace_file device capture. Training "
                    "runs only (validation.py).")
flags.DEFINE_string("tfprof_file", None,
                    "Per-op profile output (ref tfprof_file :276-289; "
                    "compiled-HLO cost analysis dump on TPU).")
flags.DEFINE_string("graph_file", None,
                    "Dump the optimized program text (StableHLO) to this "
                    "path (ref :2142-2148 GraphDef dump).")
flags.DEFINE_string("benchmark_log_dir", None,
                    "Structured JSON benchmark-log directory "
                    "(ref :1594-1608).")
flags.DEFINE_integer("tf_random_seed", 1234,
                     "Graph-level random seed (ref :609-612).")
flags.DEFINE_string("backbone_model_path", None,
                    "Warm-start backbone checkpoint (SSD; ref :613-614).")
flags.DEFINE_string("aot_save_path", None,
                    "Forward-only mode: serialize the frozen forward "
                    "program (AOT compile + weights-as-constants) to this "
                    "path -- the serving-graph/TensorRT analog "
                    "(ref trt_mode :615-620, _preprocess_graph "
                    ":2405-2525).")
flags.DEFINE_string("aot_load_path", None,
                    "Forward-only mode: load a frozen forward program "
                    "exported via --aot_save_path and benchmark ITS "
                    "images/sec -- the serving benchmark on the frozen "
                    "artifact (ref: the TRT-converted-graph timing path, "
                    "_preprocess_graph + forward-only loop).")
flags.DEFINE_boolean("use_synthetic_gpu_images", False,
                     "(parity alias; synthetic data is data_dir=None)")
# Serving engine (kf_benchmarks_tpu/serving/; bench.py --serving and
# experiments/serving_sweep.py --engine consume these). All default
# None = the engine's own defaults, so a non-serving run's config
# fingerprint is untouched (fingerprints drop None fields).
flags.DEFINE_string("serving_bucket_ladder", None,
                    "Comma-separated ascending batch buckets the "
                    "serving engine may compile decode/prefill "
                    "executables at (serving/engine.py; e.g. "
                    "'1,4,16,64'). The ladder BOUNDS the executable "
                    "set -- the auditor's serving_decode golden and "
                    "the compile-ledger e2e pin it. None = the engine "
                    "default ladder.")
flags.DEFINE_string("serving_batching", None,
                    "Serving batch policy: 'continuous' (in-flight "
                    "batching -- freed decode slots refill from the "
                    "queue every step) or 'static' (batch-and-drain: "
                    "admit a wave, decode to completion, then admit "
                    "again -- the A/B baseline arm). None = "
                    "continuous (validation.py).")
flags.DEFINE_integer("serving_max_new_tokens", None,
                     "Default per-request generation cap of the "
                     "serving engine. None = the engine default.",
                     lower_bound=1)
flags.DEFINE_integer("serving_queue_depth", None,
                     "Admission queue bound: a submit beyond this "
                     "depth is REJECTED (first-class shed result + "
                     "serving/shed metric, never an exception). None "
                     "= the engine default.", lower_bound=1)
flags.DEFINE_float("serving_ttft_slo_ms", None,
                   "TTFT service-level objective in ms: a queued "
                   "request older than this at coalesce time is "
                   "EXPIRED (deadline shedding) instead of wasting a "
                   "prefill it can no longer meet. None = no "
                   "deadline.", lower_bound=0.0)
flags.DEFINE_float("serving_tenant_tokens_per_s", None,
                   "Per-tenant token-budget rate (prompt + generated "
                   "tokens charged at submit against a token bucket): "
                   "an over-budget request is REJECTED with the "
                   "tenant_budget shed reason. None = unmetered.",
                   lower_bound=0.0)
# Decode-cost variants (ISSUE 16). All default None/off so a
# variant-off run's config fingerprint is byte-identical to before.
flags.DEFINE_enum("serving_quantize", None, ("int8",),
                  "Weight-only quantization of the served model: "
                  "'int8' stores per-out-channel {int8, f32 scale} "
                  "leaves (quantization.py) dequantized INSIDE the "
                  "compiled step -- the TPU-native analog of the "
                  "reference's --trt_mode=INT8 (ref :615-620). None "
                  "= bf16/f32 weights.")
flags.DEFINE_integer("serving_kv_page_size", None,
                     "Paged KV cache: replace the dense per-slot "
                     "(T_max) ring slab with a shared fixed-size "
                     "block pool + per-request page tables at this "
                     "page size (tokens/page; must divide the "
                     "context length -- validation.py). None = the "
                     "dense ring slab.", lower_bound=1)
flags.DEFINE_integer("serving_speculative_k", None,
                     "Speculative decoding: a shallow draft proposes "
                     "k tokens per target dispatch; the target "
                     "verifies all k in ONE prefill-shaped call "
                     "(greedy output stays token-identical to plain "
                     "greedy). Requires --serving_draft_layers "
                     "(validation.py).", lower_bound=2)
flags.DEFINE_integer("serving_draft_layers", None,
                     "Depth of the speculative draft model (same "
                     "transformer_lm family; must be < the served "
                     "model's layer count). Only meaningful with "
                     "--serving_speculative_k (validation.py).",
                     lower_bound=1)
flags.DEFINE_integer("serving_model_shards", None,
                     "Tensor-parallel serving: shard the served LM's "
                     "weights and KV cache over an M-way 'model' mesh "
                     "axis (serving/decode.py model_shardings) and let "
                     "GSPMD place the decode/prefill/verify "
                     "collectives -- the serving leg of "
                     "--partitioner=gspmd. Must divide the model's "
                     "head count and the device count "
                     "(validation.py). None = single-replica "
                     "executables (fingerprints drop None fields, so "
                     "existing serving history is untouched).",
                     lower_bound=2)
# Distributed / cluster flags (ref :570-583).
flags.DEFINE_enum("job_name", "", ("ps", "worker", "controller", ""),
                  "Job role for multi-process runs (ref :571-573).")
flags.DEFINE_list("ps_hosts", [], "Parameter-server hosts (ref :574).")
flags.DEFINE_list("worker_hosts", [], "Worker hosts (ref :575).")
flags.DEFINE_string("controller_host", None, "Controller host (ref :576).")
flags.DEFINE_integer("task_index", 0, "Task index (ref :577).")
flags.DEFINE_string("coordinator_address", None,
                    "host:port of the DCN coordination service "
                    "(kungfu-run analog, SURVEY 2.9).")
flags.DEFINE_integer("num_processes", 1,
                     "Number of cooperating host processes (kungfu-run -np).")
flags.DEFINE_integer("process_index", 0, "This process's rank.")
# Input pipeline knobs (ref :203-269).
flags.DEFINE_integer("num_intra_threads", None,
                     "Host compute threads (ref :203-208).")
flags.DEFINE_integer("datasets_prefetch_buffer_size", 2,
                     "Device prefetch depth (ref datasets_* :243-269).")
flags.DEFINE_integer("datasets_num_private_threads", None,
                     "Private threadpool for input pipeline (ref :248-253).")
flags.DEFINE_boolean("datasets_use_caching", False,
                     "Cache the input dataset in memory (ref :254-258).")
flags.DEFINE_integer("input_preprocessing_parallelism", 16,
                     "Parallel parse/augment calls (ref map parallelism).")
flags.DEFINE_boolean("use_datasets", True,
                     "Must stay true: the framework has one host input "
                     "pipeline; the reference's legacy RecordInput path "
                     "has no TPU analog and false is rejected "
                     "(ref :215-217).")
flags.DEFINE_enum("resize_method", "bilinear",
                  ("round_robin", "nearest", "bilinear", "bicubic", "area"),
                  "Eval/train resize method (ref :195-198).")
flags.DEFINE_string("input_preprocessor", "default",
                    "Name of the input preprocessor to use "
                    "(ref: benchmark_cnn.py:179-182).")
flags.DEFINE_enum("loss_type_to_report", "total_loss",
                  ("base_loss", "total_loss"),
                  "Which loss the step line prints (ref :346-353).")

# -- Reference-CLI parity corpus ---------------------------------------------
# The remaining reference flags that mean something here: wired ones
# say so, the rest are rejected in validation with the TPU-native
# alternative named. Reference flags with no TPU meaning (cuDNN, MKL,
# grappler, tf.data, GPU thread pools) are not defined: MIGRATION.md
# lists them.
flags.DEFINE_boolean("datasets_repeat_cached_sample", False,
                     "Repeat the first input sample forever to emulate "
                     "memory-speed IO (wired into the record stream; "
                     "ref :259-263).")
flags.DEFINE_string("benchmark_test_id", None,
                    "Test id attached to the benchmark-log run info "
                    "(wired; ref :344-348).")
flags.DEFINE_string("eval_dir", "/tmp/tf_cnn_benchmarks/eval",
                    "Directory for eval benchmark logs (wired; "
                    "ref :585-586).")
flags.DEFINE_string("partitioned_graph_file_prefix", None,
                    "Dump the compiled (partitioned) program text to "
                    "<prefix>.txt (wired; ref :293-296 per-device "
                    "GraphDef dumps).")
flags.DEFINE_string("debugger", None,
                    "tfdbg has no TPU analog; any value is rejected "
                    "(ref :370-377).")
flags.DEFINE_string("trt_mode", "",
                    "Precision of the frozen serving export (the "
                    "TensorRT-conversion analog, ref :615-620): FP32, "
                    "FP16 (bf16 compute on TPU), or INT8 (weight-only "
                    "post-training quantization, quantization.py). "
                    "Requires --forward_only with --aot_save_path; "
                    "empty keeps the training compute dtype.")
flags.DEFINE_boolean("use_chrome_trace_format", True,
                     "Export --trace_events_file as Chrome trace-event "
                     "JSON (the reference's timeline.Timeline toggle, "
                     "ref :271-275, wired to the run-trace exporter in "
                     "tracing.py); false writes the raw span records as "
                     "JSONL instead. The jax.profiler --trace_file "
                     "capture is unaffected (it writes its own format).")

# Accepted in both paths: make_params(**kw) translates them, and
# define_flags(aliases=ALIASES) materializes them as absl alias flags so
# reference command lines (--num_gpus=8) keep working.
ALIASES = {"num_gpus": "num_devices"}
_ALIASES = ALIASES

Params = None  # rebuilt by _rebuild_params_type()


def _rebuild_params_type():
  global Params
  Params = collections.namedtuple("Params", list(flags.param_specs.keys()))


def _params_type():
  """Rebuild Params when late DEFINEs grew the registry (the platform-hook
  / aux-CLI extension point: modules like all_reduce_benchmark register
  extra params at import, the analog of define_platform_params,
  ref: platforms/default/util.py:28-33)."""
  if Params is None or Params._fields != tuple(flags.param_specs.keys()):
    _rebuild_params_type()
  return Params


_rebuild_params_type()


def validate_params(params) -> None:
  """Per-field bounds/enum validation (ref: benchmark_cnn.py:962-990)."""
  for name, spec in flags.param_specs.items():
    flags.check_value(spec, getattr(params, name))


def make_params(**kwargs) -> "Params":
  """Construct Params from defaults + overrides (ref: benchmark_cnn.py:993)."""
  translated = {}
  for k, v in kwargs.items():
    k = _ALIASES.get(k, k)
    if k not in flags.param_specs:
      raise ValueError(f"Unknown param: {k}")
    translated[k] = flags.canonicalize_value(flags.param_specs[k], v)
  defaults = {name: spec.default_value
              for name, spec in flags.param_specs.items()}
  defaults.update(translated)
  params = _params_type()(**defaults)
  validate_params(params)
  return params


def make_params_from_flags() -> "Params":
  """Construct Params from parsed absl FLAGS (ref: benchmark_cnn.py:1013)."""
  values = flags.flag_values_as_dict()
  params = _params_type()(
      **{k: flags.canonicalize_value(flags.param_specs[k], v)
         if v is not None else None
         for k, v in values.items()})
  validate_params(params)
  return params


def remove_param_fields(params, field_names) -> "Params":
  """Null out fields (eval-mode stripping; ref: benchmark_cnn.py:1026)."""
  return params._replace(**{f: None for f in field_names
                            if f in params._fields})
