"""Serving-path measurement: forward/AOT batch sweep on the chip, and
the request-engine continuous-vs-static A/B.

Mode 1 (default; real chip, VERDICT r3 item #3) runs the CLI in
child processes, one after another (the parent stays off JAX: a chip
belongs to one process at a time), across a batch-size sweep:

  forward  -- the jitted eval program (--forward_only)
  aot      -- export once with --aot_save_path, then benchmark the
              frozen program in a FRESH process via --aot_load_path
              (the TRT-analog serving benchmark)

    python experiments/serving_sweep.py [--batches 50] [--bs 32 64 128 256]

Mode 2 (``--engine``; round 18) drives the REAL serving engine
(kf_benchmarks_tpu/serving/) in-process over a seeded Poisson request
replay, across offered arrival rates, with TWO arms per rate on the
SAME workload: continuous in-flight batching vs static batch-and-drain.
Executables are warmed across the whole bucket ladder first, so TTFT
measures the system, not XLA. Prints a markdown table + ONE JSON line;
the verdict bar is the run's OWN static-arm p99 TTFT (never a
constant). CPU-mesh by default (chip rows: not measured); results
land in PERF.md round 18.

    python experiments/serving_sweep.py --engine [--rates 40 80 160]
        [--requests 64] [--ladder 1,4,16] [--seed 0]

Mode 3 (``--variants``; round 19, ISSUE 16) A/Bs the decode-cost
variants against the engine's OWN dense/f32 arm on the SAME seeded
workload: INT8 weight-only decode (greedy agreement + weight bytes),
paged KV cache (token identity + pool-vs-slab bytes + the
max-sessions-under-budget win), speculative decoding (token identity +
accept-length distribution), and all three composed. Prints a markdown
table + ONE JSON line; the verdict is exact token identity for
paged/speculative/composed-vs-int8 and >= 99% greedy agreement for
INT8.

    python experiments/serving_sweep.py --variants [--requests 48]
        [--rate 80] [--ladder 1,4,16] [--seed 0]

Mode 4 (``--tp``; round 20, ISSUE 17) A/Bs tensor-parallel decode
(--serving_model_shards: Megatron-sharded projections + head-sharded
KV cache over the 'model' mesh, serving/decode.py tp_shardings)
against the single-replica arm on the SAME seeded workload: exact
greedy token identity is the correctness verdict (argmax absorbs the
documented ~2e-6 psum reassociation), and the table reports tok/s,
p99 TTFT, and per-device weight/KV-cache bytes (the memory win TP
exists for: the sharded matrices hold 1/M per device).

    python experiments/serving_sweep.py --tp [--shards 2 4]
        [--requests 48] [--rate 80] [--ladder 1,4,16] [--seed 0]
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOTAL_RE = re.compile(r"^total images/sec: ([\d.]+)$", re.M)

def run_child(args):
  """Run the CLI in a child process -> (returncode, stdout, stderr).

  The children run one after another and this parent stays off JAX (it
  imports nothing that initialises a backend): a chip belongs to one
  process at a time, and a parent that had touched it would leave the
  child nothing to claim. ``--device=tpu`` without a chip fails the
  child loudly (benchmark.setup), never a CPU number."""
  proc = subprocess.run(
      [sys.executable, "-m", "kf_benchmarks_tpu.cli"] + args,
      capture_output=True, text=True, cwd=REPO)
  return proc.returncode, proc.stdout, proc.stderr


def run_cli_output(args):
  """One CLI point -> (total images/sec, the child's stdout)."""
  rc, out, err = run_child(args)
  if rc != 0:
    raise RuntimeError(f"{args}: {out[-2000:]} {err[-2000:]}")
  m = TOTAL_RE.search(out)
  if not m:
    raise RuntimeError(f"no total line: {out[-2000:]}")
  return float(m.group(1)), out


def run_cli(args):
  """One CLI point -> total images/sec."""
  return run_cli_output(args)[0]


def engine_ab(args):
  """The continuous-vs-static A/B on the serving engine (in-process)."""
  if REPO not in sys.path:
    sys.path.insert(0, REPO)
  if args.engine_device == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")
  import json

  from kf_benchmarks_tpu import tracing
  from kf_benchmarks_tpu.serving import (EngineConfig, LMSpec,
                                         ServingEngine, poisson_workload)
  from kf_benchmarks_tpu.validation import parse_bucket_ladder

  spec = LMSpec(vocab=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                max_len=128, attn_block=32)
  ladder = parse_bucket_ladder(args.ladder)

  rows = []
  for rate in args.rates:
    arms = {}
    for batching in ("continuous", "static"):
      cfg = EngineConfig(spec=spec, bucket_ladder=ladder,
                         batching=batching,
                         max_new_tokens=args.max_new,
                         max_queue_depth=args.requests + 1)
      # Throwaway warm replay, same arm, same RATE, different seed:
      # engine.warm() covers the AOT decode/prefill executables, but
      # the install/grow/compact scatter ops compile lazily per (pack
      # bucket, decode bucket) shape combo in XLA's process-global op
      # cache, and WHICH combos occur depends on the arrival-rate
      # dynamics (bucket flapping) -- without this, a first-use combo
      # compile mid-measurement masquerades as a batching-policy p99
      # (the same measure-the-system hygiene as the warm pass before a
      # chip window).
      warm_eng = ServingEngine(cfg, seed=args.seed)
      warm_eng.warm()
      warm_eng.replay(poisson_workload(args.requests, rate, spec,
                                       seed=args.seed + 1,
                                       max_new_tokens=args.max_new))
      trace = tracing.RunTrace(path=None)
      tracing.activate(trace)
      try:
        eng = ServingEngine(cfg, seed=args.seed)
        eng.warm()
        # The SAME seeded workload for both arms: the A/B isolates the
        # batching policy, nothing else.
        workload = poisson_workload(args.requests, rate, spec,
                                    seed=args.seed,
                                    max_new_tokens=args.max_new)
        eng.replay(workload)
        stats = eng.stats()
        stats["compiles"] = trace.compile_ledger()["shapes"]
        arms[batching] = stats
      finally:
        tracing.deactivate()
    cont, stat = arms["continuous"], arms["static"]
    rows.append({"rate": rate, "continuous": cont, "static": stat})
    print(f"rate={rate}/s: continuous p99 TTFT "
          f"{1e3 * cont['serving/ttft_p99']:.1f} ms "
          f"({cont['serving/tokens_per_sec']:.0f} tok/s), static "
          f"{1e3 * stat['serving/ttft_p99']:.1f} ms "
          f"({stat['serving/tokens_per_sec']:.0f} tok/s)", flush=True)

  print("\n| rate req/s | arm | ttft p50 ms | ttft p99 ms | tok/s | "
        "fill | shed |")
  print("|---|---|---|---|---|---|---|")
  for row in rows:
    for arm in ("continuous", "static"):
      s = row[arm]
      print(f"| {row['rate']} | {arm} | "
            f"{1e3 * s['serving/ttft_p50']:.1f} | "
            f"{1e3 * s['serving/ttft_p99']:.1f} | "
            f"{s['serving/tokens_per_sec']:.0f} | "
            f"{s['serving/batch_fill_fraction']:.2f} | "
            f"{s['serving/shed_fraction']:.2f} |")

  # Verdict: the bar is the run's OWN static-arm measurement per rate.
  verdicts = []
  for row in rows:
    bar = row["static"]["serving/ttft_p99"]
    got = row["continuous"]["serving/ttft_p99"]
    verdicts.append(got < bar)
    print(f"verdict rate={row['rate']}/s: continuous p99 TTFT "
          f"{1e3 * got:.1f} ms vs static bar {1e3 * bar:.1f} ms -> "
          + ("PASS" if got < bar else "FAIL"), flush=True)
  ratios = [row["continuous"]["serving/ttft_p99"] /
            row["static"]["serving/ttft_p99"] for row in rows]
  record = {
      "metric": "serving_continuous_over_static_p99_ttft",
      "value": round(min(ratios), 4),
      "unit": "ratio",
      "requests": args.requests,
      "max_new_tokens": args.max_new,
      "ladder": list(ladder),
      "seed": args.seed,
      "rows": rows,
  }
  print(json.dumps(record), flush=True)
  return 0 if all(verdicts) else 1


def variants_ab(args):
  """Decode-cost variants vs the dense/f32 arm (ISSUE 16), in-process
  on the CPU mesh (chip rows: not measured)."""
  if REPO not in sys.path:
    sys.path.insert(0, REPO)
  if args.engine_device == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")
  import dataclasses
  import json

  import jax
  import numpy as np

  from kf_benchmarks_tpu.serving import decode as decode_lib
  from kf_benchmarks_tpu.serving import (EngineConfig, ServingEngine,
                                         poisson_workload)
  from kf_benchmarks_tpu.validation import parse_bucket_ladder

  base = dict(vocab=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              max_len=128, attn_block=32)
  page, spec_k, draft_l = 32, 4, 1
  arms = [
      ("dense", {}),
      ("int8", dict(quantize="int8")),
      ("paged", dict(kv_page_size=page)),
      ("speculative", dict(speculative_k=spec_k,
                           draft_n_layers=draft_l)),
      ("composed", dict(quantize="int8", kv_page_size=page,
                        speculative_k=spec_k, draft_n_layers=draft_l)),
  ]
  ladder = parse_bucket_ladder(args.ladder)
  # ONE workload for every arm, generated from the TIGHTEST admission
  # cap (the speculative spec: prompt+max_new+k must fit max_len), so
  # all arms serve byte-identical requests and token identity is
  # well-posed.
  cap_spec = decode_lib.LMSpec(**base, speculative_k=spec_k,
                               draft_n_layers=draft_l)
  workload = poisson_workload(args.requests, args.rate, cap_spec,
                              seed=args.seed,
                              max_new_tokens=args.max_new)
  variables = decode_lib.init_variables(decode_lib.LMSpec(**base),
                                        seed=args.seed)

  results = {}
  for name, kw in arms:
    spec = decode_lib.LMSpec(**base, **kw)
    cfg = EngineConfig(spec=spec, bucket_ladder=ladder,
                       max_new_tokens=args.max_new,
                       max_queue_depth=args.requests + 1)
    # Warm replay first (same hygiene as engine_ab: the scatter-op
    # combos compile lazily per shape pair).
    warm = ServingEngine(cfg, variables=variables, seed=args.seed)
    warm.warm()
    warm.replay([(t, dataclasses.replace(r)) for t, r in workload])
    eng = ServingEngine(cfg, variables=variables, seed=args.seed)
    eng.warm()
    t0 = time.time()
    res = eng.replay([(t, dataclasses.replace(r)) for t, r in workload])
    wall = time.time() - t0
    stats = eng.stats()
    weight_bytes = sum(
        x.nbytes for x in jax.tree.leaves(eng._step_vars))
    results[name] = {
        "tokens": {r.rid: list(r.tokens) for r in res
                   if r.status == "ok"},
        "stats": stats, "wall_s": wall, "weight_bytes": weight_bytes,
        "kv_cache_bytes": (int(np.prod(eng._cache.k.shape)) * 2 *
                           eng._cache.k.dtype.itemsize
                           if eng._cache is not None else 0),
    }

  dense = results["dense"]["tokens"]
  verdicts = {}
  agreements = {}
  for name in ("int8", "paged", "speculative", "composed"):
    got = results[name]["tokens"]
    ref = results["int8" if name == "composed" else "dense"]["tokens"]
    total = agree = 0
    for rid in ref:
      for a, b in zip(ref[rid], got.get(rid, [])):
        total += 1
        agree += int(a == b)
    frac = agree / max(total, 1)
    agreements[name] = frac
    exact = set(got) == set(ref) and all(
        got[rid] == ref[rid] for rid in ref)
    if name != "int8":
      verdicts[name] = exact

  # INT8 accuracy gate (decode.quantize_agreement -- the bench path's
  # serve/fall-back decision): PREFIX-CONDITIONED next-token agreement
  # (teacher-forced on the f32 arm's rows), not the sequence-zip number
  # above -- zip agreement compounds after the first flip, so it
  # understates per-decision accuracy. The arm's verdict is the gate
  # itself: the measurement is internally consistent and the decision
  # honors the bar. At RANDOM-INIT weights (this experiment) logit
  # margins are razor thin -- the adversarial case the gate exists to
  # catch; trained checkpoints have decisive margins.
  probe = [r.prompt for _, r in workload[:8]]
  ispec = decode_lib.LMSpec(**base, quantize="int8")
  gate = decode_lib.quantize_agreement(
      ispec, variables, probe, max_new_tokens=min(8, args.max_new))
  verdicts["int8"] = (
      gate["passed"] == (gate["agreement"]
                         >= decode_lib.QUANTIZE_AGREEMENT_BAR)
      and gate["max_logit_delta"] <= 0.15 * gate["logit_scale"])

  # Paged concurrency win: sessions a fixed HBM budget (one dense slab
  # at the top ladder bucket) admits. Dense needs pages_per_slot pages
  # per session; the pool is sized by expected occupancy.
  pspec = decode_lib.LMSpec(**base, kv_page_size=page)
  pps = pspec.pages_per_slot
  top = max(ladder)
  budget_pages = top * pps
  paged_sessions = top
  while (decode_lib.kv_pool_pages(pspec, paged_sessions + 1)
         <= budget_pages):
    paged_sessions += 1
  concurrency = {"budget_pages": budget_pages, "dense_sessions": top,
                 "paged_sessions": paged_sessions}

  print("\n| arm | tok/s | ttft p99 ms | weights MB | kv cache KB | "
        "agree | accept p50/p99 |")
  print("|---|---|---|---|---|---|---|")
  for name, _ in arms:
    s = results[name]["stats"]
    acc = ("-" if s.get("serving/accept_len_p50") is None else
           f"{s['serving/accept_len_p50']:.0f}/"
           f"{s['serving/accept_len_p99']:.0f}")
    print(f"| {name} | {s['serving/tokens_per_sec']:.0f} | "
          f"{1e3 * s['serving/ttft_p99']:.1f} | "
          f"{results[name]['weight_bytes'] / 1e6:.2f} | "
          f"{results[name]['kv_cache_bytes'] / 1e3:.0f} | "
          f"{agreements.get(name, 1.0):.4f} | {acc} |")
  print(f"\nconcurrency: one dense slab at bucket {top} "
        f"({budget_pages} pages) admits {top} dense sessions vs "
        f"{paged_sessions} paged sessions", flush=True)
  decision = ("serve int8" if gate["passed"]
              else "dense fallback (bench path serves f32)")
  print(f"int8 accuracy gate: prefix-conditioned agreement "
        f"{gate['agreement']:.4f} vs bar "
        f"{decode_lib.QUANTIZE_AGREEMENT_BAR}, max logit delta "
        f"{gate['max_logit_delta']:.4f} of scale "
        f"{gate['logit_scale']:.3f} -> {decision}", flush=True)
  for name, ok in verdicts.items():
    bar = ("accuracy gate measured + enforced" if name == "int8"
           else "exact token identity")
    print(f"verdict {name}: {bar} -> "
          + ("PASS" if ok else "FAIL"), flush=True)

  record = {
      "metric": "serving_decode_variants",
      "value": round(gate["agreement"], 4),
      "unit": "int8_prefix_agreement",
      "requests": args.requests, "rate": args.rate,
      "max_new_tokens": args.max_new, "ladder": list(ladder),
      "seed": args.seed, "agreements": agreements,
      "quantize_gate": {
          "agreement": round(gate["agreement"], 6),
          "max_logit_delta": round(gate["max_logit_delta"], 6),
          "logit_scale": round(gate["logit_scale"], 6),
          "passed": gate["passed"]},
      "concurrency": concurrency,
      "arms": {name: {"stats": results[name]["stats"],
                      "wall_s": round(results[name]["wall_s"], 3),
                      "weight_bytes": results[name]["weight_bytes"],
                      "kv_cache_bytes": results[name]["kv_cache_bytes"]}
               for name, _ in arms},
  }
  print(json.dumps(record), flush=True)
  return 0 if all(verdicts.values()) else 1


def tp_ab(args):
  """Tensor-parallel serving decode vs the single-replica arm
  (ISSUE 17), in-process on the CPU mesh (chip rows: not measured).
  Same seeded workload + same UNSHARDED
  init for every arm, so exact token identity is well-posed."""
  if REPO not in sys.path:
    sys.path.insert(0, REPO)
  if args.engine_device == "cpu":
    # The TP mesh needs max(shards) devices: provision the virtual CPU
    # pool BEFORE jax initializes (the tests/conftest.py recipe), then
    # flip the platform through jax.config (CLAUDE.md: overriding the
    # pinned JAX_PLATFORMS env breaks the relay).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
  import dataclasses
  import json

  import jax
  import numpy as np

  from kf_benchmarks_tpu.serving import decode as decode_lib
  from kf_benchmarks_tpu.serving import (EngineConfig, ServingEngine,
                                         poisson_workload)
  from kf_benchmarks_tpu.validation import parse_bucket_ladder

  base = dict(vocab=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              max_len=128, attn_block=32)
  ladder = parse_bucket_ladder(args.ladder)
  cap_spec = decode_lib.LMSpec(**base)
  workload = poisson_workload(args.requests, args.rate, cap_spec,
                              seed=args.seed,
                              max_new_tokens=args.max_new)
  variables = decode_lib.init_variables(cap_spec, seed=args.seed)

  def per_device_bytes(tree):
    # Addressable shard on device 0: sharded matrices count 1/M,
    # replicated leaves count whole -- the serving HBM claim per chip.
    total = 0
    for leaf in jax.tree.leaves(tree):
      shards = getattr(leaf, "addressable_shards", None)
      total += (shards[0].data.nbytes if shards else leaf.nbytes)
    return total

  arms = [("dense", 0)] + [(f"tp{m}", m) for m in args.shards]
  results = {}
  for name, m in arms:
    spec = decode_lib.LMSpec(**base,
                             **({"model_shards": m} if m else {}))
    cfg = EngineConfig(spec=spec, bucket_ladder=ladder,
                       max_new_tokens=args.max_new,
                       max_queue_depth=args.requests + 1)
    # Warm replay first (same hygiene as engine_ab/variants_ab: the
    # cache scatter combos compile lazily per shape pair).
    warm = ServingEngine(cfg, variables=variables, seed=args.seed)
    warm.warm()
    warm.replay([(t, dataclasses.replace(r)) for t, r in workload])
    eng = ServingEngine(cfg, variables=variables, seed=args.seed)
    eng.warm()
    t0 = time.time()
    res = eng.replay([(t, dataclasses.replace(r)) for t, r in workload])
    wall = time.time() - t0
    # Weights measured AS THE EXECUTABLE CONSUMES them: the engine's
    # host tree stays whole (place_serving_args re-pins per dispatch),
    # so the per-device claim is the placed tree's device-0 shards --
    # column/row-parallel matrices 1/M, embeddings/LNs/head replicated.
    ins, _ = decode_lib.tp_shardings(spec, "serving_decode",
                                     max(ladder))
    placed_vars = (jax.device_put(eng._step_vars, ins[0]) if ins
                   else eng._step_vars)
    results[name] = {
        "tokens": {r.rid: list(r.tokens) for r in res
                   if r.status == "ok"},
        "stats": eng.stats(), "wall_s": wall,
        "weight_bytes_per_device": per_device_bytes(placed_vars),
        "kv_bytes_per_device": (
            per_device_bytes([eng._cache.k, eng._cache.v])
            if eng._cache is not None else 0),
    }

  dense = results["dense"]["tokens"]
  verdicts = {}
  for name, m in arms[1:]:
    got = results[name]["tokens"]
    verdicts[name] = set(got) == set(dense) and all(
        got[rid] == dense[rid] for rid in dense)

  print("\n| arm | tok/s | ttft p99 ms | weights/device MB | "
        "kv/device KB |")
  print("|---|---|---|---|---|")
  for name, _ in arms:
    s = results[name]["stats"]
    print(f"| {name} | {s['serving/tokens_per_sec']:.0f} | "
          f"{1e3 * s['serving/ttft_p99']:.1f} | "
          f"{results[name]['weight_bytes_per_device'] / 1e6:.2f} | "
          f"{results[name]['kv_bytes_per_device'] / 1e3:.0f} |")
  for name, ok in verdicts.items():
    print(f"verdict {name}: exact token identity vs dense -> "
          + ("PASS" if ok else "FAIL"), flush=True)

  record = {
      "metric": "serving_tensor_parallel",
      "value": round(min(
          results[f"tp{m}"]["stats"]["serving/tokens_per_sec"] /
          results["dense"]["stats"]["serving/tokens_per_sec"]
          for m in args.shards), 4),
      "unit": "tp_over_dense_tokens_per_sec",
      "requests": args.requests, "rate": args.rate,
      "max_new_tokens": args.max_new, "ladder": list(ladder),
      "seed": args.seed,
      "arms": {name: {"stats": results[name]["stats"],
                      "wall_s": round(results[name]["wall_s"], 3),
                      "weight_bytes_per_device":
                          results[name]["weight_bytes_per_device"],
                      "kv_bytes_per_device":
                          results[name]["kv_bytes_per_device"]}
               for name, _ in arms},
  }
  print(json.dumps(record), flush=True)
  return 0 if all(verdicts.values()) else 1


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--model", default="resnet50")
  ap.add_argument("--batches", type=int, default=50)
  ap.add_argument("--warmup", type=int, default=10)
  ap.add_argument("--bs", type=int, nargs="+", default=[32, 64, 128, 256])
  ap.add_argument("--device", default="tpu")
  ap.add_argument("--engine", action="store_true",
                  help="run the request-engine continuous-vs-static "
                       "A/B instead of the subprocess batch sweep")
  ap.add_argument("--engine_device", default="cpu",
                  choices=("cpu", "tpu"),
                  help="engine A/B backend (cpu = the virtual-mesh "
                       "A/B; tpu rides the standing chip campaign -- "
                       "serialize, never under a kill timeout)")
  ap.add_argument("--rates", type=float, nargs="+",
                  default=[40, 80, 160],
                  help="offered arrival rates, requests/s")
  ap.add_argument("--requests", type=int, default=64)
  ap.add_argument("--max_new", type=int, default=16)
  ap.add_argument("--ladder", default="1,4,16")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--variants", action="store_true",
                  help="run the decode-cost variants A/B (INT8 / "
                       "paged KV / speculative / composed vs the "
                       "dense arm on the SAME seeded workload)")
  ap.add_argument("--rate", type=float, default=80,
                  help="variants/tp A/B: offered arrival rate, req/s")
  ap.add_argument("--tp", action="store_true",
                  help="run the tensor-parallel serving A/B "
                       "(--serving_model_shards arms vs the single-"
                       "replica arm on the SAME seeded workload)")
  ap.add_argument("--shards", type=int, nargs="+", default=[2, 4],
                  help="tp A/B: model-shard counts (each must divide "
                       "the spec's head count and the device pool)")
  args = ap.parse_args()
  if args.tp:
    raise SystemExit(tp_ab(args))
  if args.variants:
    raise SystemExit(variants_ab(args))
  if args.engine:
    raise SystemExit(engine_ab(args))

  base = [f"--model={args.model}", f"--device={args.device}",
          "--num_devices=1", f"--num_batches={args.batches}",
          f"--num_warmup_batches={args.warmup}", "--use_fp16=true",
          "--display_every=10"]
  rows = []
  for bs in args.bs:
    fwd = run_cli(base + [f"--batch_size={bs}", "--forward_only"])
    with tempfile.TemporaryDirectory() as td:
      blob = os.path.join(td, "model.bin")
      blob8 = os.path.join(td, "model_int8.bin")
      run_cli(base + [f"--batch_size={bs}", "--forward_only",
                      f"--aot_save_path={blob}", "--num_batches=5"])
      aot = run_cli(base + [f"--batch_size={bs}", "--forward_only",
                            f"--aot_load_path={blob}"])
      # The TRT INT8 analog: weight-only quantized export
      # (quantization.py), benchmarked the same way.
      run_cli(base + [f"--batch_size={bs}", "--forward_only",
                      f"--aot_save_path={blob8}", "--trt_mode=INT8",
                      "--num_batches=5"])
      aot8 = run_cli(base + [f"--batch_size={bs}", "--forward_only",
                             f"--aot_load_path={blob8}"])
    rows.append((bs, fwd, 1e3 * bs / fwd, aot, 1e3 * bs / aot,
                 aot8, 1e3 * bs / aot8))
    print(f"bs={bs}: forward {fwd:.0f} img/s ({rows[-1][2]:.2f} ms/batch), "
          f"aot {aot:.0f} img/s ({rows[-1][4]:.2f} ms/batch), "
          f"aot-int8 {aot8:.0f} img/s ({rows[-1][6]:.2f} ms/batch)",
          flush=True)

  print("\n| bs | forward img/s | forward ms/batch | aot img/s | "
        "aot ms/batch | aot-int8 img/s | aot-int8 ms/batch |")
  print("|---|---|---|---|---|---|---|")
  for bs, f_ips, f_ms, a_ips, a_ms, q_ips, q_ms in rows:
    print(f"| {bs} | {f_ips:.0f} | {f_ms:.2f} | {a_ips:.0f} | {a_ms:.2f}"
          f" | {q_ips:.0f} | {q_ms:.2f} |")


if __name__ == "__main__":
  main()
