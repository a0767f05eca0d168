"""Model-zoo training throughput sweep on the real chip.

The reference publishes multi-model throughput tables (tf_cnn_benchmarks
README methodology: alexnet/googlenet/vgg16/inception3/resnet50/... at
fixed per-device batch sizes); our hardware evidence so far covers
resnet50 (+3 north-star configs).  This sweep runs the whole classic
image zoo through the stock CLI on the real chip -- one child process
per point, one after another, from a parent that stays off JAX (a chip
belongs to one process at a time); synthetic data, bf16 training step
-- and prints the markdown table for PERF.md.

Batch sizes follow the reference's per-GPU conventions where they fit
v5e HBM (resnet50 @ 256 is the measured optimum; vgg/inception @ 128;
inception4/resnet152 @ 64 for activation footprint; alexnet @ 512 as in
the classic table).

    python experiments/zoo_sweep.py [--batches 40] [--only resnet50 ...]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from experiments.serving_sweep import run_cli_output  # noqa: E402

# The banner line naming the device the child's mesh held
# (benchmark.print_info).
DEVICE_RE = re.compile(r"^Num devices: \d+ \((\w+), (.+)\)$", re.M)


def run_point(cli, mfu=False):
  """One sweep point (one child process) -> (img/s, mfu or None).

  ``mfu=True`` adds the MFU column: measured FLOP/s over the bf16 peak
  of the device_kind the CHILD reported (observability.DEVICE_PEAKS; a
  device not in the table gets no MFU cell) -- the train program's
  static flop count from the compiled-HLO cost analysis the CLI dumps
  under --tfprof_file, times the measured steps/s. OPT-IN because
  --tfprof_file compiles the step a second time ahead of the jit
  cache's own compile (benchmark.py logs this)."""
  with tempfile.TemporaryDirectory() as td:
    prof = os.path.join(td, "prof.json")
    ips, out = run_cli_output(
        cli + ([f"--tfprof_file={prof}"] if mfu else []))
    if not mfu:
      return ips, None
    flops = None
    try:
      with open(prof) as f:
        flops = json.load(f).get("cost_analysis", {}).get("flops")
    except (OSError, ValueError):
      pass
  bs = next((int(a.split("=")[1]) for a in cli
             if a.startswith("--batch_size=")), None)
  # No explicit --batch_size (model default resolved inside the CLI):
  # steps/s is unknowable here, so the point keeps its img/s and just
  # drops the MFU cell rather than discarding a completed chip run.
  if not (flops and bs):
    return ips, None
  # Importing the table touches no backend (this parent stays off JAX).
  from kf_benchmarks_tpu.observability import DEVICE_PEAKS
  device = DEVICE_RE.search(out)
  peaks = DEVICE_PEAKS.get(device.group(2)) if device else None
  if peaks is None:
    print(f"{cli[0]}: no MFU cell -- device "
          f"{device.groups() if device else 'unknown'} has no "
          "DEVICE_PEAKS entry", flush=True)
    return ips, None
  return ips, flops * (ips / bs) / peaks.flops

# (model, batch_size, extra CLI args)
ZOO = [
    ("alexnet", 512, []),
    ("googlenet", 128, []),
    ("overfeat", 256, []),
    ("vgg16", 128, []),
    ("inception3", 128, []),
    ("inception4", 64, []),
    ("resnet50", 256, []),
    ("resnet50_v1.5", 256, []),
    ("resnet101", 128, []),
    ("resnet152", 64, []),
    ("mobilenet", 256, []),
    # The round-4 table's five gaps (VERDICT r4 missing #4): every
    # registered family gets a measured row.
    # nasnet keeps its model-default batch (32): the cifar cell stack
    # carries aux heads + drop-path, and a one-shot hardware window is
    # not the place to discover its bs-128 memory envelope.
    ("nasnet", 32, ["--data_name=cifar10"]),
    ("densenet40_k12", 256, ["--data_name=cifar10"]),
    ("lenet", 512, []),
    ("trivial", 512, []),
    ("official_resnet18", 256, []),
    # Non-image families (synthetic inputs come from each model's
    # get_synthetic_inputs; "img/s" reads examples/s).
    ("ssd300", 32, ["--data_name=coco"]),
    ("deepspeech2", 32, ["--data_name=librispeech", "--optimizer=adam"]),
    ("ncf", 16384, ["--optimizer=adam", "--weight_decay=0"]),
]


def _extra_kwargs(extra):
  """'--data_name=cifar10'-style extra CLI args as make_params kwargs
  (the autotune path runs in-process, not through the CLI parser)."""
  out = {}
  for arg in extra:
    k, _, v = arg.lstrip("-").partition("=")
    for cast in (int, float):
      try:
        v = cast(v)
        break
      except ValueError:
        pass
    out[k] = v
  return out


def autotune_bases(only, device):
  """The base configs --autotune searches: the ZOO rows' sweep
  settings, with each row's extra CLI args OVERRIDING the common
  defaults (deepspeech2/ncf set their own --optimizer). health_stats
  is pinned True -- the bench.py canonical config -- so the emitted
  entries serve `bench.py --autotuned_config` / `--check-regression`
  directly; CLI training runs apply them with `--health_stats=true`
  (the flag is program-shaping, so it is part of the table identity
  on purpose)."""
  bases = []
  for model, bs, extra in ZOO:
    if only and model not in only:
      continue
    base = dict(model=model, batch_size=bs, device=device,
                num_devices=1, use_fp16=device == "tpu",
                optimizer="momentum", health_stats=True)
    base.update(_extra_kwargs(extra))
    bases.append(base)
  return bases


def run_autotune(args):
  """--autotune: the contract-driven knob search (analysis/autotune.py)
  over the zoo, IN-PROCESS -- one process, strictly sequential probes
  (no children: this process is the one that holds the chip). Emits
  the tuned-config table --num_batches-independent runs apply via
  --autotuned_config."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.analysis import autotune

  # setup() selects the platform (--device=cpu) or refuses to go on
  # when --device=tpu finds no TPU, and names the device it found.
  benchmark.setup(params_lib.make_params(device=args.device,
                                         num_devices=1))
  print("zoo_sweep: device", benchmark.device_identity(), flush=True)
  table = autotune.autotune_configs(
      autotune_bases(args.only, args.device), out=args.out,
      seed=args.seed, dry_run=args.dry_run)
  print("\n| model | tuned knobs | default img/s | tuned img/s |")
  print("|---|---|---|---|")
  for key in sorted(table["entries"]):
    e = table["entries"][key]
    knobs = ", ".join(f"{k}={v}" for k, v in sorted(e["tuned"].items())
                      if v is not None) or "(defaults)"
    print(f"| {e['model']} | {knobs} | "
          f"{e['default_images_per_sec'] or '-'} | "
          f"{e['tuned_images_per_sec'] or '-'} |")


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--batches", type=int, default=40)
  ap.add_argument("--warmup", type=int, default=5)
  ap.add_argument("--only", nargs="*", default=None)
  ap.add_argument("--device", default="tpu")
  ap.add_argument("--mfu", action="store_true",
                  help="add the measured-MFU column (costs a second "
                       "compile per point via --tfprof_file)")
  ap.add_argument("--autotune", action="store_true",
                  help="run the contract-driven knob search per model "
                       "(analysis/autotune.py) instead of the fixed-"
                       "config sweep, and write the tuned-config table")
  ap.add_argument("--out", default="tuned_configs.json",
                  help="--autotune: tuned-table output path")
  ap.add_argument("--seed", type=int, default=0,
                  help="--autotune: candidate-subsample seed")
  ap.add_argument("--dry-run", action="store_true", dest="dry_run",
                  help="--autotune: static stages only (nothing "
                       "executes)")
  args = ap.parse_args()

  if args.only:
    known = {m for m, _, _ in ZOO}
    bad = set(args.only) - known
    if bad:
      raise SystemExit(f"unknown --only models {sorted(bad)}; "
                       f"choose from {sorted(known)}")

  if args.autotune:
    return run_autotune(args)

  rows = []
  for model, bs, extra in ZOO:
    if args.only and model not in args.only:
      continue
    cli = [f"--model={model}", f"--batch_size={bs}",
           f"--device={args.device}", "--num_devices=1",
           f"--num_batches={args.batches}",
           f"--num_warmup_batches={args.warmup}",
           "--use_fp16=true", "--optimizer=momentum",
           "--display_every=10"] + extra
    try:
      ips, mfu = run_point(cli, mfu=args.mfu)
    except (RuntimeError, subprocess.SubprocessError) as e:
      # A single failed point must not discard the completed runs --
      # record it and keep sweeping.
      print(f"{model}: FAILED -- {e}", flush=True)
      rows.append((model, bs, None, None))
      continue
    rows.append((model, bs, ips, mfu))
    print(f"{model} bs={bs}: {ips:.0f} img/s "
          f"({1e3 * bs / ips:.2f} ms/step"
          + (f", MFU {100 * mfu:.1f}%" if mfu else "") + ")",
          flush=True)

  print("\n| model | bs | img/s | ms/step | MFU |")
  print("|---|---|---|---|---|")
  for model, bs, ips, mfu in rows:
    if ips is None:
      print(f"| {model} | {bs} | failed | - | - |")
    else:
      print(f"| {model} | {bs} | {ips:.0f} | {1e3 * bs / ips:.2f} | "
            + (f"{100 * mfu:.1f}% |" if mfu else "- |"))


if __name__ == "__main__":
  main()
