"""Real-data training ON THE CHIP: feeder occupancy + steady host-bound
step rate (VERDICT r3 missing #5; ref: preprocessing.py:505-548,
:601-617 -- the reference trains its real-data path on the device, we
had only CPU-tested ours).

This host has ONE core and a measured ~310 img/s decode ceiling
(PERF.md round 3), so the point is NOT throughput parity with the
2,600 img/s synthetic rate: it is a correctness/occupancy check that

  * the TFRecord -> decode pool -> DeviceFeeder -> TPU path trains,
  * step times are steady at the HOST-bound rate (no stalls/backlog
    collapse -- jitter stays a small fraction of the mean), and
  * the decode pool's parent-side dispatch cost is negligible at rate.

Writes realistic 375x500 JPEGs (input_pipeline_bench's generator), runs
the CLI on the real chip with --input_preprocessor=multiprocess, and
scrapes the reference-format step lines.

    python experiments/real_data_occupancy.py [--batches 30] [--bs 64]
"""

from __future__ import annotations

import argparse
import os
import re
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from experiments.input_pipeline_bench import write_fixture  # noqa: E402
from experiments.serving_sweep import run_child  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_RE = re.compile(
    r"^(\d+)\timages/sec: ([\d.]+) \+/- ([\d.]+) \(jitter = ([\d.]+)\)",
    re.M)


def main():
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--batches", type=int, default=30)
  ap.add_argument("--bs", type=int, default=64)
  ap.add_argument("--images", type=int, default=768)
  ap.add_argument("--preprocessor", default="multiprocess")
  ap.add_argument("--workers", type=int, default=0,
                  help="decode workers/threads (0 = pipeline default); on "
                  "this 1-core host >1 worker only adds contention")
  args = ap.parse_args()

  with tempfile.TemporaryDirectory() as d:
    write_fixture(d, args.images, 375, 500)
    print(f"fixture: {args.images} JPEGs", flush=True)
    # One child; this parent stays off JAX (serving_sweep.run_child).
    rc, out, err = run_child(
        ["--model=resnet50", f"--data_dir={d}", "--data_name=imagenet",
         "--device=tpu", "--num_devices=1", f"--batch_size={args.bs}",
         f"--num_batches={args.batches}", "--num_warmup_batches=2",
         "--display_every=5", "--use_fp16=true", "--optimizer=momentum",
         f"--input_preprocessor={args.preprocessor}", "--nodistortions"]
        + ([f"--datasets_num_private_threads={args.workers}"]
           if args.workers else []))
  sys.stderr.write(out[-4000:] + err[-2000:])
  if rc != 0:
    raise SystemExit(f"CLI failed rc={rc}")
  rows = [(int(s), float(ips), float(jit))
          for s, ips, _, jit in STEP_RE.findall(out)]
  if not rows:
    raise SystemExit("no step lines scraped")
  rates = [ips for _, ips, _ in rows]
  jits = [j for _, _, j in rows]
  print("\n| window end | img/s | jitter |")
  print("|---|---|---|")
  for s, ips, j in rows:
    print(f"| {s} | {ips:.1f} | {j:.1f} |")
  mean = statistics.mean(rates)
  print(f"\nsteady mean {mean:.1f} img/s (host decode ceiling ~310), "
        f"median jitter {statistics.median(jits):.1f} ms, "
        f"min/max window {min(rates):.1f}/{max(rates):.1f}")


if __name__ == "__main__":
  main()
