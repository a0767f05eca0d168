"""Multi-rank run-trace merge under the kfrun launcher (tracing.py).

A 2-worker kfrun job traces to ONE shared --trace_events_file path:
every rank writes its own span file (rank 0 owns the canonical path,
rank 1 a ``.rank1`` sibling -- the flight-recorder naming convention),
all ranks inherit one KF_RUN_ID from the launcher, and rank 0 merges
the rank files into one coherent Chrome timeline at exit (pid = rank,
tid = subsystem).

Process-spawning (DISTRIBUTED_TESTS tier) and timeout-free:
kfrun.launch blocks on worker exit and the rank-0 merge waits on
sibling FILES with a bounded host-side poll -- no subprocess is killed
on a timer.
"""

import json
import os
import sys

import pytest

from kf_benchmarks_tpu import kfrun
from kf_benchmarks_tpu import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
@pytest.mark.distributed
def test_two_rank_kfrun_merges_one_timeline(tmp_path):
  trace_path = str(tmp_path / "trace.json")
  logdir = str(tmp_path / "logs")
  os.makedirs(logdir)
  worker_cmd = [
      sys.executable, "-m", "kf_benchmarks_tpu.cli",
      "--model=trivial", "--device=cpu", "--num_devices=1",
      "--batch_size=4", "--num_batches=6", "--num_warmup_batches=1",
      "--display_every=2", f"--trace_events_file={trace_path}",
  ]
  env = {
      "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
      "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
  }
  rc = kfrun.launch(2, worker_cmd, logdir=logdir, extra_env=env)
  assert rc == 0, "worker logs: " + "".join(
      open(os.path.join(logdir, n)).read()
      for n in sorted(os.listdir(logdir)) if n.endswith("stderr.log"))
  # Rank 1 wrote its own span file; rank 0 merged both at the canonical
  # path into one coherent timeline.
  assert os.path.exists(tracing.rank_path(trace_path, 1))
  merged = json.load(open(trace_path))
  assert tracing.validate_chrome_trace(merged) == [], \
      tracing.validate_chrome_trace(merged)[:5]
  xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
  assert {e["pid"] for e in xs} == {0, 1}
  # Both ranks' timelines carry the core lanes.
  for pid in (0, 1):
    cats = {e["cat"] for e in xs if e["pid"] == pid}
    assert {"dispatch", "device", "compile"} <= cats, (pid, cats)
  # One launcher-minted run id spans the whole job: the merged metadata
  # and rank 1's own file agree (KF_RUN_ID env propagation, kfrun.py).
  rank1 = json.load(open(tracing.rank_path(trace_path, 1)))
  assert merged["metadata"]["run_id"]
  assert merged["metadata"]["run_id"] == rank1["metadata"]["run_id"]
  # Thread-name metadata survives the merge for every pid (the
  # subsystem lanes stay labeled in Perfetto).
  named = {(e["pid"], e["args"]["name"])
           for e in merged["traceEvents"]
           if e["ph"] == "M" and e["name"] == "thread_name"}
  assert {(0, "dispatch"), (1, "dispatch")} <= named
