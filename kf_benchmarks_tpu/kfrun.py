"""kfrun: multi-process launcher, the ``kungfu-run`` analog.

The reference launches one process per device with
``kungfu-run -np N python3 tf_cnn_benchmarks.py ...`` and the KungFu
config server wires the peer mesh, capturing per-process logs as
``127.0.0.1.<port>.{stdout,stderr}.log`` (ref: README.md "Running
KungFu"; the committed log files of that shape are kungfu-run output).

kfrun reproduces that contract on the native coordination service
(native/kfcoord.cc): it starts a coordinator, spawns N worker processes
with KFCOORD_* env vars (host, port, world size, per-process name), and
captures per-process logs with the same naming scheme. Workers find
their rank by JOINing the coordinator; `run_barrier()` rides the same
service at exit.

Cross-process elastic resize (the KungFu resize_cluster restart leg,
SURVEY 5.3/7.4 "checkpointed rescale"): a live JAX world cannot change
its process count, so when a kfcoord RESIZE requires one, every worker
checkpoints, enters a restart barrier, and exits with
``RESTART_EXIT_CODE``. kfrun treats that exit as a coordinated restart
request: it reads the target size from its coordinator and relaunches
the SAME command with the new world size (logs append across
generations). Workers resume from the checkpoint in ``--train_dir``.

Usage:
    python -m kf_benchmarks_tpu.kfrun -np 4 -- python -m \
        kf_benchmarks_tpu.cli --model=resnet50 --variable_update=kungfu

On real multi-host TPU pods the TPU runtime launches one process per
host and JAX's distributed init handles the device mesh; kfrun covers
the CPU many-process topology (the tests) and the one-process-per-host
launch, and the coordinator serves as the DCN control plane in both.

On ONE TPU host, multi-chip means ONE process with ``--num_devices=N``
(measured on a four-chip v5e host, PR 21: sync_sgd / async_sgd / sma
all run that way). A chip belongs to the process that first touches
JAX, so ``kfrun -np N`` with N > 1 on one TPU host would hand every
chip to the first worker; kfrun does not pin chips per worker. For the
same reason this launcher parent stays OFF JAX: it imports only
``coordination`` and ``tracing`` (neither initialises a backend), so
the chips are free for the worker it starts. Keep it so.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
from typing import List, Optional, Tuple

# Exit code a worker uses to request a coordinated checkpoint-restart
# resize (chosen outside the shell/POSIX reserved ranges).
RESTART_EXIT_CODE = 42


def _run_generation(server, np_: int, command: List[str], logdir: str,
                    host: str, extra_env: Optional[dict],
                    opened_logs: Optional[set] = None) -> Tuple[int, bool]:
  """Spawn one generation of ``np_`` workers; wait.

  Returns (exit_code, restart_requested). The first time THIS launch
  opens a worker's log file it truncates it (a fresh launch -- or a
  restart that grows past the previous world size -- must not
  accumulate an earlier job's output); later generations append so one
  job's output stays in one set of files."""
  if opened_logs is None:
    opened_logs = set()
  procs = []
  log_files = []
  try:
    for i in range(np_):
      env = dict(os.environ)
      env.update(extra_env or {})
      env["KFCOORD_HOST"] = host
      env["KFCOORD_PORT"] = str(server.port)
      env["KFCOORD_WORLD"] = str(np_)
      env["KFCOORD_NAME"] = f"worker-{i}"
      # RANK_HINT is the one env var host code may BRANCH on -- any
      # collective/barrier under such a branch needs an all-ranks:
      # justification (the rank-divergent-collective lint rule), and
      # rank-guarded artifact writes a rank0-owns: marker.
      env["KFCOORD_RANK_HINT"] = str(i)
      # Per-process log capture, named the way kungfu-run names them.
      tag = f"{host}.{10000 + i}"
      mode = "a" if tag in opened_logs else "w"
      opened_logs.add(tag)
      out = open(os.path.join(logdir, f"{tag}.stdout.log"), mode)
      err = open(os.path.join(logdir, f"{tag}.stderr.log"), mode)
      log_files += [out, err]
      procs.append(subprocess.Popen(command, env=env, stdout=out,
                                    stderr=err))
    # Monitor rather than blindly wait: if one worker dies abnormally
    # while its siblings are parked in the exit barrier, the barrier can
    # never fill -- tear the job down instead of hanging (the
    # kungfu-run failure contract). RESTART_EXIT_CODE is a coordinated
    # exit, not a failure.
    import time
    while True:
      codes = [p.poll() for p in procs]
      if all(c is not None for c in codes):
        break
      if any(c not in (None, 0, RESTART_EXIT_CODE) for c in codes):
        time.sleep(1.0)  # grace: let siblings exit on their own
        for p in procs:
          if p.poll() is None:
            p.terminate()
        for p in procs:
          try:
            p.wait(timeout=10)
          except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        codes = [p.poll() for p in procs]
        break
      time.sleep(0.1)
    if (any(c == RESTART_EXIT_CODE for c in codes) and
        all(c in (0, RESTART_EXIT_CODE) for c in codes)):
      return 0, True
    # Report the original failure, not the SIGTERM we delivered: a worker
    # killed by our teardown shows -15, which would mask the real code.
    failures = [c for c in codes
                if c not in (0, RESTART_EXIT_CODE, -signal.SIGTERM)]
    if failures:
      return max(abs(c) for c in failures), False
    return (1 if any(c == -signal.SIGTERM for c in codes) else 0), False
  except KeyboardInterrupt:
    for p in procs:
      p.send_signal(signal.SIGTERM)
    for p in procs:
      p.wait()
    return 130, False
  finally:
    for f in log_files:
      f.close()


def launch(np_: int, command: List[str], logdir: str = ".",
           host: str = "127.0.0.1", base_port: int = 0,
           extra_env: Optional[dict] = None,
           max_restarts: int = 16,
           restart_on_failure: bool = False) -> int:
  """Start coordinator + N workers; relaunch on coordinated restarts;
  return the final generation's worst exit code.

  ``restart_on_failure`` adds preemption survival (the kill/rejoin
  leg): a generation where any worker died abnormally -- SIGKILL'd by
  a preemptor, OOM-killed, crashed -- is relaunched at the SAME world
  size instead of failing the job, and the rejoined workers resume
  from the checkpoint in ``--train_dir`` (KungFu's config-server
  rejoin, SURVEY 2.9, rendered as checkpointed restart). Bounded by
  ``max_restarts`` so a deterministic crash loop still terminates."""
  from kf_benchmarks_tpu.parallel import coordination
  from kf_benchmarks_tpu import tracing

  server = coordination.CoordinatorServer(port=base_port)
  try:
    gen_np = np_
    opened_logs: set = set()
    # One run id for the whole job (all ranks, all restart
    # generations): workers inherit it via env, so their flight
    # recorders and run traces share one timeline identity and the
    # rank-0 trace merge is coherent (tracing.py).
    extra_env = dict(extra_env or {})
    extra_env.setdefault("KF_RUN_ID", tracing.resolve_run_id())
    # Per-rank scrape targets: a worker command carrying --metrics_port
    # binds base + rank per process (benchmark.py resolve_port), so the
    # launcher prints the whole job's endpoint list once up front --
    # the operator's copy-paste Prometheus targets. Always loopback:
    # the endpoint binds 127.0.0.1 regardless of the coordinator
    # --host (kfrun workers share this machine). Both flag spellings
    # (--metrics_port=P and --metrics_port P) are recognized.
    metrics_base = None
    for i, tok in enumerate(command):
      if tok.startswith("--metrics_port="):
        metrics_base = tok.partition("=")[2]
      elif tok == "--metrics_port" and i + 1 < len(command):
        metrics_base = command[i + 1]
    if metrics_base and metrics_base.isdigit():
      targets = ", ".join(
          f"http://127.0.0.1:{int(metrics_base) + r}/metrics"
          for r in range(np_))
      print(f"kfrun: metrics endpoints: {targets}",
            file=sys.stderr, flush=True)
      # Serving-mode children bind the engine's endpoint on the same
      # port: point the operator at /healthz too, which carries the
      # engine state AND the per-tenant SLO burn rates -- "up" vs "up
      # but burning error budget" is the probe's whole point.
      if any(tok == "--serving" or tok.startswith("--serving=")
             for tok in command):
        health = ", ".join(
            f"http://127.0.0.1:{int(metrics_base) + r}/healthz"
            for r in range(np_))
        print("kfrun: serving healthz (engine + SLO burn state): "
              f"{health}", file=sys.stderr, flush=True)
    for _ in range(max_restarts + 1):
      code, restart = _run_generation(server, gen_np, command, logdir,
                                      host, extra_env,
                                      opened_logs=opened_logs)
      if not restart:
        # 130 = KeyboardInterrupt teardown: the operator asked the job
        # to stop; survival must not resurrect it.
        if code in (0, 130) or not restart_on_failure:
          return code
        # Abnormal worker death with survival enabled: rejoin at the
        # same world size from the last checkpoint. No resize was
        # agreed, so the scheduled-restart key is not consulted.
        print(f"kfrun: worker died (exit {code}); rejoining "
              f"np={gen_np} from the last checkpoint",
              file=sys.stderr, flush=True)
        continue
      # The workers checkpointed and exited for a resize; relaunch at
      # the PROCESS count they agreed on in the scheduled-restart key
      # (the raw RESIZE target is a global DEVICE count -- with >1
      # device per process the two differ, and respawning at the device
      # count would churn restarts forever).
      with coordination.CoordinatorClient(host=host,
                                          port=server.port) as client:
        new_np = gen_np
        try:
          gen = client.current_generation()
          sched = client.kv_tryget(f"kf_restart_sched_{gen}")
          if sched:
            new_np = max(1, int(sched.decode().partition(":")[2]))
          # No fallback to try_target_size(): that is a global DEVICE
          # count, and respawning processes at it churns restarts
          # forever when capacity > 1 (the workers re-derive the right
          # process count from a fresh poll after respawn at gen_np).
        except Exception as e:  # noqa: BLE001
          print(f"kfrun: could not read restart target ({e}); "
                f"respawning at np={gen_np}", file=sys.stderr, flush=True)
      print(f"kfrun: coordinated restart, np {gen_np} -> {new_np}",
            file=sys.stderr, flush=True)
      gen_np = new_np
    print(f"kfrun: giving up after {max_restarts} restarts",
          file=sys.stderr, flush=True)
    return 1
  finally:
    server.stop()


def main(argv=None):
  parser = argparse.ArgumentParser(
      prog="kfrun", description="kungfu-run-style multi-process launcher")
  parser.add_argument("-np", type=int, required=True, dest="np_",
                      help="number of worker processes")
  parser.add_argument("--logdir", default=".",
                      help="directory for per-process logs")
  parser.add_argument("--host", default="127.0.0.1")
  parser.add_argument("--port", type=int, default=0,
                      help="coordinator port (0 = ephemeral)")
  parser.add_argument("--restart-on-failure", action="store_true",
                      dest="restart_on_failure",
                      help="relaunch the world at the same size when a "
                           "worker dies abnormally (preemption "
                           "survival; workers resume from --train_dir)")
  parser.add_argument("command", nargs=argparse.REMAINDER,
                      help="worker command (prefix with --)")
  args = parser.parse_args(argv)
  command = args.command
  if command and command[0] == "--":
    command = command[1:]
  if not command:
    parser.error("no worker command given")
  sys.exit(launch(args.np_, command, logdir=args.logdir, host=args.host,
                  base_port=args.port,
                  restart_on_failure=args.restart_on_failure))


if __name__ == "__main__":
  main()
