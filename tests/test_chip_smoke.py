"""chip_smoke.py (repo root): the no-chip half of its contract.

The chip half runs only on a machine with a TPU (through the builder's
tool); here the script must refuse: nonzero exit, the platform it found
named, no result line -- and it must get there before building the
model (seconds, not a compile).
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_a_chip():
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  env.pop("XLA_FLAGS", None)
  t0 = time.monotonic()
  r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                     capture_output=True, text=True, env=env, cwd=REPO)
  wall = time.monotonic() - t0
  assert r.returncode != 0
  assert "platform=cpu" in r.stdout       # names what it found
  assert "FAIL no accelerator" in r.stderr and "platform=cpu" in r.stderr
  # No result line, no model build, no throughput under any name.
  assert '"ok"' not in r.stdout
  assert "images/sec" not in r.stdout and "Model:" not in r.stdout
  for line in r.stdout.splitlines():
    assert not line.startswith("{"), line
  assert wall < 60, f"took {wall:.0f} s: it must fail before any build"


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
  """In a directory that holds chip_smoke.py and nothing else of the
  repo, the script fails (here already on the platform; on a chip
  machine on the missing package) and prints no result."""
  lone = tmp_path / "chip_smoke.py"
  lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  env.pop("PYTHONPATH", None)
  r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                     text=True, env=env, cwd=str(tmp_path))
  assert r.returncode != 0
  assert '"ok"' not in r.stdout
  # ... and with the device check out of the way the package import is
  # what stops it: nothing of the repo is reachable from there.
  probe = ("import sys, runpy; sys.argv=['chip_smoke.py']; "
           "m = runpy.run_path('chip_smoke.py'); "
           "m['run_leg']('x', 1)")
  r2 = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                      text=True, env=env, cwd=str(tmp_path))
  assert r2.returncode != 0
  assert "No module named 'kf_benchmarks_tpu'" in r2.stderr
