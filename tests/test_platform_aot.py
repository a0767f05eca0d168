"""Platform hook, cluster manager, AOT export, and official-resnet tests
(SURVEY 2.1 platform hook, 2.7 cluster layer, 2.10 TRT analog, 2.5
official_resnet row)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import aot, benchmark, cluster, params as params_lib
from kf_benchmarks_tpu.models import model_config
from kf_benchmarks_tpu.platforms import util as platforms_util


def test_official_resnet_18_34_forward():
  for size, n_params_range in ((18, (11e6, 13e6)), (34, (21e6, 23e6))):
    model = model_config.get_model_config(f"official_resnet{size}",
                                          "imagenet")
    model.set_batch_size(2)
    rng = jax.random.PRNGKey(0)
    images, labels = model.get_synthetic_inputs(rng, 1001)
    module = model.make_module(nclass=1001, phase_train=False)
    variables = module.init({"params": rng, "dropout": rng}, images)
    (logits, _), _ = module.apply(variables, images,
                                  mutable=["batch_stats"])
    assert logits.shape == (2, 1001)
    n = sum(x.size for x in jax.tree.leaves(variables["params"]))
    lo, hi = n_params_range
    assert lo < n < hi, f"resnet{size}: {n/1e6:.2f}M params"


def test_official_resnet_size_validation():
  from kf_benchmarks_tpu.models import official_resnet_model
  with pytest.raises(ValueError, match="resnet_size"):
    official_resnet_model.OfficialResnetModel(77)
  with pytest.raises(ValueError, match="version"):
    official_resnet_model.OfficialResnetModel(50, 3)


def test_platform_hooks():
  platforms_util.define_platform_params()  # no-op, must not raise
  out_dir = platforms_util.get_test_output_dir()
  assert os.path.isdir(out_dir)
  p = params_lib.make_params(model="trivial", device="cpu")
  platforms_util.initialize(p)
  assert platforms_util.get_cluster_manager(p) is None  # single process


def test_cluster_manager_rejects_ps_roles():
  p = params_lib.make_params(model="trivial", device="cpu", job_name="ps")
  with pytest.raises(ValueError, match="no TPU analog"):
    cluster.BaseClusterManager(p)
  p = params_lib.make_params(model="trivial", device="cpu",
                             ps_hosts=["h:1"])
  with pytest.raises(ValueError, match="sharded state"):
    cluster.BaseClusterManager(p)


def test_cluster_manager_spec():
  p = params_lib.make_params(model="trivial", device="cpu",
                             job_name="worker",
                             worker_hosts=["h0:1111"], task_index=0)
  mgr = cluster.JaxClusterManager(p)
  assert mgr.get_target() == "h0:1111"
  assert mgr.num_workers() == 1


def test_aot_export_roundtrip(tmp_path):
  """Forward-only run exports a frozen program; reloading serves the
  same logits without the model code (the freeze+TRT analog)."""
  path = str(tmp_path / "frozen" / "trivial.jaxexport")
  p = params_lib.make_params(
      model="trivial", batch_size=4, num_batches=2, num_warmup_batches=1,
      device="cpu", num_devices=1, forward_only=True, aot_save_path=path)
  bench = benchmark.BenchmarkCNN(p)
  stats = bench.run()
  assert os.path.exists(path)
  state = stats["state"]
  serve = aot.load_forward(path)
  bench.model.set_batch_size(4)
  image_shape = tuple(bench.model.get_input_shapes("eval")[0])
  images = np.random.RandomState(0).uniform(
      0, 255, image_shape).astype(np.float32)
  logits = serve(jnp.asarray(images))
  # Compare against the live module with the same weights.
  module = bench.model.make_module(nclass=bench.dataset.num_classes,
                                   phase_train=False)
  variables = {"params": jax.tree.map(lambda x: x[0], state.params)}
  bs = jax.tree.map(lambda x: x[0], state.batch_stats)
  if bs:
    variables["batch_stats"] = bs
  live_logits, _ = module.apply(variables, jnp.asarray(images))
  np.testing.assert_allclose(np.asarray(logits), np.asarray(live_logits),
                             rtol=1e-5, atol=1e-5)


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_aot_serving_benchmark_fresh_process(tmp_path):
  """--forward_only --aot_load_path times the frozen artifact in a FRESH
  process (VERDICT r1 next #10: the TRT-serving-benchmark analog,
  ref: _preprocess_graph benchmark_cnn.py:2405-2525)."""
  import os
  import re
  import subprocess
  import sys
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  path = str(tmp_path / "frozen_forward.bin")
  env = dict(os.environ)
  env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
  env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
  common = [sys.executable, "-m", "kf_benchmarks_tpu.cli",
            "--model=trivial", "--forward_only=true", "--device=cpu",
            "--batch_size=4", "--num_warmup_batches=1"]
  # 1) Export the frozen forward program.
  save = subprocess.run(
      common + ["--num_batches=2", f"--aot_save_path={path}"],
      env=env, cwd=repo, capture_output=True, text=True, timeout=300)
  assert save.returncode == 0, (save.stdout, save.stderr)
  assert "Exported frozen forward program" in save.stdout
  assert os.path.getsize(path) > 0
  # 2) A fresh process loads and times it.
  load = subprocess.run(
      common + ["--num_batches=6", f"--aot_load_path={path}"],
      env=env, cwd=repo, capture_output=True, text=True, timeout=300)
  assert load.returncode == 0, (load.stdout, load.stderr)
  assert "Loaded frozen forward program" in load.stdout
  m = re.search(r"total images/sec: ([\d.]+)", load.stdout)
  assert m, load.stdout
  assert float(m.group(1)) > 0
