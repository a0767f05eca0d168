"""The reader of the hyper-connections' kernel share:
``mhc_kernel_share`` = ``stats["mhc"]["kernel_share"]``. It reads the
number the program's counter holds (1.0 where the mixing takes the
kernels, 0.0 where ``mhc.mhc_plan`` sends it to ``jax.numpy``), reads
nothing where the program has no such counter, agrees with its entry and
is reported in the cell its entry names (found by name); and the
program, built at the xing cell's own shapes, leaves 1.0 on a TPU and
0.0 anywhere else."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from bench_testlib import REPO, holding
from benchmarks import harness
from benchmarks import spec

H = holding("mhc_kernel_share")


def _run(stats):
  return harness.Run(cell={"name": "x", "config_data": {}}, device={},
                     peaks={}, kwargs={}, timed_steps=20, t0=0.0,
                     stats=stats)


def _read(stats):
  return spec.load_metric(REPO, "per_layer", H.NAME).read(_run(stats))


@pytest.mark.parametrize("stats, want", [
    ({"mhc": {"kernel_share": 1.0, "implementation": "pallas",
              "token_block": 128}}, 1.0),
    # 0 is a reading (jax.numpy ran), not "none".
    ({"mhc": {"kernel_share": 0.0, "implementation": "xla",
              "token_block": 0}}, 0.0),
])
def test_reads_the_programs_counter(stats, want):
  assert _read(stats) == want


@pytest.mark.parametrize("stats", [
    None, {}, {"mhc": None},
    # A stats["mhc"] from before the kernels: the streams and the residual, no
    # share.
    {"mhc": {"streams": 4, "sublayers": 12, "sinkhorn_residual": 1e-5}},
])
def test_reads_nothing_where_the_program_has_no_such_counter(stats):
  assert _read(stats) is None


def test_is_reported_in_the_cell_its_entry_names():
  H.hold(REPO)


@pytest.mark.parametrize("backend, fault, want", [
    ("tpu", None, 1.0), ("cpu", None, 0.0),
    # The check's control with the coefficients in bfloat16 runs the mixing
    # in jax.numpy, and the counter says so.
    ("tpu", "mhc_bf16", 0.0)])
def test_the_program_leaves_the_kernel_share_at_the_cells_shapes(
    monkeypatch, backend, fault, want):
  from kf_benchmarks_tpu import params as params_lib
  from kf_benchmarks_tpu.models import mla_moe_lm
  monkeypatch.setattr(jax, "default_backend", lambda: backend)
  if fault:
    controls = importlib.util.spec_from_file_location(
        "_lm_controls", os.path.join(REPO, "experiments",
                                     "lm_precision_control.py"))
    module = importlib.util.module_from_spec(controls)
    controls.loader.exec_module(module)
    module.plant(fault, monkeypatch.setattr)
  kwargs = harness.job_kwargs(spec.load_cell(REPO, H.XING), seed=1,
                              seconds=10)
  model = mla_moe_lm.MLAMoELMModel(params_lib.make_params(**kwargs))
  model.set_batch_size(kwargs["batch_size"])
  stats = {"mhc": model.mhc_stats(jnp.bfloat16)}
  assert stats["mhc"]["streams"] == 4 and stats["mhc"]["sublayers"] == 12
  assert _read(stats) == want
  # float32 streams are no streams the kernels take.
  assert _read({"mhc": model.mhc_stats(jnp.float32)}) == 0.0
