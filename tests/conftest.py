"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; sharding/collective tests run on
XLA's host platform with 8 virtual devices, mirroring how the reference
tests distributed modes without a real cluster (ref:
benchmark_cnn_distributed_test.py spawns localhost processes; we use
virtual devices instead -- SURVEY 7.1 test plan).

XLA_FLAGS must carry the device count before jax initialises the CPU
client; the platform is then selected through jax.config (the tier-1
command also sets JAX_PLATFORMS=cpu; both agree).
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
  os.environ["XLA_FLAGS"] = (
      xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (must come after XLA_FLAGS is set)

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
  config.addinivalue_line("markers", "slow: long-running test")
  config.addinivalue_line(
      "markers", "distributed: spawns subprocess workers (also selectable "
      "with -m distributed; cheap ones run in the default suite)")
