"""The state-space scan's two Pallas kernels (``ops/ssd.py``, PR 40),
interpreted on the CPU: both bodies against the ``"xla"`` form of the
same function (einsums under autodiff) and against the SEQUENTIAL
recurrence of the benchmark's reference, at a small shape that tiles, at
a head that is a whole lane tile, and at one group of the nemotron cell's
(8 heads of 64, state 128, chunks of 128, three of them, two sequences,
bfloat16 operands); the skip ``D x`` as the
kernel's epilogue; a state that has to survive two links; steps so large
that an unmasked decay table would overflow; and the plan and the stats
that say which form runs.

TOLERANCE. With float32 operands the kernel and the einsums differ in
the order of sums alone (the carry as links, not as one product): 3e-4
of the tensor's largest magnitude, as tests/test_nemotron_h_lm.py. With
bfloat16 operands both round the same operands at the same places and
differ in the cotangents' roundings (the einsums' transposes round a
cotangent to bfloat16 where the kernel keeps float32): 2e-2.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.ops import ssd
from test_nemotron_h_lm import _sequential, close

SHAPES = {
    # (batch, positions, heads, head size, groups, state, chunk, operands)
    "small": (1, 256, 4, 64, 2, 128, 128, jnp.float32),
    "whole_tile_head": (1, 256, 2, 128, 2, 128, 128, jnp.float32),
    "cell_group": (2, 384, 8, 64, 1, 128, 128, jnp.bfloat16),
}
RTOL = {jnp.float32: 3e-4, jnp.bfloat16: 2e-2}
NAMES = ("x", "dt", "a", "B", "C")


def _inputs(shape, seed=0, step_shift=-2.0):
  batch, seq, heads, p, groups, n, _, dtype = SHAPES[shape]
  k = jax.random.split(jax.random.PRNGKey(seed), 5)
  return (jax.random.normal(k[0], (batch, seq, heads, p)).astype(dtype),
          jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) +
                          step_shift),
          -jnp.exp(jax.random.normal(k[2], (heads,))),
          jax.random.normal(k[3], (batch, seq, groups, n)).astype(dtype),
          jax.random.normal(k[4], (batch, seq, groups, n)).astype(dtype))


def _plan(shape, implementation):
  _, seq, _, _, _, _, chunk, _ = SHAPES[shape]
  return ssd.ScanPlan(implementation, chunk, seq // chunk)


def _kernel(shape):
  return lambda *v, skip=None: ssd._pallas_scan(
      *v, _plan(shape, "pallas"), skip, interpret=True)


def _einsums(shape):
  return lambda *v: ssd._xla_scan(*v, _plan(shape, "xla"), jnp.float32)


@functools.cache
def _outputs(shape):
  """y of the kernel, of the einsums and of the recurrence, and the
  gradients of one scalar of y through the first two: once a shape."""
  args = _inputs(shape)
  cost = lambda fn: lambda *v: jnp.sum(jnp.sin(fn(*v)))
  grads = lambda fn: dict(zip(NAMES, jax.grad(cost(fn), argnums=range(5))(
      *args)))
  f32 = lambda v: v.astype(jnp.float32)
  return {"kernel": _kernel(shape)(*args), "einsums": _einsums(shape)(*args),
          "sequential": _sequential(*map(f32, args)),
          "kernel_grads": grads(_kernel(shape)),
          "einsums_grads": grads(_einsums(shape))}


@pytest.mark.parametrize("other", ["einsums", "sequential"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_forward_kernel_is_the_scan(shape, other):
  out = _outputs(shape)
  assert out["kernel"].dtype == jnp.float32
  close(out["kernel"], out[other], f"y against the {other}",
        RTOL[SHAPES[shape][-1]])


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_backward_kernel_is_the_einsums_gradient(shape, name):
  out = _outputs(shape)
  got, want = out["kernel_grads"][name], out["einsums_grads"][name]
  assert got.dtype == want.dtype and got.shape == want.shape
  close(got.astype(jnp.float32), want.astype(jnp.float32),
        f"gradient of {name}", RTOL[SHAPES[shape][-1]])


@pytest.mark.parametrize("quantity", ["y", "x", "skip"])
@pytest.mark.parametrize("shape", ["small", "cell_group"])
def test_the_skip_is_the_kernels_epilogue(shape, quantity):
  # ``skip[h] x`` added inside the forward kernel, its two gradients
  # written by the backward: against the einsums with the term outside.
  args = _inputs(shape)
  skip = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(7),
                                       (SHAPES[shape][2],))
  outside = lambda skip, *v: _einsums(shape)(*v) + skip[:, None] * v[0].astype(
      jnp.float32)
  inside = lambda skip, *v: _kernel(shape)(*v, skip=skip)
  rtol = RTOL[SHAPES[shape][-1]]
  if quantity == "y":
    return close(inside(skip, *args), outside(skip, *args), "y with the skip",
                 rtol)
  grad = lambda fn: jax.grad(lambda *v: jnp.sum(jnp.sin(fn(*v))),
                             argnums=(0, 1))(skip, *args)
  at = ("skip", "x").index(quantity)
  got, want = grad(inside)[at], grad(outside)[at]
  assert got.dtype == want.dtype and got.shape == want.shape
  close(got.astype(jnp.float32), want.astype(jnp.float32),
        f"gradient of {quantity} with the skip", rtol)


def test_a_state_survives_two_links_through_the_kernel():
  # tests/test_nemotron_h_lm.py::test_scan_state_decays_and_carries
  # through the kernel: ONE position's input, scaled; what it adds to
  # later positions of its own chunk, of the next and two chunks on is
  # the recurrence's, and nothing changes before it.
  shape = "cell_group"
  args = tuple(v.astype(jnp.float32) for v in _inputs(shape))
  # Slow heads, so that something is left two chunks on.
  args = (args[0], args[1] * 0.05) + args[2:]
  bumped = (args[0].at[:, 5].multiply(64.0),) + args[1:]
  rows = np.asarray([6, 127, 128 + 2, 2 * 128 + 1])
  run = _kernel(shape)
  got = run(*bumped)[:, rows] - run(*args)[:, rows]
  want = _sequential(*bumped)[:, rows] - _sequential(*args)[:, rows]
  assert np.abs(want[:, -1]).max() > 1e-3 * np.abs(want[:, 0]).max()
  close(got, want, "what position 5 adds later")
  assert np.array_equal(run(*bumped)[:, :5], run(*args)[:, :5])


def test_the_masked_triangle_never_overflows():
  # Steps near 12 under a = -e^(+-1): a chunk's running sum passes
  # -1,000, so exp(run_s - run_t) ABOVE the diagonal is inf in float32.
  # Masked before the exponential, forward and backward, nothing of it
  # reaches y or a gradient.
  shape = "small"
  args = _inputs(shape, step_shift=12.0)
  total = float(jnp.min(jnp.sum((args[1] * args[2]).reshape(
      1, 2, 128, -1), axis=2)))
  assert total < -200
  with np.errstate(over="ignore"):
    assert np.isinf(np.exp(np.float32(-total)))
  y = _kernel(shape)(*args)
  assert np.isfinite(np.asarray(y)).all()
  close(y, _einsums(shape)(*args), "y under large steps")
  grads = jax.grad(lambda *v: jnp.sum(jnp.sin(_kernel(shape)(*v))),
                   argnums=range(5))(*args)
  for name, g in zip(NAMES, grads):
    assert np.isfinite(np.asarray(g)).all(), name


# -- the plan and the stats ----------------------------------------------------

CELL = dict(seq_len=8192, heads=64, groups=8, chunk=128, head_dim=64,
            state=128)


def test_plan_takes_the_kernels_at_the_cells_shapes_on_a_tpu(monkeypatch):
  assert ssd.scan_plan(**CELL) == ssd.ScanPlan("xla", 128, 64)   # a CPU
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert ssd.scan_plan(**CELL) == ssd.ScanPlan("pallas", 128, 64)
  for shape in SHAPES.values():
    _, seq, heads, p, groups, n, chunk, _ = shape
    assert ssd.scan_plan(seq, heads, groups, chunk, p,
                         n).implementation == "pallas", shape


@pytest.mark.parametrize("why, change", [
    ("the lower-precision control", dict(scan_dtype=jnp.bfloat16)),
    ("a chunk that is no whole lane tile", dict(chunk=64)),
    ("a state that is no whole lane tile", dict(state=64)),
    ("a group narrower than a lane tile", dict(heads=8, head_dim=8)),
    ("a head that is no whole share of a lane tile", dict(head_dim=48)),
    ("more heads a group than a body writes out", dict(groups=2)),
    ("the tests' tiny stack", dict(seq_len=32, heads=8, groups=2, chunk=8,
                                   head_dim=4, state=16)),
    ("today's callers, shapes unsaid", dict(head_dim=0, state=0)),
])
def test_plan_falls_back_to_the_einsums_silently(monkeypatch, why, change):
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  plan = ssd.scan_plan(**dict(CELL, **change))
  assert plan.implementation == "xla", why
  assert plan.chunks * plan.chunk == dict(CELL, **change)["seq_len"]


def test_plan_refuses_what_it_refused_before(monkeypatch):
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with pytest.raises(ValueError, match="takes whole chunks"):
    ssd.scan_plan(**dict(CELL, seq_len=8192 + 64))
  with pytest.raises(ValueError, match="do not divide"):
    ssd.scan_plan(**dict(CELL, groups=7))


@pytest.mark.parametrize("backend, implementation, share", [
    ("tpu", "pallas", 1.0), ("cpu", "xla", 0.0)])
def test_stats_say_which_form_ran(monkeypatch, backend, implementation,
                                  share):
  monkeypatch.setattr(jax, "default_backend", lambda: backend)
  stats = ssd.scan_stats(1, 8192, 64, 64, 8, 128, 128, 4, jnp.bfloat16)
  assert stats["implementation"] == implementation
  assert stats["kernel_share"] == share
  assert stats["carried_state_bytes_per_layer"] == 64 * 64 * 64 * 128 * 4
  assert stats["residual_bytes_per_layer"] == 8192 * 10304 * 2


def test_scan_dispatches_on_the_plan(monkeypatch):
  # ``ssd_scan`` hands the kernels what the plan says and the einsums the
  # rest; off a TPU every shape is the einsums'.
  args = _inputs("small")
  seen = []
  monkeypatch.setattr(ssd, "_pallas_scan", lambda *a, **k: seen.append(
      "pallas") or jnp.zeros(args[0].shape, jnp.float32))
  ssd.ssd_scan(*args, 128)
  assert seen == []
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  ssd.ssd_scan(*args, 128)
  ssd.ssd_scan(*args, 128, jnp.bfloat16)
  ssd.ssd_scan(*args, 64)
  assert seen == ["pallas"]
