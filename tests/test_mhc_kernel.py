"""The hyper-connections' Pallas kernels (``ops/mhc.py``),
interpreted on the CPU: ``mhc_pre`` and ``mhc_post`` through the kernels
against their ``"xla"`` form (``jax.numpy`` under autodiff, the product
with phi at HIGHEST) on the same inputs, at 4 streams of 256 and of 384
lanes over two blocks of 128 tokens, bfloat16 streams: the sublayer's
input h, the three coefficients, the mixed streams, and each function's
pullback of the same cotangents (the streams', phi's, the biases' and
the scales' gradients through ``mhc_pre``; the streams', the
coefficients' and the sublayer output's through ``mhc_post``); a case
where the clamp of the logits engages; the exactness of phi's three
bfloat16 terms; the plan and the stats that say which form runs; and
the family's tiny preset, its stack scanned and rematerialised, through
the kernels.

TOLERANCE. What is float32 in both forms (the coefficients and their
inputs' gradients) differs in the order of sums alone (the statistic
over the lanes, the product against phi's three terms against
HIGHEST's six passes, the half steps' divisions; 2e-5 where the clamp
puts exp(30) into the iterations): 5e-5 of the largest magnitude. What
is bfloat16 (h, the mixed streams, the streams' and the sublayer
output's gradients) is rounded at the same places in both and may
differ by a unit of the last place where the float32 sums before the
rounding differ in their last bits: 2e-2, as
tests/test_ssd_scan_kernel.py. Chained through a sublayer, such units
move the float32 gradients by up to 3% in both forms alike (each as far
from a float32 program as the other): hence one function at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu.ops import mhc

MIXING = mhc.Mixing(20, 1e-6, (-30.0, 30.0), 1e-6)
BLOCK = 128
CASES = {
    # (batch, tokens, streams, width, scale of the logits)
    "width_256": (2, 128, 4, 256, 1.0),
    "width_384": (1, 256, 4, 384, 1.0),
    # Logits far past +-30 for most tokens: the clamp's mask decides.
    "clamped": (2, 128, 4, 256, 400.0),
}
PRE_IN = ("streams", "phi", "bias", "alpha")
PRE_OUT = ("h", "h_pre", "h_post", "h_res")
POST_IN = ("streams", "h_res", "h_post", "y")


def _inputs(case, seed=0):
  batch, t, n, d, spread = CASES[case]
  cols = 2 * n + n * n
  k = jax.random.split(jax.random.PRNGKey(seed), 5)
  return (jax.random.normal(k[0], (batch, t, n, d)).astype(jnp.bfloat16),
          0.02 * jax.random.normal(k[1], (n * d, cols)),
          jax.random.normal(k[2], (cols,)),
          jnp.array([1.0, 1.0, spread], jnp.float32),
          jax.random.normal(k[3], (batch, t, d)).astype(jnp.bfloat16))


def _pre(label):
  if label == "kernel":
    return lambda *v: mhc._pallas_pre(*v, MIXING, mhc.H_POST_SCALE,
                                      jnp.bfloat16, BLOCK, interpret=True)

  def pre(*v):
    h_pre, h_post, h_res = mhc.coefficients(*v, MIXING, mhc.H_POST_SCALE)
    return mhc.Premixed(mhc.premix(v[0], h_pre, jnp.bfloat16), h_pre, h_post,
                        h_res, v[0], mhc.MhcPlan("xla", 0))
  return pre


def _post(label):
  if label == "kernel":
    return lambda *v: mhc._pallas_post(*v, BLOCK, interpret=True)
  return mhc.residual_mix


def _cotangents(outputs, seed):
  leaves, tree = jax.tree.flatten(outputs)
  keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
  return jax.tree.unflatten(tree, [
      jax.random.normal(k, o.shape).astype(o.dtype)
      for k, o in zip(keys, leaves)])


@functools.cache
def _outputs(case):
  """Each function's outputs and its pullback of fixed random cotangents
  through each form, on the same inputs: once a case (the cotangents of
  bfloat16 outputs are bfloat16, as autodiff hands them over; the one
  of the streams ``mhc_pre`` passes on is added to the streams')."""
  streams, phi, bias, alpha, y = _inputs(case)
  out = {}
  for label in ("kernel", "xla"):
    pre, pull = jax.vjp(jax.jit(_pre(label)), streams, phi, bias, alpha)
    out[label, "pre"] = dict(zip(PRE_OUT, pre))
    out[label, "pre_grads"] = dict(zip(PRE_IN, pull(_cotangents(pre, 1))))
  h_res, h_post = out["xla", "pre"]["h_res"], out["xla", "pre"]["h_post"]
  for label in ("kernel", "xla"):
    mixed, pull = jax.vjp(jax.jit(_post(label)), streams, h_res, h_post, y)
    out[label, "post"] = {"mixed": mixed}
    out[label, "post_grads"] = dict(zip(POST_IN, pull(_cotangents(mixed,
                                                                  2))))
  return out


def _err(got, want):
  got, want = (np.asarray(v, np.float64) for v in (got, want))
  return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _limit(value):
  return 2e-2 if value.dtype == jnp.bfloat16 else 5e-5


@pytest.mark.parametrize("name", PRE_OUT + ("mixed",))
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_give_the_xla_forms_outputs(case, name):
  which = "post" if name == "mixed" else "pre"
  got, want = _outputs(case)["kernel", which][name], _outputs(case)[
      "xla", which][name]
  assert got.dtype == want.dtype and got.shape == want.shape
  assert _err(got, want) <= _limit(want)


@pytest.mark.parametrize("name", [("pre", n) for n in PRE_IN] +
                         [("post", n) for n in POST_IN])
@pytest.mark.parametrize("case", list(CASES))
def test_backward_kernels_give_the_xla_forms_gradients(case, name):
  which, name = name
  got = _outputs(case)["kernel", which + "_grads"][name]
  want = _outputs(case)["xla", which + "_grads"][name]
  assert got.dtype == want.dtype and got.shape == want.shape
  assert _err(got, want) <= _limit(want)


def test_the_clamp_engages():
  """In the clamped case most logits lie past the clamp, where their
  gradient is zero: the scales' and the biases' gradients of H_res
  agree."""
  streams, phi, bias, alpha, _ = _inputs("clamped")
  n = streams.shape[2]
  x = streams.reshape(streams.shape[0], streams.shape[1], -1).astype(
      jnp.float32)
  z = jnp.dot(x, phi, precision="highest") * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), -1, keepdims=True) + MIXING.norm_eps)
  logits = alpha[2] * z[..., 2 * n:] + bias[2 * n:]
  assert float(jnp.mean(jnp.abs(logits) > 30)) > 0.5
  grads = _outputs("clamped")
  got, want = grads["kernel", "pre_grads"], grads["xla", "pre_grads"]
  assert _err(got["alpha"], want["alpha"]) < 5e-5
  assert _err(got["bias"][2 * n:], want["bias"][2 * n:]) < 5e-5


def test_phi_splits_exactly_into_three_bfloat16_terms():
  phi = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (1024, 24))
  phi = phi.at[0, :3].set(jnp.array([1e-30, -3.0e4, 1.0 / 3.0]))
  f32 = lambda v: v.astype(jnp.float32)
  # Eagerly and as one compiled program: the terms are cut by masks, so a
  # compiler that skips a round trip through bfloat16 changes nothing.
  for split in (mhc.split3, jax.jit(mhc.split3)):
    hi, mid, lo = split(phi)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    np.testing.assert_array_equal(f32(hi) + f32(mid) + f32(lo), phi)
  # Against bfloat16 streams the three products are exact and summed in
  # float32: the float32 product at HIGHEST up to the order of its sums.
  x = jax.random.normal(jax.random.PRNGKey(4), (256, 1024)).astype(
      jnp.bfloat16)
  three = sum(jnp.dot(x, t, preferred_element_type=jnp.float32)
              for t in (hi, mid, lo))
  want = np.dot(np.asarray(x, np.float64), np.asarray(phi, np.float64))
  highest = jnp.dot(f32(x), phi, precision="highest")
  assert _err(three, want) < 1e-6
  assert _err(highest, want) < 1e-6


@pytest.fixture
def on_tpu(monkeypatch):
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_plan_takes_the_kernels_at_the_cells_shapes_on_a_tpu(on_tpu):
  assert mhc.mhc_plan(4096, 4, 3584, jnp.bfloat16) == mhc.MhcPlan(
      "pallas", 128)
  assert mhc.mhc_stats(4096, 4, 3584, jnp.bfloat16) == {
      "implementation": "pallas", "token_block": 128, "kernel_share": 1.0}
  # The stats state what the bfloat16 control runs, as ``mhc_pre`` plans
  # it.
  assert mhc.mhc_stats(4096, 4, 3584, jnp.bfloat16, jnp.bfloat16) == {
      "implementation": "xla", "token_block": 0, "kernel_share": 0.0}


@pytest.mark.parametrize("why, args", [
    ("a width that is no whole number of lane tiles",
     (4096, 4, 3500, jnp.bfloat16)),
    ("float32 streams", (4096, 4, 3584, jnp.float32)),
    ("the bfloat16 control of the coefficients",
     (4096, 4, 3584, jnp.bfloat16, jnp.bfloat16)),
    ("tokens that are no whole number of blocks",
     (4000, 4, 3584, jnp.bfloat16)),
    ("more streams than one transpose holds", (4096, 5, 3584, jnp.bfloat16)),
])
def test_plan_falls_back_to_the_xla_form(on_tpu, why, args):
  assert mhc.mhc_plan(*args) == mhc.MhcPlan("xla", 0), why


def test_plan_and_stats_off_a_tpu():
  assert mhc.mhc_plan(4096, 4, 3584, jnp.bfloat16).implementation == "xla"
  assert mhc.mhc_stats(4096, 4, 3584, jnp.bfloat16) == {
      "implementation": "xla", "token_block": 0, "kernel_share": 0.0}


def test_the_functions_dispatch_on_the_plan(on_tpu, monkeypatch):
  """On a TPU ``mhc_pre`` takes the kernels and ``mhc_post`` the plan
  ``mhc_pre`` hands it; the bfloat16 control's ``mhc_pre`` takes
  ``jax.numpy``, and so its ``mhc_post`` does too."""
  streams, phi, bias, alpha, y = _inputs("width_256")
  taken = []
  monkeypatch.setattr(mhc, "_pallas_pre", lambda *a: taken.append("pre"))
  monkeypatch.setattr(mhc, "_pallas_post", lambda *a: taken.append("post"))
  mhc.mhc_pre(streams, phi, bias, alpha, MIXING, jnp.bfloat16)
  assert taken == ["pre"]
  control = MIXING._replace(coef_dtype=jnp.bfloat16)
  pre = mhc.mhc_pre(streams, phi, bias, alpha, control, jnp.bfloat16)
  assert pre.plan == mhc.MhcPlan("xla", 0) and pre.h.shape == y.shape
  assert mhc.mhc_post(pre, y).shape == streams.shape and taken == ["pre"]
  mhc.mhc_post(pre._replace(plan=mhc.MhcPlan("pallas", BLOCK)), y)
  assert taken == ["pre", "post"]


def test_the_model_takes_the_kernels_under_its_scan_and_remat(monkeypatch):
  """The tiny preset of the family at a width the kernels take (4
  streams of 128, one sequence of 128 tokens, bfloat16), its stack
  scanned and rematerialised as the cell's: the kernels (interpreted)
  in every sublayer, the pass-through of the streams included, give the
  ``jax.numpy`` form's loss, and its gradients to what bfloat16 leaves
  of either (the median leaf; a few leaves of the MTP block's mixing
  differ by their bfloat16 roundings in both forms alike, each as far
  from a float32 program as the other)."""
  import dataclasses
  from kf_benchmarks_tpu.models import mla_moe_lm as lm
  from kf_benchmarks_tpu.models import model as model_lib
  cfg = dataclasses.replace(lm.load_lm_config("tiny-xing", 3, 4, 1,
                                              head_shards=2), hidden_size=128)
  module = lm.MLAMoELM(cfg=cfg, dtype=jnp.bfloat16)
  tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 128), 0,
                              cfg.vocab_rows)
  variables = module.init({"params": jax.random.PRNGKey(1)}, tokens)
  model = lm.MLAMoELMModel()
  model.cfg = cfg

  def loss(params):
    (heads, _), _ = module.apply(
        {"params": params, "batch_stats": variables["batch_stats"]}, tokens,
        mutable=["batch_stats"])
    return model.loss_function(model_lib.BuildNetworkResult(
        logits=(heads, None)), jnp.roll(tokens, -1, axis=1))
  want, want_grads = jax.jit(jax.value_and_grad(loss))(variables["params"])
  taken = []
  plan = mhc.mhc_plan
  monkeypatch.setattr(mhc, "mhc_plan", lambda *a: taken.append(a) or (
      mhc.MhcPlan("pallas", BLOCK) if jnp.dtype(a[3]) == jnp.bfloat16
      else plan(*a)))
  monkeypatch.setattr(mhc, "_pallas_pre", functools.partial(
      mhc._pallas_pre, interpret=True))
  monkeypatch.setattr(mhc, "_pallas_post", functools.partial(
      mhc._pallas_post, interpret=True))
  got, got_grads = jax.jit(jax.value_and_grad(loss))(variables["params"])
  # Each hyper-connected sublayer's plan, made once by its first half
  # (the scan traces a layer once).
  assert len(taken) >= 3
  assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
  errs = [_err(g, w) for g, w in zip(jax.tree.leaves(got_grads),
                                     jax.tree.leaves(want_grads))]
  assert all(np.isfinite(errs)) and float(np.median(errs)) < 1e-2

