"""The factor data plane of the mean gradient (parallel/kungfu.py
``factor_mean_dot``; models/builder.py ``affine``; the one predicate in
train_step.make_step_fns).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: the shape rule on a table of (n, B, K, N, dtypes), the
    three vgg16 dense layers and resnet50's classifier among them.
  * numerical equivalence on the 8-device mesh: a model with one
    engaging ``affine``, one that does not engage and a convolution,
    under KungFu sync_sgd, against the same step with the rule held
    shut (the all-reduce of the products): gradients equal to f32
    summation order with f32 compute and inside a stated bound with
    bf16, replicas bit-identical after 3 steps, first-step loss equal.
  * compiled-HLO structure: no all-reduce with the engaging kernel's
    shape, its two all-gathers under ``exchange``, the convolution and
    the biases still all-reduced.
  * every mode that must NOT engage: counter 0, and the step lowers to
    the program the tree had BEFORE the mechanism existed (sha256 of
    the lowered text, pinned in tests/golden_contracts/
    factor_exchange_off.json; regenerate after an intentional change to
    the step with ``python tests/test_factor_exchange.py --write``).
"""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import train_step as train_step_lib
from kf_benchmarks_tpu.models.model import CNNModel
from kf_benchmarks_tpu.parallel import strategies
from kf_benchmarks_tpu.parallel.mesh import build_mesh

N_REPLICAS = 8
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden_contracts", "factor_exchange_off.json")


# -- (d) the shape rule --------------------------------------------------------

BF16, F32 = jnp.bfloat16, jnp.float32

SHAPE_RULE_TABLE = [
    # id, n, B, K, N, compute, param, engages
    ("vgg16_fc6_4x64", 4, 64, 25088, 4096, BF16, F32, True),
    ("vgg16_fc7_4x64", 4, 64, 4096, 4096, BF16, F32, True),
    ("vgg16_fc8_4x64", 4, 64, 4096, 1001, BF16, F32, True),
    ("resnet50_classifier_4x256", 4, 256, 2048, 1001, BF16, F32, False),
    ("one_replica", 1, 64, 25088, 4096, BF16, F32, False),
    # f32 compute doubles the gathered bytes: fc8 at 4 x 64 no longer
    # passes (5.2 MB x 4 against 16.4), fc6 still does.
    ("vgg16_fc8_f32_compute", 4, 64, 4096, 1001, F32, F32, False),
    ("vgg16_fc6_f32_compute", 4, 64, 25088, 4096, F32, F32, True),
    # exactly a quarter passes, one row more does not (K = N = 2048,
    # f32 / f32: product 2048 * 2048, gathered n*B * 4096).
    ("exact_quarter", 4, 64, 2048, 2048, F32, F32, True),
    ("one_row_over_a_quarter", 5, 52, 2048, 2048, F32, F32, False),
    # the compute bound: a global batch above FACTOR_MAX_GLOBAL_BATCH
    # keeps the all-reduce however large the kernel is.
    ("global_batch_2048", 32, 64, 65536, 65536, BF16, F32, True),
    ("global_batch_2049", 683, 3, 65536, 65536, BF16, F32, False),
    # trivial's classifier on the audit's 8 x 4 mesh (the goldens).
    ("trivial_classifier_8x4", 8, 4, 4096, 1001, F32, F32, True),
    ("trivial_affine1_8x4", 8, 4, 154587, 1, F32, F32, False),
]


@pytest.mark.parametrize(
    "n,batch,k,n_out,compute,param,engages",
    [row[1:] for row in SHAPE_RULE_TABLE],
    ids=[row[0] for row in SHAPE_RULE_TABLE])
def test_shape_rule(n, batch, k, n_out, compute, param, engages):
  from kf_benchmarks_tpu.parallel import kungfu
  assert kungfu.factors_beat_product(n, batch, k, n_out, compute,
                                     param) is engages


def test_counter_bytes_of_the_vgg16_cell():
  """3 layers, 494.6 MB kept off the all-reduce, 21.7 MB gathered: the
  four-chip cell's reading (ISSUE 25), from shapes alone."""
  from kf_benchmarks_tpu.parallel import kungfu
  plan = kungfu.FactorExchange("replica", 4)
  for i, (k, n_out) in enumerate([(25088, 4096), (4096, 4096),
                                  (4096, 1001)]):
    assert plan.admits(64, k, n_out, BF16, F32)
    plan.claim((f"affine{i}", "kernel"), 64, k, n_out, BF16, F32)
  c = plan.counters()
  assert c["layers"] == 3
  assert round(c["bytes_off_allreduce"] / 1e6, 1) == 494.6
  assert round(c["bytes_gathered"] / 1e6, 1) == 21.7


# -- the model of (a) and (b) --------------------------------------------------

# 8 replicas x 2 images of 8x8x3. conv0 [3,3,3,4] keeps 8x8, so affine0
# is [256, 256]: gathered 16 * 512 f32 = 32 KiB against 256 KiB, engages
# (f32 and bf16). The classifier [256, 10] does not (16 * 266 elements
# against 2,560).
BATCH = 2
NCLASS = 10
ENGAGING = ("affine0", "kernel")


class _Net(CNNModel):

  def __init__(self, params=None):
    super().__init__("factor_net", 8, BATCH, 0.05, params=params)

  def add_inference(self, cnn):
    cnn.conv(4, 3, 3)
    cnn.affine(256)


def _step(compute_dtype=jnp.float32, **overrides):
  kw = dict(model="trivial", device="cpu", num_devices=N_REPLICAS,
            batch_size=BATCH, variable_update="kungfu",
            kungfu_option="sync_sgd", weight_decay=1e-4)
  kw.update(overrides)
  p = params_lib.make_params(**kw)
  model = _Net(params=p)
  module = model.make_module(NCLASS, True, dtype=compute_dtype)
  mesh = build_mesh(N_REPLICAS, "cpu")
  # Plain SGD at rate 1: params before - params after IS the gradient.
  return train_step_lib.make_step_fns(
      model, module, module, strategies.get_strategy(p), optax.sgd(1.0),
      lambda s: jnp.float32(1.0), p, mesh, compute_dtype=compute_dtype)


def _batch():
  r1, r2 = jax.random.split(jax.random.PRNGKey(7))
  x = jax.random.normal(r1, (N_REPLICAS * BATCH, 8, 8, 3), jnp.float32)
  y = jax.random.randint(r2, (N_REPLICAS * BATCH,), 0, NCLASS)
  return x, y


def _run(fns, steps):
  init_state, train_step = fns[0], fns[1]
  x, y = _batch()
  state = init_state(jax.random.PRNGKey(0), x[:1])
  before = jax.tree.map(np.asarray, state.params)
  losses = []
  for _ in range(steps):
    state, metrics = train_step(state, x, y)
    losses.append(float(metrics["total_loss"]))
  return before, jax.tree.map(np.asarray, state.params), losses


@pytest.fixture
def rule_shut(monkeypatch):
  """The same tree with the shape rule answering no: every leaf goes
  through the all-reduce of the products, as before the mechanism."""
  from kf_benchmarks_tpu.parallel import kungfu
  return lambda: monkeypatch.setattr(
      kungfu, "factors_beat_product", lambda *a, **k: False)


def _named(tree):
  return {tuple(k.key for k in path): leaf for path, leaf
          in jax.tree_util.tree_flatten_with_path(tree)[0]}


# bf16 compute: the all-reduce path rounds each replica's product to
# bf16 (8 bits of mantissa: 2^-9 relative) before the f32 mean; the
# factor path accumulates the global product in f32 and rounds nothing.
# The two differ by at most a bf16 rounding of the largest product.
BF16_BOUND = 2.0 ** -8


@pytest.mark.parametrize("compute_dtype,bound", [
    (jnp.float32, 2e-6), (jnp.bfloat16, BF16_BOUND)],
    ids=["f32", "bf16"])
def test_gradients_equal_the_allreduce_path(rule_shut, compute_dtype, bound):
  """(a) One SGD step at rate 1: the applied gradient of EVERY leaf
  (the engaging kernel, its bias, the classifier, the convolution)
  equals the all-reduce path's, relative to the leaf's largest entry;
  the first-step loss is the same number."""
  before_f, after_f, loss_f = _run(_step(compute_dtype), 1)
  rule_shut()
  before_p, after_p, loss_p = _run(_step(compute_dtype), 1)
  assert loss_f[0] == loss_p[0]
  b_f, a_f, b_p, a_p = map(_named, (before_f, after_f, before_p, after_p))
  assert ENGAGING in b_f and len(b_f) == 6
  for name in b_f:
    np.testing.assert_array_equal(b_f[name], b_p[name])
    g_f, g_p = b_f[name] - a_f[name], b_p[name] - a_p[name]
    scale = np.abs(g_p).max()
    assert scale > 0, name
    assert np.abs(g_f - g_p).max() <= bound * scale, (
        name, np.abs(g_f - g_p).max() / scale)


def test_replicas_bit_identical_after_three_steps():
  """(a) Sync SGD's contract under the factor plane: every replica
  holds the same bits after 3 steps (the gathered factors and the
  order of the global product are the same on every replica)."""
  _, after, losses = _run(_step(), 3)
  assert len(set(losses)) == 3 and all(np.isfinite(losses))
  for name, leaf in _named(after).items():
    assert leaf.shape[0] == N_REPLICAS
    assert (leaf == leaf[:1]).all(), name


def test_counter_and_tree_of_the_engaging_step():
  """One layer claimed, named by its parameter path; the parameter tree
  keeps nn.Dense's names and shapes."""
  from kf_benchmarks_tpu import tracing
  with tracing.session() as trace:
    fns = _step()
    assert trace.static("factor_exchange")["layers"] == 0
    before, _, _ = _run(fns, 1)
    assert trace.static("factor_exchange") == {
        "layers": 1, "bytes_off_allreduce": 256 * 256 * 4,
        "bytes_gathered": N_REPLICAS * BATCH * 512 * 4}
  shapes = {k: v.shape[1:] for k, v in _named(before).items()}
  assert shapes == {
      ("conv0", "kernel"): (3, 3, 3, 4), ("conv0", "bias"): (4,),
      ("affine0", "kernel"): (256, 256), ("affine0", "bias"): (256,),
      ("affine1", "kernel"): (256, NCLASS), ("affine1", "bias"): (NCLASS,)}


# -- (b) compiled-HLO structure ------------------------------------------------

def _compiled_hlo(fns):
  from kf_benchmarks_tpu.analysis.contracts import compile_for_audit
  init_state, train_step = fns[0], fns[1]
  x, y = _batch()
  state = init_state(jax.random.PRNGKey(0), x[:1])
  return compile_for_audit(train_step.lower(state, x, y)).as_text()


def _collectives(hlo, kind):
  """[(result type, op_name)] of every ``kind`` instruction."""
  pat = re.compile(r"=\s+(\S+)\s+" + kind + r"(?:-start)?\(.*?"
                   r'op_name="([^"]*)"')
  return [m.groups() for m in map(pat.search, hlo.splitlines()) if m]


def test_hlo_exchanges_the_factors_not_the_product(rule_shut):
  hlo = _compiled_hlo(_step())
  reduced = [t.split("{")[0] for t, _ in _collectives(hlo, "all-reduce")]
  # The engaging kernel's product is not all-reduced ...
  assert "f32[256,256]" not in reduced
  # ... the convolution, the three biases and the classifier still are.
  for shape in ("f32[3,3,3,4]", "f32[4]", "f32[256]", "f32[10]",
                "f32[256,10]"):
    assert shape in reduced, (shape, reduced)
  # Its two factors are all-gathered, under the exchange scope, inside
  # the backward pass of affine0.
  gathers = _collectives(hlo, "all-gather")
  assert sorted(t.split("{")[0] for t, _ in gathers) == [
      "f32[16,256]", "f32[16,256]"]
  for _, op_name in gathers:
    assert "exchange" in op_name and "affine0" in op_name, op_name
    assert "transpose(jvp(forward))" in op_name, op_name
  # Held shut, the same model all-reduces the product and gathers nothing.
  rule_shut()
  hlo = _compiled_hlo(_step())
  assert "f32[256,256]" in [
      t.split("{")[0] for t, _ in _collectives(hlo, "all-reduce")]
  assert not _collectives(hlo, "all-gather")


# -- (c) every mode that must NOT engage ---------------------------------------

# make_params overrides on top of trivial / batch 4 / 8 CPU devices,
# whose classifier [4096, 1001] engages under every plain-mean strategy
# (SHAPE_RULE_TABLE). "program": which of the step functions the mode
# dispatches.
MOMENTUM = dict(optimizer="momentum")
MUST_NOT_ENGAGE = {
    "one_chip_kungfu_sync": dict(num_devices=1, variable_update="kungfu"),
    "one_chip_replicated": dict(num_devices=1),
    "independent": dict(variable_update="independent"),
    "kungfu_async_sgd": dict(variable_update="kungfu",
                             kungfu_option="async_sgd"),
    "kungfu_sma": dict(variable_update="kungfu", kungfu_option="sma"),
    "async_ps_sequential_apply": dict(variable_update="parameter_server",
                                      cross_replica_sync=False, **MOMENTUM),
    "async_ps_sgd_sum": dict(variable_update="parameter_server",
                             cross_replica_sync=False),
    "reducer_spec_planner": dict(all_reduce_spec="psum"),
    "reducer_repacking": dict(gradient_repacking=2),
    "reducer_small_grad_aggregation": dict(
        agg_small_grads_max_bytes=1 << 20, agg_small_grads_max_group=10),
    "reducer_hierarchical_copy": dict(hierarchical_copy=True),
    "reducer_compact_wire": dict(gradient_repacking=2,
                                 compact_gradient_transfer_f32=True),
    "zero_sharded_state": dict(shard_optimizer_state=True, **MOMENTUM),
    "fsdp_sharded_params": dict(shard_optimizer_state=True,
                                shard_params=True, **MOMENTUM),
    "num_grad_accum_2": dict(num_grad_accum=2),
    "track_grad_noise_scale": dict(track_grad_noise_scale=True),
    "model_axis_2": dict(mesh_shape="4x2", shard_optimizer_state=True,
                         **MOMENTUM),
    "forward_only": dict(forward_only=True, program="eval_step"),
    "eval": dict(eval=True, program="eval_step"),
}


def lowered_text(overrides):
  """The lowered (StableHLO) text of the step a run with ``overrides``
  dispatches, built as the runtime builds it; nothing executes. No
  locations, no metadata: the program alone. Runs on a tree without the
  mechanism too (the goldens were written there)."""
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu.analysis import contracts
  from kf_benchmarks_tpu.parallel import mesh as mesh_lib
  kw = dict(model="trivial", batch_size=4, device="cpu",
            num_devices=N_REPLICAS, num_batches=2)
  kw.update(overrides)
  program = kw.pop("program", "train_step")
  bench = benchmark.BenchmarkCNN(params_lib.make_params(**kw))
  if program == "train_step":
    return contracts.lower_step_program(bench)[1].as_text()
  fns = bench._build()
  shapes = bench.model.get_input_shapes("train")
  dtypes = bench.model.get_input_data_types("train")
  state = fns[0].eval_shape(jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct(tuple(shapes[0]), dtypes[0]))
  batch = [jax.ShapeDtypeStruct(
      (s[0] * bench.num_devices,) + tuple(s[1:]), d,
      sharding=mesh_lib.batch_sharding(bench.mesh))
           for s, d in zip(shapes[:2], dtypes[:2])]
  return fns[2].lower(state, *batch).as_text()


def _sha(text):
  return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("mode", sorted(MUST_NOT_ENGAGE))
def test_must_not_engage(mode):
  """Counter 0, and the program is the one the tree lowered to before
  the factor plane existed, byte for byte."""
  from kf_benchmarks_tpu import tracing
  with open(GOLDEN) as f:
    golden = json.load(f)["sha256"]
  with tracing.session() as trace:
    text = lowered_text(MUST_NOT_ENGAGE[mode])
  counters = trace.static("factor_exchange")
  assert counters == {"layers": 0, "bytes_off_allreduce": 0,
                      "bytes_gathered": 0}
  assert _sha(text) == golden[mode], (
      f"{mode}: the step program changed. If that was intended, "
      "regenerate with `python tests/test_factor_exchange.py --write`")


@pytest.mark.parametrize("overrides", [
    dict(variable_update="kungfu"), dict(variable_update="replicated"),
    dict(variable_update="parameter_server"),
    dict(variable_update="horovod"),
    dict(variable_update="collective_all_reduce"),
    dict(variable_update="replicated", mesh_shape="8x1"),
], ids=lambda o: "-".join(str(v) for v in o.values()))
def test_plain_mean_strategies_engage(overrides):
  """The positive control of the parametrised test above: under every
  strategy that reduces by the plain mean, the same model's classifier
  takes the factor plane (one layer, 16.4 MB off the all-reduce) and
  its two gathers are in the program."""
  from kf_benchmarks_tpu import tracing
  with tracing.session() as trace:
    text = lowered_text(overrides)
  counters = trace.static("factor_exchange")
  assert counters == {"layers": 1, "bytes_off_allreduce": 4096 * 1001 * 4,
                      "bytes_gathered": 8 * 4 * (4096 + 1001) * 4}
  assert text.count("stablehlo.all_gather") == 2


if __name__ == "__main__":
  # Writes the goldens of test_must_not_engage from the tree this file
  # is run in (XLA_FLAGS as tests/conftest.py sets them).
  assert sys.argv[1:2] == ["--write"], "usage: --write [path]"
  os.environ.setdefault(
      "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
  os.environ.setdefault("JAX_PLATFORMS", "cpu")
  out = sys.argv[2] if len(sys.argv) > 2 else GOLDEN
  shas = {m: _sha(lowered_text(o)) for m, o in MUST_NOT_ENGAGE.items()}
  with open(out, "w") as f:
    json.dump({"what": "sha256 of the lowered step program of every mode "
               "in which the factor data plane must not engage "
               "(tests/test_factor_exchange.py MUST_NOT_ENGAGE)",
               "sha256": shas}, f, indent=1, sort_keys=True)
    f.write("\n")
  print(json.dumps(shas, indent=1))
