"""--shard_params: full FSDP (ZeRO-3) on the named 2-D mesh -- params
live as 1/n shard stacks between steps and re-assemble per builder-
layer bucket / per scanned block INSIDE the forward/backward
(train_step.py, ops/sharded.py fsdp_* layout and gather_params; the
param-sharding leg of the reference's central
variable placement, ref: variable_mgr.py:201-243, taken where the
reference never went -- SURVEY 5.8's PS server copy becomes a 1/n
shard that never re-assembles whole).

Layers, reference-style (SURVEY 7.1):
  * pure-unit: the FSDP layout laws (per-layer (n, L, k) stacks,
    whole-tree gather round-trip, the gather_params custom_vjp's
    forward re-assembly and scatter-mean backward) on the 8-device
    mesh, and the --shard_params validation matrix.
  * numerical equivalence: per-step f32 losses BIT-IDENTICAL to
    --shard_optimizer_state alone -- plain, --num_grad_accum=2, the
    4x2 model-axis mesh (tier 1), and --steps_per_dispatch=8 /
    adam-composed (slow tier); plus a small scanned-transformer
    harness driven through make_step_fns directly, so the per-block
    in-scan gather path is equivalence-pinned in tier 1 without the
    full-size LM's CPU cost.
  * program: the per-block all-gather sits INSIDE the backward scan's
    while body, no out-of-loop full-tree gather exists, and the
    compiled memory analysis shows the FSDP program's temp footprint
    below the replicated-param twin's (the PR-7 methodology).
  * checkpoint: the sharded-params layout round-trips through
    save/resume, cross-layout restores are rejected in BOTH
    directions, and the (n, L, k) reshard law holds (the 8 -> 4
    elastic rescale rides tests/test_elastic_rescale.py's harness).
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from kf_benchmarks_tpu import benchmark, checkpoint
from kf_benchmarks_tpu import params as params_lib, validation
from kf_benchmarks_tpu import train_step as train_step_lib
from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.ops import sharded as sharded_lib
from kf_benchmarks_tpu.parallel import mesh as mesh_lib
from kf_benchmarks_tpu.parallel import strategies
from kf_benchmarks_tpu.utils import log as log_util

STEP_RE = re.compile(
    r"^(\d+)\timages/sec: [\d.]+ \+/- [\d.]+ \(jitter = [\d.]+\)\t(.*)$")


def _run_and_scrape(**overrides):
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    defaults = dict(model="trivial", num_batches=8, num_warmup_batches=0,
                    device="cpu", display_every=1, batch_size=4,
                    num_devices=8, optimizer="momentum")
    defaults.update(overrides)
    p = params_lib.make_params(**defaults)
    stats = benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  return logs, stats


def _loss_columns(logs):
  return [(m.group(1), m.group(2)) for l in logs
          if (m := STEP_RE.match(l))]


def _assert_equivalent(kw_sharded_only, kw_fsdp):
  logs_a, stats_a = _run_and_scrape(**kw_sharded_only)
  logs_b, stats_b = _run_and_scrape(**kw_fsdp)
  cols_a, cols_b = _loss_columns(logs_a), _loss_columns(logs_b)
  assert cols_a, "no step lines scraped from the sharded-only run"
  assert cols_a == cols_b
  assert stats_a["last_average_loss"] == stats_b["last_average_loss"]
  return stats_a, stats_b


# -- pure-unit: validation matrix ---------------------------------------------

def test_shard_params_requires_shard_optimizer_state():
  with pytest.raises(validation.ParamError,
                     match="requires --shard_optimizer_state"):
    validation.validate_cross_flags(params_lib.make_params(
        shard_params=True))


@pytest.mark.parametrize("kw,match", [
    # The sharded exclusion matrix binds transitively through the
    # requires: staged vars / async-PS / independent / LARS all reject.
    (dict(variable_update="independent"), "replicated or parameter_server"),
    (dict(variable_update="parameter_server", cross_replica_sync=False),
     "async"),
    (dict(staged_vars=True, variable_update="parameter_server"),
     "staged_vars"),
    (dict(optimizer="lars"), "lars"),
    (dict(summary_verbosity=2, save_summaries_steps=10),
     "summary_verbosity"),
])
def test_shard_params_exclusion_matrix(kw, match):
  with pytest.raises(validation.ParamError, match=match):
    validation.validate_cross_flags(params_lib.make_params(
        shard_params=True, shard_optimizer_state=True, **kw))


def test_shard_params_valid_combinations_pass():
  for kw in [dict(),
             dict(mesh_shape="4x2"),
             dict(steps_per_dispatch=4),
             dict(num_grad_accum=2, batch_size=4),
             dict(optimizer="adam"),
             dict(reduce_bucket_mb=8),  # FSDP gather-bucket bound
             dict(elastic=True),
             dict(summary_verbosity=1, save_summaries_steps=10)]:
    validation.validate_cross_flags(params_lib.make_params(
        shard_params=True, shard_optimizer_state=True, num_devices=8,
        **kw))


def test_reduce_bucket_mb_still_needs_a_consumer():
  with pytest.raises(validation.ParamError, match="reduce_bucket_mb"):
    validation.validate_cross_flags(params_lib.make_params(
        reduce_bucket_mb=8))


# -- pure-unit: the FSDP layout laws ------------------------------------------

def test_fsdp_stacked_shards_layout():
  tree = {"dense": {"kernel": jnp.arange(10, dtype=jnp.float32)},
          "blocks": {"w": jnp.arange(24, dtype=jnp.float32).reshape(
              2, 3, 4)}}
  stacked = sharded_lib.fsdp_stacked_shards(tree, 4,
                                            scanned_prefixes=("blocks",))
  # Plain leaf: the round-11 (n, k) stack.
  assert stacked["dense"]["kernel"].shape == (4, 3)
  np.testing.assert_array_equal(
      np.asarray(stacked["dense"]["kernel"]).reshape(-1)[:10],
      np.arange(10))
  # Scanned leaf (L=2, 12 elems/layer): per-layer rows, shard dim leads.
  w = stacked["blocks"]["w"]
  assert w.shape == (4, 2, 3)  # (n, L, ceil(12/4))
  for layer in range(2):
    np.testing.assert_array_equal(
        np.asarray(w[:, layer]).reshape(-1),
        np.arange(24).reshape(2, 12)[layer])


def _shard_map_2d(fn, mesh, in_specs, out_specs):
  return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False))


@pytest.mark.parametrize("shape", [(8, 1), (4, 2)])
def test_fsdp_gather_full_roundtrip(shape):
  """stack -> local rows -> fsdp_gather_full is the identity, scanned
  and plain leaves alike, on both mesh shapes."""
  mesh = mesh_lib.build_mesh_2d(*shape, "cpu")
  tree = {"dense": jnp.arange(37, dtype=jnp.float32) * 0.5,
          "blocks": jnp.arange(42, dtype=jnp.float32).reshape(3, 14) - 7}
  stacked = sharded_lib.fsdp_stacked_shards(tree, 8, ("blocks",))

  def body(st):
    local = jax.tree.map(lambda x: jnp.squeeze(x, 0), st)
    return sharded_lib.fsdp_gather_full(local, tree, ("blocks",))

  out = _shard_map_2d(
      body, mesh,
      in_specs=({"dense": P(("batch", "model")),
                 "blocks": P(("batch", "model"))},),
      out_specs=P())(stacked)
  jax.tree.map(np.testing.assert_array_equal, out, tree)


def test_gather_params_forward_and_backward_laws():
  """The custom_vjp: forward re-assembles the bucket exactly; backward
  equals the per-leaf post-hoc scatter_mean bit-for-bit (the FSDP
  bit-identity anchor)."""
  mesh = mesh_lib.build_mesh_2d(4, 2, "cpu")
  n = 8
  leaves = {"a": jnp.arange(23, dtype=jnp.float32) * 0.25 - 2.0,
            "b": (jnp.arange(40, dtype=jnp.float32).reshape(5, 8)
                  * 0.125)}
  stacked = sharded_lib.fsdp_stacked_shards(leaves, n)
  rng = np.random.RandomState(1)
  # Per-BATCH-group cotangents, identical across the model axis (the
  # train-step invariant).
  cots = {"a": jnp.asarray(rng.randn(4, 23).astype(np.float32)),
          "b": jnp.asarray(rng.randn(4, 5, 8).astype(np.float32))}

  def body(st, ct):
    local = jax.tree.map(lambda x: jnp.squeeze(x, 0), st)
    flat, treedef = jax.tree_util.tree_flatten(local)
    spec = sharded_lib.FsdpGatherSpec(
        batch_axis="batch", model_axis="model",
        shapes=tuple(tuple(l.shape) for l in
                     jax.tree_util.tree_leaves(leaves)),
        dtypes=tuple(jnp.dtype(l.dtype).name for l in
                     jax.tree_util.tree_leaves(leaves)))
    full, vjp = jax.vjp(
        lambda sh: sharded_lib.gather_params(spec, sh), tuple(flat))
    my_ct = jax.tree.map(lambda c: c[lax.axis_index("batch")], ct)
    ct_leaves = tuple(jax.tree_util.tree_leaves(my_ct))
    (shard_cots,) = vjp(ct_leaves)
    want = sharded_lib.scatter_mean(my_ct)
    return (jax.tree_util.tree_unflatten(treedef, list(full)),
            jax.tree_util.tree_unflatten(treedef, list(shard_cots)),
            want)

  full, got, want = _shard_map_2d(
      body, mesh, in_specs=(P(("batch", "model")), P()),
      out_specs=(P(), P(("batch", "model")), P(("batch", "model"))),
  )(stacked, cots)
  # Forward: exact re-assembly.
  jax.tree.map(np.testing.assert_array_equal, full, leaves)
  # Backward: bit-identical to the post-hoc per-leaf scatter_mean.
  jax.tree.map(np.testing.assert_array_equal, got, want)


def test_fsdp_scatter_mean_matches_whole_leaf_scatter_elementwise():
  """Per-layer scatter addressing vs the whole-leaf flat scatter: the
  SAME mean values, re-addressed -- re-assembling both layouts yields
  identical full tensors."""
  mesh = mesh_lib.build_mesh_2d(8, 1, "cpu")
  rng = np.random.RandomState(2)
  g = jnp.asarray(rng.randn(8, 3, 11).astype(np.float32))
  full_tree = {"blocks": jnp.zeros((3, 11), jnp.float32)}

  def body(g_all):
    mine = {"blocks": g_all[lax.axis_index("batch")]}
    fsdp = sharded_lib.fsdp_scatter_mean(mine, ("blocks",))
    plain = sharded_lib.scatter_mean(mine)
    got = sharded_lib.fsdp_gather_full(fsdp, full_tree, ("blocks",))
    want = sharded_lib.gather_tree(plain, full_tree)
    return got, want

  got, want = _shard_map_2d(body, mesh, in_specs=(P(),),
                            out_specs=(P(), P()))(g)
  jax.tree.map(np.testing.assert_array_equal, got, want)


# -- numerical equivalence: CNN family ---------------------------------------

def test_equivalence_plain():
  stats_a, stats_b = _assert_equivalent(
      dict(shard_optimizer_state=True),
      dict(shard_optimizer_state=True, shard_params=True))
  # The FSDP memory claim: per-device PARAM bytes drop ~n-fold too.
  assert stats_b["param_bytes_per_device"] * 7 \
      < stats_a["param_bytes_per_device"]
  # Optimizer state stays sharded as before.
  assert stats_b["opt_state_bytes_per_device"] * 7 \
      < benchmark.opt_state_bytes_per_device(
          jax.tree.map(lambda x: x[:1], stats_a["state"].opt_state)) * 8


def test_equivalence_grad_accum():
  """--num_grad_accum=2: the in-compute gathers disengage (one whole-
  tree gather per step) and the post-hoc FSDP scatter keeps the
  accumulated gradient bit-identical."""
  _assert_equivalent(
      dict(shard_optimizer_state=True, num_grad_accum=2),
      dict(shard_optimizer_state=True, shard_params=True,
           num_grad_accum=2))


@pytest.mark.slow
def test_equivalence_4x2_model_axis():
  # (slow-tiered for the 870 s wall budget: plain + accum2 keep the
  # FSDP bit-identity bar in tier 1; the model-axis composition and
  # the K/adam legs ride -m slow)
  _assert_equivalent(
      dict(shard_optimizer_state=True, mesh_shape="4x2"),
      dict(shard_optimizer_state=True, shard_params=True,
           mesh_shape="4x2"))


@pytest.mark.slow
def test_equivalence_steps_per_dispatch():
  """K=8 chunked dispatch: the scan carry stays on the FSDP layout."""
  _assert_equivalent(
      dict(shard_optimizer_state=True, steps_per_dispatch=8),
      dict(shard_optimizer_state=True, shard_params=True,
           steps_per_dispatch=8))


@pytest.mark.slow
def test_equivalence_adam_composed():
  _assert_equivalent(
      dict(shard_optimizer_state=True, optimizer="adam",
           steps_per_dispatch=4, num_grad_accum=2),
      dict(shard_optimizer_state=True, shard_params=True,
           optimizer="adam", steps_per_dispatch=4, num_grad_accum=2))


# -- the scanned-transformer harness (tier-1 per-block gather pin) -----------

class _TinyBlock(nn.Module):
  d_model: int = 16
  d_ff: int = 32

  @nn.compact
  def __call__(self, carry, _):
    x, seg = carry
    h = nn.LayerNorm(name="ln")(x)
    h = nn.gelu(nn.Dense(self.d_ff, name="up")(h))
    x = x + nn.Dense(self.d_model, name="down")(h)
    return (x, seg), None


class _TinyScannedLM(nn.Module):
  """A miniature scan-over-layers LM: same structural skeleton as
  models/transformer_lm.py (nn.scan over a remat'd block with a
  'blocks' parameter stack), small enough for tier-1 CPU budgets."""
  vocab: int = 64
  d_model: int = 16
  n_layers: int = 4
  fsdp_block_hook: object = None

  @nn.compact
  def __call__(self, tokens):
    tokens = tokens.astype(jnp.int32)
    x = nn.Embed(self.vocab, self.d_model, name="embed")(tokens)
    block_cls = _TinyBlock
    if self.fsdp_block_hook is not None:
      block_cls = nn.map_variables(
          _TinyBlock, "params", trans_in_fn=self.fsdp_block_hook,
          init=True)
    blocks = nn.scan(
        nn.remat(block_cls, prevent_cse=False),
        variable_axes={"params": 0}, split_rngs={"params": True},
        length=self.n_layers)(name="blocks", d_model=self.d_model)
    (x, _), _ = blocks((x, None), None)
    logits = nn.Dense(self.vocab, name="head")(x)
    return logits, None


class _TinyModel(model_lib.Model):
  """The minimal model surface make_step_fns consumes (the optional
  hooks, such as ``step_counters``, at the base class's defaults)."""

  def __init__(self, fsdp: bool, batch: int = 8, seq: int = 8):
    self.batch, self.seq = batch, seq
    self.fsdp_gathered_prefixes = ("blocks",) if fsdp else ()
    hook = None
    if fsdp:
      plain = _TinyScannedLM()
      vs = jax.eval_shape(
          lambda: plain.init({"params": jax.random.PRNGKey(0),
                              "dropout": jax.random.PRNGKey(0)},
                             jnp.zeros((batch, seq), jnp.int32)))
      block_template = jax.tree.map(
          lambda s: jax.ShapeDtypeStruct(tuple(s.shape)[1:], s.dtype),
          vs["params"]["blocks"])
      hook = sharded_lib.fsdp_block_gatherer(
          block_template, mesh_lib.BATCH_AXIS, mesh_lib.MODEL_AXIS)
    self.module = _TinyScannedLM(fsdp_block_hook=hook)

  def get_name(self):
    return "tiny_scanned_lm"

  def get_input_shapes(self, subset):
    return [[self.batch, self.seq], [self.batch, self.seq]]

  def get_input_data_types(self, subset):
    return [jnp.int32, jnp.int32]

  def get_fp16_loss_scale(self):
    return 1.0

  def loss_function(self, result, labels):
    logits, _ = result.logits[0], result.logits[1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                             -1)
    return -jnp.mean(ll)

  def accuracy_function(self, result, labels):
    return {}


def _tiny_step_fns(fsdp: bool, **param_kw):
  mesh = mesh_lib.build_mesh_2d(8, 1, "cpu")
  model = _TinyModel(fsdp)
  kw = dict(model="trivial", device="cpu", num_devices=8,
            shard_optimizer_state=True, optimizer="momentum",
            weight_decay=0.0, init_learning_rate=0.05)
  kw.update(param_kw)
  if fsdp:
    kw["shard_params"] = True
  p = params_lib.make_params(**kw)
  strategy = strategies.get_strategy(p)
  tx = optax.sgd(0.05, momentum=0.9)
  fns = train_step_lib.make_step_fns(
      model, model.module, model.module, strategy, tx,
      lambda step: jnp.float32(0.05), p, mesh,
      total_train_steps=4)
  return fns, model


def _run_tiny(fsdp: bool, steps: int = 4, **param_kw):
  (init_state, train_step, _, _, _), model = _tiny_step_fns(
      fsdp, **param_kw)
  rng = jax.random.PRNGKey(7)
  sample = jnp.zeros((model.batch, model.seq), jnp.int32)
  state = init_state(rng, sample)
  data_rng = jax.random.PRNGKey(11)
  tokens = jax.random.randint(data_rng, (8 * model.batch, model.seq),
                              0, 64, jnp.int32)
  labels = jnp.roll(tokens, -1, axis=1)
  losses = []
  for _ in range(steps):
    state, metrics = train_step(state, tokens, labels)
    losses.append(np.asarray(metrics["base_loss"]).item())
  return losses, state, train_step, (tokens, labels)


def test_tiny_scanned_fsdp_bit_identical_and_in_loop_gather():
  """The per-block in-scan gather path, equivalence-pinned in tier 1:
  per-step f32 losses equal to the sharded-only twin's to 1 ulp,
  per-device param bytes ~1/n, and the compiled HLO carries the block
  gather INSIDE a while body with no full-gradient all-reduce."""
  losses_a, state_a, _, _ = _run_tiny(fsdp=False)
  losses_b, state_b, step_b, batch = _run_tiny(fsdp=True)
  np.testing.assert_array_max_ulp(
      np.float32(losses_a), np.float32(losses_b), maxulp=1)
  # 1 ulp of float32, not bit-identity: under jax 0.9.0's XLA:CPU the
  # step-0 loss of the in-scan-gather program reads 5.0325589 against
  # the twin's 5.0325594, and steps 1-3 agree to the bit. The gathered
  # parameters ARE the stored bits (test_gather_params_forward_and_
  # backward_laws), so the two forwards see the same inputs: the ulp is
  # the backend compiling two different programs, not the mechanism.
  # The larger FSDP equivalences (test_equivalence_*,
  # tests/test_transformer_lm_e2e.py) stay bit-identical.
  bytes_a = benchmark.opt_state_bytes_per_device(state_a.params)
  bytes_b = benchmark.opt_state_bytes_per_device(state_b.params)
  assert bytes_b * 7 < bytes_a
  hlo = step_b.lower(state_b, *batch).compile().as_text()
  from kf_benchmarks_tpu.analysis import contracts
  c = contracts.extract_contract(hlo)
  ags = [x for x in c.collectives
         if x.kind == "all-gather" and not x.scalar]
  assert any(x.in_loop for x in ags), "per-block gather left the scan"
  assert not c.gradient_collectives(), \
      "full-gradient all-reduce in an FSDP program"
  # The scanned stack never re-assembles whole: every gather is
  # smaller than the blocks stack's full bytes.
  blocks_bytes = sum(
      int(np.prod(l.shape)) * 4 for l in
      jax.tree_util.tree_leaves(
          jax.eval_shape(lambda: _TinyScannedLM().init(
              {"params": jax.random.PRNGKey(0),
               "dropout": jax.random.PRNGKey(0)},
              jnp.zeros((8, 8), jnp.int32)))["params"]["blocks"]))
  for x in ags:
    assert x.elems * 4 < blocks_bytes


def test_tiny_scanned_fsdp_memory_analysis_temp_drop():
  """The PR-7 methodology: compiled memory analysis of the FSDP
  program vs the replicated-param twin -- peak temp drops when the
  full parameter tree stops materializing (the tiny model is sized so
  params dominate activations)."""
  (_, step_a, _, _, _), model_a = _tiny_step_fns(fsdp=False)
  (init_b, step_b, _, _, _), model_b = _tiny_step_fns(fsdp=True)
  rng = jax.random.PRNGKey(7)
  sample = jnp.zeros((8, 8), jnp.int32)
  (init_a, step_a, _, _, _), _ = _tiny_step_fns(fsdp=False)
  state_a = jax.eval_shape(init_a, rng, sample)
  state_b = jax.eval_shape(init_b, rng, sample)
  gx = jax.ShapeDtypeStruct((64, 8), jnp.int32)
  try:
    temp_a = step_a.lower(state_a, gx, gx).compile() \
        .memory_analysis().temp_size_in_bytes
    temp_b = step_b.lower(state_b, gx, gx).compile() \
        .memory_analysis().temp_size_in_bytes
  except Exception:
    pytest.skip("backend without memory analysis")
  if not temp_a or not temp_b:
    pytest.skip("memory analysis reported no temp bytes")
  assert temp_b < temp_a


# -- checkpoint: layout round-trip, rejection, reshard law --------------------

def test_checkpoint_fsdp_roundtrip_and_resume(tmp_path):
  train_dir = str(tmp_path / "ckpt")
  kw = dict(shard_optimizer_state=True, shard_params=True,
            train_dir=train_dir, num_batches=4)
  logs_a, stats_a = _run_and_scrape(**kw)
  snap = checkpoint.load_checkpoint(
      checkpoint.latest_checkpoint(train_dir)[0])
  assert snap.get("params_layout") == "sharded"
  assert snap.get("opt_state_layout") == "sharded"
  # Saved params are the FULL (n, k) stacks, not a v0 slice.
  state = stats_a["state"]
  saved = {np.asarray(l).shape
           for l in jax.tree_util.tree_leaves(snap["params"])}
  live = {tuple(l.shape)
          for l in jax.tree_util.tree_leaves(
              jax.tree.map(np.asarray, state.params))}
  assert saved == live
  logs_b, stats_b = _run_and_scrape(**kw)
  assert any("Restored checkpoint at global step 4" in l for l in logs_b)
  assert int(stats_b["state"].step) == 8


def test_checkpoint_cross_layout_rejected_both_directions(tmp_path):
  fsdp_dir = str(tmp_path / "fsdp")
  _run_and_scrape(shard_optimizer_state=True, shard_params=True,
                  train_dir=fsdp_dir, num_batches=2)
  with pytest.raises(RuntimeError if False else Exception,
                     match="params layout"):
    _run_and_scrape(shard_optimizer_state=True, train_dir=fsdp_dir,
                    num_batches=2)
  plain_dir = str(tmp_path / "plain")
  _run_and_scrape(shard_optimizer_state=True, train_dir=plain_dir,
                  num_batches=2)
  with pytest.raises(Exception, match="params layout"):
    _run_and_scrape(shard_optimizer_state=True, shard_params=True,
                    train_dir=plain_dir, num_batches=2)


def test_checkpoint_fsdp_eval_deshard_restore(tmp_path):
  """restore_opt_state=False (the eval path's semantic) de-shards an
  FSDP checkpoint against the live replicated template instead of
  rejecting it: eval sidecars can read --shard_params checkpoints.
  Values are exact: at --weight_decay=0 the FSDP and sharded-only
  TRAINED PARAMS are bit-identical element-for-element (with weight
  decay, XLA's freedom to fuse g + wd*p differently between the two
  program shapes rounds a handful of elements in the last bit -- both
  valid roundings of the same math; the LOSS bit-identity bar is
  pinned with default wd elsewhere), so the de-sharded params must
  equal the replicated twin's exactly."""
  dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
  _run_and_scrape(shard_optimizer_state=True, shard_params=True,
                  train_dir=dir_a, num_batches=2, weight_decay=0.0)
  _, stats_b = _run_and_scrape(shard_optimizer_state=True,
                               train_dir=dir_b, num_batches=2,
                               weight_decay=0.0)
  snap = checkpoint.load_checkpoint(
      checkpoint.latest_checkpoint(dir_a)[0])
  state_b = stats_b["state"]
  restored = checkpoint.restore_state(state_b, snap,
                                      restore_opt_state=False)
  assert int(restored.step) == 2
  jax.tree.map(
      lambda got, want: np.testing.assert_array_equal(
          np.asarray(got), np.asarray(want)),
      restored.params, state_b.params)
  # opt_state untouched (model-variables-only restore).
  jax.tree.map(
      lambda got, want: np.testing.assert_array_equal(
          np.asarray(got), np.asarray(want)),
      restored.opt_state, state_b.opt_state)


def test_deshard_params_unit_scanned_and_plain():
  """_deshard_params inverts fsdp_stacked_shards exactly for both leaf
  families (the host-side re-assembly the eval restore rides)."""
  tree = {"dense": jnp.arange(23, dtype=jnp.float32) * 0.5,
          "blocks": jnp.arange(66, dtype=jnp.float32).reshape(3, 22)}
  stacked = sharded_lib.fsdp_stacked_shards(tree, 8, ("blocks",))
  template = jax.tree.map(
      lambda x: np.zeros((8,) + tuple(x.shape), np.float32), tree)
  full = checkpoint._deshard_params(
      template, jax.tree.map(np.asarray, stacked))
  jax.tree.map(
      lambda got, want: np.testing.assert_array_equal(
          np.asarray(got), np.asarray(want)), dict(full), tree)


@pytest.mark.parametrize("n_from,n_to", [(8, 4), (4, 8), (8, 3)])
def test_reshard_fsdp_scanned_stack_reslices_per_layer(n_from, n_to):
  """The (n, L, k) reshard law: cross-topology re-address is exact PER
  LAYER (only zero pad is cut), and re-flattening either layout yields
  the original layer rows bit-for-bit."""
  from flax import serialization
  tree = {"w": jnp.arange(66, dtype=jnp.float32).reshape(3, 22) * 0.5}
  stacked = sharded_lib.fsdp_stacked_shards(tree, n_from, ("w",))
  template = jax.tree.map(
      np.asarray, sharded_lib.fsdp_stacked_shards(tree, n_to, ("w",)))
  host = serialization.to_state_dict(jax.tree.map(np.asarray, stacked))
  out = checkpoint._reshard(template, host)
  assert out["w"].shape == template["w"].shape
  got = np.moveaxis(np.asarray(out["w"]), 1, 0).reshape(3, -1)[:, :22]
  np.testing.assert_array_equal(got, np.asarray(tree["w"]))


def test_reshard_rejects_mismatched_layer_depth():
  template = {"w": np.zeros((4, 3, 2), np.float32)}
  host = {"w": np.zeros((8, 5, 1), np.float32)}
  with pytest.raises(ValueError, match="cross-topology"):
    checkpoint._reshard(template, host)
