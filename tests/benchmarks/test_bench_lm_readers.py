"""The language-model cells' per-layer readers of PR 34, without a chip:
each reads the number a made-up traced run holds, reads nothing where
its scope, kernels or counter are absent, agrees with its entry, and is
reported in exactly the cells its entry names. The made-up step carries
both decoder families' scopes, named as the chip's traces name them: the
backward pass of an unrolled rematerialised stack under
``transpose(jvp(forward))/.../jvp(forward)/...``."""

import pytest

from bench_testlib import REPO
from benchmarks import harness
from benchmarks import lm_scopes
from benchmarks import spans
from benchmarks import spec
from benchmarks import xplane
from test_bench_spans import STEP_OPS, _handmade
from test_bench_spec import metric_rules, reported_where_named

GLM = "glm-4.7-flash-train-seq4096-bs2-1chip"
TRINITY = "trinity-mini-train-seq8192-bs1-1chip"
# name -> cells that report it (its entry may name others besides, now or
# after a later PR: no test here holds the list to these): the seven
# entries PR 34 appended and the two accepted ones the trinity-mini cell
# joined.
NAMED = {
    "attention_core_ms": [GLM],
    "gqa_attention_ms": [TRINITY],
    "attention_core_window_ms": [TRINITY],
    "attention_core_full_ms": [TRINITY],
    "attention_core_window_roofline": [TRINITY],
    "attention_core_full_roofline": [TRINITY],
    "attention_tiles_visited_share": [TRINITY],
    "moe_experts_roofline": [GLM, TRINITY],
    "moe_compact_share": [GLM, TRINITY],
}
FWD = "jit(s)/jvp(forward)/m/"
BWD = "jit(s)/transpose(jvp(forward))/m/jvp(forward)/m/checkpoint/"
ROUND = "moe_route/while/body/"


def _kernel(name, begin, end, op_name):
  return (f"%{name} = bf16[8,8]{{1,0}} custom-call(bf16[8,8]{{1,0}} %p)",
          begin, end, op_name + "/pallas_call:")


def _fusion(name, begin, end, op_name):
  return (f"%{name} = bf16[8,8]{{1,0}} fusion(bf16[8,8]{{1,0}} %p)",
          begin, end, op_name)


def _launches(kernel, first, begin, end, op_name, count):
  """``count`` launches of ``kernel`` back to back over ``begin`` to
  ``end``, numbered from ``first``."""
  each = (end - begin) / count
  return [_kernel(f"{kernel}.{first + i}", begin + i * each,
                  begin + (i + 1) * each, op_name) for i in range(count)]


def lm_step_ops(mixture_layers=4):
  """One step of the device, microseconds from its start (idle from 95),
  of a decoder with ``mixture_layers`` mixture layers, each one round:
  three grouped products a pass and a layer."""
  n = 3 * mixture_layers
  pullback = BWD + ROUND + "jit(_round_pullback)/"
  return [
      _kernel("splash_mha_fwd_residuals.1", 0, 10,
              FWD + "jvp(mla_attention)/jvp(attention_core)"),
      _fusion("fusion.2", 10, 14, FWD + "jvp(mla_attention)/dot_general"),
      _kernel("splash_mha_fwd_residuals.3", 14, 20,
              FWD + "gqa_attention/attention_core_window"),
      _fusion("fusion.4", 20, 22, FWD + "gqa_attention/dot_general"),
      _kernel("splash_mha_fwd_residuals.5", 22, 26,
              FWD + "gqa_attention/attention_core_full"),
      *_launches("gmm", 100, 26, 29, FWD + ROUND + "moe_experts/jit(gmm)",
                 n),
      _fusion("sort.7", 29, 31, FWD + "moe_route/sort"),
      # The backward pass: the forward remat repeats, the one the routed
      # path's own pullback runs again, and the backward's two.
      *_launches("gmm", 200, 31, 34, BWD + "rematted_computation/" + ROUND +
                 "moe_experts/jit(gmm)", n),
      *_launches("gmm", 300, 34, 37, pullback +
                 "jvp(jit(experts_round))/moe_experts/jit(gmm)", n),
      *_launches("gmm", 400, 37, 40, pullback +
                 "transpose(jvp(jit(experts_round)))/moe_experts/jit(gmm)",
                 n),
      *_launches("tgmm", 500, 40, 43, pullback +
                 "transpose(jvp(jit(experts_round)))/moe_experts/jit(tgmm)",
                 n),
      _kernel("splash_mha_dkv_no_residuals.12", 43, 53,
              BWD + "gqa_attention/attention_core_full"),
      _kernel("splash_mha_dkv_no_residuals.13", 53, 65,
              BWD + "gqa_attention/attention_core_window"),
      _fusion("reduce.14", 65, 66,
              BWD + "gqa_attention/attention_core_window/reduce_sum"),
      _kernel("splash_mha_dkv_no_residuals.15", 66, 86,
              "jit(s)/transpose(jvp(forward))/m/transpose(jvp(mla_attention))/"
              "transpose(jvp(attention_core))"),
      _fusion("fusion.16", 86, 95, "jit(s)/optimizer_apply/add"),
  ]


LM_STEP_OPS = lm_step_ops()
MIXTURE_LAYERS = {"glm-4.7-flash": 5, "trinity-mini": 4}
US = 1e-6
PEAK = 197e12


def _trace(tmp_path, monkeypatch, step_ops):
  path = str(tmp_path / "made_up.xplane.pb")
  with open(path, "wb") as f:
    f.write(_handmade(step_ops=step_ops))
  monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
  return path


def _run(config_name, tokens, batch, stats, traced=True):
  config = spec.load_config(REPO, config_name) if config_name else {}
  return harness.Run(
      cell={"name": "made-up", "config_data": config,
            "tokens_per_sample": tokens}, device={},
      peaks=spec.load_peaks(REPO)["TPU v5 lite"], kwargs={}, timed_steps=20,
      t0=0.0, global_batch=batch, stats=stats,
      reduction=object() if traced else None)


def _read(name, run):
  return spec.load_metric(REPO, "per_layer", name).read(run)


STATS = {"moe": {"pairs_routed_here": 1e6, "compact_share": 1.0},
         "attention": {"window": {"tiles_visited": 1232,
                                  "tiles_causal": 2240},
                       "full": {"tiles_visited": 560, "tiles_causal": 560}}}


def _share(flops, seconds):
  return 100.0 * flops / PEAK / seconds


@pytest.mark.parametrize("name, config, tokens, batch, want", [
    ("attention_core_ms", "glm-4.7-flash", 4096, 2, 0.030),
    ("gqa_attention_ms", "trinity-mini", 8192, 1, 0.035),
    ("attention_core_window_ms", "trinity-mini", 8192, 1, 0.019),
    ("attention_core_full_ms", "trinity-mini", 8192, 1, 0.014),
    # One forward launch (2 products) and one fused backward launch (5)
    # under each core's scope; the window's of the 14,681,088 pairs
    # inside its band, the full layer's of the causal half.
    ("attention_core_window_roofline", "trinity-mini", 8192, 1,
     _share(7 * 2 * 14_681_088 * 128 * 32, 19 * US)),
    ("attention_core_full_roofline", "trinity-mini", 8192, 1,
     _share(7 * 2 * (8192 * 8193 // 2) * 128 * 32, 14 * US)),
    ("attention_core_roofline", "glm-4.7-flash", 4096, 2,
     _share(7 * 4096 ** 2 * 256 * 20 * 2, 30 * US)),
    ("attention_tiles_visited_share", "trinity-mini", 8192, 1, 0.55),
    # Four times as many gmm as tgmm launches, three tgmm a mixture layer:
    # five passes of three products over a million pairs (the operations
    # bound it), under either family's key names.
    ("moe_experts_roofline", "glm-4.7-flash", 4096, 2,
     _share(5 * 3 * 2 * 1e6 * 2048 * 1536, 15 * US)),
    ("moe_experts_roofline", "trinity-mini", 8192, 1,
     _share(5 * 3 * 2 * 1e6 * 2048 * 1024, 15 * US)),
    ("moe_compact_share", "trinity-mini", 8192, 1, 1.0),
])
def test_reads_the_number_of_a_made_up_run(tmp_path, monkeypatch, name,
                                           config, tokens, batch, want):
  _trace(tmp_path, monkeypatch, lm_step_ops(MIXTURE_LAYERS[config]))
  assert _read(name, _run(config, tokens, batch, STATS)) == pytest.approx(
      want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(NAMED))
def test_reads_nothing_where_scope_kernel_or_counter_is_absent(
    tmp_path, monkeypatch, name):
  config = "trinity-mini" if TRINITY in NAMED[name] else "glm-4.7-flash"
  # An untraced run of a program without the counters.
  assert _read(name, _run(config, 8192, 1, {}, traced=False)) is None
  assert _read(name, _run(config, 8192, 1, None, traced=False)) is None
  # A traced run whose trace names none of the model's scopes, of a
  # program whose tables are another decoder's (latent attention states
  # one kind of core, flat).
  _trace(tmp_path, monkeypatch, STEP_OPS)
  other = {"moe": {"load_max_over_mean": 2.2},
           "attention": {"core_layers": 6, "backward_kernel_passes": 1}}
  assert _read(name, _run(config, 8192, 1, other)) is None


@pytest.mark.parametrize("name, without", [
    ("attention_core_roofline", "splash_mha"),
    ("attention_core_window_roofline", "splash_mha"),
    ("attention_core_full_roofline", "splash_mha"),
    ("moe_experts_roofline", "tgmm"),
    # The forward kernel under another name: the backward's launches
    # alone are not the pattern the passes are counted from.
    ("moe_experts_roofline", "gmm"),
])
def test_a_roofline_reads_nothing_without_its_kernels(tmp_path, monkeypatch,
                                                      name, without):
  # The scope is there and takes time, the kernels its numerator counts
  # are not (another kernel, or scores materialised off a TPU): no share
  # rather than a share of nothing.
  _trace(tmp_path, monkeypatch,
         [op for op in LM_STEP_OPS if not op[0].startswith("%" + without)])
  config = "glm-4.7-flash" if name == "attention_core_roofline" else \
      "trinity-mini"
  assert _read(name, _run(config, 8192, 1, STATS)) is None


@pytest.mark.parametrize("stats", [
    {"attention": {"window": {"tiles_visited": 0, "tiles_causal": 0}}},
    {"attention": {"window": {"window": 2048, "core_layers": 4}}},
    {"attention": {"full": {"tiles_visited": 5, "tiles_causal": 5}}},
])
def test_tiles_share_reads_nothing_without_tiles(stats):
  assert _read("attention_tiles_visited_share",
               _run(None, 8192, 1, stats, traced=False)) is None


@pytest.mark.parametrize("name", sorted(NAMED))
def test_file_agrees_with_its_entry_and_the_cells_named_report_it(name):
  metric_rules(REPO, "per_layer", name)
  reported_where_named(REPO, name, expected=NAMED[name])


def test_kernel_launches_and_parts_of_the_made_up_step(tmp_path,
                                                       monkeypatch):
  path = _trace(tmp_path, monkeypatch, LM_STEP_OPS)
  run = _run("trinity-mini", 8192, 1, STATS)
  assert lm_scopes.kernel_launches(run, __file__, "moe_experts") == (
      pytest.approx({"gmm": 48.0, "tgmm": 12.0}))    # as the cell's trace
  # A core's launches are its own; the scope around both holds both.
  assert lm_scopes.kernel_launches(run, __file__, "attention_core_window"
                                   ) == {"splash_mha_fwd_residuals": 1.0,
                                         "splash_mha_dkv_no_residuals": 1.0}
  assert lm_scopes.kernel_launches(run, __file__, "gqa_attention") == {
      "splash_mha_fwd_residuals": 2.0, "splash_mha_dkv_no_residuals": 2.0}
  assert lm_scopes.kernel_launches(run, __file__, "lm_head") is None
  # The step's parts: everything from the repeated forward on is the
  # backward pass, whatever its inner component says.
  parts = spans.reduce(spans.load(path))["parts_ms"]
  assert parts["forward"] == pytest.approx(0.031)
  assert parts["backward"] == pytest.approx(0.055)
  assert parts["optimizer"] == pytest.approx(0.009)
  assert sum(parts.values()) == pytest.approx(0.095)
