"""Observability subsystem tests (SURVEY 5.1/5.5: trace, program dumps,
cost analysis, benchmark logger, summary tiers)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kf_benchmarks_tpu import benchmark, observability, params as params_lib

V5E = observability.DEVICE_PEAKS["TPU v5 lite"]


def _run(tmp_path, **overrides):
  defaults = dict(model="trivial", batch_size=4, num_batches=6,
                  num_warmup_batches=1, device="cpu", num_devices=2,
                  optimizer="momentum", display_every=2)
  defaults.update(overrides)
  p = params_lib.make_params(**defaults)
  return benchmark.BenchmarkCNN(p).run()


def test_program_text_dump(tmp_path):
  path = str(tmp_path / "program.stablehlo")
  _run(tmp_path, graph_file=path)
  text = open(path).read()
  assert "module" in text  # StableHLO module header
  assert len(text) > 1000


def test_cost_analysis_dump(tmp_path):
  path = str(tmp_path / "profile.json")
  _run(tmp_path, tfprof_file=path)
  report = json.load(open(path))
  assert "cost_analysis" in report or "cost_analysis_error" in report
  if "cost_analysis" in report:
    assert report["cost_analysis"].get("flops", 0) > 0


def test_per_op_profile_needs_a_known_device(tmp_path, capsys):
  """--tfprof_file on a device with no DEVICE_PEAKS entry (the CPU
  here) prints NO roofline table and no MFU line -- it says why -- and
  the host-axis line comes from the run's own measured dispatch
  overhead, not a constant."""
  path = str(tmp_path / "profile.json")
  _run(tmp_path, model="lenet", tfprof_file=path)
  out = capsys.readouterr().out
  assert "device_kind 'cpu' has no entry in observability.DEVICE_PEAKS" \
      in out
  assert not os.path.exists(path + ".ops.txt")
  assert "MFU: " not in out
  assert observability.PER_OP_TABLE_HEADER not in out
  measured = [l for l in out.splitlines()
              if l.startswith("dispatch overhead:")]
  assert len(measured) == 1 and "measured" in measured[0]


def test_device_peaks_table_is_keyed_by_device_kind():
  assert V5E.flops == 197e12 and V5E.bytes_per_s == 819e9
  assert "TPU v5e" in V5E.source
  # The test host's device has no entry: no default peak exists.
  assert jax.devices()[0].device_kind not in observability.DEVICE_PEAKS


def test_per_op_profile_table(tmp_path):
  """The operator-facing top-op ranking the reference printed from
  tfprof (ref: benchmark_cnn.py:1208-1228), over a real compiled
  program and the v5e peaks: a <path>.ops.txt table with MXU flops
  attributed to dot/conv rows (VERDICT r2 #7)."""
  bench = benchmark.BenchmarkCNN(params_lib.make_params(
      model="lenet", device="cpu", batch_size=4, num_devices=1,
      num_batches=1))
  from kf_benchmarks_tpu.analysis import contracts
  _, lowered = contracts.lower_step_program(bench, "train_step")
  path = str(tmp_path / "profile.json.ops.txt")
  table = observability.dump_per_op_profile(lowered.compile(), path, V5E)
  assert open(path).read() == table + "\n"
  lines = table.splitlines()
  assert lines[0].startswith("Top 20 ops by estimated accelerator time")
  assert lines[1] == observability.PER_OP_TABLE_HEADER
  # The table closes with the two whole-program lines the per-op rows
  # cannot carry: the roofline MFU ceiling and the comm/compute overlap
  # fraction.
  assert lines[-2].startswith("MFU: ")
  assert lines[-1].startswith("comm/compute overlap:")
  ranked = lines[2:-2]
  assert len(ranked) > 1  # actual ranked rows
  # Ranked by estimated time, descending.
  times = [float(l.split()[1]) for l in ranked]
  assert times == sorted(times, reverse=True)
  # lenet's convs/dots must carry nonzero flops estimates.
  mxu_rows = [l for l in ranked
              if l.endswith(" convolution") or l.endswith(" dot")]
  assert mxu_rows and all(float(r.split()[3]) > 0 for r in mxu_rows)


def test_per_op_costs_parses_synthetic_hlo():
  """Parser unit test on a hand-written HLO snippet: symbol-table
  operand resolution, conv/dot flops math, fusion-body exclusion."""
  hlo = """
HloModule jit_f

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %t = f32[8]{0} tanh(%p0)
}

ENTRY %main (x: f32[4,8,8,16], k: f32[3,3,16,32], w: f32[32,10]) -> f32[4,10] {
  %x = f32[4,8,8,16]{3,2,1,0} parameter(0)
  %k = f32[3,3,16,32]{3,2,1,0} parameter(1)
  %w = f32[32,10]{1,0} parameter(2)
  %conv = f32[4,8,8,32]{3,2,1,0} convolution(%x, %k), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
  %resh = f32[256,32]{1,0} reshape(%conv)
  ROOT %dot = f32[256,10]{1,0} dot(%resh, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
  rows = {r["name"]: r for r in observability.per_op_costs(hlo, V5E)}
  assert "%t" not in rows  # fusion body excluded
  assert rows["%conv"]["flops"] == 2 * (4 * 8 * 8 * 32) * (3 * 3 * 16)
  assert rows["%dot"]["flops"] == 2 * 256 * 10 * 32
  # Operand bytes resolved through the symbol table (bare %names).
  conv_bytes = (4 * 8 * 8 * 32 + 4 * 8 * 8 * 16 + 3 * 3 * 16 * 32) * 4
  assert rows["%conv"]["bytes"] == conv_bytes


def test_per_op_costs_depthwise_conv_flops():
  """Grouped convs: the HLO kernel's 'i' dim already holds
  Cin/feature_group_count, so a depthwise 3x3 is 2*out*9 flops (no
  further group division -- the separable convs NASNet/MobileNet lean
  on would otherwise be undercounted by the group factor)."""
  import jax.numpy as jnp
  def dw(x, k):
    return jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=32)
  txt = jax.jit(dw).lower(
      jnp.ones((4, 8, 8, 32), jnp.float32),
      jnp.ones((3, 3, 1, 32), jnp.float32)).compile().as_text()
  convs = [r for r in observability.per_op_costs(txt, V5E)
           if r["opcode"] == "convolution"]
  assert convs and convs[0]["flops"] == 2 * (4 * 8 * 8 * 32) * 9


def test_benchmark_logger_files(tmp_path):
  log_dir = str(tmp_path / "bench_logs")
  stats = _run(tmp_path, benchmark_log_dir=log_dir)
  run_info = json.load(open(os.path.join(log_dir, "benchmark_run.log")))
  assert run_info["model_name"] == "trivial"
  assert run_info["machine_config"]["num_devices"] == 2
  assert any(rp["name"] == "batch_size" for rp in
             run_info["run_parameters"])
  metrics = [json.loads(l) for l in
             open(os.path.join(log_dir, "metric.log"))]
  names = {m["name"] for m in metrics}
  assert "current_examples_per_sec" in names
  assert "average_examples_per_sec" in names
  assert all(np.isfinite(m["value"]) for m in metrics)


def test_summary_tiers(tmp_path):
  train_dir = str(tmp_path / "train")
  _run(tmp_path, train_dir=train_dir, save_summaries_steps=2,
       summary_verbosity=2)
  events = [json.loads(l) for l in
            open(os.path.join(train_dir, "events.jsonl"))]
  scalar_events = [e for e in events if "scalars" in e]
  hist_events = [e for e in events if "histograms" in e]
  assert scalar_events and hist_events
  assert "total_loss" in scalar_events[0]["scalars"]
  first_hist = next(iter(hist_events[0]["histograms"].values()))
  assert sum(first_hist["counts"]) > 0


def test_write_histograms_unstacks_scanned_layers(tmp_path):
  """Scan-stacked params (PR 2 rebuilt transformer_lm layers on nn.scan,
  so 'blocks' leaves carry a leading layer axis) must unstack into
  per-layer-indexed histogram keys instead of blending all depths into
  one histogram; non-stacked leaves keep their plain keys."""
  rng = np.random.RandomState(0)
  layers = 4
  tree = {
      "blocks": {"mlp": {"kernel": rng.randn(layers, 3, 5).astype(
          np.float32)}},
      "embed": {"kernel": rng.randn(7, 3).astype(np.float32)},
  }
  w = observability.SummaryWriter(str(tmp_path), verbosity=3)
  w.write_histograms(11, tree, "params", stacked_prefixes=("blocks",))
  events = [json.loads(l) for l in open(os.path.join(str(tmp_path),
                                                     "events.jsonl"))]
  hists = events[0]["histograms"]
  layer_keys = [f"params/blocks/layer{i}/mlp/kernel"
                for i in range(layers)]
  assert set(hists) == set(layer_keys) | {"params/embed/kernel"}
  # Each per-layer histogram summarizes THAT layer's slice.
  for i, key in enumerate(layer_keys):
    sl = tree["blocks"]["mlp"]["kernel"][i]
    assert hists[key]["mean"] == pytest.approx(float(sl.mean()), rel=1e-6)
    assert sum(hists[key]["counts"]) == sl.size
  # Without the prefix the stacked leaf stays one blended histogram
  # (the pre-round-9 behavior, still the default).
  w2 = observability.SummaryWriter(str(tmp_path / "plain"), verbosity=3)
  w2.write_histograms(11, tree, "params")
  ev2 = [json.loads(l) for l in open(os.path.join(str(tmp_path / "plain"),
                                                  "events.jsonl"))]
  assert "params/blocks/mlp/kernel" in ev2[0]["histograms"]


def test_transformer_lm_exposes_scanned_prefixes(monkeypatch):
  """The scanned model names its depth-stacked top-level keys so the
  benchmark loop can pass them to write_histograms; the unrolled-loop
  variant exposes none."""
  from kf_benchmarks_tpu.models import model_config
  model = model_config.get_model_config("transformer_lm", "synthetic")
  model.make_module(nclass=1, phase_train=True)
  assert model.scanned_param_prefixes == ("blocks",)
  monkeypatch.setenv("KF_TRANSFORMER_LM_LAYERS", "loop")
  model2 = model_config.get_model_config("transformer_lm", "synthetic")
  model2.make_module(nclass=1, phase_train=True)
  assert model2.scanned_param_prefixes == ()


def test_summary_verbosity_zero_writes_nothing(tmp_path):
  train_dir = str(tmp_path / "train")
  _run(tmp_path, train_dir=train_dir, save_summaries_steps=2,
       summary_verbosity=0)
  assert not os.path.exists(os.path.join(train_dir, "events.jsonl"))


def test_trace_one_step(tmp_path):
  trace_file = str(tmp_path / "traces" / "trace")
  _run(tmp_path, trace_file=trace_file)
  trace_dir = str(tmp_path / "traces")
  # jax.profiler writes plugins/profile/<run>/*.
  found = []
  for root, _, files in os.walk(trace_dir):
    found += files
  assert found, "expected profiler output files"


def test_measured_op_costs_aggregation():
  """Unit: op events aggregate by hlo_op with trip-count-weighted totals;
  non-op events (no args.hlo_op) are never loaded in the first place, so
  the aggregator only sees real executions."""
  events = [
      {"ph": "X", "dur": 10.0, "args": {"hlo_op": "fusion.1",
                                        "hlo_module": "jit_step"}},
      {"ph": "X", "dur": 30.0, "args": {"hlo_op": "fusion.1",
                                        "hlo_module": "jit_step"}},
      {"ph": "X", "dur": 5.0, "args": {"hlo_op": "copy.2",
                                       "hlo_module": "jit_step"}},
  ]
  rows = {r["name"]: r for r in observability.measured_op_costs(events)}
  assert rows["fusion.1"]["total_us"] == 40.0
  assert rows["fusion.1"]["count"] == 2
  assert rows["fusion.1"]["avg_us"] == 20.0
  assert rows["copy.2"]["total_us"] == 5.0


def test_measured_op_costs_keyed_by_module():
  """Two modules in one traced span can both own a 'fusion.1'; their
  rows must not merge (and the table disambiguates with [module])."""
  events = [
      {"ph": "X", "dur": 10.0, "args": {"hlo_op": "fusion.1",
                                        "hlo_module": "jit_step"}},
      {"ph": "X", "dur": 99.0, "args": {"hlo_op": "fusion.1",
                                        "hlo_module": "jit_metrics"}},
  ]
  rows = observability.measured_op_costs(events)
  assert len(rows) == 2
  assert {(r["module"], r["total_us"]) for r in rows} == {
      ("jit_step", 10.0), ("jit_metrics", 99.0)}


def test_stale_profiler_run_excluded(tmp_path):
  """A pre-existing dump at the same trace path must not masquerade as
  this run's measured profile: runs listed in ``exclude`` are skipped."""
  import gzip
  run_dir = tmp_path / "plugins" / "profile" / "2020_01_01_00_00_00"
  run_dir.mkdir(parents=True)
  ev = {"traceEvents": [{"ph": "X", "dur": 7.0, "name": "fusion.9",
                         "args": {"hlo_op": "fusion.9",
                                  "hlo_module": "jit_old"}}]}
  with gzip.open(str(run_dir / "host.trace.json.gz"), "wt") as f:
    json.dump(ev, f)
  stale = observability.list_profile_runs(str(tmp_path))
  assert len(stale) == 1
  # Without exclusion the stale run is readable...
  assert observability.load_trace_op_events(str(tmp_path))
  # ...with exclusion it is invisible and no table is produced.
  assert observability.load_trace_op_events(str(tmp_path),
                                            exclude=stale) == []
  assert observability.measured_per_op_table(str(tmp_path),
                                             exclude=stale) is None


def test_measured_per_op_profile_e2e(tmp_path, capsys):
  """--trace_file + --tfprof_file together emit the MEASURED top-op table
  (the RunMetadata-read half of the reference's tfprof, ref:
  benchmark_cnn.py:1208-1228) parsed from the captured profiler trace,
  next to the static .ops.txt."""
  trace_file = str(tmp_path / "traces" / "trace")
  prof = str(tmp_path / "profile.json")
  _run(tmp_path, model="lenet", trace_file=trace_file, tfprof_file=prof)
  path = prof + ".measured_ops.txt"
  assert os.path.exists(path), "measured per-op table not written"
  lines = open(path).read().splitlines()
  assert lines[0].startswith("Top 20 ops by MEASURED accelerator time")
  assert lines[1] == observability.MEASURED_OP_TABLE_HEADER
  assert len(lines) > 2  # ranked rows from the real trace
  # Ranked by measured total time, descending, with positive durations
  # and execution counts.
  totals = [float(l.split()[1]) for l in lines[2:]]
  assert totals == sorted(totals, reverse=True)
  assert all(t > 0 for t in totals)
  counts = [int(l.split()[3]) for l in lines[2:]]
  assert all(c >= 1 for c in counts)
  # Operator-facing: also printed to the step log.
  out = capsys.readouterr().out
  assert observability.MEASURED_OP_TABLE_HEADER in out


def test_measured_profile_absent_without_trace(tmp_path):
  """No trace -> no measured table (and, on this CPU host, no static
  roofline table either: no DEVICE_PEAKS entry);
  dump_measured_op_profile returns None rather than writing a header-only
  file -- and an untraced run REMOVES a stale table a previous traced run
  left at the same profile path (it must not masquerade as this run's)."""
  prof = str(tmp_path / "profile.json")
  stale = prof + ".measured_ops.txt"
  with open(stale, "w") as f:
    f.write("previous run's table\n")
  _run(tmp_path, model="lenet", tfprof_file=prof)
  assert not os.path.exists(stale)
  assert observability.dump_measured_op_profile(
      str(tmp_path / "empty"), str(tmp_path / "out.txt")) is None
  assert not os.path.exists(str(tmp_path / "out.txt"))
  # A PREVIOUS run's table at the same path is removed, not left to
  # masquerade as this run's measured profile.
  stale_path = str(tmp_path / "stale.txt")
  open(stale_path, "w").write("old table\n")
  assert observability.dump_measured_op_profile(
      str(tmp_path / "empty"), stale_path) is None
  assert not os.path.exists(stale_path)


def test_eval_metrics_logged(tmp_path):
  log_dir = str(tmp_path / "bench_logs")
  _run(tmp_path, benchmark_log_dir=log_dir, eval=True,
       num_eval_batches=2)
  metrics = [json.loads(l) for l in
             open(os.path.join(log_dir, "metric.log"))]
  names = {m["name"] for m in metrics}
  assert {"eval_top_1_accuracy", "eval_top_5_accuracy",
          "eval_images_per_sec"} <= names


# -- MFU + peak-HBM lines (VERDICT stretch #9) --------------------------------

def test_mfu_line_math_and_format():
  # 98.5 TFLOP/s over the 197 TFLOP/s peak = 50%.
  line = observability.mfu_line(98.5e12 * 0.004, 0.004, V5E)
  assert line.startswith("MFU: 50.0%"), line
  assert "98.50 TFLOP/s" in line
  assert "197 TFLOP/s" in line
  assert observability.mfu_line(1.0, 0.0, V5E) == \
      "MFU: n/a (no step time)"
  # Measured-rate variant names its source for auditability.
  assert "measured" in observability.mfu_line(1e12, 1.0, V5E,
                                              source="measured")


def test_dispatch_overhead_line_reads_the_measurement():
  line = observability.dispatch_overhead_line(0.002, 0.1, 4)
  assert line.startswith("dispatch overhead: 2.000 ms host time/dispatch "
                         "measured over 4 step(s)/dispatch")
  assert "2.0% of dispatch wall" in line


def test_per_op_table_ends_with_mfu_line():
  hlo = """
HloModule m
ENTRY e {
  %p0 = f32[64,64] parameter(0)
  %p1 = f32[64,64] parameter(1)
  ROOT %d = f32[64,64] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
  table = observability.per_op_table(hlo, V5E)
  lines = table.splitlines()
  # Closing order: MFU, comm/compute overlap.
  assert lines[-2].startswith("MFU: ")
  assert lines[-1].startswith("comm/compute overlap:")
  # flops of the dot appear in the MFU line's flops/step field.
  assert "5.243e+05" in lines[-2], lines[-2]


def test_hbm_breakdown_line():
  class Mem:
    argument_size_in_bytes = 3 * 1024 * 1024
    output_size_in_bytes = 1024 * 1024
    temp_size_in_bytes = 5 * 1024 * 1024
  line = observability.hbm_breakdown_line(Mem())
  assert "peak HBM (compiled): 8.0 MiB" in line
  assert "arguments 3.0" in line and "temps 5.0" in line


def test_tfprof_run_logs_hbm_line(tmp_path):
  """--tfprof_file runs log the peak-HBM breakdown next to the per-op
  table (the footprint line the round-7 HBM levers move)."""
  from kf_benchmarks_tpu.utils import log as log_util
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    p = params_lib.make_params(
        model="trivial", device="cpu", batch_size=2, num_devices=2,
        num_batches=2, num_warmup_batches=0,
        tfprof_file=str(tmp_path / "prof.json"))
    benchmark.BenchmarkCNN(p).run()
  finally:
    log_util.log_fn = orig
  hbm = [l for l in logs if l.startswith("peak HBM (compiled):")]
  assert len(hbm) == 1, [l for l in logs if "HBM" in l]


# -- run_tests.py tiering helpers ---------------------------------------------

def test_run_tests_report_slowest_flag():
  import argparse
  import importlib.util
  spec = importlib.util.spec_from_file_location(
      "run_tests", os.path.join(os.path.dirname(__file__), "..",
                                "run_tests.py"))
  rt = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(rt)
  ns = argparse.Namespace(full_tests=False, run_distributed_tests=False,
                          report_slowest=15)
  args = rt.build_pytest_args(ns, [])
  assert "--durations=15" in args and "--durations-min=1.0" in args
  assert ["-m", "not slow"] == [a for a in args if a in ("-m", "not slow")]
  ns.report_slowest = None
  assert not any(a.startswith("--durations") for a in
                 rt.build_pytest_args(ns, []))
  # The new memory-regression suites ride the fast tier (they are
  # compile-only seconds, not minutes); the heavy e2e stays tiered out.
  fast_targets = [a for a in args if a.startswith("tests/")]
  assert "tests/test_fused_loss.py" in fast_targets
  assert "tests/test_transformer_lm_e2e.py" not in fast_targets


def test_run_tests_report_slowest_reclaims_swallowed_target(monkeypatch):
  """nargs='?' would otherwise eat a passthrough pytest target as N;
  main() gives it back and keeps the default (review-caught)."""
  import importlib.util
  spec = importlib.util.spec_from_file_location(
      "run_tests2", os.path.join(os.path.dirname(__file__), "..",
                                 "run_tests.py"))
  rt = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(rt)
  captured = {}

  def fake_call(cmd, cwd=None):
    captured["cmd"] = cmd
    return 0

  monkeypatch.setattr(rt.subprocess, "call", fake_call)
  assert rt.main(["--report-slowest", "tests/test_observability.py"]) == 0
  cmd = captured["cmd"]
  assert "--durations=15" in cmd
  assert "tests/test_observability.py" in cmd
  assert rt.main(["--report-slowest=5"]) == 0
  assert "--durations=5" in captured["cmd"]


def test_run_tests_check_tiering_flags_and_parsing():
  import argparse
  import importlib.util
  spec = importlib.util.spec_from_file_location(
      "run_tests3", os.path.join(os.path.dirname(__file__), "..",
                                 "run_tests.py"))
  rt = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(rt)
  ns = argparse.Namespace(full_tests=False, run_distributed_tests=False,
                          report_slowest=None, check_tiering=True)
  args = rt.build_pytest_args(ns, [])
  # Enforcement mode reports EVERY call at/above the 60 s rule on the
  # fast tier.
  assert "--durations=0" in args
  assert f"--durations-min={rt.TIER1_TEST_BUDGET_S}" in args
  assert ["-m", "not slow"] == [a for a in args if a in ("-m", "not slow")]

  output = """
============================= slowest durations ===============================
75.31s call     tests/test_heavy.py::test_way_over
61.00s call     tests/test_heavy.py::test_just_over
59.99s call     tests/test_ok.py::test_under
70.00s setup    tests/test_fixture.py::test_slow_setup_is_not_a_violation
"""
  viols = rt.tiering_violations(output)
  assert viols == [(75.31, "tests/test_heavy.py::test_way_over"),
                   (61.0, "tests/test_heavy.py::test_just_over")]
  assert rt.tiering_violations("no durations table") == []


def test_run_tests_check_tiering_fails_on_violation(monkeypatch, capsys,
                                                    tmp_path):
  import importlib.util
  import subprocess as sp
  spec = importlib.util.spec_from_file_location(
      "run_tests4", os.path.join(os.path.dirname(__file__), "..",
                                 "run_tests.py"))
  rt = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(rt)
  # --check-tiering persists its durations for the --audit re-check;
  # point that at a scratch path so the FAKE output below cannot
  # poison the real repo's saved report.
  monkeypatch.setattr(rt, "TIERING_REPORT",
                      str(tmp_path / "tiering_report.json"))

  class FakeProc:
    def __init__(self, stdout):
      self.stdout = stdout
      self.stderr = ""
      self.returncode = 0

  outputs = {"out": "80.00s call tests/test_x.py::test_big\n1 passed\n"}

  def fake_run(cmd, cwd=None, capture_output=None, text=None):
    return FakeProc(outputs["out"])

  monkeypatch.setattr(rt.subprocess, "run", fake_run)
  assert rt.main(["--check-tiering"]) == 1
  assert "TIERING VIOLATIONS" in capsys.readouterr().out
  # ...and the violating durations were persisted for --audit.
  ok, lines = rt.audit_tiering_static()
  assert not ok and any("test_big" in l for l in lines)
  outputs["out"] = "12 passed\n"
  assert rt.main(["--check-tiering"]) == 0
  assert "tiering check OK" in capsys.readouterr().out
  ok, _ = rt.audit_tiering_static()
  assert ok
  # The 60 s rule audits the fast tier only.
  import pytest as _pytest
  with _pytest.raises(SystemExit):
    rt.main(["--check-tiering", "--full_tests"])


# -- comm/compute overlap-fraction line ---------------------------------------

_OVERLAP_HLO = """
HloModule test

%wide.body_spmd (p: (f32[8])) -> (f32[8]) {
  %p = parameter(0)
  %x = f32[8]{0} get-tuple-element((f32[8]) %p), index=0
  %ar.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}, to_apply=%add
  ROOT %t = (f32[8]{0}) tuple(f32[8]{0} %ar.1)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = parameter(0)
  %w = (f32[8]{0}) while((f32[8]{0}) %tup), condition=%cond, body=%wide.body_spmd
  %y = f32[8]{0} get-tuple-element((f32[8]) %w), index=0
  ROOT %ar.2 = f32[8]{0} all-reduce(f32[8]{0} %y), replica_groups={}, to_apply=%add
}
"""


def test_collective_overlap_stats_splits_in_loop_vs_trailing():
  stats = observability.collective_overlap_stats(_OVERLAP_HLO, V5E)
  assert stats["num_collectives"] == 2
  # One of the two rides the while body (in-backward, overlappable).
  assert 0.0 < stats["overlap_fraction"] < 1.0
  assert abs(stats["overlap_fraction"] - 0.5) < 1e-6
  line = observability.overlap_fraction_line(_OVERLAP_HLO, V5E)
  assert "50.0% issued inside loop bodies" in line
  assert "2 collectives" in line


def test_overlap_fraction_line_no_collectives():
  line = observability.overlap_fraction_line("ENTRY %main () -> f32[] {\n}",
                                            V5E)
  assert "no collectives" in line


def test_per_op_table_includes_overlap_line():
  table = observability.per_op_table(_OVERLAP_HLO, V5E)
  assert "comm/compute overlap:" in table.splitlines()[-1]


# Collective opcodes beyond all-reduce: as tensor/sequence/expert
# parallel modes land, their reduce-scatter / all-gather /
# collective-permute traffic must count toward the overlap-fraction
# accounting too (only all-reduce paths were pinned before round 9).
_MULTI_COLLECTIVE_HLO = """
HloModule multi

%loop.body (p: (f32[64])) -> (f32[64]) {
  %p = parameter(0)
  %x = f32[64]{0} get-tuple-element((f32[64]) %p), index=0
  %cp = f32[64]{0} collective-permute(f32[64]{0} %x), source_target_pairs={{0,1},{1,0}}
  ROOT %t = (f32[64]{0}) tuple(f32[64]{0} %cp)
}

ENTRY %main (a: f32[64], b: f32[128]) -> f32[128] {
  %a = parameter(0)
  %b = parameter(1)
  %w = (f32[64]{0}) while((f32[64]{0}) %tup), condition=%cond, body=%loop.body
  %rs = f32[16]{0} reduce-scatter(f32[64]{0} %a), dimensions={0}, to_apply=%add
  %ag = f32[128]{0} all-gather-start(f32[16]{0} %rs), dimensions={0}
  ROOT %agd = f32[128]{0} all-gather-done(f32[128]{0} %ag)
}
"""


def test_collective_overlap_stats_counts_non_allreduce_opcodes():
  stats = observability.collective_overlap_stats(_MULTI_COLLECTIVE_HLO,
                                                 V5E)
  # collective-permute (in-loop), reduce-scatter, all-gather-start; the
  # -done half of the async pair is not a second collective.
  assert stats["num_collectives"] == 3
  assert stats["comm_s"] > 0
  # Only the collective-permute rides the while body.
  permute_bytes = 64 * 4
  assert stats["comm_in_loop_s"] == pytest.approx(
      permute_bytes / V5E.bytes_per_s)
  assert 0.0 < stats["overlap_fraction"] < 1.0
  line = observability.overlap_fraction_line(_MULTI_COLLECTIVE_HLO, V5E)
  assert "3 collectives" in line


def test_per_op_costs_rows_for_non_allreduce_collectives():
  rows = {r["opcode"]: r for r in observability.per_op_costs(
      _MULTI_COLLECTIVE_HLO, V5E)}
  assert "reduce-scatter" in rows and "collective-permute" in rows
  assert rows["reduce-scatter"]["bytes"] == (16 + 64) * 4
  assert rows["collective-permute"]["bytes"] == (64 + 64) * 4
  # Bandwidth-bound ops: no flops, ranked by bytes.
  assert rows["reduce-scatter"]["flops"] == 0.0
  assert rows["reduce-scatter"]["est_time_s"] > 0
