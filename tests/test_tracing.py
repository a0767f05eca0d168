"""Unified run tracing (kf_benchmarks_tpu/tracing.py).

Reference-style layering (SURVEY 7.1):
  * pure-unit: spans / percentiles / compile ledger under an INJECTED
    deterministic clock (no wall-clock flakiness anywhere in this
    layer), Chrome trace-event schema validation, rank-file merge.
  * log-scraping e2e: BenchmarkCNN.run() with ``--trace_events_file``
    -- the emitted JSON validates against the trace-event schema
    check, the percentile + compile-ledger lines are whole lines that
    never interleave inside step lines (the test_benchmark.py scrape
    guard), and the flight-recorder rows cross-link span ids and share
    the run id.
  * equivalence: per-step f32 losses and trained params BIT-identical
    trace-on vs trace-off, through --steps_per_dispatch /
    --num_grad_accum / --shard_optimizer_state (the host-only
    contract; the program-shape half is the auditor's twin rule).
"""

import json
import os
import re

import numpy as np
import pytest

import jax

from kf_benchmarks_tpu import benchmark
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import tracing
from kf_benchmarks_tpu import validation
from kf_benchmarks_tpu.analysis import baseline
from kf_benchmarks_tpu.utils import log as log_util

from tests.test_benchmark import STEP_RE, TOTAL_RE, _run_and_scrape


class FakeClock:
  """Injected monotonic clock: tests advance it explicitly."""

  def __init__(self, t: float = 100.0):
    self.t = t

  def __call__(self) -> float:
    return self.t

  def tick(self, dt: float) -> float:
    self.t += dt
    return self.t


def _trace(tmp_path=None, name="trace.json", **kw):
  clock = FakeClock()
  kw.setdefault("time_fn", clock)
  kw.setdefault("wall_fn", lambda: 1_000.0)
  path = str(tmp_path / name) if tmp_path is not None else None
  return tracing.RunTrace(path=path, **kw), clock


# -- percentiles --------------------------------------------------------------

def test_percentile_math():
  assert tracing.percentile([], 50) is None
  assert tracing.percentile([7.0], 99) == 7.0
  assert tracing.percentile([1, 2, 3, 4], 50) == 2.5
  assert tracing.percentile([4, 3, 2, 1], 50) == 2.5  # order-free
  assert abs(tracing.percentile([1, 2, 3, 4], 90) - 3.7) < 1e-12
  assert tracing.percentile(range(1, 101), 99) == 99.01 or \
      abs(tracing.percentile(range(1, 101), 99) - 99.01) < 1e-9


def test_samples_to_fields_and_lines():
  tr, _ = _trace()
  for v in (0.010, 0.020, 0.030, 0.040):
    tr.add_sample("chunk_wall", v)
  tr.add_sample("feed_wait", 0.005)
  fields = tr.percentile_fields()
  assert fields["chunk_wall_p50"] == 0.025
  assert fields["feed_wait_p99"] == 0.005
  lines = tr.latency_lines()
  assert all(l.startswith("latency percentiles: ") for l in lines)
  assert any(re.fullmatch(
      r"latency percentiles: chunk_wall p50=25\.000ms p90=[\d.]+ms "
      r"p99=[\d.]+ms \(n=4\)", l) for l in lines), lines
  # The scrape-guard contract: no percentile line carries the step-line
  # marker.
  assert not any("images/sec" in l for l in lines)


# -- spans + Chrome export ----------------------------------------------------

def test_span_forms_and_chrome_schema(tmp_path):
  tr, clock = _trace(tmp_path)
  t0 = tr.now()
  clock.tick(0.5)
  sid = tr.add_span("dispatch", "train_step", t0, 0.5, {"step": 1})
  with tr.span("checkpoint", "save", step=2) as args:
    clock.tick(0.25)
    args["extra"] = "yes"
  iid = tr.instant("faults", "kill at step 10", step=10)
  assert 0 < sid < iid
  out = tr.export()
  assert out == str(tmp_path / "trace.json")
  obj = json.load(open(out))
  assert tracing.validate_chrome_trace(obj) == []
  events = obj["traceEvents"]
  xs = [e for e in events if e["ph"] == "X"]
  names = {e["name"] for e in xs}
  assert {"train_step", "save"} <= names
  # Monotonic -> epoch mapping: anchor wall 1000.0 s at mono 100.0 s,
  # so t0=100.0 lands at exactly 1e9 us.
  disp = next(e for e in xs if e["name"] == "train_step")
  assert disp["ts"] == 1_000.0 * 1e6
  assert disp["dur"] == 0.5 * 1e6
  assert disp["args"]["span_id"] == sid
  save = next(e for e in xs if e["name"] == "save")
  assert save["dur"] == 0.25 * 1e6
  assert save["args"]["extra"] == "yes"  # args mutated inside the span
  inst = next(e for e in events if e["ph"] == "i")
  assert inst["args"]["step"] == 10
  # Metadata rows name the subsystem lanes actually used.
  threads = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
  assert {"dispatch", "checkpoint", "faults"} <= threads
  assert obj["metadata"]["run_id"] == tr.run_id


def test_validate_chrome_trace_rejects_malformed():
  assert tracing.validate_chrome_trace([]) != []
  assert tracing.validate_chrome_trace({}) != []
  bad_ph = {"traceEvents": [{"ph": "Q", "name": "x", "pid": 0, "tid": 0}]}
  assert any("ph" in p for p in tracing.validate_chrome_trace(bad_ph))
  no_ts = {"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                            "dur": 1}]}
  assert any("ts" in p for p in tracing.validate_chrome_trace(no_ts))


def test_span_cap_counts_drops(tmp_path, monkeypatch):
  monkeypatch.setattr(tracing.RunTrace, "MAX_SPANS", 2)
  tr, clock = _trace(tmp_path)
  for i in range(4):
    tr.add_span("dispatch", f"s{i}", tr.now(), 0.1)
  obj = json.load(open(tr.export()))
  assert len([e for e in obj["traceEvents"] if e["ph"] == "X"]) == 2
  assert obj["metadata"]["dropped_spans"] == 2


def test_no_path_keeps_samples_but_not_spans():
  tr, _ = _trace(None)
  # Unretained spans return id 0 (falsy): a cross-link consumer (the
  # flight recorder's span_id) must never reference a span absent from
  # every exported timeline.
  assert tr.add_span("dispatch", "s", tr.now(), 0.1) == 0
  assert tr.instant("faults", "x") == 0
  tr.add_sample("chunk_wall", 0.1)
  assert tr.export() is None
  assert tr.percentile_fields()["chunk_wall_p50"] == 0.1


def test_dropped_spans_return_id_zero(monkeypatch):
  monkeypatch.setattr(tracing.RunTrace, "MAX_SPANS", 1)
  tr = tracing.RunTrace(path="/tmp/unused-trace.json",
                        time_fn=FakeClock(), wall_fn=lambda: 1.0)
  assert tr.add_span("dispatch", "kept", 0.0, 0.1) > 0
  assert tr.add_span("dispatch", "dropped", 0.0, 0.1) == 0


def test_sample_decimation_bounds_memory(monkeypatch):
  monkeypatch.setattr(tracing.RunTrace, "MAX_SAMPLES", 8)
  tr, _ = _trace(None)
  for i in range(100):
    tr.add_sample("feed_wait", float(i))
  row = tr.percentiles()["feed_wait"]
  assert row["n"] == 100  # true observation count survives decimation
  assert len(tr._samples["feed_wait"]) < 8 * 2
  # The strided subsample keeps the distribution's shape.
  assert 30.0 <= row["p50"] <= 70.0


def test_raw_jsonl_export_when_chrome_format_off(tmp_path):
  tr, clock = _trace(tmp_path, chrome_format=False)
  tr.add_span("dispatch", "train_step", tr.now(), 0.5)
  lines = open(tr.export()).read().splitlines()
  head = json.loads(lines[0])
  assert head["run_id"] == tr.run_id and "anchor_wall" in head
  spans = [json.loads(l) for l in lines[1:]]
  assert [s["name"] for s in spans] == ["train_step"]


# -- multi-rank merge ---------------------------------------------------------

def test_rank_path_convention(tmp_path):
  p = str(tmp_path / "t.json")
  assert tracing.rank_path(p, 0) == p
  assert tracing.rank_path(p, 2) == str(tmp_path / "t.rank2.json")


def test_rank0_merge_produces_one_coherent_timeline(tmp_path):
  path = str(tmp_path / "t.json")
  run_id = "run-shared"
  r1, c1 = _trace(tmp_path, name="t.json", rank=1, num_ranks=2,
                  run_id=run_id)
  r1.add_span("dispatch", "peer_step", r1.now(), 0.1)
  assert r1.export() == tracing.rank_path(path, 1)
  r0, c0 = _trace(tmp_path, name="t.json", rank=0, num_ranks=2,
                  run_id=run_id)
  r0.add_span("dispatch", "chief_step", r0.now(), 0.1)
  assert r0.export(merge_wait_s=1.0) == path
  obj = json.load(open(path))
  assert tracing.validate_chrome_trace(obj) == []
  pids = {e["pid"] for e in obj["traceEvents"] if e["ph"] == "X"}
  assert pids == {0, 1}
  assert obj["metadata"]["run_id"] == run_id


def test_restart_generation_extends_same_run_id_file(tmp_path):
  """A kfrun checkpoint-restart re-execs the same command with the
  same KF_RUN_ID: the relaunched generation's export must EXTEND the
  job's timeline, not truncate it; a FRESH run (different run id) at
  the same path overwrites."""
  path = str(tmp_path / "t.json")
  gen0, _ = _trace(tmp_path, name="t.json", run_id="run-job")
  gen0.add_span("dispatch", "gen0_step", gen0.now(), 0.1)
  gen0.export()
  gen1, _ = _trace(tmp_path, name="t.json", run_id="run-job")
  gen1.add_span("dispatch", "gen1_step", gen1.now(), 0.1)
  gen1.export()
  names = {e["name"] for e in json.load(open(path))["traceEvents"]
           if e["ph"] == "X"}
  assert names == {"gen0_step", "gen1_step"}
  fresh, _ = _trace(tmp_path, name="t.json", run_id="run-other")
  fresh.add_span("dispatch", "fresh_step", fresh.now(), 0.1)
  fresh.export()
  names = {e["name"] for e in json.load(open(path))["traceEvents"]
           if e["ph"] == "X"}
  assert names == {"fresh_step"}
  # Raw JSONL mode appends under the same run id too.
  raw_path = str(tmp_path / "raw.json")
  for gen in range(2):
    tr, _ = _trace(tmp_path, name="raw.json", run_id="run-raw",
                   chrome_format=False)
    tr.add_span("dispatch", f"raw_gen{gen}", tr.now(), 0.1)
    tr.export()
  lines = open(raw_path).read().splitlines()
  assert [json.loads(l)["name"] for l in lines[1:]] == \
      ["raw_gen0", "raw_gen1"]


def test_standalone_merge_rank_files(tmp_path):
  path = str(tmp_path / "t.json")
  for r in (0, 1):
    tr, _ = _trace(tmp_path, name="t.json", rank=r, num_ranks=1)
    tr.add_span("dispatch", f"rank{r}", tr.now(), 0.1)
    tr.export()
  assert tracing.merge_rank_files(path, 2) == path
  obj = json.load(open(path))
  assert {e["pid"] for e in obj["traceEvents"] if e["ph"] == "X"} == {0, 1}


# -- compile ledger -----------------------------------------------------------

def test_compile_ledger_totals_and_table(tmp_path):
  tr, _ = _trace(tmp_path)
  tr.note_compile("aaaa111122223333", "train_chunk", 12.0,
                  model="resnet50")
  tr.note_compile("bbbb111122223333", "eval_step", 0.5, model="resnet50")
  ledger = tr.compile_ledger()
  assert ledger["shapes"] == 2
  assert ledger["total_compile_s"] == 12.5
  lines = tr.ledger_lines()
  assert lines[0] == ("compile ledger: 2 program shape(s), total "
                      "compile 12.50 s")
  assert all(l.startswith("compile ledger:") for l in lines)
  assert any("aaaa111122223333" in l and "train_chunk" in l
             for l in lines)
  assert not any("images/sec" in l for l in lines)
  # Each episode also lands on the compile lane of the timeline.
  obj = json.load(open(tr.export()))
  compile_spans = [e for e in obj["traceEvents"]
                   if e["ph"] == "X" and e["cat"] == "compile"]
  assert {e["name"] for e in compile_spans} == {"train_chunk",
                                                "eval_step"}
  assert compile_spans[0]["args"]["fingerprint"]


def test_ledger_persists_and_merges_across_runs(tmp_path):
  tr, _ = _trace()
  tr.note_compile("k1", "train_step", 10.0, model="trivial")
  path = tr.write_ledger(str(tmp_path))
  assert path == str(tmp_path / "compile_ledger.json")
  tr2, _ = _trace()
  tr2.note_compile("k1", "train_step", 8.0, model="trivial")
  tr2.note_compile("k2", "train_chunk", 3.0, model="trivial")
  tr2.write_ledger(str(tmp_path))
  data = json.load(open(path))
  assert set(data["entries"]) == {"k1", "k2"}
  k1 = data["entries"]["k1"]
  assert k1["compiles"] == 2
  assert k1["min_wall_s"] == 8.0 and k1["last_wall_s"] == 8.0
  # A corrupt prior file starts fresh rather than crashing the run end.
  with open(path, "w") as f:
    f.write("{torn")
  tr3, _ = _trace()
  tr3.note_compile("k3", "train_step", 1.0)
  tr3.write_ledger(str(tmp_path))
  assert set(json.load(open(path))["entries"]) == {"k3"}


def test_empty_ledger_writes_nothing(tmp_path):
  tr, _ = _trace()
  assert tr.write_ledger(str(tmp_path)) is None
  assert not os.path.exists(tmp_path / "compile_ledger.json")


# -- fingerprint keys ---------------------------------------------------------

def test_config_fingerprint_key_identity_and_exclusions():
  base = dict(model="trivial", batch_size=4, num_devices=8)
  k = baseline.config_fingerprint_key(base)
  assert re.fullmatch(r"[0-9a-f]{16}", k)
  assert baseline.config_fingerprint_key(dict(base)) == k
  # Host-side sinks/cadences do not fragment the key...
  assert baseline.config_fingerprint_key(
      dict(base, train_dir="/tmp/x", trace_events_file="/tmp/t.json",
           display_every=7)) == k
  # ...while program-shaping fields and the program name do.
  assert baseline.config_fingerprint_key(dict(base, batch_size=8)) != k
  assert baseline.config_fingerprint_key(base, "train_chunk") != k


# -- active-session registry --------------------------------------------------

def test_active_registry_and_null_sink():
  assert tracing.active() is tracing.NULL_TRACE
  # The null sink accepts the full emission + reporting surface.
  tracing.active().add_span("feed", "wait", 0.0, 0.1)
  tracing.active().add_sample("feed_wait", 0.1)
  with tracing.active().span("checkpoint", "save"):
    pass
  assert tracing.active().latency_lines() == []
  assert tracing.active().compile_ledger()["shapes"] == 0
  tr, _ = _trace()
  try:
    assert tracing.activate(tr) is tr
    assert tracing.active() is tr
  finally:
    tracing.deactivate()
  assert tracing.active() is tracing.NULL_TRACE


def test_state_sets_a_block_and_logs_it_once_a_value(monkeypatch):
  lines = []
  tr = tracing.RunTrace(log_fn=lines.append)
  tr.state("block", {"a": 1}, "block: a 1", "block: its second line")
  tr.state("block", {"a": 1}, "block: a 1")     # the evaluation build
  assert lines == ["block: a 1", "block: its second line"]
  assert tr.static("block") == {"a": 1}
  tr.state("block", {"a": 2}, "block: a 2")     # SET, and said again
  assert lines[2:] == ["block: a 2"]
  tr.state("other", {"b": 0})
  assert tr.statics() == {"block": {"a": 2}, "other": {"b": 0}}
  tr.statics()["block"]["a"] = 9                # a copy
  assert tr.static("block") == {"a": 2} and len(lines) == 3
  # With no session the sink holds nothing and says nothing.
  said = []
  monkeypatch.setattr(log_util, "log_fn", said.append)
  tracing.active().state("block", {"a": 1}, "block: a 1")
  assert tracing.active().static("block") is None
  assert tracing.active().statics() == {} and said == []


def test_resolve_run_id_prefers_env(monkeypatch):
  monkeypatch.setenv("KF_RUN_ID", "run-fixed")
  assert tracing.resolve_run_id() == "run-fixed"
  monkeypatch.delenv("KF_RUN_ID")
  a = tracing.resolve_run_id(wall_fn=lambda: 1.0)
  assert a.startswith("run-") and a != "run-fixed"


# -- DeviceFeeder feed lane ---------------------------------------------------

def test_device_feeder_emits_feed_spans_and_wait_samples(tmp_path):
  from kf_benchmarks_tpu.data import device_feed
  from kf_benchmarks_tpu.parallel import mesh as mesh_lib

  def produce():
    for i in range(3):
      yield np.full((2, 2), i, np.float32), np.zeros((2,), np.int32)

  tr = tracing.RunTrace(path=str(tmp_path / "t.json"))
  tracing.activate(tr)
  try:
    mesh = mesh_lib.build_mesh(1, "cpu")
    f = device_feed.DeviceFeeder(produce(), mesh_lib.batch_sharding(mesh),
                                 prefetch=2)
    try:
      for _ in range(3):
        next(f)
      with pytest.raises(StopIteration):
        next(f)
    finally:
      f.stop()
  finally:
    tracing.deactivate()
  # The sample keeps to delivered batches; the span counts every wait
  # the consumer sat through, the end-of-stream drain included.
  assert tr.percentiles()["feed_wait"]["n"] == 3
  spans = tr.span_totals()[tracing.PHASE_SETUP]["spans"]
  assert spans["feed/wait"]["n"] == 4 and spans["feed/h2d"]["n"] == 3
  obj = json.load(open(tr.export()))
  feed = [e for e in obj["traceEvents"]
          if e["ph"] == "X" and e["cat"] == "feed"]
  names = {e["name"] for e in feed}
  assert {"fetch", "h2d", "wait"} <= names


# -- flag validation ----------------------------------------------------------

@pytest.mark.parametrize("mode", ["eval", "forward_only"])
def test_trace_events_file_is_training_only(mode):
  p = params_lib.make_params(model="trivial", device="cpu",
                             trace_events_file="/tmp/t.json",
                             **{mode: True})
  with pytest.raises(validation.ParamError, match="training runs only"):
    validation.validate_cross_flags(p)


# -- log-scraping e2e ---------------------------------------------------------

def _schema_checked(path):
  obj = json.load(open(path))
  problems = tracing.validate_chrome_trace(obj)
  assert problems == [], problems
  return obj


def test_e2e_trace_file_covers_the_run(tmp_path):
  """Acceptance: one CLI-shaped run emits a schema-valid Chrome trace
  covering dispatch/device/compile/checkpoint/eval spans, the
  percentile + ledger lines are whole lines outside every step line,
  and the flight-recorder rows cross-link span ids under the shared
  run id."""
  trace_path = str(tmp_path / "trace.json")
  train_dir = str(tmp_path / "train")
  logs, stats = _run_and_scrape(
      num_batches=8, display_every=1, train_dir=train_dir,
      save_model_steps=4, trace_events_file=trace_path,
      eval_during_training_at_specified_steps=["5"])
  obj = _schema_checked(trace_path)
  xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
  cats = {e["cat"] for e in xs}
  assert {"run", "dispatch", "device", "compile", "checkpoint",
          "eval"} <= cats, cats
  assert obj["metadata"]["run_id"] == stats["run_id"]
  # Scrape guard: every marker-carrying line is a step line or the
  # closing total -- the new report lines never interleave inside them.
  marker_lines = [l for l in logs if "images/sec:" in l]
  assert all(STEP_RE.match(l) or TOTAL_RE.match(l) for l in marker_lines)
  lat_lines = [l for l in logs if l.startswith("latency percentiles: ")]
  assert any("chunk_wall" in l for l in lat_lines)
  assert any("checkpoint_save" in l for l in lat_lines)
  ledger_lines = [l for l in logs if l.startswith("compile ledger:")]
  assert len(ledger_lines) >= 3  # header + column row + >= 1 entry
  # Stats fields (what bench.py forwards).
  lat = stats["latency_percentiles"]
  assert lat["chunk_wall_p50"] > 0
  assert stats["compile_ledger"]["shapes"] >= 2  # train + eval programs
  assert stats["compile_ledger"]["total_compile_s"] > 0
  # Ledger entries carry the auditor's fingerprint-key format.
  for e in stats["compile_ledger"]["entries"]:
    assert re.fullmatch(r"[0-9a-f]{16}", e["key"])
  # Persisted ledger merged under train_dir.
  data = json.load(open(os.path.join(train_dir, "compile_ledger.json")))
  assert data["run_id"] == stats["run_id"]
  assert len(data["entries"]) == stats["compile_ledger"]["shapes"]
  # Flight recorder: every step row cross-links an enclosing span id
  # and shares the run id; timestamps carry wall AND monotonic clocks.
  rows = [json.loads(l)
          for l in open(os.path.join(train_dir, "flight_recorder.jsonl"))]
  step_rows = [r for r in rows if "step" in r and "loss" in r]
  assert step_rows
  span_ids = {e["args"].get("span_id") for e in xs}
  for r in step_rows:
    assert r["run_id"] == stats["run_id"]
    assert r["t_mono"] > 0 and r["t_wall"] > 0
    assert r["span_id"] in span_ids
  # The cross-linked spans are the device-completion spans.
  linked = [e for e in xs
            if e["args"].get("span_id") in {r["span_id"]
                                            for r in step_rows}]
  assert {e["cat"] for e in linked} == {"device"}


def test_e2e_raw_jsonl_when_chrome_format_off(tmp_path):
  trace_path = str(tmp_path / "trace.json")
  logs, stats = _run_and_scrape(num_batches=4,
                                trace_events_file=trace_path,
                                use_chrome_trace_format=False)
  lines = open(trace_path).read().splitlines()
  head = json.loads(lines[0])
  assert head["run_id"] == stats["run_id"]
  names = {json.loads(l)["name"] for l in lines[1:]}
  assert "train_step" in names


def test_trace_off_still_reports_percentiles_and_ledger(tmp_path):
  """The flag gates the FILE, not the aggregates: bench.py's JSON
  fields ride every run."""
  logs, stats = _run_and_scrape(num_batches=4)
  assert stats["latency_percentiles"]["chunk_wall_p50"] > 0
  assert stats["compile_ledger"]["shapes"] == 1
  assert not (tmp_path / "trace.json").exists()
  # No percentile line interleaves inside step lines here either.
  marker_lines = [l for l in logs if "images/sec:" in l]
  assert all(STEP_RE.match(l) or TOTAL_RE.match(l) for l in marker_lines)


# -- equivalence: trace-on vs off ---------------------------------------------

# The compositions compile two full step programs apiece: slow-tiered
# (CLAUDE.md 60 s rule); [plain] stays tier-1 as the regression pin.
@pytest.mark.parametrize("extra", [
    {},
    pytest.param({"steps_per_dispatch": 4}, marks=pytest.mark.slow),
    pytest.param({"num_grad_accum": 2}, marks=pytest.mark.slow),
    pytest.param({"shard_optimizer_state": True, "optimizer": "momentum"},
                 marks=pytest.mark.slow),
], ids=["plain", "K4", "accum2", "sharded"])
def test_trace_on_bit_identical_to_off(tmp_path, extra):
  """Acceptance: tracing is a pure host-side observer -- per-step
  losses AND trained params bit-identical with --trace_events_file on
  vs off, on the 8-device mesh, through the chunked / microbatched /
  sharded compositions (the auditor's twin rule pins the program-shape
  half of the same contract)."""
  on_logs, on = _run_and_scrape(
      num_devices=8, display_every=1,
      trace_events_file=str(tmp_path / "t.json"), **extra)
  off_logs, off = _run_and_scrape(num_devices=8, display_every=1,
                                  **extra)
  st_on = [(m.group(1), m.group(5)) for l in on_logs
           if (m := STEP_RE.match(l))]
  st_off = [(m.group(1), m.group(5)) for l in off_logs
            if (m := STEP_RE.match(l))]
  assert len(st_on) == 8 and st_on == st_off, (st_on, st_off)
  for a, b in zip(jax.tree.leaves(on["state"].params),
                  jax.tree.leaves(off["state"].params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  _schema_checked(str(tmp_path / "t.json"))


@pytest.fixture
def restore_compile_cache():
  """The cache config is process-global: put it back to off (the CPU
  default) after a test placed it somewhere."""
  yield
  benchmark.configure_compile_cache("cpu")


def test_compile_cache_dir_rule(monkeypatch):
  """The one resolver (benchmark.resolve_compile_cache_dir): the env
  wins and is left to jax; else the flag; else <checkout>/.jax_cache
  for --device=tpu and OFF for CPU -- never a path built from
  train_dir, a temp name, a pid or the time."""
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
  assert benchmark.resolve_compile_cache_dir("tpu") == (
      os.path.join(repo, ".jax_cache"), False)
  assert benchmark.resolve_compile_cache_dir("cpu") == (None, False)
  for device in ("tpu", "cpu"):
    assert benchmark.resolve_compile_cache_dir(device, "/x/flag") == (
        "/x/flag", False)
  monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/env")
  for device in ("tpu", "cpu"):
    for flag in (None, "/x/flag"):
      assert benchmark.resolve_compile_cache_dir(device, flag) == (
          "/x/env", True)


@pytest.mark.parametrize("with_train_dir", [False, True])
def test_env_placed_cache_is_never_touched_in_code(
    tmp_path, monkeypatch, with_train_dir):
  """JAX_COMPILATION_CACHE_DIR set: no code path sets
  jax_compilation_cache_dir -- after setup() + BenchmarkCNN(...).run(),
  with and without --train_dir (and with a --compilation_cache_dir that
  is ignored with one log line), the config still equals the env path,
  which filled during the run."""
  from jax.experimental.compilation_cache import compilation_cache as cc
  env_dir = str(tmp_path / "env_cache")
  monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
  real_update = jax.config.update
  # What jax itself does with the env var at import time.
  cc.reset_cache()
  real_update("jax_compilation_cache_dir", env_dir)
  touched = []

  def spy(name, value):
    if name == "jax_compilation_cache_dir":
      touched.append(value)
    return real_update(name, value)

  monkeypatch.setattr(jax.config, "update", spy)
  kw = dict(model="trivial", batch_size=4, num_batches=2,
            num_warmup_batches=0, device="cpu", num_devices=1,
            compilation_cache_dir=str(tmp_path / "flag_cache"))
  if with_train_dir:
    kw["train_dir"] = str(tmp_path / "train")
  logs = []
  orig = log_util.log_fn
  log_util.log_fn = logs.append
  try:
    p = benchmark.setup(params_lib.make_params(**kw))
    stats = benchmark.BenchmarkCNN(p).run()
    assert jax.config.jax_compilation_cache_dir == env_dir
  finally:
    log_util.log_fn = orig
    cc.reset_cache()
    real_update("jax_compilation_cache_dir", None)
  assert touched == []
  assert f"XLA compilation cache: {env_dir}" in logs
  assert any("--compilation_cache_dir" in l and "ignored" in l
             for l in logs)
  assert os.listdir(env_dir)
  assert not os.path.exists(str(tmp_path / "flag_cache"))
  assert not os.path.exists(str(tmp_path / "train" / "xla_cache"))
  assert stats["compile_ledger"]["entries"]


def _setup_counters(stats):
  """The compile-cache counters of everything before the timed loop."""
  blocks = [stats["span_totals"][phase]["counters"]
            for phase in (tracing.PHASE_SETUP, tracing.PHASE_WARMUP)]
  return {key: sum(b[key] for b in blocks) for key in tracing.COUNTER_KEYS}


def test_compilation_cache_flag_and_ledger_cache_hit(
    tmp_path, monkeypatch, restore_compile_cache):
  """--compilation_cache_dir on a CPU run (env unset): configured
  before the first trace; a SECOND run of the same program ledgers its
  compile episodes as cache_hit=True, by the compilation cache's own
  events (jax.monitoring; tracing.RunTrace.on_event): every request of
  the episode was a hit and nothing was written. Without the flag a CPU
  run keeps the cache OFF -- nothing lands under train_dir."""
  monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
  train_dir = str(tmp_path / "train")
  cache = str(tmp_path / "explicit_cache")
  logs1, stats1 = _run_and_scrape(num_batches=2, train_dir=train_dir,
                                  compilation_cache_dir=cache)
  assert f"XLA compilation cache: {cache}" in logs1
  assert os.listdir(cache)
  entries1 = stats1["compile_ledger"]["entries"]
  assert entries1 and all(e["cache_hit"] is False for e in entries1)
  counters1 = _setup_counters(stats1)
  assert counters1["cache_misses"] > 0 and counters1["cache_hits"] == 0
  assert counters1["backend_compiles"] == counters1["cache_requests"]
  # The same program again: keep the ledger, drop the checkpoint (a
  # RESUMED run starts from restored arrays, which is another program
  # -- the real signal says so where the old directory-is-warm guess
  # called it a hit).
  for name in os.listdir(train_dir):
    if name != tracing.LEDGER_FILENAME:
      os.unlink(os.path.join(train_dir, name))
  logs2, stats2 = _run_and_scrape(num_batches=2, train_dir=train_dir,
                                  compilation_cache_dir=cache)
  entries2 = stats2["compile_ledger"]["entries"]
  assert entries2 and all(e["cache_hit"] is True for e in entries2)
  counters2 = _setup_counters(stats2)
  assert counters2["cache_misses"] == 0
  assert counters2["cache_hits"] == counters2["cache_requests"] > 0
  # The merged on-disk ledger keeps the LAST cache_hit (a shape's
  # first run legitimately misses; later runs read as the hit they
  # were).
  data = json.load(open(os.path.join(train_dir, "compile_ledger.json")))
  assert all(row.get("cache_hit") is True
             for row in data["entries"].values())
  # No flag, no env, CPU: the cache stays off and train_dir holds none.
  t2 = str(tmp_path / "t2")
  logs3, _ = _run_and_scrape(num_batches=2, train_dir=t2)
  assert not any(l.startswith("XLA compilation cache: ") for l in logs3)
  assert not os.path.exists(os.path.join(t2, "xla_cache"))
  assert jax.config.jax_compilation_cache_dir is None


# -- the profiler sink, the always-on totals, JAX's own compile events --------

class FakeAnnotation:
  """Stands in for jax.profiler.TraceAnnotation: records construction,
  entry and exit in one shared list."""

  def __init__(self, log, name, **kwargs):
    self.log, self.name = log, name
    log.append(("new", name, kwargs))

  def __enter__(self):
    self.log.append(("enter", self.name))
    return self

  def __exit__(self, *exc):
    self.log.append(("exit", self.name))
    return False


def test_span_enters_the_injected_annotation_once_with_name_and_args():
  log = []
  factory = lambda name, **kw: FakeAnnotation(log, name, **kw)
  tr, clock = _trace(annotation=factory, step_annotation=factory)
  with tr.step("train", 7):
    with tr.span("dispatch", "train_step", step=3, first_call=False) as a:
      a["found_inside"] = 1   # results join the span, not the annotation
      clock.tick(0.5)
  assert log == [
      ("new", "train", {"step_num": 7}), ("enter", "train"),
      ("new", "kf/dispatch/train_step", {"step": 3, "first_call": False}),
      ("enter", "kf/dispatch/train_step"),
      ("exit", "kf/dispatch/train_step"), ("exit", "train")]
  # Retrospective records never reach the profiler.
  tr.add_span("device", "step", 100.0, 0.1)
  tr.instant("faults", "kill")
  assert len(log) == 6
  # An exception inside the span still leaves the annotation.
  with pytest.raises(KeyError):
    with tr.span("handle", "step"):
      raise KeyError("x")
  assert log[-2:] == [("enter", "kf/handle/step"),
                      ("exit", "kf/handle/step")]


def test_null_sink_and_default_session_enter_no_annotation(monkeypatch):
  calls = []
  monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                      lambda *a, **k: calls.append(a))
  with tracing.NULL_TRACE.span("dispatch", "train_step", step=1):
    pass
  with tracing.NULL_TRACE.step("train", 1):
    pass
  tr, _ = _trace()  # no factory injected: no profiler sink
  with tr.span("dispatch", "train_step"):
    pass
  assert calls == []
  assert tracing.NULL_TRACE.span_totals() == {}
  assert tracing.NULL_TRACE.compiles_since(
      tracing.NULL_TRACE.compile_mark()) == 0


@pytest.mark.parametrize("with_path", [False, True])
def test_span_totals_count_n_total_and_max_per_phase(tmp_path, with_path):
  tr, clock = _trace(tmp_path if with_path else None)
  for dur in (0.25, 1.0):
    with tr.span("setup", "init_state"):
      clock.tick(dur)
  tr.begin_phase(tracing.PHASE_TIMED)
  for dur in (0.002, 0.004, 0.003):
    with tr.span("fetch", "metrics"):
      clock.tick(dur)
  tr.add_span("feed", "wait", clock(), 0.5)   # retrospective counts too
  tr.instant("faults", "kill")                # instants do not
  totals = tr.span_totals()
  assert totals[tracing.PHASE_SETUP]["spans"] == {
      "setup/init_state": {"n": 2, "total_s": 1.25, "max_s": 1.0,
                           "self_s": 1.25}}
  timed = totals[tracing.PHASE_TIMED]["spans"]
  assert set(timed) == {"fetch/metrics", "feed/wait"}
  assert timed["fetch/metrics"]["n"] == 3
  assert timed["fetch/metrics"]["total_s"] == pytest.approx(0.009)
  assert timed["fetch/metrics"]["max_s"] == pytest.approx(0.004)
  assert timed["feed/wait"] == {"n": 1, "total_s": 0.5, "max_s": 0.5,
                                "self_s": 0.5}
  # The span list is kept only with a path; the totals either way.
  assert len(tr.chrome_events()) > 1 if with_path else \
      len(tr.chrome_events()) == 1


def test_monitoring_time_span_becomes_a_compile_lane_span(tmp_path):
  tr, clock = _trace(tmp_path)   # wall anchor 1000.0 == mono 100.0
  jax.monitoring.register_event_time_span_listener(tr.on_time_span)
  try:
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/jaxpr_trace_duration", 1001.0, 1001.5,
        fun_name="relu")
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/jaxpr_trace_duration", 1000.5, 1003.0,
        fun_name="per_replica_train")
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 1003.0, 1004.0,
        fun_name="jit(per_replica_train)")
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", 1004.0, 1009.0,
        fun_name="jit(per_replica_train)")
    jax.monitoring.record_event_time_span(
        "/some/other/event", 1.0, 2.0)
  finally:
    jax.monitoring.unregister_event_time_span_listener(tr.on_time_span)
  spans = [e for e in tr.chrome_events() if e["ph"] == "X"]
  assert [(e["cat"], e["name"], e["args"]["fun_name"]) for e in spans] == [
      ("compile", "jaxpr_trace", "relu"),
      ("compile", "jaxpr_trace", "per_replica_train"),
      ("compile", "jaxpr_to_mlir", "jit(per_replica_train)"),
      ("compile", "backend_compile", "jit(per_replica_train)")]
  # On the session's axis: wall 1001.0 is 1 s after the anchor.
  assert spans[0]["ts"] == pytest.approx(1001.0e6)
  assert spans[0]["dur"] == pytest.approx(0.5e6)
  # The nested trace is an event of its own but not time of its own:
  # totals count outermost intervals, so they add up to the wall spent.
  setup = tr.span_totals()[tracing.PHASE_SETUP]
  assert setup["spans"]["compile/jaxpr_trace"] == {
      "n": 2, "total_s": 2.5, "max_s": 2.5, "self_s": 2.5}
  assert setup["spans"]["compile/jaxpr_to_mlir"]["total_s"] == 1.0
  assert setup["spans"]["compile/backend_compile"]["total_s"] == 5.0
  assert setup["counters"]["backend_compiles"] == 1


def test_cache_events_move_the_counters_and_the_ledgers_cache_hit():
  tr, _ = _trace()
  hit, miss, request = (
      "/jax/compilation_cache/cache_hits",
      "/jax/compilation_cache/cache_misses",
      "/jax/compilation_cache/compile_requests_use_cache")
  jax.monitoring.register_event_listener(tr.on_event)
  try:
    # Episode 1: one request, compiled and written (a miss).
    mark = tr.compile_mark()
    jax.monitoring.record_event(request)
    jax.monitoring.record_event(miss)
    tr.on_time_span("/jax/core/compile/backend_compile_duration", 1.0, 2.0,
                    fun_name="jit(step)")
    assert tr.compiles_since(mark) == 1
    tr.note_compile("k1", "train_step", 1.0, since=mark)
    # Episode 2: two requests, both answered from the cache.
    tr.begin_phase(tracing.PHASE_TIMED)
    mark = tr.compile_mark()
    assert tr.compiles_since(mark) == 0
    for _ in range(2):
      jax.monitoring.record_event(request)
      jax.monitoring.record_event(hit)
    tr.note_compile("k2", "eval_step", 0.5, since=mark)
    # Episode 3: one of two requests missed -- not a hit. And without a
    # mark the row says nothing about the cache.
    mark = tr.compile_mark()
    jax.monitoring.record_event(request)
    jax.monitoring.record_event(hit)
    jax.monitoring.record_event(request)
    tr.note_compile("k3", "train_chunk", 0.5, since=mark)
    tr.note_compile("k4", "warm", 0.5)
  finally:
    jax.monitoring.unregister_event_listener(tr.on_event)
  entries = tr.compile_ledger()["entries"]
  assert [e.get("cache_hit") for e in entries] == [False, True, False, None]
  totals = tr.span_totals()
  assert totals[tracing.PHASE_SETUP]["counters"] == {
      "cache_hits": 0, "cache_misses": 1, "cache_requests": 1,
      "backend_compiles": 1, "gc_collections": 0}
  assert totals[tracing.PHASE_TIMED]["counters"] == {
      "cache_hits": 3, "cache_misses": 0, "cache_requests": 4,
      "backend_compiles": 0, "gc_collections": 0}


def test_run_stats_carry_span_totals_for_every_phase():
  """The main path's own boundaries, in any run (no span file): set-up
  pieces, JAX's trace / lower / compile in warm-up, and per timed
  iteration one train step with its dispatch, fetch and handling."""
  _, stats = _run_and_scrape(num_batches=6, num_warmup_batches=2,
                             display_every=1)
  totals = stats["span_totals"]
  setup = totals[tracing.PHASE_SETUP]["spans"]
  assert {"setup/build_model", "setup/make_step_fns", "setup/open_input",
          "setup/first_batch", "setup/init_state",
          "setup/broadcast_init"} <= set(setup)
  warm = totals[tracing.PHASE_WARMUP]["spans"]
  assert warm["dispatch/train_step"]["n"] == 2
  assert warm["compile/backend_compile"]["n"] >= 1
  # Outermost intervals only: trace + lower + compile fit inside the
  # first dispatch, whose wall is compile_s.
  compile_s = sum(warm[k]["total_s"] for k in (
      "compile/jaxpr_trace", "compile/jaxpr_to_mlir",
      "compile/backend_compile"))
  assert 0 < compile_s <= stats["compile_s"]
  timed = totals[tracing.PHASE_TIMED]
  for name in ("run/train", "dispatch/train_step", "fetch/metrics",
               "handle/step"):
    assert timed["spans"][name]["n"] == 6, name
  assert timed["counters"]["backend_compiles"] == 0
  assert timed["spans"]["run/train"]["total_s"] >= sum(
      timed["spans"][k]["total_s"]
      for k in ("dispatch/train_step", "handle/step"))
  # The scopes the step program names ride beside them, for the trace
  # reader to hold the device operations' op_names to.
  from kf_benchmarks_tpu import train_step
  assert stats["step_scopes"] == list(train_step.STEP_SCOPES)
  # The registry flattening leaves both out.
  from kf_benchmarks_tpu import metrics as metrics_lib
  assert not any("span_totals" in k or "step_scopes" in k
                 for k in metrics_lib.flatten_stats(stats))


# -- parents, self times, the step account -------------------------------------

MS = 2.0 ** -10   # a binary "millisecond": sums of these are exact


def _spans_by_name(tr):
  return {e["name"]: e for e in tr.chrome_events() if e["ph"] == "X"}


def test_live_spans_record_parent_and_self_time(tmp_path):
  tr, clock = _trace(tmp_path)
  with tr.span("handle", "step"):
    clock.tick(2 * MS)
    with tr.span("handle", "log_line"):
      clock.tick(8 * MS)
      with tr.span("checkpoint", "save"):
        clock.tick(16 * MS)
    with tr.span("fetch", "metrics"):     # a sibling of log_line
      clock.tick(4 * MS)
    # Retrospective records take no part: nobody's child, and the time
    # they cover stays their enclosing span's own.
    tr.add_span("device", "chunk", clock(), 32 * MS)
    clock.tick(MS)
  spans = _spans_by_name(tr)
  ids = {name: e["args"]["span_id"] for name, e in spans.items()}
  assert "parent_id" not in spans["step"]["args"]
  assert spans["log_line"]["args"]["parent_id"] == ids["step"]
  assert spans["save"]["args"]["parent_id"] == ids["log_line"]
  assert spans["metrics"]["args"]["parent_id"] == ids["step"]
  assert "parent_id" not in spans["chunk"]["args"]
  rows = tr.span_totals()[tracing.PHASE_SETUP]["spans"]
  assert rows["handle/step"]["total_s"] == 31 * MS
  assert rows["handle/step"]["self_s"] == 3 * MS      # less 24 and 4
  assert rows["handle/log_line"]["total_s"] == 24 * MS
  assert rows["handle/log_line"]["self_s"] == 8 * MS
  assert rows["checkpoint/save"]["self_s"] == 16 * MS
  assert rows["fetch/metrics"]["self_s"] == 4 * MS
  assert rows["device/chunk"]["self_s"] == 32 * MS
  assert tracing.validate_chrome_trace(
      {"traceEvents": tr.chrome_events()}) == []


def test_spans_nest_per_thread(tmp_path):
  """The feeder's worker thread has a stack of its own: what it opens
  while the main thread is inside a span is nobody's child, and takes
  nothing off that span's self time."""
  import threading
  tr, clock = _trace(tmp_path)
  inside, done = threading.Event(), threading.Event()

  def worker():
    with tr.span("feed", "fetch"):
      with tr.span("feed", "h2d"):
        inside.set()
        done.wait(5.0)

  t = threading.Thread(target=worker)
  with tr.step("train", 1):
    with tr.span("dispatch", "train_step"):
      t.start()
      assert inside.wait(5.0)
      assert tr.open_spans() == ["run/train", "dispatch/train_step"]
      clock.tick(4 * MS)
      done.set()
      t.join()
  spans = _spans_by_name(tr)
  assert "parent_id" not in spans["fetch"]["args"]
  assert spans["h2d"]["args"]["parent_id"] == \
      spans["fetch"]["args"]["span_id"]
  assert spans["train_step"]["args"]["parent_id"] == \
      spans["train"]["args"]["span_id"]
  rows = tr.span_totals()[tracing.PHASE_SETUP]["spans"]
  assert rows["dispatch/train_step"]["self_s"] == \
      rows["dispatch/train_step"]["total_s"]
  assert tr.open_spans() == []


def _iteration(tr, clock, step, plant=None, seconds=0.0):
  """One made-up iteration of the timed loop, a binary millisecond in
  every place; ``seconds`` more in the place ``plant`` names."""
  def spend(place):
    clock.tick(MS + (seconds if plant == place else 0.0))
  with tr.step("train", step):
    with tr.span("dispatch", "train_step", step=step):
      spend("dispatch/train_step")
    spend("self")                               # the bare loop body
    with tr.span("fetch", "metrics"):
      spend("fetch/metrics")
    with tr.span("handle", "step"):
      spend("handle/step")
      with tr.span("handle", "log_line"):
        spend("handle/log_line")


@pytest.mark.parametrize("place", [
    "dispatch/train_step", "fetch/metrics", "handle/step",
    "handle/log_line", "self"])
def test_step_account_adds_up_and_names_the_planted_stall(place):
  tr, clock = _trace()
  # Iterations outside the timed loop (warm-up has none today) leave no
  # row.
  _iteration(tr, clock, 99)
  assert tr.step_account()["iterations"] == 0
  tr.begin_phase(tracing.PHASE_TIMED)
  for step in range(5, 17):                     # a resumed run: step 5 first
    _iteration(tr, clock, step, place if step == 11 else None, 51 * MS)
  account = tr.step_account()
  assert account["iterations"] == 12 and len(account["rows"]) == 12
  assert account["median_s"] == 5 * MS
  for row in account["rows"]:
    assert set(row) == {"step", "t0", "dur_s", "by_span"}
    assert set(row["by_span"]) == {
        "dispatch/train_step", "fetch/metrics", "handle/step",
        "handle/log_line", "self"}
    assert sum(row["by_span"].values()) == row["dur_s"]   # exactly
  assert [r["step"] for r in account["rows"]] == list(range(5, 17))
  assert account["rows"][1]["t0"] == account["rows"][0]["t0"] + 5 * MS
  (stall,) = account["stalls"]
  assert stall["step"] == 11 and stall["timed_step"] == 7
  assert stall["dur_s"] == 56 * MS and stall["excess_s"] == 51 * MS
  assert stall["under"] == place
  assert stall["by_span"][place] == 52 * MS
  (line,) = tr.stall_lines()
  assert line.startswith(
      "host stall: timed step 7 took %.1f ms (median %.1f): %s %.1f, " % (
          56e3 * MS, 5e3 * MS, place, 52e3 * MS)), line
  assert line.endswith("; then %.1f, %.1f" % (5e3 * MS, 5e3 * MS)), line
  assert stall["then_s"] == [5 * MS, 5 * MS]
  assert "images/sec" not in line and "\n" not in line


def test_no_stall_no_line_and_at_most_eight_lines_longest_first():
  tr, clock = _trace()
  tr.begin_phase(tracing.PHASE_TIMED)
  for step in range(40):
    _iteration(tr, clock, step)
  assert tr.step_account()["stalls"] == [] and tr.stall_lines() == []
  for step in range(40, 50):
    _iteration(tr, clock, step, "self", (step - 30) * MS)
  account = tr.step_account()
  assert [s["step"] for s in account["stalls"]] == list(range(49, 39, -1))
  lines = tr.stall_lines()
  assert len(lines) == tracing.MAX_STALL_LINES == 8
  assert lines[0].startswith("host stall: timed step 50 took ")
  assert lines[-1].startswith("host stall: timed step 43 took ")
  # The run's last iteration has none after it.
  assert account["stalls"][0]["then_s"] == [] and lines[0].endswith("; then -")


def test_step_account_ring_keeps_the_newest_rows():
  tr, clock = _trace()
  tr.begin_phase(tracing.PHASE_TIMED)
  extra = 10
  for step in range(tracing.ACCOUNT_ROWS + extra):
    with tr.step("train", step):
      clock.tick(MS)
  account = tr.step_account()
  assert tracing.ACCOUNT_ROWS == 4096
  assert account["iterations"] == 4096 + extra
  assert len(account["rows"]) == 4096
  assert account["rows"][0]["step"] == extra
  assert account["rows"][-1]["by_span"] == {"self": MS}


def test_host_gc_is_a_span_of_the_thread_the_collector_ran_on(tmp_path):
  """The real collector under the real clock: ``activate`` installs the
  one ``gc.callbacks`` hook, a full collection inside an iteration is a
  ``host/gc`` span with its generation, a child of the iteration, named
  by the account; ``deactivate`` takes the hook out."""
  import gc

  class Node:
    def __init__(self):
      self.me = self

  hooks = list(gc.callbacks)
  tr = tracing.RunTrace(path=str(tmp_path / "t.json"))
  tracing.activate(tr)
  try:
    assert tracing._on_gc in gc.callbacks
    tracing.activate(tr)                        # installed once
    assert gc.callbacks.count(tracing._on_gc) == 1
    tr.begin_phase(tracing.PHASE_TIMED)
    for step in range(1, 8):
      garbage = [Node() for _ in range(200_000 if step == 4 else 0)]
      with tr.step("train", step):
        del garbage
        if step == 4:
          gc.collect()
  finally:
    tracing.deactivate()
  assert gc.callbacks == hooks
  trains = {e["args"]["step_num"]: e["args"]["span_id"]
            for e in tr.chrome_events() if e["name"] == "train"}
  # (building the garbage drew passes of its own, outside any iteration)
  (full,) = [e for e in tr.chrome_events()
             if e["name"] == "gc" and e["args"]["generation"] == 2
             and e["args"].get("parent_id") == trains[4]]
  assert full["cat"] == "host" and full["args"]["collected"] >= 200_000
  timed = tr.span_totals()[tracing.PHASE_TIMED]
  assert timed["counters"]["gc_collections"] == timed["spans"]["host/gc"]["n"]
  account = tr.step_account()
  stall = account["stalls"][0]
  assert stall["timed_step"] == 4 and stall["under"] == "host/gc"
  assert tr.stall_lines()[0].startswith(
      "host stall: timed step 4 took ")
  assert ": host/gc " in tr.stall_lines()[0]
  # A pass with no session active goes nowhere, and raises nothing.
  tracing._on_gc("start", {"generation": 0})
  # A pass that began before the hook went in has no span to close.
  tr.on_gc("stop", {"generation": 0, "collected": 0})


def test_null_sink_answers_the_account_calls():
  null = tracing.NULL_TRACE
  assert null.step_account() == {"iterations": 0, "median_s": None,
                                 "rows": [], "stalls": []}
  assert null.stall_lines() == [] and null.open_spans() == []
  tr, _ = _trace()
  assert tr.step_account() == null.step_account()
  assert tr.stall_lines() == [] and tr.open_spans() == []


def test_e2e_step_account_names_a_slow_listener():
  """Through ``BenchmarkCNN.run()``: one row per timed dispatch, every
  row adding up, and a listener of the step line that sleeps is reported
  under ``handle/log_line`` -- by the iteration that PRINTS the line,
  which is the one that dispatches two steps later (the lag-2 metric
  pipeline). The sleeper changes no loss: the account is the host's."""
  import time as time_lib
  slow_line, lines = 6, {}
  orig = log_util.log_fn

  def run(sleep_s):
    logs = []

    def listener(msg):
      m = STEP_RE.match(str(msg))
      if m and int(m.group(1)) == slow_line:
        time_lib.sleep(sleep_s)
      logs.append(str(msg))

    log_util.log_fn = listener
    try:
      p = params_lib.make_params(
          model="trivial", num_batches=12, num_warmup_batches=1,
          device="cpu", display_every=1, batch_size=4)
      stats = benchmark.BenchmarkCNN(p).run()
    finally:
      log_util.log_fn = orig
    return logs, stats

  logs, stats = run(0.25)
  account = stats["step_account"]
  assert account["iterations"] == 12 == len(account["rows"])
  assert account["iterations"] == \
      stats["span_totals"][tracing.PHASE_TIMED]["spans"][
          "dispatch/train_step"]["n"]
  for row in account["rows"]:
    assert abs(sum(row["by_span"].values()) - row["dur_s"]) < 1e-6
  stall = account["stalls"][0]
  assert stall["timed_step"] == slow_line + 2
  assert stall["under"] == "handle/log_line"
  assert stall["by_span"]["handle/log_line"] >= 0.25
  stall_lines = [l for l in logs if l.startswith("host stall: ")]
  assert stall_lines and stall_lines[0].startswith(
      "host stall: timed step %d took " % (slow_line + 2))
  assert ": handle/log_line " in stall_lines[0]
  # Whole lines, after the banner, never inside a step line.
  marker_lines = [l for l in logs if "images/sec:" in l]
  assert all(STEP_RE.match(l) or TOTAL_RE.match(l) for l in marker_lines)
  assert logs.index(stall_lines[0]) > max(
      i for i, l in enumerate(logs) if TOTAL_RE.match(l))
  # handle/step's own time no longer holds the listener's.
  timed = stats["span_totals"][tracing.PHASE_TIMED]["spans"]
  assert timed["handle/log_line"]["n"] == 12
  assert timed["handle/step"]["self_s"] < 0.25 <= \
      timed["handle/step"]["total_s"]
  # The two retrospective spans nothing read are gone.
  for phase in stats["span_totals"].values():
    assert not {"run/warmup", "run/timed_loop"} & set(phase["spans"])
  quiet_logs, quiet = run(0.0)
  losses = lambda ls: [m.group(5) for l in ls if (m := STEP_RE.match(l))]
  assert len(losses(logs)) == 12 and losses(logs) == losses(quiet_logs)
  from kf_benchmarks_tpu import metrics as metrics_lib
  assert not any("step_account" in k
                 for k in metrics_lib.flatten_stats(stats))


def test_program_spans_land_in_the_profilers_host_plane(tmp_path):
  """ONE clock: with a jax.profiler capture open around a tiny run, the
  program's live spans are events of plane /host:CPU, in order within
  each train step -- dispatch, then the blocking fetch, then the
  handling of the resolved step -- and carry their arguments."""
  from jax.profiler import ProfileData
  trace_dir = str(tmp_path / "prof")
  jax.profiler.start_trace(trace_dir)
  try:
    _run_and_scrape(num_batches=6, num_warmup_batches=1, display_every=1)
  finally:
    jax.profiler.stop_trace()
  (path,) = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
  host = [p for p in ProfileData.from_file(path).planes
          if p.name == "/host:CPU"]
  assert len(host) == 1
  events = sorted(
      ((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
       for line in host[0].lines for e in line.events
       if e.name == "train" or e.name.startswith("kf/")),
      key=lambda e: (e[0], -e[1]))
  names = [e[2] for e in events]
  assert {"kf/setup/init_state", "kf/setup/make_step_fns",
          "kf/dispatch/train_step", "kf/fetch/metrics",
          "kf/handle/step", "train"} <= set(names)
  steps = [e for e in events if e[2] == "train"]
  assert [e[3]["step_num"] for e in steps] == [1, 2, 3, 4, 5, 6]
  for start, end, _, _ in steps[2:]:   # the lag-2 ring is full from here
    # (a pass of the garbage collector may fall anywhere)
    inside = [e[2] for e in events
              if e[2] not in ("train", "kf/host/gc")
              and start <= e[0] and e[1] <= end]
    assert inside == ["kf/dispatch/train_step", "kf/fetch/metrics",
                      "kf/handle/step", "kf/handle/log_line"], inside
  dispatches = [e for e in events if e[2] == "kf/dispatch/train_step"]
  assert [e[3]["step"] for e in dispatches[-6:]] == [0, 1, 2, 3, 4, 5]
  assert dispatches[0][3]["first_call"] == 1


@pytest.mark.parametrize("overrides", [
    dict(variable_update="replicated"),
    dict(variable_update="kungfu", kungfu_option="sync_sgd"),
    dict(variable_update="replicated", shard_optimizer_state=True),
], ids=["replicated", "kungfu_sync_sgd", "sharded_state"])
def test_step_program_names_its_phases(overrides):
  """The lowered step's op_names carry the four scopes the benchmark's
  trace reader keys on: one ``forward`` scope separates the passes
  (jvp going forward, its transpose coming back)."""
  from kf_benchmarks_tpu.analysis import contracts
  p = params_lib.make_params(model="trivial", device="cpu", num_devices=8,
                             num_batches=2, **overrides)
  _, lowered = contracts.lower_step_program(benchmark.BenchmarkCNN(p))
  op_names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
      debug_info=True)))
  from kf_benchmarks_tpu import train_step
  for scope in ("jvp(forward)/", "transpose(jvp(forward))/") + tuple(
      s + "/" for s in train_step.STEP_SCOPES if s != "forward"):
    assert any(scope in name for name in op_names), scope
  # ... and they are all it names: every scope in the source is declared
  # (the reader raises on a scope the program does not declare).
  source = open(train_step.__file__, encoding="utf-8").read()
  assert set(re.findall(r'named_scope\("([a-z_]+)"\)', source)) == set(
      train_step.STEP_SCOPES)
  # The model lives under the scope in both directions.
  assert any(n.startswith("jvp(forward)/") and "affine" in n
             for n in op_names)
  assert any(n.startswith("transpose(jvp(forward))/") and "affine" in n
             for n in op_names)
