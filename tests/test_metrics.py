"""Metrics fabric (kf_benchmarks_tpu/metrics.py).

Reference-style layering (SURVEY 7.1):
  * pure-unit: registry typing + Prometheus exposition, run-record
    store (validation, baseline auto-promotion, merge), regression
    sentinel on synthetic run histories, the metrics-schema audit.
  * log-scraping / live e2e: a CPU-mesh training run with
    ``--metrics_port`` serves schema-valid Prometheus text and a
    watchdog-backed /healthz WHILE training; no socket binds when the
    flag is unset.
  * equivalence: per-step f32 losses and trained params bit-identical
    endpoint-on vs off through ``--steps_per_dispatch`` and
    ``--shard_optimizer_state`` (the host-only contract; the
    program-shape half is the auditor's metrics-twin rule against the
    ``metrics_on`` golden).
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

import bench
from kf_benchmarks_tpu import metrics
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import validation

from tests.test_benchmark import STEP_RE, _run_and_scrape

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
  s = socket.socket()
  s.bind(("127.0.0.1", 0))
  port = s.getsockname()[1]
  s.close()
  return port


def _get(url: str, timeout: float = 2.0) -> str:
  return urllib.request.urlopen(url, timeout=timeout).read().decode()


def _record(value, run_id, fingerprint="fp-a", metric="x_per_sec",
            platform="tpu", t_wall=None, **kw):
  return metrics.run_record(
      metric=metric, value=value, unit="images/sec",
      fingerprint=fingerprint, run_id=run_id, platform=platform,
      t_wall=t_wall, **kw)


# -- registry -----------------------------------------------------------------

def test_registry_is_typed_by_the_schema():
  reg = metrics.MetricRegistry()
  reg.set("images_per_sec", 100.0)
  reg.inc("step")
  reg.inc("step", 2)
  reg.observe("feed_wait_s", 0.25)
  reg.set("mesh_shape", "8x1")
  snap = reg.snapshot()
  assert snap["images_per_sec"] == 100.0
  assert snap["step"] == 3.0
  assert snap["mesh_shape"] == "8x1"
  assert snap["feed_wait_s/count"] == 1
  # Unregistered keys are rejected -- the registry IS the schema gate.
  with pytest.raises(ValueError, match="unregistered metric key"):
    reg.set("made_up_metric", 1.0)
  # Kind misuse is rejected, not coerced.
  with pytest.raises(ValueError, match="counter-only"):
    reg.inc("images_per_sec")
  with pytest.raises(ValueError, match="histogram-only"):
    reg.observe("images_per_sec", 1.0)
  with pytest.raises(ValueError, match="use observe"):
    reg.set("feed_wait_s", 1.0)


def test_prometheus_render_is_schema_valid():
  reg = metrics.MetricRegistry()
  reg.set("images_per_sec", 123.456)
  reg.inc("num_steps", 8)
  for v in (0.01, 0.02, 0.03, 0.04):
    reg.observe("feed_wait_s", v)
  reg.set("run_id", 'run-"x"\n')
  text = reg.render()
  assert metrics.validate_prometheus_text(text) == []
  assert "kf_images_per_sec 123.456" in text
  assert "# TYPE kf_num_steps counter" in text
  # Histogram-kind keys render as TRUE cumulative histograms (round
  # 21): le-bucket counts monotone to +Inf == _count, sum preserved.
  assert "# TYPE kf_feed_wait_s histogram" in text
  assert 'kf_feed_wait_s_bucket{le="0.01"} 1' in text
  assert 'kf_feed_wait_s_bucket{le="0.025"} 2' in text
  assert 'kf_feed_wait_s_bucket{le="0.05"} 4' in text
  assert 'kf_feed_wait_s_bucket{le="+Inf"} 4' in text
  assert "kf_feed_wait_s_sum 0.1" in text
  assert "kf_feed_wait_s_count 4" in text
  # Info values collapse onto one labeled row, label-escaped.
  assert 'kf_run_info{run_id="run-\\"x\\"\\n"} 1' in text
  # The health/ namespace sanitizes onto a legal exposition name.
  reg.set("health/grad_norm", 1.0)
  assert "kf_health_grad_norm 1" in reg.render()


def test_validate_prometheus_text_rejects_malformed():
  assert metrics.validate_prometheus_text("not a metric line!") != []
  assert metrics.validate_prometheus_text("# TYPE kf_x nonsense") != []
  assert metrics.validate_prometheus_text(
      "kf_x 1\nkf_y{a=\"b\"} 2.5\nkf_z NaN\n") == []


def test_histogram_bins_are_bounded_and_exact():
  # Bucket-count storage (round 21): memory is fixed at
  # len(bounds) + 1 bins regardless of observation volume, and count /
  # sum stay exact (no decimation).
  reg = metrics.MetricRegistry()
  for i in range(1000):
    reg.observe("feed_wait_s", float(i))  # most overflow to +Inf
  snap = reg.snapshot()
  assert snap["feed_wait_s/count"] == 1000
  assert snap["feed_wait_s/sum"] == sum(float(i) for i in range(1000))
  bins = reg._hists["feed_wait_s"][2]
  assert len(bins) == len(metrics.HIST_BUCKETS_SECONDS) + 1
  assert sum(bins) == 1000
  # Values past the last bound land in the +Inf bin.
  assert bins[-1] == 1000 - sum(
      1 for i in range(1000) if i <= metrics.HIST_BUCKETS_SECONDS[-1])


def test_active_registry_and_null_sink():
  assert metrics.active() is metrics.NULL_REGISTRY
  # The null sink accepts the full producer surface (deep producers
  # publish unconditionally) -- including keys nobody registered.
  metrics.active().set("anything", 1)
  metrics.active().inc("anything")
  metrics.active().observe("anything", 1.0)
  reg = metrics.MetricRegistry()
  try:
    assert metrics.activate(reg) is reg
    assert metrics.active() is reg
  finally:
    metrics.deactivate()
  assert metrics.active() is metrics.NULL_REGISTRY


def test_flatten_and_publish_stats():
  stats = {
      "images_per_sec": 100.0,
      "num_steps": 8,
      "state": object(),            # bookkeeping: dropped
      "unknown_field": 3.0,         # unregistered: dropped
      "compile_s": None,            # unset: dropped
      "mesh_shape": "4x2",
      "health": {"max_grad_norm": 2.0, "watchdog_stalls": 0},
      "latency_percentiles": {"chunk_wall_p50": 0.1,
                              "feed_wait_p99": None},
      "compile_ledger": {"shapes": 2, "total_compile_s": 3.5,
                         "entries": [{"key": "k"}]},
  }
  flat = metrics.flatten_stats(stats)
  assert flat["images_per_sec"] == 100.0
  assert flat["health/max_grad_norm"] == 2.0
  assert flat["chunk_wall_p50"] == 0.1
  assert flat["compile_ledger/shapes"] == 2.0
  assert flat["mesh_shape"] == "4x2"
  for absent in ("state", "unknown_field", "compile_s",
                 "feed_wait_p99"):
    assert absent not in flat
  reg = metrics.MetricRegistry()
  metrics.publish_stats(reg, stats)
  assert reg.snapshot()["compile_ledger/total_compile_s"] == 3.5
  assert metrics.validate_prometheus_text(reg.render()) == []


def test_benchmark_logger_mirrors_registered_names(tmp_path):
  """The reference-schema BenchmarkLogger (observability.py) mirrors
  registered metric names into the active registry -- one emission,
  two sinks -- mapping summary names through the health/ namespace;
  reference-only names stay file-only, and without a session the
  mirror is a no-op."""
  from kf_benchmarks_tpu import observability
  logger = observability.BenchmarkLogger(str(tmp_path))
  reg = metrics.MetricRegistry()
  try:
    metrics.activate(reg)
    logger.log_metric("eval_images_per_sec", 123.0)
    logger.log_metric("max_grad_norm", 2.5)
    logger.log_metric("current_examples_per_sec", 9.0)
  finally:
    metrics.deactivate()
  snap = reg.snapshot()
  assert snap["eval_images_per_sec"] == 123.0
  assert snap["health/max_grad_norm"] == 2.5
  assert "current_examples_per_sec" not in snap
  logger.log_metric("eval_images_per_sec", 1.0)  # sessionless: no-op
  lines = open(os.path.join(str(tmp_path), "metric.log")).read()
  assert lines.count('"name"') >= 4  # every emission still hits the file


# -- endpoint (unit) ----------------------------------------------------------

def test_metrics_server_serves_registry_and_healthz():
  reg = metrics.MetricRegistry()
  reg.set("images_per_sec", 42.0)
  server = metrics.MetricsServer(
      reg, 0, healthz_fn=lambda: {"status": "ok", "watchdog_stalls": 0})
  try:
    base = f"http://127.0.0.1:{server.port}"
    text = _get(base + "/metrics")
    assert metrics.validate_prometheus_text(text) == []
    assert "kf_images_per_sec 42" in text
    health = json.loads(_get(base + "/healthz"))
    assert health == {"status": "ok", "watchdog_stalls": 0}
    with pytest.raises(urllib.error.HTTPError):
      _get(base + "/other")
    # Scrapes read LIVE values, not a bind-time snapshot.
    reg.set("images_per_sec", 43.0)
    assert "kf_images_per_sec 43" in _get(base + "/metrics")
  finally:
    server.close()


def test_metrics_server_healthz_never_raises():
  reg = metrics.MetricRegistry()

  def broken():
    raise RuntimeError("probe bug")

  server = metrics.MetricsServer(reg, 0, healthz_fn=broken)
  try:
    health = json.loads(_get(f"http://127.0.0.1:{server.port}/healthz"))
    assert health["status"] == "error"
  finally:
    server.close()


def test_resolve_port_per_rank_offset():
  assert metrics.resolve_port(9100, 0) == 9100
  assert metrics.resolve_port(9100, 3) == 9103


# -- run-record store ---------------------------------------------------------

def test_run_record_validates(tmp_path):
  rec = _record(100.0, "r1")
  assert metrics.validate_record(rec) == []
  bad = dict(rec, value=float("nan"))
  assert any("value" in p for p in metrics.validate_record(bad))
  bad = dict(rec, schema_version=99)
  assert any("schema_version" in p for p in metrics.validate_record(bad))
  bad = dict(rec, snapshot={"not_a_registered_key": 1.0})
  assert any("snapshot key" in p for p in metrics.validate_record(bad))
  store = metrics.RunStore(str(tmp_path))
  with pytest.raises(ValueError, match="invalid run record"):
    store.append(bad)


def test_store_appends_and_queries(tmp_path):
  store = metrics.RunStore(str(tmp_path))
  store.append(_record(100.0, "r1", t_wall=1.0))
  store.append(_record(90.0, "r2", t_wall=2.0))
  store.append(_record(5.0, "r3", fingerprint="fp-b", t_wall=3.0))
  assert len(store.records()) == 3
  rows = store.query(fingerprint="fp-a")
  assert [r["run_id"] for r in rows] == ["r1", "r2"]
  assert store.has_run("r3", "x_per_sec")
  assert not store.has_run("r9", "x_per_sec")
  # A torn trailing line (crashed writer) is skipped, not fatal.
  with open(store.path, "a") as f:
    f.write('{"torn')
  assert len(store.records()) == 3


def test_first_real_chip_record_promotes_to_baseline(tmp_path):
  store = metrics.RunStore(str(tmp_path))
  # cpu-platform rows are NEVER baseline-eligible.
  r2 = store.append(_record(2.0, "cpu2", platform="cpu"))
  assert not r2["baseline"]
  # The first real-chip record per fingerprint self-promotes...
  r3 = store.append(_record(100.0, "chip1", platform="tpu"))
  assert r3["baseline"]
  # ...later chip records do not, but a new fingerprint's first does.
  r4 = store.append(_record(101.0, "chip2", platform="tpu"))
  assert not r4["baseline"]
  r5 = store.append(_record(7.0, "chip3", platform="tpu",
                            fingerprint="fp-b"))
  assert r5["baseline"]


def test_store_merge_dedups(tmp_path):
  a = metrics.RunStore(str(tmp_path / "a"))
  b = metrics.RunStore(str(tmp_path / "b"))
  a.append(_record(1.0, "r1", t_wall=1.0))
  shared = _record(2.0, "r2", t_wall=2.0)
  a.append(shared)
  b.append(shared)
  b.append(_record(3.0, "r3", t_wall=3.0))
  merged = metrics.RunStore.merge([a.path, b.path])
  assert [r["run_id"] for r in merged] == ["r1", "r2", "r3"]


# -- regression sentinel ------------------------------------------------------

def _history(values, fingerprint="fp-a", platform="tpu"):
  return [_record(v, f"h{i}", fingerprint=fingerprint,
                  platform=platform, t_wall=float(i))
          for i, v in enumerate(values)]


def test_sentinel_flags_seeded_20pct_drop():
  hist = _history([1000, 1010, 990, 1005, 995, 1002])
  fresh = _record(0.8 * 1000, "fresh")
  v = metrics.check_regression(hist, fresh)
  assert v["status"] == "regression"
  line = metrics.verdict_line(v)
  assert line.startswith("regression check: REGRESSION")
  assert "x_per_sec" in line


def test_sentinel_quiet_under_5pct_noise():
  # +-5% run-to-run noise around 1000: every fresh value drawn from the
  # same band stays quiet (the MAD bar adapts to the measured noise).
  rng = np.random.RandomState(7)
  vals = [1000.0 * (1 + rng.uniform(-0.05, 0.05)) for _ in range(12)]
  hist = _history(vals)
  for draw in (950.0, 1050.0, 1000.0):
    v = metrics.check_regression(hist, _record(draw, "fresh"))
    assert v["status"] == "ok", (draw, v)


def test_sentinel_noise_free_history_floors_the_bar():
  hist = _history([1000.0] * 6)  # MAD = 0: the relative floor holds
  assert metrics.check_regression(
      hist, _record(999.0, "fresh"))["status"] == "ok"
  assert metrics.check_regression(
      hist, _record(800.0, "fresh"))["status"] == "regression"


def test_sentinel_never_compares_across_fingerprints():
  hist = _history([1000] * 6, fingerprint="fp-other")
  v = metrics.check_regression(hist, _record(1.0, "fresh"))
  assert v["status"] == "no_history"
  assert "NO HISTORY" in metrics.verdict_line(v)


def test_sentinel_excludes_the_fresh_run_itself():
  hist = _history([1000] * 5)
  fresh = _record(750.0, "h0")  # same run_id as a history row
  v = metrics.check_regression(hist + [fresh], fresh)
  assert v["n"] == 4  # h0 dropped: a run never judges itself


# -- bench.py sentinel leg ----------------------------------------------------

def _bench_record(value):
  return {"metric": "resnet50_synthetic_images_per_sec", "value": value,
          "unit": "images/sec",
          "vs_baseline": round(value / bench.BASELINE_IMAGES_PER_SEC, 3),
          "platform": "tpu", "device_kind": "TPU v5 lite",
          "device_count": 1, "git_rev": "abc1234"}


def _seed_chip_history(store_dir, values):
  """A store with a tight chip history of the headline bench."""
  store = metrics.RunStore(str(store_dir))
  for i, v in enumerate(values):
    store.append(metrics.run_record(
        metric="resnet50_synthetic_images_per_sec", value=v,
        unit="images/sec", fingerprint=metrics.bench_fingerprint(),
        run_id=f"seed-{i}", platform="tpu", t_wall=float(i)))


def test_bench_check_regression_exit_codes(tmp_path, capsys):
  """Acceptance: bench.py --check-regression exits nonzero on a seeded
  20% regression against a seeded store, zero on a healthy value
  against the same synthetic history."""
  _seed_chip_history(tmp_path, [2400, 2410, 2390, 2405, 2395])
  rc_bad = bench.record_and_check(_bench_record(0.8 * 2400),
                                  str(tmp_path), True)
  assert rc_bad == 1
  assert "regression check: REGRESSION" in capsys.readouterr().err
  rc_ok = bench.record_and_check(_bench_record(2402.0),
                                 str(tmp_path), True)
  assert rc_ok == 0
  assert "regression check: OK" in capsys.readouterr().err
  # Both runs were recorded either way (the store is the trajectory's
  # memory, sentinel on or off).
  assert len(metrics.RunStore(str(tmp_path)).records()) == 7


def test_bench_no_history_is_not_a_failure(tmp_path, capsys):
  rc = bench.record_and_check(_bench_record(2400.0),
                              str(tmp_path), True)
  assert rc == 0
  err = capsys.readouterr().err
  assert "NO HISTORY" in err
  # The first chip record of a fingerprint self-promoted.
  assert "promoted to baseline" in err
  recs = metrics.RunStore(str(tmp_path)).records()
  assert len(recs) == 1 and recs[0]["baseline"]


def test_bench_record_attribution(tmp_path):
  rc = bench.record_and_check(_bench_record(2400.0),
                              str(tmp_path), False,
                              run_id="run-shared-with-trace")
  assert rc == 0
  rec = metrics.RunStore(str(tmp_path)).records()[0]
  # Platform, device_kind and device count come from the bench line
  # (jax.devices()), never from a flag.
  assert rec["platform"] == "tpu"
  assert rec["snapshot"]["device_kind"] == "TPU v5 lite"
  assert rec["snapshot"]["device_count"] == 1
  # The record carries the RUN'S id (bench.main threads the trace
  # session's stats["run_id"] through), so it joins the run's trace
  # and flight-recorder artifacts.
  assert rec["run_id"] == "run-shared-with-trace"
  assert rec["git_rev"] == "abc1234"
  # Not a version gate: the record must ATTRIBUTE the run to the jax
  # version it executed under (an XLA upgrade re-times everything).
  assert rec["jax_version"] == jax.__version__


def test_bench_fingerprint_is_stable_and_follows_resolved_params():
  assert metrics.bench_fingerprint() == metrics.bench_fingerprint()
  tuned = params_lib.make_params(
      **dict(metrics.bench_params_kwargs(), steps_per_dispatch=4))
  assert metrics.bench_fingerprint() != metrics.bench_fingerprint(
      params=tuned)


# -- schema audit -------------------------------------------------------------

def test_schema_audit_clean_at_head():
  problems = metrics.schema_audit(REPO)
  assert problems == [], "\n".join(problems)


def test_schema_audit_catches_seeded_problems(tmp_path):
  # An invalid store record is named.
  store = metrics.RunStore(str(tmp_path))
  os.makedirs(store.dir, exist_ok=True)
  with open(store.path, "w") as f:
    f.write(json.dumps({"metric": "m", "value": 1.0,
                        "schema_version": 99}) + "\n")
  problems = metrics.schema_audit(str(tmp_path))
  assert any("schema_version" in p for p in problems)
  assert metrics.main(["audit", "--repo", str(tmp_path)]) == 1


def test_schema_covers_tracing_and_health_namespaces():
  from kf_benchmarks_tpu import tracing
  for key in tracing.SAMPLE_KEYS:
    for q in tracing.QUANTILES:
      assert f"{key}_p{q}" in metrics.SCHEMA
  from kf_benchmarks_tpu import telemetry
  for k in telemetry.HEALTH_KEYS:
    assert metrics.health_key(k) in metrics.SCHEMA


# -- flag validation ----------------------------------------------------------

@pytest.mark.parametrize("mode", ["eval", "forward_only"])
@pytest.mark.parametrize("flag", [{"metrics_port": 9100},
                                  {"run_store_dir": "/tmp/s"}])
def test_metrics_flags_are_training_only(mode, flag):
  p = params_lib.make_params(model="trivial", device="cpu",
                             **{mode: True}, **flag)
  with pytest.raises(validation.ParamError):
    validation.validate_cross_flags(p)


# -- live e2e -----------------------------------------------------------------

def test_e2e_endpoint_serves_during_cpu_mesh_run(tmp_path):
  """Acceptance: with --metrics_port set, /metrics serves valid
  Prometheus text and /healthz watchdog+recorder state WHILE a CPU-mesh
  run trains; the step lines stay scrape-clean; the run record lands in
  the store."""
  port = _free_port()
  out = {}

  def run():
    out["result"] = _run_and_scrape(
        num_batches=48, display_every=1, metrics_port=port,
        health_stats=True, run_store_dir=str(tmp_path),
        train_dir=str(tmp_path / "train"))

  thread = threading.Thread(target=run)
  thread.start()
  base = f"http://127.0.0.1:{port}"
  scraped = health = None
  deadline = time.monotonic() + 120
  try:
    while time.monotonic() < deadline and thread.is_alive():
      try:
        text = _get(base + "/metrics", timeout=1)
        if "kf_step" in text:
          scraped = text
          health = json.loads(_get(base + "/healthz", timeout=1))
          break
      except (urllib.error.URLError, OSError):
        pass
      time.sleep(0.1)
  finally:
    thread.join()
  assert scraped is not None, "never scraped a mid-run /metrics"
  assert metrics.validate_prometheus_text(scraped) == []
  assert "kf_loss" in scraped and "kf_health_grad_norm" in scraped
  assert "kf_run_info" in scraped
  assert health["status"] in ("ok", "stalled")
  assert "watchdog_stalls" in health
  logs, stats = out["result"]
  assert any(l.startswith("metrics endpoint: http://127.0.0.1:")
             for l in logs)
  # Scrape guard: the endpoint lines are whole lines; step lines intact.
  assert sum(1 for l in logs if STEP_RE.match(l)) == 48
  # The run record landed, keyed on the train fingerprint, validating.
  recs = metrics.RunStore(str(tmp_path)).records()
  assert len(recs) == 1
  assert recs[0]["metric"] == "images_per_sec"
  assert recs[0]["run_id"] == stats["run_id"]
  assert metrics.validate_record(recs[0]) == []
  assert recs[0]["snapshot"]["images_per_sec"] == pytest.approx(
      stats["images_per_sec"])
  # After the run the socket is down.
  with pytest.raises((urllib.error.URLError, OSError)):
    _get(base + "/metrics", timeout=1)


def test_no_port_flag_binds_nothing(tmp_path):
  """Acceptance: unset --metrics_port binds no socket and writes no
  store; the run is byte-identical in its log surface."""
  logs, stats = _run_and_scrape(num_batches=4)
  assert not any("metrics endpoint" in l for l in logs)
  assert not os.path.exists(os.path.join(str(tmp_path),
                                         metrics.STORE_FILENAME))


# -- equivalence: endpoint-on vs off ------------------------------------------

# Compositions compile two full step programs apiece: slow-tiered
# (CLAUDE.md 60 s rule); [plain] stays tier-1 as the regression pin.
@pytest.mark.parametrize("extra", [
    {},
    pytest.param({"steps_per_dispatch": 4}, marks=pytest.mark.slow),
    pytest.param({"shard_optimizer_state": True, "optimizer": "momentum"},
                 marks=pytest.mark.slow),
], ids=["plain", "K4", "sharded"])
def test_metrics_on_bit_identical_to_off(tmp_path, extra):
  """Acceptance: the metrics fabric is a pure host-side observer --
  per-step losses AND trained params bit-identical with the endpoint +
  run store on vs off, on the 8-device mesh, through the chunked and
  sharded compositions (the auditor's metrics-twin rule pins the
  program-shape half against the metrics_on golden)."""
  on_logs, on = _run_and_scrape(
      num_devices=8, display_every=1, metrics_port=_free_port(),
      run_store_dir=str(tmp_path), **extra)
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
