"""Dimensional metrics + SLO burn monitoring + fleet report (round 21).

Reference-style layering (SURVEY 7.1):
  * pure-unit: labeled registry publishes / canonical labeled-key
    codec, Prometheus exposition conformance for labeled series and
    cumulative histograms (promtool-style grammar, including seeded
    violations), SLOMonitor burn windows on a fake clock, direction
    lookup and the direction-aware sentinel for both polarities,
    fleet-report grouping/rendering.
  * numerical-equivalence: the serving engine's per-tenant TTFT /
    token-latency percentiles vs a hand-rolled reference computed from
    the engine's own RequestResults over a seeded multi-tenant
    workload.
  * e2e: seeded budget exhaustion fires exactly ONE alert episode
    (flight-recorder rows included) and recovery clears it; a seeded
    run store renders a non-empty report through the CLI; bench.py
    --serving --check-regression prints one
    direction-aware verdict line per gated serving key.
"""

import json
import os

import numpy as np
import pytest

import jax

import bench
from kf_benchmarks_tpu import metrics
from kf_benchmarks_tpu import telemetry
from kf_benchmarks_tpu import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- labeled keys + registry --------------------------------------------------

def test_labeled_key_codec_roundtrips():
  key = metrics.labeled_key("serving/shed",
                            {"shed_reason": "queue_depth",
                             "tenant": 'a"b\\c'})
  base, labels = metrics.parse_labeled_key(key)
  assert base == "serving/shed"
  assert labels == {"shed_reason": "queue_depth", "tenant": 'a"b\\c'}
  # Canonical ordering: label names sort, so dict order never forks
  # the flat key.
  assert key == metrics.labeled_key(
      "serving/shed", {"tenant": 'a"b\\c',
                       "shed_reason": "queue_depth"})
  assert metrics.parse_labeled_key("plain_key") == ("plain_key", {})
  with pytest.raises(ValueError, match="malformed"):
    metrics.parse_labeled_key("x{not_label_syntax}")


def test_registry_accepts_declared_labels_only():
  reg = metrics.MetricRegistry()
  reg.inc("serving/requests", labels={"tenant": "a"})
  reg.inc("serving/requests", labels={"tenant": "b"})
  reg.inc("serving/requests")  # unlabeled aggregate coexists
  reg.set("serving/ttft_p99", 0.5, labels={"tenant": "a"})
  reg.observe("serving/ttft_s", 0.03, labels={"tenant": "a"})
  snap = reg.snapshot()
  assert snap['serving/requests{tenant="a"}'] == 1.0
  assert snap['serving/requests{tenant="b"}'] == 1.0
  assert snap["serving/requests"] == 1.0
  assert snap['serving/ttft_s/count{tenant="a"}'] == 1
  # An undeclared label name fails exactly like an unregistered key.
  with pytest.raises(ValueError, match="unregistered label name"):
    reg.set("images_per_sec", 1.0, labels={"tenant": "a"})
  with pytest.raises(ValueError, match="unregistered label name"):
    reg.inc("serving/requests", labels={"bucket": "4"})


# -- exposition conformance ---------------------------------------------------

def test_labeled_series_render_under_one_type_block():
  reg = metrics.MetricRegistry()
  reg.set("serving/ttft_p99", 0.5, labels={"tenant": "a"})
  reg.set("serving/ttft_p99", 0.7, labels={"tenant": "b"})
  text = reg.render()
  assert metrics.validate_prometheus_text(text) == []
  # One HELP/TYPE block, two series.
  assert text.count("# TYPE kf_serving_ttft_p99 gauge") == 1
  assert 'kf_serving_ttft_p99{tenant="a"} 0.5' in text
  assert 'kf_serving_ttft_p99{tenant="b"} 0.7' in text


def test_labeled_histogram_grammar():
  reg = metrics.MetricRegistry()
  for v in (0.004, 0.02, 0.02, 9.0, 120.0):
    reg.observe("serving/ttft_s", v, labels={"tenant": "a"})
  text = reg.render()
  assert metrics.validate_prometheus_text(text) == []
  assert "# TYPE kf_serving_ttft_s histogram" in text
  assert 'kf_serving_ttft_s_bucket{tenant="a",le="0.005"} 1' in text
  assert 'kf_serving_ttft_s_bucket{tenant="a",le="0.025"} 3' in text
  # +Inf carries the overflow sample and equals _count.
  assert 'kf_serving_ttft_s_bucket{tenant="a",le="+Inf"} 5' in text
  assert 'kf_serving_ttft_s_count{tenant="a"} 5' in text


def test_validator_rejects_histogram_grammar_violations():
  head = ("# TYPE kf_serving_ttft_s histogram\n")
  # Missing +Inf bucket.
  assert any("missing +Inf" in p for p in metrics.validate_prometheus_text(
      head + 'kf_serving_ttft_s_bucket{le="1"} 3\n'))
  # Non-monotone cumulative counts.
  assert any("monotone" in p for p in metrics.validate_prometheus_text(
      head + 'kf_serving_ttft_s_bucket{le="1"} 3\n'
      'kf_serving_ttft_s_bucket{le="+Inf"} 2\n'))
  # _count disagreeing with +Inf.
  assert any("_count" in p for p in metrics.validate_prometheus_text(
      head + 'kf_serving_ttft_s_bucket{le="+Inf"} 2\n'
      "kf_serving_ttft_s_count 3\n"))
  # _bucket without le (only under a declared-histogram family).
  assert any("without le" in p for p in metrics.validate_prometheus_text(
      head + "kf_serving_ttft_s_bucket 3\n"))
  # A plain gauge whose NAME ends in _bucket is not a histogram series.
  assert metrics.validate_prometheus_text(
      "# TYPE kf_serving_decode_bucket gauge\n"
      "kf_serving_decode_bucket 4\n") == []


def test_flatten_stats_expands_tenant_block_onto_labeled_keys():
  flat = metrics.flatten_stats({
      "serving_tenants": {
          "a": {"serving/ttft_p50": 0.1,
                "serving/shed": {"queue_depth": 2},
                "serving/tokens_per_sec": None,      # off: dropped
                "not_registered": 1.0},              # unknown: dropped
      },
  })
  assert flat['serving/ttft_p50{tenant="a"}'] == 0.1
  assert flat['serving/shed{shed_reason="queue_depth",tenant="a"}'] == 2.0
  assert not any("tokens_per_sec" in k or "not_registered" in k
                 for k in flat)
  # validate_record accepts the labeled snapshot and rejects
  # undeclared label names on it.
  rec = metrics.run_record(metric="x_per_sec", value=1.0, unit="u",
                           fingerprint="f", run_id="r", platform="cpu",
                           snapshot=flat)
  assert metrics.validate_record(rec) == []
  rec["snapshot"]['images_per_sec{tenant="a"}'] = 1.0
  assert any("undeclared label" in p
             for p in metrics.validate_record(rec))


# -- SLO burn-rate monitor ----------------------------------------------------

class _Clock:
  def __init__(self):
    self.t = 0.0

  def __call__(self):
    return self.t


def test_slo_monitor_burn_windows():
  clock = _Clock()
  mon = metrics.SLOMonitor(objectives={"ttft_deadline": 0.9},
                           fast_window_s=9.5, slow_window_s=40.0,
                           time_fn=clock)
  # 10 good events spread over 30 s, then 10 bad over the last 10 s.
  # fast_window_s=9.5 keeps the last good event (exactly 10 s back,
  # and the window edge is inclusive) OUT of the fast window.
  for _ in range(10):
    clock.t += 3.0
    mon.observe("ttft_deadline", "a", good=True)
  for _ in range(10):
    clock.t += 1.0
    mon.observe("ttft_deadline", "a", good=False)
  burns = mon.burn("ttft_deadline", "a")
  # Fast window (last 9.5 s) holds the 10 bad events only: burn =
  # (10/10) / 0.1 = 10. Slow window holds bad + the good tail.
  assert burns["fast"] == pytest.approx(10.0)
  assert 0.0 < burns["slow"] < burns["fast"]
  with pytest.raises(ValueError, match="unknown SLO objective"):
    mon.observe("made_up", "a", good=True)
  with pytest.raises(ValueError, match="unknown SLO objective"):
    metrics.SLOMonitor(objectives={"nope": 0.9})


def test_slo_alert_fires_one_episode_and_recovers():
  clock = _Clock()
  recorder = telemetry.FlightRecorder(path=None, window=32)
  mon = metrics.SLOMonitor(objectives={"shed_fraction": 0.99},
                           fast_window_s=10.0, slow_window_s=30.0,
                           burn_threshold=2.0, time_fn=clock,
                           recorder=recorder)
  # Budget exhaustion: sustained bad events on both windows. The
  # episode is edge-triggered -- ONE firing record however long the
  # burn lasts.
  for _ in range(50):
    clock.t += 0.5
    mon.observe("shed_fraction", "a", good=False)
  firing = [a for a in mon.alerts if a["state"] == "firing"]
  assert len(firing) == 1
  assert firing[0]["slo_alert"] == "shed_fraction"
  assert firing[0]["tenant"] == "a"
  assert firing[0]["burn_fast"] >= 2.0
  assert mon.firing() == [("shed_fraction", "a")]
  assert mon.state()["status"] == "burning"
  # Quiet recovery: no new events, the windows drain; the probe itself
  # re-evaluates and emits exactly one resolved record.
  clock.t += 100.0
  assert mon.firing() == []
  states = [a["state"] for a in mon.alerts]
  assert states == ["firing", "resolved"]
  assert mon.state()["status"] == "ok"
  # Alert records rode the flight recorder as rows (alerts are data).
  rows = [r for r in recorder.tail(10) if r.get("slo_alert")]
  assert [r["state"] for r in rows] == ["firing", "resolved"]


def test_telemetry_healthz_carries_slo_state():
  import types
  params = types.SimpleNamespace(health_stats=True, train_dir=None)
  session = telemetry.TelemetrySession(params)
  try:
    clock = _Clock()
    mon = metrics.SLOMonitor(objectives={"shed_fraction": 0.99},
                             fast_window_s=10.0, slow_window_s=30.0,
                             time_fn=clock, recorder=session.recorder)
    session.attach_slo(mon)
    payload = session.healthz()
    assert payload["status"] == "ok"
    assert payload["slo"]["status"] == "ok"
    for _ in range(50):
      clock.t += 0.5
      mon.observe("shed_fraction", "a", good=False)
    payload = session.healthz()
    assert payload["status"] == "burning"
    assert payload["slo"]["objectives"]["shed_fraction"]["a"]["firing"]
  finally:
    session.close()


# -- direction-aware sentinel -------------------------------------------------

def test_metric_direction_reads_schema_then_heuristics():
  assert metrics.metric_direction("images_per_sec") is True
  assert metrics.metric_direction("serving/ttft_p99") is False
  assert metrics.metric_direction("serving/shed_fraction") is False
  # Labeled keys resolve through their base.
  assert metrics.metric_direction(
      'serving/ttft_p99{tenant="a"}') is False
  # Unregistered headline names fall to the heuristics (the bench's
  # composite metric names).
  assert metrics.metric_direction("serving_tokens_per_sec") is True
  assert metrics.metric_direction(
      "resnet50_synthetic_images_per_sec") is True


def _rows(values, metric, fingerprint="fp-d"):
  return [metrics.run_record(
      metric=metric, value=v, unit="u", fingerprint=fingerprint,
      run_id=f"r{i}", platform="tpu", t_wall=1000.0 + i)
      for i, v in enumerate(values)]


def test_sentinel_direction_both_polarities():
  # higher-is-better: a DROP regresses, a jump does not.
  hist = _rows([100.0, 101.0, 99.0, 100.0], "x_per_sec")
  drop = metrics.run_record(metric="x_per_sec", value=50.0, unit="u",
                            fingerprint="fp-d", run_id="rf",
                            platform="tpu", t_wall=2000.0)
  assert metrics.check_regression(
      hist, drop, higher_is_better=True)["status"] == "regression"
  assert metrics.check_regression(
      hist, drop, higher_is_better=False)["status"] == "ok"
  # lower-is-better (TTFT): an INCREASE regresses, an improvement
  # passes -- the bench.py:482 bug this PR fixes flagged the opposite.
  jump = metrics.run_record(metric="x_per_sec", value=150.0, unit="u",
                            fingerprint="fp-d", run_id="rf",
                            platform="tpu", t_wall=2000.0)
  assert metrics.check_regression(
      hist, jump, higher_is_better=False)["status"] == "regression"
  assert metrics.check_regression(
      hist, jump, higher_is_better=True)["status"] == "ok"


def test_record_and_check_gates_serving_snapshot_keys(tmp_path, capsys):
  store_dir = str(tmp_path)
  # Seed history: healthy TTFT p99 ~50 ms, shed fraction 0, tokens/s
  # ~100 -- via record_and_check itself so the store shape is real.
  for i in range(4):
    rec = {"metric": "serving_tokens_per_sec", "value": 100.0 + i,
           "unit": "tokens/sec", "platform": "tpu",
           "serving/ttft_p99": 0.05, "serving/shed_fraction": 0.0}
    assert bench.record_and_check(
        rec, store_dir, False, run_id=f"seed{i}",
        fingerprint="fp-s") == 0
  # Fresh run: throughput fine, TTFT p99 10x worse -- only the
  # snapshot gate can catch it, and only with the LOWER-is-better
  # polarity.
  rec = {"metric": "serving_tokens_per_sec", "value": 101.0,
         "unit": "tokens/sec", "platform": "tpu",
         "serving/ttft_p99": 0.5, "serving/shed_fraction": 0.0}
  rc = bench.record_and_check(
      rec, store_dir, True, run_id="fresh", fingerprint="fp-s",
      extra_keys=("serving/ttft_p99", "serving/shed_fraction"))
  err = capsys.readouterr().err
  assert rc == 1
  lines = [ln for ln in err.splitlines()
           if ln.startswith("regression check:")]
  # One verdict line per gated metric, each self-identifying.
  assert len(lines) == 3
  assert any("serving_tokens_per_sec" in ln and "OK" in ln
             for ln in lines)
  assert any("serving/ttft_p99" in ln and "REGRESSION" in ln
             for ln in lines)
  assert any("serving/shed_fraction" in ln and "OK" in ln
             for ln in lines)
  # The same TTFT value judged higher-is-better (the old bug) would
  # have passed: prove the direction field is what catches it.
  hist = metrics.RunStore(store_dir).records()
  fresh = [r for r in hist if r["run_id"] == "fresh"][0]
  v = metrics.snapshot_check([r for r in hist
                              if r["run_id"] != "fresh"], fresh,
                             "serving/ttft_p99")
  assert v["status"] == "regression"


# -- per-tenant engine e2e ----------------------------------------------------

def _small_engine(**cfg_kw):
  from kf_benchmarks_tpu.serving import decode as decode_lib
  from kf_benchmarks_tpu.serving import engine as engine_lib
  spec = decode_lib.LMSpec(vocab=64, d_model=16, n_heads=2, d_ff=32,
                           n_layers=1, max_len=64)
  cfg = engine_lib.EngineConfig(spec=spec, bucket_ladder=(1, 4),
                                max_new_tokens=4, **cfg_kw)
  return engine_lib.ServingEngine(cfg, seed=0), spec


@pytest.fixture
def _registry():
  reg = metrics.activate(metrics.MetricRegistry())
  trace = tracing.RunTrace(path=None)
  tracing.activate(trace)
  yield reg
  tracing.deactivate()
  metrics.deactivate()


# Slow tier: tier-1's 870 s wall is the constraint (PR 21 tiering).
@pytest.mark.slow
def test_engine_per_tenant_percentiles_match_hand_rolled(_registry):
  from kf_benchmarks_tpu.serving import engine as engine_lib
  eng, spec = _small_engine(ttft_slo_s=30.0)
  workload = engine_lib.poisson_workload(
      15, 50.0, spec, seed=3, max_new_tokens=4,
      tenants=("a", "b", "c"))
  results = eng.replay(workload)
  stats = eng.stats()
  tenants = stats["serving_tenants"]
  assert sorted(tenants) == ["a", "b", "c"]
  # Hand-rolled reference: per-tenant TTFTs from the engine's own
  # results, percentiled with the repo's one convention.
  for tenant in ("a", "b", "c"):
    ttfts = [r.ttft_s for r in results
             if r.tenant == tenant and r.status == "ok"]
    assert ttfts, "seeded workload must complete requests per tenant"
    for q in (50, 90, 99):
      assert tenants[tenant][f"serving/ttft_p{q}"] == pytest.approx(
          tracing.percentile(ttfts, q))
    n_ok = sum(1 for r in results
               if r.tenant == tenant and r.status == "ok")
    assert tenants[tenant]["serving/completed"] == n_ok
  # The labeled exposition carries the per-tenant series.
  text = _registry.render()
  assert metrics.validate_prometheus_text(text) == []
  assert 'kf_serving_ttft_p99{tenant="a"}' in text
  assert 'kf_serving_ttft_s_count{tenant="a"}' in text
  # And flatten_stats lands them in run-store snapshot form.
  flat = metrics.flatten_stats(stats)
  assert flat['serving/ttft_p50{tenant="a"}'] == pytest.approx(
      tenants["a"]["serving/ttft_p50"])


def test_engine_sheds_count_by_tenant_and_reason(_registry):
  from kf_benchmarks_tpu.serving import engine as engine_lib
  eng, spec = _small_engine()
  # Empty prompts shed at submit with reason empty_prompt.
  for i, tenant in enumerate(("a", "a", "b")):
    eng.submit(engine_lib.Request(rid=f"s{i}", prompt=np.zeros((0,)),
                                  tenant=tenant))
  stats = eng.stats()
  assert stats["serving_tenants"]["a"]["serving/shed"] == {
      "empty_prompt": 2}
  assert stats["serving_tenants"]["b"]["serving/shed"] == {
      "empty_prompt": 1}
  snap = _registry.snapshot()
  key = metrics.labeled_key("serving/shed",
                            {"tenant": "a",
                             "shed_reason": "empty_prompt"})
  assert snap[key] == 2.0
  # Sheds burned the shed-fraction objective for their tenants.
  assert eng.slo.burn("shed_fraction", "a")["fast"] > 0
  # healthz reports the SLO state alongside engine liveness.
  hz = eng.healthz()
  assert "slo" in hz and "shed_fraction" in hz["slo"]["objectives"]


# -- fleet report -------------------------------------------------------------

def test_fleet_rows_group_filter_and_verdict():
  recs = (_rows([100.0, 101.0, 99.0, 100.0, 50.0], "x_per_sec",
                fingerprint="fp-good")
          + _rows([1.0, 1.0], "y_per_sec", fingerprint="fp-thin"))
  for r in recs[5:]:
    r["platform"] = "cpu"
  rows = metrics.fleet_rows(recs)
  by_fp = {r["fingerprint"]: r for r in rows}
  assert by_fp["fp-good"]["n"] == 5
  assert by_fp["fp-good"]["verdict"] == "regression"  # last = 50
  assert by_fp["fp-thin"]["verdict"] == "no_history"
  assert by_fp["fp-thin"]["platform"] == "cpu"
  assert metrics.fleet_rows(recs, platform="tpu") == [by_fp["fp-good"]]
  assert metrics.fleet_rows(recs, fingerprint="fp-g")[0][
      "fingerprint"] == "fp-good"
  assert metrics.fleet_rows(recs, metric="y_per_sec")[0][
      "metric"] == "y_per_sec"
  text = metrics.format_fleet_report(rows)
  assert "fp-good" in text and "regression" in text
  assert "2 trend row(s) over 7 record(s)" in text
  assert "no matching run records" in metrics.format_fleet_report([])


def test_fleet_report_html_is_self_contained(tmp_path):
  recs = _rows([100.0, 101.0, 99.0], "x_per_sec", fingerprint="fp-h")
  for r in recs:
    r["snapshot"] = {"serving/ttft_p50": 0.01, "serving/ttft_p90": 0.02,
                     "serving/ttft_p99": 0.03}
  html = metrics.fleet_report_html(metrics.fleet_rows(recs))
  assert html.startswith("<!doctype html>")
  assert "<svg" in html and "polyline" in html
  assert "No matching run records" in metrics.fleet_report_html([])
  # Self-contained: no external fetches of any kind.
  assert "http://" not in html and "https://" not in html
  assert "<script" not in html


def test_report_cli_on_a_seeded_store(tmp_path, capsys):
  # Acceptance: a store renders a non-empty trajectory through the
  # actual CLI, and the platform filter narrows it.
  store_dir = str(tmp_path)
  store = metrics.RunStore(store_dir)
  for r in (_rows([2400.0, 2410.0, 2390.0], "x_per_sec",
                  fingerprint="fp-chip")
            + _rows([1.0, 1.1], "x_per_sec", fingerprint="fp-cpu")):
    if r["fingerprint"] == "fp-cpu":
      r["platform"] = "cpu"
    store.append(r)
  out_html = str(tmp_path / "fleet.html")
  assert metrics.main(["report", "--repo", REPO,
                       "--run_store_dir", store_dir,
                       "--html", out_html]) == 0
  out = capsys.readouterr().out
  assert "FINGERPRINT" in out and "2 trend row(s)" in out
  with open(out_html) as f:
    assert "<svg" in f.read()
  assert metrics.main(["report", "--repo", REPO,
                       "--run_store_dir", store_dir,
                       "--platform", "tpu"]) == 0
  narrowed = capsys.readouterr().out
  assert "fp-cpu" not in narrowed
  assert "1 trend row(s)" in narrowed
