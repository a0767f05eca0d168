"""Hazard lint (kf_benchmarks_tpu/analysis/lint.py).

Layers:
  * acceptance: the lint is CLEAN at HEAD (every rule holds on the
    real tree, with its reasoned allowlists), and exits
    nonzero on each seeded violation class.
  * seeded violations in throwaway repo layouts (tmp_path): an
    uncommented version gate, a second step-line literal, an
    unvalidated flag -- each caught by exactly
    the intended rule, and each rule's negative (compliant) twin stays
    clean.
  * allowlist staleness: entries that stop tripping their rule are
    themselves violations, so allowlists cannot rot.

The lint is pure stdlib; these tests never build a mesh.
"""

import os

import pytest

from kf_benchmarks_tpu.analysis import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seed(tmp_path, rel, text):
  path = tmp_path / rel
  path.parent.mkdir(parents=True, exist_ok=True)
  path.write_text(text)
  return path


@pytest.fixture
def empty_allowlists(monkeypatch):
  """Seeded-tree tests run with the HEAD allowlists cleared: those
  entries reference real-repo paths, which read as 'file gone' stale
  entries under a tmp root."""
  monkeypatch.setattr(lint, "VERSION_GATE_ALLOWLIST", {})


def _rules(tmp_path, rule):
  return [v for v in lint.run_lint(str(tmp_path), rules=[rule])]


# -- acceptance: clean at HEAD ------------------------------------------------

def test_lint_clean_at_head():
  violations = lint.run_lint(REPO)
  assert not violations, "\n".join(v.render() for v in violations)


def test_cli_zero_at_head(capsys):
  assert lint.main(["--root", REPO]) == 0


# -- version-gate-comment -----------------------------------------------------

GATED = "import jax\n\nif hasattr(jax.lax, 'new_api'):\n  pass\n"


def test_uncommented_version_gate_seeded(tmp_path, empty_allowlists):
  _seed(tmp_path, "kf_benchmarks_tpu/gated.py", GATED)
  violations = _rules(tmp_path, "version-gate-comment")
  assert [v.rule for v in violations] == ["version-gate-comment"]
  assert "new_api" in violations[0].message
  assert lint.main(["--root", str(tmp_path),
                    "--rules", "version-gate-comment"]) == 1


def test_commented_version_gate_clean(tmp_path, empty_allowlists):
  _seed(tmp_path, "kf_benchmarks_tpu/gated.py",
        "import jax\n\n"
        "# lax.new_api is the missing API on older jax; identity there.\n"
        "if hasattr(jax.lax, 'new_api'):\n  pass\n")
  assert not _rules(tmp_path, "version-gate-comment")


def test_trailing_comment_on_gate_line_counts(tmp_path, empty_allowlists):
  # The comment channel on the gate's own line must survive the
  # string-argument exclusion (hasattr's arg names the attr by
  # construction, but a trailing comment there is documentation).
  _seed(tmp_path, "kf_benchmarks_tpu/gated.py",
        "import jax\n\n"
        "if hasattr(jax.lax, 'new_api'):  # new_api missing on older jax\n"
        "  pass\n")
  assert not _rules(tmp_path, "version-gate-comment")


def test_version_compare_gate_needs_comment(tmp_path, empty_allowlists):
  _seed(tmp_path, "kf_benchmarks_tpu/vers.py",
        "import jax\n\nNEW = jax.__version__ >= '0.5'\n")
  assert _rules(tmp_path, "version-gate-comment")
  _seed(tmp_path, "kf_benchmarks_tpu/vers.py",
        "import jax\n\n# version gate: shard_map API moved in 0.5\n"
        "NEW = jax.__version__ >= '0.5'\n")
  assert not _rules(tmp_path, "version-gate-comment")


def test_non_jax_hasattr_is_not_a_gate(tmp_path, empty_allowlists):
  _seed(tmp_path, "kf_benchmarks_tpu/attr.py",
        "def f(leaf):\n  return hasattr(leaf, 'dtype')\n")
  assert not _rules(tmp_path, "version-gate-comment")


# -- signal-chain -------------------------------------------------------------

UNCHAINED = ("import signal\n\n"
             "def install(handler):\n"
             "  signal.signal(signal.SIGTERM, handler)\n")


def test_unchained_signal_registration_seeded(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_signals.py", UNCHAINED)
  violations = _rules(tmp_path, "signal-chain")
  assert [v.path for v in violations] == [
      "kf_benchmarks_tpu/rogue_signals.py"]
  assert violations[0].line == 4 and "chain" in violations[0].message
  assert lint.main(["--root", str(tmp_path),
                    "--rules", "signal-chain"]) == 1


def test_chained_signal_registration_clean(tmp_path, monkeypatch):
  # The compliant twin captures the previous handler (the chaining
  # contract telemetry.py's handlers follow).
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/ok_signals.py",
        "import signal\n\n"
        "def install(handler):\n"
        "  old = signal.signal(signal.SIGTERM, handler)\n"
        "  return old\n")
  assert not _rules(tmp_path, "signal-chain")


def test_signal_registration_allowed_in_homes(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/telemetry.py", UNCHAINED)
  _seed(tmp_path, "kf_benchmarks_tpu/faults.py", UNCHAINED)
  assert not _rules(tmp_path, "signal-chain")


def test_direct_import_form_caught(tmp_path, monkeypatch):
  # `from signal import signal` must not evade the rule.
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/direct.py",
        "from signal import signal, SIGTERM\n\n"
        "def install(handler):\n"
        "  signal(SIGTERM, handler)\n")
  violations = _rules(tmp_path, "signal-chain")
  assert [v.line for v in violations] == [4]
  # ...including aliased imports, of the function AND of the module.
  _seed(tmp_path, "kf_benchmarks_tpu/direct.py",
        "from signal import signal as sig\n\n"
        "def install(handler):\n"
        "  sig(2, handler)\n")
  assert _rules(tmp_path, "signal-chain")
  _seed(tmp_path, "kf_benchmarks_tpu/direct.py",
        "import signal as sig\n\n"
        "def install(handler):\n"
        "  sig.signal(sig.SIGTERM, handler)\n")
  assert _rules(tmp_path, "signal-chain")


def test_non_signal_module_signal_attr_not_a_registration(tmp_path,
                                                          monkeypatch):
  # p.send_signal(...) / custom .signal(...) methods are not handler
  # registrations (kfrun.py's teardown is the in-repo example).
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/proc.py",
        "def stop(p):\n  p.send_signal(15)\n  p.bus.signal('x')\n")
  assert not _rules(tmp_path, "signal-chain")


def test_signal_chain_allowlist_staleness(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "SIGNAL_CHAIN_ALLOWLIST",
                      {"kf_benchmarks_tpu/clean.py": "test reason"})
  _seed(tmp_path, "kf_benchmarks_tpu/clean.py", "X = 1\n")
  violations = _rules(tmp_path, "signal-chain")
  assert len(violations) == 1 and "stale" in violations[0].message


# -- step-line-format ---------------------------------------------------------

def test_second_step_line_literal_seeded(tmp_path):
  marker = "images/sec" + ":"
  _seed(tmp_path, "kf_benchmarks_tpu/rogue.py",
        f"LINE = '5\\t{marker} 100.0'\n")
  violations = _rules(tmp_path, "step-line-format")
  assert [v.path for v in violations] == ["kf_benchmarks_tpu/rogue.py"]


def test_step_line_literal_allowed_in_log(tmp_path):
  marker = "images/sec" + ":"
  _seed(tmp_path, "kf_benchmarks_tpu/utils/log.py",
        f"FMT = '{marker} %.1f'\n")
  _seed(tmp_path, "tests/test_scrape.py",
        f"RE = r'{marker} ([0-9.]+)'\n")  # scrapers pin the format
  assert not _rules(tmp_path, "step-line-format")


# -- trace-event-emission -----------------------------------------------------

def test_trace_event_dict_outside_home_seeded(tmp_path):
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_trace.py",
        "def emit(name, ts):\n"
        "  return {'ph': 'X', 'name': name, 'ts': ts, 'dur': 1}\n")
  violations = _rules(tmp_path, "trace-event-emission")
  assert [v.path for v in violations] == \
      ["kf_benchmarks_tpu/rogue_trace.py"]
  assert "tracing.py" in violations[0].message
  assert lint.main(["--root", str(tmp_path),
                    "--rules", "trace-event-emission"]) == 1


def test_trace_helper_def_outside_home_seeded(tmp_path):
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_stats.py",
        "def percentile(values, q):\n  return sorted(values)[0]\n")
  violations = _rules(tmp_path, "trace-event-emission")
  assert len(violations) == 1 and "percentile" in violations[0].message


def test_trace_emission_allowed_in_home_and_reads_clean(tmp_path):
  # The home constructs events; other modules READ profiler output
  # (observability.py's load_trace_op_events pattern) -- only
  # construction is emission.
  _seed(tmp_path, "kf_benchmarks_tpu/tracing.py",
        "def chrome_events(spans):\n"
        "  return [{'ph': 'X', 'name': s} for s in spans]\n")
  _seed(tmp_path, "kf_benchmarks_tpu/reader.py",
        "import json\n\n"
        "def op_events(path):\n"
        "  data = json.load(open(path))\n"
        "  return [e for e in data.get('traceEvents', [])\n"
        "          if e.get('ph') == 'X']\n")
  _seed(tmp_path, "tests/test_free.py",
        "EVENT = {'ph': 'X', 'name': 'tests may build fixtures'}\n")
  assert not _rules(tmp_path, "trace-event-emission")


def test_profiler_annotation_outside_runtrace_seeded(tmp_path):
  # Constructed directly, or smuggled through an alias: both fork the
  # one emission point. Handing the class to RunTrace(...) is the way.
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_annotation.py",
        "import jax\n\n"
        "def step(i):\n"
        "  with jax.profiler.StepTraceAnnotation('train', step_num=i):\n"
        "    pass\n"
        "mark = jax.profiler.TraceAnnotation\n")
  _seed(tmp_path, "kf_benchmarks_tpu/session.py",
        "import jax\nfrom kf_benchmarks_tpu import tracing\n\n"
        "trace = tracing.RunTrace(\n"
        "    annotation=jax.profiler.TraceAnnotation,\n"
        "    step_annotation=jax.profiler.StepTraceAnnotation)\n")
  violations = _rules(tmp_path, "trace-event-emission")
  assert [(v.path, v.line) for v in violations] == [
      ("kf_benchmarks_tpu/rogue_annotation.py", 4),
      ("kf_benchmarks_tpu/rogue_annotation.py", 6)]
  assert "profiler annotation" in violations[0].message


def test_trace_emission_allowlist_staleness(tmp_path, monkeypatch):
  _seed(tmp_path, "kf_benchmarks_tpu/clean.py", "x = 1\n")
  monkeypatch.setattr(lint, "TRACE_EMISSION_ALLOWLIST",
                      {"kf_benchmarks_tpu/clean.py": "legacy emitter"})
  violations = _rules(tmp_path, "trace-event-emission")
  assert len(violations) == 1 and "stale" in violations[0].message


# -- metric-key-literal -------------------------------------------------------

# A minimal schema home: the rule parses registered keys out of the
# registration calls' literal first args.
METRICS_HOME = ("def _gauge(name, unit, help_, source):\n  return name\n"
                "_gauge('chunk_wall_p50', 's', 'help', 'tracing')\n"
                "_gauge('health/grad_norm', '1', 'help', 'telemetry')\n")


def test_unregistered_metric_key_literal_seeded(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", METRICS_HOME)
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_metrics.py",
        "STATS = {'queue_depth_p50': 1.0}\n")
  violations = _rules(tmp_path, "metric-key-literal")
  assert [v.path for v in violations] == \
      ["kf_benchmarks_tpu/rogue_metrics.py"]
  assert "queue_depth_p50" in violations[0].message
  assert lint.main(["--root", str(tmp_path),
                    "--rules", "metric-key-literal"]) == 1


def test_registered_metric_key_literal_clean(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  # The compliant twin reads REGISTERED keys -- reads are free, only
  # unregistered lookalikes are violations.
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", METRICS_HOME)
  _seed(tmp_path, "kf_benchmarks_tpu/reader.py",
        "def f(lat):\n  return lat.get('chunk_wall_p50')\n")
  _seed(tmp_path, "kf_benchmarks_tpu/recorder.py",
        "def g(rec):\n  return rec['health/grad_norm']\n")
  assert not _rules(tmp_path, "metric-key-literal")


def test_fstring_metric_key_construction_seeded(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", METRICS_HOME)
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_health.py",
        "def scalars(keys, vals):\n"
        "  return {f'health/{k}': v for k, v in zip(keys, vals)}\n")
  violations = _rules(tmp_path, "metric-key-literal")
  assert len(violations) == 1 and "f-string" in violations[0].message
  # ...and the percentile-suffix form is construction too -- with the
  # quantile formatted OR literal (the `f"{key}_p50"` evasion).
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_health.py",
        "def fields(key, q):\n  return f'{key}_p{q}'\n")
  assert _rules(tmp_path, "metric-key-literal")
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_health.py",
        "def fields(key):\n  return f'{key}_p50'\n")
  assert _rules(tmp_path, "metric-key-literal")
  # ...and '+'-concatenation is the same construction by other means.
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_health.py",
        "def scalars(k):\n  return 'health/' + k\n")
  violations = _rules(tmp_path, "metric-key-literal")
  assert len(violations) == 1 and "concatenation" in violations[0].message


def test_metric_key_construction_allowed_in_home(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py",
        METRICS_HOME + "def health_key(k):\n  return 'health/' + k\n"
        "X = {f'health/{k}': 1 for k in ('a',)}\n")
  assert not _rules(tmp_path, "metric-key-literal")


def test_metric_key_literal_outside_package_not_this_rules_business(
    tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", METRICS_HOME)
  _seed(tmp_path, "tests/test_x.py", "K = 'made_up_p99'\n")
  _seed(tmp_path, "experiments/probe.py", "K = 'made_up_p99'\n")
  assert not _rules(tmp_path, "metric-key-literal")


def test_metric_key_allowlist_staleness(tmp_path, monkeypatch):
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", METRICS_HOME)
  _seed(tmp_path, "kf_benchmarks_tpu/clean.py", "X = 1\n")
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST",
                      {"kf_benchmarks_tpu/clean.py": "legacy producer"})
  violations = _rules(tmp_path, "metric-key-literal")
  assert len(violations) == 1 and "stale" in violations[0].message


# Dimensional half of the rule: label names on publish calls are
# single-sourced in the schema's LABEL_NAMES tuple.
LABELS_HOME = METRICS_HOME + "LABEL_NAMES = ('tenant', 'bucket')\n"


def test_unregistered_label_name_seeded(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", LABELS_HOME)
  _seed(tmp_path, "kf_benchmarks_tpu/rogue_labels.py",
        "def f(reg):\n"
        "  reg.inc('health/grad_norm', labels={'user': 't0'})\n")
  violations = _rules(tmp_path, "metric-key-literal")
  assert len(violations) == 1
  assert "unregistered metric label name 'user'" in violations[0].message
  assert "tenant" in violations[0].message  # names the declared set


def test_registered_label_name_clean(tmp_path, monkeypatch):
  monkeypatch.setattr(lint, "METRIC_KEY_ALLOWLIST", {})
  _seed(tmp_path, "kf_benchmarks_tpu/metrics.py", LABELS_HOME)
  # Declared names are clean; non-literal label dicts are the runtime
  # check's business, not the lint's.
  _seed(tmp_path, "kf_benchmarks_tpu/publisher.py",
        "def f(reg, labs):\n"
        "  reg.set('health/grad_norm', 1.0, labels={'tenant': 't0'})\n"
        "  reg.observe('health/grad_norm', 0.1, labels=labs)\n")
  assert not _rules(tmp_path, "metric-key-literal")


# -- flag-validation ----------------------------------------------------------

PARAMS = ("from kf_benchmarks_tpu import flags\n\n"
          "flags.DEFINE_boolean('mystery', False, 'help')\n"
          "flags.DEFINE_integer('checked', 1, 'help')\n")


def test_unvalidated_flag_seeded(tmp_path):
  _seed(tmp_path, "kf_benchmarks_tpu/params.py", PARAMS)
  _seed(tmp_path, "kf_benchmarks_tpu/validation.py",
        "def validate(p):\n  assert p.checked\n")
  violations = _rules(tmp_path, "flag-validation")
  assert len(violations) == 1 and "--mystery" in violations[0].message


def test_marker_satisfies_and_goes_stale(tmp_path):
  _seed(tmp_path, "kf_benchmarks_tpu/params.py", PARAMS)
  _seed(tmp_path, "kf_benchmarks_tpu/validation.py",
        "NO_CROSS_FLAG_VALIDATION = {\n"
        "    'mystery': 'display knob only',\n"
        "}\n\n"
        "def validate(p):\n  assert p.checked\n")
  assert not _rules(tmp_path, "flag-validation")
  # The flag later GAINS validation: the marker is now stale.
  _seed(tmp_path, "kf_benchmarks_tpu/validation.py",
        "NO_CROSS_FLAG_VALIDATION = {\n"
        "    'mystery': 'display knob only',\n"
        "}\n\n"
        "def validate(p):\n  assert p.checked and p.mystery\n")
  violations = _rules(tmp_path, "flag-validation")
  assert len(violations) == 1 and "stale" in violations[0].message


def test_marker_for_unknown_flag_flagged(tmp_path):
  _seed(tmp_path, "kf_benchmarks_tpu/params.py", PARAMS)
  _seed(tmp_path, "kf_benchmarks_tpu/validation.py",
        "NO_CROSS_FLAG_VALIDATION = {\n"
        "    'mystery': 'display knob only',\n"
        "    'ghost': 'never defined',\n"
        "}\n")
  violations = _rules(tmp_path, "flag-validation")
  assert any("ghost" in v.message and "unknown" in v.message
             for v in violations)


# -- malformed files ----------------------------------------------------------

def test_malformed_file_does_not_crash_the_lint(tmp_path, empty_allowlists):
  # An unclosed bracket raises tokenize.TokenError mid-scan (and
  # SyntaxError in ast.parse); the lint must report on the rest of the
  # tree, not die on the half-saved file.
  _seed(tmp_path, "kf_benchmarks_tpu/halfsaved.py", "x = (\n")
  _seed(tmp_path, "kf_benchmarks_tpu/foo.py", GATED)
  violations = _rules(tmp_path, "version-gate-comment")
  assert [v.path for v in violations] == ["kf_benchmarks_tpu/foo.py"]


# -- allowlist staleness ------------------------------------------------------

def test_stale_allowlist_entry_is_a_violation(tmp_path, monkeypatch):
  _seed(tmp_path, "kf_benchmarks_tpu/clean.py", "X = 1\n")
  monkeypatch.setattr(lint, "VERSION_GATE_ALLOWLIST",
                      {"kf_benchmarks_tpu/clean.py": "test reason"})
  violations = _rules(tmp_path, "version-gate-comment")
  assert len(violations) == 1 and "stale" in violations[0].message
  # A file that still trips the rule keeps its entry quiet.
  _seed(tmp_path, "kf_benchmarks_tpu/clean.py", GATED)
  assert not _rules(tmp_path, "version-gate-comment")


def test_every_head_allowlist_entry_is_live():
  """The shipped allowlists must themselves be staleness-clean (covered
  by test_lint_clean_at_head, but name the failure mode explicitly)."""
  violations = [v for v in lint.run_lint(REPO)
                if "stale" in v.message]
  assert not violations, "\n".join(v.render() for v in violations)
