"""Repo-wide hazard lint: CLAUDE.md's hard-won rules as an AST pass.

Each rule encodes a convention the repo learned the hard way (a
silently unvalidated flag, a drifted second copy of a scraped format, a
rank that skips a barrier).
Pure stdlib: this file imports nothing beyond the standard library, so
loaded by path (as ``run_tests.py --audit`` does) the lint runs in any
interpreter in ~a second -- note that importing it as
``kf_benchmarks_tpu.analysis.lint`` pulls the package ``__init__``,
which imports jax.

Rules (ids):

* ``version-gate-comment`` -- jax version gates (``hasattr(jax.lax,
  "pcast")``-style probes, ``jax.__version__`` comparisons) require a
  nearby comment/docstring naming the missing API, so a gate can be
  retired when the API lands (CLAUDE.md: "Add no new version gates
  without a comment naming the missing API").
* ``step-line-format`` -- the reference step-line format literal is
  single-sourced in ``utils/log.py`` (tests scrape stdout; a drifted
  second copy would print lines the scrapers half-match).
* ``flag-validation`` -- every flag in the params registry either
  appears in ``validation.py`` or carries an explicit entry in its
  ``NO_CROSS_FLAG_VALIDATION`` marker (with a reason); a flag that is
  both is a stale marker.
* ``signal-chain`` -- a ``signal.signal`` registration outside
  ``telemetry.py``/``faults.py`` must capture the previous handler so
  it can chain (the PR-4 SIGTERM contract: a handler that discards the
  chain silences the flight-recorder post-mortem, or eats ctrl-C). A
  bare ``signal.signal(...)`` statement drops the old handler on the
  floor; the compliant form assigns it.
* ``trace-event-emission`` -- run-trace span emission and timing
  helpers are single-sourced in ``tracing.py`` (the same pattern as
  the step-line rule): constructing Chrome trace-event dicts (a dict
  literal carrying a ``"ph"`` or ``"traceEvents"`` key) or defining a
  percentile/chrome-trace helper anywhere else in the package would
  fork the trace schema the tests validate. READING profiler output
  (``e.get("ph")``, observability.py) is fine -- only construction is
  emission. The profiler's annotations are the same timeline's second
  sink: ``TraceAnnotation`` / ``StepTraceAnnotation`` may be named only
  where they are handed to ``RunTrace(...)`` as a keyword argument, and
  constructed nowhere -- ``RunTrace.span()`` / ``.step()`` enter them.
* ``metric-key-literal`` -- metric keys are single-sourced in the
  metric registry schema (``metrics.py``; the same pattern as the
  step-line and trace-event rules): a string literal in one of the
  schema's namespaces (``health/<k>``, ``<k>_p50/_p90/_p99``) that is
  NOT a registered key, or an f-string ASSEMBLING such a key outside
  ``metrics.py``, forks the key corpus the run stats / bench JSON /
  flight-recorder rows render from. Reading registered keys is free --
  only unregistered lookalikes and out-of-home construction are
  violations; a reasoned allowlist (staleness-checked) covers the one
  producer that cannot import the registry.
* ``citation`` -- every top-level module (and subpackage) cites the
  reference ``file:line`` span it covers, with a reasoned allowlist
  for TPU-native-only modules (folded in from the former standalone
  citation lint; tests/test_citation_lint.py pins it).
* ``rank-divergent-collective`` -- the host-side leg of the SPMD
  divergence analyzer (ISSUE 20; the compiled-program legs live in
  analysis/spmd.py): a collective/barrier call (run_barrier,
  kfcoord_barrier, multihost_utils.*, the ops/ psum/all_gather
  helpers) reachable under a branch on ``jax.process_index()`` /
  ``process_count()`` / ``KFCOORD_RANK_HINT`` / ``is_chief`` is the
  multi-host deadlock class -- one rank skips the rendezvous, every
  other rank hangs.
  Requires a nearby ``all-ranks:`` justification comment; plain
  unguarded barrier calls need the same marker as the documented
  barrier convention (MIGRATION.md, SURVEY 2.9 KungFu exit barrier).
* ``rank-guarded-write`` -- a filesystem write (checkpoint /
  run-store / golden artifacts) under a rank branch must carry the
  ``rank0-owns:`` ownership marker: the one-writer convention has to
  be explicit at the site, or an elastic/resharded run double-writes.

Every allowlist entry is checked for staleness: an entry whose file no
longer trips the rule must be removed, so allowlists cannot rot into
blanket exemptions.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from typing import Dict, List, NamedTuple, Optional

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_SCAN_DIRS = ("kf_benchmarks_tpu", "tests", "experiments")
_SKIP_PARTS = {"__pycache__", ".git", "native"}


class LintViolation(NamedTuple):
  rule: str
  path: str    # repo-relative, forward slashes
  line: int
  message: str

  def render(self) -> str:
    return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# -- allowlists (every entry carries its reason; staleness-checked) ----------

VERSION_GATE_ALLOWLIST: Dict[str, str] = {}

SIGNAL_CHAIN_ALLOWLIST: Dict[str, str] = {}

# Citation allowlist (moved here from tests/test_citation_lint.py):
# TPU-native-only units with NO reference analog; each entry names why.
# Directory entries (trailing '/') cover a whole subpackage.
CITATION_ALLOWLIST = {
    "elastic.py": "elastic scaling lives in KungFu's external runtime, "
                  "not the reference repo (SURVEY 2.9); TPU-native "
                  "design module",
    "faults.py": "deterministic fault injection for the elastic tests; "
                 "the reference never kills a worker (KungFu's failure "
                 "handling is external runtime, SURVEY 2.9)",
    "telemetry.py": "runtime training-health layer; the reference's "
                    "observability is post-hoc only (SURVEY 5.1/9)",
    # "analysis/" left the allowlist in round 22: spmd.py cites the
    # reference KungFu exit-barrier span it guards against, so the
    # subpackage now carries a real citation.
}


# -- file plumbing -----------------------------------------------------------

class _Source(NamedTuple):
  path: str          # repo-relative
  text: str
  lines: List[str]
  tree: Optional[ast.AST]
  doc_lines: Dict[int, str]      # line -> comment/string text on that line
  comment_lines: Dict[int, str]  # line -> comment text only


def _doc_lines(text: str, tree: Optional[ast.AST]):
  """(comments+strings, comments-only) text by line: the 'documentation
  channel' the version-gate rule searches for API names. The
  comments-only channel lets the rule discard a gate's own string
  argument without also discarding a trailing comment on that line."""
  out: Dict[int, str] = {}
  comments: Dict[int, str] = {}

  def add(d: Dict[int, str], line: int, s: str) -> None:
    d[line] = d.get(line, "") + " " + s

  try:
    for tok in tokenize.generate_tokens(io.StringIO(text).readline):
      if tok.type == tokenize.COMMENT:
        add(out, tok.start[0], tok.string)
        add(comments, tok.start[0], tok.string)
  except (tokenize.TokenError, IndentationError):
    pass  # malformed file: the string channel below still applies
  if tree is not None:
    for node in ast.walk(tree):
      if isinstance(node, ast.Constant) and isinstance(node.value, str):
        for line in range(node.lineno, (node.end_lineno or node.lineno) + 1):
          add(out, line, node.value)
  return out, comments


def iter_sources(root: str) -> List[_Source]:
  files = []
  for entry in sorted(os.listdir(root)):
    full = os.path.join(root, entry)
    if entry.endswith(".py") and os.path.isfile(full):
      files.append(entry)
    elif entry in _SCAN_DIRS and os.path.isdir(full):
      for dirpath, dirnames, filenames in os.walk(full):
        dirnames[:] = [d for d in sorted(dirnames) if d not in _SKIP_PARTS]
        for name in sorted(filenames):
          if name.endswith(".py"):
            rel = os.path.relpath(os.path.join(dirpath, name), root)
            files.append(rel.replace(os.sep, "/"))
  sources = []
  for rel in files:
    text = open(os.path.join(root, rel), encoding="utf-8").read()
    try:
      tree = ast.parse(text)
    except SyntaxError:
      tree = None
    docs, comments = _doc_lines(text, tree)
    sources.append(_Source(rel, text, text.splitlines(), tree, docs,
                           comments))
  return sources


def _stale_allowlist(rule: str, allowlist: Dict[str, str],
                     hit_paths, known_paths) -> List[LintViolation]:
  out = []
  for path, why in sorted(allowlist.items()):
    if path not in known_paths:
      out.append(LintViolation(rule, path, 0,
                               f"stale allowlist entry (file gone): {why}"))
    elif path not in hit_paths:
      out.append(LintViolation(
          rule, path, 0,
          "stale allowlist entry (no longer trips the rule) -- remove "
          f"it: {why}"))
  return out


# -- rule: version-gate-comment ----------------------------------------------

def _gate_attr(node: ast.Call) -> Optional[str]:
  """The gated attr name when ``node`` is a jax version probe
  (hasattr(jax[.lax], "attr")), else None."""
  if not (isinstance(node.func, ast.Name) and node.func.id == "hasattr"
          and len(node.args) == 2
          and isinstance(node.args[1], ast.Constant)
          and isinstance(node.args[1].value, str)):
    return None
  target = ast.unparse(node.args[0])
  if target == "jax" or target.endswith("lax") or target.startswith("jax."):
    return node.args[1].value
  return None


def rule_version_gate_comment(sources: List[_Source]
                              ) -> List[LintViolation]:
  out, hits = [], set()
  for src in sources:
    if src.tree is None:
      continue
    gates = []
    for node in ast.walk(src.tree):
      if isinstance(node, ast.Call):
        attr = _gate_attr(node)
        if attr is not None:
          gates.append((node.lineno, attr, node.args[1].lineno))
      elif isinstance(node, ast.Compare):
        names = {ast.unparse(n) for n in ast.walk(node)
                 if isinstance(n, ast.Attribute)}
        if any(n.endswith("__version__") and "jax" in n for n in names):
          gates.append((node.lineno, "version", node.lineno))
    for lineno, attr, arg_line in gates:
      # The documentation channel: comments/strings in the surrounding
      # window. On the gate's own argument line only COMMENTS count
      # (hasattr's string arg names the attr by construction, but a
      # trailing comment there is legitimate documentation).
      window = ""
      for line in range(max(1, lineno - 12), lineno + 4):
        channel = (src.comment_lines if line == arg_line
                   else src.doc_lines)
        window += channel.get(line, "")
      if attr in window:
        continue
      hits.add(src.path)
      if src.path in VERSION_GATE_ALLOWLIST:
        continue
      out.append(LintViolation(
          "version-gate-comment", src.path, lineno,
          f"version gate on {attr!r} without a nearby comment naming "
          "the missing API (CLAUDE.md: gates must say what API absence "
          "they bridge, so they can be retired when it lands)"))
  out += _stale_allowlist("version-gate-comment", VERSION_GATE_ALLOWLIST,
                          hits, {s.path for s in sources})
  return out


# -- rule: signal-chain ------------------------------------------------------

# The two modules allowed to own handler registration: telemetry.py
# (the chained SIGTERM/SIGINT post-mortem handlers, PR 4) and faults.py
# (the injection harness that exercises them).
_SIGNAL_HOMES = ("kf_benchmarks_tpu/telemetry.py",
                 "kf_benchmarks_tpu/faults.py")


def _imported_signal_names(tree: ast.AST):
  """(direct, modules): local names bound to signal.signal by ``from
  signal import signal [as X]`` (the direct-call form) and local names
  the signal MODULE is bound to by ``import signal [as Y]`` (the
  ``Y.signal(...)`` form)."""
  direct, modules = set(), set()
  for node in ast.walk(tree):
    if isinstance(node, ast.ImportFrom) and node.module == "signal":
      for alias in node.names:
        if alias.name == "signal":
          direct.add(alias.asname or alias.name)
    elif isinstance(node, ast.Import):
      for alias in node.names:
        if alias.name == "signal":
          modules.add(alias.asname or alias.name)
  return direct, modules


def _is_signal_signal_call(node: ast.Call, direct_names: set,
                           module_names: set) -> bool:
  if isinstance(node.func, ast.Attribute) and node.func.attr == "signal":
    base = ast.unparse(node.func.value).split(".")[-1]
    return base == "signal" or base in module_names
  if isinstance(node.func, ast.Name):
    return node.func.id in direct_names
  return False


def rule_signal_chain(sources: List[_Source]) -> List[LintViolation]:
  out, hits = [], set()
  for src in sources:
    if src.path in _SIGNAL_HOMES or src.tree is None:
      continue
    direct_names, module_names = _imported_signal_names(src.tree)
    for node in ast.walk(src.tree):
      # A registration whose RESULT is discarded (a bare expression
      # statement) drops the previous handler; the compliant form
      # assigns it so the new handler can chain.
      if not (isinstance(node, ast.Expr)
              and isinstance(node.value, ast.Call)
              and _is_signal_signal_call(node.value, direct_names,
                                         module_names)):
        continue
      hits.add(src.path)
      if src.path in SIGNAL_CHAIN_ALLOWLIST:
        continue
      out.append(LintViolation(
          "signal-chain", src.path, node.lineno,
          "signal.signal registration discards the previous handler -- "
          "capture it (`old = signal.signal(...)`) and chain, or move "
          "the registration into telemetry.py/faults.py (the PR-4 "
          "SIGTERM chaining contract: an unchained handler silences "
          "the flight-recorder post-mortem or eats ctrl-C)"))
  out += _stale_allowlist("signal-chain", SIGNAL_CHAIN_ALLOWLIST, hits,
                          {s.path for s in sources})
  return out


# -- rule: step-line-format --------------------------------------------------

# Concatenated so this module's own constants never contain the marker
# (the rule scans every package file, this one included).
_STEP_LINE_MARKER = "images/sec" + ":"
_STEP_LINE_HOME = "kf_benchmarks_tpu/utils/log.py"


def rule_step_line_format(sources: List[_Source]) -> List[LintViolation]:
  out = []
  for src in sources:
    if not (src.path.startswith("kf_benchmarks_tpu/")
            or src.path == "bench.py"):
      continue
    if src.path == _STEP_LINE_HOME or src.tree is None:
      continue
    for node in ast.walk(src.tree):
      if isinstance(node, ast.Constant) and isinstance(node.value, str) \
          and _STEP_LINE_MARKER in node.value:
        out.append(LintViolation(
            "step-line-format", src.path, node.lineno,
            f"step-line format literal outside {_STEP_LINE_HOME}: tests "
            "scrape stdout against the single-sourced format "
            "(utils/log.py format_step_line/format_total_line); call "
            "the helper instead of re-stating the literal"))
  return out


# -- rule: trace-event-emission ----------------------------------------------

# Trace-event construction markers: a dict literal carrying one of
# these keys IS a Chrome trace event being built. Reads
# (e.get("ph"), data["traceEvents"]) do not match -- only construction.
_TRACE_EVENT_KEYS = {"ph", "traceEvents"}
# Helper names whose definitions outside the home fork the timing
# conventions the exported schema depends on.
_TRACE_HELPER_NAMES = {"percentile", "percentiles", "chrome_events",
                       "chrome_trace_events"}
_TRACE_HOME = "kf_benchmarks_tpu/tracing.py"
# The profiler's annotation classes: named outside the home only as a
# keyword argument of the RunTrace(...) call that injects them.
_ANNOTATION_NAMES = {"TraceAnnotation", "StepTraceAnnotation"}

TRACE_EMISSION_ALLOWLIST: Dict[str, str] = {}


def _names_annotation(node) -> bool:
  return ((isinstance(node, ast.Attribute)
           and node.attr in _ANNOTATION_NAMES) or
          (isinstance(node, ast.Name) and node.id in _ANNOTATION_NAMES))


def rule_trace_event_emission(sources: List[_Source]
                              ) -> List[LintViolation]:
  out, hits = [], set()
  for src in sources:
    if not (src.path.startswith("kf_benchmarks_tpu/")
            or src.path == "bench.py"):
      continue
    if src.path == _TRACE_HOME or src.tree is None:
      continue
    findings = []
    injected = set()  # annotation classes handed to RunTrace(...)
    for node in ast.walk(src.tree):
      if isinstance(node, ast.Call) and (
          getattr(node.func, "attr", None) == "RunTrace" or
          getattr(node.func, "id", None) == "RunTrace"):
        injected.update(id(k.value) for k in node.keywords)
    for node in ast.walk(src.tree):
      if _names_annotation(node) and id(node) not in injected:
        findings.append((node.lineno,
                         "profiler annotation named or constructed"))
      elif isinstance(node, ast.Dict):
        keys = {k.value for k in node.keys
                if isinstance(k, ast.Constant)
                and isinstance(k.value, str)}
        if keys & _TRACE_EVENT_KEYS:
          findings.append((node.lineno,
                           "Chrome trace-event dict constructed"))
      elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
          and node.name in _TRACE_HELPER_NAMES:
        findings.append((node.lineno,
                         f"trace helper {node.name}() defined"))
    for lineno, what in findings:
      hits.add(src.path)
      if src.path in TRACE_EMISSION_ALLOWLIST:
        continue
      out.append(LintViolation(
          "trace-event-emission", src.path, lineno,
          f"{what} outside {_TRACE_HOME}: span emission and timing "
          "helpers are single-sourced there (the exported Chrome "
          "schema is validated against that one writer; emit through "
          "tracing.active() / import tracing.percentile instead)"))
  out += _stale_allowlist("trace-event-emission", TRACE_EMISSION_ALLOWLIST,
                          hits, {s.path for s in sources})
  return out


# -- rule: metric-key-literal ------------------------------------------------

_METRICS_HOME = "kf_benchmarks_tpu/metrics.py"
# Schema-registration helper names in the home (the first literal arg
# of each call IS a registered key); parsed from the AST so this lint
# stays pure stdlib (importing metrics.py as a package module would
# pull jax via the package __init__).
_METRIC_REGISTER_FUNCS = {"_register", "_gauge", "_counter", "_hist",
                          "_info"}
# The key namespaces the schema owns: a whole-string literal matching
# one of these is a metric key by construction.
_METRIC_KEY_PATTERNS = (
    re.compile(r"health/\w+"),
    re.compile(r"\w+_p(?:50|90|99)"),
)


def _is_metric_key_fragment(s: str) -> bool:
  """A string FRAGMENT that assembles a schema-namespace key when
  joined with other pieces (f-string parts, '+'-concatenation
  operands): the health/ prefix, or a percentile suffix -- bare
  (``"_p" + q``) or literal (``f"{key}_p50"``)."""
  return ("health/" in s or s.endswith("_p")
          or bool(re.search(r"_p(?:50|90|99)$", s)))

METRIC_KEY_ALLOWLIST = {
    "kf_benchmarks_tpu/tracing.py":
        "percentile_fields builds <key>_p<q> over SAMPLE_KEYS x "
        "QUANTILES -- the one producer that cannot import the registry "
        "(tracing.py must stay loadable standalone, and the package "
        "import would pull jax); metrics.schema_audit cross-checks "
        "every rendered key against the schema instead",
}


def _registered_metric_keys(sources: List[_Source]):
  """(keys, found_home): literal first args of the schema-registration
  calls in metrics.py."""
  keys = set()
  src = next((s for s in sources if s.path == _METRICS_HOME), None)
  if src is None or src.tree is None:
    return keys, False
  for node in ast.walk(src.tree):
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
        and node.func.id in _METRIC_REGISTER_FUNCS and node.args
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)):
      keys.add(node.args[0].value)
  return keys, True


# Publish methods whose labels= keyword names must come from the
# schema's LABEL_NAMES tuple (the dimensional half of single-sourcing:
# an emitter inventing a label name is the same hazard as inventing a
# key -- the runtime check catches it live, this catches it in CI).
_METRIC_PUBLISH_METHODS = {"set", "inc", "observe"}


def _registered_label_names(sources: List[_Source]):
  """The LABEL_NAMES tuple literal from metrics.py, parsed from the
  AST (same stdlib-only discipline as _registered_metric_keys)."""
  src = next((s for s in sources if s.path == _METRICS_HOME), None)
  if src is None or src.tree is None:
    return set()
  for node in ast.walk(src.tree):
    if (isinstance(node, ast.Assign) and len(node.targets) == 1
        and isinstance(node.targets[0], ast.Name)
        and node.targets[0].id == "LABEL_NAMES"
        and isinstance(node.value, (ast.Tuple, ast.List))):
      return {e.value for e in node.value.elts
              if isinstance(e, ast.Constant)
              and isinstance(e.value, str)}
  return set()


def rule_metric_key_literal(sources: List[_Source]) -> List[LintViolation]:
  keys, found_home = _registered_metric_keys(sources)
  label_names = _registered_label_names(sources)
  out, hits = [], set()
  for src in sources:
    if not (src.path.startswith("kf_benchmarks_tpu/")
            or src.path == "bench.py"):
      continue
    if src.path == _METRICS_HOME or src.tree is None:
      continue
    # String constants that sit inside an ASSEMBLY expression are
    # judged as fragments there, not as whole-key literals here.
    assembled_constants = set()
    for node in ast.walk(src.tree):
      if isinstance(node, ast.JoinedStr):
        for v in node.values:
          if isinstance(v, ast.Constant):
            assembled_constants.add(id(v))
      elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        for side in (node.left, node.right):
          if isinstance(side, ast.Constant):
            assembled_constants.add(id(side))
    findings = []
    for node in ast.walk(src.tree):
      if isinstance(node, ast.Constant) and isinstance(node.value, str) \
          and id(node) not in assembled_constants:
        if any(p.fullmatch(node.value) for p in _METRIC_KEY_PATTERNS) \
            and node.value not in keys:
          findings.append((node.lineno,
                           f"metric-key literal {node.value!r} is not "
                           "registered in the metrics.py schema"))
      elif isinstance(node, ast.JoinedStr):
        parts = [v.value for v in node.values
                 if isinstance(v, ast.Constant)
                 and isinstance(v.value, str)]
        if any(_is_metric_key_fragment(p) for p in parts):
          findings.append((node.lineno,
                           "metric key assembled by f-string"))
      elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        sides = [s.value for s in (node.left, node.right)
                 if isinstance(s, ast.Constant)
                 and isinstance(s.value, str)]
        if any(_is_metric_key_fragment(s) for s in sides):
          findings.append((node.lineno,
                           "metric key assembled by concatenation"))
      elif (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _METRIC_PUBLISH_METHODS
            and label_names):
        for kw in node.keywords:
          if kw.arg != "labels" or not isinstance(kw.value, ast.Dict):
            continue
          for k in kw.value.keys:
            if (isinstance(k, ast.Constant)
                and isinstance(k.value, str)
                and k.value not in label_names):
              findings.append((
                  node.lineno,
                  f"unregistered metric label name {k.value!r} "
                  f"(LABEL_NAMES declares {sorted(label_names)})"))
    for lineno, what in findings:
      hits.add(src.path)
      if src.path in METRIC_KEY_ALLOWLIST:
        continue
      msg = (f"{what} outside {_METRICS_HOME}: metric keys are "
             "single-sourced in the registry schema (register the key "
             "there, or build it through its helpers -- "
             "metrics.health_key / the registered percentile fields)")
      if not found_home:
        msg = (f"{what}: no {_METRICS_HOME} schema found to check "
               "against (package moved?)")
      out.append(LintViolation("metric-key-literal", src.path, lineno,
                               msg))
  out += _stale_allowlist("metric-key-literal", METRIC_KEY_ALLOWLIST,
                          hits, {s.path for s in sources})
  return out


# -- rule: flag-validation ---------------------------------------------------

def _registry_flags(src: _Source) -> List[str]:
  names = []
  if src.tree is None:
    return names
  for node in ast.walk(src.tree):
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
        and node.func.attr.startswith("DEFINE_") and node.args
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)):
      names.append(node.args[0].value)
  return names


def _marker_dict(src: _Source):
  """(entries, lineno_span) of validation.py's NO_CROSS_FLAG_VALIDATION
  marker dict, or ({}, None)."""
  if src.tree is None:
    return {}, None
  for node in ast.walk(src.tree):
    if (isinstance(node, ast.Assign) and len(node.targets) == 1
        and isinstance(node.targets[0], ast.Name)
        and node.targets[0].id == "NO_CROSS_FLAG_VALIDATION"
        and isinstance(node.value, ast.Dict)):
      entries = {}
      for k, v in zip(node.value.keys, node.value.values):
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
          entries[k.value] = (ast.unparse(v) if not isinstance(
              v, ast.Constant) else v.value)
      return entries, (node.lineno, node.end_lineno or node.lineno)
  return {}, None


def rule_flag_validation(sources: List[_Source]) -> List[LintViolation]:
  by_path = {s.path: s for s in sources}
  params_src = by_path.get("kf_benchmarks_tpu/params.py")
  val_src = by_path.get("kf_benchmarks_tpu/validation.py")
  if params_src is None or val_src is None:
    return []
  flags = _registry_flags(params_src)
  marked, span = _marker_dict(val_src)
  # Mentions are searched OUTSIDE the marker dict (a marker entry must
  # not count as validation coverage).
  lines = list(val_src.lines)
  if span is not None:
    for line in range(span[0], span[1] + 1):
      lines[line - 1] = ""
  val_text = "\n".join(lines)
  out = []
  for name in flags:
    mentioned = re.search(rf"\b{re.escape(name)}\b", val_text)
    if mentioned and name in marked:
      out.append(LintViolation(
          "flag-validation", "kf_benchmarks_tpu/validation.py", span[0],
          f"stale NO_CROSS_FLAG_VALIDATION marker: --{name} now appears "
          "in validation.py -- remove the marker entry"))
    elif not mentioned and name not in marked:
      out.append(LintViolation(
          "flag-validation", "kf_benchmarks_tpu/params.py", 0,
          f"--{name} neither appears in validation.py nor carries a "
          "NO_CROSS_FLAG_VALIDATION marker entry (validation.py): add "
          "a cross-flag check or an explicit reasoned marker"))
  for name in marked:
    if name not in flags:
      out.append(LintViolation(
          "flag-validation", "kf_benchmarks_tpu/validation.py",
          span[0] if span else 0,
          f"NO_CROSS_FLAG_VALIDATION marker for unknown flag --{name}"))
  return out


# -- rule: citation ----------------------------------------------------------

_FILE_LINE_CITE = re.compile(r"[\w/.\-]+\.(?:py|cc|md|proto|sh):\d+")
_MD_SECTION_CITE = re.compile(r'[\w/.\-]+\.md "[^"]+"')


def _has_citation(text: str) -> bool:
  return bool(_FILE_LINE_CITE.search(text) or _MD_SECTION_CITE.search(text))


def rule_citation(sources: List[_Source]) -> List[LintViolation]:
  pkg = "kf_benchmarks_tpu/"
  modules = {}   # unit name ("foo.py" or "sub/") -> [texts]
  for src in sources:
    if not src.path.startswith(pkg):
      continue
    rel = src.path[len(pkg):]
    if "/" in rel:
      unit = rel.split("/", 1)[0] + "/"
    else:
      unit = rel
    modules.setdefault(unit, []).append(src.text)
  if len(modules) < 15:
    # Guard against the walker silently matching nothing (e.g. a moved
    # package): the tree this lint protects has >= 15 top-level units.
    return [LintViolation("citation", pkg, 0,
                          f"citation walker found only {len(modules)} "
                          "units -- package moved?")]
  out = []
  for unit, texts in sorted(modules.items()):
    cited = any(_has_citation(t) for t in texts)
    if unit in CITATION_ALLOWLIST:
      if cited:
        out.append(LintViolation(
            "citation", pkg + unit, 0,
            "allowlist entry now carries a citation -- remove it from "
            "CITATION_ALLOWLIST"))
      continue
    if not cited:
      out.append(LintViolation(
          "citation", pkg + unit, 0,
          "module missing the reference file:line citation comment "
          "(CLAUDE.md convention): cite the reference span it covers, "
          "or add a CITATION_ALLOWLIST entry stating why there is no "
          "analog"))
  for unit, why in CITATION_ALLOWLIST.items():
    if unit not in modules:
      out.append(LintViolation(
          "citation", pkg + unit, 0,
          f"stale CITATION_ALLOWLIST entry (unit gone): {why}"))
  return out


# -- rules: rank-divergence (ISSUE 20 leg c) ---------------------------------

# Host-level calls that issue or await a cross-rank rendezvous: every
# rank must reach them or the job hangs. These are the HOST-side sites
# the compiler never sees (the compiled step's schedule is checked by
# analysis/spmd.py; this rule owns the python control flow around it).
_BARRIER_CALL_NAMES = {"run_barrier", "kfcoord_barrier", "barrier",
                       "sync_global_devices",
                       "make_array_from_process_local_data"}
_BARRIER_TEXT_MARKERS = ("multihost_utils", "distributed.initialize")
# In-SPMD collective helpers (ops/, parallel/kungfu.py): fine unguarded
# (the compiler schedules them identically on every rank), but reached
# under a rank branch they are the same deadlock hazard.
_COLLECTIVE_HELPER_NAMES = {"allreduce_mean", "broadcast", "pair_average",
                            "sync_average", "gossip_shift", "psum",
                            "pmean", "all_gather", "ppermute",
                            "all_to_all"}
# Host control flow that diverges by rank: tests mentioning any of
# these make the branch rank-divergent.
_RANK_TEST_MARKERS = ("process_index", "process_count",
                      "KFCOORD_RANK_HINT", "is_chief", "current_rank")
# Justification markers; COMMENT channel only (a docstring merely
# mentioning the convention must not silence the rule). Concatenated so
# this module's own constants never contain them.
_ALL_RANKS_MARKER = "all-ranks" + ":"
_RANK0_MARKER = "rank0-owns" + ":"

RANK_DIVERGENCE_ALLOWLIST: Dict[str, str] = {}

RANK_WRITE_ALLOWLIST: Dict[str, str] = {}


def _call_names(node: ast.Call):
  """(last, dotted) name of a call target: the final attr/id plus the
  full dotted text (for module-path markers like multihost_utils)."""
  func = node.func
  last = (func.attr if isinstance(func, ast.Attribute)
          else func.id if isinstance(func, ast.Name) else "")
  try:
    dotted = ast.unparse(func)
  except Exception:
    dotted = last
  return last, dotted


def _rank_guard_regions(src: _Source):
  """[(guard_line, lo, hi)] line spans where host control flow has
  already diverged by rank: each rank-test If's own span, plus -- for
  the early-return shape (``if <rank-test>: return/raise`` with no
  else, checkpoint.save_checkpoint's idiom) -- the remainder of the
  smallest enclosing function (or module)."""
  if src.tree is None:
    return []
  funcs = [n for n in ast.walk(src.tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
  regions = []
  for node in ast.walk(src.tree):
    if not isinstance(node, ast.If):
      continue
    try:
      test_text = ast.unparse(node.test)
    except Exception:
      continue
    if not any(m in test_text for m in _RANK_TEST_MARKERS):
      continue
    end = node.end_lineno or node.lineno
    regions.append((node.lineno, node.lineno, end))
    terminal = bool(node.body) and isinstance(
        node.body[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))
    if terminal and not node.orelse:
      scope_end = len(src.lines)
      best_span = None
      for f in funcs:
        f_end = f.end_lineno or f.lineno
        if f.lineno <= node.lineno <= f_end:
          span = f_end - f.lineno
          if best_span is None or span < best_span:
            best_span, scope_end = span, f_end
      regions.append((node.lineno, end + 1, scope_end))
  return regions


def _rank_guard_for(regions, lineno: int) -> Optional[int]:
  """The nearest rank-test guard line whose divergent region covers
  ``lineno``, or None when the site is reached by every rank."""
  best = None
  for guard, lo, hi in regions:
    if lo <= lineno <= hi and (best is None or guard > best):
      best = guard
  return best


def _marker_in_comments(src: _Source, marker: str, lo: int,
                        hi: int) -> bool:
  return any(marker in src.comment_lines.get(line, "")
             for line in range(max(1, lo), hi + 1))


def rule_rank_divergent_collective(sources: List[_Source]
                                   ) -> List[LintViolation]:
  out, hits = [], set()
  for src in sources:
    if not src.path.startswith("kf_benchmarks_tpu/") or src.tree is None:
      continue
    regions = _rank_guard_regions(src)
    for node in ast.walk(src.tree):
      if not isinstance(node, ast.Call):
        continue
      last, dotted = _call_names(node)
      is_barrier = (last in _BARRIER_CALL_NAMES
                    or any(m in dotted for m in _BARRIER_TEXT_MARKERS))
      is_helper = last in _COLLECTIVE_HELPER_NAMES
      if not (is_barrier or is_helper):
        continue
      guard = _rank_guard_for(regions, node.lineno)
      if guard is not None:
        lo = guard
      elif is_barrier:
        # The barrier convention: even an unguarded cross-rank barrier
        # documents at the site why every rank reaches it.
        lo = node.lineno - 4
      else:
        continue  # unguarded in-SPMD helper: the compiler's schedule
      if _marker_in_comments(src, _ALL_RANKS_MARKER, lo,
                             node.lineno + 1):
        continue
      hits.add(src.path)
      if src.path in RANK_DIVERGENCE_ALLOWLIST:
        continue
      if guard is not None:
        msg = (f"collective/barrier call {last or dotted}() is "
               f"rank-divergent (rank-test guard at line {guard}) "
               f"without an '{_ALL_RANKS_MARKER}' justification "
               "comment -- a rank that skips the rendezvous hangs "
               "every other rank (the multi-host deadlock class)")
      else:
        msg = (f"cross-rank barrier call {last or dotted}() without "
               f"an '{_ALL_RANKS_MARKER}' convention comment naming "
               "why every rank reaches it (the lint-enforced barrier "
               "convention -- MIGRATION.md, SURVEY 2.9 KungFu exit "
               "barrier)")
      out.append(LintViolation("rank-divergent-collective", src.path,
                               node.lineno, msg))
  out += _stale_allowlist("rank-divergent-collective",
                          RANK_DIVERGENCE_ALLOWLIST, hits,
                          {s.path for s in sources})
  return out


# Filesystem mutations the one-writer convention covers. `dump`/`open`
# appear everywhere; they only count here when RANK-GUARDED.
_WRITE_CALL_NAMES = {"makedirs", "mkdir", "save_checkpoint",
                     "write_golden", "write_text", "dump", "replace",
                     "rename", "unlink", "remove", "rmtree"}


def _is_write_open(node: ast.Call) -> bool:
  last, _ = _call_names(node)
  if last != "open":
    return False
  mode = None
  if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
    mode = node.args[1].value
  for kw in node.keywords:
    if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
      mode = kw.value.value
  return isinstance(mode, str) and any(c in mode for c in "wax")


def rule_rank_guarded_write(sources: List[_Source]) -> List[LintViolation]:
  out, hits = [], set()
  for src in sources:
    if not src.path.startswith("kf_benchmarks_tpu/") or src.tree is None:
      continue
    regions = _rank_guard_regions(src)
    if not regions:
      continue
    for node in ast.walk(src.tree):
      if not isinstance(node, ast.Call):
        continue
      last, _ = _call_names(node)
      if not (last in _WRITE_CALL_NAMES or _is_write_open(node)):
        continue
      guard = _rank_guard_for(regions, node.lineno)
      if guard is None:
        continue
      if _marker_in_comments(src, _RANK0_MARKER, guard,
                             node.lineno + 1):
        continue
      hits.add(src.path)
      if src.path in RANK_WRITE_ALLOWLIST:
        continue
      out.append(LintViolation(
          "rank-guarded-write", src.path, node.lineno,
          f"rank-guarded filesystem write {last or 'open'}() (rank-test "
          f"guard at line {guard}) without a '{_RANK0_MARKER}' "
          "ownership comment -- the rank-0-owns-it convention must be "
          "explicit at the site (checkpoint/run-store/golden artifacts "
          "have exactly one writer; an elastic or resharded run would "
          "otherwise double-write)"))
  out += _stale_allowlist("rank-guarded-write", RANK_WRITE_ALLOWLIST,
                          hits, {s.path for s in sources})
  return out


# -- driver ------------------------------------------------------------------

RULES = {
    "version-gate-comment": rule_version_gate_comment,
    "signal-chain": rule_signal_chain,
    "step-line-format": rule_step_line_format,
    "trace-event-emission": rule_trace_event_emission,
    "metric-key-literal": rule_metric_key_literal,
    "flag-validation": rule_flag_validation,
    "citation": rule_citation,
    "rank-divergent-collective": rule_rank_divergent_collective,
    "rank-guarded-write": rule_rank_guarded_write,
}


def run_lint(root: str = REPO,
             rules: Optional[List[str]] = None) -> List[LintViolation]:
  sources = iter_sources(root)
  out: List[LintViolation] = []
  for rule_id, rule in RULES.items():
    if rules is not None and rule_id not in rules:
      continue
    out.extend(rule(sources))
  return sorted(out)


def main(argv=None) -> int:
  import argparse
  parser = argparse.ArgumentParser(description="repo hazard lint")
  parser.add_argument("--root", default=REPO)
  parser.add_argument("--rules", default=None,
                      help="comma-separated rule ids (default: all)")
  args = parser.parse_args(argv)
  rules = args.rules.split(",") if args.rules else None
  violations = run_lint(args.root, rules)
  for v in violations:
    print(v.render())
  print(f"hazard lint: {len(violations)} violation(s) across "
        f"{len(RULES if rules is None else rules)} rule(s)")
  return 1 if violations else 0


if __name__ == "__main__":
  raise SystemExit(main())
