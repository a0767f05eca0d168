"""BENCHMARK.json against the builder's contract, every name against its
file, and the proof that a configuration, a cell and a metric are each
added by new files and new entries alone."""

import glob
import os
import re
import shutil

import pytest

from bench_testlib import REPO, TINY_CELL, make_tiny_tree, read_json, \
    write_json
from benchmarks import checks
from benchmarks import harness
from benchmarks import spec
from benchmarks import xplane

BENCH = spec.load_benchmark(REPO)
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYER_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
E2E = [m["name"] for m in BENCH["end_to_end"]]
LAYER = [m["name"] for m in BENCH["per_layer"]]


# -- the contract's shape -----------------------------------------------------

def test_exactly_the_contract_keys():
  assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
  assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
  assert BENCH["command"] == ["python3", "benchmarks/run.py"]
  assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
  for arg in BENCH["command"]:
    assert not arg.startswith("/") and ".." not in arg
  assert isinstance(BENCH["run_seconds"], int)
  assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_plain_and_unique():
  names = CELLS + CONFIGS + E2E + LAYER
  assert len(names) == len(set(names))
  for name in names:
    assert NAME_RE.match(name), name


def test_files_under_paths_have_plain_names():
  for top in BENCH["paths"]:
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, top)):
      dirnames[:] = [d for d in dirnames if d != "__pycache__"]
      for f in filenames:
        rel = os.path.relpath(os.path.join(dirpath, f), REPO)
        assert PATH_RE.match(rel), rel


def test_cells_chips_and_whys():
  assert 2 <= len(CELLS) <= 24
  pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
  assert len(pairs) == len(set(pairs))
  four = [w for w in BENCH["workloads"] if w["chips"] == 4]
  assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
  assert len(four) <= max(1, len(CELLS) // 4)
  for entry in BENCH["workloads"] + BENCH["configs"]:
    assert 0 < len(entry["why"]) <= 200, entry["name"]


def test_every_config_is_used_and_has_a_file_of_its_own():
  used = {w["config"] for w in BENCH["workloads"]}
  assert used == set(CONFIGS)
  files = [c["file"] for c in BENCH["configs"]]
  assert len(files) == len(set(files))
  # The two CNNs run whole; what a cut one owes is in config_rules.
  assert {c["name"]: c["reduced"] for c in BENCH["configs"]
          if c["name"] in ("resnet50", "vgg16")} == {"resnet50": [],
                                                     "vgg16": []}


def entry_rules(bench, kind, entry):
  """The shape the contract gives a metric's entry."""
  assert entry["better"] in ("higher", "lower")
  cells = [w["name"] for w in bench["workloads"]]
  assert set(entry.get("workloads", cells)) <= set(cells), (
      f"{entry['name']}: its entry names {entry.get('workloads')}, the "
      f"cells are {cells}")
  if kind == "end_to_end":
    assert set(entry) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.1
    return
  assert set(entry) <= {"name", "unit", "better", "source", "layer",
                        "moves", "workloads"}
  assert entry["source"] in SOURCES
  assert LAYER_RE.match(entry["layer"]), entry["layer"]
  assert entry["moves"] in [m["name"] for m in bench["end_to_end"]]
  if entry["name"].endswith("_roofline"):
    assert entry["unit"] == "%"


@pytest.mark.parametrize("kind, entry",
                         [(k, m) for k in ("end_to_end", "per_layer")
                          for m in BENCH[k]],
                         ids=lambda v: v["name"] if isinstance(v, dict) else v)
def test_metric_entry(kind, entry):
  entry_rules(BENCH, kind, entry)


def test_setup_s_is_an_end_to_end_metric():
  assert "setup_s" in E2E


# -- the admission rules ------------------------------------------------------
# What a configuration, a cell and a metric owe, as functions of a tree,
# so that the temporary tree of a later PR's additions (below) is held
# to them like the checkout. Each says what it protects.

# A cut may name how MANY there are, never how WIDE anything is
# (model-configs guide, section 4): a narrower model measures overheads,
# and its numbers do not carry over. So `reduced` is held to a closed
# list of what a chip's share of a deployment cuts, and any other key is
# refused, whatever it is called: hidden, feed-forward, expert, latent,
# state, head and window sizes, experts per token, shared experts (every
# token passes them), periods, scales.
COUNT_RE = re.compile("|".join((
    # How many layers, blocks or MTP modules; where the leading dense
    # layers end.
    r"(\w+_)?(num|n)_(\w+_)?(layers?|blocks|modules)",
    r"first_k_dense_replace",
    # Which layer is of which kind: a list or a pattern as long as the
    # depth, which a cut in depth shortens.
    r"(\w+_)?layer_types", r"(\w+_)?layers_block_type",
    r"(\w+_)?hybrid_(override|layer)_pattern", r"\w+_layer_i(ds?|ndices)",
    r"(mlp_only|full_attention|gqa|max_window)_layers",
    r"moe_layer_freq", r"moe_layers_enum", r"attn_type_list",
    # Experts held, heads held, rows of the vocabulary held.
    r"n_routed_experts", r"(moe_)?num_(local_|routed_)?experts",
    r"(\w+_)?(num|n)_(\w+_)?heads", r"(\w+_)?vocab_size")))


def config_rules(root, name):
  bench = spec.load_benchmark(root)
  entry = next(c for c in bench["configs"] if c["name"] == name)
  config = spec.load_config(root, name)
  assert entry["file"].startswith("benchmarks/")
  assert entry["source"].startswith("https://")
  assert config["source"] == entry["source"]
  reduced = entry["reduced"]
  assert isinstance(reduced, list) and len(reduced) <= 16
  assert config["reduced"] == reduced, (
      f"{name}: reduced is {config['reduced']} in the file and {reduced} "
      "in BENCHMARK.json")
  for key in reduced:
    assert NAME_RE.match(key), key
    assert COUNT_RE.fullmatch(key), (
        f"{name}: reduced names {key}, which counts no layers, experts, "
        "heads or rows of the vocabulary (COUNT_RE): a width is never cut")
  if reduced:
    # A cut configuration stands for one chip of a deployment: the file
    # gives each cut key as the source has it and as it is held here, and
    # says over how many chips a layer is divided, and how.
    published = config.get("published", {})
    for key in reduced:
      assert key in published and key in config, (
          f"{name}: reduced names {key}; the file has to give the published "
          f"value (`published`) and the value held here (`{key}`)")
      assert config[key] != published[key], key
    deployment = config.get("deployment", {})
    chips = deployment.get("chips_per_layer")
    assert isinstance(chips, int) and chips >= 1 and deployment.get("how"), (
        f"{name}: a cut configuration states its deployment: "
        "`chips_per_layer` and `how` a layer is divided over them")
  assert config["params"]["device"] == "tpu" or root != REPO
  # (`sample_unit`, and a count of tokens where it says tokens, are held
  # by spec.load_cell itself: a run that miscounts must not start.)
  flops = [config.get("forward_flops_per_sample")] + [
      spec.load_cell(root, w["name"]).get("forward_flops_per_sample")
      for w in bench["workloads"] if w["config"] == name]
  # The operation count is the configuration's, or each of its cells'.
  assert flops[0] or all(flops[1:]), f"{name}: no forward_flops_per_sample"
  assert all(f is None or f > 0 for f in flops)


def cell_rules(root, name):
  bench = spec.load_benchmark(root)
  cell = spec.load_cell(root, name)   # refuses tokens without a count
  assert cell["step_s_hint"] > 0
  trace = cell["trace"]
  assert set(trace) == {"after_steps", "min_steps", "min_s", "max_s"}
  assert cell["who"] and cell["why"]
  # What a cell reports is what the entries say (spec.cell_metrics).
  e2e = spec.cell_metrics(root, "end_to_end", name)
  assert "setup_s" in e2e and len(e2e) >= 2
  layer = spec.cell_metrics(root, "per_layer", name)
  assert len(layer) >= 1
  harness.load_checks(root, cell)     # every check named resolves
  kwargs = harness.job_kwargs(cell, seed=3, seconds=bench["run_seconds"])
  assert kwargs["num_devices"] == cell["chips"]
  assert kwargs["tf_random_seed"] == 3 and kwargs["display_every"] == 1
  # The cell has what each metric it reports needs to find something to
  # read (the metric's own file says what, `NEEDS`): enough chips, and at
  # run_seconds enough timed steps; in any case ten intervals under
  # `step_ms_p90` in a traced run, which loses those the profiler's start
  # and stop stall.
  steps = kwargs["num_batches"]
  p90 = spec.load_metric(root, "per_layer", "step_ms_p90")
  need = {"steps": p90.MIN_INTERVALS + 2 * harness.STALL_STEPS + 1}
  for metric in layer:
    needs = getattr(spec.load_metric(root, "per_layer", metric), "NEEDS", {})
    assert set(needs) <= {"steps", "chips"}, metric
    for key, least in needs.items():
      need[key] = max(need.get(key, 0), least)
  assert steps >= need["steps"], (
      f"{name}: {steps} timed steps in {bench['run_seconds']} s, and what "
      f"it reports needs {need['steps']}")
  assert cell["chips"] >= need.get("chips", 1), (
      f"{name}: {cell['chips']} chip(s), and what it reports needs "
      f"{need['chips']}")
  # ... and for its own trace window: the profiled stretch and the lines
  # its start and stop stall end two lines before the run does, so that
  # `steady_samples_per_sec` (and `mfu`) has intervals outside it; and
  # the stretch reaches its `min_steps` before `max_s` cuts it.
  fits = (trace["after_steps"] + trace["min_steps"] +
          2 * harness.STALL_STEPS + 2 <= steps)
  lasts = ((trace["min_steps"] + xplane.SKIP_STEPS + 1) *
           cell["step_s_hint"] <= trace["max_s"])
  assert fits and lasts, (
      f"{name}: the trace window does not fit: {trace} at "
      f"{cell['step_s_hint']} s a step in {steps} timed steps")


def metric_rules(root, kind, name):
  module = spec.load_metric(root, kind, name)
  bench = spec.load_benchmark(root)
  entry = next(m for m in bench[kind] if m["name"] == name)
  for field in spec.METRIC_FIELDS[kind]:
    assert getattr(module, field) == entry[field.lower()], field
  assert callable(module.read) and module.__doc__
  entry_rules(bench, kind, entry)
  # A metric that not every cell can read names its cells (cell_rules
  # holds each to the metric's `NEEDS`): the driver refuses a traced line
  # that lacks a metric whose entry has no `workloads`, in every cell,
  # those of later PRs too.
  assert "workloads" in entry or not getattr(module, "NEEDS", {}), (
      f"{name}: its file states what a cell needs (NEEDS), so its entry "
      "names the cells that have it under `workloads`")


def reported_where_named(root, name, expected=()):
  """What a metric's own test protects about its entry's ``workloads``,
  and no more: the cells named exist, each of ``expected`` is among them,
  and the metric is reported in exactly the cells named. Never equality
  with a list the test keeps: a later cell joins an accepted entry by
  adding its name there, and that fails nothing."""
  bench = spec.load_benchmark(root)
  named = spec._entry(bench["per_layer"], name, "per-layer metric")[
      "workloads"]
  cells = [w["name"] for w in bench["workloads"]]
  assert named and set(named) <= set(cells), name
  assert set(expected) <= set(named), name
  assert [cell for cell in cells
          if name in spec.cell_metrics(root, "per_layer", cell)] == [
              cell for cell in cells if cell in named], name


def file_rules(root):
  """Every file under the benchmark's directories has an entry that names
  it: nothing lies there that no run reads."""
  bench = spec.load_benchmark(root)
  def files(sub, ext):
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(root, "benchmarks", sub,
                                                  "*" + ext)))
  def names(key):
    return sorted(e["name"] for e in bench[key])
  assert files("configs", ".json") == names("configs")
  assert files("workloads", ".json") == names("workloads")
  assert files("traffic", ".json") == sorted(
      {w["traffic"] for w in bench["workloads"]})
  assert files("end_to_end", ".py") == names("end_to_end")
  assert files("layer_metrics", ".py") == names("per_layer")
  named = {c for w in names("workloads")
           for c in spec.check_names(spec.load_cell(root, w))}
  assert files("named_checks", ".py") == sorted(named - set(checks.NAMED))


# -- every name resolves to a file, every file to a name ----------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_config_file(name):
  config_rules(REPO, name)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
  cell_rules(REPO, name)


@pytest.mark.parametrize("key", [
    "num_hidden_layers", "num_layers", "n_layer", "n_layers",
    "num_nextn_predict_layers", "mtp_num_hidden_layers", "num_mtp_modules",
    "num_dense_layers", "n_dense_first_layers", "first_k_dense_replace",
    "transformer_num_blocks", "max_window_layers", "layer_types",
    "mlp_layer_types", "layers_block_type", "hybrid_override_pattern",
    "mlp_only_layers", "full_attention_layers", "hybrid_layer_ids",
    "attn_layer_indices", "moe_layer_freq", "n_routed_experts",
    "num_experts", "num_local_experts", "moe_num_experts",
    "num_attention_heads", "num_key_value_heads", "index_n_heads",
    "mamba_num_heads", "linear_num_value_heads", "vocab_size",
])
def test_a_count_may_be_cut(key):
  assert COUNT_RE.fullmatch(key)


@pytest.mark.parametrize("key", [
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "ffn_hidden_size", "ffn_hidden_size_list", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "head_dim", "mamba_d_state",
    "ssm_state_size", "mamba_expand", "mlp_expansion_factor",
    "moe_latent_size", "attention_projection_size", "sliding_window",
    "sliding_window_pattern", "conv_kernel", "num_experts_per_tok",
    "num_experts_per_token", "moe_top_k", "moe_router_topk",
    "num_selected_experts", "n_shared_experts", "n_group", "topk_group",
    # ... and what a deny-list of width words would have let through.
    "d_model", "n_embd", "d_ff", "d_kv", "dim", "n_inner", "d_inner",
    "width", "channels", "rope_theta", "max_position_embeddings",
    "routed_scaling_factor", "tokens_per_sample", "num_hidden_layers_x",
])
def test_anything_else_may_not(key):
  assert not COUNT_RE.fullmatch(key)


@pytest.mark.parametrize("kind, name",
                         [("end_to_end", n) for n in E2E] +
                         [("per_layer", n) for n in LAYER])
def test_metric_file_agrees_with_its_entry(kind, name):
  metric_rules(REPO, kind, name)


def test_no_file_without_an_entry():
  file_rules(REPO)


def test_peaks_table():
  peaks = spec.load_peaks(REPO)
  v5e = peaks["TPU v5 lite"]
  assert v5e["bf16_flops_per_s"] == 197e12
  assert v5e["hbm_bytes_per_s"] == 819e9
  assert all(p["source"] for p in peaks.values())


# -- data-driven: add, do not edit --------------------------------------------

CUT_CELL = "cutmoe-train-seq8k-cpu"
CUT_CONFIG = "benchmarks/configs/cutmoe.json"
CUT_WORKLOAD = f"benchmarks/workloads/{CUT_CELL}.json"


def add_cut_cell(root):
  """A later PR's additions to the tiny tree, as files and entries alone:
  a mixture of experts cut to one chip's share of a deployment and
  counted in tokens, a training cell of 8k sequences whose step takes
  half a second, with an operation count of its own (attention's depends
  on the sequence length), a reference check by file and a metric."""
  def path(rel):
    return os.path.join(root, rel)
  before = {f: os.path.getmtime(os.path.join(d, f))
            for d, _, files in os.walk(path("benchmarks")) for f in files}
  write_json(path(CUT_CONFIG), {
      "source": "https://example.org/cutmoe/config.json",
      "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
      "num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360,
      "hidden_size": 2048, "moe_intermediate_size": 1536,
      "num_experts_per_tok": 4,
      "published": {"num_hidden_layers": 47, "n_routed_experts": 64,
                    "vocab_size": 154880},
      "deployment": {
          "chips_per_layer": 8,
          "how": "each layer's experts and the vocabulary's rows divided "
                 "over 8 chips; the layers left out lie on further chips"},
      "params": {"model": "lenet", "device": "cpu"},
      "sample_unit": "tokens", "checks": ["reference_agrees"]})
  write_json(path("benchmarks/traffic/train-seq8k-cpu.json"), {
      "chips": 1, "checks": [],
      "params": {"batch_size": 2, "num_devices": 1,
                 "variable_update": "replicated"}})
  write_json(path(CUT_WORKLOAD), {
      "config": "cutmoe", "traffic": "train-seq8k-cpu", "chips": 1,
      "step_s_hint": 0.5, "tokens_per_sample": 8192,
      "forward_flops_per_sample": 3600000000,
      "trace": {"after_steps": 3, "min_steps": 4, "min_s": 1.0,
                "max_s": 6.0},
      "who": "whoever pre-trains a mixture of experts", "why": "8k rows"})
  for sub, name, text in (
      ("layer_metrics", "new_metric",
       '"""Steps the run timed."""\nLAYER = "driver_loop"\n'
       'UNIT = "count"\nBETTER = "higher"\nSOURCE = "program_counter"\n'
       'MOVES = "samples_per_sec"\n\n\n'
       'def read(run):\n  return run.timed_steps\n'),
      ("named_checks", "reference_agrees",
       '"""Stands for a comparison with the plain reference."""\n\n\n'
       'def check(run, devices):\n  return []\n')):
    os.makedirs(path(f"benchmarks/{sub}"), exist_ok=True)
    with open(path(f"benchmarks/{sub}/{name}.py"), "w",
              encoding="utf-8") as f:
      f.write(text)
  bench = spec.load_benchmark(root)
  bench["configs"].append({
      "name": "cutmoe", "source": "https://example.org/cutmoe/config.json",
      "file": CUT_CONFIG,
      "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]})
  bench["workloads"].append({"name": CUT_CELL, "config": "cutmoe",
                             "traffic": "train-seq8k-cpu", "chips": 1})
  bench["per_layer"].append({
      "name": "new_metric", "unit": "count", "better": "higher",
      "source": "program_counter", "layer": "driver_loop",
      "moves": "samples_per_sec", "workloads": [CUT_CELL]})
  bench["run_seconds"] = 10
  write_json(path("BENCHMARK.json"), bench)
  # No file that was there has been edited.
  assert {f: os.path.getmtime(os.path.join(d, f))
          for d, _, files in os.walk(path("benchmarks")) for f in files
          if f in before} == before


def admit(root):
  """Every rule above, on every configuration, cell and metric of the
  tree under ``root``."""
  bench = spec.load_benchmark(root)
  for c in bench["configs"]:
    config_rules(root, c["name"])
  for w in bench["workloads"]:
    cell_rules(root, w["name"])
  for kind in spec.METRIC_DIRS:
    for m in bench[kind]:
      metric_rules(root, kind, m["name"])
  file_rules(root)


def test_config_cell_and_metric_added_as_files(tmp_path):
  root = make_tiny_tree(str(tmp_path))
  add_cut_cell(root)
  admit(root)

  cell = spec.load_cell(root, CUT_CELL)
  assert cell["config_data"]["params"]["model"] == "lenet"
  kwargs = harness.job_kwargs(cell, seed=1, seconds=10)
  assert kwargs["model"] == "lenet" and kwargs["batch_size"] == 2
  assert kwargs["num_batches"] == 20
  # It reports what the entries say: every metric that names no cells,
  # and the one that names this cell; not the 64th step line, which the
  # entry keeps to the cell that has one.
  layer = spec.cell_metrics(root, "per_layer", CUT_CELL)
  assert "new_metric" in layer and "step_ms_p90" in layer
  assert "train_loss_step_64" not in layer
  assert "new_metric" not in spec.cell_metrics(root, "per_layer", TINY_CELL)
  assert len(harness.load_checks(root, cell)) == 2   # standing + the file

  # Twenty steps of two 8k sequences, half a second each, through the
  # readers: tokens a second, and `mfu` from the CELL's operation count.
  log = harness.StepLog(lambda line: None)
  log.steps = [harness.Step(i, 0.5 * i, 1.0) for i in range(1, 21)]
  run = harness.Run(cell=cell, device={}, peaks=spec.load_peaks(root)[
      "TPU v5 lite"], kwargs=kwargs, timed_steps=20, t0=0.0,
                    global_batch=2, log=log)
  read = lambda kind, name: spec.load_metric(root, kind, name).read(run)
  assert read("end_to_end", "samples_per_sec") == pytest.approx(32768.0)
  assert read("per_layer", "mfu") == pytest.approx(
      100 * 3 * 3.6e9 * 32768.0 / 197e12)
  assert read("per_layer", "new_metric") == 20
  assert read("per_layer", "step_ms_p90") == pytest.approx(500.0)
  # ... and the cell that was there still loads, untouched.
  assert spec.load_cell(root, TINY_CELL)["config"] == "trivial"


def _edit(root, rel, change):
  path = os.path.join(root, rel)
  data = read_json(path)
  change(data)
  write_json(path, data)


def _an_entry(root, bench, needs):
  """A per-layer entry of the tree to plant a fault in, chosen by what
  it is and never by where it stands: one that names its cells and,
  with ``needs``, whose file states a NEEDS that any cell has (so that
  taking its ``workloads`` away breaks that rule and no cell's)."""
  for entry in bench["per_layer"]:
    stated = getattr(spec.load_metric(root, "per_layer", entry["name"]),
                     "NEEDS", {})
    if "workloads" in entry and (
        not needs or stated and all(v <= 1 for v in stated.values())):
      return entry
  raise AssertionError("the tree has no such entry")


@pytest.mark.parametrize("breakage, error, message", [
    ("unknown", spec.SpecError, "no workload named"),
    ("chips", spec.SpecError, "chips is"),
    ("no_metric", spec.SpecError, "no file"),
    ("harness_key", spec.SpecError, "which the harness fixes"),
    ("metrics_listed_in_the_cell", spec.SpecError, "said by the metrics"),
    # The additions of add_cut_cell, each broken in one way.
    ("width_reduced", AssertionError, "moe_intermediate_size, which counts no"),
    ("reduced_disagrees", AssertionError, "in BENCHMARK.json"),
    ("no_published_value", AssertionError, "the published value"),
    ("no_deployment", AssertionError, "states its deployment"),
    ("tokens_without_a_count", spec.SpecError, "tokens_per_sample"),
    ("sample_unit", spec.SpecError, "sample_unit is 'words'"),
    ("too_few_steps", AssertionError, "needs 17"),
    ("trace_window_too_late", AssertionError, "trace window does not fit"),
    ("trace_window_cut_short", AssertionError, "trace window does not fit"),
    ("check_without_a_file", spec.SpecError, "check 'not_there': no file"),
    ("no_operation_count", AssertionError, "no forward_flops_per_sample"),
    ("metric_names_no_cell", AssertionError, "the cells are"),
    ("cell_without_what_its_metric_needs", AssertionError,
     "1 chip.s., and what it reports needs 2"),
    ("needs_without_workloads", AssertionError, "names the cells that have"),
    ("file_without_an_entry", AssertionError, "stray"),
])
def test_disagreements_are_refused(tmp_path, breakage, error, message):
  root = make_tiny_tree(str(tmp_path))
  add_cut_cell(root)
  admit(root)
  traffic = "benchmarks/traffic/train-bs4-cpu.json"
  changes = {
      "chips": (traffic, lambda d: d.update(chips=4)),
      "harness_key": (traffic, lambda d: d["params"].update(num_batches=5)),
      "metrics_listed_in_the_cell": (
          CUT_WORKLOAD, lambda d: d.update(per_layer=["mfu"])),
      "width_reduced": (CUT_CONFIG, lambda d: (
          d["reduced"].append("moe_intermediate_size"),
          d["published"].update(moe_intermediate_size=2048))),
      "reduced_disagrees": (CUT_CONFIG, lambda d: d["reduced"].pop()),
      "no_published_value": (
          CUT_CONFIG, lambda d: d["published"].pop("vocab_size")),
      "no_deployment": (CUT_CONFIG, lambda d: d.pop("deployment")),
      "tokens_without_a_count": (
          CUT_WORKLOAD, lambda d: d.pop("tokens_per_sample")),
      "sample_unit": (CUT_CONFIG, lambda d: d.update(sample_unit="words")),
      "too_few_steps": (CUT_WORKLOAD, lambda d: d.update(step_s_hint=0.7)),
      "trace_window_too_late": (
          CUT_WORKLOAD, lambda d: d["trace"].update(after_steps=9)),
      "trace_window_cut_short": (
          CUT_WORKLOAD, lambda d: d["trace"].update(max_s=3.0)),
      "check_without_a_file": (
          CUT_CONFIG, lambda d: d["checks"].append("not_there")),
      "no_operation_count": (
          CUT_WORKLOAD, lambda d: d.pop("forward_flops_per_sample")),
      "metric_names_no_cell": ("BENCHMARK.json", lambda b: _an_entry(
          root, b, needs=False).update(workloads=["not_there"])),
      "needs_without_workloads": ("BENCHMARK.json", lambda b: _an_entry(
          root, b, needs=True).pop("workloads")),
  }
  new_metric = "benchmarks/layer_metrics/new_metric.py"
  appended = {
      "cell_without_what_its_metric_needs": (new_metric,
                                             'NEEDS = {"chips": 2}\n'),
      "needs_without_workloads": (new_metric, 'NEEDS = {"chips": 1}\n'),
      "file_without_an_entry": ("benchmarks/layer_metrics/stray.py", "\n"),
  }
  if breakage in appended:
    rel, text = appended[breakage]
    with open(os.path.join(root, rel), "a", encoding="utf-8") as f:
      f.write(text)
  if breakage in changes:
    _edit(root, *changes[breakage])
  if breakage == "width_reduced":   # ... in the entry as in the file
    _edit(root, "BENCHMARK.json", lambda b: b["configs"][-1]["reduced"].append(
        "moe_intermediate_size"))
  with pytest.raises(error, match=message):
    if breakage == "unknown":
      spec.load_cell(root, "nope")
    elif breakage == "no_metric":
      spec.load_metric(root, "per_layer", "not_there")
    elif breakage == "harness_key":
      harness.job_kwargs(spec.load_cell(root, TINY_CELL), 1, 10)
    else:
      admit(root)


def test_a_later_cell_joins_a_listed_metric_by_an_entry_of_its_own(tmp_path):
  # An entry that names its cells is not edited by a later PR. A later
  # cell that has what the metric needs reports it under a split name
  # (as the contract splits `dispatch_ms.train` / `dispatch_ms.serve`):
  # an entry, and a file that takes the reader whole.
  root = make_tiny_tree(str(tmp_path))
  name = "train_loss_step_64.later"
  with open(os.path.join(root, f"benchmarks/layer_metrics/{name}.py"), "w",
            encoding="utf-8") as f:
    f.write('"""`train_loss_step_64` in the cells of a later PR."""\n'
            "from benchmarks.layer_metrics.train_loss_step_64 import *"
            "  # noqa: F401,F403\n")
  _edit(root, "BENCHMARK.json", lambda b: b["per_layer"].append(dict(
      next(m for m in b["per_layer"] if m["name"] == "train_loss_step_64"),
      name=name, workloads=[TINY_CELL])))
  admit(root)
  assert name in spec.cell_metrics(root, "per_layer", TINY_CELL)
  module = spec.load_metric(root, "per_layer", name)
  assert module.NEEDS == {"steps": 64}
  log = harness.StepLog(lambda line: None)
  log.steps = [harness.Step(i, 0.1 * i, 7.0 - 0.01 * i) for i in range(1, 71)]
  run = harness.Run(cell={}, device={}, peaks={}, kwargs={}, timed_steps=70,
                    t0=0.0, log=log)
  assert module.read(run) == pytest.approx(6.36)


def test_metric_file_missing_a_field_is_refused(tmp_path):
  root = make_tiny_tree(str(tmp_path))
  path = os.path.join(root, "benchmarks/layer_metrics/half.py")
  with open(path, "w", encoding="utf-8") as f:
    f.write("UNIT = 'ms'\n\n\ndef read(run):\n  return 1\n")
  with pytest.raises(spec.SpecError, match="does not define"):
    spec.load_metric(root, "per_layer", "half")


# -- where an entry stands is nobody's business -------------------------------

@pytest.mark.parametrize("order", ["reversed", "appended", "joined"])
def test_no_rule_or_cell_depends_on_where_an_entry_stands(tmp_path, order):
  # A later PR appends its metrics' entries at the end of ``per_layer``,
  # whatever stands there, and its cell's name at the end of an accepted
  # entry's ``workloads``. The real tree, copied, with the list reversed,
  # with one entry more at its end, or with one more cell in every entry
  # that names its cells and whose file asks nothing of them: every rule
  # admits it, every cell reports what it reported (and what it joined),
  # in the entries' order, and what each metric's own test holds about
  # the cells it names (``reported_where_named``) holds there too.
  root = str(tmp_path)
  shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
  shutil.copytree(os.path.join(REPO, "benchmarks"),
                  os.path.join(root, "benchmarks"),
                  ignore=shutil.ignore_patterns("__pycache__"))
  before = {cell: spec.cell_metrics(root, "per_layer", cell)
            for cell in CELLS}
  named = {m["name"]: list(m["workloads"]) for m in BENCH["per_layer"]
           if "workloads" in m}
  new = {cell: [] for cell in CELLS}
  if order == "reversed":
    _edit(root, "BENCHMARK.json", lambda b: b["per_layer"].reverse())
  elif order == "appended":
    new[CELLS[0]] = ["later_metric"]
    with open(os.path.join(root, "benchmarks/layer_metrics/later_metric.py"),
              "w", encoding="utf-8") as f:
      f.write('"""Steps the run timed."""\nLAYER = "driver_loop"\n'
              'UNIT = "count"\nBETTER = "higher"\n'
              'SOURCE = "program_counter"\nMOVES = "samples_per_sec"\n\n\n'
              'def read(run):\n  return run.timed_steps\n')
    _edit(root, "BENCHMARK.json", lambda b: b["per_layer"].append({
        "name": "later_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "driver_loop",
        "moves": "samples_per_sec", "workloads": [CELLS[0]]}))
  else:
    def join(bench):
      for entry in bench["per_layer"]:
        needs = getattr(spec.load_metric(root, "per_layer", entry["name"]),
                        "NEEDS", {})
        late = [c for c in CELLS if c not in entry.get("workloads", CELLS)]
        if late and not needs:
          entry["workloads"].append(late[0])
          new[late[0]].append(entry["name"])
    _edit(root, "BENCHMARK.json", join)
    # The two the trinity-mini cell joined in PR 34 are among them.
    assert {"moe_compact_share", "moe_experts_roofline"} <= {
        name for names in new.values() for name in names}
  admit(root)
  listed = [m["name"] for m in spec.load_benchmark(root)["per_layer"]]
  for cell in CELLS:
    now = spec.cell_metrics(root, "per_layer", cell)
    assert set(now) == set(before[cell] + new[cell])
    assert now == [name for name in listed if name in now]
  for name, cells in named.items():
    reported_where_named(root, name, expected=cells)
