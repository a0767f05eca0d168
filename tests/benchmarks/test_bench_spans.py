"""The program's spans and scopes read beside the device trace
(benchmarks/spans.py): on a hand-made trace whose answers are known, then
on a trace recorded on the chip, then through the harness."""

import json
import os
import time

import pytest

import bench_testlib
from bench_testlib import REPO, TINY_CELL, make_tiny_tree
from benchmarks import harness
from benchmarks import spans
from benchmarks import spec
from benchmarks import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "vgg16_4chip_spans.xplane.pb")
# The metrics that read the program's spans, counters and scopes (PR 23).
NEW_METRICS = ["state_init_s", "step_trace_s", "step_compile_s",
               "compile_cache_misses", "host_busy_ms", "forward_ms",
               "backward_ms", "optimizer_ms", "unscoped_ms",
               "idle_attributed_share"]
US = 1e-6


# -- op_name -> part ----------------------------------------------------------

@pytest.mark.parametrize("op_name, collective, part", [
    ("jit(per_replica_train)/jvp(forward)/_CNNModule/conv10/conv:", False,
     "forward"),
    ("jit(per_replica_train)/transpose(jvp(forward))/_CNNModule/affine0/"
     "dot_general:", False, "backward"),
    ("jit(f)/shard_map/transpose(jvp(forward))/checkpoint/rematted_"
     "computation/tanh", False, "backward"),      # recomputation
    ("jit(f)/forward/dense/dot_general", False, "forward"),  # no grad
    ("jit(f)/shard_map/optimizer_apply/mul:", False, "optimizer"),
    ("jit(f)/shard_map/exchange/convert_element_type", False, "exchange"),
    ("jit(f)/shard_map/metrics/reduce_sum", False, "metrics"),
    ("jit(f)/shard_map/broadcast_in_dim:", False, "unscoped"),
    ("", False, "unscoped"),
    # A collective is exchange whatever scope it sits in.
    ("jit(f)/shard_map/metrics/psum", True, "exchange"),
    ("", True, "exchange"),
    # Innermost scope wins: a reduction hook inside the model.
    ("jit(f)/transpose(jvp(forward))/block/transpose(jvp(exchange))/mul",
     False, "exchange"),
    # A scope is a whole component, not a substring.
    ("jit(f)/forward_hook/metrics_table/add", False, "unscoped"),
    # A model differentiated under the step's own scope (an unrolled,
    # rematerialised stack): the backward pass's operations carry an
    # untransposed ``forward`` INSIDE the transposed one. Backward, the
    # forward that remat repeats there included; forward only where no
    # ``forward`` around the operation is transposed.
    ("transpose(jvp(forward))/x/jvp(forward)/y", False, "backward"),
    ("jvp(forward)/x", False, "forward"),
    ("jit(per_replica_train)/transpose(jvp(forward))/MLAMoELM/jvp(forward)/"
     "MLAMoELM/checkpoint/layer_1/self_attn/gqa_attention/"
     "attention_core_full/splash_mha_dkv_no_residuals/pallas_call:", False,
     "backward"),
    ("jit(per_replica_train)/transpose(jvp(forward))/MLAMoELM/jvp(forward)/"
     "MLAMoELM/checkpoint/rematted_computation/layer_0/mlp/moe_route/while/"
     "body/jit(experts_round)/moe_experts/jit(gmm)/pallas_call:", False,
     "backward"),
    # The remat paths of the glm trace: the scanned layers' (one
    # transposed ``forward``) and the MTP block's (two components).
    ("jit(per_replica_train)/transpose(jvp(forward))/MLAMoELM/layers/while/"
     "body/checkpoint/rematted_computation/mla_attention/attention_core/"
     "pallas_call:", False, "backward"),
    ("jit(per_replica_train)/transpose(jvp(forward))/mtp/jvp(forward)/mtp/"
     "checkpoint/rematted_computation/mtp_block/mul", False, "backward"),
    ("jit(per_replica_train)/jvp(forward)/mtp/jvp(forward)/mtp/checkpoint/"
     "mtp_block/mul", False, "forward"),
    # The innermost scope still decides where it is not ``forward``.
    ("transpose(jvp(forward))/x/jvp(forward)/y/transpose(jvp(exchange))/mul",
     False, "exchange"),
])
def test_part_of(op_name, collective, part):
  assert spans.part_of(op_name, collective) == part


# -- a hand-made trace --------------------------------------------------------

# One step of the device, microseconds from the step's start:
# (name as the trace gives it, begin, end, op_name or None).
STEP_OPS = [
    ("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p)", 0, 30,
     "jit(s)/jvp(forward)/conv"),
    ("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)", 30, 70,
     "jit(s)/transpose(jvp(forward))/while"),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", 30, 50,
     "jit(s)/transpose(jvp(forward))/conv"),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %b)", 50, 70,
     "jit(s)/transpose(jvp(forward))/dot"),
    ("%psum.5 = f32[8]{0} all-reduce(f32[8]{0} %g), channel_id=1", 70, 80,
     "jit(s)/exchange/psum"),
    ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %psum.5)", 80, 82,
     "jit(s)/exchange/div"),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %fusion.6)", 82, 90,
     "jit(s)/optimizer_apply/add"),
    ("%fusion.8 = f32[]{} fusion(f32[8]{0} %l)", 90, 92,
     "jit(s)/metrics/reduce_sum"),
    ("%copy.9 = f32[8]{0} copy(f32[8]{0} %fusion.7)", 92, 95, None),
]                                               # idle from 95 to 100
STEP_US = 100
STEPS = 5   # executions; the window is steps 2 and 3 (xplane.SKIP_STEPS)
# The host, microseconds from the same step's start, one thread.
STEP_HOST = [("train", 1, 99), ("kf/dispatch/train_step", 2, 6),
             ("kf/fetch/metrics", 7, 90), ("kf/feed/wait", 91, 92),
             ("kf/handle/step", 93, 98)]
ENQUEUE_US = 6.5   # the runtime's enqueue, on another thread


def _handmade(host_shift_us=0.0, step_ops=None):
  """The trace above as a serialized XSpace (``step_ops``: another
  step's operations than ``STEP_OPS``, in its form)."""
  step_ops = STEP_OPS if step_ops is None else step_ops
  from jax.profiler import ProfileData
  stat_ids = {"tf_op": 1, "_c": 2, "_p": 3, "run_id": 4, "step_num": 5,
              "step": 6}
  ps = lambda us: int(round(us * 1e6))

  def plane(name, lines, metadata):
    out = [f'planes {{ name: "{name}"']
    ids = {}
    for line_name, events in lines:
      out.append(f'lines {{ name: "{line_name}" timestamp_ns: 0')
      for ev_name, begin, end, stats in events:
        mid = ids.setdefault(ev_name, len(ids) + 1)
        stat_text = " ".join(
            f"stats {{ metadata_id: {stat_ids[k]} int64_value: {v} }}"
            for k, v in stats.items())
        out.append(f"events {{ metadata_id: {mid} offset_ps: {ps(begin)} "
                   f"duration_ps: {ps(end - begin)} {stat_text} }}")
      out.append("}")
    for ev_name, mid in ids.items():
      op_name = metadata.get(ev_name)
      stat_text = ("" if op_name is None else
                   f'stats {{ metadata_id: 1 str_value: "{op_name}" }}')
      out.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                 f'name: "{ev_name}" {stat_text} }} }}')
    for k, sid in stat_ids.items():
      out.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} "
                 f'name: "{k}" }} }}')
    out.append("}")
    return "\n".join(out)

  ops, modules, host, runtime = [], [], [], []
  for k in range(STEPS):
    t = k * STEP_US
    # Execution k was enqueued by dispatch k-1 (flow id 1000 + k - 1).
    modules.append(("jit_step(1)", t, t + 95, {"_c": 1000 + k - 1}))
    ops += [(n, t + b, t + e, {}) for n, b, e, _ in step_ops]
    h = t + host_shift_us
    for name, b, e in STEP_HOST:
      stats = ({"step_num": k} if name == "train" else
               {"step": k} if name.startswith("kf/dispatch") else {})
      host.append((name, h + b, h + e, stats))
    runtime.append(("DoEnqueueProgram", h + ENQUEUE_US, h + ENQUEUE_US + 0.1,
                    {"run_id": 40 + k, "_p": 1000 + k}))
    runtime.append(("CompleteCallbacks", t + 96, t + 96.1,
                    {"run_id": 40 + k, "_c": 1000 + k - 1}))
  text = "\n".join([
      plane("/device:TPU:0",
            [(xplane.MODULES_LINE, modules), (xplane.OPS_LINE, ops)],
            {n: op for n, _, _, op in step_ops}),
      plane("/host:CPU", [("python3", host), ("tfrt-queue", runtime)], {}),
  ])
  return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture
def handmade(tmp_path):
  path = str(tmp_path / "handmade.xplane.pb")
  with open(path, "wb") as f:
    f.write(_handmade())
  return path


def test_op_names_are_read_from_the_event_metadata(handmade):
  names = spans.op_names(handmade)
  assert list(names) == ["/device:TPU:0"]          # device planes only
  assert names["/device:TPU:0"] == {
      n: op for n, _, _, op in STEP_OPS if op is not None}


def test_handmade_parts_are_exclusive_and_add_up_to_busy(handmade):
  reading = spans.reduce(spans.load(handmade))
  assert reading["devices"] == 1
  assert reading["scopes"] == ["exchange", "forward", "metrics",
                               "optimizer_apply"]
  want = {"forward": 30, "backward": 40, "exchange": 12, "optimizer": 8,
          "metrics": 2, "unscoped": 3}             # us per step
  assert reading["parts_ms"] == pytest.approx(
      {k: v * 1e-3 for k, v in want.items()})
  assert reading["busy_ms"] == pytest.approx(0.095)
  # ... which is the yardstick's own busy time over the same window.
  r = xplane.reduce(xplane.load(handmade))
  assert r.steps == 2
  assert reading["busy_ms"] == pytest.approx(1e3 * r.busy_s / r.steps)
  assert reading["parts_ms"]["exchange"] >= r.exchange_exposed_ms


def test_handmade_host_busy_and_idle_attribution(handmade):
  reading = spans.reduce(spans.load(handmade))
  # train is 98 us; 83 us of it blocked on the fetch and 1 us on input.
  assert reading["train_steps"] == 2        # wholly inside the window
  assert reading["host_busy_ms"] == pytest.approx(0.014)
  assert reading["host_spans_ms"] == pytest.approx({
      "kf/dispatch/train_step": 0.004, "kf/fetch/metrics": 0.083,
      "kf/feed/wait": 0.001, "kf/handle/step": 0.005})
  # The chip idles 95..100 of each step; kf/handle/step covers 95..98.
  assert reading["idle_s"] == pytest.approx(10 * US)
  assert reading["idle_attributed_share"] == pytest.approx(60.0)
  # Executions 1..4 were enqueued inside the trace, each after the
  # dispatch that launched it.
  assert reading["launches_checked"] == 4


def _shifted(tmp_path, host_shift_us):
  path = str(tmp_path / f"shifted{host_shift_us:g}.xplane.pb")
  with open(path, "wb") as f:
    f.write(_handmade(host_shift_us=host_shift_us))
  return path


def test_clocks_that_disagree_raise(tmp_path):
  # The host plane 20 ms late: dispatch k now begins long after the
  # device ran what it launched.
  with pytest.raises(RuntimeError, match="clocks disagree"):
    spans.reduce(spans.load(_shifted(tmp_path, 20e3)))


def test_one_clock_read_with_a_millisecond_of_error_does_not_raise(tmp_path):
  # Seen on four chips: the first execution of the window 0.04 ms
  # "before" its dispatch. The profiler's alignment of the two clocks
  # wanders by about a millisecond; that is one clock, not two.
  reading = spans.reduce(spans.load(_shifted(tmp_path, 150.0)))
  assert reading["launches_checked"] == 4
  assert spans.CLOCK_SLACK_S < 0.1 * 57.8e-3   # a tenth of the shortest step


def test_a_trace_without_spans_or_scopes_reads_as_nothing():
  # The parent's program: the old recorded trace has no host plane, no
  # statistics and so no scope. Nothing raises; nothing is reported.
  reading = spans.reduce(spans.load(
      os.path.join(DATA, "vgg16_4chip.xplane.pb")))
  assert reading["scopes"] == [] and reading["parts_ms"] is None
  assert reading["host_busy_ms"] is None
  assert reading["idle_attributed_share"] is None
  assert reading["launches_checked"] == 0
  assert reading["busy_ms"] > 0


# -- the trace held to the program's own scopes ---------------------------------

ALL_SCOPES = ["forward", "exchange", "metrics", "optimizer_apply"]


@pytest.mark.parametrize("found, declared, complaint", [
    (["forward", "exchange"], ALL_SCOPES, None),      # fused away: fine
    (ALL_SCOPES, None, None),                # the parent declares nothing
    ([], ALL_SCOPES, "compile cache"),       # a scopeless executable
    (["exchange", "optimizer_apply"], ALL_SCOPES, "compile cache"),
    (ALL_SCOPES, ["forward", "exchange"], "compile cache"),  # since removed
    (["forward"], ALL_SCOPES + ["remat"], "books under no part"),
], ids=["subset", "undeclared_program", "no_scope", "no_forward",
        "scope_since_removed", "unknown_to_reader"])
def test_check_scopes(found, declared, complaint):
  if complaint is None:
    spans.check_scopes(found, declared)
  else:
    with pytest.raises(RuntimeError, match=complaint):
      spans.check_scopes(found, declared)


def _traced_run(tmp_path, trace_file, stats):
  """A traced run of TINY_CELL whose profiler file is ``trace_file``,
  under a root laid out as ``trace_reading`` expects it."""
  root = str(tmp_path)
  trace_dir = os.path.join(root, harness.TRACE_DIR, TINY_CELL, "plugins",
                           "profile", "run")
  os.makedirs(trace_dir)
  os.symlink(trace_file, os.path.join(trace_dir, "t.xplane.pb"))
  metric_file = os.path.join(root, "benchmarks", "layer_metrics", "m.py")
  return _Run(stats, reduction=object()), metric_file


def test_a_trace_whose_scopes_are_not_the_programs_raises(tmp_path):
  # PR 22's executable out of a warm cache, handed to a program that
  # names its phases: the trace has operations and no scope.
  run, metric_file = _traced_run(
      tmp_path, os.path.join(DATA, "vgg16_4chip.xplane.pb"),
      {"span_totals": {}, "step_scopes": ALL_SCOPES})
  with pytest.raises(RuntimeError, match="persistent compile cache"):
    spans.part_ms(run, metric_file, "forward")


def test_a_program_that_names_no_scopes_gets_no_parts(tmp_path):
  # The other way round (seen on the chip): the parent served an
  # executable that carries this PR's scopes. Not its phases; no raise.
  run, metric_file = _traced_run(tmp_path, RECORDED, {"compile_s": 3.0})
  assert spans.part_ms(run, metric_file, "forward") is None
  assert spans.from_trace(run, metric_file, "host_busy_ms") > 0


def test_a_trace_with_the_programs_scopes_reads(tmp_path):
  run, metric_file = _traced_run(
      tmp_path, RECORDED, {"span_totals": {}, "step_scopes": ALL_SCOPES})
  assert spans.part_ms(run, metric_file, "forward") > 10


# -- stats["span_totals"] -----------------------------------------------------

class _Run:
  """What the readers use of a harness.Run."""

  def __init__(self, stats, reduction=None, cell=TINY_CELL):
    self.stats, self.reduction, self.cell = stats, reduction, {"name": cell}


def test_setup_metrics_read_the_programs_totals():
  row = lambda s: {"n": 1, "total_s": s, "max_s": s}
  counters = lambda misses: {"cache_hits": 2, "cache_misses": misses,
                             "cache_requests": 2 + misses,
                             "backend_compiles": 2 + misses}
  run = _Run({"span_totals": {
      "setup": {"spans": {"setup/build_model": row(0.5),
                          "setup/init_state": row(2.0),
                          "checkpoint/restore": row(0.25),
                          "compile/jaxpr_trace": row(1.5),   # init_state's
                          "compile/backend_compile": row(0.4)},
                "counters": counters(1)},
      "warmup": {"spans": {"compile/jaxpr_trace": row(13.0),
                           "compile/jaxpr_to_mlir": row(0.75),
                           "compile/backend_compile": row(1.25),
                           "dispatch/train_step": row(15.5)},
                 "counters": counters(2)},
      "timed_loop": {"spans": {"compile/backend_compile": row(9.0)},
                     "counters": counters(4)}}})
  read = lambda name: spec.load_metric(REPO, "per_layer", name).read(run)
  assert read("state_init_s") == 2.75
  assert read("step_trace_s") == 13.75
  assert read("step_compile_s") == 1.25
  assert read("compile_cache_misses") == 3   # before the loop


@pytest.mark.parametrize("name", NEW_METRICS)
def test_parent_program_reports_none_and_does_not_raise(name):
  # No span_totals in stats, no reduction (untraced): every reader of
  # spans returns None, which the harness leaves out of the line.
  module = spec.load_metric(REPO, "per_layer", name)
  assert module.read(_Run({"compile_s": 3.0})) is None


# -- the trace recorded on the chip -------------------------------------------

@pytest.fixture(scope="module")
def recorded():
  return spans.reduce(spans.load(RECORDED))


def test_recorded_parts_add_up_to_the_busy_time_per_step(recorded):
  r = xplane.reduce(xplane.load(RECORDED))
  assert recorded["devices"] == r.devices == 2
  assert recorded["scopes"] == ["exchange", "forward", "optimizer_apply"]
  parts = recorded["parts_ms"]
  assert set(parts) == set(spans.PARTS)
  # Busy time per step, chip by chip (the chips of a cut trace hold
  # different numbers of whole steps), by the yardstick's own reduction.
  chips = [xplane.reduce_device(t) for t in xplane.load(RECORDED)]
  busy_ms = sum(1e3 * d.busy_s / d.steps for d in chips) / len(chips)
  assert sum(parts.values()) == pytest.approx(busy_ms, rel=1e-9)
  assert busy_ms == pytest.approx(
      r.device_step_ms * (1 - r.idle_share_worst), rel=2e-3)
  # The exchange part holds every collective, exposed or not, and the
  # arithmetic the program put around them.
  assert parts["exchange"] >= r.exchange_ms >= r.exchange_exposed_ms > 9.0
  # Forward : backward near 1 : 2, and the scopes leave little unnamed.
  assert 1.8 < parts["backward"] / parts["forward"] < 2.6
  assert parts["unscoped"] < 0.05 * r.device_step_ms
  assert parts["optimizer"] > 3.0   # the update of 138 M f32 parameters


def test_recorded_host_side_equals_a_reading_by_hand(recorded):
  """The same numbers from the events themselves, the long way round."""
  from jax.profiler import ProfileData
  planes = {p.name: p for p in ProfileData.from_file(RECORDED).planes}
  host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
          for line in planes[spans.HOST_PLANE].lines for e in line.events
          if e.name == "train" or e.name.startswith("kf/")]
  lo = hi = None
  for name, plane in planes.items():
    if not xplane.DEVICE_PLANE_RE.match(name):
      continue
    (modules,) = [l for l in plane.lines if l.name == xplane.MODULES_LINE]
    starts = sorted(e.start_ns for e in modules.events)[xplane.SKIP_STEPS:]
    lo = starts[0] if lo is None else max(lo, starts[0])
    hi = starts[-1] if hi is None else min(hi, starts[-1])
  steps = [(b, e) for n, b, e in host if n == "train" and b >= lo and e <= hi]
  assert len(steps) == recorded["train_steps"] >= 3
  busy = []
  for b, e in steps:
    blocked = sum(e2 - b2 for n, b2, e2 in host
                  if n in ("kf/fetch/metrics", "kf/feed/wait")
                  and b2 >= b and e2 <= e)
    busy.append((e - b) - blocked)
  assert recorded["host_busy_ms"] == pytest.approx(
      1e-6 * sum(busy) / len(busy), rel=1e-9)
  # A few milliseconds of host against 70 ms of device step.
  assert 0.5 < recorded["host_busy_ms"] < 10
  # Idle gaps here are tens of microseconds of launch latency between
  # operations: no span of the program's lies over them.
  assert recorded["idle_s"] > 0
  assert recorded["idle_attributed_share"] == pytest.approx(0.0, abs=1.0)
  assert recorded["launches_checked"] >= 2 * 3


# -- through the harness ------------------------------------------------------

def test_traced_rehearsal_prints_all_ten_new_metrics(
    tmp_path, stub_machine, monkeypatch):
  # The set-up metrics come from the CPU run's own stats; the trace
  # metrics from the recorded TPU trace, which stands in for the file the
  # profiler wrote (a CPU trace has no device plane).
  # (`optimizer_ms` names its cells, and in the tiny tree the tiny cell.)
  root = make_tiny_tree(str(tmp_path),
                        per_layer=bench_testlib.TINY_LAYER + NEW_METRICS)
  real_find = xplane.find_xplane
  asked = []

  def find(trace_dir):
    asked.append(trace_dir)
    assert real_find(trace_dir)
    return RECORDED

  monkeypatch.setattr(xplane, "find_xplane", find)
  result = harness.run_cell(root, TINY_CELL, seed=1, seconds=7.0,
                            traced=True, t0=time.monotonic(),
                            say=lambda obj: None)
  # The readers looked where the harness wrote: under the root the metric
  # files were loaded from.
  assert set(asked) == {os.path.join(root, harness.TRACE_DIR, TINY_CELL)}
  metrics = {k: v["value"] for k, v in result["metrics"].items()}
  assert set(metrics) == set(bench_testlib.TINY_LAYER + NEW_METRICS)
  assert result["correct"] is True
  assert 0 < metrics["state_init_s"]
  assert 0 < metrics["step_trace_s"] + metrics["step_compile_s"] \
      <= metrics["first_dispatch_s"]
  assert metrics["compile_cache_misses"] == 0      # the cache is off here
  parts = sum(metrics[k] for k in ("forward_ms", "backward_ms",
                                   "optimizer_ms", "unscoped_ms"))
  assert 0.8 * metrics["device_step_ms"] < parts < metrics["device_step_ms"]


def test_the_command_wants_a_trace(capsys):
  with pytest.raises(SystemExit):
    spans.main([])
  assert "trace" in capsys.readouterr().err


def test_the_command_reads_a_trace(capsys):
  assert spans.main([RECORDED]) == 0
  reading = json.loads(capsys.readouterr().out)
  assert set(reading["parts_ms"]) == set(spans.PARTS)
