"""The trace reduction: interval arithmetic on hand-made timelines whose
answers are known, then the same code on a trace recorded on the chip."""

import os

import pytest

import bench_testlib  # noqa: F401
from benchmarks import xplane
from benchmarks.xplane import DeviceTimeline, Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 1), (0.5, 2), (3, 4)], [(0, 2), (3, 4)]),
    ([(3, 4), (0, 1), (1, 2)], [(0, 2), (3, 4)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([(1, 1), (2, 1)], []),
])
def test_union(intervals, merged):
  assert xplane.union(intervals) == merged
  assert xplane.total(merged) == sum(e - s for s, e in merged)


@pytest.mark.parametrize("a, b, left", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(1, 2), (3, 4), (9, 12)], [(0, 1), (2, 3), (4, 9)]),
    ([(0, 2), (5, 7)], [(1, 6)], [(0, 1), (6, 7)]),
    ([(0, 2)], [(0, 2)], []),
    ([(0, 1), (2, 3)], [(-1, 5)], []),
])
def test_subtract(a, b, left):
  assert xplane.subtract(a, b) == left


def test_split_leaves_self_time_and_containers():
  ops = [Event("while.1", 0, 10), Event("dot.2", 1, 4),
         Event("call.3", 5, 9), Event("fusion.4", 6, 8),
         Event("copy.5", 12, 13)]
  leaves, self_s = xplane.split_leaves(ops)
  assert [e.name for e in leaves] == ["dot.2", "fusion.4", "copy.5"]
  assert self_s == {"while.1": 3, "dot.2": 3, "call.3": 2, "fusion.4": 2,
                    "copy.5": 1}


@pytest.mark.parametrize("name, opcode, kind", [
    ("all-reduce.4", "", ("all-reduce", "")),
    ("all-reduce-start.1 f32[8]", "", ("all-reduce", "-start")),
    ("all-gather-done.12", "", ("all-gather", "-done")),
    ("collective-permute", "", ("collective-permute", "")),
    ("reduce-scatter.3.clone", "", ("reduce-scatter", "")),
    # The opcode decides where the trace gives one: lax.psum's all-reduce
    # is named after the primitive.
    ("psum_invariant.205 f32[25088,4096]", "all-reduce", ("all-reduce", "")),
    ("all-reduce-like-name.1", "fusion", None),
    ("fusion.7", "", None),
    ("reduce.5", "reduce", None),
    ("all-reducer", "", None),
])
def test_collective_kind(name, opcode, kind):
  assert xplane.collective_kind(Event(name, 0, 1, opcode)) == kind


@pytest.mark.parametrize("text, label, opcode", [
    ("%psum_invariant.205 = f32[25088,4096]{1,0:T(8,128)} all-reduce("
     "f32[25088,4096]{1,0:T(8,128)} %fusion.3), channel_id=1, "
     "replica_groups={{0,1,2,3}}",
     "psum_invariant.205 f32[25088,4096]", "all-reduce"),
    ("%all-reduce.34 = (f32[4096]{0:T(1024)S(1)}, f32[4096,4096]{1,0:T(8,128)}"
     ", /*index=5*/f32[1001]{0:T(1024)S(1)}) all-reduce(f32[4096]{0} %a)",
     "all-reduce.34 f32[4096,4096]", "all-reduce"),
    ("%fusion.328 = (bf16[64]{0:T(256)(128)(2,1)}, bf16[64,224,224,64]"
     "{3,0,2,1:T(8,128)(2,1)}) fusion(bf16[64,224,224,64]{3,0,2,1} %s), "
     "kind=kOutput, calls=%fused_computation.503",
     "fusion.328 bf16[64,224,224,64]", "fusion"),
    ("%copy-done.77 = f32[1,64]{1,0:T(1,128)S(1)} copy-done((f32[1,64]{1,0}, "
     "f32[1,64]{1,0}, u32[]{:S(2)}) %copy-start.77)",
     "copy-done.77 f32[1,64]", "copy-done"),
    ("%while.3 = ((f32[2]{0}, s32[]), f32[8]{0}) while(((f32[2], s32[]), "
     "f32[8]) %t), condition=%c", "while.3 f32[8]", "while"),
    ("%fusion.641 = f32[]{:T(128)} fusion(s32[32]{0:T(128)S(1)} %reduce.1)",
     "fusion.641 f32[]", "fusion"),
    ("dot.3", "dot.3", ""),
    ("%bare", "bare", ""),
])
def test_parse_op(text, label, opcode):
  assert xplane.parse_op(text) == (label, opcode)


def _step(b):
  """One 10 s step starting at ``b``: 8 s busy, 2 s idle, 2.5 s of
  collectives of which 2.0 s exposed."""
  return [Event("fusion.1", b + 0, b + 4), Event("while.2", b + 4, b + 8),
          Event("dot.3", b + 4.5, b + 6), Event("all-reduce.4", b + 6, b + 7.5),
          Event("all-gather-start.5", b + 8, b + 8.1),
          Event("fusion.6", b + 8.1, b + 8.6),
          Event("all-gather-done.5", b + 8.6, b + 9)]


def _timeline(device=0, steps=4, shift=0.0):
  ops, modules = [], []
  for i in range(steps):
    b = shift + 10 * i
    ops += _step(b)
    modules.append(Event("jit_step(1)", b, b + 9))
    modules.append(Event("jit_small(2)", b + 9.2, b + 9.3))
  return DeviceTimeline(device, ops, modules)


def test_reduce_device_known_answers():
  r = xplane.reduce_device(_timeline(steps=5), skip_steps=2)
  # Steps start at 0, 10, 20, 30, 40; two skipped; the last one only
  # closes the window: two whole steps, 20 to 40.
  assert r.window == (20, 40) and r.steps == 2
  assert r.step_intervals_s == [10, 10]
  assert r.busy_s == pytest.approx(16.0)
  assert r.exchange_s == pytest.approx(5.0)
  assert r.exchange_exposed_s == pytest.approx(4.0)
  assert r.op_self_s["fusion.1"] == pytest.approx(8.0)
  assert r.op_self_s["while.2"] == pytest.approx(2.0)  # 4 s less its body
  gaps = dict()
  for name, seconds in r.gaps:
    gaps[name] = gaps.get(name, 0) + seconds
  assert gaps["between steps: all-gather-done.5 -> fusion.1"] == \
      pytest.approx(1.0)
  assert gaps["between steps: all-gather-done.5 -> window end"] == \
      pytest.approx(1.0)
  assert gaps["in step: fusion.1 -> dot.3"] == pytest.approx(1.0)
  assert sum(gaps.values()) == pytest.approx(20 - 16)


def test_reduce_means_over_devices_and_reports_the_worst_idle_share():
  slow = _timeline(device=1, steps=5)
  # Device 1 loses fusion.6 in every step: 0.5 s less busy, and the
  # all-gather it hid under is now fully exposed.
  slow.ops = [e for e in slow.ops if e.name != "fusion.6"]
  r = xplane.reduce([_timeline(device=0, steps=5), slow], skip_steps=2)
  assert r.devices == 2 and r.steps == 2
  assert r.window_s == pytest.approx(20.0)
  assert r.busy_s == pytest.approx((16.0 + 15.0) / 2)
  assert r.idle_share_worst == pytest.approx(1 - 15.0 / 20.0)
  assert r.device_step_ms == pytest.approx(10e3)
  assert r.exchange_ms == pytest.approx(2500.0)
  assert r.exchange_exposed_ms == pytest.approx((2000.0 + 2500.0) / 2)
  assert r.device_ops[0] == ["fusion.1", pytest.approx(8.0)]
  assert len(r.device_ops) <= xplane.BREAKDOWN_ROWS
  assert len(r.idle_gaps) <= xplane.BREAKDOWN_ROWS


def test_no_collectives_means_no_exchange():
  t = _timeline(steps=5)
  t.ops = [e for e in t.ops if xplane.collective_kind(e) is None]
  r = xplane.reduce([t], skip_steps=2)
  assert r.exchange_ms is None and r.exchange_exposed_ms is None


def test_without_step_boundaries_the_window_is_the_ops_extent():
  t = DeviceTimeline(0, _step(0), [])
  r = xplane.reduce_device(t)
  assert r.window == (0, 9) and r.steps == 0
  assert r.busy_s == pytest.approx(8.0)


def test_empty_trace_reduces_to_nothing():
  assert xplane.reduce([]) is None
  assert xplane.reduce([DeviceTimeline(0, [], [])]) is None
  assert xplane.find_xplane(DATA + "/nowhere") is None


# -- the recorded trace -------------------------------------------------------
# vgg16-train-bs64-kungfu-sync-4chip on four v5e chips (PR 22), trimmed to
# two of the four device planes and six steps: see data/README.md.

FIXTURE = os.path.join(DATA, "vgg16_4chip.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
  return xplane.load(FIXTURE)


def test_recorded_trace_has_the_lines_the_reduction_reads(recorded):
  assert [t.device for t in recorded] == [0, 3]
  for t in recorded:
    assert len(t.modules) == 7 and len(t.ops) > 3000
    assert xplane.step_module(t.modules).startswith("jit_per_replica_train(")
    for e in t.ops:
      assert len(e.name) < 80 and "%" not in e.name and " = " not in e.name
      assert e.opcode and e.end >= e.start


def test_recorded_step_boundaries(recorded):
  for t in recorded:
    d = xplane.reduce_device(t)
    # 7 executions of the step: 2 skipped, the last closes the window.
    assert d.steps == 4 and len(d.step_intervals_s) == 4
    for interval in d.step_intervals_s:
      assert interval == pytest.approx(0.07033, rel=1e-3)
    assert d.window[1] - d.window[0] == pytest.approx(sum(d.step_intervals_s))


def test_recorded_busy_union_and_idle_share(recorded):
  r = xplane.reduce(recorded)
  assert r.devices == 2 and r.steps == 4
  assert r.device_step_ms == pytest.approx(70.328, abs=0.01)
  assert r.window_s == pytest.approx(0.281314, rel=1e-4)
  assert 0 < r.busy_s < r.window_s
  # The chips are busy all but 0.05% of whole steps.
  assert r.idle_share_worst == pytest.approx(5.2e-4, rel=0.05)
  assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-9


def test_recorded_collectives_are_found_by_opcode_and_fully_exposed(recorded):
  r = xplane.reduce(recorded)
  # Three synchronous all-reduces a step (one named psum_invariant.*), 553 MB
  # of f32 gradients: 9.70 ms, during which nothing else runs on the core.
  assert r.exchange_ms == pytest.approx(9.70, abs=0.02)
  assert r.exchange_exposed_ms == pytest.approx(r.exchange_ms)
  names = [n for n, _ in r.device_ops]
  assert names[0] == "psum_invariant.205 f32[25088,4096]"
  assert "all-reduce.34 f32[4096,4096]" in names
  per_device = [xplane.reduce_device(t) for t in recorded]
  for d in per_device:
    kinds = {e.name.split(".")[0] for e in recorded[0].ops
             if xplane.collective_kind(e)}
    assert kinds == {"psum_invariant", "all-reduce"}
    assert d.exchange_exposed_s == pytest.approx(d.exchange_s)
