"""``moe_compact_share`` (PR 28): the share of mixture layers whose
pairs fit one round of the routed path, read from the program's
counter; nothing where the program has none; admitted by the rules every
metric is held to, and reported in the cells its entry names (found by
its name: where an entry stands in ``per_layer`` is nobody's business)."""

import pytest

from bench_testlib import REPO
from benchmarks import harness
from benchmarks import spec
from test_bench_spec import metric_rules, reported_where_named

NAME = "moe_compact_share"
CELL = "glm-4.7-flash-train-seq4096-bs2-1chip"


def _run(stats):
  return harness.Run(cell={"name": "x", "config_data": {}}, device={},
                     peaks={}, kwargs={}, timed_steps=20, t0=0.0,
                     stats=stats)


@pytest.mark.parametrize("moe, want", [
    ({"compact_share": 1.0, "buffer_rows": 8192, "steps": 25}, 1.0),
    ({"compact_share": 0.96, "buffer_rows": 8192}, 0.96),   # 5 of 125 not
    ({"compact_share": 0.0}, 0.0),          # 0 is a reading, not "none"
])
def test_reads_the_share_from_the_programs_counter(moe, want):
  assert spec.load_metric(REPO, "per_layer", NAME).read(_run({"moe": moe})
                                                        ) == want


@pytest.mark.parametrize("stats", [
    None, {}, {"moe": None},
    # The parent of the PR that added the counter: the other counters
    # and no share.
    {"moe": {"pairs_routed_here": 20480.0, "pairs_dropped": 0.0,
             "load_max_over_mean": 2.2, "experts_held": 8}},
])
def test_reads_nothing_where_the_program_has_no_such_counter(stats):
  assert spec.load_metric(REPO, "per_layer", NAME).read(_run(stats)) is None


def test_is_reported_in_exactly_the_cells_its_entry_names():
  metric_rules(REPO, "per_layer", NAME)
  reported_where_named(REPO, NAME, expected=[CELL])


def test_the_program_leaves_what_the_metric_reads():
  # The keys of ``stats["moe"]`` as the program's model makes them from
  # its counter rows (two steps of three mixture layers, one of which
  # took a second round in the second step).
  import numpy as np
  from kf_benchmarks_tpu.models import mla_moe_lm
  model = mla_moe_lm.MLAMoELMModel()
  model.cfg = mla_moe_lm.load_lm_config("tiny", 3, 4, 1)
  assert mla_moe_lm.MOE_COUNTERS[3] == "compact_layers"
  moe = model.counter_stats(np.asarray([[30.0, 0.0, 1.5, 3.0],
                                        [34.0, 0.0, 2.5, 2.0]]))
  assert moe["compact_share"] == pytest.approx(5 / 6)
  assert spec.load_metric(REPO, "per_layer", NAME).read(
      _run({"moe": moe})) == pytest.approx(5 / 6)
