"""``moe_rows_per_pair``: the rows the routed path's grouped products
compute for a pair routed here, read from the program's counter;
nothing where the program has none; admitted by the rules every metric
is held to, and reported in the cells its entry names."""

import numpy as np
import pytest

from bench_testlib import REPO, holding
from benchmarks import harness
from benchmarks import spec

H = holding("moe_rows_per_pair")
NAME = H.NAME


def _run(stats):
  return harness.Run(cell={"name": "x", "config_data": {}}, device={},
                     peaks={}, kwargs={}, timed_steps=20, t0=0.0,
                     stats=stats)


@pytest.mark.parametrize("moe, want", [
    ({"rows_per_pair": 1.46, "row_tile": 256, "steps": 25}, 1.46),
    ({"rows_per_pair": 2.75}, 2.75),
    ({"rows_per_pair": 1.0}, 1.0),
])
def test_reads_the_ratio_from_the_programs_counter(moe, want):
  assert spec.load_metric(REPO, "per_layer", NAME).read(_run({"moe": moe})
                                                        ) == want


@pytest.mark.parametrize("stats", [
    None, {}, {"moe": None},
    # The parent of the PR that added the counter: the other counters
    # and no ratio.
    {"moe": {"pairs_routed_here": 20480.0, "pairs_dropped": 0.0,
             "load_max_over_mean": 2.2, "compact_share": 1.0,
             "buffer_rows": 8192}},
])
def test_reads_nothing_where_the_program_has_no_such_counter(stats):
  assert spec.load_metric(REPO, "per_layer", NAME).read(_run(stats)) is None


def test_is_reported_in_exactly_the_cells_its_entry_names():
  H.hold(REPO)


def test_the_program_leaves_what_the_metric_reads(monkeypatch):
  # ``stats["moe"]`` as the program's model makes it from its counter
  # rows (two steps; the fifth counter the rows in the products' tiles)
  # and the log line that states the tile beside it.
  from kf_benchmarks_tpu.models import mla_moe_lm
  from kf_benchmarks_tpu.utils import log as log_util
  lines = []
  monkeypatch.setattr(log_util, "log_fn", lines.append)
  model = mla_moe_lm.MLAMoELMModel()
  model.cfg = mla_moe_lm.load_lm_config("tiny", 3, 4, 1)
  assert mla_moe_lm.MOE_COUNTERS[4] == "rows_in_tiles"
  moe = model.counter_stats(np.asarray([[30.0, 0.0, 1.5, 3.0, 45.0],
                                        [34.0, 0.0, 2.5, 2.0, 51.0]]))
  assert moe["rows_per_pair"] == pytest.approx(96 / 64)
  assert spec.load_metric(REPO, "per_layer", NAME).read(
      _run({"moe": moe})) == pytest.approx(1.5)
  assert [line for line in lines if line.startswith(
      "moe layout: row tile ")] == [lines[-1]]
  assert "rows per pair 1.5 " in lines[-1]
