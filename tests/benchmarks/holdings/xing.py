"""What test_bench_xing.py holds about BENCHMARK.json: the
xing4.0-29b-a4b cell and its configuration are there, and the metrics
the cell reports are found by name, each entry naming the cell among
whichever others: never where an entry, the cell or the configuration
stands, nor how many there are."""

from bench_testlib import reports, reports_none_of
from benchmarks import harness
from benchmarks import spec

CONFIG = "xing4.0-29b-a4b"
CELL = "xing4.0-29b-a4b-train-seq4096-bs1-1chip"
CHECK = "xing4.0-29b-a4b_reference_agrees"
# The accepted metrics whose readers find something in this cell.
JOINED = ["mla_attention_ms", "attention_core_ms", "moe_route_ms",
          "moe_experts_ms", "lm_head_ms", "moe_load_max_over_mean",
          "moe_compact_share", "moe_experts_roofline", "train_loss_step_16",
          "optimizer_ms.lm", "hbm_peak_in_use_gib", "hbm_peak_reserved_gib",
          "lm_head_weight_grad_passes"]
# The entries PR 42 added for it; a later multi-stream cell may join them.
NEW = ["mhc_ms", "mhc_roofline", "mhc_sinkhorn_residual",
       "attention_core_2size_roofline"]
# ``attention_core_roofline`` counts v at q's head size; the grouped-query
# and state-space scopes are no scopes of this decoder; two-matrix experts
# are not its experts.
FOREIGN = ["attention_core_roofline", "gqa_attention_ms",
           "attention_core_window_ms", "attention_core_full_ms",
           "attention_core_window_roofline", "attention_core_full_roofline",
           "attention_tiles_visited_share", "mamba_mixer_ms", "ssd_scan_ms",
           "ssd_scan_roofline", "moe_experts_2mat_roofline",
           "ssd_scan_kernel_share"]


def reads_no_metric_of_the_other_families(root):
  reports_none_of(root, CELL, FOREIGN)
  entries = [spec._entry(spec.load_benchmark(root)["per_layer"], name,
                         "per-layer metric") for name in NEW]
  assert all(CELL in entry["workloads"] for entry in entries)
  assert {entry["layer"] for entry in entries} == {"residual_streams",
                                                   "kernels"}


def cell_and_configuration_are_there(root):
  bench = spec.load_benchmark(root)
  assert CELL in [w["name"] for w in bench["workloads"]]
  assert CONFIG in [c["name"] for c in bench["configs"]]
  cell = spec.load_cell(root, CELL)
  kwargs = harness.job_kwargs(cell, seed=2 ** 31 + 5, seconds=10)
  assert kwargs["lm_head_shards"] == 8 and kwargs["seq_len"] == 4096
  assert kwargs["batch_size"] == 1 and kwargs["lm_first_layer_held"] == 1
  assert spec.check_names(cell) == [CHECK]


def hold(root):
  reports(root, CELL, JOINED + NEW)
  reads_no_metric_of_the_other_families(root)
  cell_and_configuration_are_there(root)
