"""What test_bench_moe_rows_per_pair.py holds about BENCHMARK.json: the
reader of the grouped products' rows per pair is reported in the four
language-model cells, which its entry names."""

from bench_testlib import reports

NAME = "moe_rows_per_pair"
CELLS = ("glm-4.7-flash-train-seq4096-bs2-1chip",
         "trinity-mini-train-seq8192-bs1-1chip",
         "nemotron-3-nano-30b-a3b-train-seq8192-bs1-1chip",
         "xing4.0-29b-a4b-train-seq4096-bs1-1chip")


def hold(root):
  for cell in CELLS:
    reports(root, cell, [NAME])
