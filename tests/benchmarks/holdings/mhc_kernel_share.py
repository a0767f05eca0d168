"""What test_bench_mhc_kernel_share.py holds about BENCHMARK.json: the
reader of the hyper-connections' kernel share appended last is
reported in the xing cell, among whichever others its entry names."""

from bench_testlib import reports

XING = "xing4.0-29b-a4b-train-seq4096-bs1-1chip"
NAME = "mhc_kernel_share"


def hold(root):
  reports(root, XING, [NAME])
