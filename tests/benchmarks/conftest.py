"""Fixtures of the benchmark's own tests."""

import pytest

import bench_testlib


@pytest.fixture
def stub_machine(monkeypatch):
  bench_testlib.stub_machine(monkeypatch)
