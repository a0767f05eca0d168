"""forward_flops_per_sample of each configuration, recomputed from its
layer table and held against the published figure."""

import json
import os

import pytest

from bench_testlib import REPO
from benchmarks import flops

# Forward multiply-accumulates per 224x224 image and parameters, as
# published: ResNet-50 v1.5 ~4.1 GMACs / 25.6 M; VGG-16 ~15.5 GMACs / 138 M.
PUBLISHED = {"resnet50": (4.1e9, 25.6e6), "vgg16": (15.5e9, 138e6)}


def _config(name):
  with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
    return json.load(f)


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_file_number_is_the_table_sum(name):
  config = _config(name)
  assert config["forward_flops_per_sample"] == flops.forward_flops(
      config["layer_table"])


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_macs_within_3_percent_of_published(name):
  macs = flops.forward_macs(_config(name)["layer_table"])
  assert macs == pytest.approx(PUBLISHED[name][0], rel=0.03)


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_table_weights_match_the_parameter_count(name):
  # Biases and batch-norm scales are not in the table: under 0.3%.
  config = _config(name)
  weights = flops.table_params(config["layer_table"])
  assert weights == pytest.approx(config["parameters"], rel=0.003)
  assert config["parameters"] == pytest.approx(PUBLISHED[name][1], rel=0.01)


@pytest.mark.parametrize("row, macs", [
    (["conv", 3, 3, 64, 128, 56, 56, 2], 3 * 3 * 64 * 128 * 56 * 56 * 2),
    (["dense", 4096, 1001, 1], 4096 * 1001),
])
def test_row_macs(row, macs):
  assert flops.row_macs(row) == macs


def test_unknown_row_kind_is_an_error():
  with pytest.raises(ValueError):
    flops.row_macs(["pool", 2, 2])
