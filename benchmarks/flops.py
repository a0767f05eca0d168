"""Operations a model's forward pass requires, from its layer table.

A configuration file states ``forward_flops_per_sample`` as a number, so
a new configuration brings data and no code; where it also carries a
``layer_table`` this module recomputes the number from it and the tests
hold the two together. Only multiply-accumulates in convolutions and
dense layers are counted (2 operations each): normalisation,
activations, pooling and the loss are under 1% of either model here and
are bound by memory, not by the MXU the peak describes.

Table rows:
  ["conv", k_h, k_w, c_in, c_out, out_h, out_w, repeat]
  ["dense", n_in, n_out, repeat]
"""

from __future__ import annotations

from typing import Sequence


def row_macs(row: Sequence) -> int:
  kind = row[0]
  if kind == "conv":
    _, k_h, k_w, c_in, c_out, out_h, out_w, repeat = row
    return k_h * k_w * c_in * c_out * out_h * out_w * repeat
  if kind == "dense":
    _, n_in, n_out, repeat = row
    return n_in * n_out * repeat
  raise ValueError(f"unknown layer-table row kind {kind!r}")


def forward_macs(layer_table: Sequence[Sequence]) -> int:
  return sum(row_macs(row) for row in layer_table)


def forward_flops(layer_table: Sequence[Sequence]) -> int:
  return 2 * forward_macs(layer_table)


def table_params(layer_table: Sequence[Sequence]) -> int:
  """Weights in the counted layers (no biases, no normalisation)."""
  total = 0
  for row in layer_table:
    if row[0] == "conv":
      _, k_h, k_w, c_in, c_out, _, _, repeat = row
      total += k_h * k_w * c_in * c_out * repeat
    elif row[0] == "dense":
      _, n_in, n_out, repeat = row
      total += n_in * n_out * repeat
    else:
      raise ValueError(f"unknown layer-table row kind {row[0]!r}")
  return total
