"""The rows the routed path's grouped products compute for each (token,
expert) pair routed to a held expert: the rows the kernel's row tiles
cover over the rounds that ran, every mixture layer of the timed steps,
over the pairs routed here (the program's counter
``stats["moe"]["rows_per_pair"]``, models/mla_moe_lm.py; the tile is
``stats["moe"]["row_tile"]``, parallel/expert.row_tile). megablox visits a
row tile once for every group that touches it: with the groups packed end
to end an expert's rows straddle tiles that its neighbours visit too, so
the products compute two to three rows a pair at the cells' sizes; with
every held expert's rows from a tile boundary, one plus the padding of
each expert's last tile. 1 is no row computed beyond the pairs. A ratio
that rises is work the products do for rows that hold no pair of their
expert, and moves ``moe_experts_ms`` with it. None where the program has
no such counter (a program before the tile-aligned layout)."""

LAYER = "moe"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  return ((run.stats or {}).get("moe") or {}).get("rows_per_pair")
