"""The block skip as a number: of the score tiles (block x block, the
forward's and the backward's grids together) that a causal mask visits
at the window layers' tiling, the share the band's kernels visit (the
program's static counters ``stats["attention"]["window"]``:
``tiles_visited`` / ``tiles_causal``, from ``sequence.plan_tiles``,
which a test holds to the kernel's own mask tables). The pairs inside
the band are a smaller share than this (0.44 against 0.55 at 8,192
tokens and a window of 2,048): edge tiles are visited whole. None where
the program states no such table (latent attention has one kind of
core) or no tiles."""

LAYER = "attention"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"


def read(run):
  window = ((run.stats or {}).get("attention") or {}).get("window")
  if not isinstance(window, dict) or not window.get("tiles_causal"):
    return None
  visited = window.get("tiles_visited")
  return None if visited is None else visited / window["tiles_causal"]
