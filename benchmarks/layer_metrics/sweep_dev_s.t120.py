"""``sweep_dev_s`` in the cell ``cal-t120``: the reader of ``sweep_dev_s.py``
under a name of this cell's own, because that entry lists ``cal-m8x3``
alone and a list that exists is not a ``model_config`` PR's to edit (PR
34; a ``benchmark`` issue folds the two entries into one)."""

import harness

NAME, UNIT = "sweep_dev_s.t120", "s"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    return harness.load_module("layer_metrics", "sweep_dev_s").read(run)
