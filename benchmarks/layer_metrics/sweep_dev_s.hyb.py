"""``sweep_dev_s`` in the cell ``cal-m16x3-hybrid``: the reader of
``sweep_dev_s.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not a ``model_config`` PR's to
edit (PR 44, as PR 34's ``.t120`` readers; a ``benchmark`` issue folds the
entries).
Here the sweeps carry their running residual on flat rows and gather
the Jones for every row (``sweep_rows`` "flat")."""

import harness

NAME, UNIT = "sweep_dev_s.hyb", "s"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    return harness.load_module("layer_metrics", "sweep_dev_s").read(run)
