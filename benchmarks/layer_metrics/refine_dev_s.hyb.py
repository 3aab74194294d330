"""``refine_dev_s`` in the cell ``cal-m16x3-hybrid``: the reader of
``refine_dev_s.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not a ``model_config`` PR's to
edit (PR 44, as PR 34's ``.t120`` readers; a ``benchmark`` issue folds the
entries).
Here the joint refine's model passes run on flat rows (``refine_rows``
"flat") over 26 live solutions in 80 slots."""

import harness

NAME, UNIT = "refine_dev_s.hyb", "s"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    return harness.load_module("layer_metrics", "refine_dev_s").read(run)
