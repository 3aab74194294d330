"""``solve_s`` in the cell ``cal-m16x3-hybrid``: the reader of ``solve_s.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 44, as PR 34's
``.t120`` readers; a ``benchmark`` issue folds the entries).
Under a hybrid cluster file the solve is 16 clusters of kmax = 5 chunk
slots each on flat rows ``[8, B]``."""

import harness

NAME, UNIT = "solve_s.hyb", "s"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    return harness.load_module("layer_metrics", "solve_s").read(run)
