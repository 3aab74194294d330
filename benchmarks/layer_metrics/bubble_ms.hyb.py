"""``bubble_ms.cal`` in the cell ``cal-m16x3-hybrid``: the reader of
``bubble_ms.cal.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not a ``model_config`` PR's to
edit (PR 44, as PR 34's ``.t120`` readers; a ``benchmark`` issue folds the
entries).
The tiles are ``cal-m8x3``'s size, the solve several times as long: the
reader thread has more time to hide behind."""

import harness

NAME, UNIT = "bubble_ms.hyb", "ms"
LAYER, MOVES = "tile loop and overlap", "vis_per_s"


def read(run):
    return harness.load_module("layer_metrics", "bubble_ms.cal").read(run)
