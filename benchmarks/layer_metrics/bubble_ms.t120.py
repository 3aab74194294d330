"""``bubble_ms.cal`` in the cell ``cal-t120``: the reader of
``bubble_ms.cal.py`` under a name of this cell's own, because that entry
lists ``cal-m8x3`` alone and a list that exists is not a ``model_config``
PR's to edit (PR 34; a ``benchmark`` issue folds the two entries into
one).  At 226 920 rows a tile the reader thread has 76 MB to read and
stage behind the solve, twelve times ``cal-m8x3``'s."""

import harness

NAME, UNIT = "bubble_ms.t120", "ms"
LAYER, MOVES = "tile loop and overlap", "vis_per_s"


def read(run):
    return harness.load_module("layer_metrics", "bubble_ms.cal").read(run)
