"""``residual_ms`` in the cell ``cal-m16x3-hybrid``: the reader of
``residual_ms.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not a ``model_config`` PR's to
edit (PR 44, as PR 34's ``.t120`` readers; a ``benchmark`` issue folds the
entries).
Here the residual program gathers a Jones a row and chunk, and leaves
the negative-id cluster out of the model it subtracts."""

import harness

NAME, UNIT = "residual_ms.hyb", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    return harness.load_module("layer_metrics", "residual_ms").read(run)
