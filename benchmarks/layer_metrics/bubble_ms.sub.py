"""``bubble_ms.predict`` in the cell ``subtract-m8x128``: the reader of
``bubble_ms.predict.py`` under a name of this cell's own, because that
entry lists ``predict-m8x128`` alone and a list that exists is not a
``model_config`` PR's to edit (PR 37; a ``benchmark`` issue folds the
two entries into one, with PR 34's ``.t120`` twins).  A tile's read here
holds an input column that matters, and its write keeps that column
beside the output."""

import harness

_WAS = harness.load_module("layer_metrics", "bubble_ms.predict")
NAME, UNIT = "bubble_ms.sub", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
