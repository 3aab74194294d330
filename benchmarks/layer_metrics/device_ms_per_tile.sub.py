"""``device_ms_per_tile`` in the cell ``subtract-m8x128``: the reader of
``device_ms_per_tile.py`` under a name of this cell's own, because that
entry lists ``predict-m8x128`` alone and a list that exists is not a
``model_config`` PR's to edit (PR 37; a ``benchmark`` issue folds the
two entries into one, with PR 34's ``.t120`` twins).  Here a tile's
device time is eight clusters' source sums, the Jones sandwich under the
mask and one pass over the input's 0.6 MB."""

import harness

_WAS = harness.load_module("layer_metrics", "device_ms_per_tile")
NAME, UNIT = "device_ms_per_tile.sub", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
