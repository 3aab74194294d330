"""Device milliseconds a tile spends in the Jones sandwich: self time of
the LEAF operations under the scope ``rime/corrupt``
(``rime/predict.py``: ``predict_model``, ``apply_jones``, ``model8``) in
the traced slice, over the tiles begun in it."""

import scopes

NAME, UNIT = "corrupt_dev_ms", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    return scopes.per_tile(run, "rime/corrupt", 1e3)
