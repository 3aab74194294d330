"""``corrupt_dev_ms`` in the cell ``subtract-m8x128``: the reader of
``corrupt_dev_ms.py`` under a name of this cell's own, because that
entry lists ``predict-m8x128`` alone and a list that exists is not a
``model_config`` PR's to edit (PR 37; a ``benchmark`` issue folds the
two entries into one, with PR 34's ``.t120`` twins).  The sandwich runs
over all eight clusters under the mask, as in ``predict-m8x128``."""

import harness

_WAS = harness.load_module("layer_metrics", "corrupt_dev_ms")
NAME, UNIT = "corrupt_dev_ms.sub", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
