"""``phasor_dev_ms`` in the cell ``subtract-m8x128``: the reader of
``phasor_dev_ms.py`` under a name of this cell's own, because that entry
lists ``predict-m8x128`` alone and a list that exists is not a
``model_config`` PR's to edit (PR 37; a ``benchmark`` issue folds the
two entries into one, with PR 34's ``.t120`` twins).  The ignored
cluster's coherencies are still formed (the mask acts in
``predict_model``), so this should read what ``predict-m8x128``'s does."""

import harness

_WAS = harness.load_module("layer_metrics", "phasor_dev_ms")
NAME, UNIT = "phasor_dev_ms.sub", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
