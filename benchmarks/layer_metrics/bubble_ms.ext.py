"""``bubble_ms.predict`` in the cell ``predict-extended``: the reader of ``bubble_ms.predict.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 51, as PR 37's
``.sub``, PR 34's ``.t120``, PR 44's ``.hyb`` and PR 48's ``.beam`` readers;
a ``benchmark`` issue folds the entries).
The loop is ``predict-m8x128``'s; with a device program of tenths of a
second a tile the reader and the writer run far ahead of it."""

import harness

_WAS = harness.load_module("layer_metrics", "bubble_ms.predict")
NAME, UNIT = "bubble_ms.ext", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
