"""``corrupt_dev_ms`` in the cell ``predict-extended``: the reader of ``corrupt_dev_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 51, as PR 37's
``.sub``, PR 34's ``.t120``, PR 44's ``.hyb`` and PR 48's ``.beam`` readers;
a ``benchmark`` issue folds the entries).
The Jones sandwich over eight clusters' planes: the sources' kind does
not reach it, so this should read what ``predict-m8x128``'s does."""

import harness

_WAS = harness.load_module("layer_metrics", "corrupt_dev_ms")
NAME, UNIT = "corrupt_dev_ms.ext", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
