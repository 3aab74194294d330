"""``chip_wait_ms`` in the cell ``predict-extended``: the reader of ``chip_wait_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 51, as PR 37's
``.sub``, PR 34's ``.t120``, PR 44's ``.hyb`` and PR 48's ``.beam`` readers;
a ``benchmark`` issue folds the entries).
The device's idle milliseconds a tile: one program a tile, the next
dispatched before the one before is waited for."""

import harness

_WAS = harness.load_module("layer_metrics", "chip_wait_ms")
NAME, UNIT = "chip_wait_ms.ext", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
