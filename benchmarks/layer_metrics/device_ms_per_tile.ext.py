"""``device_ms_per_tile`` in the cell ``predict-extended``: the reader of ``device_ms_per_tile.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 51, as PR 37's
``.sub``, PR 34's ``.t120``, PR 44's ``.hyb`` and PR 48's ``.beam`` readers;
a ``benchmark`` issue folds the entries).
A tile's device time here is eight clusters' source sums with the
shapelet basis evaluated for every source slot, and the sandwich."""

import harness

_WAS = harness.load_module("layer_metrics", "device_ms_per_tile")
NAME, UNIT = "device_ms_per_tile.ext", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
