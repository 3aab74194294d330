"""``chip_wait_ms`` in the cell ``admm-f8-fold``: the reader of ``chip_wait_ms.py``
under a name of this cell's own, because that entry's list of cells
exists and is not a ``model_config`` PR's to edit (PR 42; a
``benchmark`` issue folds the twins into one entry each, with PR 34's
``.t120`` and PR 37's ``.sub``).  Here one chip, so no mean over devices."""

import harness

_WAS = harness.load_module("layer_metrics", "chip_wait_ms")
NAME, UNIT = "chip_wait_ms.fold", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
