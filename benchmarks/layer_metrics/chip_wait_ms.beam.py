"""``chip_wait_ms`` in the cell ``dosage-beam``: the reader of ``chip_wait_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
The device's idle milliseconds a tile: three programs a tile (the coherency
program, the solve, the residual program) with the host's dispatches and
read-backs between them."""

import harness

_WAS = harness.load_module("layer_metrics", "chip_wait_ms")
NAME, UNIT = "chip_wait_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
