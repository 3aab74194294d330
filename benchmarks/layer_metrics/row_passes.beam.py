"""``row_passes`` in the cell ``dosage-beam``: the reader of ``row_passes.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
The passes through the tile's rows a solve makes; the beam changes the
coherencies' values, not their shapes, so this should read what
``cal-m8x3`` reads."""

import harness

_WAS = harness.load_module("layer_metrics", "row_passes")
NAME, UNIT = "row_passes.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
