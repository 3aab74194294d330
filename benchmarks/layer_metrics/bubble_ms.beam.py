"""``bubble_ms.cal`` in the cell ``dosage-beam``: the reader of ``bubble_ms.cal.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
The reader's thread stages the beam's leaves with each tile
(``stage/beam``); what of that the loop waits for shows here."""

import harness

_WAS = harness.load_module("layer_metrics", "bubble_ms.cal")
NAME, UNIT = "bubble_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
