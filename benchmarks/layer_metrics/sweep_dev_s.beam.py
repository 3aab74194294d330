"""``sweep_dev_s`` in the cell ``dosage-beam``: the reader of ``sweep_dev_s.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
Eight clusters on summed coherencies, as in ``cal-m8x3``: the beam is
folded in before the sweeps see anything."""

import harness

_WAS = harness.load_module("layer_metrics", "sweep_dev_s")
NAME, UNIT = "sweep_dev_s.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
