"""``solver_trips`` in the cell ``dosage-beam``: the reader of ``solver_trips.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
The trips the per-cluster solvers executed a tile (``-g 2`` a sweep), to be
read beside ``cal-m8x3``'s for the same flags without a beam."""

import harness

_WAS = harness.load_module("layer_metrics", "solver_trips")
NAME, UNIT = "solver_trips.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
