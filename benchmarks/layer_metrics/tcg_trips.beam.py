"""``tcg_trips`` in the cell ``dosage-beam``: the reader of ``tcg_trips.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
Under ``-B 1`` a cluster's curvature ranges over the squares of its
stations' array-factor gains, so the sweeps' truncated CG takes more trips
a solve than on ``cal-m8x3``'s sky: this is the counter behind
``sage/sweep/inner``."""

import harness

_WAS = harness.load_module("layer_metrics", "tcg_trips")
NAME, UNIT = "tcg_trips.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
