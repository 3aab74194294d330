"""``solve_s`` in the cell ``dosage-beam``: the reader of ``solve_s.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
Under ``-B 1`` the solve's span begins with the XLA coherency program
(the beam's tables, the source sum with the gains folded in) in the Pallas
kernel's place, then the sweeps and the refine of ``cal-m8x3``."""

import harness

_WAS = harness.load_module("layer_metrics", "solve_s")
NAME, UNIT = "solve_s.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
