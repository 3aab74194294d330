"""``admm_iters`` in the cell ``admm-f8-fold``: the reader of ``admm_iters.py``
under a name of this cell's own, because that entry's list of cells
exists and is not a ``model_config`` PR's to edit (PR 42; a
``benchmark`` issue folds the twins into one entry each, with PR 34's
``.t120`` and PR 37's ``.sub``).  Here expected here: the source's ``-A 10``, uncut."""

import harness

_WAS = harness.load_module("layer_metrics", "admm_iters")
NAME, UNIT = "admm_iters.fold", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
