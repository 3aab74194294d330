"""``solve_s.admm`` in the cell ``admm-f8-fold``: the reader of ``solve_s.admm.py``
under a name of this cell's own, because that entry's list of cells
exists and is not a ``model_config`` PR's to edit (PR 42; a
``benchmark`` issue folds the twins into one entry each, with PR 34's
``.t120`` and PR 37's ``.sub``).  Here ONE device execution of all ten ADMM iterations of an interval, the eight J updates of each batched under ``jax.vmap``."""

import harness

_WAS = harness.load_module("layer_metrics", "solve_s.admm")
NAME, UNIT = "solve_s.fold", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
