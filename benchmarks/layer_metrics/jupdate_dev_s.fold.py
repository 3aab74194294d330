"""``jupdate_dev_s`` in the cell ``admm-f8-fold``: the reader of ``jupdate_dev_s.py``
under a name of this cell's own, because that entry's list of cells
exists and is not a ``model_config`` PR's to edit (PR 42; a
``benchmark`` issue folds the twins into one entry each, with PR 34's
``.t120`` and PR 37's ``.sub``).  Here the J updates of EIGHT subbands on the one chip, batched: against ``admm-f4-mesh``'s (one subband a chip) it says what of a J update is paid once for eight."""

import harness

_WAS = harness.load_module("layer_metrics", "jupdate_dev_s")
NAME, UNIT = "jupdate_dev_s.fold", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
