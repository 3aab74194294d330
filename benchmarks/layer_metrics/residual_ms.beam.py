"""``residual_ms`` in the cell ``dosage-beam``: the reader of ``residual_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
Under ``-B 1`` the residual program makes the beam's tables and the XLA
source sum of 1024 sources again before it subtracts
(``residual._model_multifreq``): the dispatch this reads is that program's."""

import harness

_WAS = harness.load_module("layer_metrics", "residual_ms")
NAME, UNIT = "residual_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
