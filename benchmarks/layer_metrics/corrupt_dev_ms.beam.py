"""``corrupt_dev_ms`` in the cell ``dosage-beam``: the reader of ``corrupt_dev_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
Here the sandwich ``J_p C J_q^H`` of the residual program over eight
clusters whose coherencies already hold the beam's gains."""

import harness

_WAS = harness.load_module("layer_metrics", "corrupt_dev_ms")
NAME, UNIT = "corrupt_dev_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
