"""``phasor_dev_ms`` in the cell ``dosage-beam``: the reader of ``phasor_dev_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
With ``-B 1`` the scope ``rime/phasor`` holds the source sum of 8 x 128
sources AND the gather of the beam's gains to rows (``aft[tslot, sta1] *
aft[tslot, sta2]``), in both programs of a tile that form coherencies
(the solve's and the residual's); ``predict-m8x128`` reads the same sky
without a beam, one program a tile."""

import harness

_WAS = harness.load_module("layer_metrics", "phasor_dev_ms")
NAME, UNIT = "phasor_dev_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
