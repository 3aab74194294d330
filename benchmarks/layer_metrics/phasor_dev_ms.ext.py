"""``phasor_dev_ms`` in the cell ``predict-extended``: the reader of ``phasor_dev_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 51, as PR 37's
``.sub``, PR 34's ``.t120``, PR 44's ``.hyb`` and PR 48's ``.beam`` readers;
a ``benchmark`` issue folds the entries).
The scope ``rime/phasor`` holds this sky's source sum with every
envelope in it: the Gaussian, ring and disk factors fuse into the sum's
one fusion, the shapelet basis is read apart as ``shapelet_dev_ms.ext``
(its seconds are part of these)."""

import harness

_WAS = harness.load_module("layer_metrics", "phasor_dev_ms")
NAME, UNIT = "phasor_dev_ms.ext", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
