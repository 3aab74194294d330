"""``host_serial_ms`` in the cell ``dosage-beam``: the reader of ``host_serial_ms.py``
under a name of this cell's own, because that entry lists its cells and a
list that exists is not a ``model_config`` PR's to edit (PR 48, as PR 37's
``.sub``, PR 34's ``.t120`` and PR 44's ``.hyb`` readers; a
``benchmark`` issue folds the entries).
The host's own work a tile beside a reader thread that also stages the
tile's ``gmst`` track (``stage/beam``)."""

import harness

_WAS = harness.load_module("layer_metrics", "host_serial_ms")
NAME, UNIT = "host_serial_ms.beam", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
