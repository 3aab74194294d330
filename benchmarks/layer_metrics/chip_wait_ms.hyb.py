"""``chip_wait_ms`` in the cell ``cal-m16x3-hybrid``: the reader of
``chip_wait_ms.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not this PR's to edit (PR 53, as
PR 48's ``.beam`` and PR 51's ``.ext`` readers; a ``benchmark`` issue
folds the entries).  The device's idle milliseconds a tile with the
``[wait]`` table that charges each gap to the loop's innermost span: the
one cell that had none."""

import harness

_WAS = harness.load_module("layer_metrics", "chip_wait_ms")
NAME, UNIT = "chip_wait_ms.hyb", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
