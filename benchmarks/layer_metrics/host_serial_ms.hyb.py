"""``host_serial_ms`` in the cell ``cal-m16x3-hybrid``: the reader of
``host_serial_ms.py`` under a name of this cell's own, because that entry
lists its cells and a list that exists is not this PR's to edit (PR 53, as
PR 48's ``.beam`` and PR 51's ``.ext`` readers; a ``benchmark`` issue
folds the entries).  The one cell that had no ``[host]`` table: its chip
is idle 11-13 % in gaps of 21-27 ms, and the host's own work a tile beside
sixteen clusters' hybrid chunks is what those are sized from."""

import harness

_WAS = harness.load_module("layer_metrics", "host_serial_ms")
NAME, UNIT = "host_serial_ms.hyb", _WAS.UNIT
LAYER, MOVES = _WAS.LAYER, _WAS.MOVES


def read(run):
    return _WAS.read(run)
