"""The reader thread's own milliseconds a tile: per tile of the window,
the seconds of the reader's thread in the root span that produced that
tile (``read``, found through the ``cause`` of the loop's ``io``:
``threadspans.py``), less every ``wait`` under it; the mean over the
window's tiles.  From the program's ``phase`` records alone.

The ``[reader]`` table beside it: for each path of the reader's thread
(``read``, ``read/load``, ``read/stage``, ``read/stage/pack``,
``read/stage/copy``, ``read/stage/beam``) the median and the mean SELF
milliseconds a tile; the means add up to the roots.  ``read``'s own row
holds what runs under no span of the program's, the harness's dataset
wrapper among it.  ``copy`` is where the runtime holds a thread behind
the program that runs: in a solver cell most of the reader's seconds are
there and are no work.  ``arrival_wait`` (the tenant's data rate) is
printed apart and not counted.
``None`` on a program whose records carry no ``cause``."""

import threadspans

NAME, UNIT = "reader_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    return threadspans.reader_ms(run)
