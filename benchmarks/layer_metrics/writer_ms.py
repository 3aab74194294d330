"""The writer thread's own milliseconds a tile: per tile of the window,
the seconds of the writer's thread in the root spans of the jobs that
tile's ``submit`` spans queued (found through ``cause``:
``threadspans.py``), less every ``wait`` (blocked on the device) under
them; the mean over the window's tiles.  From the program's ``phase``
records alone.  Where this is the cycle, the writer sets the pace and a
faster device or loop buys nothing.

The ``[writer]`` table beside it: for each path of the writer's thread
(``write``, ``write/convert``, ``write/put/keep``, ``write/put/savez``,
``write/put/replace``, ``write/wait``, ``solutions``) the median and the
mean SELF milliseconds a tile; the means add up to the roots.
``None`` on a program whose records carry no ``cause``."""

import threadspans

NAME, UNIT = "writer_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    return threadspans.writer_ms(run)
