"""The loop thread's milliseconds a tile blocked on ANOTHER HOST THREAD:
per tile of the window its SELF seconds in the root ``io`` (the wait for
the reader's next tile, less the carved ``arrival_wait``) and in
``submit`` (the writer's back-pressure); the mean over the window's
tiles.  What ``bubble_ms.*`` times from the ``tile`` record, from the
spans; not the device's (``wait``) and not the loop's own work, both of
which ``host_serial_ms`` and ``chip_wait_ms`` book elsewhere.

The ``[pace]`` table beside it follows each blocked interval to the
thread at the queue's other end (found through ``cause``:
``threadspans.py``) and charges it, by overlap, to the INNERMOST span
then open there, as a path (``write/put/savez``, ``read/load``): rows in
falling order, then ``that thread idle``; the rows add up to the value.
Then one ``[verdict]`` line says who sets the pace: the cycle, the loop's own
milliseconds, blocked on the writer and on the reader, ``wait``, and
``writer_ms`` and ``reader_ms`` as shares of the cycle.
``None`` on a program whose records carry no ``cause``."""

import threadspans

NAME, UNIT = "loop_blocked_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    return threadspans.loop_blocked_ms(run)
