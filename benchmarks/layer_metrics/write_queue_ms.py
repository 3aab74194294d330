"""How long a job waited for the writer: the mean ``queued_s`` (the put
into the ordered writer's queue to the job's root span, ``diag/trace.py``)
of the root spans of the writer jobs the window's tiles queued, in
milliseconds; beside it the longest and the share of jobs that lay
queued over a millisecond.  Near a whole cycle where the writer is
saturated behind a queue of one, near nothing where it idles.
``None`` on a program whose records carry no ``cause``."""

import threadspans

NAME, UNIT = "write_queue_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    return threadspans.write_queue_ms(run)
