"""Mean ``bubble_s`` of the window's ``tile`` records of the simulation
loop (``pipeline.FullBatchPipeline.run_simulation``): the host seconds
of a tile in ``io`` (the ``next()`` on the dataset's tiles) plus
``write``, the loop being synchronous.  In milliseconds.  The ``[span]``
table beside it holds the medians of the loop's five phases and, from
the profiler's slice, the device's idle seconds inside each
``sagecal/<name>`` span."""

import statistics

import scopes

NAME, UNIT = "bubble_ms.predict", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    vals = [r["bubble_s"] for r in scopes.window_records(run)
            if r.get("ev") == "tile" and "bubble_s" in r]
    scopes.span_table(run, ("io", "stage", "predict", "fetch", "write"))
    if not vals:
        print("[span] no tile record with bubble_s in the window")
        return None
    return 1e3 * statistics.mean(vals)
