"""Mean ``bubble_s`` of the window's ``tile`` records of the consensus
interval loop: host seconds an interval's step was blocked on data
movement (the wait for the staged interval, writer back-pressure), as
``cli_mpi.ConsensusStepper.step`` adds them up.  In milliseconds.  The
``[span]`` table beside it holds the medians of the loop's phases and,
from the profiler's slice, the first device's idle seconds inside each
``sagecal/<name>`` span.  ``None`` on a program whose interval records
carry no ``bubble_s``."""

import statistics

import scopes

NAME, UNIT = "bubble_ms.admm", "ms"
LAYER, MOVES = "tile loop and overlap", "vis_per_s"


def read(run):
    vals = [r["bubble_s"] for r in scopes.window_records(run)
            if r.get("ev") == "tile" and "bubble_s" in r]
    scopes.span_table(run, ("io", "read", "stage", "solve", "fetch",
                            "residual", "write"))
    if not vals:
        print("[span] no tile record with bubble_s in the window")
        return None
    return 1e3 * statistics.mean(vals)
