"""Mean ``bubble_s`` of the window's ``tile`` records: host seconds a
tile's step was blocked on data movement (waiting for the prefetched
tile, writer back-pressure), as ``pipeline.TileStepper.step`` adds them
up.  In milliseconds."""

import statistics

NAME, UNIT = "bubble_ms.cal", "ms"
LAYER, MOVES = "tile loop and overlap", "vis_per_s"


def read(run):
    vals = [r["bubble_s"] for r in run.diag_records()
            if r.get("ev") == "tile" and "bubble_s" in r]
    return 1e3 * statistics.mean(vals) if vals else None
