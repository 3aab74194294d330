"""Median duration of the window's ``phase name=residual`` records: the
dispatch of the per-tile residual program (its read-back is the
writer's).  In milliseconds."""

import statistics

NAME, UNIT = "residual_ms", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    vals = [r["dur_s"] for r in run.diag_records()
            if r.get("ev") == "phase" and r.get("name") == "residual"]
    return 1e3 * statistics.median(vals) if vals else None
