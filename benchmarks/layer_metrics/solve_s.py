"""Median duration of the window's ``phase name=solve`` records: the
whole SAGE solve of a tile (EM sweeps and the joint refine), timed by
the program around work that ends in a read-back."""

import statistics

NAME, UNIT = "solve_s", "s"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    vals = [r["dur_s"] for r in run.diag_records()
            if r.get("ev") == "phase" and r.get("name") == "solve"]
    return statistics.median(vals) if vals else None
