"""Mean ``refine_passes`` of the window's ``tile`` records: how many
times a tile's joint LBFGS refine went through the model of all
clusters (a cost pass, a gradient pass, a ``jvp``, a plain evaluation:
one each), as ``solvers/lbfgs.py``'s loop counts them.  About 31 where
the Fletcher search runs on the cost restricted to its line (``-l 10``:
a ``jvp``, a model evaluation and a gradient an iteration, and the first
gradient), five times that where every trial walks the model.  ``None``
on a program that has no such counter."""

import statistics

NAME, UNIT = "refine_passes", "count"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    vals = [r["refine_passes"] for r in run.diag_records()
            if r.get("ev") == "tile" and "refine_passes" in r]
    return statistics.mean(vals) if vals else None
