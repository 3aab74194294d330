"""Mean ``cg_iters`` of the window's ``tile`` records: bodies of the
truncated-CG loop (``solvers/rtr.py:_tcg``) a tile executed, summed over
its ``solver_trips`` outer trust-region iterations.  The loop ends when
every chunk has stopped (boundary, negative curvature, residual target)
and ``RTRConfig.tcg_iters`` = 30 is its cap: 128 outer iterations a
``cal-m8x3`` tile would be 3840 at the cap.  ``None`` on a program that
has no such count: one whose ``tile`` record lacks the key, or holds 0
there (a tree that runs the cap on every trip and says nothing)."""

import statistics

NAME, UNIT = "tcg_trips", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    vals = [r["cg_iters"] for r in run.diag_records()
            if r.get("ev") == "tile" and r.get("cg_iters")]
    return statistics.mean(vals) if vals else None
