"""Mean ``row_passes`` of the window's ``tile`` records: evaluations of
the row model ``V = J_p C J_q^H`` with its Wirtinger factors
(``normal_eq.row_model``, through ``rtr.make_row_pass`` the cost and the
gradient of the same pass) that a tile's RTR solves executed: one at each
solve's start and one per trial point, summed over the IRLS rounds of
``rtr_solve_robust``.  A ``cal-m8x3`` tile runs 64 rounds (4 EM sweeps x
8 clusters x 2) and ``solver_trips`` = 128 iterations: 192.  ``None`` on
a program that has no such count: one whose ``tile`` record lacks the
key, or holds 0 there (LM and NSD solves run no row pass; the tiny
rehearsal cell's eight stations are under the program's ``LMCUT``, which
turns ``-j 5`` into robust LM, so its line has no ``row_passes``)."""

import statistics

NAME, UNIT = "row_passes", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    vals = [r["row_passes"] for r in run.diag_records()
            if r.get("ev") == "tile" and r.get("row_passes")]
    return statistics.mean(vals) if vals else None
