"""``admm_iter`` records an interval, mean over the window's intervals:
the ADMM iterations the program says it ran (one record each, iteration
0, the plain solve, included).  Expected: the configuration's ``-A``."""

import statistics

import scopes

NAME, UNIT = "admm_iters", "count"
LAYER = "consensus driver (cli_mpi.py, consensus/admm.py)"
MOVES = "tile_s.p50"


def read(run):
    per = {}
    for r in scopes.window_records(run):
        if r.get("ev") == "admm_iter":
            per[r.get("interval")] = per.get(r.get("interval"), 0) + 1
    return statistics.mean(per.values()) if per else None
