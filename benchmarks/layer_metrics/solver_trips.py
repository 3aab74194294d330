"""Mean ``solver_iters`` of the window's ``tile`` records: inner solver
trips (RTR/LM iterations over all clusters and sweeps) a tile executed.
The joint refine's ``lbfgs_iters`` is printed beside it."""

import statistics

NAME, UNIT = "solver_trips", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    tiles = [r for r in run.diag_records() if r.get("ev") == "tile"]
    trips = [r["solver_iters"] for r in tiles if "solver_iters" in r]
    lbfgs = [r["lbfgs_iters"] for r in tiles if "lbfgs_iters" in r]
    if lbfgs:
        print(f"[layer] lbfgs_iters mean {statistics.mean(lbfgs):.6g} "
              f"over {len(lbfgs)} tiles")
    return statistics.mean(trips) if trips else None
