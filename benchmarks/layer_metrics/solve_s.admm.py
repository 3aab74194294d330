"""Median duration of the window's ``phase name=solve`` records of the
consensus interval loop (``cli_mpi.ConsensusStepper.step``): ONE mesh
execution of all ADMM iterations of an interval (the J updates of every
subband, the manifold average, the consensus rounds), timed by the
program to the execution's end."""

import statistics

NAME, UNIT = "solve_s.admm", "s"
LAYER = "consensus driver (cli_mpi.py, consensus/admm.py)"
MOVES = "tile_s.p50"


def read(run):
    vals = [r["dur_s"] for r in run.diag_records()
            if r.get("ev") == "phase" and r.get("name") == "solve"]
    return statistics.median(vals) if vals else None
