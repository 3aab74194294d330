"""Mean ``jupdate_trips`` of the window's ``tile`` records of the
consensus interval loop: the USEFUL loop bodies of an interval's J
updates, trust-region iterations plus truncated-CG iterations, summed
over its subbands and ADMM iterations, as each subband's own
``sagefit`` counted them (``solver_iters`` and ``cg_iters`` of its
info, returned by the mesh program in the fetch it already makes).
What the device executed under a fold is this over
``1 - lockstep_pct / 100``.  ``None`` on a program whose interval
records carry no ``jupdate_trips``."""

import harness

NAME, UNIT = "jupdate_trips.fold", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    return harness.load_module(
        "layer_metrics", "lockstep_pct.fold").read(run, "jupdate_trips")
