"""Mean ``solve_dispatches`` of the window's ``tile`` records: device
executions a tile's solve issued through ``solvers/sage.py:_call``, as
``sagefit_host`` counts them: 1 where the promoted ``_jit_sagefit`` ran,
prelude + sweeps + refine + final where the sweeps are fused, a dispatch
a cluster and sweep more where they are not.  Which of the three a warm
tile runs is the plan learner's verdict (``sage._FUSION_CACHE``,
``_PROMOTE_CACHE``, thresholds of 25 s and 35 s of host seconds), foregone
at 18 910 rows and not at 226 920: the ``plan`` of each record is printed
beside the value.  ``None`` on a program whose ``tile`` record has no
such key."""

import collections
import statistics

NAME, UNIT = "solve_dispatches.t120", "count"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    tiles = [r for r in run.diag_records()
             if r.get("ev") == "tile" and "solve_dispatches" in r]
    if not tiles:
        return None
    plans = collections.Counter(r.get("plan", "?") for r in tiles)
    print("[layer] plan " + ", ".join(
        f"{p} x {n}" for p, n in sorted(plans.items()))
        + f" over {len(tiles)} tiles")
    return statistics.mean(r["solve_dispatches"] for r in tiles)
