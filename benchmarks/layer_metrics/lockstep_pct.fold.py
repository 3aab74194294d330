"""Mean ``lockstep_pct`` of the window's ``tile`` records of the
consensus interval loop: the share of the J updates' executed loop
bodies spent on a subband that had already ended.  Subbands folded on
one device run their J update under ``jax.vmap``, where every
``lax.while_loop`` of the solvers (the trust region, truncated CG) runs
the trips of the SLOWEST subband for all, the finished ones frozen by
masks.  The program counts, per ADMM iteration and device,
``100 x (1 - sum_f trips_f / (Fl x max_f trips_f))`` over the ``Fl``
subbands of the device (trips: trust-region plus tCG bodies a subband
needed) and records the mean over iterations and devices
(``cli_mpi.ConsensusStepper``).  Zero at one subband a device.
``fold`` (``Fl``), ``ndev`` and ``plan`` of the same records are printed
beside it.  ``None`` on a program whose interval records carry no
``lockstep_pct``."""

import statistics

import scopes

NAME, UNIT = "lockstep_pct.fold", "%"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"

KEY = "lockstep_pct"


def read(run, key=KEY):
    recs = [r for r in scopes.window_records(run)
            if r.get("ev") == "tile" and key in r]
    if not recs:
        print(f"[fold] no tile record with {key} in the window")
        return None
    said = sorted({(r.get("fold"), r.get("ndev"), r.get("plan"))
                   for r in recs}, key=str)
    print(f"[fold] {key} over {len(recs)} interval(s): " + "; ".join(
        f"fold {f}, ndev {n}, plan {p}" for f, n, p in said))
    return statistics.mean(r[key] for r in recs)
