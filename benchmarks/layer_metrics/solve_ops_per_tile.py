"""Leaf device operations under ``sage/`` (prelude, sweep, refine,
final) per tile begun in the traced slice: the count that PERF.md
section 5 names as ``cal-m8x3``'s bottleneck.  The sweep's and the
refine's counts are printed beside it."""

import scopes

NAME, UNIT = "solve_ops_per_tile", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    sl = scopes.load(run)
    if sl is None or not run.slice_tiles:
        return None
    counts = {f: sl.first_level(f)[1]
              for f in ("sage/prelude", "sage/sweep", "sage/refine",
                        "sage/final")}
    total = sum(counts.values())
    if not total:
        print("[scope] no scoped event in the trace: no operation under "
              "sage/ to count")
        return None
    per = sl.n_devices * run.slice_tiles
    print("[scope] leaf operations per tile: " + ", ".join(
        f"{f} {n / per:.6g}" for f, n in counts.items()))
    return total / per
