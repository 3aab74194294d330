"""Mean over the window's ``tile`` records of the passes over the rows
that ran on a layout other than ``"periodic"``: the RTR solves'
evaluations of the row model (``row_passes``) where ``sweep_rows`` is
not ``"periodic"``, plus the joint refine's passes through the model
(``refine_passes``) where ``refine_rows`` is not.  On the periodic
layout (one chunk a cluster: every other cell) the Jones are gathered
for ``nbase`` rows and the planes are ``[8, tilesz, nbase]``; with
``kmax > 1`` ``rime/planes.periodic_rows`` says no and every pass
gathers a Jones a row.  A chunk is a run of whole timeslots, so a
program that keeps the planes inside a chunk brings this to 0.  The
layouts the records name are printed beside it.  ``None`` on a program
whose ``tile`` record names no layout."""

import statistics

import scopes

NAME, UNIT = "flat_row_passes.hyb", "count"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"

#: (the count, the layout it ran on)
PASSES = (("row_passes", "sweep_rows"), ("refine_passes", "refine_rows"))


def read(run):
    recs = [r for r in scopes.window_records(run)
            if r.get("ev") == "tile"
            and any(rows in r for _, rows in PASSES)]
    if not recs:
        print("[hybrid] no tile record names a row layout in the window")
        return None
    said = sorted({tuple(r.get(k) for k in ("sweep_rows", "assemble_rows",
                                            "refine_rows")) for r in recs},
                  key=str)
    print(f"[hybrid] layouts over {len(recs)} tile(s): " + "; ".join(
        f"sweep_rows {s}, assemble_rows {a}, refine_rows {f}"
        for s, a, f in said))
    return statistics.mean(
        sum(r.get(count, 0) for count, rows in PASSES
            if rows in r and r[rows] != "periodic") for r in recs)
