"""Mean ``shapelet_slots`` of the window's ``tile`` records: the source
slots for which the compiled source sum evaluates the shapelet basis
(``pipeline.source_kinds``).  ``rime/predict.coherencies`` compiles the
basis in or out for the whole model, so ``lofar62-m8x128-ext``'s four
shapelet sources make it 8 x 128 = 1024; a program that evaluates it
where there is a shapelet says 4.  The records' counts of sources by
kind and ``shapelet_n0max`` are printed beside it.  Nothing where the
records have no such key (a tree before PR 51)."""

import statistics

import scopes

NAME, UNIT = "shapelet_slots.ext", "count"
LAYER, MOVES = "predict and residual", "tile_s.p50"

KINDS = ("point", "gaussian", "disk", "ring", "shapelet")


def read(run):
    tiles = [r for r in scopes.window_records(run)
             if r.get("ev") == "tile" and "shapelet_slots" in r]
    if not tiles:
        print("[span] no tile record with shapelet_slots in the window")
        return None
    said = sorted({tuple(r.get(f"sources_{k}") for k in KINDS)
                   + (r.get("shapelet_n0max"),) for r in tiles}, key=str)
    for *counts, n0max in said:
        print("[span] the window's tile records: sources " + ", ".join(
            f"{k} {n}" for k, n in zip(KINDS, counts))
            + f"; shapelet_n0max {n0max}")
    return statistics.mean(r["shapelet_slots"] for r in tiles)
