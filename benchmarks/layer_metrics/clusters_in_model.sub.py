"""Mean ``clusters_in_model`` of the window's ``tile`` records of the
simulation loop (``pipeline.FullBatchPipeline.run_simulation``): how many
clusters the ignore list (``-z``) left in the model that was added,
subtracted or written.  ``lofar62-sub-m8x128`` leaves seven of eight; a
program that does not honour the list says eight.  The records' ``mode``
(``-a``) is printed beside it.  Nothing where the records have no such
key (a tree before PR 37)."""

import collections
import statistics

import scopes

NAME, UNIT = "clusters_in_model.sub", "count"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    tiles = [r for r in scopes.window_records(run)
             if r.get("ev") == "tile" and "clusters_in_model" in r]
    if not tiles:
        print("[span] no tile record with clusters_in_model in the window")
        return None
    modes = collections.Counter(r.get("mode") for r in tiles)
    print("[span] simulation mode: " + ", ".join(
        f"-a {m} x {n}" for m, n in sorted(modes.items(), key=str)))
    return statistics.mean(r["clusters_in_model"] for r in tiles)
