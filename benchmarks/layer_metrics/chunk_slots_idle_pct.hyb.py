"""``100 * (1 - chunk_slots_live / chunk_slots)`` of the window's
``tile`` records: the share of the Jones' chunk slots that hold no
solution.  Every cluster's Jones is ``[kmax, N, 2, 2]`` with ``kmax``
the most chunks ANY cluster has (``pipeline.FullBatchPipeline.cmask``),
so a cluster of one chunk beside one of five assembles and factorises
five ``[8N, 8N]`` systems, four of them masked.  ``chunk_slots`` is
``M * kmax``, ``chunk_slots_live`` is ``sum(nchunk)`` (80 and 26 in
``cal-m16x3-hybrid``: 67.5); what a program that stops padding brings
to 0.  ``kmax`` and the two counts are printed beside it.  ``None`` on a
program whose ``tile`` record has no such keys."""

import scopes

NAME, UNIT = "chunk_slots_idle_pct.hyb", "%"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    recs = [r for r in scopes.window_records(run)
            if r.get("ev") == "tile" and r.get("chunk_slots")]
    if not recs:
        print("[hybrid] no tile record with chunk_slots in the window")
        return None
    said = sorted({(r.get("kmax"), r["chunk_slots"], r["chunk_slots_live"])
                   for r in recs})
    print(f"[hybrid] over {len(recs)} tile(s): " + "; ".join(
        f"kmax {k}, {live} of {slots} chunk slots live"
        for k, slots, live in said))
    return 100.0 * (1.0 - sum(r["chunk_slots_live"] for r in recs)
                    / sum(r["chunk_slots"] for r in recs))
