"""Median duration of the window's ``phase name=beam`` records, in
milliseconds: what staging a tile's beam leaves costs the thread that
stages the tile (``pipeline._tile_beam``, path ``stage/beam`` in
``hostspans.Spans``, ``sagecal/beam`` in the profile; the reader's thread
under ``--prefetch 1``).  Since PR 48 that is the tile's ``gmst`` track
alone: sidereal angles of ten time stamps on the host and one
host-to-device copy; the static leaves were staged at construction.
``None`` on a program without the span."""

import statistics

NAME, UNIT = "beam_stage_ms.beam", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    vals = [r["dur_s"] for r in run.diag_records()
            if r.get("ev") == "phase" and r.get("name") == "beam"]
    if not vals:
        print("[beam] no phase record named beam in the window")
        return None
    print(f"[beam] stage/beam: median {1e3 * statistics.median(vals):.4f} "
          f"ms, max {1e3 * max(vals):.4f} ms over {len(vals)} tile(s)")
    return 1e3 * statistics.median(vals)
