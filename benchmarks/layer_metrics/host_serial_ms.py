"""Host milliseconds a tile on the cycle's critical path during which
the device is not being waited for: per tile of the window, the loop
thread's seconds in its root spans ``io`` (the wait for the next tile)
and ``step`` less every ``wait`` (blocked on the device's execution)
under them; the mean over the window's tiles.  From the program's
``phase`` records alone (``id``, ``parent``, ``thread``): no profile is
needed.  What a host-side change is sized from: the device can get
faster and this stays.

The ``[host]`` table beside it: for each path of the loop's thread
(``step/solve/dispatch``) the median SELF milliseconds a tile (a span's
duration less its children's), so the rows add up to ``io`` + ``step``;
``unspanned``, the cycle from one ``tile`` record to the next less that
tile's ``io`` and ``step`` (what the loop's thread does under no span:
the caller's own work between two steps); the other threads' spans; and
the same mean over the tiles that ended before the profiler's start,
whose Python tracer slows the host's own code.
``None`` on a program whose records carry no ``id``."""

import hostspans

NAME, UNIT = "host_serial_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    recs = hostspans.phase_records(run)
    spans = hostspans.Spans(recs)
    w = run.window
    cycles = spans.cycles(w.t_open, w.t_drain) \
        if spans.ok and w.t_open is not None else []
    if not cycles:
        print("[host] no step span with an id in the window's records: "
              "nothing to read")
        return None
    value, rows = hostspans.host_table(
        spans, cycles, [r["tm"] for r in recs if r["ev"] == "tile"])
    for row in rows:
        print(row)
    print(f"[host] host_serial_ms {value:.4f}: io + step less every wait, "
          f"mean over {len(cycles)} tiles")
    # the profiler's Python tracer slows the host's own code: the tiles
    # that ended before it started are the nearer to an untraced run's
    t0 = getattr(run, "_prof_t0", None)
    before = [c for c in cycles
              if t0 is not None and hostspans.step_of(c)["tm"] <= t0]
    if before:
        quiet, _ = hostspans.host_table(spans, before, [])
        print(f"[host] of which the {len(before)} tiles that ended before "
              f"the profiler's start: {quiet:.4f}")
    return value
