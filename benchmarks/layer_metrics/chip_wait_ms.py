"""The device's idle milliseconds a tile: the traced slice's length less
the device's busy seconds (the union of its leaf operations), over the
tiles begun in the slice; the mean over the devices, as
``device_idle_pct`` takes it, of which this is the same seconds in the
unit a host-side change is judged by (a share doubles when the device
gets twice as fast; these milliseconds do not).  From the slice the run
already loaded (``scopes.load(run)``: ``spans``, ``merged``).

The ``[wait]`` table beside it charges every gap of 10 us and over to
the INNERMOST span of the loop's thread that overlaps it, by overlap (a
gap under two spans is split between them), as a path
(``step/solve/dispatch``): rows in falling order, then ``outside every
span`` and ``gaps under 10 us`` (the device's own, between operations of
one program).  The slice runs from the profiler's start to its stop
(``run._prof_t0``, ``run._prof_t1``), so the rows add up to the value.

The profile keeps a span's name, start and end; which thread a span ran
on and what holds it come from the ``phase`` records (``id``,
``parent``, ``thread``), which are on another clock:
``hostspans.clock_offset`` ties the two by the spans both hold.  ``None``
on a program whose records carry no ``id``, and where the two clocks
cannot be tied."""

import hostspans
import scopes

NAME, UNIT = "chip_wait_ms", "ms"
LAYER, MOVES = "tile loop and overlap", "vis_per_s"

NS = hostspans.NS


def read(run):
    sl = scopes.load(run)
    if sl is None or run.profile is None or not run.slice_tiles:
        return None
    recs = hostspans.phase_records(run)
    spans = hostspans.Spans(recs)
    if not spans.ok:
        print("[wait] no step span with an id in the records: nothing to "
              "charge the gaps to")
        return None
    tied = hostspans.clock_offset(sl.spans, recs)
    if tied is None or 2 * tied[1] < tied[2]:
        print(f"[wait] the profile's clock could not be tied to the "
              f"records': {tied}")
        return None
    offset, votes, n_spans = tied
    pieces = hostspans.innermost(
        [(a + offset, b + offset, spans.path(r))
         for r in spans.on_loop() for a, b in [spans.interval(r)]])
    window_s, tiles, n_dev = (run.profile["window_s"], run.slice_tiles,
                              len(sl.merged))
    # the slice on the profile's clock: from the profiler's start to its
    # stop, as run.py took them on the records' clock
    t0, t1 = run._prof_t0 + offset, run._prof_t1 + offset
    rows, short = {}, 0.0
    for merged in sl.merged:
        busy = [(a * NS, b * NS) for a, b in merged]
        gaps = [(a, b) for a, b in zip(
            [t0] + [b for _, b in busy], [a for a, _ in busy] + [t1])
            if b > a]
        long = [g for g in gaps if g[1] - g[0] >= hostspans.SHORT_GAP_S]
        short += sum(b - a for a, b in gaps) - sum(b - a for a, b in long)
        for label, sec in hostspans.charge(long, pieces).items():
            rows[label] = rows.get(label, 0.0) + sec
    per_tile = 1e3 / n_dev / tiles
    value = 1e3 * (window_s - sl.busy_s) / tiles
    outside = rows.pop(None, 0.0)
    listed = sorted(rows.items(), key=lambda kv: -kv[1]) + [
        ("outside every span", outside), ("gaps under 10 us", short)]
    for label, sec in listed:
        print(f"[wait] {label:<28} {per_tile * sec:10.4f} ms a tile")
    total = per_tile * sum(sec for _, sec in listed)
    print(f"[wait] rows add up to {total:.4f} ms; chip_wait_ms "
          f"{value:.4f} = {100 * value * tiles / (1e3 * window_s):.2f} % "
          f"of the slice's {1e3 * window_s / tiles:.4f} ms a tile over "
          f"{tiles} tile(s) and {n_dev} device(s); clocks tied by "
          f"{votes} votes of the profile's {n_spans} spans")
    return value
