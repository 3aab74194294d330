"""Device milliseconds a tile spends making the array beam's tables: self
time of the LEAF operations under the scope ``rime/beam``
(``rime/predict.coherencies`` with a beam: per cluster the azimuth and
elevation of every source at every timeslot and station, the phases over
the station's elements, their cosines and sines and ``|mean|``:
``rime/beam.cluster_beam``) in the traced slice, over the tiles begun in
it.  Every program of a tile that forms coherencies makes the tables
(``beam_tables_per_tile.beam`` counts them in the trace).  The line
beside it gives the nanoseconds a cosine-sine pair: this over
``beam_sources x tilesz x stations x beam_elements`` of the window's
``tile`` records, times those programs.

``None`` where no operation of the trace is under ``rime/beam``: a
program that makes its tables inside the map over clusters books them
under ``rime/phasor`` (``scopes.scope_path`` takes the FIRST root)."""

import harness
import scopes

NAME, UNIT = "beam_dev_ms.beam", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"
SCOPE = "rime/beam"


def read(run):
    sl = scopes.load(run)
    if sl is None or not run.slice_tiles or not sl.scoped():
        return None
    sec, n = sl.first_level(SCOPE)
    if not n:
        print(f"[scope] no operation under {SCOPE} in the trace: nothing "
              f"to read for {NAME}")
        return None
    ms = 1e3 * sec / sl.n_devices / run.slice_tiles
    print(f"[scope] {SCOPE}: {sec:.6g} s in {n} leaf operations over "
          f"{run.slice_tiles} tile(s) of the slice")
    rec = next((r for r in scopes.window_records(run)
                if r.get("ev") == "tile" and r.get("beam_sources")), None)
    programs = harness.load_module(
        "layer_metrics", "beam_tables_per_tile.beam").count(run)
    if rec and programs:
        n_prog = len(programs[0])
        pairs = (rec["beam_sources"] * int(run.config["tilesz"])
                 * int(run.config["n_stations"]) * rec["beam_elements"]
                 * n_prog)
        print(f"[beam] {pairs} cosine-sine pairs a tile "
              f"({rec['beam_sources']} sources x {run.config['tilesz']} "
              f"timeslots x {run.config['n_stations']} stations x "
              f"{rec['beam_elements']} elements x {n_prog} programs): "
              f"{1e6 * ms / pairs:.4g} ns a pair")
    return ms
