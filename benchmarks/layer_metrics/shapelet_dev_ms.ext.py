"""Device milliseconds a tile spends in the shapelet basis: self time of
the LEAF operations whose second-level scope is ``shapelet``
(``rime/envelopes.shapelet``: the projection, two Hermite recursions of
``n0max`` terms, the ``n0max^2`` products with the modes and their two
sums, for EVERY source slot of the model where it holds one shapelet
source) under any first level, in the traced slice, over the tiles begun
in it.  The ``[scope]`` table prints the same seconds as
``rime/phasor/shapelet``.  ``None`` where the trace has no scoped event or
none under ``shapelet``: a model without a shapelet, or a tree whose
``envelopes.shapelet`` has no scope of its own (before PR 51), whose
basis is booked under ``rime/phasor`` with the rest of the source sum."""

import scopes

NAME, UNIT = "shapelet_dev_ms.ext", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"

SECOND = "shapelet"


def read(run):
    sl = scopes.load(run)
    if sl is None or not run.slice_tiles or not sl.scoped():
        return None
    sel = [v for (_, second), v in sl.leaf.items() if second == SECOND]
    if not sel:
        print(f"[scope] no leaf operation under {SECOND}")
        return None
    sec, n = sum(v[0] for v in sel), sum(v[1] for v in sel)
    print(f"[scope] */{SECOND}: {sec:.6g} s in {n} leaf operations over "
          f"{run.slice_tiles} tile(s) of the slice")
    return 1e3 * sec / sl.n_devices / run.slice_tiles
