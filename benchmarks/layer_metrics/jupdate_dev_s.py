"""Device seconds an interval spends in its J updates, a chip: self time
of the LEAF operations under the solver's own scopes ``sage/prelude``,
``sage/sweep``, ``sage/refine`` and ``sage/final`` (``solvers/sage.py``,
inside every ADMM iteration of the mesh program) in the traced slice,
mean over the chips, over the intervals begun in it.  The ``[scope]``
table holds each and the second level."""

import scopes

NAME, UNIT = "jupdate_dev_s", "s"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"

FIRSTS = ("sage/prelude", "sage/sweep", "sage/refine", "sage/final")


def read(run, firsts=FIRSTS, label="J updates"):
    sl = scopes.load(run)
    if sl is None or not run.slice_tiles:
        return None
    if not sl.scoped():
        print(f"[scope] no scoped event in the trace: nothing to read "
              f"for the {label}")
        return None
    found = {f: sl.first_level(f) for f in firsts}
    print(f"[scope] {label}, summed over {sl.n_devices} chip(s) and "
          f"{run.slice_tiles} interval(s): " + ", ".join(
              f"{f} {sec:.6g} s in {n} leaf operations"
              for f, (sec, n) in found.items()))
    if not any(n for _, n in found.values()):
        return None         # a program without these scopes
    return sum(sec for sec, _ in found.values()) \
        / sl.n_devices / run.slice_tiles
