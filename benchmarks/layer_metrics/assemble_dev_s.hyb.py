"""Device seconds a tile spends assembling the per-cluster solves'
Gauss-Newton matrix: self time of the LEAF operations whose second-level
scope is ``assemble`` (``solvers/normal_eq.py``: under a hybrid cluster
file ``normal_equations``' generic branch, ``[B, 2, 2]`` complex
products, ``[B, 2, 2, 4, 4]`` Gram blocks and the scatter
``.at[chunk_id, sta1, sta2].add`` into ``[kmax, N, N, 2, 2, 4, 4]``;
on periodic rows ``plane_equations``) under any first level, in the
traced slice, over the tiles begun in it.  The ``[scope]`` table prints
the same seconds as ``sage/sweep/assemble``.  ``None`` where the trace
has no scoped event or none under ``assemble``."""

import scopes

NAME, UNIT = "assemble_dev_s.hyb", "s"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"

SECOND = "assemble"


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
    return sec / sl.n_devices / run.slice_tiles
