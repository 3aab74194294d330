"""Device seconds a tile spends in the EM sweeps: self time of the LEAF
operations under the scope ``sage/sweep`` (``solvers/sage.py``: the body
of ``em_iter_width`` in the promoted ``_jit_sagefit``, and
``_jit_em_sweep`` / ``_jit_cluster_update`` / ``_jit_group_update`` on
the host-driven path) in the traced slice, over the tiles begun in it.
The ``[scope]`` table printed beside it holds the second level:
``sage/sweep/assemble``, ``/inner``, ``/update``."""

import scopes

NAME, UNIT = "sweep_dev_s", "s"
LAYER, MOVES = "per-cluster solvers", "tile_s.p50"


def read(run):
    return scopes.per_tile(run, "sage/sweep")
