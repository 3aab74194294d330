"""Device seconds a tile spends in the joint LBFGS refine: self time of
the LEAF operations under the scope ``sage/refine`` (``solvers/sage.py``:
the refine block of ``sagefit``, and ``_jit_refine``) in the traced
slice, over the tiles begun in it.  Second level in the ``[scope]``
table: ``sage/refine/linesearch`` and ``/direction``
(``solvers/lbfgs.py``)."""

import scopes

NAME, UNIT = "refine_dev_s", "s"
LAYER, MOVES = "SAGE-EM driver and refine", "tile_s.p50"


def read(run):
    return scopes.per_tile(run, "sage/refine")
