"""Device milliseconds a tile spends in the add or subtract of the
simulation modes: self time of the LEAF operations under the scope
``rime/residual`` (``rime/residual.py``: ``simulate_pairs`` stacks the
model's two parts and adds them to, or takes them from, the input's real
pairs there; in ``-a 1`` only the stacking is left) in the traced slice,
over the tiles begun in it.  Nothing where the program has no such scope
in its simulate program."""

import scopes

NAME, UNIT = "subtract_dev_ms", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    return scopes.per_tile(run, "rime/residual", 1e3)
