"""Device milliseconds a tile spends in the source sum: self time of the
LEAF operations under the scope ``rime/phasor``
(``rime/predict.py:_cluster_coherency`` and the map over clusters:
fringe phase, cos/sin, smearing, envelopes, flux, the sum over sources)
in the traced slice, over the tiles begun in it."""

import scopes

NAME, UNIT = "phasor_dev_ms", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    return scopes.per_tile(run, "rime/phasor", 1e3)
