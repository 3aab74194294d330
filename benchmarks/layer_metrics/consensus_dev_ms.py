"""Device milliseconds an interval spends in what consensus adds to its J
updates, a chip: self time of the LEAF operations under ``sage/consensus``
(the z-sum ``psum``, the ``Bii`` solve, the dual and rho updates:
``consensus/admm.py``) and ``sage/manifold`` (``manifold_average_mesh``:
its ``psum``s and Procrustes rotations) in the traced slice, mean over
the chips, over the intervals begun in it.  A collective's leaf time
holds the wait for the slowest chip.  ``None`` on a program without the
two scopes."""

import harness

NAME, UNIT = "consensus_dev_ms", "ms"
LAYER, MOVES = "consensus collective", "tile_s.p50"


def read(run):
    val = harness.load_module("layer_metrics", "jupdate_dev_s").read(
        run, ("sage/consensus", "sage/manifold"), "consensus")
    return None if val is None else 1e3 * val
