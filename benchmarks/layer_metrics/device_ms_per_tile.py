"""Device busy time in the traced slice over the tiles whose cycle began
in it (``xplane.reduce``: union of leaf device operations).  In
milliseconds."""

NAME, UNIT = "device_ms_per_tile", "ms"
LAYER, MOVES = "predict and residual", "tile_s.p50"


def read(run):
    if run.profile is None or not run.slice_tiles:
        return None
    return 1e3 * run.profile["busy_s"] / run.slice_tiles
