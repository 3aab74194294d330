"""How unevenly the chips of the mesh are loaded: (max - min) / mean of
the chips' busy seconds in the traced slice
(``run.profile["per_device"][*]["busy_s"]``), in percent.  Every subband
solves the same sizes, so what is left is the data's own (line searches,
inner iterations) and the chip that waits in a collective counts as
busy there.  ``None`` with fewer than two device planes."""

NAME, UNIT = "chip_skew_pct", "%"
LAYER, MOVES = "device", "vis_per_s"


def read(run):
    if run.profile is None:
        return None
    busy = [d["busy_s"] for d in run.profile["per_device"].values()]
    if len(busy) < 2:
        return None
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean else None
