"""Share of the traced slice in which no device operation ran:
100 * (1 - busy / slice), busy from ``xplane.reduce``."""

NAME, UNIT = "device_idle_pct", "%"
LAYER, MOVES = "device", "vis_per_s"


def read(run):
    if run.profile is None:
        return None
    return 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])
