"""Compile requests between the window's opening and its drain, as
``sagecal_tpu.diag.guard`` counts them (requests that go through the
persistent compile cache, which ``setup_backend`` turns on).  Expected 0:
everything the window runs was compiled or read from the cache in set-up."""

NAME, UNIT = "compiles_in_window", "count"
LAYER, MOVES = "entry points", "vis_per_s"


def read(run):
    before, after = run.compiles
    if before is None or after is None:
        return None
    return after - before
