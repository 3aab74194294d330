"""Mean seconds per tile inside the dataset's ``read_tile`` and
``write_tile`` during the window, on the harness's clock inside its
dataset wrapper (``drivers/predict.py``).  In milliseconds."""

NAME, UNIT = "io_ms.predict", "ms"
LAYER, MOVES = "tile loop and overlap", "tile_s.p50"


def read(run):
    io_s = run.counters.get("io_s")
    n = len(run.window.entries)
    return 1e3 * io_s / n if io_s is not None and n else None
