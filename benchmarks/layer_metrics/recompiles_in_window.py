"""Backend compiles (cache reads included) that ``diag.guard``'s compile
log holds with ``tm`` inside the window, their functions printed.  The
log is of JAX's duration events, which fire with or without the
persistent cache; ``compiles_in_window`` counts cache requests.
Expected 0."""

import scopes

NAME, UNIT = "recompiles_in_window", "count"
LAYER, MOVES = "entry points", "vis_per_s"


def read(run):
    split = scopes.compile_log(run)
    if split is None:
        return None
    inside = [r for r in split[1] if r[1] == "backend_compile"]
    for tm, _stage, fun, dur in inside:
        print(f"[compile] in the window, {tm - run.window.t_open:.3f} s "
              f"after its opening: {fun} ({dur:.3f} s)")
    return len(inside)
