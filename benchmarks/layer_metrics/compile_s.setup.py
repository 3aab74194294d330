"""Seconds of set-up spent tracing, lowering and compiling (or reading
the compile cache: that is what a warm start pays): the union of the
intervals that ``diag.guard``'s compile log holds before the window's
opening.  The largest backend compiles are printed."""

import scopes

NAME, UNIT = "compile_s.setup", "s"
LAYER, MOVES = "entry points", "setup_s"


def read(run):
    split = scopes.compile_log(run)
    if split is None:
        return None
    before = split[0]
    from sagecal_tpu.diag import guard
    print(f"[compile] requests through the persistent cache since the "
          f"start: {guard.compile_count()} (0 with the cache off; the log "
          f"below does not depend on it)")
    by_stage = {}
    for _tm, stage, _fun, dur in before:
        by_stage[stage] = by_stage.get(stage, 0.0) + dur
    print("[compile] set-up, summed per stage: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in sorted(by_stage.items())))
    top = sorted((r for r in before if r[1] == "backend_compile"),
                 key=lambda r: -r[3])[:5]
    for _tm, _stage, fun, dur in top:
        print(f"[compile]   {fun}: {dur:.3f} s")
    return scopes.union_seconds(before)
