"""Milliseconds an interval's collectives occupy the chip that spends
most in them: ``run.profile["per_device"][*]["collective_s"]`` (self
seconds of the ``all-reduce``, ``all-gather``, ... operations of a
plane's ``XLA Ops`` line) over the intervals begun in the traced slice,
the LARGEST chip's; every chip's is printed beside it.  The transfer is
32 kB: what is measured is the wait for the slowest chip.

The walk reads ``XLA Ops`` alone.  Where a plane's collectives are only
the ``-start``/``-done`` halves of asynchronous ones, their duration
lives on the line ``Async XLA Ops``: this file then reads that line from
``run.trace_path`` itself, says so, and reports the larger of the two
readings per chip."""

import xplane

NAME, UNIT = "collective_ms.admm", "ms"
LAYER, MOVES = "consensus collective", "tile_s.p50"

HALVES = ("-start", "-done")


def only_halves(dev) -> bool:
    """Whether every collective of a walked device is a half."""
    names = [n for n in dev.self_s
             if xplane.op_class(n) in xplane.COLLECTIVES]
    return bool(names) and all(
        n.split(".", 1)[0].endswith(HALVES) for n in names)


def async_seconds(trace_path: str) -> dict:
    """{plane: seconds of the collectives on its ``Async XLA Ops``}."""
    out = {}
    for pl in xplane.load(trace_path).planes:
        if not pl.name.startswith("/device:TPU:"):
            continue
        for ln in pl.lines:
            if ln.name == "Async XLA Ops":
                out[pl.name] = xplane.NS * sum(
                    e.duration_ns for e in ln.events
                    if xplane.op_class(xplane.op_name(e.name))
                    in xplane.COLLECTIVES)
    return out


def read(run):
    if run.profile is None or not run.slice_tiles:
        return None
    per = run.profile["per_device"]
    sec = {name: d["collective_s"] for name, d in per.items()}
    kinds = sorted({k for d in per.values() for k in d["collectives"]})
    print("[chips] collective operations on XLA Ops: "
          + (", ".join(kinds) or "none"))
    devices = run.slice.profile.devices if run.slice is not None else {}
    if any(only_halves(d) for d in devices.values()):
        more = async_seconds(run.trace_path)
        print("[chips] XLA Ops holds only -start/-done halves: read "
              "Async XLA Ops from the trace file too: " + ", ".join(
                  f"{k} {v:.6f} s" for k, v in sorted(more.items())))
        sec = {k: max(v, more.get(k, 0.0)) for k, v in sec.items()}
    for name in sorted(per):
        print(f"[chips] {name}: busy {per[name]['busy_s']:.4f} s, of "
              f"which collectives {sec[name]:.4f} s")
    ms = {k: 1e3 * v / run.slice_tiles for k, v in sec.items()}
    print("[chips] collective ms per interval: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(ms.items()))
        + f"; largest {max(ms.values()):.4f}")
    return max(ms.values())
