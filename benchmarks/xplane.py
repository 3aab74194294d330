"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, the operations that took most of it, and the
longest idle gaps with the harness span they fall in.

Read with ``jax.profiler.ProfileData`` alone.  What counts as a device
operation:

- on a TPU, the events of the line ``XLA Ops`` of each ``/device:TPU:<n>``
  plane;
- in a CPU rehearsal (no such plane), the host plane's events that carry
  an ``hlo_op`` stat, all lines taken as one device.

Operations nest (a ``while`` spans its body's operations).  Busy time is
the union of the LEAF operations' intervals: the time in which some
operation that holds no other was running.  An operation's time in the
table is its self time, its duration less its children's.  Busy time is
averaged over the devices that ran anything.
"""

from __future__ import annotations

import glob
import os

#: the harness's own spans (run.py's ``annotate``), outermost first
ANNOTATIONS = ("tile_cycle", "step", "read_stage", "drain", "read_tile",
               "write_tile")

NS = 1e-9


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def newest_trace(profile_dir: str) -> str:
    found = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(found, key=os.path.getmtime)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the TPU
    profile prints an operation as its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def device_events(pd) -> dict:
    """{device name: [(name, start_ns, end_ns), ...]}."""
    planes = list(pd.planes)
    out = {}
    for pl in planes:
        if pl.name.startswith("/device:TPU:"):
            ev = [(op_name(e.name), e.start_ns,
                   e.start_ns + e.duration_ns)
                  for ln in pl.lines if ln.name == "XLA Ops"
                  for e in ln.events]
            if ev:
                out[pl.name] = ev
    if out:
        return out
    ev = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.duration_ns > 0 and any(k == "hlo_op"
                                             for k, _ in e.stats):
                    ev.append((e.name, e.start_ns,
                               e.start_ns + e.duration_ns))
    return {"/host:CPU": ev} if ev else {}


def host_spans(pd) -> list:
    """[(name, start_ns, end_ns)] of the harness's spans, any thread."""
    out = []
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in ln.events if e.name in ANNOTATIONS]
    return out


def self_times(events):
    """(leaf intervals [(start, end)], {name: self seconds}) of one
    device's nested events."""
    order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    stack, leaves, total = [], [], {}

    def close(item):
        name, start, end, child_ns, has_child = item
        total[name] = total.get(name, 0.0) + max(
            0.0, (end - start) - child_ns) * NS
        if not has_child:
            leaves.append((start, end))

    for name, start, end in order:
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][2]) - start
            stack[-1][4] = True
        stack.append([name, start, end, 0.0, False])
    while stack:
        close(stack.pop())
    return leaves, total


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def span_at(spans, t) -> str:
    """The harness spans that hold instant ``t``, outermost first."""
    inside = sorted((s for s in spans if s[1] <= t < s[2]),
                    key=lambda s: s[1])
    names = []
    for s in inside:
        if s[0] not in names:
            names.append(s[0])
    return "/".join(names) if names else "outside_any_span"


def reduce(pd) -> dict:
    devices = device_events(pd)
    if not devices:
        raise ValueError("the trace holds no device operation")
    spans = host_spans(pd)
    busy, ops, gaps = [], {}, []
    for ev in devices.values():
        leaves, total = self_times(ev)
        merged = union(leaves)
        busy.append(sum(b - a for a, b in merged) * NS)
        for name, sec in total.items():
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
        gaps += [(b[0] - a[1], a[1]) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    idle = [[span_at(spans, start + length / 2), length * NS]
            for length, start in gaps[:5]]
    for label, lo, hi in (("all gaps under 10 us", 0, 1e4),
                          ("all gaps 10 us to 1 ms", 1e4, 1e6),
                          ("all gaps of 1 ms and over", 1e6, float("inf"))):
        sel = [g for g, _ in gaps if lo <= g < hi]
        idle.append([f"{label} ({len(sel)})",
                     sum(sel) * NS / len(devices)])
    return {
        "busy_s": sum(busy) / len(busy),
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle,
        "n_device_events": sum(len(e) for e in devices.values()),
        "devices": sorted(devices),
    }


def reduce_dir(profile_dir: str) -> dict:
    return reduce(load(newest_trace(profile_dir)))


def describe(path: str, limit: int = 4) -> str:
    """Planes, lines and a few events of a trace: what to look at by
    hand before trusting ``reduce`` on a new kind of device."""
    rows = []
    for pl in load(path).planes:
        rows.append(f"PLANE {pl.name}")
        for ln in pl.lines:
            ev = list(ln.events)
            rows.append(f"  LINE {ln.name!r}: {len(ev)} events")
            for e in ev[:limit]:
                rows.append(f"      {e.name[:60]!r} start {e.start_ns:.0f} "
                            f"ns, {e.duration_ns:.0f} ns")
    return "\n".join(rows)


if __name__ == "__main__":
    import json
    import sys
    target = sys.argv[1]
    if os.path.isdir(target):
        target = newest_trace(target)
    print(describe(target))
    print(json.dumps(reduce(load(target)), indent=1))
