"""The program's host spans as a tree, for the two metrics that read the
host's share of a tile's cycle (``layer_metrics/host_serial_ms.py``,
``layer_metrics/chip_wait_ms.py``).

A ``phase`` record of the program (``sagecal_tpu/diag/trace.py``) has
``name``, ``tm`` (its end) and ``dur_s`` on ``time.perf_counter()``, and,
from the PR that brought these metrics on, ``id``, ``parent`` (the span
open on the same thread when it was entered) and ``thread``.  The loop's
thread is the one that holds the root spans ``step``; with ``io`` (the
wait for the next tile) a ``step`` covers a tile's cycle.  ``wait`` is
the one name under which a host thread is blocked on the device.

A program whose records carry no ``id`` (every tree before that PR) has
no tree: ``Spans.ok`` is false and the readers report nothing.
"""

from __future__ import annotations

import bisect
import collections
import os
import statistics

import xplane

NS = xplane.NS
#: the roots of the loop's thread: a cycle is the roots up to a ``step``
ROOTS = ("io", "step", "arrival_wait")
WAIT = "wait"
#: gaps shorter than this are the device's own, between two operations
#: of one program; no host span is charged for them
SHORT_GAP_S = 1e-5


def phase_records(run) -> list:
    """Every ``phase`` and ``tile`` record of the run's ``--diag`` file
    (warm-up included: a window tile's ``io`` ends before the window
    opens), read once."""
    if not hasattr(run, "_host_records"):
        run._host_records = []
        if os.path.exists(run.diag_path):
            from sagecal_tpu.diag import trace as dtrace
            run._host_records = [r for r in dtrace.read(run.diag_path)
                                 if r.get("ev") in ("phase", "tile")]
    return run._host_records


class Spans:
    """The ``phase`` records that carry an ``id``, as a forest: each
    span's start and end, its path (``step/solve/wait``), its self
    seconds (duration less its children's), the loop's thread."""

    def __init__(self, records):
        self.by_id = {r["id"]: r for r in records
                      if r.get("ev") == "phase" and "id" in r}
        steps = collections.Counter(
            r.get("thread") for r in self.by_id.values()
            if r["name"] == "step" and r.get("parent") is None)
        self.ok = bool(steps)
        self.loop_thread = steps.most_common(1)[0][0] if steps else None
        self.self_s = {i: r["dur_s"] for i, r in self.by_id.items()}
        for r in self.by_id.values():
            if r.get("parent") in self.self_s:
                self.self_s[r["parent"]] -= r["dur_s"]
        self._path = {}

    @staticmethod
    def interval(r):
        return r["tm"] - r["dur_s"], r["tm"]

    def path(self, r) -> str:
        """``step/solve/wait``: the names from the root down."""
        got = self._path.get(r["id"])
        if got is None:
            up = self.by_id.get(r.get("parent"))
            got = (self.path(up) + "/" if up else "") + r["name"]
            self._path[r["id"]] = got
        return got

    def on_loop(self):
        """The loop thread's spans, in order of start (a parent before
        its children)."""
        sel = [r for r in self.by_id.values()
               if r.get("thread") == self.loop_thread]
        return sorted(sel, key=lambda r: (r["tm"] - r["dur_s"], -r["dur_s"]))

    def cycles(self, t_open: float, t_drain: float) -> list:
        """The tiles whose ``step`` began and ended in the window: per
        tile the loop thread's spans in order of start, from the roots
        after the ``step`` before (this tile's ``io``) to its own
        ``step`` and everything that holds."""
        out, cur, has_step, under = [], [], False, set()
        for r in self.on_loop():
            if r.get("parent") is None:
                if r["name"] not in ROOTS:
                    continue
                if has_step:
                    out.append(cur)
                    cur = []
                has_step = r["name"] == "step"
            elif r["parent"] not in under:
                continue
            under.add(r["id"])
            cur.append(r)
        if has_step:
            out.append(cur)

        def in_window(cyc):
            start, end = self.interval(step_of(cyc))
            return t_open <= start and end <= t_drain
        return [c for c in out if in_window(c)]


def step_of(cycle):
    """A cycle's root ``step``."""
    return next(r for r in cycle
                if r["name"] == "step" and r.get("parent") is None)


def host_table(spans: Spans, cycles: list, tile_tms: list):
    """(``host_serial_ms``, the ``[host]`` rows): per path of the loop's
    thread the median self milliseconds a tile; ``unspanned``, the cycle
    from one ``tile`` record to the next less that tile's ``io`` and
    ``step``; the background threads' spans beside them."""
    n = len(cycles)
    per_path = {}
    serial = 0.0
    for k, cyc in enumerate(cycles):
        for r in cyc:
            p = spans.path(r)
            per_path.setdefault(p, [0.0] * n)[k] += spans.self_s[r["id"]]
            if r["name"] != WAIT:
                serial += spans.self_s[r["id"]]
    rows = [f"[host] {p:<28} {1e3 * statistics.median(v):10.4f} ms self a "
            f"tile (median of {n}), mean {1e3 * sum(v) / n:.4f}"
            for p, v in per_path.items()]
    # a cycle by the tile records: the record of a cycle's own step is
    # the first one at or after that step's start
    gaps = []
    tms = sorted(tile_tms)
    for cyc in cycles:
        step = step_of(cyc)
        i = bisect.bisect_left(tms, step["tm"] - step["dur_s"])
        if 0 < i < len(tms) and tms[i] <= step["tm"]:
            roots = sum(r["dur_s"] for r in cyc if r.get("parent") is None)
            gaps.append((tms[i] - tms[i - 1], roots))
    if gaps:
        cycle = statistics.median(g for g, _ in gaps)
        un = statistics.median(g - c for g, c in gaps)
        rows.append(f"[host] {'unspanned':<28} {1e3 * un:10.4f} ms a tile "
                    f"(median of {len(gaps)}): {100 * un / cycle:.3f} % of "
                    f"the cycle, tile record to tile record "
                    f"{1e3 * cycle:.4f} ms")
    bg = {}
    t0 = min(spans.interval(c[0])[0] for c in cycles)
    t1 = max(r["tm"] for c in cycles for r in c)
    for r in spans.by_id.values():
        if (r.get("thread") != spans.loop_thread
                and t0 <= r["tm"] - r["dur_s"] < t1):
            acc = bg.setdefault(spans.path(r), [0.0, 0])
            acc[0] += spans.self_s[r["id"]]
            acc[1] += 1
    for p, (sec, cnt) in sorted(bg.items()):
        rows.append(f"[host] (other threads) {p:<12} {1e3 * sec / n:10.4f} "
                    f"ms self a tile (mean), {cnt / n:.2f} spans a tile")
    return 1e3 * serial / n, rows


# -- the device's gaps on the records' clock ----------------------------------

def clock_offset(profile_spans, records, tol_s=5e-5, width_s=5e-5):
    """Seconds to add to a record's ``time.perf_counter()`` instant to
    get the same instant on the profile's clock, or None.

    The profile keeps a span's name, start and end, no thread and no
    tile; a record keeps name, start and duration.  Every profile span
    votes for the offsets to the records of its name whose duration is
    its own within ``tol_s``; the true offset gets a vote of every span,
    a wrong pairing votes a tile's jitter away.  Returns ``(offset,
    votes in the densest ``width_s``, profile spans)``."""
    by_name = {}
    for r in records:
        if r.get("ev") == "phase":
            by_name.setdefault(r["name"], []).append(
                (r["dur_s"], r["tm"] - r["dur_s"]))
    for v in by_name.values():
        v.sort()
    cands = []
    for name, s, e in profile_spans:
        durs = by_name.get(name)
        if not durs:
            continue
        d = (e - s) * NS
        lo = bisect.bisect_left(durs, (d - tol_s,))
        hi = bisect.bisect_right(durs, (d + tol_s, float("inf")))
        cands += [s * NS - start for _, start in durs[lo:hi]]
    if not cands:
        return None
    cands.sort()
    best_n, best_j, j = 0, 0, 0
    for i, c in enumerate(cands):
        while c - cands[j] > width_s:
            j += 1
        if i - j + 1 > best_n:
            best_n, best_j = i - j + 1, j
    return (statistics.median(cands[best_j:best_j + best_n]), best_n,
            len(profile_spans))


def innermost(nested) -> list:
    """``[(start, end, label)]`` of one thread's spans (each inside or
    beside every other) -> the same instants cut into pieces that each
    carry the label of the innermost span open there, in order."""
    out, stack = [], []         # stack: [end, label]
    at = None

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, label = stack.pop()
            if end > at:
                out.append((at, end, label))
            at = max(at, end)

    for start, end, label in sorted(nested, key=lambda s: (s[0], -s[1])):
        close_until(start)
        if stack and start > at:
            out.append((at, start, stack[-1][1]))
        at = start
        stack.append([end, label])
    close_until(float("inf"))
    return out


def charge(gaps, pieces) -> dict:
    """{label: seconds} of ``gaps`` ``[(start, end)]`` by the piece that
    overlaps them; what no piece overlaps under ``None``."""
    starts = [p[0] for p in pieces]
    out = {}
    for a, b in gaps:
        left = b - a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, label = pieces[i]
            over = min(b, e) - max(a, s)
            if over > 0:
                out[label] = out.get(label, 0.0) + over
                left -= over
            i += 1
        if left > 0:
            out[None] = out.get(None, 0.0) + left
    return out
