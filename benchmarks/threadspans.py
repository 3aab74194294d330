"""The threads that set the pace where the device does not: the writer's
and the reader's own work a tile, how long a job lay in the writer's
queue, and what the loop's thread was blocked on while it stood in
``io`` and ``submit`` (``layer_metrics/writer_ms.py``, ``reader_ms.py``,
``write_queue_ms.py``, ``loop_blocked_ms.py``).

A ``phase`` record of the program may carry ``cause``, the ``id`` of the
span ON ANOTHER THREAD that handed it its work or produced what it
waited for, and ``queued_s``, the seconds between that hand-over and its
entry (``sagecal_tpu/diag/trace.py``).  A root span of the writer's
thread names the ``submit`` that queued its job; the loop's ``io`` names
the reader's root that produced its tile.  So a window tile's own write
and read are found through its own spans, wherever in time the other
thread did them, and a blocked interval of the loop is followed to the
thread at the queue's other end.

Every host thread is on ``time.perf_counter()``: no clock has to be
tied, no profile is needed, and the whole window is read, not the
profiler's slice.  A program whose records carry no ``cause`` (every
tree before the PR that brought these metrics) has nothing to follow:
the readers print one line and report nothing.
"""

from __future__ import annotations

import statistics

import hostspans

WAIT = hostspans.WAIT
#: a job that lay queued longer than this "waited" (``write_queue_ms``)
WAITED_S = 1e-3


class Threads:
    """The window's cycles and, through ``cause``, the writer's roots
    each cycle's ``submit`` spans queued and the reader's root that
    produced each cycle's tile."""

    def __init__(self, records, t_open, t_drain):
        self.spans = spans = hostspans.Spans(records)
        self.cycles = (spans.cycles(t_open, t_drain)
                       if spans.ok and t_open is not None else [])
        self.n = len(self.cycles)
        by_id = spans.by_id
        self.kids = {}
        for r in by_id.values():
            self.kids.setdefault(r.get("parent"), []).append(r)
        caused = {}     # the id of a cause -> the roots it handed work to
        for r in by_id.values():
            if r.get("cause") in by_id and r.get("parent") is None:
                caused.setdefault(r["cause"], []).append(r)
        self.ok = bool(self.cycles) and bool(caused)
        self.written = []   # per cycle: the writer's roots it queued
        self.read = []      # per cycle: the reader's root(s) of its tile
        for cyc in self.cycles:
            self.written.append(sorted(
                (w for r in cyc if r["name"] == "submit"
                 for w in caused.get(r["id"], [])),
                key=lambda w: w["tm"]))
            self.read.append([
                by_id[r["cause"]] for r in cyc
                if r["name"] == "io" and r.get("parent") is None
                and r.get("cause") in by_id])
        tms = sorted(r["tm"] for r in records if r.get("ev") == "tile"
                     and t_open is not None and t_open <= r["tm"] <= t_drain)
        gaps = [b - a for a, b in zip(tms, tms[1:])]
        self.cycle_s = statistics.median(gaps) if gaps else None
        self._own, self._pieces, self._writers = {}, {}, None

    def subtree(self, root):
        """A span and everything under it, a parent before its
        children."""
        out, todo = [], [root]
        while todo:
            r = todo.pop()
            out.append(r)
            todo += self.kids.get(r["id"], [])
        return out

    def own(self, which):
        """({path: [self seconds in each cycle]}, the mean seconds a
        tile in those roots less every ``wait`` under them) of the
        ``written`` or the ``read`` roots, made once."""
        if which not in self._own:
            self._own[which] = self._own_of(getattr(self, which))
        return self._own[which]

    def _own_of(self, roots_per_cycle):
        spans, per_path, own = self.spans, {}, 0.0
        for k, roots in enumerate(roots_per_cycle):
            for root in roots:
                for r in self.subtree(root):
                    sec = spans.self_s[r["id"]]
                    per_path.setdefault(spans.path(r),
                                        [0.0] * self.n)[k] += sec
                    if r["name"] != WAIT:
                        own += sec
        return per_path, own / self.n

    # -- the loop's blocked intervals ------------------------------------

    def blocked(self):
        """[(start, end, the loop's span)]: where the loop's thread
        stood in the SELF time of a window cycle's root ``io`` (the
        carved ``arrival_wait`` is beside it, not in it) or of a
        ``submit``: blocked on another host thread, not on the device."""
        spans = self.spans
        mine = {r["id"]: r for cyc in self.cycles for r in cyc
                if r["name"] == "submit"
                or (r["name"] == "io" and r.get("parent") is None)}
        pieces = hostspans.innermost(
            [(a, b, r["id"]) for r in spans.on_loop()
             for a, b in [spans.interval(r)]])
        return [(a, b, mine[i]) for a, b, i in pieces if i in mine]

    def other_end(self, r):
        """The thread at the other end of the queue the loop's span
        ``r`` stands at: the reader's for an ``io`` (its cause's), the
        writer's for a ``submit`` (that of the roots its job ran
        under, or of any ``submit``'s where this one's job ran under
        none in the records)."""
        by_id = self.spans.by_id
        if r["name"] == "io":
            up = by_id.get(r.get("cause"))
            return up and up.get("thread")
        if self._writers is None:
            self._writers = {}
            for w in by_id.values():
                up = by_id.get(w.get("cause"))
                if up is not None and up["name"] == "submit":
                    self._writers[up["id"]] = w.get("thread")
        return self._writers.get(r["id"]) or next(
            iter(self._writers.values()), None)

    def pieces_of(self, thread):
        """``thread``'s spans cut into the pieces that each carry the
        path of the innermost span open there."""
        if thread not in self._pieces:
            spans = self.spans
            self._pieces[thread] = hostspans.innermost(
                [(a, b, spans.path(r)) for r in spans.by_id.values()
                 if r.get("thread") == thread
                 for a, b in [spans.interval(r)]])
        return self._pieces[thread]


def load(run, tag):
    """The run's :class:`Threads`, read once; None, with one printed
    line, where there is nothing to follow."""
    if not hasattr(run, "_threads"):
        w = run.window
        run._threads = Threads(hostspans.phase_records(run), w.t_open,
                               w.t_drain)
    th = run._threads
    if not th.ok:
        print(f"[{tag}] no span with a cause in the window's records: "
              f"nothing to follow to another thread")
        return None
    return th


def own_table(th, which, tag, name, what):
    """Print the ``[tag]`` rows and return the value: per path the
    median and the mean SELF milliseconds a tile under the ``written``
    or the ``read`` roots; the means add up to the roots, and less every
    ``wait`` row to the value."""
    per_path, own = th.own(which)
    total = 0.0
    for p, v in per_path.items():
        total += sum(v) / th.n
        print(f"[{tag}] {p:<28} {1e3 * statistics.median(v):10.4f} ms self "
              f"a tile (median of {th.n}), mean {1e3 * sum(v) / th.n:.4f}")
    roots = sum(len(r) for r in getattr(th, which))
    print(f"[{tag}] the means add up to {1e3 * total:.4f} ms, the roots "
          f"({roots / th.n:.2f} a tile); less every wait: {name} "
          f"{1e3 * own:.4f}, {what}, mean over {th.n} tiles")
    return 1e3 * own


def writer_ms(run):
    th = load(run, "writer")
    if th is None:
        return None
    return own_table(th, "written", "writer", "writer_ms",
                     "the writer thread's own work")


def reader_ms(run):
    th = load(run, "reader")
    if th is None:
        return None
    value = own_table(th, "read", "reader", "reader_ms",
                      "the reader thread's own work")
    # the tenant's data rate, not the reader's work: said apart
    threads = {r.get("thread") for roots in th.read for r in roots}
    t0 = min(th.spans.interval(c[0])[0] for c in th.cycles)
    t1 = max(r["tm"] for c in th.cycles for r in c)
    arrival = sum(r["dur_s"] for r in th.spans.by_id.values()
                  if r["name"] == "arrival_wait"
                  and r.get("thread") in threads
                  and t0 <= r["tm"] - r["dur_s"] < t1)
    print(f"[reader] {'arrival_wait (not counted)':<28} "
          f"{1e3 * arrival / th.n:10.4f} ms a tile (mean)")
    return value


def write_queue_ms(run):
    th = load(run, "queue")
    if th is None:
        return None
    q = [w["queued_s"] for roots in th.written for w in roots]
    if not q:
        print("[queue] no writer job was queued in the window")
        return None
    waited = sum(1 for s in q if s > WAITED_S)
    print(f"[queue] write_queue_ms {1e3 * statistics.mean(q):.4f}: mean "
          f"queued_s of {len(q)} writer-job roots ({len(q) / th.n:.2f} a "
          f"tile); max {1e3 * max(q):.4f} ms; {100 * waited / len(q):.1f} "
          f"% of them lay queued over {1e3 * WAITED_S:g} ms")
    return 1e3 * statistics.mean(q)


def loop_blocked_ms(run):
    th = load(run, "pace")
    if th is None:
        return None
    spans, n = th.spans, th.n
    rows, by_end = {}, {"io": 0.0, "submit": 0.0}
    for a, b, r in th.blocked():
        by_end[r["name"]] += b - a
        thread = th.other_end(r)
        got = (hostspans.charge([(a, b)], th.pieces_of(thread))
               if thread is not None else {None: b - a})
        for label, sec in got.items():
            rows[label] = rows.get(label, 0.0) + sec
    value = 1e3 * sum(by_end.values()) / n
    idle = rows.pop(None, 0.0)
    listed = sorted(rows.items(), key=lambda kv: -kv[1]) + [
        ("that thread idle", idle)]
    for label, sec in listed:
        print(f"[pace] {label:<28} {1e3 * sec / n:10.4f} ms a tile")
    total = 1e3 * sum(sec for _, sec in listed) / n
    print(f"[pace] rows add up to {total:.4f} ms; loop_blocked_ms "
          f"{value:.4f}: the loop's self time in io and submit, mean "
          f"over {n} tiles")
    # the verdict: who sets the pace
    serial = wait = 0.0
    for cyc in th.cycles:
        for r in cyc:
            if r["name"] == WAIT:
                wait += spans.self_s[r["id"]]
            else:
                serial += spans.self_s[r["id"]]
    cycle = th.cycle_s
    if cycle is None:       # one tile record: the roots stand for it
        cycle = sum(r["dur_s"] for c in th.cycles for r in c
                    if r.get("parent") is None) / n
    _, w_own = th.own("written")
    _, r_own = th.own("read")
    print(f"[verdict] cycle {1e3 * cycle:.4f} ms (tile record to tile "
          f"record, median); the loop's own "
          f"{1e3 * serial / n - value:.4f} ms (io + step less wait less "
          f"blocked), blocked on the writer "
          f"{1e3 * by_end['submit'] / n:.4f}, on the reader "
          f"{1e3 * by_end['io'] / n:.4f}, wait (the device) "
          f"{1e3 * wait / n:.4f}; writer_ms {1e3 * w_own:.4f} = "
          f"{100 * w_own / cycle:.1f} % of the cycle, reader_ms "
          f"{1e3 * r_own:.4f} = {100 * r_own / cycle:.1f} %")
    return value
