"""What the program's own names say about a traced run: device time per
scope (``sage/*``, ``rime/*``: ``jax.named_scope`` in the program), idle
time per host span (``sagecal/<name>``: ``diag.trace.phase``), and the
``--diag`` and compile-log records that fall inside the window.

The traced slice is read once per run (``xplane.load`` of the newest
trace under ``run.profile_dir``) and kept on ``run``.  A device
operation's scope is looked for in three places, the first that has it:

1. the event's own text, should a profile print an operation's
   ``metadata={op_name="jit(f)/sage/sweep/..."}`` with its HLO line (the
   v5e's of libtpu 0.0.34 prints the line without it);
2. a table ``(module, operation) -> op_name`` read out of the HLO
   modules that the profiler stores in the trace file itself (plane
   ``/host:metadata``).  On the TPU an operation's module is the event
   of the device plane's line ``XLA Modules`` that holds it in time
   (``jit__jit_sagefit(<fingerprint>)``); in a CPU rehearsal it is the
   event's ``hlo_module`` stat.  This is the route both take today;
3. a string stat of the event (``tf_op``, ``long_name`` and the like),
   where an event names no module.

A program compiled before the scopes existed has none of the three; a
reader then says "no scoped event in the trace" and reports nothing.

Seconds per scope are of LEAF operations (those that hold no other):
their durations add up to the device's busy time, so the shares are of
what ``device_idle_pct`` calls busy.  A window record is one whose
``tm`` (``time.perf_counter()`` at emit) lies between the window's
opening and its drain.
"""

from __future__ import annotations

import bisect
import os
import statistics

import xplane

NS = xplane.NS
#: first-level scopes start with one of these
ROOTS = ("sage/", "rime/")
#: second-level names under ``sage/sweep`` and ``sage/refine``; the
#: innermost one found in an operation's path is its second level
SECOND = ("assemble", "inner", "update", "linesearch", "direction")
SPAN_PREFIX = "sagecal/"
UNSCOPED = "(unscoped)"


# -- protobuf wire format, as far as the HLO table needs it -------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: bytes for length-delimited
    fields, ints for varints; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire}")


def _sub(buf, *path):
    """Every sub-message reached by following ``path`` (field numbers)."""
    level = [buf]
    for num in path:
        level = [v for b in level for f, v in _fields(b)
                 if f == num and isinstance(v, (bytes, memoryview))]
    return level


def _text(buf, num):
    for f, v in _fields(buf):
        if f == num and not isinstance(v, int):
            return bytes(v).decode("utf-8", "replace")
    return ""


def hlo_table(path: str) -> dict:
    """{module name: {instruction name: op_name}} of the HLO modules
    stored in a trace file (XSpace.planes -> XPlane ``/host:metadata``
    .event_metadata -> XEventMetadata.stats -> XStat.bytes_value ->
    HloProto.hlo_module -> computations -> instructions ->
    metadata.op_name).  Empty where the profiler stored none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        # map entry (4) -> value (2) -> stats (5) -> bytes_value (6)
        for proto in _sub(plane, 4, 2, 5, 6):
            try:
                for module in _sub(proto, 1):
                    ops = out.setdefault(_text(module, 1), {})
                    for ins in _sub(module, 3, 2):
                        meta = _sub(ins, 7)
                        name = meta and _text(meta[0], 2)
                        if name:
                            ops[_text(ins, 1)] = name
            except (ValueError, IndexError):
                continue        # a bytes stat that is no HloProto
    return {m: ops for m, ops in out.items() if ops}


# -- scopes -------------------------------------------------------------------

def scope_path(text: str):
    """``jit(f)/jit(main)/sage/sweep/inner/while/body/mul`` ->
    ``("sage/sweep", "inner")``; None where no root is in ``text``.
    The first level is the first root found and the name after it; the
    second the innermost of ``SECOND`` after that."""
    at = min((i for i in (text.find(r) for r in ROOTS) if i >= 0),
             default=-1)
    if at < 0 or (at > 0 and text[at - 1] not in "/(\"' ="):
        return None
    parts = []
    for p in text[at:].split("/"):
        p = p.strip("()\"' ")
        parts.append(p.rsplit("(", 1)[-1])    # transpose(jvp(name -> name
    first = "/".join(parts[:2]).split('"')[0].split(")")[0]
    second = next((p for p in reversed(parts[2:]) if p in SECOND), None)
    return first, second


class Slice:
    """The traced slice of one run, reduced once.

    An operation the compiler made (a layout copy, a bitcast fusion) has
    no source name.  Inside a loop that has one it is that loop's work
    by the program's structure, and is counted under the innermost
    enclosing operation with a scope (``made`` keeps those seconds
    apart); at a module's top level it stays unscoped."""

    def __init__(self, trace_path: str):
        self.path = trace_path
        pd = xplane.load(trace_path)
        self.table = None           # read only if events carry no scope
        self.how = set()            # where scopes were found
        devices, self.spans = self._events(pd)
        self.leaf = {}              # (first, second) -> [seconds, count]
        self.made = {}              # first -> seconds placed by nesting
        self.unscoped = {}          # operation name -> seconds
        self.merged = []            # per device, the union of leaves
        for events in devices.values():
            leaves = self._leaves(events)
            self.merged.append(xplane.union((s, e) for _, s, e in leaves))
            for lab, s, e in leaves:
                if isinstance(lab, str):
                    self.unscoped[lab] = (self.unscoped.get(lab, 0.0)
                                          + (e - s) * NS)
                    lab = (UNSCOPED, None)
                acc = self.leaf.setdefault(lab, [0.0, 0])
                acc[0] += (e - s) * NS
                acc[1] += 1
        self.n_devices = max(1, len(devices))
        self.busy_s = sum(b - a for m in self.merged
                          for a, b in m) * NS / self.n_devices

    def _leaves(self, events):
        """The events that hold no other, each with its own label or,
        where it has none, the innermost enclosing scoped one's."""
        order = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
        stack, items = [], []   # stack: [end, nearest scoped label, item]
        for lab, start, end in order:
            while stack and stack[-1][0] <= start:
                stack.pop()
            near = None
            if stack:
                near = stack[-1][1]
                stack[-1][2][4] = False         # the parent is no leaf
            placed = isinstance(lab, str) and near is not None
            if placed:
                lab = near
            item = [lab, start, end, placed, True]
            items.append(item)
            stack.append([end, near if isinstance(lab, str) else lab, item])
        leaves = []
        for lab, start, end, placed, is_leaf in items:
            if is_leaf:
                leaves.append((lab, start, end))
                if placed:
                    self.made[lab[0]] = (self.made.get(lab[0], 0.0)
                                         + (end - start) * NS)
        return leaves

    # the scope of one event; labels are (first, second) or, unscoped,
    # the operation's short name
    def _label(self, event, module, cache):
        name = event.name
        hit = cache.get((module, name))
        if hit is not None:
            return hit
        found = scope_path(name)
        if found:
            self.how.add("the event's text")
        op = xplane.op_name(name)
        if not found and module is None:
            stats = dict(event.stats)
            module, op = stats.get("hlo_module"), stats.get("hlo_op", op)
            hit = cache.get((module, op))
            if hit is not None:
                return hit
            if module is None:
                for k, v in stats.items():
                    found = isinstance(v, str) and scope_path(v)
                    if found:
                        self.how.add(f"the stat {k!r}")
                        break
            name = op
        if not found and module is not None:
            if self.table is None:
                self.table = hlo_table(self.path)
            text = self.table.get(module, {}).get(op)
            found = text and scope_path(text)
            if found:
                self.how.add("the HLO modules stored in the trace")
        cache[(module, name)] = found or op
        return cache[(module, name)]

    def _events(self, pd):
        """({device: [(label, start_ns, end_ns)]}, [(span name, start,
        end)]): the device events as ``xplane.device_events`` selects
        them, labelled, and the program's host spans."""
        planes = list(pd.planes)
        devices, spans, cache = {}, [], {}
        for pl in planes:
            if not pl.name.startswith("/device:TPU:"):
                continue
            lines = {ln.name: ln for ln in pl.lines}
            if "XLA Ops" not in lines:
                continue
            # an operation's module: the XLA Modules event that holds it
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0])
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]

            def module_at(t):
                i = bisect.bisect_right(starts, t) - 1
                return mods[i][2] if i >= 0 and t < mods[i][1] else None

            ev = [(self._label(e, module_at(e.start_ns), cache),
                   e.start_ns, e.start_ns + e.duration_ns)
                  for e in lines["XLA Ops"].events]
            if ev:
                devices[pl.name] = ev
        host_ops = []
        for pl in planes:
            if not pl.name.startswith("/host:"):
                continue
            for ln in pl.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif not devices and e.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in e.stats):
                        host_ops.append(e)
        if not devices and host_ops:
            devices["/host:CPU"] = [
                (self._label(e, None, cache), e.start_ns,
                 e.start_ns + e.duration_ns) for e in host_ops]
        return devices, spans

    # -- what the readers ask ------------------------------------------------

    def scoped(self) -> bool:
        return any(k[0] != UNSCOPED for k in self.leaf)

    def first_level(self, first: str):
        """(seconds, count) of the leaf operations under ``first``,
        summed over the devices."""
        sel = [v for (f, _), v in self.leaf.items() if f == first]
        return sum(v[0] for v in sel), sum(v[1] for v in sel)

    def idle_in_spans(self) -> dict:
        """{span name: [spans, seconds of them, idle seconds inside
        them]}: idle is the span's length less the device's busy time
        inside it, the first device's."""
        merged = self.merged[0] if self.merged else []
        out = {}
        for name, s, e in self.spans:
            busy = sum(min(b, e) - max(a, s) for a, b in merged
                       if b > s and a < e)
            acc = out.setdefault(name, [0, 0.0, 0.0])
            acc[0] += 1
            acc[1] += (e - s) * NS
            acc[2] += max(0.0, (e - s) - busy) * NS
        return out

    def table_lines(self) -> list:
        """The ``[scope]`` table: seconds, share of busy time and count
        for every first and second level, then the largest unscoped
        operations."""
        total = sum(v[0] for v in self.leaf.values()) or 1.0
        rows = []
        firsts = sorted({f for f, _ in self.leaf},
                        key=lambda f: -self.first_level(f)[0])
        for f in firsts:
            sec, n = self.first_level(f)
            rows.append(f"[scope] {f:<28} {sec:10.4f} s "
                        f"{100 * sec / total:6.2f} % {n:9d} ops")
            if f in self.made:
                rows.append(f"[scope]   of which in operations the compiler "
                            f"made (no source name), placed by the loop "
                            f"that holds them: {self.made[f]:.4f} s "
                            f"{100 * self.made[f] / total:.2f} %")
            seconds = sorted(((s, v) for (ff, s), v in self.leaf.items()
                              if ff == f and s), key=lambda kv: -kv[1][0])
            for s, v in seconds:
                rows.append(f"[scope]   {f + '/' + s:<26} {v[0]:10.4f} s "
                            f"{100 * v[0] / total:6.2f} % {v[1]:9d} ops")
        for name, sec in sorted(self.unscoped.items(),
                                key=lambda kv: -kv[1])[:8]:
            rows.append(f"[scope]   unscoped {name:<17} {sec:10.4f} s "
                        f"{100 * sec / total:6.2f} %")
        where = ", ".join(sorted(self.how)) or "nowhere"
        rows.append(f"[scope] busy {self.busy_s:.4f} s on "
                    f"{self.n_devices} device(s); scopes found in: {where}")
        return rows


def load(run):
    """The run's :class:`Slice` (read once), or None with the reason
    printed: no profiler trace, or a trace with no device operation."""
    if not hasattr(run, "_scopes"):
        run._scopes = None
        if run.profile is None:
            print("[scope] no profiler trace in this run")
        else:
            try:
                run._scopes = Slice(xplane.newest_trace(run.profile_dir))
            except (FileNotFoundError, ValueError) as e:
                print(f"[scope] {e}")
            else:
                for row in run._scopes.table_lines():
                    print(row)
        cycle_line(run)
    return run._scopes


def cycle_line(run) -> None:
    """Print the traced run's own tile cycle, from one ``tile`` record
    of the window to the next: what the tracer costs when it is on is
    this against an untraced run's ``tile_s.p50``."""
    tms = [r["tm"] for r in window_records(run) if r.get("ev") == "tile"]
    gaps = [b - a for a, b in zip(tms, tms[1:])]
    if gaps:
        print(f"[span] traced run, tile record to tile record: median "
              f"{statistics.median(gaps):.6g} s over {len(gaps)} cycles")


def per_tile(run, first: str, unit: float = 1.0):
    """Leaf seconds under ``first`` per tile begun in the slice, times
    ``unit``; None (and why) where the trace has no scoped event."""
    sl = load(run)
    if sl is None or not run.slice_tiles:
        return None
    if not sl.scoped():
        print(f"[scope] no scoped event in the trace: nothing to read "
              f"for {first}")
        return None
    sec, n = sl.first_level(first)
    print(f"[scope] {first}: {sec:.6g} s in {n} leaf operations over "
          f"{run.slice_tiles} tile(s) of the slice")
    return unit * sec / sl.n_devices / run.slice_tiles


# -- records of the window ----------------------------------------------------

def window_records(run) -> list:
    """The ``--diag`` records whose ``tm`` lies inside the window.  The
    tile number will not do: in ``predict-m8x128`` it is the disk index,
    which warm-up and window share."""
    if not hasattr(run, "_window_records"):
        run._window_records = []
        w = run.window
        if os.path.exists(run.diag_path) and w.t_open is not None:
            from sagecal_tpu.diag import trace as dtrace
            run._window_records = [
                r for r in dtrace.read(run.diag_path)
                if "tm" in r and w.t_open <= r["tm"] <= w.t_drain]
    return run._window_records


def compile_log(run):
    """``guard.compile_log()`` split at the window's edges: (before the
    opening, inside the window); None where the program keeps no log."""
    from sagecal_tpu.diag import guard
    if not hasattr(guard, "compile_log") or run.window.t_open is None:
        return None
    w = run.window
    log = guard.compile_log()
    return ([r for r in log if r[0] < w.t_open],
            [r for r in log if w.t_open <= r[0] <= w.t_drain])


def union_seconds(records) -> float:
    """Seconds covered by the records' intervals ``[tm - dur, tm]``: a
    function's trace holds the traces of what it calls, and the union
    counts those seconds once."""
    ivals = [(tm - dur, tm) for tm, _stage, _fun, dur in records]
    return sum(b - a for a, b in xplane.union(ivals))


def span_table(run, names) -> None:
    """Print the ``[span]`` table: per phase name the median seconds of
    the window's records, and from the profiler's slice how many
    ``sagecal/<name>`` spans it holds and the device's idle seconds
    inside them."""
    recs = [r for r in window_records(run) if r.get("ev") == "phase"]
    sl = load(run)
    idle = sl.idle_in_spans() if sl is not None else {}
    for name in names:
        durs = [r["dur_s"] for r in recs if r.get("name") == name]
        row = (f"[span] {SPAN_PREFIX + name:<18} "
               + (f"median {1e3 * statistics.median(durs):9.4f} ms over "
                  f"{len(durs)} window records" if durs
                  else "no window record"))
        if name in idle:
            n, sec, idl = idle[name]
            row += (f"; in the slice {n} spans, {sec:.4f} s, device idle "
                    f"inside them {idl:.4f} s")
        print(row)


def describe(path: str, limit: int = 2) -> str:
    """Planes, lines, and a few events with their full text and stats:
    the first thing to look at on a new kind of device."""
    rows = []
    for pl in xplane.load(path).planes:
        rows.append(f"PLANE {pl.name} stats={dict(pl.stats)}")
        for ln in pl.lines:
            ev = list(ln.events)
            rows.append(f"  LINE {ln.name!r}: {len(ev)} events")
            for e in ev[:limit] + ev[len(ev) // 2:len(ev) // 2 + limit]:
                rows.append(f"      {e.name[:700]!r}\n        "
                            f"{e.duration_ns:.0f} ns stats={dict(e.stats)}")
    table = hlo_table(path)
    rows.append(f"HLO modules stored in the trace: "
                f"{ {m: len(o) for m, o in table.items()} }")
    with open(path, "rb") as f:
        raw = f.read()
    rows.append("raw counts: " + ", ".join(
        f"{k!r} {raw.count(k.encode())}"
        for k in ("sage/", "rime/", "op_name", "sagecal/")))
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    target = sys.argv[1]
    if os.path.isdir(target):
        target = xplane.newest_trace(target)
    print(describe(target))
    for line in Slice(target).table_lines():
        print(line)
