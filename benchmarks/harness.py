"""What every cell shares: the manifest, the window's clock and its
arithmetic, the comparison that decides ``correct``, the work directory.

A cell is found by name in ``BENCHMARK.json``; its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``),
the driver the mix names (``drivers/<driver>.py``) and the per-layer
metrics that list it (``layer_metrics/<metric>.py``) are files found by
those names.  Adding a cell, a configuration, a mix, a driver or a metric
is adding a file and an entry; nothing here names any of them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import statistics
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# -- manifest and files found by name ----------------------------------------

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots
    and dashes, so this is not an import statement)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(path: str) -> dict:
    """A configuration file.  One that names a ``base`` (a path from the
    root) is that file with its own keys laid over it: the tiny rehearsal
    configurations are the real ones at other sizes."""
    conf = load_json(ROOT, path)
    if "base" in conf:
        conf = {**load_config(conf["base"]), **conf}
    return conf


class Cell:
    """One entry of ``workloads`` with everything its name leads to.

    ``more`` is a file of further ``configs`` and ``workloads`` (the tiny
    rehearsal cells under ``tests/``).  Such a cell reports the metrics
    of the cell of ``BENCHMARK.json`` that it ``stands_for``: the metric
    lists and the bounds are the root manifest's alone."""

    def __init__(self, name: str, more: dict | None = None):
        self.manifest = load_json(ROOT, "BENCHMARK.json")
        more = more or {}
        workloads = self.manifest["workloads"] + more.get("workloads", [])
        byname = {w["name"]: w for w in workloads}
        if name not in byname:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have: {', '.join(sorted(byname))})")
        self.entry = byname[name]
        self.name = name
        self.reports_as = self.entry.get("stands_for", name)
        self.chips = int(self.entry["chips"])
        cfg_entry = next(
            c for c in self.manifest["configs"] + more.get("configs", [])
            if c["name"] == self.entry["config"])
        self.config = load_config(cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.entry["traffic"] + ".json")
        self.driver = load_module("drivers", self.traffic["driver"])

    def metrics(self, group: str):
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` key, and those listing it."""
        return [m for m in self.manifest[group]
                if self.reports_as in m.get("workloads", [self.reports_as])]


def work_dir(cell_name: str) -> str:
    """A fresh, fixed directory for this run's data and outputs, inside
    the checkout (listed in benchmarks/.gitignore)."""
    path = os.path.join(HERE, ".work", cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


# -- the window ---------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; with few values the upper ones are the maximum."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Window:
    """The measured window of one run, on the host's monotonic clock.

    A driver calls ``enter(tile, n_vis)`` as each tile's cycle begins
    (the first call opens the window) and ``drain()`` once the writer has
    nothing left to write.  ``due()`` says whether ``seconds`` have
    passed, which a driver asks at a tile boundary only: the window closes
    at the first boundary after ``seconds``, never inside a tile.
    """

    def __init__(self, seconds: float, t_process_start: float,
                 clock=time.perf_counter):
        self.seconds = float(seconds)
        self.clock = clock
        self.t_start = t_process_start
        self.entries: list[tuple[int, float, int]] = []
        self.t_open = None
        self.t_due = None
        self.t_drain = None

    def due(self) -> bool:
        if self.t_open is None:
            return False
        now = self.clock()
        if now - self.t_open < self.seconds:
            return False
        if self.t_due is None:
            self.t_due = now        # the boundary that closes the window
        return True

    def enter(self, tile: int, n_vis: int) -> float:
        now = self.clock()
        if self.t_open is None:
            self.t_open = now
        self.entries.append((tile, now, int(n_vis)))
        return now

    def drain(self) -> None:
        self.t_drain = self.clock()

    # arithmetic, kept apart from the clock so that it is testable on
    # synthetic records
    @property
    def tiles(self):
        return [t for t, _, _ in self.entries]

    def tile_seconds(self):
        """Each tile's cycle: entry to the next entry, the last one to
        the drain."""
        stamps = [t for _, t, _ in self.entries] + [self.t_drain]
        return [b - a for a, b in zip(stamps, stamps[1:])]

    def length_s(self) -> float:
        return self.t_drain - self.t_open

    def setup_s(self) -> float:
        return self.t_open - self.t_start

    def vis_per_s(self) -> float:
        return sum(n for _, _, n in self.entries) / self.length_s()

    def end_to_end(self) -> dict:
        ts = self.tile_seconds()
        return {
            "vis_per_s": self.vis_per_s(),
            "tile_s.p50": statistics.median(ts),
            "tile_s.p95": percentile(ts, 95.0),
            "setup_s": self.setup_s(),
        }


class Clock:
    """Where a run's seconds went: phases in the order they ended.

    ``mark(name)`` books the seconds since the last mark under ``name``
    (``at`` gives the instant where the harness already read the clock);
    ``carve(name, seconds)`` books seconds of the phase now running
    under a name of their own.  The phases add up to ``total()`` exactly,
    whatever was left unnamed falling to the next mark."""

    def __init__(self, t_start: float, clock=time.perf_counter):
        self.clock = clock
        self.t_start = self._last = t_start
        self.phases: dict[str, float] = {}
        self.notes: dict[str, object] = {}      # counts printed beside

    def _add(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    def mark(self, name: str, at: float | None = None) -> None:
        at = self.clock() if at is None else at
        self._add(name, at - self._last)
        self._last = at

    def carve(self, name: str, seconds: float) -> None:
        self._add(name, seconds)
        self._last += seconds

    def total(self) -> float:
        return self._last - self.t_start

    def line(self) -> str:
        return ("[clock] " + ", ".join(
            f"{k} {v:.2f}" for k, v in self.phases.items())
            + f"; total {self.total():.2f} s"
            + "".join(f"; {k} {v}" for k, v in self.notes.items()))


# -- the comparison -----------------------------------------------------------

class Comparison:
    """One number compared with its limit; ``correct`` is all of them."""

    def __init__(self, name: str, value: float, limit: float, note: str = ""):
        self.name, self.value, self.limit, self.note = (
            name, float(value), float(limit), note)

    @property
    def ok(self) -> bool:
        # a NaN compares false: not correct
        return self.value <= self.limit

    def line(self, note: bool = True) -> str:
        return (f"[check] {self.name} = {self.value:.6g}  (limit "
                f"{self.limit:.6g}) {'ok' if self.ok else 'NOT CORRECT'}"
                + (f"  {self.note}" if note and self.note else ""))


def worse(a: float, b: float) -> float:
    """The larger of two compared numbers; a NaN wins."""
    return a if (a != a or a >= b) else b


def pick_tiles(tiles, count: int):
    """The first, the middle ones and the last of ``tiles``: ``count`` of
    them, fewer when the window held fewer."""
    tiles = list(tiles)
    if len(tiles) <= count:
        return tiles
    idx = sorted({round(k * (len(tiles) - 1) / (count - 1))
                  for k in range(count)})
    return [tiles[i] for i in idx]
