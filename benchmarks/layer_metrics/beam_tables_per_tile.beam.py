"""How many device programs of a tile make the beam's tables: the distinct
programs (HLO modules) that ran in the traced slice and hold operations
under the scope ``rime/beam``.  Two today: the solve's coherency program
and the residual program, which forms the same coherencies again
(``residual._model_multifreq``); a program that carries the solve's
coherencies to the residual brings it to 1.  Counted from the trace, not
said by the program: which modules hold such operations is read from the
HLO modules stored in the trace (``scopes.hlo_table``), which of them ran
from the device's ``XLA Modules`` line (on a host-only trace, from the
operations' ``hlo_module``).  Their executions a tile of the slice are
printed beside it, and the ``tile`` records' beam fields (``beam_mode``,
``beam_elements``, ``beam_sources``, ``coh_path``).

``None`` where no stored module has an operation under ``rime/beam`` (a
program that makes its tables inside the map over clusters books them
under ``rime/phasor``), or where the run has no trace."""

import collections

import scopes
import xplane

NAME, UNIT = "beam_tables_per_tile.beam", "count"
LAYER, MOVES = "predict and residual", "tile_s.p50"
SCOPE = "rime/beam"


def table_programs(table: dict) -> set:
    """The modules of ``scopes.hlo_table`` with an operation under
    ``SCOPE``."""
    def under(text):
        found = scopes.scope_path(text)
        return bool(found) and found[0] == SCOPE
    return {m for m, ops in table.items() if any(map(under, ops.values()))}


def executions(pd, programs: set):
    """({module: executions in the trace} of ``programs``, True): the
    events of each device's ``XLA Modules`` line.  Where no device plane
    has one: ({module: 1} for a module that any host operation names,
    False)."""
    ran = collections.Counter()
    for pl in pd.planes:
        if pl.name.startswith("/device:TPU:"):
            for ln in pl.lines:
                if ln.name == "XLA Modules":
                    ran.update(m for m in (e.name.split("(")[0]
                                           for e in ln.events)
                               if m in programs)
    if ran:
        return ran, True
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    m = dict(e.stats).get("hlo_module")
                    if m in programs:
                        ran[m] = 1
    return ran, False


def count(run):
    """(distinct programs that ran, their executions a tile or None on a
    host-only trace); None where there is nothing to read."""
    if not hasattr(run, "_beam_table_programs"):
        run._beam_table_programs = None
        sl = scopes.load(run)
        if sl is not None and run.slice_tiles:
            table = sl.table if sl.table is not None \
                else scopes.hlo_table(sl.path)
            programs = table_programs(table)
            ran, counted = executions(xplane.load(sl.path), programs) \
                if programs else ({}, False)
            if ran:
                run._beam_table_programs = (
                    sorted(ran),
                    sum(ran.values()) / run.slice_tiles if counted else None)
    return run._beam_table_programs


def read(run):
    said = sorted({(r.get("beam_mode"), r.get("beam_elements"),
                    r.get("beam_sources"), r.get("coh_path"))
                   for r in scopes.window_records(run)
                   if r.get("ev") == "tile" and "beam_mode" in r}, key=str)
    for m, e, s, c in said:
        print(f"[beam] the window's tile records: beam_mode {m}, "
              f"beam_elements {e}, beam_sources {s}, coh_path {c}")
    found = count(run)
    if found is None:
        print(f"[beam] no program with operations under {SCOPE} ran in the "
              f"trace: nothing to read for {NAME}")
        return None
    names, per_tile = found
    print(f"[beam] programs with operations under {SCOPE} that ran in the "
          f"slice: {', '.join(names)}"
          + (f"; {per_tile:.4g} executions a tile begun in it"
             if per_tile else ""))
    return len(names)
