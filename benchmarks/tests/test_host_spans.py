"""The two metrics that read the host's share of a tile's cycle,
``host_serial_ms`` and ``chip_wait_ms``: on hand-made records and a
hand-made slice (nested spans, a background span over a gap, a gap
outside every span, a gap split between two spans, a program whose
records carry no ``id``), their entries in the manifest (the LAST of
``per_layer``, for all five cells), the tiny rehearsal cells traced, and
the three tests of the benchmark that pin a cell's metric list by place,
held here on the manifest less the two new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_host_spans.py -q
"""

import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import harness      # noqa: E402
import hostspans    # noqa: E402

NEW = ["host_serial_ms", "chip_wait_ms"]
CELLS = ["cal-m8x3", "predict-m8x128", "admm-f4-mesh", "cal-t120",
         "subtract-m8x128"]
#: the profile's clock is this far ahead of the records'
OFFSET = 1000.0
NS = 1e9


def metric(name):
    return harness.load_module("layer_metrics", name)


class Records:
    """Hand-made ``phase`` records: ``add`` returns the id."""

    def __init__(self):
        self.recs, self._id = [], 0

    def add(self, name, start, end, parent=None, thread="MainThread",
            **more):
        self._id += 1
        self.recs.append({"t": 0.0, "ev": "phase", "name": name,
                          "tm": end, "dur_s": end - start, "id": self._id,
                          "parent": parent, "thread": thread, **more})
        return self._id

    def tile(self, tm, tile):
        self.recs.append({"t": 0.0, "ev": "tile", "tm": tm, "tile": tile})


def two_tiles():
    """Two tiles of 100 ms, 1 ms apart under no span: ``io`` 4 ms, then
    ``step`` 95 ms = ``carry`` 5 + ``solve`` 70 (``dispatch`` 10,
    ``wait`` 55, 5 its own) + ``residual`` 10 (``dispatch`` 4) +
    ``record`` 2 + 8 its own; a background ``write`` of 30 ms with a
    ``wait`` of 6 across the boundary."""
    r = Records()
    for k, t0 in enumerate((10.0, 10.1)):
        r.add("io", t0, t0 + 0.004, tile=k)
        s = t0 + 0.005
        step = r.add("step", s, s + 0.095, tile=k)
        r.add("carry", s + 0.001, s + 0.006, step, tile=k)
        solve = r.add("solve", s + 0.006, s + 0.076, step, tile=k)
        r.add("dispatch", s + 0.008, s + 0.018, solve, tile=k,
              prog="sagefit")
        r.add("wait", s + 0.019, s + 0.074, solve, tile=k)
        res = r.add("residual", s + 0.078, s + 0.088, step, tile=k)
        r.add("dispatch", s + 0.082, s + 0.086, res, tile=k,
              prog="residual")
        r.add("record", s + 0.090, s + 0.092, step, tile=k)
        r.tile(s + 0.091, k)
        w = r.add("write", s + 0.089, s + 0.119, None, "async-writer",
                  tile=k, bg=True)
        r.add("wait", s + 0.090, s + 0.096, w, "async-writer", tile=k)
    return r.recs


def fake_run(tmp_path, recs, merged=(), spans=(), prof=(10.0, 10.2),
             slice_tiles=2):
    path = tmp_path / "diag.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    window = types.SimpleNamespace(t_open=10.0, t_drain=10.25)
    busy = [sum(b - a for a, b in m) / NS for m in merged]
    sl = types.SimpleNamespace(
        spans=list(spans), merged=[[list(iv) for iv in m] for m in merged],
        n_devices=max(1, len(merged)),
        busy_s=sum(busy) / max(1, len(busy)))
    return types.SimpleNamespace(
        diag_path=str(path), window=window, slice_tiles=slice_tiles,
        profile={"window_s": prof[1] - prof[0]}, _scopes=sl,
        _prof_t0=prof[0], _prof_t1=prof[1])


def profile_spans(recs):
    """What the profile would hold of ``recs``: name, start and end in
    ns on the profile's clock, a microsecond wider than the record."""
    return [(r["name"], (r["tm"] - r["dur_s"] + OFFSET) * NS - 500,
             (r["tm"] + OFFSET) * NS + 500)
            for r in recs if r["ev"] == "phase"]


def table(out, tag):
    """{row label: first number} of the printed ``[tag]`` rows."""
    rows = {}
    for ln in out.splitlines():
        if (ln.startswith(f"[{tag}] ") and " ms " in ln
                and not ln.startswith("[wait] rows add up")):
            label, rest = ln[len(tag) + 3:].rsplit(" ms ", 1)[0].rsplit(
                None, 1)
            rows[label.strip()] = float(rest)
    return rows


# -- host_serial_ms -----------------------------------------------------------

def test_host_serial_ms_is_io_and_step_less_every_wait(tmp_path, capsys):
    run = fake_run(tmp_path, two_tiles())
    value = metric("host_serial_ms").read(run)
    # io 4 + step 95 - wait 55, both tiles alike
    assert value == pytest.approx(44.0)
    rows = table(capsys.readouterr().out, "host")
    want = {"io": 4.0, "step": 8.0, "step/carry": 5.0, "step/solve": 5.0,
            "step/solve/dispatch": 10.0, "step/solve/wait": 55.0,
            "step/residual": 6.0, "step/residual/dispatch": 4.0,
            "step/record": 2.0, "unspanned": 1.0,
            "(other threads) write": 24.0,
            "(other threads) write/wait": 6.0}
    assert rows.keys() == want.keys()
    for k, v in want.items():
        assert rows[k] == pytest.approx(v, abs=1e-3), k
    # the loop's rows are self times: they add up to io + step
    loop = [v for k, v in rows.items()
            if not k.startswith("(") and k != "unspanned"]
    assert sum(loop) == pytest.approx(99.0, abs=1e-2)


def test_host_serial_ms_counts_the_tiles_whose_step_lies_in_the_window(
        tmp_path, capsys):
    recs = two_tiles()
    run = fake_run(tmp_path, recs)
    run.window.t_open = 10.05       # the first step began before it
    assert metric("host_serial_ms").read(run) == pytest.approx(44.0)
    out = capsys.readouterr().out
    assert "mean over 1 tiles" in out and "before the profiler" not in out
    # the tiles that ended before the profiler's start are said apart
    run = fake_run(tmp_path, recs, prof=(10.15, 10.2))
    assert metric("host_serial_ms").read(run) == pytest.approx(44.0)
    assert ("of which the 1 tiles that ended before the profiler's start: "
            "44.0000") in capsys.readouterr().out


# -- chip_wait_ms -------------------------------------------------------------

def on_profile(*ivals):
    return [((a + OFFSET) * NS, (b + OFFSET) * NS) for a, b in ivals]


def test_chip_wait_ms_charges_each_gap_to_the_innermost_loop_span(
        tmp_path, capsys):
    """Tile 0 of ``two_tiles``: ``io`` 10.000-10.004, ``step``
    10.005-10.100 (``carry`` .006-.011, ``solve`` .011-.081 with
    ``dispatch`` .013-.023 and ``wait`` .024-.079, ``residual``
    .083-.093); the writer's ``write`` 10.094-10.124 is background."""
    recs = two_tiles()
    busy = on_profile(
        (10.020, 10.078),       # the solve program
        (10.0785, 10.0785005),  # 0.5 us after a gap of 0.5 ms in wait
        (10.090, 10.092),       # the residual program
        (10.1045, 10.1046),     # a copy while the loop waits in io
        (10.125, 10.183),       # tile 1's solve program
        (10.183004, 10.19),     # 4 us on: a gap the device's own
    )
    run = fake_run(tmp_path, recs, merged=[busy],
                   spans=profile_spans(recs))
    value = metric("chip_wait_ms").read(run)
    idle = 0.2 - sum(b - a for a, b in busy) / NS
    assert value == pytest.approx(1e3 * idle / 2)
    out = capsys.readouterr().out
    rows = table(out, "wait")
    # milliseconds of the slice's seven gaps by the innermost span of
    # the loop's thread, both tiles together: from the slice's start to
    # the solve program (io, the millisecond under no span, step's own,
    # carry, solve's own, the head of dispatch); 0.5 ms inside wait; a
    # gap SPLIT between wait's tail, solve's own, step's own, residual's
    # own and the head of its dispatch; from the residual program to the
    # copy (the writer's background write and its wait overlap this gap
    # and are charged nothing); on to tile 1's solve program; 4 us the
    # device's own; the residual program to the slice's end
    want = {"io": 4 + 4, "outside every span": 1 + 0.5 + 0.4,
            "step": 1 + 2 + 2 + 3 + 1 + 2 + 3, "step/carry": 5 + 5,
            "step/solve": 2 + 2 + 2 + 1, "step/solve/dispatch": 7 + 10,
            "step/solve/wait": 0.5 + 0.4995 + 1,
            "step/residual": 4 + 1 + 2, "step/residual/dispatch": 3 + 1,
            "step/record": 2 + 2, "gaps under 10 us": 0.004}
    assert rows.keys() == want.keys()
    for k, ms in want.items():
        assert rows[k] == pytest.approx(ms / 2, abs=1e-3), k
    # falling order, then the two rows that are no span's
    listed = list(rows)
    assert listed[-2:] == ["outside every span", "gaps under 10 us"]
    assert [rows[k] for k in listed[:-2]] == sorted(
        (rows[k] for k in listed[:-2]), reverse=True)
    assert sum(rows.values()) == pytest.approx(value, rel=1e-3)
    said = float(out.split("[wait] rows add up to ")[1].split()[0])
    assert said == pytest.approx(value, rel=1e-3)


def test_chip_wait_ms_is_the_mean_over_the_devices(tmp_path, capsys):
    recs = two_tiles()
    a = on_profile((10.020, 10.078), (10.125, 10.183))
    b = on_profile((10.020, 10.060))
    run = fake_run(tmp_path, recs, merged=[a, b],
                   spans=profile_spans(recs))
    value = metric("chip_wait_ms").read(run)
    assert value == pytest.approx(1e3 * (0.2 - (0.116 + 0.040) / 2) / 2)
    rows = table(capsys.readouterr().out, "wait")
    assert sum(rows.values()) == pytest.approx(value, rel=1e-3)


def test_the_clocks_are_tied_by_the_spans_both_hold():
    """Regular tiles: every span of tile 0 also fits a record of tile 1
    in duration, and votes a whole cycle off; the true offset has every
    span's vote."""
    recs = two_tiles()
    offset, votes, n = hostspans.clock_offset(profile_spans(recs), recs)
    assert offset == pytest.approx(OFFSET, abs=2e-6)
    assert n == len([r for r in recs if r["ev"] == "phase"]) == votes
    # one interval in the profile (profile_tiles 1), twenty in the
    # records, a little jitter from one to the next
    r = Records()
    for k in range(20):
        t0 = 5.0 + 0.6 * k + 1e-4 * (k * k % 7)
        step = r.add("step", t0, t0 + 0.55 + 2e-4 * (k % 5), tile=k)
        r.add("dispatch", t0 + 0.01, t0 + 0.02 + 1e-4 * (k % 3), step)
        r.add("wait", t0 + 0.03, t0 + 0.5 + 1e-4 * (k % 4), step)
    one = [s for s in profile_spans(r.recs)
           if 5.0 + 0.6 * 7 - 0.1 <= s[1] / NS - OFFSET < 5.0 + 0.6 * 8 - 0.1]
    offset, votes, n = hostspans.clock_offset(one, r.recs)
    assert (votes, n) == (3, 3)
    assert offset == pytest.approx(OFFSET, abs=2e-6)
    assert hostspans.clock_offset([("solve", 0, 1e6)], r.recs) is None


def test_innermost_and_charge():
    pieces = hostspans.innermost([
        (0.0, 10.0, "a"), (1.0, 4.0, "a/b"), (2.0, 3.0, "a/b/c"),
        (6.0, 7.0, "a/d"), (12.0, 13.0, "e")])
    assert pieces == [
        (0.0, 1.0, "a"), (1.0, 2.0, "a/b"), (2.0, 3.0, "a/b/c"),
        (3.0, 4.0, "a/b"), (4.0, 6.0, "a"), (6.0, 7.0, "a/d"),
        (7.0, 10.0, "a"), (12.0, 13.0, "e")]
    got = hostspans.charge([(2.5, 6.5), (9.0, 12.5), (20.0, 21.0)], pieces)
    assert got == pytest.approx({"a/b/c": 0.5, "a/b": 1.0, "a": 3.0,
                                 "a/d": 0.5, "e": 0.5, None: 3.0})


def test_on_a_program_whose_records_carry_no_id_both_report_nothing(
        tmp_path, capsys):
    """The parent of the PR that brought the metrics: the same names, no
    ``id``, ``parent`` or ``thread``.  Nothing is raised."""
    recs = [{k: v for k, v in r.items()
             if k not in ("id", "parent", "thread")} for r in two_tiles()]
    run = fake_run(tmp_path, recs, merged=[on_profile((10.02, 10.07))],
                   spans=profile_spans(recs))
    assert metric("host_serial_ms").read(run) is None
    assert metric("chip_wait_ms").read(run) is None
    out = capsys.readouterr().out
    assert "[host] no step span" in out and "[wait] no step span" in out
    # and with no profile at all
    run = fake_run(tmp_path, two_tiles())
    run.profile, run._scopes = None, None
    assert metric("chip_wait_ms").read(run) is None
    # spans on the profile that fit no record: the clocks are not tied
    run = fake_run(tmp_path, two_tiles(),
                   merged=[on_profile((10.02, 10.07))],
                   spans=[("step", 0.0, 1.0)])
    assert metric("chip_wait_ms").read(run) is None
    assert "could not be tied" in capsys.readouterr().out


# -- the manifest -------------------------------------------------------------

def test_the_two_entries_are_the_lists_last_and_for_all_five_cells():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    assert [w["name"] for w in man["workloads"]] == CELLS
    last = man["per_layer"][-2:]
    assert [m["name"] for m in last] == NEW
    for m, source in zip(last, ("program_span", "device_trace")):
        mod = metric(m["name"])
        assert m == {"name": mod.NAME, "unit": "ms", "better": "lower",
                     "source": source, "layer": "tile loop and overlap",
                     "moves": mod.MOVES, "workloads": CELLS}
        assert mod.UNIT == "ms" and mod.LAYER == m["layer"]
    assert (metric("host_serial_ms").MOVES, metric("chip_wait_ms").MOVES) \
        == ("tile_s.p50", "vis_per_s")
    for cell in CELLS:
        names = [m["name"] for m in harness.Cell(cell).metrics("per_layer")]
        assert names[-2:] == NEW


@pytest.mark.parametrize("module", ["test_subtract", "test_t120",
                                    "test_consensus"])
def test_what_pins_a_cells_list_by_place_holds_less_the_new_entries(
        module, monkeypatch):
    """``test_the_cell_is_files_and_entries`` of these three holds a
    cell's whole per-layer list, and one of them the manifest's last
    entries: each fails from the first entry a later PR appends, and
    only a ``benchmark`` PR may edit them.  What they guard (every entry
    that was there, in its place, for its cells; the configuration) is
    held here: each runs whole on the manifest less the two entries this
    file's PR appended."""
    load = harness.load_json

    def less_the_new(*parts):
        out = load(*parts)
        if parts[-1] == "BENCHMARK.json":
            out["per_layer"] = [m for m in out["per_layer"]
                                if m["name"] not in NEW]
        return out

    monkeypatch.setattr(harness, "load_json", less_the_new)
    # by path: ``tests/`` has a ``test_consensus`` of its own
    spec = importlib.util.spec_from_file_location(
        "pinned_" + module, os.path.join(HERE, module + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.test_the_cell_is_files_and_entries()


# -- the rehearsal cells, traced ----------------------------------------------

@pytest.mark.parametrize("cells, workload, seconds, paths", [
    ("cells.json", "cal-tiny", "1",
     ("step/solve/dispatch", "step/solve/wait", "step/residual/carry",
      "step/submit", "step/record")),
    ("cells.json", "predict-tiny", "1",
     ("step/stage", "step/predict", "step/fetch/wait", "step/write")),
    ("consensus-cells.json", "admm-tiny", "1",
     ("step/carry", "step/solve/dispatch", "step/solve/wait", "step/fetch",
      "step/primal", "step/record", "step/residual/dispatch")),
])
def test_a_rehearsal_cell_prints_both_tables(capsys, cells, workload,
                                             seconds, paths):
    """One tiny cell for each of the three loops (``cal-t120-tiny`` and
    ``subtract-tiny`` run the first two's loops, and are traced by their
    own files)."""
    import run as runner
    rc = runner.main(["--cells", os.path.join(HERE, "rehearsal", cells),
                      "--workload", workload, "--seed", str(2 ** 31 + 5),
                      "--seconds", seconds, "--trace", "1", "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    for name in NEW:
        assert line["metrics"][name]["unit"] == "ms"
        assert line["metrics"][name]["value"] > 0
    host, wait = table(out, "host"), table(out, "wait")
    assert {"io", "step", "unspanned", *paths} <= set(host), host
    assert {"outside every span", "gaps under 10 us"} <= set(wait)
    assert sum(wait.values()) == pytest.approx(
        line["metrics"]["chip_wait_ms"]["value"], rel=0.01)
    # the same seconds as device_idle_pct, in milliseconds a tile
    dev = line["device"]
    assert line["metrics"]["chip_wait_ms"]["value"] == pytest.approx(
        line["metrics"]["device_idle_pct"]["value"] / 100
        * 1e3 * dev["window_s"] / line_tiles(out), rel=1e-6)


def line_tiles(out):
    """The tiles begun in the slice, as the ``[wait]`` line says them."""
    ln = next(ln for ln in out.splitlines()
              if ln.startswith("[wait] rows add up"))
    return int(ln.split(" a tile over ")[1].split()[0])
