"""The four metrics that follow a blocked loop into the thread it is
blocked on (``writer_ms``, ``reader_ms``, ``write_queue_ms``,
``loop_blocked_ms``; ``threadspans.py``): on a hand-made forest (rows add
up to the value, a ``submit`` is charged to the writer's innermost span
and the rest to ``that thread idle``, ``None`` without ``cause``), on the
``--diag`` files the suite's traced tiny runs leave behind (no tiny run
is made here), and their entries in the manifest with the two ``.hyb``
twins: the LAST six of ``per_layer``.  The cases of this suite that pin a
list by place and fail from the first entry appended are run here on the
manifest less the six, case for case.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_thread_spans.py -q
"""

import contextlib
import io
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
import threadspans  # noqa: E402
import test_host_spans as ths   # noqa: E402
import test_hybrid as hyb       # noqa: E402

FOUR = ["writer_ms", "reader_ms", "write_queue_ms", "loop_blocked_ms"]
TWINS = {"host_serial_ms.hyb": ("host_serial_ms", "program_span"),
         "chip_wait_ms.hyb": ("chip_wait_ms", "device_trace")}
#: in the manifest's order
NEW = FOUR + list(TWINS)
CELLS = ["cal-m8x3", "predict-m8x128", "admm-f4-mesh", "cal-t120",
         "subtract-m8x128", "admm-f8-fold", "cal-m16x3-hybrid",
         "dosage-beam", "predict-extended"]
TAGS = {"writer_ms": "writer", "reader_ms": "reader",
        "write_queue_ms": "queue", "loop_blocked_ms": "pace"}


def metric(name):
    return harness.load_module("layer_metrics", name)


# -- a hand-made forest -------------------------------------------------------

def forest():
    """Two tiles of 100 ms (``t0`` = 10.0, 10.1; ``s`` = ``t0`` + 5 ms).

    The loop: ``io`` 4 ms (its tile's ``read`` as cause), ``step`` 95 ms
    with ``solve`` 70 (``wait`` 55), ``submit`` 10 ms from ``s`` + 80
    (blocked: the writer is still in the tile before's job) and a second
    ``submit`` of 0.5 ms from ``s`` + 90.5.

    The writer: tile k's ``write`` of 90 ms from ``s`` + 96 = ``wait`` 5,
    ``convert`` 10, ``put`` 74 (``keep`` 20, ``savez`` 50, ``replace``
    2, 2 its own), 1 its own; then ``solutions`` 2 ms.  The jobs of the
    tile before run to ``s`` + 88.5: the first ``submit`` overlaps their
    ``savez`` 3 ms, ``replace`` 2, ``put`` 1, ``solutions`` 2, and 2 ms of
    nothing.

    The reader: tile k's ``read`` of 40 ms from ``t0`` - 98 = ``load``
    19, ``stage`` 18 (``copy`` 13), 3 its own; tile k + 1's begins 2 ms
    into tile k's ``io``: 1 ms in ``read``, 1 in ``load``, 2 of
    nothing."""
    r = ths.Records()
    W, R = "async-writer", "prefetch-read"

    def read(t0, tile):
        a = t0 - 0.098
        rd = r.add("read", a, a + 0.040, None, R, tile=tile, bg=True)
        r.add("load", a + 0.001, a + 0.020, rd, R, tile=tile)
        st = r.add("stage", a + 0.021, a + 0.039, rd, R, tile=tile)
        r.add("copy", a + 0.022, a + 0.035, st, R, tile=tile)
        return rd, a + 0.040

    def write(s, tile, **cause):
        a = s + 0.096
        w = r.add("write", a, a + 0.090, None, W, tile=tile, bg=True,
                  **cause)
        r.add("wait", a + 0.001, a + 0.006, w, W, tile=tile)
        r.add("convert", a + 0.006, a + 0.016, w, W, tile=tile)
        p = r.add("put", a + 0.016, a + 0.090, w, W, tile=tile)
        r.add("keep", a + 0.017, a + 0.037, p, W, tile=tile)
        r.add("savez", a + 0.037, a + 0.087, p, W, tile=tile)
        r.add("replace", a + 0.087, a + 0.089, p, W, tile=tile)

    write(10.005 - 0.1, -1)             # the tile before the window's
    r.add("solutions", 10.005 + 0.0865, 10.005 + 0.0885, None, W, tile=-1)
    for k, t0 in enumerate((10.0, 10.1)):
        rd, put_at = read(t0, k)
        r.add("io", t0, t0 + 0.004, tile=k, cause=rd,
              queued_s=t0 - put_at)
        s = t0 + 0.005
        step = r.add("step", s, s + 0.095, tile=k)
        solve = r.add("solve", s + 0.006, s + 0.076, step, tile=k)
        r.add("wait", s + 0.019, s + 0.074, solve, tile=k)
        sub = r.add("submit", s + 0.080, s + 0.090, step, tile=k)
        sub2 = r.add("submit", s + 0.0905, s + 0.0910, step, tile=k)
        r.tile(s + 0.093, k)
        write(s, k, cause=sub, queued_s=0.016)
        r.add("solutions", s + 0.1865, s + 0.1885, None, W, tile=k,
              cause=sub2, queued_s=0.096)
    read(10.2, 2)                       # read ahead, not yet taken
    return r.recs


def read(tmp_path, capsys, recs, name):
    run = ths.fake_run(tmp_path, recs)
    value = metric(name).read(run)
    return value, capsys.readouterr().out


def test_writer_ms_is_the_roots_less_every_wait(tmp_path, capsys):
    value, out = read(tmp_path, capsys, forest(), "writer_ms")
    assert value == pytest.approx(90.0 - 5.0 + 2.0)
    rows = ths.table(out, "writer")
    want = {"write": 1.0, "write/wait": 5.0, "write/convert": 10.0,
            "write/put": 2.0, "write/put/keep": 20.0,
            "write/put/savez": 50.0, "write/put/replace": 2.0,
            "solutions": 2.0}
    assert rows.keys() == want.keys()
    for k, v in want.items():
        assert rows[k] == pytest.approx(v, abs=1e-3), k
    # the rows add up to the roots, and less the wait row to the value
    assert sum(rows.values()) == pytest.approx(92.0, abs=1e-2)
    assert sum(rows.values()) - rows["write/wait"] == pytest.approx(
        value, abs=1e-2)
    assert "the means add up to 92.0000 ms, the roots (2.00 a tile)" in out
    # the job of the tile before the window's is no window tile's
    assert "mean over 2 tiles" in out


def test_reader_ms_is_the_tiles_own_read(tmp_path, capsys):
    value, out = read(tmp_path, capsys, forest(), "reader_ms")
    assert value == pytest.approx(40.0)
    rows = ths.table(out, "reader")
    want = {"read": 3.0, "read/load": 19.0, "read/stage": 5.0,
            "read/stage/copy": 13.0, "arrival_wait (not counted)": 0.0}
    assert rows.keys() == want.keys()
    for k, v in want.items():
        assert rows[k] == pytest.approx(v, abs=1e-3), k
    assert sum(rows.values()) == pytest.approx(value, abs=1e-2)


def test_reader_ms_says_the_arrival_wait_apart(tmp_path, capsys):
    recs = forest()
    r = ths.Records()
    r._id = 1000
    r.add("arrival_wait", 10.05, 10.07, None, "prefetch-read", bg=True)
    value, out = read(tmp_path, capsys, recs + r.recs, "reader_ms")
    assert value == pytest.approx(40.0)
    assert ths.table(out, "reader")["arrival_wait (not counted)"] \
        == pytest.approx(10.0, abs=1e-3)


def test_write_queue_ms_is_the_mean_queued_s_of_the_windows_jobs(
        tmp_path, capsys):
    value, out = read(tmp_path, capsys, forest(), "write_queue_ms")
    assert value == pytest.approx((16.0 + 96.0) / 2)
    assert "of 4 writer-job roots (2.00 a tile)" in out
    assert "max 96.0000 ms" in out and "100.0 % of them" in out


def test_pace_charges_a_submit_to_the_writers_innermost_span(
        tmp_path, capsys):
    value, out = read(tmp_path, capsys, forest(), "loop_blocked_ms")
    assert value == pytest.approx(4.0 + 10.0 + 0.5)
    rows = ths.table(out, "pace")
    want = {"write/put/savez": 3.0, "write/put/replace": 2.0,
            "write/put": 1.0, "solutions": 2.0, "read": 1.0,
            "read/load": 1.0, "that thread idle": 2.0 + 0.5 + 2.0}
    assert rows.keys() == want.keys()
    for k, v in want.items():
        assert rows[k] == pytest.approx(v, abs=1e-3), k
    listed = list(rows)
    assert listed[-1] == "that thread idle"
    assert [rows[k] for k in listed[:-1]] == sorted(
        (rows[k] for k in listed[:-1]), reverse=True)
    assert sum(rows.values()) == pytest.approx(value, rel=1e-3)
    said = float(out.split("[pace] rows add up to ")[1].split()[0])
    assert said == pytest.approx(value, rel=1e-3)
    # the verdict: io 4 + step 95 - wait 55 - blocked 14.5 is the loop's
    verdict = out.strip().splitlines()[-1]
    assert verdict.startswith("[verdict] cycle 100.0000 ms")
    for needle in ("the loop's own 29.5000 ms", "on the writer 10.5000",
                   "on the reader 4.0000", "wait (the device) 55.0000",
                   "writer_ms 87.0000 = 87.0 % of the cycle",
                   "reader_ms 40.0000 = 40.0 %"):
        assert needle in verdict, needle


def test_the_window_is_the_tiles_whose_step_lies_in_it(tmp_path, capsys):
    run = ths.fake_run(tmp_path, forest())
    run.window.t_open = 10.05       # the first step began before it
    assert metric("writer_ms").read(run) == pytest.approx(87.0)
    assert "mean over 1 tiles" in capsys.readouterr().out
    assert metric("loop_blocked_ms").read(run) == pytest.approx(14.5)


@pytest.mark.parametrize("name", FOUR)
def test_without_a_cause_each_reports_nothing_in_one_line(
        tmp_path, capsys, name):
    """The parent of the PR that brought them: the same spans, no
    ``cause``, no ``queued_s``.  And a program with no ``id`` at all."""
    recs = [{k: v for k, v in r.items() if k not in ("cause", "queued_s")}
            for r in forest()]
    value, out = read(tmp_path, capsys, recs, name)
    assert value is None
    assert out.strip() == (f"[{TAGS[name]}] no span with a cause in the "
                           f"window's records: nothing to follow to "
                           f"another thread")
    bare = [{k: v for k, v in r.items()
             if k not in ("id", "parent", "thread")} for r in recs]
    value, out = read(tmp_path, capsys, bare, name)
    assert value is None and len(out.strip().splitlines()) == 1


# -- the diag files the suite's traced tiny runs leave behind -----------------

#: the paths each tiny cell's records held on the parent of PR 53
PARENT_PATHS = {
    "cal-tiny": {
        "io", "read", "read/stage", "step", "step/carry", "step/solve",
        "step/solve/dispatch", "step/solve/wait", "step/residual",
        "step/residual/carry", "step/residual/dispatch", "step/submit",
        "step/record", "write", "write/wait"},
    "admm-tiny": {
        "io", "read", "read/stage", "step", "step/carry", "step/solve",
        "step/solve/dispatch", "step/solve/wait", "step/fetch",
        "step/record", "step/residual", "step/residual/carry",
        "step/residual/dispatch", "step/submit", "step/primal", "write",
        "write/wait"},
    "predict-tiny": {
        "io", "read", "read/stage", "step", "step/predict", "step/fetch",
        "step/fetch/wait", "step/submit", "write"},
}
#: what the window's writer does, by the names PR 53 gave it
WRITES = {"write", "write/convert", "write/put", "write/put/keep",
          "write/put/savez", "write/put/replace"}


@pytest.fixture(params=sorted(PARENT_PATHS))
def left_behind(request):
    """(cell, a run that reads the ``--diag`` file the suite's last
    traced run of the tiny cell left in its work directory).  The whole
    file is the window: a rehearsal's warm-up compiles, its spans are
    spans all the same."""
    cell = request.param
    path = os.path.join(BENCH, ".work", cell, "diag.jsonl")
    if not os.path.exists(path):
        pytest.skip(f"no traced run of {cell} has left {path}: this case "
                    f"reads what the suite's other files ran (no tiny "
                    f"run is made here)")
    return cell, types.SimpleNamespace(
        diag_path=path,
        window=types.SimpleNamespace(t_open=0.0, t_drain=float("inf")))


def test_a_tiny_cells_records_give_all_four_and_their_tables_add_up(
        left_behind):
    cell, run = left_behind
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        values = {name: metric(name).read(run) for name in FOUR}
    out = out.getvalue()
    assert all(v is not None and v >= 0 for v in values.values()), out
    for tag, name in (("writer", "writer_ms"), ("reader", "reader_ms")):
        rows = ths.table(out, tag)
        waits = sum(v for k, v in rows.items() if k.endswith("/wait"))
        apart = rows.pop("arrival_wait (not counted)", 0.0)
        # the printed medians are not additive; the means are, and say so
        line = next(ln for ln in out.splitlines()
                    if ln.startswith(f"[{tag}] the means add up to "))
        roots = float(line.split("add up to ")[1].split()[0])
        means = sum(float(ln.rsplit("mean ", 1)[1])
                    for ln in out.splitlines()
                    if ln.startswith(f"[{tag}] ") and ", mean " in ln
                    and "add up" not in ln)
        assert means == pytest.approx(roots, rel=0.01), tag
        assert values[name] <= roots + 1e-3 and waits >= 0 and apart >= 0
    assert WRITES <= set(ths.table(out, "writer")), out
    assert {"read", "read/load", "read/stage/pack", "read/stage/copy"} \
        <= set(ths.table(out, "reader")), out
    pace = ths.table(out, "pace")
    assert list(pace)[-1] == "that thread idle"
    assert sum(pace.values()) == pytest.approx(values["loop_blocked_ms"],
                                               rel=0.01, abs=1e-3)
    assert "[verdict] cycle " in out and "% of the cycle" in out
    # what bubble_ms.* times from the tile record, from the spans
    bubble = [r["bubble_s"] for r in hostspans.phase_records(run)
              if r["ev"] == "tile" and "bubble_s" in r]
    assert abs(len(bubble) - run._threads.n) <= 1    # the drain's tile
    assert values["loop_blocked_ms"] == pytest.approx(
        1e3 * sum(bubble) / len(bubble), abs=0.5)


def test_a_tiny_cells_causes_lie_in_its_file_on_another_thread(left_behind):
    cell, run = left_behind
    spans = hostspans.Spans(hostspans.phase_records(run))
    caused = [r for r in spans.by_id.values() if "cause" in r]
    assert caused
    for r in caused:
        up = spans.by_id[r["cause"]]
        assert up["thread"] != r["thread"] and r["queued_s"] >= 0
        assert up["name"] in ("submit", "read")
    # every path the parent's records held is there, under its name
    have = {spans.path(r) for r in spans.by_id.values()}
    assert PARENT_PATHS[cell] <= have, PARENT_PATHS[cell] - have
    if cell == "admm-tiny":
        tiles = [r for r in hostspans.phase_records(run)
                 if r["ev"] == "tile"]
        assert tiles and not any("rho_mean" in r for r in tiles)


# -- the manifest -------------------------------------------------------------

def test_the_six_entries_are_the_lists_last():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    assert [w["name"] for w in man["workloads"]] == CELLS
    last = man["per_layer"][-6:]
    assert [m["name"] for m in last] == NEW
    for m in last[:4]:
        mod = metric(m["name"])
        assert m == {"name": mod.NAME, "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "tile loop and overlap",
                     "moves": "tile_s.p50", "workloads": CELLS}
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
            "ms", m["layer"], "tile_s.p50")
    for m in last[4:]:
        was, source = TWINS[m["name"]]
        mod, old = metric(m["name"]), metric(was)
        assert m == {"name": mod.NAME, "unit": old.UNIT, "better": "lower",
                     "source": source, "layer": old.LAYER,
                     "moves": old.MOVES, "workloads": ["cal-m16x3-hybrid"]}
        assert mod._WAS.__file__ == old.__file__
        assert mod.read.__code__.co_names == ("_WAS", "read")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_the_four_behind_what_it_reported(cell):
    """By name: a cell's per-layer list is what it was on the manifest
    less this PR's entries, then the four (and the hybrid cell's twins),
    in the manifest's order."""
    names = [m["name"] for m in harness.Cell(cell).metrics("per_layer")]
    man = harness.load_json(ROOT, "BENCHMARK.json")
    was = [m["name"] for m in man["per_layer"]
           if m["name"] not in NEW and cell in m.get("workloads", [cell])]
    assert names == was + FOUR + (
        list(TWINS) if cell == "cal-m16x3-hybrid" else [])
    # every cell reports the end-to-end metric the four move
    assert "tile_s.p50" in [
        m["name"] for m in harness.Cell(cell).metrics("end_to_end")]


def test_a_tiny_cell_reports_as_its_real_cell():
    for cells, tiny, real in (
            ("cells.json", "cal-tiny", "cal-m8x3"),
            ("cells.json", "predict-tiny", "predict-m8x128"),
            ("consensus-cells.json", "admm-tiny", "admm-f4-mesh")):
        cell = harness.Cell(tiny, harness.load_json(
            HERE, "rehearsal", cells))
        assert cell.reports_as == real
        assert [m["name"] for m in cell.metrics("per_layer")][-4:] == FOUR


#: the cases of this suite that pin a list by place and fail from the
#: first of this PR's entries: (module, case, arguments before
#: ``monkeypatch``, whether it takes ``monkeypatch``)
PINNED = [
    ("test_host_spans", "test_what_pins_a_cells_list_by_place_holds_less_"
     "the_new_entries", ("test_t120",), True),
    ("test_host_spans", "test_what_pins_a_cells_list_by_place_holds_less_"
     "the_new_entries", ("test_consensus",), True),
    *[("test_fold", "test_an_older_cells_per_layer_list_is_unchanged",
       (cell,), False) for cell in CELLS[:5]],
    ("test_fold", "test_what_pins_lists_by_place_holds_less_what_was_"
     "appended_since", ("test_t120",), True),
    ("test_fold", "test_what_pins_lists_by_place_holds_less_what_was_"
     "appended_since", ("test_consensus",), True),
    ("test_hybrid", "test_the_cell_is_files_and_entries_held_by_name", (),
     False),
    ("test_hybrid", "test_what_older_cells_pin_by_place_holds_less_"
     "everything_since", ("test_t120",), True),
    ("test_hybrid", "test_what_older_cells_pin_by_place_holds_less_"
     "everything_since", ("test_consensus",), True),
    ("test_beam_cell", "test_the_cell_is_files_and_entries_held_by_name",
     (), False),
    ("test_extended", "test_the_cell_is_files_and_entries_held_by_name",
     (), False),
    ("test_extended", "test_the_older_cells_lists_are_as_pr48_held_them",
     (), False),
]


def _pr48_cases():
    ext = hyb.load_test_module("test_extended")
    mark = next(m for m in getattr(
        ext.test_what_pr48_pins_by_place_holds_less_this_prs_entries,
        "pytestmark") if m.name == "parametrize")
    return dict(zip(mark.kwargs["ids"], mark.args[1]))


@pytest.mark.parametrize(
    "module, case, args, patched", PINNED,
    ids=[f"{m}-{c[5:40]}-{'-'.join(a) or 'whole'}" for m, c, a, _ in PINNED])
def test_what_pins_a_list_by_place_holds_less_this_prs_entries(
        module, case, args, patched, monkeypatch):
    """Each of these fails on the manifest as it is, from the first
    entry this PR appended for its cell (``tests/test_benchmarks_suite.py``:
    ``OVERTAKEN``), and only a ``benchmark`` PR may edit it.  Each runs
    whole here on the manifest less this PR's six entries: what it
    guards stays guarded, case for case."""
    hyb.manifest_less(monkeypatch, NEW)
    fn = getattr(hyb.load_test_module(module), case)
    fn(*args, monkeypatch) if patched else fn(*args)


@pytest.mark.parametrize("case", ["fold-cell", "fold-pr40", "subtract",
                                  "older-lists-pr42", "older-lists-pr44"])
def test_what_pr51_runs_less_its_own_holds_less_this_prs_too(
        case, monkeypatch):
    """``test_extended.py`` runs what ``test_beam_cell.py`` pins by
    place on the manifest less PR 51's entries; five of its six cases
    end in a cell's whole list and fail from this PR's four.  Here each
    runs less this PR's six as well."""
    hyb.manifest_less(monkeypatch, NEW)
    ext = hyb.load_test_module("test_extended")
    inner, args = _pr48_cases()[case]
    ext.test_what_pr48_pins_by_place_holds_less_this_prs_entries(
        inner, args, monkeypatch)
