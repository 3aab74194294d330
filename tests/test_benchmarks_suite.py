"""The benchmark's own tests (``benchmarks/tests``: the yardstick, the
tiny rehearsal cells, the controls that have to come out not correct)
are not collected by this suite: they build whole tiny runs, share one
work directory and take minutes.  This runs them ONCE, in a process of
its own with a time limit of its own, and reports one case here for
every test there, under that test's name and with that test's message,
so that a PR's count of passes guards the harness test by test.

The names come from a ``--collect-only`` call in a process of its own
(nothing of ``benchmarks/tests`` is imported here), the results from
the JUnit file the one run writes.  All cases of this file land on one
worker under ``--dist loadfile``, so the module's fixture runs once."""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = "benchmarks/tests"
# 140 s alone (PR 30), 520-640 s beside 5 workers (PR 40); 1001 s beside 5
# workers with PR 44's test_hybrid.py at six tiny runs, which was cut to
# three for it (the whole suite has 1470 s).  PR 45: 827 s alone on the
# parent's tree in this sandbox, and beside five workers anything from
# 1150 s to past 1250 s on the same tree (two whole runs here; the
# driver's whole tier-1 run of PR 45's tree took 915 s): this run is
# tier-1's longest chain, so its limit leaves a cold run that room and
# ``NOT_RUN`` keeps the one tiny run known to fail out of it
LIMIT_S = 1350
PYTEST = [sys.executable, "-m", "pytest", SUITE, "-q",
          "-p", "no:cacheprovider", "-p", "no:randomly"]


def _env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}       # not a worker of ours
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _collect():
    """(node ids below SUITE, the call's output where it failed)."""
    run = subprocess.run(PYTEST + ["--collect-only"], cwd=ROOT, env=_env(),
                         timeout=LIMIT_S, text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    ids = [ln[len(SUITE) + 1:] for ln in run.stdout.splitlines()
           if ln.startswith(SUITE + "/") and "::" in ln]
    return ids, ("" if run.returncode == 0 else run.stdout[-4000:])


IDS, COLLECT_ERROR = _collect()

# A test of the benchmark that the benchmark's own rule has overtaken, and
# that only a PR of kind ``benchmark`` may edit.  It holds the LAST entry
# of ``per_layer`` equal to ``tcg_trips``, and a PR that adds entries has
# to put them at the end of the list (PR 34 was refused with them before
# it), so it fails from the first entry appended.  Its failure is reported
# as expected; what it guards is held by name in
# ``test_tcg_trips_entry_is_unchanged`` below.  PERF.md section 7 has the
# edit that lets this go.
_PR53 = ("the cell's per-layer list has grown by writer_ms, reader_ms, "
         "write_queue_ms and loop_blocked_ms (PR 53): new entries go at "
         "the end, for every cell")
OVERTAKEN = {
    "test_tcg_trips.py::test_entry_is_appended_for_the_one_cell":
        "per_layer[-1] is no longer tcg_trips: new entries go at the end",
    # PR 40 appended two entries that list every cell: these three hold a
    # cell's WHOLE per-layer list (one of them the manifest's last
    # entries too) and fail from the first entry appended for their cell.
    # benchmarks/tests/test_host_spans.py runs each of them whole on the
    # manifest less the two new entries
    # (test_what_pins_a_cells_list_by_place_holds_less_the_new_entries),
    # so what they guard stays guarded, case for case.
    **{f"{module}.py::test_the_cell_is_files_and_entries":
       "the cell's per-layer list has grown by host_serial_ms and "
       "chip_wait_ms: new entries go at the end, for every cell"
       for module in ("test_subtract", "test_t120", "test_consensus")},
    # PR 42 appended a cell, a configuration and nine entries that list
    # the new cell alone.  These two pin lists by PLACE and fail from the
    # first entry appended after PR 40's two: the first holds ``workloads``
    # equal to the five cells there were and ``per_layer[-2:]`` equal to
    # PR 40's entries; the second runs test_subtract.py's case above on
    # the manifest less PR 40's two entries only, and that case holds the
    # LAST of ``configs``, ``workloads`` and ``per_layer``.  What they
    # guard is held by name in benchmarks/tests/test_fold.py: PR 40's two
    # entries still list the five older cells and only PR 42's follow
    # them, every older cell's own per-layer list is unchanged, and the
    # three place-pinning cases run whole on the manifest less everything
    # appended since.  PERF.md section 7, Open after PR 40 (1), asks the
    # next ``benchmark`` issue to hold these lists by name.
    "test_host_spans.py::"
    "test_the_two_entries_are_the_lists_last_and_for_all_five_cells":
        "workloads is no longer the five cells and per_layer[-2:] no "
        "longer PR 40's two: PR 42's cell and entries go at the end",
    "test_host_spans.py::test_what_pins_a_cells_list_by_place_holds_less_"
    "the_new_entries[test_subtract]":
        "test_subtract.py holds the LAST configuration, cell and entries "
        "of the manifest, which are PR 42's now",
    # PR 44 appended a cell, a configuration and eight entries that list
    # the new cell alone.  test_fold.py holds PR 42's as the LAST of
    # their lists and ``workloads`` as six cells (three cases), and runs
    # test_subtract.py's case on the manifest less PR 40's and PR 42's
    # entries only (the fourth).  benchmarks/tests/test_hybrid.py runs
    # each of the first three whole on the manifest less this PR's cell,
    # configuration and entries
    # (test_what_pr42_pins_by_place_holds_less_this_prs_entries), runs
    # test_subtract.py's, test_t120.py's and test_consensus.py's case
    # less everything appended since they were written
    # (test_what_older_cells_pin_by_place_holds_less_everything_since),
    # and holds every older cell's list, PR 40's and PR 42's entries and
    # the order of cells and configurations by name
    # (test_the_older_cells_lists_are_as_pr42_held_them).
    **{f"test_fold.py::{case}":
       "PR 42's cell, configuration and nine entries are no longer the "
       "LAST of their lists, nor the cells six: PR 44's go at the end"
       for case in (
           "test_the_cell_is_files_and_entries",
           "test_the_configuration_is_the_sources_at_eight_subbands",
           "test_pr40s_entries_still_list_the_older_cells_and_only_ours_"
           "follow")},
    "test_fold.py::test_what_pins_lists_by_place_holds_less_what_was_"
    "appended_since[test_subtract]":
        "test_subtract.py holds the LAST configuration, cell and entries "
        "of the manifest, which are PR 44's now",
    # PR 45 keeps a hybrid tile on the [tilesz, nbase] planes: the tiny
    # cell's records say ``periodic`` three times and
    # ``flat_row_passes.hyb`` reads 0, which is what that reader was
    # written to see ("a program that keeps the planes inside a chunk
    # brings this to 0").  This case pinned the slow state (``> 0`` and
    # the line ``sweep_rows flat, assemble_rows generic, refine_rows
    # flat``); everything else it guards is held by
    # ``tests/test_hybrid_cell.py``, and it is one of ``NOT_RUN`` below.
    # PERF.md section 7 has the edit for the next ``benchmark`` issue.
    "test_hybrid.py::test_sound_tiny_cell_is_correct_and_reports_the_eight":
        "flat_row_passes.hyb is 0 and the layouts are periodic: a hybrid "
        "chunk is a run of whole timeslots and stays on planes (PR 45)",
    # PR 46 overlaps the simulation loop: the reader thread stages, the
    # ordered writer writes, and ``bubble_s`` is what the loop's thread
    # was blocked on the two.  These two cases pinned the synchronous
    # loop: the first looks for ``step/stage`` and ``step/write`` among
    # the LOOP thread's paths (they are ``(other threads) read/stage``
    # and ``(other threads) write`` now), the second ends in
    # ``bubble_ms.predict`` within half of ``io_ms.predict`` (the loop
    # no longer stands in the read and the write).  Everything else they
    # guard is held by ``tests/test_predict_cell.py``; both still run,
    # and fail in that one assertion.  PERF.md section 7 has the edit for
    # the next ``benchmark`` issue.
    "test_host_spans.py::test_a_rehearsal_cell_prints_both_tables"
    "[cells.json-predict-tiny-1-paths1]":
        "step/stage and step/write are the reader's and the writer's: "
        "the simulation loop stages ahead and writes behind (PR 46)",
    "test_scopes.py::test_tiny_cell_traced_end_to_end[predict-tiny]":
        "bubble_ms.predict is the loop thread's blocked seconds, no "
        "longer io + write = io_ms.predict (PR 46)",
    # PR 48 appended a cell, a configuration and fifteen entries that list
    # the new cell alone.  test_hybrid.py holds PR 44's as the LAST of
    # their lists (``configs[-2:]``, ``workloads``: one case), runs
    # test_fold.py's three place-pinning cases on the manifest less
    # PR 44's entries only (three), and test_subtract.py's less everything
    # up to PR 44's (one).  benchmarks/tests/test_beam_cell.py runs each
    # of the five whole on the manifest less this PR's cell,
    # configuration and entries
    # (test_what_pr44_pins_by_place_holds_less_this_prs_entries), and
    # holds every older cell's list, PR 40's, PR 42's and PR 44's entries
    # and the order of cells and configurations by name
    # (test_the_older_cells_lists_are_as_pr44_held_them).
    **{"test_hybrid.py::test_what_pr42_pins_by_place_holds_less_this_prs_"
       f"entries[{case}]":
       "PR 44's cell, configuration and eight entries are no longer the "
       "LAST of their lists: PR 48's go at the end"
       for case in (
           "test_the_cell_is_files_and_entries",
           "test_the_configuration_is_the_sources_at_eight_subbands",
           "test_pr40s_entries_still_list_the_older_cells_and_only_ours_"
           "follow")},
    "test_hybrid.py::test_what_older_cells_pin_by_place_holds_less_"
    "everything_since[test_subtract]":
        "test_subtract.py holds the LAST configuration, cell and entries "
        "of the manifest, which are PR 48's now",
    "test_hybrid.py::test_the_older_cells_lists_are_as_pr42_held_them":
        "configs[-2:] and workloads end with PR 44's no longer: PR 48's "
        "cell and configuration go at the end",
    # PR 51 appended a cell, a configuration and eight entries that list
    # the new cell alone.  test_beam_cell.py holds PR 48's as the LAST of
    # their lists (``workloads``, ``configs[-3:]``, the counts of eight:
    # one case) and runs the five cases test_hybrid.py pins by place on
    # the manifest less PR 48's entries only.
    # benchmarks/tests/test_extended.py runs each of the six whole on the
    # manifest less this PR's cell, configuration and entries
    # (test_what_pr48_pins_by_place_holds_less_this_prs_entries), and
    # holds every older cell's list, PR 48's entries and the order of
    # cells and configurations by name
    # (test_the_older_cells_lists_are_as_pr48_held_them).
    **{"test_beam_cell.py::test_what_pr44_pins_by_place_holds_less_this_"
       f"prs_entries[{case}]":
       "PR 48's cell, configuration and fifteen entries are no longer "
       "the LAST of their lists: PR 51's go at the end"
       for case in ("fold-cell", "fold-configuration", "fold-pr40",
                    "subtract", "older-lists")},
    "test_beam_cell.py::test_the_older_cells_lists_are_as_pr44_held_them":
        "workloads and configs end with PR 48's no longer, and are nine: "
        "PR 51's cell and configuration go at the end",
    # PR 52 evaluates the shapelet basis on the model's compact pack of
    # shapelet sources: the tiny cell's ``shapelet_slots.ext`` and its
    # records' ``shapelet_slots`` read ``M x S_sh`` = 3 x 1, which is what
    # that counter was written to show ("4 once the basis is evaluated
    # where there is a shapelet").  This case pinned the slow state (24:
    # 3 clusters x 8 slots for 2 shapelets); everything else it guards is
    # held by ``tests/test_extended_cell.py``, on the same traced tiny run.
    # It still runs, and fails in that one number.  PERF.md section 7 has
    # the edit for the next ``benchmark`` issue.
    "test_extended.py::test_sound_tiny_cell_traced_reports_the_eight_and_"
    "the_record_fields":
        "shapelet_slots.ext is 3 (M x S_sh), not 24 (M x Smax): the basis "
        "is evaluated on the pack of the model's shapelet sources (PR 52)",
    # PR 53 appended six entries, four of which list EVERY cell
    # (``writer_ms``, ``reader_ms``, ``write_queue_ms``,
    # ``loop_blocked_ms``) and two the hybrid cell (``host_serial_ms.hyb``,
    # ``chip_wait_ms.hyb``).  As with PR 40's two, every case that holds a
    # cell's WHOLE per-layer list fails from the first of them, also the
    # cases that ran an older case on the manifest less what had been
    # appended up to their own PR.  benchmarks/tests/test_thread_spans.py
    # runs each of the twenty whole on the manifest less these six
    # (test_what_pins_a_list_by_place_holds_less_this_prs_entries,
    # fifteen cases, and test_what_pr51_runs_less_its_own_holds_less_this_
    # prs_too, five), and holds every cell's list by name
    # (test_every_cell_reports_the_four_behind_what_it_reported, nine
    # cases): what they guard stays guarded, case for case.
    **{f"test_host_spans.py::test_what_pins_a_cells_list_by_place_holds_"
       f"less_the_new_entries[{module}]": _PR53
       for module in ("test_t120", "test_consensus")},
    **{f"test_fold.py::test_an_older_cells_per_layer_list_is_unchanged"
       f"[{cell}]": _PR53
       for cell in ("cal-m8x3", "predict-m8x128", "admm-f4-mesh",
                    "cal-t120", "subtract-m8x128")},
    **{f"test_fold.py::test_what_pins_lists_by_place_holds_less_what_was_"
       f"appended_since[{module}]": _PR53
       for module in ("test_t120", "test_consensus")},
    **{f"test_hybrid.py::test_what_older_cells_pin_by_place_holds_less_"
       f"everything_since[{module}]": _PR53
       for module in ("test_t120", "test_consensus")},
    **{f"{module}.py::test_the_cell_is_files_and_entries_held_by_name":
       _PR53 for module in ("test_hybrid", "test_beam_cell",
                            "test_extended")},
    **{"test_extended.py::test_what_pr48_pins_by_place_holds_less_this_prs_"
       f"entries[{case}]": _PR53
       for case in ("fold-cell", "fold-pr40", "subtract",
                    "older-lists-pr42", "older-lists-pr44")},
    "test_extended.py::test_the_older_cells_lists_are_as_pr48_held_them":
        _PR53,
    # ... and the one traced tiny run that holds a cell's EXACT set of
    # reported metrics (``set(m) == set(EVERY + NEW) - {"hbm_peak_gb"}``),
    # which has grown by the four.  Everything else it guards is held by
    # ``tests/test_fold_cell.py``, which makes the same run on another
    # worker; it is one of ``NOT_RUN`` below.
    "test_fold.py::test_sound_tiny_cell_is_correct_traced":
        "the folded cell reports writer_ms, reader_ms, write_queue_ms "
        "and loop_blocked_ms beside what it reported (PR 53)",
}


#: Overtaken cases that the one run leaves out (``--deselect``): each is
#: a whole traced tiny run (80 s alone on the planes' programs, more
#: beside five workers) that ends in the assertion known to fail, inside
#: the one time limit this file shares with the whole suite.  Reported as
#: expected failures like the others; ``tests/test_hybrid_cell.py`` and
#: ``tests/test_fold_cell.py`` make the same runs, on another worker, and
#: hold the rest of what they guard.
NOT_RUN = ["test_hybrid.py::test_sound_tiny_cell_is_correct_and_reports_"
           "the_eight",
           # 55 s traced; ``tests/test_fold_cell.py`` makes it (PR 53)
           "test_fold.py::test_sound_tiny_cell_is_correct_traced"]
assert set(NOT_RUN) <= set(OVERTAKEN)


def _junit_key(node_id):
    """A node id as JUnit spells it: (classname, name)."""
    path, *inner = node_id.split("::")
    module = (SUITE + "/" + path)[:-len(".py")].replace("/", ".")
    return ".".join([module] + inner[:-1]), inner[-1]


@pytest.fixture(scope="module")
def suite(tmp_path_factory):
    """The one run: {(classname, name): (outcome, message)} and its output;
    a run that met its time limit has no results."""
    xml = tmp_path_factory.mktemp("benchmarks_suite") / "junit.xml"
    try:
        run = subprocess.run(
            PYTEST + [f"--junitxml={xml}"]
            + [f"--deselect={SUITE}/{n}" for n in NOT_RUN], cwd=ROOT,
            env=_env(), timeout=LIMIT_S, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        rc, out = run.returncode, run.stdout
    except subprocess.TimeoutExpired as e:
        out = e.stdout or ""
        if isinstance(out, bytes):      # what a timed-out run hands back
            out = out.decode(errors="replace")
        return {}, None, f"still running after {LIMIT_S} s\n{out[-4000:]}"
    results = {}
    if xml.exists():
        for case in ET.parse(xml).getroot().iter("testcase"):
            bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
            results[case.get("classname"), case.get("name")] = (
                (bad[0].tag, (bad[0].text or bad[0].get("message") or ""))
                if bad else ("passed", ""))
    return results, rc, out[-4000:]


def test_the_benchmarks_own_tests_ran_to_their_end(suite):
    """Collected without an error, and the run ended by itself with every
    collected test in its report (rc 1 is a failing test: its own case
    says so)."""
    results, rc, tail = suite
    assert not COLLECT_ERROR, COLLECT_ERROR
    assert IDS and rc in (0, 1), tail
    assert {_junit_key(i) for i in IDS if i not in NOT_RUN} \
        <= set(results), tail


@pytest.mark.parametrize("node_id", IDS)
def test_benchmark(suite, node_id):
    results, _, tail = suite
    if node_id in NOT_RUN:
        pytest.xfail(OVERTAKEN[node_id])
    outcome, message = results.get(_junit_key(node_id),
                                   ("error", "no result of this test:\n"
                                    + tail))
    if outcome == "skipped":
        pytest.skip(message)
    if outcome != "passed" and node_id in OVERTAKEN:
        pytest.xfail(OVERTAKEN[node_id])
    if outcome != "passed":
        pytest.fail(message, pytrace=False)


def test_tcg_trips_entry_is_unchanged():
    """What the overtaken test guards, by name and not by place: the entry
    as PR 31 appended it, for the one cell."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        layer = json.load(f)["per_layer"]
    at = [m["name"] for m in layer].index("tcg_trips")
    assert layer[at] == {
        "name": "tcg_trips", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "per-cluster solvers",
        "moves": "tile_s.p50", "workloads": ["cal-m8x3"]}
