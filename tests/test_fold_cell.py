"""The tiny folded cell, traced, with the four metrics that follow a
blocked loop into the thread it is blocked on (PR 53).

What ``benchmarks/tests/test_fold.py::test_sound_tiny_cell_is_correct_
traced`` guards beside the one thing PR 53 overtook, the cell's EXACT set
of reported metrics, which has grown by ``writer_ms``, ``reader_ms``,
``write_queue_ms`` and ``loop_blocked_ms`` (``tests/test_benchmarks_
suite.py``: ``OVERTAKEN`` and ``NOT_RUN``; no file under ``benchmarks/``
that exists is this PR's to edit).  That test's output is consumed where
it runs, so this is the same run under a cell name, hence a work
directory, of its own (``tests/rehearsal/fold-threads-cells.json``), in a
process of its own on another worker: the suite's longest chain loses a
traced tiny run and gains none.  It is also the one place a traced run's
PRINTED ``[writer]``, ``[reader]``, ``[queue]``, ``[pace]`` and
``[verdict]`` are read (``benchmarks/tests/test_thread_spans.py`` reads
the records other runs left behind)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
CELLS = "tests/rehearsal/fold-threads-cells.json"
WORKLOAD = "fold-tiny.threads"
FOLD = ["solve_s.fold", "jupdate_dev_s.fold", "consensus_dev_ms.fold",
        "bubble_ms.fold", "admm_iters.fold", "host_serial_ms.fold",
        "chip_wait_ms.fold", "lockstep_pct.fold", "jupdate_trips.fold"]
EVERY = ["compiles_in_window", "device_idle_pct", "recompiles_in_window",
         "compile_s.setup"]      # one CPU device has no memory statistics
FOUR = ["writer_ms", "reader_ms", "write_queue_ms", "loop_blocked_ms"]


def rows(out, tag):
    """{row label: first number} of the printed ``[tag]`` rows."""
    got = {}
    for ln in out.splitlines():
        if ln.startswith(f"[{tag}] ") and " ms " in ln:
            label, rest = ln[len(tag) + 3:].rsplit(" ms ", 1)[0].rsplit(
                None, 1)
            got[label.strip()] = float(rest)
    return got


def test_tiny_fold_cell_traced_reports_its_metrics_and_the_four():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--cells", CELLS,
         "--workload", WORKLOAD, "--seed", str(2 ** 31 + 5),
         "--seconds", "120", "--trace", "1", "--allow-cpu"], cwd=ROOT,
        env=env, timeout=900, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    out = run.stdout
    assert run.returncode == 0, (out[-2000:], run.stderr[-2000:])
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # what the overtaken case held, the set now by name
    assert set(m) == set(EVERY + FOLD + FOUR)
    assert m["admm_iters.fold"] == 10
    assert m["compiles_in_window"] == m["recompiles_in_window"] == 0
    assert 0 < m["consensus_dev_ms.fold"] < 1e3 * m["jupdate_dev_s.fold"]
    assert m["jupdate_trips.fold"] > 0
    assert 0 < m["lockstep_pct.fold"] < 100
    assert "[fold] lockstep_pct over 4 interval(s): fold 4, ndev 1, " \
        "plan traced" in out
    # ONE interval in the profile, whatever the window held
    clock = [ln for ln in out.splitlines() if ln.startswith("[clock]")][0]
    assert "stop_trace_in_window_s" in clock
    assert "[scope] sage/consensus" in out and "[scope] sage/manifold" in out
    from sagecal_tpu.diag import trace as dtrace
    recs = dtrace.read(os.path.join(ROOT, "benchmarks", ".work", WORKLOAD,
                                    "diag.jsonl"))
    tiles = [r for r in recs if r["ev"] == "tile"]
    assert len(tiles) == 6
    for r in tiles:
        assert (r["fold"], r["ndev"], r["plan"]) == (4, 1, "traced")
        assert r["jupdate_trips"] > 0 and 0 <= r["lockstep_pct"] < 100
        assert "rho_mean" not in r          # PR 53: nothing read it
    # the four, and their printed tables
    assert all(m[n] >= 0 for n in FOUR)
    writer, reader, pace = (rows(out, t) for t in ("writer", "reader",
                                                   "pace"))
    # four subbands: a put, a keep, a savez and a replace each, one
    # convert, two solutions jobs (the subbands' files, the Z file)
    assert {"write", "write/wait", "write/convert", "write/put",
            "write/put/keep", "write/put/savez", "write/put/replace",
            "solutions"} == set(writer), writer
    assert {"read", "read/load", "read/stage", "read/stage/pack",
            "read/stage/copy", "arrival_wait (not counted)"} == set(reader)
    assert "of 12 writer-job roots (3.00 a tile)" in out
    assert list(pace)[-1] == "that thread idle"
    assert sum(pace.values()) == pytest.approx(m["loop_blocked_ms"],
                                               rel=0.01, abs=1e-3)
    # what bubble_ms.fold times from the tile record, from the spans
    assert m["loop_blocked_ms"] == pytest.approx(m["bubble_ms.fold"],
                                                 abs=0.5)
    verdict = [ln for ln in out.splitlines()
               if ln.startswith("[verdict] cycle ")]
    assert len(verdict) == 1 and "% of the cycle" in verdict[0]
    puts = [r for r in recs if r["ev"] == "phase" and r["name"] == "put"]
    assert sorted({r["sub"] for r in puts}) == [0, 1, 2, 3]
