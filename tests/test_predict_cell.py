"""The tiny predict cell, traced, on the loop it runs since PR 46.

What two of the benchmark's own tests guard beside the synchronous loop
they pinned (``tests/test_benchmarks_suite.py``, ``OVERTAKEN``; no file
under ``benchmarks/`` is a ``perf_opt`` PR's to edit):
``benchmarks/tests/test_host_spans.py::test_a_rehearsal_cell_prints_both_
tables[cells.json-predict-tiny-1-paths1]`` looks for ``step/stage`` and
``step/write`` on the loop's thread, and ``benchmarks/tests/test_scopes.
py::test_tiny_cell_traced_end_to_end[predict-tiny]`` holds
``bubble_ms.predict`` within half of ``io_ms.predict``.  The reader
thread stages and the ordered writer writes now, and ``bubble_s`` is what
the loop's thread was blocked on the two.  Their output is consumed where
they run, so this is a run of its own: the same configuration and traffic
under a cell name of its own (``tests/rehearsal/predict-overlap-cells.
json``), hence a work directory of its own, in a process of its own."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = "tests/rehearsal/predict-overlap-cells.json"
SECONDS = 5


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


def test_tiny_predict_cell_runs_overlapped():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--cells", CELLS,
         "--workload", "predict-tiny.overlap", "--seed", str(2 ** 31 + 46),
         "--seconds", str(SECONDS), "--trace", "1", "--allow-cpu"],
        cwd=ROOT, env=env, timeout=900, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    out = run.stdout
    assert run.returncode == 0, (out[-2000:], run.stderr[-2000:])
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["device"]["platform"] == "cpu"
    m = line["metrics"]
    # no reader of the cell has fallen silent
    for name in ("phasor_dev_ms", "corrupt_dev_ms", "device_ms_per_tile",
                 "bubble_ms.predict", "io_ms.predict", "host_serial_ms",
                 "chip_wait_ms", "device_idle_pct", "recompiles_in_window",
                 "compile_s.setup"):
        assert m[name]["value"] is not None, name
    assert m["recompiles_in_window"]["value"] == 0 \
        == m["compiles_in_window"]["value"]
    assert m["compile_s.setup"]["value"] > 0
    assert m["host_serial_ms"]["value"] > 0 < m["chip_wait_ms"]["value"]
    # the loop's thread no longer stands in the read and the write:
    # what it was blocked on them is less than they took
    assert 0 <= m["bubble_ms.predict"]["value"] < m["io_ms.predict"]["value"]
    for needle in ("[scope] rime/phasor", "[span] sagecal/io",
                   "[span] sagecal/stage", "[span] sagecal/predict",
                   "[span] sagecal/fetch", "[span] sagecal/write",
                   "[compile] set-up"):
        assert needle in out, needle
    assert "no window record" not in out
    # both tables: the loop's thread dispatches, waits and hands over;
    # the staging and the write are other threads'
    host, wait = table(out, "host"), table(out, "wait")
    assert {"io", "step", "unspanned", "step/predict", "step/fetch/wait",
            "step/submit", "(other threads) read/stage",
            "(other threads) write"} <= set(host), host
    assert not {"step/stage", "step/write"} & set(host)
    assert {"outside every span", "gaps under 10 us"} <= set(wait)
    assert sum(wait.values()) == pytest.approx(
        m["chip_wait_ms"]["value"], rel=0.01)
    # the profile: whole tiles from the end of the window
    assert "no profiler trace in this run" not in out
    dev = line["device"]
    assert 0 < dev["busy_s"] < dev["window_s"] < SECONDS
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
    assert 0 <= m["device_idle_pct"]["value"] < 100
    tiles = int(next(ln for ln in out.splitlines()
                     if ln.startswith("[wait] rows add up")).split(
                         " a tile over ")[1].split()[0])
    assert m["chip_wait_ms"]["value"] == pytest.approx(
        m["device_idle_pct"]["value"] / 100 * 1e3 * dev["window_s"] / tiles,
        rel=1e-6)
    # the line before the result line: every phase of the run, adding up
    clock = out.strip().splitlines()[-2]
    assert clock.startswith("[clock] backend ")
    phases, rest = clock[len("[clock] "):].split("; total ")
    for name in ("data", "warmup", "window", "drain", "stop_trace", "load",
                 "walk", "reduce", "check", "scopes", "readers"):
        assert f" {name} " in " " + phases, name
    assert sum(float(p.rsplit(" ", 1)[1]) for p in phases.split(", ")) \
        == pytest.approx(float(rest.split(" s")[0]), abs=0.1)
    assert "device_events" in rest and "device_planes 1" in rest
