"""The tiny hybrid cell, traced, on the layouts it runs since PR 45.

What ``benchmarks/tests/test_hybrid.py::test_sound_tiny_cell_is_correct_
and_reports_the_eight`` guards beside the slow state it pinned
(``flat_row_passes.hyb > 0`` and the line ``sweep_rows flat, assemble_rows
generic, refine_rows flat``: ``tests/test_benchmarks_suite.py``,
``OVERTAKEN``; no file under ``benchmarks/`` is a ``perf_opt`` PR's to
edit).  That test's output is consumed where it runs, so this is a run of
its own: the same configuration and traffic under a cell name of its
own (``tests/rehearsal/hybrid-planes-cells.json``), hence a work
directory of its own, in a process of its own, so that it can run beside
the benchmark's suite on another worker."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = "tests/rehearsal/hybrid-planes-cells.json"
HYB = ["assemble_dev_s.hyb", "bubble_ms.hyb", "chunk_slots_idle_pct.hyb",
       "flat_row_passes.hyb", "refine_dev_s.hyb", "residual_ms.hyb",
       "solve_s.hyb", "sweep_dev_s.hyb"]
#: PR 53: the accepted readers of ``host_serial_ms`` and ``chip_wait_ms``
#: under this cell's name, so it has a ``[host]`` and a ``[wait]`` table
TWINS = ["chip_wait_ms.hyb", "host_serial_ms.hyb"]


def test_tiny_hybrid_cell_runs_on_planes():
    """8 stations, 4 clusters with chunk counts 5, 3, 1, 1 and the
    brightest kept, traced through ``run.py``: ``correct``, the eight
    ``.hyb`` metrics none ``None``, and every pass over the rows on
    ``[tilesz, nbase]`` planes."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--cells", CELLS,
         "--workload", "cal-hybrid-tiny.planes", "--seed", str(2 ** 31 + 45),
         "--seconds", "60", "--trace", "1", "--allow-cpu"], cwd=ROOT,
        env=env, timeout=900, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    out = run.stdout
    assert run.returncode == 0, (out[-2000:], run.stderr[-2000:])
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert sorted(n for n in got if n.endswith(".hyb")) == sorted(
        HYB + TWINS)
    assert all(got[n]["value"] is not None for n in HYB + TWINS)
    assert "[host] step/solve/dispatch" in out and "[wait] rows add up" in out
    # 4 clusters x kmax 5, 5 + 3 + 1 + 1 live
    assert got["chunk_slots_idle_pct.hyb"]["value"] == pytest.approx(50.0)
    assert got["flat_row_passes.hyb"]["value"] == 0
    assert 0 < got["assemble_dev_s.hyb"]["value"] \
        <= got["sweep_dev_s.hyb"]["value"]
    assert "kmax 5, 10 of 20 chunk slots live" in out
    assert ("sweep_rows periodic, assemble_rows periodic, "
            "refine_rows periodic") in out
    for scope in ("sage/sweep/assemble", "sage/sweep/inner",
                  "sage/sweep/update", "sage/refine", "rime/corrupt",
                  "rime/residual"):
        assert f"[scope] {scope}" in out or f"[scope]   {scope}" in out, scope
    assert "controls on tile 3: kept cluster subtracted" in out
