"""The tiny extended-source cell, traced, on the shapelet pack it runs
since PR 52.

What ``benchmarks/tests/test_extended.py::test_sound_tiny_cell_traced_
reports_the_eight_and_the_record_fields`` guards beside the slow state it
pinned (``shapelet_slots.ext == 24`` and the ``tile`` records'
``shapelet_slots`` 24, 3 clusters x 8 slots for 2 shapelets:
``tests/test_benchmarks_suite.py``, ``OVERTAKEN``; no file under
``benchmarks/`` is a ``perf_opt`` PR's to edit).  The basis is evaluated
for the model's compact pack now, ``M x S_sh`` = 3 x 1 slots.  That
test's output is consumed where it runs, so this is a run of its own: the
same configuration and traffic under a cell name of its own
(``tests/rehearsal/ext-pack-cells.json``), hence a work directory of its
own, in a process of its own."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
CELLS = "tests/rehearsal/ext-pack-cells.json"
TINY = "predict-extended-tiny.pack"
SEED = 2 ** 31 + 52
EXT = ["bubble_ms.ext", "chip_wait_ms.ext", "corrupt_dev_ms.ext",
       "device_ms_per_tile.ext", "host_serial_ms.ext", "phasor_dev_ms.ext",
       "shapelet_dev_ms.ext", "shapelet_slots.ext"]
#: every ``tile`` record of the tiny run
RECORD_FIELDS = {"sources_point": 7, "sources_gaussian": 9,
                 "sources_disk": 3, "sources_ring": 3, "sources_shapelet": 2,
                 "shapelet_n0max": 4, "shapelet_slots": 3,
                 "coh_path": "xla", "beam_mode": 0, "mode": 1,
                 "clusters_in_model": 3}


def test_tiny_extended_cell_evaluates_the_basis_on_the_pack():
    """8 stations, 3 clusters x 8 sources of all five kinds, shapelets of
    ``n0`` 4 and 3 in two of the clusters, traced through ``run.py``:
    ``correct``, the eight ``.ext`` metrics none ``None``, the basis read
    apart under its scope and evaluated for three slots, the records'
    fields, the controls, and the files the program read the
    reference's."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--cells", CELLS,
         "--workload", TINY, "--seed", str(SEED), "--seconds", "1",
         "--trace", "1", "--allow-cpu"], cwd=ROOT, env=env, timeout=900,
        text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    out = run.stdout
    assert run.returncode == 0, (out[-2000:], run.stderr[-2000:])
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 8
    assert list(line["checks"]) == ["model_vs_reference",
                                    "short_model_vs_reference"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert sorted(n for n in got if n.endswith(".ext")) == EXT
    assert all(got[n] is not None for n in EXT)
    assert {"compiles_in_window", "device_idle_pct", "recompiles_in_window",
            "compile_s.setup"} <= set(got)
    assert got["compiles_in_window"] == got["recompiles_in_window"] == 0
    assert got["shapelet_slots.ext"] == 3           # M x S_sh: 3 x 1
    assert 0 < got["shapelet_dev_ms.ext"] < got["phasor_dev_ms.ext"] \
        < got["device_ms_per_tile.ext"]
    # the basis is read apart from the rest of the source sum
    assert "[scope]   rime/phasor/shapelet " in out
    assert "[scope] */shapelet: " in out
    assert ("sources point 7, gaussian 9, disk 3, ring 3, shapelet 2; "
            "shapelet_n0max 4") in out
    assert "[control] seed" in out and "every source a point" in out
    assert "the shapelet sources left out" in out
    from sagecal_tpu.diag import trace as dtrace
    work = os.path.join(ROOT, "benchmarks", ".work", TINY)
    tiles = [r for r in dtrace.read(os.path.join(work, "diag.jsonl"))
             if r.get("ev") == "tile"]
    assert len(tiles) >= line["attempted"] + 5
    for r in tiles:
        assert {k: r[k] for k in RECORD_FIELDS} == RECORD_FIELDS
    # the files the program read are the reference's
    import harness
    import reference_extended
    cell = harness.Cell(TINY, harness.load_json(os.path.join(ROOT, CELLS)))
    obs = reference_extended.Observation(cell.config, SEED)
    assert open(os.path.join(work, "sky.txt")).read().splitlines() \
        == obs.sky_lines
    for name, text in obs.modes.items():
        assert open(os.path.join(work, name + ".fits.modes")).read() == text
    assert "Coherency path: xla" in open(
        os.path.join(work, "program.log")).read()
