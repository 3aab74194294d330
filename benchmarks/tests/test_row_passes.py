"""``row_passes``: the reader on ``tile`` records with the count, with 0
in its place, and without the key; and its entry in the manifest."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

TILE = {"ev": "tile", "tile": 3, "solver_iters": 128, "cg_iters": 480}


@pytest.mark.parametrize("records, value", [
    # 64 IRLS rounds + 128 trust-region iterations a tile; a tile whose
    # line search took one trial more
    ([{**TILE, "row_passes": 192}, {**TILE, "tile": 4, "row_passes": 193}],
     192.5),
    # LM and NSD solves fill the slot with 0: nothing to say
    ([{**TILE, "row_passes": 0}], None),
    # a program before PR 33 has no such key; other events are not tiles
    ([TILE, {"ev": "em_sweep", "tile": 3, "row_passes": 7}], None),
    ([], None),
], ids=["count", "zero", "no-key", "no-records"])
def test_reader(records, value):
    run = types.SimpleNamespace(diag_records=lambda: records)
    assert harness.load_module("layer_metrics", "row_passes").read(run) \
        == value


def test_entry_is_the_lists_last_and_for_the_one_cell():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    mod = harness.load_module("layer_metrics", "row_passes")
    entry = next(m for m in man["per_layer"] if m["name"] == mod.NAME)
    assert entry == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "lower",
        "source": "program_counter", "layer": mod.LAYER,
        "moves": mod.MOVES, "workloads": ["cal-m8x3"]}
    # appended: every entry that was there is where it was
    names = [m["name"] for m in man["per_layer"]]
    assert names.index("row_passes") > names.index("bubble_ms.t120")
    cells = lambda cell: {m["name"]
                          for m in harness.Cell(cell).metrics("per_layer")}
    assert "row_passes" in cells("cal-m8x3")
    assert "row_passes" not in (cells("predict-m8x128")
                                | cells("admm-f4-mesh") | cells("cal-t120"))
