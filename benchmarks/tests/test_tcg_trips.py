"""``tcg_trips``: the reader on ``tile`` records with the count, with 0
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

TILE = {"ev": "tile", "tile": 3, "solver_iters": 128, "lbfgs_iters": 10}


@pytest.mark.parametrize("records, value", [
    # the change: the executed bodies of two window tiles
    ([{**TILE, "cg_iters": 450}, {**TILE, "tile": 4, "cg_iters": 480}],
     465),
    # a program that fills the slot with 0 for RTR says nothing
    ([{**TILE, "cg_iters": 0}], None),
    # the parent's record has no such key; other events are not tiles
    ([TILE, {"ev": "em_sweep", "tile": 3, "cg_iters": 7}], None),
    ([], None),
], ids=["count", "zero", "no-key", "no-records"])
def test_reader(records, value):
    run = types.SimpleNamespace(diag_records=lambda: records)
    assert harness.load_module("layer_metrics", "tcg_trips").read(run) \
        == value


def test_entry_is_appended_for_the_one_cell():
    """By name, not by place: what later PRs append comes behind it."""
    man = harness.load_json(ROOT, "BENCHMARK.json")
    mod = harness.load_module("layer_metrics", "tcg_trips")
    entry = next(m for m in man["per_layer"] if m["name"] == mod.NAME)
    assert entry == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "lower",
        "source": "program_counter", "layer": mod.LAYER,
        "moves": mod.MOVES, "workloads": ["cal-m8x3"]}
    names = lambda cell: {m["name"]
                          for m in harness.Cell(cell).metrics("per_layer")}
    assert "tcg_trips" in names("cal-m8x3")
    assert "tcg_trips" not in names("predict-m8x128") | names("admm-f4-mesh")
