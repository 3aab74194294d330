"""The production solve interval's cell, ``cal-t120``: its entries in
the manifest, its configuration file, the tiny rehearsal cell that
stands for it (``-t 120`` at 8 stations: 3360 rows a tile) traced and
broken underneath, and the reader of the plan learner's counter.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_t120.py -q
"""

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

import harness      # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "t120-cells.json")
SEED = 2 ** 31 + 5
NEW = ["solve_dispatches.t120", "solve_s.t120", "sweep_dev_s.t120",
       "refine_dev_s.t120", "bubble_ms.t120"]


def run_cell(capsys, trace):
    """``--seconds`` beyond the two window tiles the tiny observation
    has: the window is both of them however slow this machine is, and a
    traced run ends with the last one in its profile (PR 35; a 1 s
    window held one tile beside five busy workers, and no profile)."""
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "cal-t120-tiny",
                      "--seed", str(SEED), "--seconds", "60",
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_files_and_entries():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    real = harness.Cell("cal-t120")
    tiny = harness.Cell("cal-t120-tiny", harness.load_json(CELLS))
    base = harness.Cell("cal-m8x3")
    assert real.chips == 1 and real.entry["traffic"] == "calibrate-tiles"
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    layer = [m["name"] for m in real.metrics("per_layer")]
    assert layer == ["compiles_in_window", "device_idle_pct", "hbm_peak_gb",
                     "recompiles_in_window", "compile_s.setup"] + NEW
    # the cell it shares everything with reports none of the new names,
    # and every entry that was there is where it was: the new ones
    # follow tcg_trips (what later PRs append comes behind them)
    assert not set(NEW) & {m["name"] for m in base.metrics("per_layer")}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("tcg_trips")
    assert names[at + 1:at + 6] == NEW
    for m in man["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["cal-t120"]
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"] \
        == base.config["guarantees"]

    # the deployment: the base's observation at upstream's default -t
    conf = real.config
    own = harness.load_json(ROOT, "benchmarks/configs/lofar62-t120-m8x3.json")
    assert conf["tilesz"] == 120 and conf["tdelta_s"] == 10.0
    cli, base_cli = conf["cli"], base.config["cli"]
    assert cli[:2] == ["-t", "120"] and base_cli[:2] == ["-t", "10"]
    assert cli[2:] == base_cli[2:]
    assert sorted(own["reduced"]) == ["beam", "n_tiles_on_disk"]
    assert set(own["limits"]) == set(base.config["limits"])
    for k in ("n_stations", "n_clusters", "n_sources_per_cluster",
              "freq_hz", "noise_sigma", "jones_scale", "layout_seed",
              "sky_seed"):
        assert conf[k] == base.config[k]
    assert set(conf["assumed"]) == set(base.config["assumed"]) | {"tdelta_s"}
    # 1891 baselines x 120 timeslots
    assert conf["n_stations"] * (conf["n_stations"] - 1) // 2 \
        * conf["tilesz"] == 226920
    # warm-up tiles, a window's tiles and two to spare are on disk
    assert conf["n_tiles_on_disk"] >= real.traffic["warmup_tiles"] + 3 + 2


def test_sound_tiny_cell_is_correct_and_says_its_plan(capsys):
    line = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    got = line["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    from sagecal_tpu.diag import trace as dtrace
    path = os.path.join(BENCH, ".work", "cal-t120-tiny", "diag.jsonl")
    tiles = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert len(tiles) >= 4                       # warm-up and window
    for r in tiles:
        assert r["plan"] in ("promoted", "fused", "per_cluster")
        assert r["solve_dispatches"] >= 1
        assert (r["plan"] == "promoted") == (r["solve_dispatches"] == 1)
    # -e 4: a host-driven solve is a prelude, four sweeps at least and
    # the refine
    assert all(r["solve_dispatches"] >= 6 for r in tiles
               if r["plan"] != "promoted")
    assert got["solve_dispatches.t120"]["value"] >= 1
    assert got["solve_s.t120"]["value"] > 0

    line = run_cell(capsys, trace=0)
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"vis_per_s", "tile_s.p50", "setup_s"}


def test_a_solver_that_returns_its_jones_is_not_correct(capsys, monkeypatch):
    from sagecal_tpu import pipeline
    real = pipeline.FullBatchPipeline._build_solver

    def build(self, emiter_mult, warm=False):
        solve = real(self, emiter_mult, warm)

        def unchanged(x8, u, v, w, sta1, sta2, wt, J0_r8, beam, tile_idx=0):
            _, info = solve(x8, u, v, w, sta1, sta2, wt, J0_r8, beam,
                            tile_idx=tile_idx)
            return J0_r8, info
        return unchanged

    monkeypatch.setattr(pipeline.FullBatchPipeline, "_build_solver", build)
    line = run_cell(capsys, trace=0)
    assert line["correct"] is False
    check = line["checks"]["residual_over_noise"]
    assert check["value"] > check["limit"]


TILE = {"ev": "tile", "tile": 3, "solver_iters": 128}


@pytest.mark.parametrize("records, value, said", [
    ([{**TILE, "plan": "promoted", "solve_dispatches": 1},
      {**TILE, "tile": 4, "plan": "promoted", "solve_dispatches": 1}],
     1, "promoted x 2"),
    # the learner changed its mind inside the window
    ([{**TILE, "plan": "fused", "solve_dispatches": 6},
      {**TILE, "tile": 4, "plan": "promoted", "solve_dispatches": 1}],
     3.5, "fused x 1, promoted x 1"),
    # the parent's record has neither key; other events are not tiles
    ([TILE, {"ev": "em_sweep", "tile": 3, "solve_dispatches": 9}],
     None, ""),
    ([], None, ""),
], ids=["promoted", "mixed", "no-key", "no-records"])
def test_dispatch_reader(capsys, records, value, said):
    run = types.SimpleNamespace(diag_records=lambda: records)
    mod = harness.load_module("layer_metrics", "solve_dispatches.t120")
    assert mod.read(run) == value
    assert said in capsys.readouterr().out


@pytest.mark.parametrize("name, old", [
    ("solve_s.t120", "solve_s"), ("sweep_dev_s.t120", "sweep_dev_s"),
    ("refine_dev_s.t120", "refine_dev_s"),
    ("bubble_ms.t120", "bubble_ms.cal")])
def test_renamed_readers_are_the_readers_that_exist(name, old):
    """Each gives what the accepted reader gives, under this cell's
    name, with that reader's unit, layer and end-to-end metric; and
    nothing where that one finds nothing (the parent's program)."""
    new = harness.load_module("layer_metrics", name)
    was = harness.load_module("layer_metrics", old)
    assert (new.NAME, new.UNIT, new.LAYER, new.MOVES) == (
        name, was.UNIT, was.LAYER, was.MOVES)
    recs = [{"ev": "phase", "name": "solve", "dur_s": 2.0, "tile": 3},
            {"ev": "phase", "name": "solve", "dur_s": 4.0, "tile": 4},
            {"ev": "tile", "tile": 3, "bubble_s": 0.25}]
    values = {"solve_s.t120": 3.0, "bubble_ms.t120": 250.0}
    for records in (recs, []):
        # no profiler trace: the device readers find nothing, as on a
        # program without the scopes (their values are the traced tiny
        # run's, above)
        run = types.SimpleNamespace(
            diag_records=lambda: records, profile=None, slice=None,
            slice_tiles=0, diag_path=os.path.join(HERE, "no-such-file"),
            window=types.SimpleNamespace(t_open=None))
        assert new.read(run) == was.read(run) == (
            values.get(name) if records else None)
