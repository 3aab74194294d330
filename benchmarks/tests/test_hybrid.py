"""The hybrid cluster file's cell, ``cal-m16x3-hybrid``: its entries in
the manifest and its files, held BY NAME and not by place; the tiny
rehearsal cell that stands for it (8 stations, 4 clusters with chunk
counts 5, 3, 1, 1 and the brightest kept) traced and untraced; each of
its controls failing a limit; each new reader on synthetic records.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_hybrid.py -q
"""

import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness              # noqa: E402
import reference_hybrid     # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "hybrid-cells.json")
READINGS = os.path.join(HERE, "rehearsal", "hybrid-readings.json")
SEED = 2 ** 31 + 44
CELL, CONFIG, MIX = ("cal-m16x3-hybrid", "lofar62-m16x3-hybrid",
                     "calibrate-hybrid-tiles")
#: the new per-layer entries and the accepted reader each one is
RENAMED = {"solve_s.hyb": "solve_s", "sweep_dev_s.hyb": "sweep_dev_s",
           "refine_dev_s.hyb": "refine_dev_s",
           "residual_ms.hyb": "residual_ms", "bubble_ms.hyb": "bubble_ms.cal"}
OWN = ["assemble_dev_s.hyb", "chunk_slots_idle_pct.hyb",
       "flat_row_passes.hyb"]
NEW = sorted(list(RENAMED) + OWN)
UNLISTED = ["compiles_in_window", "device_idle_pct", "hbm_peak_gb",
            "recompiles_in_window", "compile_s.setup"]


# -- the manifest and the files, by name --------------------------------------

def test_the_cell_is_files_and_entries_held_by_name():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in man["workloads"]}
    configs = {c["name"]: c for c in man["configs"]}
    layer = {m["name"]: m for m in man["per_layer"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": MIX,
                           "chips": 1}
    assert configs[CONFIG]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(configs[CONFIG]["reduced"]) == ["beam", "n_tiles_on_disk"]
    # one cell of this configuration, one configuration of this file
    assert [w["name"] for w in man["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in man["configs"]
            if c["file"] == configs[CONFIG]["file"]] == [CONFIG]
    real = harness.Cell(CELL)
    assert real.chips == 1 and real.traffic["driver"] == "calibrate_hybrid"
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    assert sorted(m["name"] for m in real.metrics("per_layer")) \
        == sorted(UNLISTED + NEW)
    for name in NEW:
        m, mod = layer[name], harness.load_module("layer_metrics", name)
        assert m["workloads"] == [CELL]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
    # no older cell reports a new name, and no older entry lists the cell
    for name in cells:
        if name != CELL:
            got = {m["name"] for m in harness.Cell(name).metrics("per_layer")}
            assert not got & set(NEW), name
    for name, m in layer.items():
        assert (CELL in m.get("workloads", [])) == (name in NEW)
    tiny = harness.Cell("cal-hybrid-tiny", harness.load_json(CELLS))
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"]
    assert tiny.config["limits"] == real.config["limits"]


def test_the_configuration_is_the_base_observation_under_a_hybrid_file():
    conf = harness.Cell(CELL).config
    base = harness.Cell("cal-m8x3").config
    own = harness.load_json(ROOT, f"benchmarks/configs/{CONFIG}.json")
    for k in ("n_stations", "tilesz", "tdelta_s", "freq_hz", "chan_width_hz",
              "ra0_rad", "dec0_rad", "layout_seed", "sky_seed", "sky_format",
              "log_flux_mean", "jones_scale", "noise_sigma", "cli",
              "n_sources_per_cluster", "precision"):
        assert conf[k] == base[k], k
    assert "-B" not in conf["cli"] and conf["cli"][:2] == ["-t", "10"]
    assert conf["n_clusters"] == 16
    counts = conf["nchunk_by_flux_rank"]
    assert counts == [5, 3, 2, 2, 2, 2] + [1] * 10
    assert sum(counts) == 26 and conf["kept_flux_ranks"] == [0]
    assert sorted(own["reduced"]) == ["beam", "n_tiles_on_disk"]
    assert {"nchunk_by_flux_rank", "kept_flux_ranks", "chunk_jones_scale",
            "n_clusters, n_sources_per_cluster"} <= set(conf["assumed"])
    assert conf["guarantees"][:3] == base["guarantees"]
    assert len(conf["guarantees"]) == 5
    assert set(conf["limits"]) == {"residual_vs_reference",
                                   "residual_over_noise"}
    for lim in conf["limits"].values():
        assert all(lim[k] for k in ("what", "sound", "control", "limit",
                                    "why"))
    # at most 256 tiles on disk, and more than the warm-up
    mix = harness.Cell(CELL).traffic
    assert mix["warmup_tiles"] + 10 <= conf["n_tiles_on_disk"] <= 256
    assert mix["check_tiles"] == 64 and mix["profile_slice_s"] == 8.0


def test_the_observation_is_what_the_configuration_says():
    """The cluster file as the reference writes it for the real cell:
    chunk counts by summed flux, one negative id (the brightest), and
    the published rule cutting ten timeslots 4, 4, 2 for three chunks."""
    obs = reference_hybrid.Observation(harness.Cell(CELL).config, SEED)
    flux = obs.sky[3].sum(axis=1)
    order = np.argsort(-flux)
    assert list(obs.nchunk[order]) == [5, 3, 2, 2, 2, 2] + [1] * 10
    assert list(np.flatnonzero(obs.ids < 0)) == [order[0]]
    assert (obs.n_eff, obs.kmax, obs.nrows) == (26, 5, 18910)
    three = int(order[1])
    slots = obs.chunk_of_row()[three].reshape(10, -1)[:, 0]
    assert list(slots) == [0] * 4 + [1] * 4 + [2] * 2
    first = obs.cluster_lines[order[0]].split()
    assert int(first[0]) < 0 and first[1] == "5" and len(first) == 2 + 3
    # the same seed, the same observation; dead chunk slots hold NaN
    again = reference_hybrid.Observation(harness.Cell(CELL).config, SEED)
    assert np.array_equal(obs.jones(), again.jones(), equal_nan=True)
    live = np.arange(5)[None, :] < obs.nchunk[:, None]
    assert np.isnan(obs.jones()[~live]).all()
    assert not np.isnan(obs.jones()[live]).any()


@pytest.mark.parametrize("cell, says", [
    ("cal-m16x3-ones", {"nchunk_by_flux_rank": [1] * 16,
                        "kept_flux_ranks": []}),
    ("cal-m16x3-hybrid.ones-file", {"control": "all_ones_cluster_file",
                                    "kept_flux_ranks": [0]}),
])
def test_the_readings_are_the_cells_configuration_with_one_thing_changed(
        cell, says):
    """The all-ones reading and the control that needs a run of its own
    are the cell's configuration at the cell's size, under names that no
    manifest has."""
    real = harness.Cell(CELL)
    other = harness.Cell(cell, harness.load_json(READINGS))
    assert other.reports_as == CELL and other.traffic == real.traffic
    for k, v in says.items():
        assert other.config[k] == v
    same = set(real.config) - set(says) - {"name", "deployment", "base"}
    assert all(other.config[k] == real.config[k] for k in same)
    man = harness.load_json(ROOT, "BENCHMARK.json")
    assert cell not in {w["name"] for w in man["workloads"]}


# -- what older tests pin by place, on the manifest as it was -----------------

def load_test_module(name):
    spec = importlib.util.spec_from_file_location(
        "as_it_was_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest_less(monkeypatch, layer=(), cells=(), configs=()):
    """``harness.load_json`` gives ``BENCHMARK.json`` without the named
    entries: the manifest as an older test saw it."""
    load = harness.load_json

    def as_it_was(*parts):
        out = load(*parts)
        if parts[-1] == "BENCHMARK.json":
            out["per_layer"] = [m for m in out["per_layer"]
                                if m["name"] not in layer]
            out["workloads"] = [w for w in out["workloads"]
                                if w["name"] not in cells]
            out["configs"] = [c for c in out["configs"]
                              if c["name"] not in configs]
        return out

    monkeypatch.setattr(harness, "load_json", as_it_was)


@pytest.mark.parametrize("case", [
    "test_the_cell_is_files_and_entries",
    "test_the_configuration_is_the_sources_at_eight_subbands",
    "test_pr40s_entries_still_list_the_older_cells_and_only_ours_follow"])
def test_what_pr42_pins_by_place_holds_less_this_prs_entries(
        case, monkeypatch):
    """``test_fold.py`` holds PR 42's cell, configuration and nine
    entries as the LAST of their lists, and ``workloads`` as six cells;
    this PR's go behind them (the driver refuses any other place), so
    those three cases fail on the manifest as it is
    (``tests/test_benchmarks_suite.py``: ``OVERTAKEN``).  Each runs
    whole here on the manifest less this PR's cell, configuration and
    eight entries: what it guards stays guarded, case for case."""
    manifest_less(monkeypatch, NEW, [CELL], [CONFIG])
    getattr(load_test_module("test_fold"), case)()


@pytest.mark.parametrize("module", ["test_subtract", "test_t120",
                                    "test_consensus"])
def test_what_older_cells_pin_by_place_holds_less_everything_since(
        module, monkeypatch):
    """``test_the_cell_is_files_and_entries`` of these three pins a
    cell's whole per-layer list (and the first the manifest's LAST
    entries).  ``test_host_spans.py`` runs them less PR 40's entries,
    ``test_fold.py`` less PR 42's too, which the first no longer
    survives; here less everything appended since they were written:
    PR 40's two entries, PR 42's cell, configuration and nine, and this
    PR's."""
    fold = load_test_module("test_fold")
    manifest_less(monkeypatch, fold.PR40 + fold.NEW + NEW,
                  [fold.CELL, CELL], [fold.CONFIG, CONFIG])
    load_test_module(module).test_the_cell_is_files_and_entries()


def test_the_older_cells_lists_are_as_pr42_held_them():
    """Every cell there was reports the per-layer entries it reported,
    in their order; PR 40's two still list the five cells of their day
    and PR 42's nine the one; this PR's eight follow, and nothing else."""
    fold = load_test_module("test_fold")
    man = harness.load_json(ROOT, "BENCHMARK.json")
    for cell, names in fold.OLDER_LISTS.items():
        assert [m["name"] for m in harness.Cell(cell).metrics("per_layer")] \
            == names
    assert [m["name"] for m in harness.Cell(fold.CELL).metrics("per_layer")] \
        == fold.EVERY + fold.NEW
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(fold.PR40[0])
    assert names[at:at + 2] == fold.PR40
    assert names[at + 2:at + 2 + len(fold.NEW)] == fold.NEW
    assert sorted(names[at + 2 + len(fold.NEW):]) == NEW
    for m in man["per_layer"][at:at + 2]:
        assert m["workloads"] == fold.OLDER
    assert [w["name"] for w in man["workloads"]] \
        == fold.OLDER + [fold.CELL, CELL]
    assert [c["name"] for c in man["configs"]][-2:] == [fold.CONFIG, CONFIG]
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] \
        == ["admm-f4-mesh"]


# -- the tiny cell, end to end ------------------------------------------------

def run_cell(capsys, trace):
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "cal-hybrid-tiny",
                      "--seed", str(SEED), "--seconds", "60",
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_sound_tiny_cell_is_correct_and_reports_the_eight(capsys):
    """The traced run through ``run.main``; the untraced path is the
    ``runs`` fixture's below (a tiny run is 20-30 s of compiling, and
    this file runs inside the suite's one time limit)."""
    line, out = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    got = line["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    assert all(got[n]["value"] is not None for n in NEW)
    # 4 clusters x kmax 5, 5 + 3 + 1 + 1 live
    assert got["chunk_slots_idle_pct.hyb"]["value"] == pytest.approx(50.0)
    assert got["flat_row_passes.hyb"]["value"] > 0
    assert 0 < got["assemble_dev_s.hyb"]["value"] \
        <= got["sweep_dev_s.hyb"]["value"]
    assert "kmax 5, 10 of 20 chunk slots live" in out
    assert "sweep_rows flat, assemble_rows generic, refine_rows flat" in out
    for scope in ("sage/sweep/assemble", "sage/sweep/inner",
                  "sage/sweep/update", "sage/refine", "rime/corrupt",
                  "rime/residual"):
        assert f"[scope] {scope}" in out or f"[scope]   {scope}" in out, scope
    assert "controls on tile 3: kept cluster subtracted" in out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The tiny cell run untraced in this process, as ``limits.py`` runs a
    cell, so that ``compare`` can be asked again under each control; and
    the same data solved under the all-ones cluster file."""
    import run as runner
    assert runner.open_backend(True, 1) is not None
    more = harness.load_json(CELLS)
    control = tmp_path_factory.mktemp("hybrid") / "ones-file.json"
    control.write_text(json.dumps({
        "base": more["configs"][0]["file"], "name": "tiny-ones-file",
        "control": "all_ones_cluster_file"}))
    more["configs"].append({"name": "tiny-ones-file", "file": str(control)})
    more["workloads"].append({**more["workloads"][0],
                              "name": "cal-hybrid-tiny.ones-file",
                              "config": "tiny-ones-file"})
    out = {}
    for name in ("cal-hybrid-tiny", "cal-hybrid-tiny.ones-file"):
        cell = harness.Cell(name, more)
        run = runner.Run(cell, SEED, 60.0, trace=False)
        outcome = cell.driver.run(run)
        out[name] = (cell, run, outcome)
    return out


def test_the_program_read_the_file_the_reference_wrote(runs):
    cell, run, outcome = runs["cal-hybrid-tiny"]
    hyb = cell.driver.observation(run)
    lines, ids, nchunk = cell.driver.handed(run)
    assert lines == hyb.cluster_lines and list(nchunk) == list(hyb.nchunk)
    assert run.counters["nchunk"] == list(hyb.nchunk)
    assert run.counters["cluster_ids"] == list(hyb.ids)
    assert outcome == {"attempted": 3, "failed": 0}
    assert run.obs is not hyb       # the harness's own is left alone
    written = reference_hybrid.read_solutions(run.sol_path, hyb.nchunk)
    assert len(written) == 6        # every tile on disk, in order


@pytest.mark.parametrize("control, fails", [
    ({"keep": False}, ["residual_vs_reference"]),
    ({"rule": "floor"}, ["residual_vs_reference", "residual_over_noise"]),
    ({"low": "bfloat16"}, ["residual_vs_reference"]),
], ids=["kept-cluster-subtracted", "floor-boundaries", "one-bfloat16-pass"])
def test_a_reference_side_control_fails_its_limit(runs, control, fails):
    """What ``check`` would read of a program that subtracted the kept
    cluster (its whole model is in the difference), that cut the 3-chunk
    cluster's ten timeslots 3, 3, 4, or whose Jones products were made
    in one bfloat16 pass (the reference's own model in that type, in the
    written residual's place; in three passes it is inside the limit,
    as in ``cal-m8x3``)."""
    cell, run, _ = runs["cal-hybrid-tiny"]
    if "low" in control:
        low = pytest.importorskip("ml_dtypes").bfloat16
        control = {"low": low}
        three, _, _ = cell.driver.compare(run, run.window.tiles, low=low,
                                          passes=3)
        assert three <= run.config["limits"]["residual_vs_reference"]["limit"]
    limits = run.config["limits"]
    tiles = run.window.tiles
    a, b, _ = cell.driver.compare(run, tiles)
    assert a <= limits["residual_vs_reference"]["limit"]
    assert b <= limits["residual_over_noise"]["limit"]
    a, b, _ = cell.driver.compare(run, tiles, **control)
    got = {"residual_vs_reference": a, "residual_over_noise": b}
    for name in fails:
        assert got[name] > 2 * limits[name]["limit"], (name, got)


def test_the_same_data_under_an_all_ones_file_is_not_correct(runs):
    """One solution a tile cannot follow a Jones that changes from chunk
    to chunk: the written residual is still the reference's under the
    written solutions (the program is consistent with the file it was
    given), and far above the noise."""
    cell, run, _ = runs["cal-hybrid-tiny.ones-file"]
    hyb = cell.driver.observation(run)
    _, ids, nchunk = cell.driver.handed(run)
    assert list(nchunk) == [1] * 4 and (ids > 0).all()
    assert list(hyb.nchunk) != [1] * 4 and (hyb.ids < 0).any()
    assert run.counters["nchunk"] == [1] * 4
    checks = {c.name: c for c in cell.driver.check(run)}
    assert checks["residual_vs_reference"].ok
    bad = checks["residual_over_noise"]
    assert not bad.ok and bad.value > 5 * bad.limit


# -- the readers on synthetic records -----------------------------------------

def fake_run(tmp_path, records):
    path = tmp_path / "diag.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    return types.SimpleNamespace(
        diag_path=str(path), profile=None, slice=None, slice_tiles=0,
        profile_dir=str(tmp_path), diag_records=lambda: records,
        window=types.SimpleNamespace(t_open=100.0, t_drain=200.0))


TILE = {"t": 0.0, "tm": 150.0, "ev": "tile", "tile": 3}
FLAT = {"sweep_rows": "flat", "refine_rows": "flat",
        "assemble_rows": "generic"}
PERIODIC = {"sweep_rows": "periodic", "refine_rows": "periodic",
            "assemble_rows": "periodic"}


@pytest.mark.parametrize("records, value, said", [
    ([{**TILE, "kmax": 5, "chunk_slots": 80, "chunk_slots_live": 26}],
     67.5, "kmax 5, 26 of 80 chunk slots live"),
    # one chunk a cluster: nothing is padded
    ([{**TILE, "kmax": 1, "chunk_slots": 16, "chunk_slots_live": 16}],
     0.0, "kmax 1, 16 of 16"),
    # a warm-up tile's record (before the window) is not counted
    ([{**TILE, "tm": 50.0, "kmax": 1, "chunk_slots": 8,
       "chunk_slots_live": 8},
      {**TILE, "kmax": 5, "chunk_slots": 20, "chunk_slots_live": 10}],
     50.0, "over 1 tile(s)"),
    # the parent's record has no such key
    ([TILE], None, "no tile record with chunk_slots"),
    ([], None, "no tile record"),
], ids=["the-cell", "all-ones", "window-only", "no-key", "no-records"])
def test_chunk_slots_reader(tmp_path, capsys, records, value, said):
    mod = harness.load_module("layer_metrics", "chunk_slots_idle_pct.hyb")
    got = mod.read(fake_run(tmp_path, records))
    assert got == (value if value is None else pytest.approx(value))
    assert said in capsys.readouterr().out


@pytest.mark.parametrize("records, value, said", [
    ([{**TILE, **FLAT, "row_passes": 384, "refine_passes": 31}],
     415, "sweep_rows flat, assemble_rows generic, refine_rows flat"),
    ([{**TILE, **PERIODIC, "row_passes": 192, "refine_passes": 31}],
     0, "sweep_rows periodic"),
    # a refine on planes under sweeps on flat rows counts the sweeps'
    ([{**TILE, **FLAT, "refine_rows": "periodic", "row_passes": 100,
       "refine_passes": 30},
      {**TILE, "tile": 4, **FLAT, "row_passes": 200}], 150, "2 tile(s)"),
    # LM solves count no row pass (the tiny cell): the refine's are left
    ([{**TILE, **FLAT, "refine_passes": 31}], 31, "refine_rows flat"),
    ([TILE], None, "names a row layout"),
    ([], None, "names a row layout"),
], ids=["flat", "periodic", "mixed", "no-row-passes", "no-key",
        "no-records"])
def test_flat_row_passes_reader(tmp_path, capsys, records, value, said):
    mod = harness.load_module("layer_metrics", "flat_row_passes.hyb")
    assert mod.read(fake_run(tmp_path, records)) == value
    assert said in capsys.readouterr().out


def test_assemble_reader_sums_the_second_level_over_every_first(capsys):
    """Leaf seconds whose second level is ``assemble``, a device and a
    tile begun in the slice; nothing where the trace has none, no scoped
    event, or no tile."""
    mod = harness.load_module("layer_metrics", "assemble_dev_s.hyb")
    leaf = {("sage/sweep", "assemble"): [6.0, 10],
            ("sage/prelude", "assemble"): [2.0, 1],
            ("sage/sweep", "inner"): [9.0, 5], ("sage/refine", None): [1, 1]}

    def run(leaf, tiles=4):
        sl = types.SimpleNamespace(leaf=leaf, n_devices=1,
                                   scoped=lambda: bool(leaf))
        return types.SimpleNamespace(_scopes=sl, slice_tiles=tiles)

    assert mod.read(run(leaf)) == pytest.approx(2.0)
    assert "*/assemble: 8 s in 11 leaf operations" in capsys.readouterr().out
    assert mod.read(run({("sage/sweep", "inner"): [9.0, 5]})) is None
    assert mod.read(run({})) is None
    assert mod.read(run(leaf, tiles=0)) is None
    assert mod.read(types.SimpleNamespace(_scopes=None, slice_tiles=4)) is None


@pytest.mark.parametrize("name", sorted(RENAMED))
def test_renamed_readers_are_the_readers_that_exist(tmp_path, name):
    """Each gives what the accepted reader gives, under this cell's name,
    with that reader's unit, layer and end-to-end metric; and nothing
    where that one finds nothing (the parent's program)."""
    new = harness.load_module("layer_metrics", name)
    was = harness.load_module("layer_metrics", RENAMED[name])
    assert (new.NAME, new.UNIT, new.LAYER, new.MOVES) == (
        name, was.UNIT, was.LAYER, was.MOVES)
    recs = [{"t": 0.0, "ev": "phase", "name": "solve", "dur_s": 2.0, "tile": 3},
            {"t": 0.0, "ev": "phase", "name": "solve", "dur_s": 4.0, "tile": 4},
            {"t": 0.0, "ev": "phase", "name": "residual", "dur_s": 0.002,
             "tile": 3},
            {"t": 0.0, "ev": "tile", "tile": 3, "bubble_s": 0.25}]
    values = {"solve_s.hyb": 3.0, "residual_ms.hyb": 2.0,
              "bubble_ms.hyb": 250.0}
    for records in (recs, []):
        # no profiler trace: the device readers find nothing, as on a
        # program without the scopes (their values are the traced tiny
        # run's, above)
        run = fake_run(tmp_path, records)
        assert new.read(run) == was.read(run) == (
            values.get(name) if records else None)
