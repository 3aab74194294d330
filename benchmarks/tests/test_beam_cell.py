"""The array beam's cell, ``dosage-beam``: its entries in the manifest and
its files, held BY NAME and not by place; the tiny rehearsal cell that
stands for it (``tiny-lofar62-m8x3``'s shapes with 4 and 6 live elements
a station) traced and untraced; each control that has to come out not
correct; a dataset without ``beam.npz`` refused; each new reader on
synthetic records.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_beam_cell.py -q
"""

import importlib.util
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

import harness              # noqa: E402
import reference_beam       # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "beam-cells.json")
READINGS = os.path.join(HERE, "rehearsal", "beam-readings.json")
SEED = 2 ** 31 + 48
CELL, CONFIG, MIX = ("dosage-beam", "lofar62-m8x128-beam",
                     "calibrate-beam-tiles")
#: the new per-layer entries and the accepted reader each one is
RENAMED = {"phasor_dev_ms.beam": "phasor_dev_ms",
           "corrupt_dev_ms.beam": "corrupt_dev_ms",
           "solve_s.beam": "solve_s", "sweep_dev_s.beam": "sweep_dev_s",
           "refine_dev_s.beam": "refine_dev_s",
           "bubble_ms.beam": "bubble_ms.cal",
           "tcg_trips.beam": "tcg_trips", "solver_trips.beam": "solver_trips",
           "row_passes.beam": "row_passes", "residual_ms.beam": "residual_ms",
           "host_serial_ms.beam": "host_serial_ms",
           "chip_wait_ms.beam": "chip_wait_ms"}
OWN = ["beam_dev_ms.beam", "beam_stage_ms.beam", "beam_tables_per_tile.beam"]
#: in the manifest's order
NEW = ["beam_dev_ms.beam", "phasor_dev_ms.beam", "corrupt_dev_ms.beam",
       "beam_stage_ms.beam", "beam_tables_per_tile.beam", "solve_s.beam",
       "sweep_dev_s.beam", "refine_dev_s.beam", "bubble_ms.beam",
       "tcg_trips.beam", "solver_trips.beam", "row_passes.beam",
       "residual_ms.beam", "host_serial_ms.beam", "chip_wait_ms.beam"]
#: what the tiny cell cannot read: eight stations are under LMCUT, so its
#: sweeps are LM's, which count neither truncated-CG trips nor passes
#: through the rows (as in ``cal-tiny``)
NOT_AT_TINY = ["tcg_trips.beam", "row_passes.beam"]
UNLISTED = ["compiles_in_window", "device_idle_pct", "hbm_peak_gb",
            "recompiles_in_window", "compile_s.setup"]
RECORD_FIELDS = {"beam_mode": 1, "beam_elements": 6, "beam_sources": 6,
                 "coh_path": "xla"}


# -- the manifest and the files, by name --------------------------------------

def test_the_cell_is_files_and_entries_held_by_name():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in man["workloads"]}
    configs = {c["name"]: c for c in man["configs"]}
    layer = {m["name"]: m for m in man["per_layer"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": MIX,
                           "chips": 1}
    assert configs[CONFIG]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(configs[CONFIG]["reduced"]) == ["n_tiles_on_disk", "tilesz"]
    assert "-B 1" in configs[CONFIG]["source"]
    # one cell of this configuration, one configuration of this file
    assert [w["name"] for w in man["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in man["configs"]
            if c["file"] == configs[CONFIG]["file"]] == [CONFIG]
    real = harness.Cell(CELL)
    assert real.chips == 1 and real.traffic["driver"] == "calibrate_beam"
    assert real.driver.BOUNDARY_OUTSIDE_SPANS is True
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    assert [m["name"] for m in real.metrics("per_layer")] == UNLISTED + NEW
    assert sorted(NEW) == sorted(list(RENAMED) + OWN)
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
    more = harness.load_json(CELLS)
    for name in ("dosage-beam-tiny", "dosage-beam-tiny.solved-B0"):
        tiny = harness.Cell(name, more)
        assert tiny.metrics("per_layer") == real.metrics("per_layer")
        assert tiny.config["guarantees"] == real.config["guarantees"]
        assert tiny.config["limits"] == real.config["limits"]


def test_the_configuration_is_the_base_observation_under_B1():
    conf = harness.Cell(CELL).config
    base = harness.Cell("cal-m8x3").config
    sky = harness.Cell("predict-m8x128").config
    own = harness.load_json(ROOT, f"benchmarks/configs/{CONFIG}.json")
    assert own["base"] == "benchmarks/configs/lofar62-m8x3.json"
    for k in ("n_stations", "n_clusters", "tilesz", "tdelta_s", "freq_hz",
              "chan_width_hz", "ra0_rad", "dec0_rad", "layout_seed",
              "sky_format", "jones_scale", "jones_per_interval",
              "noise_sigma", "precision"):
        assert conf[k] == base[k], k
    for k in ("n_sources_per_cluster", "sky_seed", "log_flux_mean"):
        assert conf[k] == sky[k], k
    assert conf["cli"] == base["cli"] + ["-B", "1"]
    assert (conf["beam_elements_core"], conf["beam_elements_remote"]) \
        == (24, 48)
    assert sorted(own["reduced"]) == ["n_tiles_on_disk", "tilesz"]
    assert own["reduced"]["tilesz"] == base["reduced"]["tilesz"]
    assert {"sky", "epoch", "beam_freq_hz", "apparent flux",
            "station longitude, latitude",
            "beam_elements_core, beam_elements_remote, element layout",
            "one chunk a cluster, positive ids"} <= set(own["assumed"])
    assert conf["guarantees"][:3] == base["guarantees"]
    assert len(conf["guarantees"]) == 5
    assert "array-beam gains" in conf["guarantees"][3]
    assert set(conf["limits"]) == {"residual_vs_reference",
                                   "residual_over_noise",
                                   "residual_over_noise.settled"}
    for lim in conf["limits"].values():
        assert all(lim[k] for k in ("what", "sound", "control", "limit",
                                    "why"))
        assert "TBD" not in json.dumps(lim)
    mix = harness.Cell(CELL).traffic
    # the issue's traffic: the base's three tiles of warm-up, 32 on disk;
    # the chain's descent to the noise is in the window, timed and checked
    assert mix["warmup_tiles"] == harness.Cell("cal-m8x3").traffic[
        "warmup_tiles"] == 3 and conf["n_tiles_on_disk"] == 32
    assert mix["warmup_tiles"] < mix["settled_from_tile"] \
        < conf["n_tiles_on_disk"]
    noise = conf["limits"]["residual_over_noise"]["limit"]
    assert conf["limits"]["residual_over_noise.settled"]["limit"] \
        == base["limits"]["residual_over_noise"]["limit"] < noise
    assert mix["check_tiles"] >= conf["n_tiles_on_disk"] - mix["warmup_tiles"]
    assert mix["profile_slice_s"] == 3.0     # about thirty tiles of 0.09 s


def test_the_observation_is_what_the_configuration_says():
    """62 stations of 24 (the 48 core ears) and 48 (the 14 remote) live
    elements in 48 slots, 8 x 128 sources, 18 910 rows, the field well
    above the horizon, and the beam a large thing: some cluster reaches
    the array at under a tenth of its catalogue flux."""
    obs = reference_beam.Observation(harness.Cell(CELL).config, SEED)
    assert obs.elem.shape == (62, 48, 3)
    assert list(obs.mask.sum(axis=1)) == [24] * 48 + [48] * 14
    assert obs.sky[3].shape == (8, 128) and obs.nrows == 18910
    assert obs.freq0 == obs.freq == 150e6
    assert obs.time_mjd(0)[0] > reference_beam.EPOCH_MJD_S
    again = reference_beam.Observation(harness.Cell(CELL).config, SEED)
    assert again.t_start == obs.t_start
    seen = obs.apparent_flux(0) / obs.sky[3].sum(axis=1)
    assert seen.min() < 0.1 and seen.max() > 0.8


def test_the_reading_is_the_cells_configuration_solved_without_the_beam():
    """Control (a) is the cell's configuration at the cell's size with
    ``-B 1`` taken off the program's flags, under a name that no manifest
    has: the data keep the beam."""
    real = harness.Cell(CELL)
    other = harness.Cell("dosage-beam.solved-B0", harness.load_json(READINGS))
    assert other.reports_as == CELL and other.traffic == real.traffic
    assert other.config["cli"] == real.config["cli"][:-2]
    assert "-B" not in other.config["cli"]
    same = set(real.config) - {"cli", "name", "deployment", "base"}
    assert all(other.config[k] == real.config[k] for k in same)
    man = harness.load_json(ROOT, "BENCHMARK.json")
    assert "dosage-beam.solved-B0" not in {w["name"] for w in man["workloads"]}


# -- what older tests pin by place, on the manifest as it was -----------------

def load_test_module(name):
    spec = importlib.util.spec_from_file_location(
        "as_it_was_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case, args", [
    ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
     ("test_the_cell_is_files_and_entries",)),
    ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
     ("test_the_configuration_is_the_sources_at_eight_subbands",)),
    ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
     ("test_pr40s_entries_still_list_the_older_cells_and_only_ours_follow",)),
    ("test_what_older_cells_pin_by_place_holds_less_everything_since",
     ("test_subtract",)),
    ("test_the_older_cells_lists_are_as_pr42_held_them", None),
], ids=["fold-cell", "fold-configuration", "fold-pr40", "subtract",
        "older-lists"])
def test_what_pr44_pins_by_place_holds_less_this_prs_entries(
        case, args, monkeypatch):
    """``test_hybrid.py`` holds PR 44's cell and configuration as the LAST
    of their lists (``configs[-2:]``, ``workloads``), runs ``test_fold.py``'s
    place-pinning cases on the manifest less PR 44's entries only, and
    ``test_subtract.py``'s less everything up to PR 44's; this PR's go
    behind them (the driver refuses any other place), so those five cases
    fail on the manifest as it is (``tests/test_benchmarks_suite.py``:
    ``OVERTAKEN``).  Each runs whole here on the manifest less this PR's
    cell, configuration and fifteen entries: what it guards stays guarded,
    case for case."""
    hyb = load_test_module("test_hybrid")
    hyb.manifest_less(monkeypatch, NEW, [CELL], [CONFIG])
    if args is None:
        getattr(hyb, case)()
    else:
        getattr(hyb, case)(*args, monkeypatch)


def test_the_older_cells_lists_are_as_pr44_held_them():
    """By name: every cell there was reports the per-layer entries it
    reported, in their order; PR 40's two entries still list the five
    cells of their day, PR 42's nine the one and PR 44's eight the one;
    this PR's fifteen follow, and nothing else; the cells and the
    configurations stand in the order they came, one cell on four
    chips."""
    hyb, fold = load_test_module("test_hybrid"), load_test_module("test_fold")
    man = harness.load_json(ROOT, "BENCHMARK.json")
    for cell, names in fold.OLDER_LISTS.items():
        assert [m["name"] for m in harness.Cell(cell).metrics("per_layer")] \
            == names
    assert [m["name"] for m in harness.Cell(fold.CELL).metrics("per_layer")] \
        == fold.EVERY + fold.NEW
    assert sorted(m["name"] for m in
                  harness.Cell(hyb.CELL).metrics("per_layer")) \
        == sorted(hyb.UNLISTED + hyb.NEW)
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(fold.PR40[0])
    assert names[at:at + 2] == fold.PR40
    assert names[at + 2:at + 2 + len(fold.NEW)] == fold.NEW
    rest = names[at + 2 + len(fold.NEW):]
    assert sorted(rest[:len(hyb.NEW)]) == hyb.NEW
    assert rest[len(hyb.NEW):] == NEW
    for m in man["per_layer"][at:at + 2]:
        assert m["workloads"] == fold.OLDER
    for m in man["per_layer"]:
        if m["name"] in hyb.NEW:
            assert m["workloads"] == [hyb.CELL]
    assert [w["name"] for w in man["workloads"]] \
        == fold.OLDER + [fold.CELL, hyb.CELL, CELL]
    assert [c["name"] for c in man["configs"]][-3:] \
        == [fold.CONFIG, hyb.CONFIG, CONFIG]
    assert len(man["workloads"]) == 8 and len(man["configs"]) == 8
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] \
        == ["admm-f4-mesh"]


# -- the tiny cell, end to end ------------------------------------------------

def run_cell(capsys, trace, workload="dosage-beam-tiny"):
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", workload,
                      "--seed", str(SEED), "--seconds", "60",
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_sound_tiny_cell_traced_reports_the_new_and_the_record_fields(
        capsys):
    line, out = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["attempted"] == 10
    got = line["metrics"]
    here = [n for n in NEW if n not in NOT_AT_TINY]
    assert set(here) <= set(got), sorted(got)
    assert all(got[n]["value"] is not None for n in here)
    # hbm_peak_gb is the chip's alone: the CPU reports no memory statistics
    assert set(UNLISTED) - {"hbm_peak_gb"} <= set(got)
    assert got["beam_tables_per_tile.beam"]["value"] == 2
    assert got["beam_dev_ms.beam"]["value"] > 0
    assert got["beam_stage_ms.beam"]["value"] > 0
    # the tables are read apart from the source sum
    assert "[scope] rime/beam " in out and "[scope] rime/phasor " in out
    assert ("beam_mode 1, beam_elements 6, beam_sources 6, coh_path xla"
            in out)
    # counted in the trace: the solve's coherency program and the residual's
    assert ("programs with operations under rime/beam that ran in the "
            "slice: jit__lambda, jit__residuals") in out
    assert "cosine-sine pairs a tile (6 sources x 4 timeslots x 8 " \
           "stations x 6 elements x 2 programs)" in out
    assert "stage/beam: median" in out
    assert "controls on tile 3: the reference's gains without precession" \
        in out
    # the four record fields, in every tile record of the run
    from sagecal_tpu.diag import trace as dtrace
    diag = os.path.join(BENCH, ".work", "dosage-beam-tiny", "diag.jsonl")
    tiles = [r for r in dtrace.read(diag) if r.get("ev") == "tile"]
    assert len(tiles) == 13
    for r in tiles:
        assert {k: r[k] for k in RECORD_FIELDS} == RECORD_FIELDS
    beams = [r for r in dtrace.read(diag)
             if r.get("ev") == "phase" and r.get("name") == "beam"]
    assert sorted(r["tile"] for r in beams) == list(range(13))
    stage = {r["id"]: r for r in dtrace.read(diag)
             if r.get("ev") == "phase" and r.get("name") == "stage"}
    assert all(r["parent"] in stage for r in beams)


def test_sound_tiny_cell_untraced_reports_the_end_to_end_metrics(capsys):
    line, _ = run_cell(capsys, trace=0)
    assert line["correct"] is True and line["failed"] == 0, line
    assert sorted(line["metrics"]) == ["setup_s", "tile_s.p50", "vis_per_s"]
    assert set(line["checks"]) == {"residual_vs_reference",
                                   "residual_over_noise",
                                   "residual_over_noise.settled"}


def test_the_same_data_solved_under_B0_is_not_correct(capsys):
    """Control (a): the Jones absorb a cluster's mean gain, the
    reference's model with the beam then applies it twice, and gains that
    differ from source to source cannot be absorbed at all."""
    line, out = run_cell(capsys, trace=0,
                         workload="dosage-beam-tiny.solved-B0")
    assert line["correct"] is False and line["failed"] == 0, line
    checks = line["checks"]
    bad = checks["residual_over_noise.settled"]
    assert bad["value"] > 2 * bad["limit"], checks
    assert checks["residual_over_noise"]["value"] >= bad["value"]
    assert checks["residual_vs_reference"]["value"] \
        > 2 * checks["residual_vs_reference"]["limit"], checks


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """The tiny cell run untraced in this process, as ``limits.py`` runs a
    cell, so that ``compare`` can be asked again under each control."""
    import run as runner
    assert runner.open_backend(True, 1) is not None
    cell = harness.Cell("dosage-beam-tiny", harness.load_json(CELLS))
    run = runner.Run(cell, SEED, 60.0, trace=False)
    outcome = cell.driver.run(run)
    return cell, run, outcome


def test_the_program_read_the_files_the_reference_wrote(sound):
    cell, run, outcome = sound
    obs = cell.driver.observation(run)
    assert outcome == {"attempted": 10, "failed": 0}
    assert run.counters["dobeam"] == 1
    assert run.obs is not obs       # the harness's own is left alone
    assert os.path.exists(os.path.join(run.ms_path, "beam.npz"))
    import numpy as np
    with np.load(os.path.join(run.ms_path, "beam.npz")) as z:
        assert np.array_equal(z["elem_xyz"], obs.elem)
        assert np.array_equal(z["elem_mask"], obs.mask)
        assert np.array_equal(z["longitude"], obs.lon)
        assert (float(z["ra0"]), float(z["dec0"])) == (obs.ra0, obs.dec0)
    with np.load(os.path.join(run.ms_path, "tile00004.npz")) as z:
        assert np.array_equal(z["time_mjd"], obs.time_mjd(4))
    log = open(os.path.join(run.work, "program.log")).read()
    assert "SYNTHETIC" not in log and "Coherency path: xla" in log
    assert "Precessed source/beam coordinates" in log
    assert sorted(obs.kept) == list(range(13))  # kept for the check


@pytest.mark.parametrize("control, fails", [
    ({"low": "bfloat16"}, True), ({"low": "bfloat16", "passes": 3}, False),
    ({"precessed": False}, None),
], ids=["one-bfloat16-pass", "three-passes", "no-precession"])
def test_a_reference_side_control_against_its_limit(sound, control, fails):
    """Control (c): the reference's own model with its Jones products
    made in one bfloat16 pass, in the written residual's place, is far
    outside the limit; in three passes inside it (nothing separates
    ``high`` in the solver cells).  Control (b), the reference's gains
    without precession, is REPORTED whichever way it falls: with four to
    six elements a station the tiny beam is 20 degrees wide and 0.2
    degrees move it by less than the limit; the configuration's file has
    the real cell's reading."""
    cell, run, _ = sound
    if "low" in control:
        control = {**control,
                   "low": pytest.importorskip("ml_dtypes").bfloat16}
    limit = run.config["limits"]["residual_vs_reference"]["limit"]
    a, b, settled, _ = cell.driver.compare(run, run.window.tiles)
    assert a <= limit
    # tiles 10 to 12 are the settled ones, and among all of the window's
    assert 0 < settled <= b
    assert settled <= run.config["limits"][
        "residual_over_noise.settled"]["limit"]
    a = cell.driver.compare(run, run.window.tiles, **control)[0]
    if fails is None:
        assert a > 0
    else:
        assert (a > 2 * limit) == fails, a


def test_the_chain_line_splits_a_tile_at_the_uv_cut(sound, monkeypatch):
    """``[chain]``: a tile's ``residual_over_noise`` apart for the rows
    under the ``cli``'s ``-x`` cut, which the solve never sees, and for
    the others; the tile's own number lies between the two.  The tiny
    layout has no baseline under 30 wavelengths (the cell has 18), so the
    cut is widened here; without ``-x``, or with no row under it, nothing
    is said."""
    cell, run, _ = sound
    tile = run.window.tiles[0]
    cell.driver.compare(run, [tile])        # parses the written solutions
    assert cell.driver.cut_split(run, tile) is None
    cli = list(run.config["cli"])
    cli[cli.index("-x") + 1] = "400"
    monkeypatch.setattr(run, "config", {**run.config, "cli": cli})
    rows, under, others = cell.driver.cut_split(run, tile)
    assert 0 < rows < cell.driver.observation(run).nrows
    b = cell.driver.compare(run, [tile])[1]
    assert min(under, others) <= b <= max(under, others)
    cli = [a for a in cli if a not in ("-x", "400")]
    monkeypatch.setattr(run, "config", {**run.config, "cli": cli})
    assert cell.driver.cut_split(run, tile) is None


def test_a_dataset_without_beam_npz_is_refused(tmp_path, monkeypatch):
    """With ``-B`` and no stored metadata the program makes up a station
    layout of its own (``resolve_beaminfo``, loudly); the driver refuses
    such a dataset before anything is timed."""
    from sagecal_tpu.io import dataset as ds
    create = ds.SimMS.create.__func__
    monkeypatch.setattr(
        ds.SimMS, "create",
        classmethod(lambda cls, path, tiles, beam_info=None:
                    create(cls, path, tiles)))
    cell = harness.Cell("dosage-beam-tiny", harness.load_json(CELLS))
    obs = reference_beam.Observation(cell.config, SEED)
    with pytest.raises(RuntimeError, match="no beam.npz"):
        cell.driver.write_observation(obs, str(tmp_path), 2)


# -- the readers on synthetic records -----------------------------------------

def fake_run(tmp_path, records, leaf=None, tiles=4):
    path = tmp_path / "diag.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    sl = None
    if leaf is not None:
        sl = types.SimpleNamespace(
            leaf=leaf, n_devices=1, scoped=lambda: bool(leaf),
            first_level=lambda f: (
                sum(v[0] for (ff, _), v in leaf.items() if ff == f),
                sum(v[1] for (ff, _), v in leaf.items() if ff == f)))
    return types.SimpleNamespace(
        diag_path=str(path), profile=None, slice=None, slice_tiles=tiles,
        profile_dir=str(tmp_path), diag_records=lambda: records, _scopes=sl,
        config={"tilesz": 10, "n_stations": 62},
        window=types.SimpleNamespace(t_open=100.0, t_drain=200.0))


TILE = {"t": 0.0, "tm": 150.0, "ev": "tile", "tile": 3}
BEAM = {"beam_mode": 1, "beam_elements": 48, "beam_sources": 1024,
        "coh_path": "xla"}


def fake_trace(device_modules=(), host_modules=()):
    """What ``xplane.load`` gives, as far as the reader looks: a device
    plane with an ``XLA Modules`` line, a host plane whose operations say
    their ``hlo_module``."""
    def ev(name, **stats):
        return types.SimpleNamespace(name=name, stats=stats)

    def line(name, events):
        return types.SimpleNamespace(name=name, events=events)

    planes = [types.SimpleNamespace(name="/host:CPU", lines=[line(
        "python3", [ev("fusion.1", hlo_module=m, hlo_op="fusion.1")
                    for m in host_modules] + [ev("a host span")])])]
    if device_modules:
        planes.append(types.SimpleNamespace(
            name="/device:TPU:0", lines=[
                line("XLA Ops", [ev("%fusion.1 = ...")]),
                line("XLA Modules", [ev(f"{m}({i})") for i, m
                                     in enumerate(device_modules)])]))
    return types.SimpleNamespace(planes=planes)


#: the HLO modules a trace stores: the two programs that form
#: coherencies, the solve, and a parent's coherency program, whose
#: tables are inside the map and so under ``rime/phasor``
TABLE = {
    "jit__lambda": {"fusion.1": "jit(<lambda>)/rime/beam/while/body/cos",
                    "fusion.2": "jit(<lambda>)/rime/phasor/while/body/mul"},
    "jit__residuals": {"fusion.7": "jit(_residuals)/jit(main)/rime/beam/sin",
                       "fusion.8": "jit(_residuals)/rime/residual/sub"},
    "jit__jit_sagefit": {"while.3": "jit(_jit_sagefit)/sage/sweep/inner/dot"},
    "jit_parent": {"fusion.1": "jit(f)/rime/phasor/while/body/rime/beam/cos"},
}


def test_tables_per_tile_is_counted_in_the_trace():
    """The programs with operations whose FIRST scope is ``rime/beam``,
    of those that ran: their executions on the device's ``XLA Modules``
    line, or on a host-only trace the modules its operations name."""
    mod = harness.load_module("layer_metrics", "beam_tables_per_tile.beam")
    programs = mod.table_programs(TABLE)
    assert programs == {"jit__lambda", "jit__residuals"}
    ran, counted = mod.executions(
        fake_trace(["jit__lambda", "jit__jit_sagefit", "jit__residuals"] * 4
                   + ["jit__lambda"]), programs)
    assert counted and dict(ran) == {"jit__lambda": 5, "jit__residuals": 4}
    # a program that carries the solve's coherencies to the residual
    ran, _ = mod.executions(
        fake_trace(["jit__lambda", "jit__jit_sagefit", "jit_carried"] * 4),
        programs)
    assert dict(ran) == {"jit__lambda": 4}
    ran, counted = mod.executions(
        fake_trace(host_modules=["jit__residuals", "jit__jit_sagefit",
                                 "jit__residuals", "jit__lambda"]), programs)
    assert not counted and dict(ran) == {"jit__lambda": 1,
                                         "jit__residuals": 1}
    assert mod.executions(fake_trace(["jit_parent"]), set()) == ({}, False)


@pytest.mark.parametrize("found, value, said", [
    ((["jit__lambda", "jit__residuals"], 2.036), 2,
     "jit__lambda, jit__residuals; 2.036 executions a tile"),
    ((["jit__lambda"], None), 1, "ran in the slice: jit__lambda\n"),
    # the parent's program, a run under -B 0, a run without a trace
    (None, None, "no program with operations under rime/beam ran"),
], ids=["the-cell", "carried-host-only", "nothing"])
def test_tables_per_tile_reader(tmp_path, capsys, found, value, said):
    mod = harness.load_module("layer_metrics", "beam_tables_per_tile.beam")
    run = fake_run(tmp_path, [{**TILE, **BEAM},
                              {**TILE, **BEAM, "tm": 50.0, "beam_mode": 7}])
    run._beam_table_programs = found
    assert mod.read(run) == value
    out = capsys.readouterr().out
    assert said in out
    # the window's records alone say what the beam is
    assert "beam_mode 1, beam_elements 48, beam_sources 1024, coh_path xla" \
        in out and "beam_mode 7" not in out


def test_tables_per_tile_reader_without_a_trace(tmp_path, capsys):
    mod = harness.load_module("layer_metrics", "beam_tables_per_tile.beam")
    assert mod.read(fake_run(tmp_path, [TILE])) is None
    assert "no program with operations under rime/beam ran in the trace" \
        in capsys.readouterr().out


@pytest.mark.parametrize("records, value, said", [
    ([{"ev": "phase", "name": "beam", "dur_s": 0.002, "tile": 3},
      {"ev": "phase", "name": "beam", "dur_s": 0.004, "tile": 4},
      {"ev": "phase", "name": "beam", "dur_s": 0.050, "tile": 5},
      {"ev": "phase", "name": "stage", "dur_s": 0.5, "tile": 3}],
     4.0, "max 50.0000 ms over 3 tile(s)"),
    ([{"ev": "phase", "name": "stage", "dur_s": 0.5, "tile": 3}], None,
     "no phase record named beam"),
    ([], None, "no phase record"),
], ids=["median", "the-parent", "no-records"])
def test_beam_stage_reader(tmp_path, capsys, records, value, said):
    mod = harness.load_module("layer_metrics", "beam_stage_ms.beam")
    got = mod.read(fake_run(tmp_path, records))
    assert got == (value if value is None else pytest.approx(value))
    assert said in capsys.readouterr().out


def test_beam_dev_reader_reads_its_scope_apart_or_nothing(tmp_path, capsys):
    """Leaf seconds under ``rime/beam`` a tile begun in the slice, with
    the nanoseconds a cosine-sine pair from the record beside it; NOTHING
    (never 0) where the trace has no operation under that scope, which is
    the parent's program: its tables are made inside the map over
    clusters, under ``rime/phasor``."""
    mod = harness.load_module("layer_metrics", "beam_dev_ms.beam")
    leaf = {("rime/beam", None): [0.2, 40], ("rime/phasor", None): [0.4, 90],
            ("sage/sweep", "inner"): [9.0, 5]}
    run = fake_run(tmp_path, [{**TILE, **BEAM}], leaf)
    run._beam_table_programs = (["jit__lambda", "jit__residuals"], 2.0)
    assert mod.read(run) == pytest.approx(50.0)
    out = capsys.readouterr().out
    # 1024 x 10 x 62 x 48 x 2 = 60 948 480 pairs in 50 ms
    assert "60948480 cosine-sine pairs a tile" in out
    assert "0.8204 ns a pair" in out
    # records without the fields (the parent's): the value, no pairs line
    run = fake_run(tmp_path, [TILE], leaf)
    run._beam_table_programs = None
    assert mod.read(run) == pytest.approx(50.0)
    assert "cosine-sine" not in capsys.readouterr().out
    parent = {("rime/phasor", None): [0.6, 130]}
    assert mod.read(fake_run(tmp_path, [TILE], parent)) is None
    assert "no operation under rime/beam" in capsys.readouterr().out
    assert mod.read(fake_run(tmp_path, [TILE], {})) is None
    assert mod.read(fake_run(tmp_path, [TILE], leaf, tiles=0)) is None
    assert mod.read(fake_run(tmp_path, [TILE], None)) is None


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
            {"t": 0.0, "ev": "tile", "tile": 3, "bubble_s": 0.25}]
    values = {"solve_s.beam": 3.0, "bubble_ms.beam": 250.0}
    for records in (recs, []):
        run = fake_run(tmp_path, records)
        run._scopes = None      # no profiler trace: device readers find none
        run.slice_tiles = 0
        assert new.read(run) == was.read(run) == (
            values.get(name) if records else None)
