"""The extended-source cell, ``predict-extended``: its entries in the
manifest and its files, held BY NAME and not by place; the tiny rehearsal
cell that stands for it (8 stations, 3 clusters x 8 sources of all five
kinds, shapelets of ``n0`` 4 and 3) traced and untraced, under the
program's read-ahead loop and under its synchronous one; the three
falsifications of the ``[control]`` line, each far outside the limit; a
program whose envelopes are 1 driven to ``correct: false``; each new
reader on synthetic records.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_extended.py -q
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness                  # noqa: E402
import reference_extended       # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "ext-cells.json")
TINY = "predict-extended-tiny"
SEED = 2 ** 31 + 51
CELL, CONFIG, MIX = ("predict-extended", "lofar62-m8x128-ext",
                     "predict-extended-tiles")
#: the new per-layer entries and the accepted reader each one is
RENAMED = {"phasor_dev_ms.ext": "phasor_dev_ms",
           "corrupt_dev_ms.ext": "corrupt_dev_ms",
           "device_ms_per_tile.ext": "device_ms_per_tile",
           "bubble_ms.ext": "bubble_ms.predict",
           "host_serial_ms.ext": "host_serial_ms",
           "chip_wait_ms.ext": "chip_wait_ms"}
OWN = ["shapelet_dev_ms.ext", "shapelet_slots.ext"]
#: in the manifest's order
NEW = list(RENAMED) + OWN
UNLISTED = ["compiles_in_window", "device_idle_pct", "hbm_peak_gb",
            "recompiles_in_window", "compile_s.setup"]
CHECKS = ["model_vs_reference", "short_model_vs_reference"]
#: every ``tile`` record of the tiny run
RECORD_FIELDS = {"sources_point": 7, "sources_gaussian": 9,
                 "sources_disk": 3, "sources_ring": 3, "sources_shapelet": 2,
                 "shapelet_n0max": 4, "shapelet_slots": 24,
                 "coh_path": "xla", "beam_mode": 0, "mode": 1,
                 "clusters_in_model": 3}


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
    assert "-F 1" in configs[CONFIG]["source"]
    assert "G/D/R/S" in configs[CONFIG]["source"]
    assert len(configs[CONFIG]["source"]) <= 200 >= len(cells[CELL]["why"])
    assert "TBD" not in json.dumps([cells[CELL], configs[CONFIG]])
    # one cell of this configuration, one configuration of this file
    assert [w["name"] for w in man["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in man["configs"]
            if c["file"] == configs[CONFIG]["file"]] == [CONFIG]
    real = harness.Cell(CELL)
    assert real.chips == 1 and real.traffic["driver"] == "predict_extended"
    assert not getattr(real.driver, "BOUNDARY_OUTSIDE_SPANS", False)
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    assert [m["name"] for m in real.metrics("per_layer")] == UNLISTED + NEW
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
    tiny = harness.Cell(TINY, harness.load_json(CELLS))
    assert tiny.reports_as == CELL
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"]
    assert tiny.config["limits"] == real.config["limits"]


def test_the_configuration_is_predict_m8x128s_on_a_sky_that_is_not_points():
    conf = harness.Cell(CELL).config
    base = harness.Cell("predict-m8x128").config
    own = harness.load_json(ROOT, f"benchmarks/configs/{CONFIG}.json")
    assert "base" not in own        # a copy, every key its own
    for k in ("n_stations", "n_clusters", "n_sources_per_cluster", "tilesz",
              "tdelta_s", "freq_hz", "chan_width_hz", "ra0_rad", "dec0_rad",
              "n_tiles_on_disk", "layout_seed", "log_flux_mean",
              "jones_scale", "jones_per_interval", "noise_sigma",
              "precision", "reduced"):
        assert conf[k] == base[k], k
    assert conf["cli"] == base["cli"] + ["-F", "1"]
    assert conf["sky_format"] == 1 and conf["f0_hz"] == 130e6 != conf[
        "freq_hz"]
    assert conf["sources"] == {"P": 68, "G": 54, "D": 3, "R": 3}
    assert sum(conf["sources"].values()) == conf["n_sources_per_cluster"]
    assert conf["shapelet_n0"] == [10, 8, 6, 4]
    assert conf["spectra"]["flat_share"] == 1 / 16
    assert {"why assumed", "sources", "extents", "shapelets", "spectra",
            "sky_seed"} <= set(own["assumed"])
    assert "3c196.sky.txt" in own["assumed"]["why assumed"]
    assert conf["guarantees"][:2] == base["guarantees"][:2]
    assert "extended sources" in conf["guarantees"][2]
    assert list(conf["limits"]) == CHECKS
    for lim in conf["limits"].values():
        assert all(lim[k] for k in ("what", "sound", "control", "limit",
                                    "why"))
        assert "TBD" not in json.dumps(lim)
    # the mix is predict-tiles' but for its driver (and its prose)
    mix, was = harness.Cell(CELL).traffic, harness.Cell(
        "predict-m8x128").traffic
    for k, v in was.items():
        if k not in ("name", "driver", "loop"):
            assert mix[k] == v, k
    assert mix["warmup_tiles"] == 5 and mix["profile_slice_s"] == 3.0
    assert "profile_tiles" not in mix


def test_the_observation_is_what_the_configuration_says():
    """8 x 128 sources of the five kinds in the configuration's counts,
    four shapelets of ``n0`` 10, 8, 6, 4 in the first four clusters, each
    its cluster's brightest at zero spacing, sources on both sides of
    ``PROJ_CUT``, one in sixteen on the flux law's unscaled branch; the
    same sky in every seed, another hour angle."""
    conf = harness.Cell(CELL).config
    obs = reference_extended.Observation(conf, SEED)
    sky = obs.sky
    assert sky.ll.shape == (8, 128) and obs.nrows == 18910
    assert sky.counts() == {"point": 540, "gaussian": 432, "disk": 24,
                            "ring": 24, "shapelet": 4}
    shp = sky.kind == reference_extended.SHAPELET
    assert list(shp.sum(axis=1)) == [1, 1, 1, 1, 0, 0, 0, 0]
    assert list(sky.n0[shp]) == [10, 8, 6, 4]
    assert sorted(obs.modes) == sorted(
        n for row in sky.names for n in row if n[0] == "S")
    far = (sky.nn + 1 < reference_extended.PROJ_CUT) \
        & (sky.kind != reference_extended.POINT)
    assert 0 < far.sum() < 0.5 * (~far).sum() and far[shp].sum() == 1
    flat = sky.si == 0
    assert 40 <= flat.sum() <= 90 and (sky.si1[flat] != 0).all()
    assert (sky.f0 == 130e6).all() and obs.freq == 150e6
    assert (sky.flux[shp] < 0).any()            # the sign is kept
    other = reference_extended.Observation(conf, SEED + 1)
    assert other.sky_lines == obs.sky_lines and other.modes == obs.modes
    assert other.ha0 != obs.ha0
    # resolved: the long baselines see a few percent of what the short do
    u, v, w = obs.geometry(0)[:3]
    length = np.hypot(u, v)
    coh = np.abs(obs.coherencies(0, rows=np.argsort(length)[[0, -1]]))
    assert (coh[:, 1] < 0.5 * coh[:, 0]).all()


# -- what older tests pin by place, on the manifest as it was -----------------

def load_test_module(name):
    spec = importlib.util.spec_from_file_location(
        "as_it_was_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case, args", [
    ("test_what_pr44_pins_by_place_holds_less_this_prs_entries",
     ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
      ("test_the_cell_is_files_and_entries",))),
    ("test_what_pr44_pins_by_place_holds_less_this_prs_entries",
     ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
      ("test_the_configuration_is_the_sources_at_eight_subbands",))),
    ("test_what_pr44_pins_by_place_holds_less_this_prs_entries",
     ("test_what_pr42_pins_by_place_holds_less_this_prs_entries",
      ("test_pr40s_entries_still_list_the_older_cells_and_only_ours_"
       "follow",))),
    ("test_what_pr44_pins_by_place_holds_less_this_prs_entries",
     ("test_what_older_cells_pin_by_place_holds_less_everything_since",
      ("test_subtract",))),
    ("test_what_pr44_pins_by_place_holds_less_this_prs_entries",
     ("test_the_older_cells_lists_are_as_pr42_held_them", None)),
    ("test_the_older_cells_lists_are_as_pr44_held_them", None),
], ids=["fold-cell", "fold-configuration", "fold-pr40", "subtract",
        "older-lists-pr42", "older-lists-pr44"])
def test_what_pr48_pins_by_place_holds_less_this_prs_entries(
        case, args, monkeypatch):
    """``test_beam_cell.py`` holds PR 48's cell, configuration and fifteen
    entries as the LAST of their lists (``workloads``, ``configs[-3:]``,
    the count of eight), and runs what ``test_hybrid.py`` pins by place
    on the manifest less PR 48's entries only; this PR's go behind them
    (the driver refuses any other place), so those six cases fail on the
    manifest as it is (``tests/test_benchmarks_suite.py``:
    ``OVERTAKEN``).  Each runs whole here on the manifest less this PR's
    cell, configuration and eight entries: what it guards stays guarded,
    case for case."""
    beam = load_test_module("test_beam_cell")
    load_test_module("test_hybrid").manifest_less(
        monkeypatch, NEW, [CELL], [CONFIG])
    if args is None:
        getattr(beam, case)()
    else:
        getattr(beam, case)(*args, monkeypatch)


def test_the_older_cells_lists_are_as_pr48_held_them():
    """By name: every cell there was reports the per-layer entries it
    reported; PR 48's fifteen still list the one cell and this PR's eight
    follow them, and nothing else; the cells and the configurations stand
    in the order they came, one cell on four chips."""
    beam, hyb, fold = (load_test_module(n) for n in (
        "test_beam_cell", "test_hybrid", "test_fold"))
    man = harness.load_json(ROOT, "BENCHMARK.json")
    for cell, names in fold.OLDER_LISTS.items():
        assert [m["name"] for m in harness.Cell(cell).metrics("per_layer")] \
            == names
    for mod in (fold, hyb, beam):
        assert sorted(m["name"] for m in
                      harness.Cell(mod.CELL).metrics("per_layer")) \
            == sorted(beam.UNLISTED + list(mod.NEW)), mod.CELL
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(beam.NEW[0])
    assert names[at:at + len(beam.NEW)] == beam.NEW
    assert names[at + len(beam.NEW):] == NEW
    for m in man["per_layer"][at:at + len(beam.NEW)]:
        assert m["workloads"] == [beam.CELL]
    assert [w["name"] for w in man["workloads"]] \
        == fold.OLDER + [fold.CELL, hyb.CELL, beam.CELL, CELL]
    assert [c["name"] for c in man["configs"]][-4:] \
        == [fold.CONFIG, hyb.CONFIG, beam.CONFIG, CONFIG]
    assert len(man["workloads"]) == 9 and len(man["configs"]) == 9
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] \
        == ["admm-f4-mesh"]


# -- the tiny cell, end to end ------------------------------------------------

def run_cell(capsys, trace, seconds="1"):
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", TINY,
                      "--seed", str(SEED), "--seconds", seconds,
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.fixture
def fresh_programs():
    """A sound run's traced program is cached for the process; a run
    over a program broken underneath must not be served it, nor leave
    its own behind."""
    from sagecal_tpu.serve import cache as pcache
    pcache.PROGRAMS.clear()
    yield
    pcache.PROGRAMS.clear()


def test_sound_tiny_cell_traced_reports_the_eight_and_the_record_fields(
        capsys):
    line, out = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["attempted"] >= 8 and list(line["checks"]) == CHECKS
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    assert all(got[n] is not None for n in NEW)
    assert set(UNLISTED) - {"hbm_peak_gb"} <= set(got)
    assert got["compiles_in_window"] == got["recompiles_in_window"] == 0
    assert got["shapelet_slots.ext"] == 24          # 3 x 8, for 2 shapelets
    assert 0 < got["shapelet_dev_ms.ext"] < got["phasor_dev_ms.ext"] \
        < got["device_ms_per_tile.ext"]
    # the basis is read apart from the rest of the source sum
    assert "[scope]   rime/phasor/shapelet " in out
    assert "[scope] */shapelet: " in out
    assert ("sources point 7, gaussian 9, disk 3, ring 3, shapelet 2; "
            "shapelet_n0max 4") in out
    assert "[control] seed" in out and "every source a point" in out
    from sagecal_tpu.diag import trace as dtrace
    diag = os.path.join(BENCH, ".work", TINY, "diag.jsonl")
    tiles = [r for r in dtrace.read(diag) if r.get("ev") == "tile"]
    assert len(tiles) >= line["attempted"] + 5
    for r in tiles:
        assert {k: r[k] for k in RECORD_FIELDS} == RECORD_FIELDS
    # the files the program read are the reference's
    work = os.path.join(BENCH, ".work", TINY)
    cell = harness.Cell(TINY, harness.load_json(CELLS))
    obs = reference_extended.Observation(cell.config, SEED)
    assert open(os.path.join(work, "sky.txt")).read().splitlines() \
        == obs.sky_lines
    for name, text in obs.modes.items():
        assert open(os.path.join(work, name + ".fits.modes")).read() == text
    assert "Coherency path: xla" in open(
        os.path.join(work, "program.log")).read()


def test_sound_tiny_cell_untraced_reports_the_end_to_end_metrics(capsys):
    line, out = run_cell(capsys, trace=0, seconds="0.5")
    assert line["correct"] is True and line["failed"] == 0, line
    assert sorted(line["metrics"]) == ["setup_s", "tile_s.p50", "vis_per_s"]
    assert list(line["checks"]) == CHECKS
    assert "[control] seed" in out


def in_process(cli_more=()):
    """The tiny cell run untraced in this process, as ``limits.py`` runs
    a cell: (cell, run, outcome, checks)."""
    import run as runner
    assert runner.open_backend(True, 1) is not None
    cell = harness.Cell(TINY, harness.load_json(CELLS))
    cell.config = {**cell.config, "cli": cell.config["cli"] + list(cli_more)}
    run = runner.Run(cell, SEED, 0.5, trace=False)
    outcome = cell.driver.run(run)
    return cell, run, outcome, cell.driver.check(run)


@pytest.mark.parametrize("cli_more, depth", [((), 1), (("--prefetch", "0"),
                                                       0)],
                         ids=["read-ahead", "synchronous"])
def test_the_sound_program_is_correct_under_either_loop(
        capsys, cli_more, depth):
    """``run_simulation`` reads two tiles ahead of the device and writes
    from a thread of its own (the default, PR 46), or does one thing at a
    time under ``--prefetch 0``: the kept rows follow the tile that is
    written either way."""
    cell, run, outcome, checks = in_process(cli_more)
    assert outcome["failed"] == 0 and outcome["attempted"] >= 8
    assert [c.name for c in checks] == CHECKS and all(c.ok for c in checks)
    log = open(os.path.join(run.work, "program.log")).read()
    assert log.count("simulated (mode=1)") >= outcome["attempted"] + 5
    assert isinstance(run.obs, reference_extended.Observation)
    assert sorted(run.kept) == list(range(5, 5 + outcome["attempted"]))


def test_each_falsification_is_five_limits_away(capsys):
    """The ``[control]`` line's three: the reference's own model with
    every source a point, without the shapelet sources, with the spectrum
    at ``f0``, in the program's place, each at least five times the limit
    of ``model_vs_reference``; the fourth (the flux law by the parse rule)
    is reported whichever way it falls."""
    cell, run, _, checks = in_process()
    limit = run.config["limits"]["model_vs_reference"]["limit"]
    assert checks[0].name == "model_vs_reference" and checks[0].value < limit
    got = cell.driver.falsified(run, run.window.tiles[0])
    assert list(got) == [key for _, key in cell.driver.CONTROLS]
    for key in ("points", "no_shapelets", "at_f0"):
        assert got[key] > 5 * limit, (key, got)
    assert 0 < got["any_term"] < got["at_f0"]
    said = capsys.readouterr().out
    assert f"every source a point {got['points']:.4g}" in said


@pytest.mark.parametrize("what", ["every-envelope", "the-shapelets"])
def test_a_program_whose_envelopes_are_one_is_not_correct(
        capsys, monkeypatch, fresh_programs, what):
    """Broken underneath a whole run: the source sum without its
    envelopes (a sky of points), or without the shapelet basis alone."""
    from sagecal_tpu.rime import envelopes
    if what == "every-envelope":
        monkeypatch.setattr(envelopes, "apply_envelopes",
                            lambda phasor, *a, **k: phasor)
    else:
        import jax.numpy as jnp
        monkeypatch.setattr(
            envelopes, "shapelet", lambda u, v, w, *a, **k: jnp.ones(
                jnp.broadcast_shapes(u.shape, a[0].shape), u.dtype))
    line, _ = run_cell(capsys, trace=0, seconds="0.5")
    assert line["correct"] is False and line["failed"] == 0
    bad = line["checks"]["model_vs_reference"]
    assert bad["value"] > 5 * bad["limit"], line["checks"]


# -- the readers on synthetic records -----------------------------------------

#: a run as far as a reader looks, from synthetic records and leaf seconds
fake_run = load_test_module("test_beam_cell").fake_run


TILE = {"t": 0.0, "tm": 150.0, "ev": "tile", "tile": 3}
KINDS = {"sources_point": 540, "sources_gaussian": 432, "sources_disk": 24,
         "sources_ring": 24, "sources_shapelet": 4, "shapelet_n0max": 10}


@pytest.mark.parametrize("records, value, said", [
    ([{**TILE, **KINDS, "shapelet_slots": 1024},
      {**TILE, **KINDS, "shapelet_slots": 1024, "tile": 4},
      {**TILE, **KINDS, "shapelet_slots": 7, "tm": 50.0}], 1024,
     "sources point 540, gaussian 432, disk 24, ring 24, shapelet 4; "
     "shapelet_n0max 10"),
    ([{**TILE, **KINDS, "shapelet_slots": 4}], 4, "shapelet 4"),
    # the parent's records, and a run without records
    ([TILE], None, "no tile record with shapelet_slots"),
    ([], None, "no tile record with shapelet_slots"),
], ids=["the-cell", "where-there-is-a-shapelet", "the-parent", "no-records"])
def test_shapelet_slots_reader(tmp_path, capsys, records, value, said):
    mod = harness.load_module("layer_metrics", "shapelet_slots.ext")
    assert mod.read(fake_run(tmp_path, records)) == value
    assert said in capsys.readouterr().out


def test_shapelet_dev_reader_reads_its_scope_apart_or_nothing(
        tmp_path, capsys):
    """Leaf seconds whose second level is ``shapelet`` a tile begun in
    the slice, in milliseconds; NOTHING (never 0) where the trace has no
    operation under that name, which is the parent's program (its basis
    is booked under ``rime/phasor``) and any model without a shapelet."""
    mod = harness.load_module("layer_metrics", "shapelet_dev_ms.ext")
    leaf = {("rime/phasor", "shapelet"): [1.2, 400],
            ("rime/phasor", None): [0.4, 90],
            ("rime/corrupt", None): [0.01, 8]}
    assert mod.read(fake_run(tmp_path, [TILE], leaf)) == pytest.approx(300.0)
    assert "*/shapelet: 1.2 s in 400 leaf operations over 4 tile(s)" \
        in capsys.readouterr().out
    parent = {("rime/phasor", None): [1.6, 490]}
    assert mod.read(fake_run(tmp_path, [TILE], parent)) is None
    assert "no leaf operation under shapelet" in capsys.readouterr().out
    assert mod.read(fake_run(tmp_path, [TILE], {})) is None
    assert mod.read(fake_run(tmp_path, [TILE], leaf, tiles=0)) is None
    assert mod.read(fake_run(tmp_path, [TILE], None)) is None


def test_the_driver_gives_the_scope_table_its_second_level():
    """``scopes.scope_path`` knows its second levels from a fixed list
    that this PR may not edit; the cell's driver adds ``shapelet`` to it
    as it is loaded, before any trace is read.  The operation names are
    the compiled program's (a described v5e, cpu)."""
    import scopes
    harness.Cell(CELL)          # loads the driver
    assert scopes.scope_path(
        "jit(sim_fn)/rime/phasor/while/body/closed_call/rime/phasor/"
        "vmap(shapelet)/reduce_sum") == ("rime/phasor", "shapelet")
    assert scopes.scope_path(
        "jit(sim_fn)/rime/phasor/while/body/closed_call/rime/phasor/"
        "vmap(jit(_where))/select_n") == ("rime/phasor", None)
    assert scopes.scope_path(
        "jit(_jit_sagefit)/sage/sweep/inner/while/body/mul") \
        == ("sage/sweep", "inner")
    assert scopes.SECOND.count("shapelet") == 1


@pytest.mark.parametrize("name", sorted(RENAMED))
def test_renamed_readers_are_the_readers_that_exist(tmp_path, name):
    """Each gives what the accepted reader gives, under this cell's name,
    with that reader's unit, layer and end-to-end metric; and nothing
    where that one finds nothing."""
    new = harness.load_module("layer_metrics", name)
    was = harness.load_module("layer_metrics", RENAMED[name])
    assert (new.NAME, new.UNIT, new.LAYER, new.MOVES) == (
        name, was.UNIT, was.LAYER, was.MOVES)
    recs = [{**TILE, "bubble_s": 0.25}, {**TILE, "tile": 4, "bubble_s": 0.75},
            {**TILE, "tm": 50.0, "bubble_s": 9.0}]     # before the window
    values = {"bubble_ms.ext": 500.0}
    for records in (recs, []):
        run = fake_run(tmp_path, records)
        run._scopes = None      # no profiler trace: device readers find none
        run.slice_tiles = 0
        assert new.read(run) == was.read(run) == (
            values.get(name) if records else None)
