"""The folded consensus cell's own tests: ``admm-f8-fold`` as files and
entries held BY NAME (and what the tests that pin lists by place
guarded, on the manifest less what later PRs appended); the tiny
rehearsal cell that stands for it traced and untraced; the ways its
``correct`` has to come out false; the two readers of the fold's
counters on faked records.

A skipped consensus is broken underneath a whole run.  The Z file half a
percent off, the lost interval and the bfloat16 control are made on
what a sound run left on disk, which is all ``check`` reads (the same
three underneath whole runs: ``test_consensus.py``, ``test_benchmark.py``).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_fold.py -q
"""

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness                      # noqa: E402
import reference                    # noqa: E402
import reference_consensus as refc  # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "fold-cells.json")
SEED = 2 ** 31 + 5
CELL, CONFIG, MIX = ("admm-f8-fold", "lofar62-f8-fold-m8x3",
                     "consensus-fold-intervals")
#: this PR's per-layer entries, in the order they were appended
NEW = ["solve_s.fold", "jupdate_dev_s.fold", "consensus_dev_ms.fold",
       "bubble_ms.fold", "admm_iters.fold", "host_serial_ms.fold",
       "chip_wait_ms.fold", "lockstep_pct.fold", "jupdate_trips.fold"]
#: PR 40's two, which list every cell there was then
PR40 = ["host_serial_ms", "chip_wait_ms"]
OLDER = ["cal-m8x3", "predict-m8x128", "admm-f4-mesh", "cal-t120",
         "subtract-m8x128"]
EVERY = ["compiles_in_window", "device_idle_pct", "hbm_peak_gb",
         "recompiles_in_window", "compile_s.setup"]
#: every older cell's own per-layer list, as PR 40 left it
OLDER_LISTS = {
    "cal-m8x3": [
        "compiles_in_window", "bubble_ms.cal", "solve_s", "solver_trips",
        "residual_ms", "device_idle_pct", "hbm_peak_gb", "sweep_dev_s",
        "refine_dev_s", "solve_ops_per_tile", "recompiles_in_window",
        "compile_s.setup", "refine_passes", "tcg_trips", "row_passes"]
    + PR40,
    "predict-m8x128": [
        "compiles_in_window", "io_ms.predict", "device_ms_per_tile",
        "device_idle_pct", "hbm_peak_gb", "phasor_dev_ms", "corrupt_dev_ms",
        "bubble_ms.predict", "recompiles_in_window", "compile_s.setup"]
    + PR40,
    "admm-f4-mesh": EVERY + [
        "solve_s.admm", "bubble_ms.admm", "admm_iters", "jupdate_dev_s",
        "consensus_dev_ms", "collective_ms.admm", "chip_skew_pct"] + PR40,
    "cal-t120": EVERY + [
        "solve_dispatches.t120", "solve_s.t120", "sweep_dev_s.t120",
        "refine_dev_s.t120", "bubble_ms.t120"] + PR40,
    "subtract-m8x128": EVERY + [
        "subtract_dev_ms", "clusters_in_model.sub",
        "device_ms_per_tile.sub", "phasor_dev_ms.sub", "corrupt_dev_ms.sub",
        "bubble_ms.sub"] + PR40,
}


def manifest():
    return harness.load_json(ROOT, "BENCHMARK.json")


# -- the manifest, by name -----------------------------------------------------

def test_the_cell_is_files_and_entries():
    man = manifest()
    real = harness.Cell(CELL)
    tiny = harness.Cell("fold-tiny", harness.load_json(CELLS))
    # what this PR appended stands last in its list
    assert man["configs"][-1]["name"] == CONFIG
    assert man["workloads"][-1] == real.entry
    assert [m["name"] for m in man["per_layer"]][-len(NEW):] == NEW
    for m in man["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL]
    assert real.entry == {**real.entry, "config": CONFIG, "traffic": MIX,
                          "chips": 1}
    assert real.traffic["driver"] == "consensus"
    assert real.traffic["profile_tiles"] == 1
    assert real.traffic["warmup_tiles"] == 2
    assert real.traffic["check_tiles"] == 64
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    assert [m["name"] for m in real.metrics("per_layer")] == EVERY + NEW
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"]
    assert set(tiny.config["limits"]) == set(real.config["limits"])
    # six cells, one of them on four chips
    assert len(man["workloads"]) == 6
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] == [
        "admm-f4-mesh"]
    # the collective and the skew have nothing to read on one chip
    for name in ("collective_ms.admm", "chip_skew_pct"):
        assert CELL not in next(m for m in man["per_layer"]
                                if m["name"] == name)["workloads"]


def test_the_configuration_is_the_sources_at_eight_subbands():
    real, f4 = harness.Cell(CELL), harness.Cell("admm-f4-mesh")
    base = harness.Cell("cal-m8x3")
    conf = real.config
    own = harness.load_json(ROOT, f"benchmarks/configs/{CONFIG}.json")
    entry = manifest()["configs"][-1]
    assert conf["architecture"] is None
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    for part in ("dosage-mpi.sh:6", "sagecal_master.cpp:155-221"):
        assert part in conf["source"]
    assert conf["subband_freqs_hz"] == [
        1e6 * f for f in range(120, 177, 8)]
    cli, cli4 = conf["cli"], f4.config["cli"]
    # the source's ten iterations, uncut; every other flag as the f4 file
    assert cli[cli.index("-A") + 1] == "10"
    assert cli4[cli4.index("-A") + 1] == "3"
    at = cli.index("-A") + 1
    assert cli[:at] + cli[at + 1:] == cli4[:at] + cli4[at + 1:]
    assert real.driver.npoly(conf) == 2
    assert sorted(own["reduced"]) == sorted(entry["reduced"]) == [
        "beam", "n_subbands", "n_tiles_on_disk"]
    assert "admm_iterations" not in own["reduced"]
    assert "Scurrent" in own["departure"]
    for k in ("n_stations", "n_clusters", "n_sources_per_cluster", "tilesz",
              "tdelta_s", "chan_width_hz", "noise_sigma", "jones_scale",
              "layout_seed", "sky_seed", "precision"):
        assert conf[k] == base.config[k] and k not in own
    assert conf["cluster_rho"] == f4.config["cluster_rho"] == 5.0
    assert 20 <= conf["n_tiles_on_disk"] <= 60
    assert len(conf["guarantees"]) == 3
    assert all("eight" in g for g in conf["guarantees"][:2])
    # the four limits are its own: each with its readings and its reason
    assert set(own["limits"]) == {
        "residual_vs_reference", "residual_over_noise",
        "consensus_over_noise", "consensus_primal"}
    for lim in own["limits"].values():
        assert set(lim) == {"what", "sound", "control", "limit", "why"}
        assert "PR 42" in lim["sound"]


def test_pr40s_entries_still_list_the_older_cells_and_only_ours_follow():
    layer = manifest()["per_layer"]
    names = [m["name"] for m in layer]
    at = names.index(PR40[0])
    assert names[at:at + 2] == PR40 and names[at + 2:] == NEW
    for m in layer[at:at + 2]:
        assert m["workloads"] == OLDER
    assert [w["name"] for w in manifest()["workloads"]] == OLDER + [CELL]


@pytest.mark.parametrize("cell", OLDER)
def test_an_older_cells_per_layer_list_is_unchanged(cell):
    assert [m["name"] for m in harness.Cell(cell).metrics("per_layer")] \
        == OLDER_LISTS[cell]


@pytest.mark.parametrize("module", ["test_subtract", "test_t120",
                                    "test_consensus"])
def test_what_pins_lists_by_place_holds_less_what_was_appended_since(
        module, monkeypatch):
    """``test_the_cell_is_files_and_entries`` of these three holds a
    cell's whole per-layer list and, one of them, the LAST entries of
    ``configs``, ``workloads`` and ``per_layer``; only a ``benchmark``
    PR may edit them.  ``test_host_spans.py`` runs them on the manifest
    less PR 40's two entries, which the one that pins the lists' ends no
    longer survives.  Here each runs whole on the manifest less
    everything appended since it was written: PR 40's two entries, this
    PR's nine, its cell and its configuration."""
    load = harness.load_json

    def as_it_was(*parts):
        out = load(*parts)
        if parts[-1] == "BENCHMARK.json":
            out["per_layer"] = [m for m in out["per_layer"]
                                if m["name"] not in PR40 + NEW]
            out["workloads"] = [w for w in out["workloads"]
                                if w["name"] != CELL]
            out["configs"] = [c for c in out["configs"]
                              if c["name"] != CONFIG]
        return out

    monkeypatch.setattr(harness, "load_json", as_it_was)
    spec = importlib.util.spec_from_file_location(
        "as_it_was_" + module, os.path.join(HERE, module + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.test_the_cell_is_files_and_entries()


@pytest.mark.parametrize("name", NEW[:7])
def test_a_twin_is_its_readers_own_under_this_cells_name(name):
    was = {"solve_s.fold": "solve_s.admm", "bubble_ms.fold": "bubble_ms.admm"
           }.get(name, name[:-len(".fold")])
    twin = harness.load_module("layer_metrics", name)
    orig = harness.load_module("layer_metrics", was)
    assert twin.NAME == name
    assert (twin.UNIT, twin.LAYER, twin.MOVES) == (
        orig.UNIT, orig.LAYER, orig.MOVES)
    entry = next(m for m in manifest()["per_layer"] if m["name"] == was)
    assert CELL not in entry["workloads"]


# -- the tiny cell -------------------------------------------------------------

def run_cell(capsys, trace=0, seconds="120"):
    """``--seconds`` beyond the four window intervals the tiny
    observation has: the window is all of them."""
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "fold-tiny",
                      "--seed", str(SEED), "--seconds", seconds,
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound untraced run, kept: the ``Run`` whose files the
    post-hoc controls alter, and its driver.  The files are moved out of
    the cell's work directory, which the next run of the cell empties."""
    import run as runner
    cell = harness.Cell("fold-tiny", harness.load_json(CELLS))
    assert runner.open_backend(True, 1) is not None
    run = runner.Run(cell, SEED, 120.0, trace=False)
    outcome = cell.driver.run(run)
    kept = str(tmp_path_factory.mktemp("sound") / "work")
    shutil.copytree(run.work, kept)
    run.ms_paths = [p.replace(run.work, kept) for p in run.ms_paths]
    run.z_path = run.z_path.replace(run.work, kept)
    run.work = kept
    return run, cell.driver, outcome


def correct(checks):
    return bool(checks) and all(c.ok for c in checks)


def test_sound_tiny_cell_is_correct_untraced(sound):
    run, driver, outcome = sound
    assert outcome == {"attempted": 4, "failed": 0}
    checks = driver.check(run)
    assert correct(checks), [c.line() for c in checks]
    assert "Subbands: 4 over 1 device(s)" in open(
        os.path.join(run.work, "program.log")).read()
    assert set(run.window.end_to_end()) >= {"vis_per_s", "tile_s.p50",
                                            "setup_s"}


def test_sound_tiny_cell_is_correct_traced(capsys):
    line, out = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # one CPU device has no memory statistics
    assert set(m) == set(EVERY + NEW) - {"hbm_peak_gb"}
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
    recs = [r for r in dtrace.read(os.path.join(
        BENCH, ".work", "fold-tiny", "diag.jsonl")) if r["ev"] == "tile"]
    assert len(recs) == 6
    for r in recs:
        assert (r["fold"], r["ndev"], r["plan"]) == (4, 1, "traced")
        assert r["jupdate_trips"] > 0 and 0 <= r["lockstep_pct"] < 100


def test_a_consensus_that_is_skipped_is_not_correct(capsys, monkeypatch):
    """As ``test_consensus.py``'s, underneath the folded run: the J
    updates lose their consensus term and Z is never fitted."""
    from sagecal_tpu.consensus import admm as cadmm
    real = cadmm.sage.sagefit
    monkeypatch.setattr(cadmm.sage, "sagefit",
                        lambda *a, admm=None, **kw: real(*a, **kw))
    monkeypatch.setattr(cadmm.cpoly, "z_from_contributions",
                        lambda zsum, Bi: 0.0 * zsum)
    line, _ = run_cell(capsys)
    assert line["correct"] is False
    for name in ("consensus_primal", "consensus_over_noise"):
        assert line["checks"][name]["value"] \
            > 3 * line["checks"][name]["limit"]
    rn = line["checks"]["residual_over_noise"]
    assert rn["value"] < rn["limit"]


@contextlib.contextmanager
def altered(path):
    """``path`` may be rewritten inside; it is as it was afterwards."""
    keep = path + ".sound"
    shutil.copy(path, keep)
    try:
        yield
    finally:
        shutil.move(keep, path)


def by_name(checks):
    return {c.name: c for c in checks}


def test_a_z_file_off_by_half_a_percent_is_not_correct(sound):
    run, driver, _ = sound
    with altered(run.z_path):
        lines = open(run.z_path).read().splitlines()
        header = next(i for i, ln in enumerate(lines)
                      if not ln.startswith("#"))
        with open(run.z_path, "w") as f:
            for i, ln in enumerate(lines):
                if i > header:          # a data row: counter, columns
                    t = ln.split()
                    ln = " ".join([t[0]] + [
                        f"{1.005 * float(x):e}" for x in t[1:]])
                f.write(ln + "\n")
        checks = driver.check(run)
    assert not correct(checks)
    c = by_name(checks)
    assert not c["consensus_over_noise"].ok
    # the subbands' own solutions and residuals were left alone
    assert c["residual_over_noise"].ok and c["residual_vs_reference"].ok
    assert correct(driver.check(run))       # and the files are back


def test_an_interval_missing_from_one_subband_is_not_correct(sound):
    run, driver, _ = sound
    path = refc.subband_solutions_path(run.ms_paths[1])
    rows = 8 * int(run.config["n_stations"])
    with altered(path):
        lines = open(path).read().splitlines()
        with open(path, "w") as f:
            f.write("\n".join(lines[:-rows]) + "\n")
        checks = driver.check(run)
    assert not correct(checks)
    assert all(c.value != c.value for c in checks)      # every one a NaN
    assert "sb1: 5" in checks[0].note


def test_a_residual_from_a_bfloat16_model_is_not_correct(sound):
    """The control: one subband's residual of one interval written as
    the data minus the model with its Jones products made in bfloat16,
    one pass, under that subband's own written solutions."""
    bfloat16 = pytest.importorskip("ml_dtypes").bfloat16
    run, driver, _ = sound
    subs = refc.subbands(run.config, run.seed)
    k, t = 2, run.window.tiles[1]
    ms = run.ms_paths[k]
    j = reference.read_solutions(refc.subband_solutions_path(ms))[t]
    u, v, w, s1, s2 = subs[k].geometry(t)
    coh = reference.coherencies(subs[k].sky, u, v, w, subs[k].freq,
                                subs[k].fdelta)
    tile = os.path.join(ms, f"tile{t:05d}.npz")
    with altered(tile):
        with np.load(tile) as z:
            cols = {name: z[name] for name in z.files}
        x = cols["x"][:, 0]
        low = x - reference.model(j, coh, s1, s2, dtype=bfloat16)
        cols["x_corrected_data"] = low[:, None].astype(
            cols["x_corrected_data"].dtype)
        np.savez(tile, **cols)
        checks = driver.check(run)
    assert not correct(checks)
    c = by_name(checks)
    assert c["residual_vs_reference"].value \
        > 3 * c["residual_vs_reference"].limit
    assert c["consensus_primal"].ok


# -- the two readers, on faked records ----------------------------------------

def fake_run(tmp_path, recs):
    path = tmp_path / "diag.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return types.SimpleNamespace(
        diag_path=str(path),
        window=types.SimpleNamespace(t_open=10.0, t_drain=20.0))


def tile(tm, **more):
    return {"t": 0.0, "ev": "tile", "tm": tm, "tile": int(tm), **more}


def test_the_readers_take_the_mean_over_the_windows_intervals(
        tmp_path, capsys):
    said = dict(fold=8, ndev=1, plan="traced")
    run = fake_run(tmp_path, [
        tile(5.0, lockstep_pct=90.0, jupdate_trips=1, **said),  # warm-up
        tile(11.0, lockstep_pct=10.0, jupdate_trips=3000, **said),
        tile(12.0, lockstep_pct=20.0, jupdate_trips=5000, **said),
        {"t": 0.0, "ev": "phase", "tm": 12.5, "name": "solve",
         "dur_s": 1.0},
        tile(25.0, lockstep_pct=90.0, jupdate_trips=1, **said)])  # drained
    lock = harness.load_module("layer_metrics", "lockstep_pct.fold")
    trips = harness.load_module("layer_metrics", "jupdate_trips.fold")
    assert lock.read(run) == pytest.approx(15.0)
    assert trips.read(run) == pytest.approx(4000.0)
    out = capsys.readouterr().out
    assert "[fold] lockstep_pct over 2 interval(s): fold 8, ndev 1, " \
        "plan traced" in out


@pytest.mark.parametrize("name", NEW[7:])
def test_no_key_reads_as_nothing(tmp_path, capsys, name):
    """The parent's records: a ``tile`` record without the counters, as
    every tree before PR 42 writes it.  The reader returns nothing and
    does not raise; the line leaves the metric out."""
    run = fake_run(tmp_path, [tile(11.0, bubble_s=0.1),
                              tile(12.0, bubble_s=0.1)])
    assert harness.load_module("layer_metrics", name).read(run) is None
    assert "[fold] no tile record with" in capsys.readouterr().out
    # and a run with no records at all
    empty = types.SimpleNamespace(
        diag_path=str(tmp_path / "none.jsonl"),
        window=types.SimpleNamespace(t_open=10.0, t_drain=20.0))
    assert harness.load_module("layer_metrics", name).read(empty) is None
