"""The subtract cell, ``subtract-m8x128``: its entries in the manifest
(the LAST of their lists), its configuration, the tiny rehearsal cell
that stands for it (``-a 3 -p -z`` at 8 stations, 3 clusters of which
the first is left in the data) traced and untraced, the ways its
``correct`` has to come out false (an ignore list that is not honoured,
the model written in the data's place, Jones products in bfloat16, one
early cycle off), the driver on a tree without the seam, and the
readers this cell brings.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_subtract.py -q
"""

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

import harness      # noqa: E402
import reference    # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "subtract-cells.json")
SEED = 2 ** 31 + 5
NEW = ["subtract_dev_ms", "clusters_in_model.sub", "device_ms_per_tile.sub",
       "phasor_dev_ms.sub", "corrupt_dev_ms.sub", "bubble_ms.sub"]
CHECKS = ["residual_vs_reference", "short_residual_vs_reference",
          "error_over_noise"]


def run_cell(capsys, trace=0, seconds="0.5"):
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "subtract-tiny",
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


def test_the_cell_is_files_and_entries():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    real = harness.Cell("subtract-m8x128")
    tiny = harness.Cell("subtract-tiny", harness.load_json(CELLS))
    base = harness.Cell("predict-m8x128")
    # the new entries are the last of their lists
    assert man["configs"][-1]["name"] == "lofar62-sub-m8x128"
    assert man["workloads"][-1] == real.entry
    assert [m["name"] for m in man["per_layer"]][-len(NEW):] == NEW
    for m in man["per_layer"][-len(NEW):]:
        assert m["workloads"] == ["subtract-m8x128"]
    assert real.chips == 1 and real.traffic["driver"] == "subtract"
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    assert [m["name"] for m in real.metrics("per_layer")] == [
        "compiles_in_window", "device_idle_pct", "hbm_peak_gb",
        "recompiles_in_window", "compile_s.setup"] + NEW
    assert not set(NEW) & {m["name"] for m in base.metrics("per_layer")}
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"]

    # the deployment: the base's array, tile and sky, subtracted
    conf = real.config
    own = harness.load_json(ROOT, "benchmarks/configs/lofar62-sub-m8x128.json")
    assert conf["cli"] == ["-t", "10", "-a", "3"]
    assert base.config["cli"] == ["-t", "10", "-a", "1"]
    for k in ("n_stations", "n_clusters", "n_sources_per_cluster", "tilesz",
              "tdelta_s", "freq_hz", "chan_width_hz", "noise_sigma",
              "jones_scale", "jones_per_interval", "layout_seed",
              "sky_seed", "precision"):
        assert conf[k] == base.config[k] and k not in own
    assert 4 <= conf["n_tiles_on_disk"] <= 8
    assert sorted(own["reduced"]) == ["n_tiles_on_disk", "tilesz"]
    assert set(own["limits"]) == set(CHECKS)
    assert len(conf["guarantees"]) == len(base.config["guarantees"]) + 1
    assert set(conf["assumed"]) == set(base.config["assumed"]) | {
        "ignore file"}
    # the mix is predict-tiles' but for its driver (and its prose)
    for k, v in base.traffic.items():
        if k not in ("name", "driver", "loop", "warmup_why", "check_why"):
            assert real.traffic[k] == v


def test_sound_tiny_cell_is_correct_traced_and_untraced(capsys):
    line, _ = run_cell(capsys, trace=0)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert set(line["metrics"]) == {"vis_per_s", "tile_s.p50", "setup_s"}
    assert list(line["checks"]) == CHECKS
    assert line["attempted"] >= 8       # every disk tile, twice over
    # DATA is as the reference wrote it, after all those cycles
    cell = harness.Cell("subtract-tiny", harness.load_json(CELLS))
    obs = reference.Observation(cell.config, SEED)
    import datagen
    ms = os.path.join(BENCH, ".work", "subtract-tiny", "obs.ms")
    for i in range(cell.config["n_tiles_on_disk"]):
        np.testing.assert_array_equal(datagen.read_column(ms, i, "x"),
                                      obs.data(i))

    line, out = run_cell(capsys, trace=1, seconds="1")
    assert line["correct"] is True, line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(m), sorted(m)
    assert m["clusters_in_model.sub"] == 2       # three, less the target
    assert m["compiles_in_window"] == m["recompiles_in_window"] == 0
    assert 0 < m["subtract_dev_ms"] < m["device_ms_per_tile.sub"]
    assert "[span] simulation mode: -a 3 x" in out
    assert "[scope] rime/residual" in out


def broken(capsys):
    line, _ = run_cell(capsys)
    assert line["correct"] is False
    return {k: (v["value"], v["limit"]) for k, v in line["checks"].items()}


def test_an_ignore_list_that_is_not_honoured_is_not_correct(
        capsys, monkeypatch, fresh_programs):
    """A program that ignores ``-z`` subtracts the target too: what is
    written is the noise, a whole cluster away from the reference."""
    from sagecal_tpu import skymodel
    monkeypatch.setattr(skymodel, "read_ignore_list", lambda path: set())
    checks = broken(capsys)
    for name in CHECKS:
        assert checks[name][0] > 10 * checks[name][1]


def test_the_model_in_the_datas_place_is_not_correct(
        capsys, monkeypatch, fresh_programs):
    """``-a 1``'s answer under ``-a 3``'s flags: the right model,
    written and not subtracted."""
    from sagecal_tpu.rime import residual as rr
    real = rr.simulate_pairs

    def replace(*a, mode, **kw):
        return real(*a, mode=1, **kw)

    monkeypatch.setattr(rr, "simulate_pairs", replace)
    value, limit = broken(capsys)["residual_vs_reference"]
    assert value > 1000 * limit


def test_one_early_cycle_altered_is_not_correct(capsys, monkeypatch):
    """The third cycle of the window comes out of the program half a
    percent off.  Its disk tile is written again before the window
    closes, so only the rows kept as that cycle was handed to the writer
    can show it."""
    from sagecal_tpu import pipeline
    real = pipeline.FullBatchPipeline._jit_cached

    def jit_cached(self, kind, build, *extra):
        prog = real(self, kind, build, *extra)
        calls = [0]

        def once_off(*args):
            calls[0] += 1           # five warm-up tiles, then the window
            out = prog(*args)
            return out * 1.005 if calls[0] == 8 else out
        return once_off if kind == "sim" else prog

    monkeypatch.setattr(pipeline.FullBatchPipeline, "_jit_cached",
                        jit_cached)
    line, _ = run_cell(capsys)
    assert line["attempted"] >= 12      # 4 disk tiles: cycle 7 is gone
    assert line["correct"] is False and line["failed"] == 0
    check = line["checks"]["residual_vs_reference"]
    assert check["value"] > 3 * check["limit"]


def test_control_reference_in_bfloat16_fails_the_limits():
    """The control at the tiny size: the reference in the program's
    place, the products of its Jones sandwich made in bfloat16, taken
    from the reference's own input.  One pass (the TPU's default) has to
    exceed every limit three times over; float32 in the same place has to
    stay far inside them.  Three passes (``high``) on the short
    baselines' rows have to stand well clear of float32: that is what
    ``short_residual_vs_reference`` is there to see (its limit was set
    from the chip's own ``high``, ``limits.py``)."""
    bfloat16 = pytest.importorskip("ml_dtypes").bfloat16
    sub = harness.load_module("drivers", "subtract")
    conf = harness.Cell("subtract-tiny", harness.load_json(CELLS)).config
    limit = {k: conf["limits"][k]["limit"] for k in CHECKS}
    for seed in (5, 6, 2 ** 31 + 7):
        obs = reference.Observation(conf, seed)
        rest = sub.target_and_rest(obs)[1]
        assert list(rest) == [1, 2]

        def gaps(rows, **kw):
            """(error over the model, error over the noise) of an output
            whose model's products were made as ``kw`` says."""
            x, m_ref, noise = sub.expected(obs, 1, obs.jones(1), rest, rows)
            u, v, w, s1, s2 = (a[rows] for a in obs.geometry(1))
            coh = reference.coherencies(obs.sky, u, v, w, obs.freq,
                                        obs.fdelta)
            m = reference.model(obs.jones(1)[rest], coh[rest], s1, s2, **kw)
            err = reference.rms((x - m) - (x - m_ref))
            return err / reference.rms(m_ref), err / reference.rms(noise)

        rows, short = np.arange(obs.nrows), obs.short_rows(250.0)
        one, one_noise = gaps(rows, dtype=bfloat16)
        assert one > 3 * limit["residual_vs_reference"]
        assert one_noise > 3 * limit["error_over_noise"]
        assert gaps(short, dtype=bfloat16)[0] \
            > 3 * limit["short_residual_vs_reference"]
        f32, f32_noise = gaps(rows, dtype=np.float32)
        assert f32 < limit["residual_vs_reference"] / 30
        assert f32_noise < limit["error_over_noise"] / 30
        assert gaps(short, dtype=np.float32)[0] \
            < limit["short_residual_vs_reference"] / 30
        assert gaps(short, dtype=bfloat16, passes=3)[0] \
            > 30 * gaps(short, dtype=np.float32)[0]


def test_a_tree_without_the_seam_fails_at_once(capsys, monkeypatch):
    """On the parent of PR 37 ``rime/residual`` has no ``simulate_pairs``
    and ``-a 3`` aborts the TPU compiler: the harness's look-up of the
    cell ends the process with exit 4, before the backend is opened, with
    a message and no result line."""
    from sagecal_tpu.rime import residual as rr
    monkeypatch.delattr(rr, "simulate_pairs")
    import run as runner
    with pytest.raises(SystemExit) as e:
        runner.main(["--cells", CELLS, "--workload", "subtract-tiny",
                     "--seed", "1", "--seconds", "1", "--trace", "0",
                     "--allow-cpu"])
    assert e.value.code == 4
    cap = capsys.readouterr()
    assert "no simulate_pairs" in cap.err and cap.out == ""


TILE = {"ev": "tile", "tile": 3, "tm": 1.0, "bubble_s": 0.25}


@pytest.mark.parametrize("records, value, said", [
    ([{**TILE, "mode": 3, "clusters_in_model": 7},
      {**TILE, "tile": 4, "mode": 3, "clusters_in_model": 7}],
     7, "-a 3 x 2"),
    # the parent's record has neither key; other events are not tiles
    ([TILE, {"ev": "phase", "name": "io", "clusters_in_model": 8}],
     None, "no tile record"),
    ([], None, "no tile record"),
], ids=["seven", "no-key", "no-records"])
def test_clusters_reader(capsys, monkeypatch, records, value, said):
    import scopes
    monkeypatch.setattr(scopes, "window_records", lambda run: records)
    mod = harness.load_module("layer_metrics", "clusters_in_model.sub")
    assert mod.read(types.SimpleNamespace()) == value
    assert said in capsys.readouterr().out


@pytest.mark.parametrize("name, old", [
    ("device_ms_per_tile.sub", "device_ms_per_tile"),
    ("phasor_dev_ms.sub", "phasor_dev_ms"),
    ("corrupt_dev_ms.sub", "corrupt_dev_ms"),
    ("bubble_ms.sub", "bubble_ms.predict")])
def test_renamed_readers_are_the_readers_that_exist(monkeypatch, name, old):
    """Each gives what the accepted reader gives, under this cell's
    name, with that reader's unit, layer and end-to-end metric; and
    nothing where that one finds nothing (the parent's program)."""
    import scopes
    new = harness.load_module("layer_metrics", name)
    was = harness.load_module("layer_metrics", old)
    assert (new.NAME, new.UNIT, new.LAYER, new.MOVES) == (
        name, was.UNIT, was.LAYER, was.MOVES)
    values = {"device_ms_per_tile.sub": 12.5, "bubble_ms.sub": 250.0}
    for records in ([TILE], []):
        monkeypatch.setattr(scopes, "window_records", lambda run: records)
        # a reduced profile and no scoped trace: the scope readers find
        # nothing (their values are the traced tiny run's, above)
        run = types.SimpleNamespace(
            profile={"busy_s": 0.025} if records else None, slice=None,
            slice_tiles=2 if records else 0, trace=False,
            diag_path=os.path.join(HERE, "no-such-file"),
            profile_dir=os.path.join(HERE, "no-such-dir"),
            window=types.SimpleNamespace(t_open=None))
        assert new.read(run) == was.read(run) == (
            values.get(name) if records else None)
