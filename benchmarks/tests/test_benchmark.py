"""The benchmark's own tests.  Run by hand and in the CPU rehearsal:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

(not under ``tests/``: the repo's tier-1 suite neither collects nor needs
them).  They cover the yardstick itself: the reference against a hand
computation, the trace reduction on a trace recorded here, the window's
arithmetic on synthetic records, the lookup of cells by files, and the
two ways ``correct`` has to come out false: the control (the reference
with its Jones products in bfloat16 put in the program's place) and a
timed path broken underneath the harness (every answer, one early answer
of many, one answer not finite, a solver that returns its state).
"""

import collections
import concurrent.futures
import glob
import json
import math
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
import xplane       # noqa: E402

#: the tiny cells: further configs and workloads, nothing else
CELLS = os.path.join(HERE, "rehearsal", "cells.json")


# -- reference ----------------------------------------------------------------

def test_reference_against_hand_computation():
    """Three sources in two directions, two baselines, written out term
    by term."""
    freq, fdelta = 150e6, 180e3
    ll = np.array([[0.01, -0.02], [0.003, 0.0]])
    mm = np.array([[-0.005, 0.015], [0.02, 0.0]])
    nn = np.sqrt(1 - ll ** 2 - mm ** 2) - 1
    flux = np.array([[2.0, 0.5], [1.5, 0.0]])         # 4th slot: no source
    u = np.array([1.0e-6, -3.0e-6])
    v = np.array([2.0e-6, 0.5e-6])
    w = np.array([0.1e-6, -0.2e-6])
    coh = reference.coherencies((ll, mm, nn, flux), u, v, w, freq, fdelta)

    def term(b, m, s):
        g = 2 * math.pi * (u[b] * ll[m, s] + v[b] * mm[m, s]
                           + w[b] * nn[m, s])
        x = g * fdelta / 2
        smear = abs(math.sin(x) / x) if x else 1.0
        return flux[m, s] * smear * complex(math.cos(g * freq),
                                            math.sin(g * freq))

    for b in range(2):
        assert coh[0, b] == pytest.approx(term(b, 0, 0) + term(b, 0, 1),
                                          rel=1e-12)
        assert coh[1, b] == pytest.approx(term(b, 1, 0), rel=1e-12)

    rng = np.random.default_rng(1)
    jones = reference.draw_jones(2, 3, 0.3, rng)
    s1, s2 = np.array([0, 1]), np.array([2, 2])
    vis = reference.model(jones, coh, s1, s2)
    for b in range(2):
        want = sum(coh[m, b] * jones[m, s1[b]] @ jones[m, s2[b]].conj().T
                   for m in range(2))
        np.testing.assert_allclose(vis[b], want, rtol=1e-12)


def test_solutions_text_round_trip(tmp_path):
    rng = np.random.default_rng(2)
    blocks = [reference.draw_jones(3, 5, 0.2, rng) for _ in range(2)]
    path = str(tmp_path / "s.txt")
    reference.write_solutions(path, blocks, 150e6, 180e3, 1.5)
    back = reference.read_solutions(path)
    assert len(back) == 2
    np.testing.assert_allclose(back[1], blocks[1], rtol=1e-9)
    # the same file read by the program's reader gives the same matrices
    from sagecal_tpu.io import solutions as sol
    _, prog = sol.read_solutions(path, np.ones(3, np.int32))
    np.testing.assert_allclose(prog[0][:, 0], blocks[0], rtol=1e-9)


def test_observation_is_its_seed():
    conf = harness.load_config(
        "benchmarks/tests/rehearsal/tiny-lofar62-m8x3.json")
    a = reference.Observation(conf, 2 ** 31 + 11)
    b = reference.Observation(conf, 2 ** 31 + 11)
    c = reference.Observation(conf, 2 ** 31 + 12)
    np.testing.assert_array_equal(a.data(1), b.data(1))
    assert not np.allclose(a.data(1), c.data(1))
    # the sky and the array are the deployment's, not the seed's
    assert a.sky_lines == c.sky_lines
    np.testing.assert_array_equal(a.xyz, c.xyz)
    # residual under the true Jones is the noise
    r = a.data(2) - a.model(2, a.jones())
    assert reference.rms(r) == pytest.approx(
        math.sqrt(2) * conf["noise_sigma"], rel=0.1)


# -- window arithmetic --------------------------------------------------------

def fake_window(stamps, t_drain, seconds=10.0, n_vis=100, t_start=-5.0):
    clock = iter(stamps + [t_drain])
    w = harness.Window(seconds, t_start, clock=lambda: next(clock))
    for k in range(len(stamps)):
        w.enter(k, n_vis)
    w.drain()
    return w


def test_window_arithmetic():
    w = fake_window([0.0, 2.0, 5.0, 6.0], 10.0)
    assert w.tile_seconds() == [2.0, 3.0, 1.0, 4.0]   # last: to the drain
    assert w.length_s() == 10.0
    assert w.vis_per_s() == 40.0                      # 4 x 100 / 10 s
    e2e = w.end_to_end()
    assert e2e["tile_s.p50"] == 2.5
    assert e2e["setup_s"] == 5.0                      # process start -5
    assert e2e["tile_s.p95"] == pytest.approx(3.85)   # between 3 and 4
    # with few values the 95th percentile goes to the maximum
    assert harness.percentile([1.0, 9.0], 95) == pytest.approx(8.6)
    assert harness.percentile(list(range(101)), 95) == 95


def test_window_closes_at_a_boundary_after_seconds():
    now = [0.0]
    w = harness.Window(3.0, 0.0, clock=lambda: now[0])
    assert not w.due()                                # not open yet
    w.enter(0, 1)
    now[0] = 2.9
    assert not w.due()
    now[0] = 3.0
    assert w.due()


def test_pick_tiles():
    assert harness.pick_tiles([3, 4, 5, 6, 7, 8, 9], 3) == [3, 6, 9]
    assert harness.pick_tiles([3, 4], 3) == [3, 4]
    assert harness.pick_tiles(range(5, 505), 3) == [5, 255, 504]


def test_a_nan_is_not_correct():
    assert not harness.Comparison("x", float("nan"), 1.0).ok
    assert harness.Comparison("x", 0.5, 1.0).ok
    assert not harness.Comparison("x", 1.5, 1.0).ok


# -- manifest, cells found by files -------------------------------------------

def test_manifest_and_files_agree():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        mod = harness.load_module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["moves"] in e2e
    for w in man["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        assert cell.metrics("per_layer")
        assert hasattr(cell.driver, "run") and hasattr(cell.driver, "check")
    for c in man["configs"]:
        conf = harness.load_json(ROOT, c["file"])
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert "TPU v5 lite" in harness.load_json(BENCH, "peaks.json")


def test_a_cell_added_as_files_only_is_found():
    """The rehearsal cells are nothing but files and entries: two
    workloads, two configuration files that lay other sizes over the real
    ones, the same mixes and drivers.  Their metric lists and bounds are
    the root manifest's: those of the cell each stands for."""
    cell = harness.Cell("predict-tiny", harness.load_json(CELLS))
    real = harness.Cell("predict-m8x128")
    assert cell.config["n_stations"] == 8
    assert cell.config["guarantees"] == real.config["guarantees"]
    assert cell.traffic["driver"] == "predict"
    assert cell.metrics("end_to_end") == real.metrics("end_to_end")
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "tile_s.p95", "setup_s"]
    assert cell.metrics("per_layer") == real.metrics("per_layer")
    with pytest.raises(KeyError):
        harness.Cell("no-such-cell", harness.load_json(CELLS))
    with pytest.raises(KeyError):
        harness.Cell("predict-tiny")        # not a cell of the benchmark


# -- the trace reduction ------------------------------------------------------

def test_self_times_and_union():
    ev = [("while", 0, 100), ("a", 10, 30), ("b", 40, 60), ("c", 45, 50),
          ("d", 200, 300)]
    dev = xplane.walk(((name, None), s, e) for name, s, e in ev)
    assert dev.n_events == 5
    # the union of the leaves: a, c and d
    assert dev.merged == [[10, 30], [45, 50], [200, 300]]
    assert dev.busy_s == pytest.approx(125e-9)
    assert dev.self_s["while"] == pytest.approx(60e-9)
    assert dev.self_s["b"] == pytest.approx(15e-9)
    assert xplane.union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]
    spans = [("tile_cycle", 0, 100), ("step", 10, 50)]
    assert xplane.span_at(spans, 20) == "tile_cycle/step"
    assert xplane.span_at(spans, 70) == "tile_cycle"
    assert xplane.span_at(spans, 500) == "outside_any_span"
    # a line's order is start, then longest first: nothing is sorted
    with pytest.raises(xplane.Unsorted):
        xplane.walk([(("a", None), 10, 30), (("w", None), 0, 100)])
    with pytest.raises(xplane.Unsorted):
        xplane.walk([(("a", None), 0, 30), (("w", None), 0, 100)])
    assert xplane.walk_any_order(
        [(("a", None), 10, 30), (("w", None), 0, 100)]).merged == [[10, 30]]


def test_the_clock_adds_up_to_the_wall():
    now = [100.0]
    c = harness.Clock(100.0, clock=lambda: now[0])
    now[0] = 103.0
    c.mark("backend")
    c.mark("warmup", at=110.0)      # an instant the harness had read
    now[0] = 125.0
    c.carve("hlo_table", 2.0)       # inside the phase that is running
    c.mark("walk")
    c.notes["device_events"] = 7
    assert c.phases == {"backend": 3.0, "warmup": 7.0, "hlo_table": 2.0,
                        "walk": 13.0}
    assert sum(c.phases.values()) == c.total() == 25.0
    assert c.line() == ("[clock] backend 3.00, warmup 7.00, hlo_table 2.00, "
                        "walk 13.00; total 25.00 s; device_events 7")


def test_xplane_on_a_trace_recorded_here(tmp_path):
    import time
    import jax
    import jax.numpy as jnp
    import jax.profiler

    @jax.jit
    def work(x):
        return jnp.sin(x @ x).sum()

    x = jnp.ones((300, 300), jnp.float32)
    work(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("tile_cycle"):
            with jax.profiler.TraceAnnotation("step"):
                work(x).block_until_ready()
            with jax.profiler.TraceAnnotation("write_tile"):
                time.sleep(0.02)
    wall = time.perf_counter() - t0
    # stopped as run.py stops it: the trace written once, no trace.json.gz
    path, how = xplane.stop_session(str(tmp_path))
    assert how == "session.stop"
    assert [os.path.basename(p) for p in glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*"))] \
        == [os.path.basename(path)] == ["slice.xplane.pb"]
    assert xplane.newest_trace(str(tmp_path)) == path
    with pytest.raises(RuntimeError):
        xplane.stop_session(str(tmp_path))      # nothing is running now
    jax.profiler.start_trace(str(tmp_path / "again"))   # and JAX agrees
    jax.profiler.stop_trace()
    red = xplane.reduce_dir(str(tmp_path))
    assert 0.0 < red["busy_s"] < wall
    assert red["n_device_events"] >= 3
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    # the long gaps are the sleeps, inside the harness's write span
    assert red["idle_gaps"][0][0] == "tile_cycle/write_tile"
    assert red["idle_gaps"][0][1] > 0.015
    assert "PLANE" in xplane.describe(xplane.newest_trace(str(tmp_path)))


def test_a_jax_without_the_private_session_is_stopped_by_stop_trace(
        tmp_path, monkeypatch):
    """``stop_session`` reads JAX's private ``_profile_state``; where a
    JAX bump has renamed it, the public call stops the profiler and the
    newest trace under the directory is the one."""
    import jax._src.profiler as jp
    import jax.profiler
    trace = tmp_path / "plugins" / "profile" / "2026" / "h.xplane.pb"

    def stop_trace():
        trace.parent.mkdir(parents=True)
        trace.write_bytes(b"")

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    monkeypatch.delattr(jp, "_profile_state")
    assert xplane.stop_session(str(tmp_path)) == (
        str(trace), "jax.profiler.stop_trace")
    # a session object that has lost its ``stop`` likewise
    trace.unlink()
    trace.parent.rmdir()
    monkeypatch.setattr(jp, "_profile_state", types.SimpleNamespace(
        lock=None, reset=None, profile_session=object()), raising=False)
    assert xplane.stop_session(str(tmp_path))[1] == "jax.profiler.stop_trace"


# -- correct has to be able to come out false ---------------------------------

def bfloat16():
    return pytest.importorskip("ml_dtypes").bfloat16


TINY = "benchmarks/tests/rehearsal/tiny-lofar62-"
SEEDS = (5, 6, 2 ** 31 + 7)


def test_control_reference_in_bfloat16_fails_predict_limits():
    """The control of ``predict-m8x128`` at the tiny size: the reference
    in the program's place, the products of its Jones sandwich made in
    bfloat16, against the f64 reference.  One pass (the TPU's default)
    has to exceed both limits, three times over; float32 in the same
    place has to stay far inside both.  Three passes (``high``) on the
    short baselines' rows have to stand well clear of float32: that is
    what ``short_model_vs_reference`` is there to see.  Its limit was set
    from the chip's own ``high`` (``limits.py``), which reads five times
    this emulation's three-pass error."""
    conf = harness.load_config(TINY + "m8x128.json")
    limit = conf["limits"]["model_vs_reference"]["limit"]
    short_limit = conf["limits"]["short_model_vs_reference"]["limit"]
    for seed in SEEDS:
        obs = reference.Observation(conf, seed)

        def gap(rows=None, **kw):
            want = obs.model(1, obs.jones(1), rows=rows)
            got = obs.model(1, obs.jones(1), rows=rows, **kw)
            return reference.rms(got - want) / reference.rms(want)

        short = obs.short_rows(250.0)
        assert gap(dtype=bfloat16()) > 3 * limit
        assert gap(short, dtype=bfloat16()) > 3 * short_limit
        assert gap(dtype=np.float32) < limit / 30
        assert gap(short, dtype=np.float32) < short_limit / 30
        assert gap(short, dtype=bfloat16(), passes=3) \
            > 30 * gap(short, dtype=np.float32)


def test_control_reference_in_bfloat16_fails_calibrate_limit():
    """The control of ``cal-m8x3``, check (a): a residual written from a
    bfloat16 model is not the data minus the reference's model."""
    conf = harness.load_config(TINY + "m8x3.json")
    limit = conf["limits"]["residual_vs_reference"]["limit"]
    for seed in SEEDS:
        obs = reference.Observation(conf, seed)
        x, jones = obs.data(3), obs.jones()
        r_ref = x - obs.model(3, jones)
        r_low = x - obs.model(3, jones, dtype=bfloat16())
        assert reference.rms(r_low - r_ref) / reference.rms(r_ref) \
            > 3 * limit


def test_three_passes_keep_what_one_pass_drops():
    rng = np.random.default_rng(3)
    a, b = rng.normal(size=1000), rng.normal(size=1000)

    def err(**kw):
        got = reference.product(np.multiply, a, b, **kw)
        return reference.rms(got - a * b) / reference.rms(a * b)

    assert err(dtype=None, passes=1) == 0.0
    assert 1e-3 < err(dtype=bfloat16(), passes=1) < 1e-2
    assert 1e-6 < err(dtype=bfloat16(), passes=3) < 1e-4


def run_cell(capsys, workload, seconds="0.5"):
    """The rest of a run, past the look for a chip: ``run.main`` with
    ``--allow-cpu`` on a tiny cell; its result line."""
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", workload,
                      "--seed", str(2 ** 31 + 5), "--seconds", seconds,
                      "--trace", "0", "--allow-cpu"])
    assert rc == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    # each number compared beside its limit: the line's last key, and
    # the last lines on standard error
    assert list(line)[-1] == "checks"
    said = cap.err.strip().splitlines()[-len(line["checks"]):]
    assert [ln.split()[:2] for ln in said] == [
        ["[check]", name] for name in line["checks"]]
    return line


def test_sound_tiny_cells_are_correct(capsys):
    for workload in ("cal-tiny", "predict-tiny"):
        line = run_cell(capsys, workload)
        assert line["correct"] is True, line
        assert line["device"]["platform"] == "cpu"
        assert set(line) >= {"correct", "attempted", "failed", "metrics",
                             "device"}
        assert "setup_s" in line["metrics"] and line["attempted"] > 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    """Calibrate, broken underneath: the solver hands back the Jones it
    was given.  The residual written is then far above the noise."""
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
    line = run_cell(capsys, "cal-tiny")
    assert line["correct"] is False
    assert line["checks"]["residual_over_noise"]["value"] \
        > line["checks"]["residual_over_noise"]["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    """Predict, broken underneath: the predict program's output is off by
    half a percent."""
    from sagecal_tpu.rime import residual as rr
    from sagecal_tpu.serve import cache as pcache
    pcache.PROGRAMS.clear()     # a sound run's traced program is cached
    real = rr.simulate_visibilities
    monkeypatch.setattr(rr, "simulate_visibilities",
                        lambda *a, **kw: 1.005 * real(*a, **kw))
    line = run_cell(capsys, "predict-tiny")
    pcache.PROGRAMS.clear()
    assert line["correct"] is False


def test_one_early_cycle_altered_is_not_correct(capsys, monkeypatch):
    """Predict, one answer of many: the third cycle of the window comes
    out of the predict program half a percent off.  Its disk tile is
    written again dozens of times before the window closes, so only the
    rows kept as that cycle was handed to the writer can show it."""
    from sagecal_tpu import pipeline
    real = pipeline.FullBatchPipeline._jit_cached

    def jit_cached(self, kind, build, *extra):
        prog = real(self, kind, build, *extra)
        if kind != "sim":
            return prog
        calls = [0]

        def once_off(*args):
            calls[0] += 1           # five warm-up tiles, then the window
            out = prog(*args)
            return out * 1.005 if calls[0] == 8 else out
        return once_off

    monkeypatch.setattr(pipeline.FullBatchPipeline, "_jit_cached",
                        jit_cached)
    line = run_cell(capsys, "predict-tiny")
    assert line["attempted"] >= 12      # 4 disk tiles: cycle 7 is gone
    assert line["correct"] is False
    check = line["checks"]["model_vs_reference"]
    assert 0.004 < check["value"] < 0.006


def test_a_cycle_that_is_not_finite_counts_as_failed(capsys, monkeypatch):
    from sagecal_tpu import pipeline
    real = pipeline.FullBatchPipeline._jit_cached

    def jit_cached(self, kind, build, *extra):
        prog = real(self, kind, build, *extra)
        calls = [0]

        def once_nan(*args):
            calls[0] += 1
            out = prog(*args)
            return out * float("nan") if calls[0] == 9 else out
        return once_nan if kind == "sim" else prog

    monkeypatch.setattr(pipeline.FullBatchPipeline, "_jit_cached",
                        jit_cached)
    line = run_cell(capsys, "predict-tiny")
    assert line["failed"] == 1 and line["correct"] is False


class Overlapped:
    """The predict cell's dataset as a loop with overlap drives it, the
    program's own loop underneath: ``tiles()`` has read ``ahead`` tiles
    beyond the one it hands out, and with ``threaded`` the writes run on
    a thread of their own, in the order they were handed in."""

    def __init__(self, ms, ahead, threaded):
        self._ms, self._ahead = ms, ahead
        self._writer = concurrent.futures.ThreadPoolExecutor(1) \
            if threaded else None
        self._writes = []

    def __getattr__(self, name):
        return getattr(self._ms, name)

    def tiles(self):
        read = collections.deque()
        for item in self._ms.tiles():
            read.append(item)
            if len(read) > self._ahead:
                yield read.popleft()
        yield from read

    def write_tile(self, i, tile, column=None):
        if self._writer is None:
            return self._ms.write_tile(i, tile, column)
        self._writes.append(
            self._writer.submit(self._ms.write_tile, i, tile, column))

    def close(self):
        if self._writer is not None:
            self._writer.shutdown(wait=True)
        for w in self._writes:
            w.result()              # a write that raised raises here


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["two-ahead", "second-thread"])
def test_a_loop_that_reads_ahead_of_its_writes_is_correct(
        capsys, monkeypatch, threaded):
    """Predict with every line of the program right and its loop
    overlapped: tile k + 2 is read before tile k is written (and the
    write may come from another thread).  The kept rows follow the tile
    that is written; filed by the loop's position, as they were, every
    cycle's rows sat under the wrong key and this run was not correct."""
    from sagecal_tpu import pipeline
    real = pipeline.FullBatchPipeline.run_simulation
    seen = []

    def overlapped(self, log=print):
        inner = self.ms
        self.ms = Overlapped(inner, 2, threaded)
        try:
            return real(self, log=log)
        finally:
            self.ms.close()
            seen.append(type(inner))
            self.ms = inner

    monkeypatch.setattr(pipeline.FullBatchPipeline, "run_simulation",
                        overlapped)
    line = run_cell(capsys, "predict-tiny")
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 12
    # every cycle read was written, each under its own key
    (ms,) = seen
    assert not ms.out
    assert sorted(ms.kept) == list(range(5, 5 + line["attempted"]))


def test_without_a_chip_and_without_allow_cpu_it_fails(capsys):
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "predict-tiny",
                      "--seed", "1", "--seconds", "0.2", "--trace", "0"])
    assert rc != 0
    assert "correct" not in capsys.readouterr().out
