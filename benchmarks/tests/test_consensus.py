"""The consensus cell's own tests: the tiny rehearsal cell that stands
for ``admm-f4-mesh`` traced and untraced, the ways its ``correct`` has to
come out false (a global Z file half a percent off, a consensus that
never pulls J towards BZ, an interval missing from one subband's
solutions file), the driver on a tree without the seam, and the two
readers of ``run.profile["per_device"]`` on faked device planes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_consensus.py -q
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

import harness                      # noqa: E402
import reference                    # noqa: E402
import reference_consensus as refc  # noqa: E402
import xplane                       # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "consensus-cells.json")
SEED = 2 ** 31 + 5


def run_cell(capsys, trace=0, seconds="60"):
    """``--seconds`` beyond the six window intervals the tiny
    observation has: the window is all of them however slow this machine
    is (PR 35), and a traced run's profile is the second of them."""
    import run as runner
    rc = runner.main(["--cells", CELLS, "--workload", "admm-tiny",
                      "--seed", str(SEED), "--seconds", seconds,
                      "--trace", str(trace), "--allow-cpu"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_the_cell_is_files_and_entries():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    real = harness.Cell("admm-f4-mesh")
    tiny = harness.Cell("admm-tiny", harness.load_json(CELLS))
    assert real.chips == 4 and real.traffic["driver"] == "consensus"
    assert real.traffic["profile_tiles"] == 1
    assert real.driver.BOUNDARY_OUTSIDE_SPANS is True
    assert [m["name"] for m in real.metrics("end_to_end")] == [
        "vis_per_s", "tile_s.p50", "setup_s"]
    layer = [m["name"] for m in real.metrics("per_layer")]
    assert layer == [
        "compiles_in_window", "device_idle_pct", "hbm_peak_gb",
        "recompiles_in_window", "compile_s.setup", "solve_s.admm",
        "bubble_ms.admm", "admm_iters", "jupdate_dev_s", "consensus_dev_ms",
        "collective_ms.admm", "chip_skew_pct"]
    assert tiny.metrics("per_layer") == real.metrics("per_layer")
    assert tiny.config["guarantees"] == real.config["guarantees"]
    # a rehearsal's limits are its own (8 stations), the checks are not
    assert set(tiny.config["limits"]) == set(real.config["limits"])
    # at most half of the cells ask for four chips, and one always may
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 2)
    conf = real.config
    assert conf["architecture"] is None
    assert len(conf["subband_freqs_hz"]) == 4
    cli = conf["cli"]
    assert cli[cli.index("-A") + 1] == "3" and real.driver.npoly(conf) == 2
    assert "admm_iterations" in conf["reduced"]


def test_subbands_are_one_observation():
    conf = harness.Cell("admm-tiny", harness.load_json(CELLS)).config
    subs = refc.subbands(conf, SEED)
    again = refc.subbands(conf, SEED)
    assert [s.freq for s in subs] == conf["subband_freqs_hz"]
    # one array, one sky text, one hour angle; each its own flux scale
    assert subs[0].ha0 == subs[2].ha0
    assert subs[0].sky_lines == subs[2].sky_lines
    np.testing.assert_allclose(subs[0].sky[3] / subs[1].sky[3],
                               (120 / 150) ** -0.7, rtol=1e-12)
    # at the catalogue frequency: unscaled
    np.testing.assert_array_equal(
        subs[1].sky[3], reference.Observation(conf, SEED).sky[3])
    # its own noise, the same from the same seed
    assert not np.allclose(subs[0].noise(1), subs[1].noise(1))
    np.testing.assert_array_equal(subs[2].data(1), again[2].data(1))
    # the true Jones is first order in frequency: the middle subband's is
    # the mean of the outer ones', so a two-term Bernstein fit is exact
    j = [s.jones() for s in subs]
    np.testing.assert_allclose(j[1], 0.5 * (j[0] + j[2]), rtol=1e-12)
    basis = refc.bernstein_basis(conf["subband_freqs_hz"], 2)
    z = refc.z_update(basis, np.zeros_like(np.stack(j)), np.stack(j),
                      np.ones((3, j[0].shape[0])))
    assert refc.primal_residual(np.stack(j), basis, z) < 1e-12


def test_sound_tiny_cell_is_correct_traced_and_untraced(capsys):
    line, _ = run_cell(capsys, trace=0)
    assert line["correct"] is True, line
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert set(line["metrics"]) == {"vis_per_s", "tile_s.p50", "setup_s"}
    assert line["attempted"] >= 2
    line, out = run_cell(capsys, trace=1)
    assert line["correct"] is True, line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # one CPU device has no memory statistics and no second plane
    assert set(m) >= {"compiles_in_window", "recompiles_in_window",
                      "compile_s.setup", "device_idle_pct", "solve_s.admm",
                      "bubble_ms.admm", "admm_iters", "jupdate_dev_s",
                      "consensus_dev_ms", "collective_ms.admm"}
    assert m["admm_iters"] == 3
    assert m["compiles_in_window"] == m["recompiles_in_window"] == 0
    assert 0 < m["consensus_dev_ms"] < 1e3 * m["jupdate_dev_s"]
    assert m["solve_s.admm"] > m["jupdate_dev_s"] * 0.2
    # ONE interval in the profile, whatever the window held
    clock = [ln for ln in out.splitlines() if ln.startswith("[clock]")][0]
    assert "stop_trace_in_window_s" in clock
    assert "[scope] sage/consensus" in out and "[scope] sage/manifold" in out
    assert "[span] sagecal/fetch" in out


def broken(capsys):
    line, _ = run_cell(capsys)
    assert line["correct"] is False
    return {k: (v["value"], v["limit"]) for k, v in line["checks"].items()}


def test_a_z_file_off_by_half_a_percent_is_not_correct(capsys, monkeypatch):
    from sagecal_tpu.io import solutions as sol
    real = sol.SolutionWriter.write_interval

    def write_interval(self, J, nchunk):
        off = 1.005 if self.f.name.endswith("global.solutions") else 1.0
        real(self, off * np.asarray(J), nchunk)

    monkeypatch.setattr(sol.SolutionWriter, "write_interval", write_interval)
    checks = broken(capsys)
    value, limit = checks["consensus_over_noise"]
    assert value > limit
    # the subbands' own solutions and residuals were left alone
    assert checks["residual_over_noise"][0] < checks["residual_over_noise"][1]


def test_a_consensus_that_is_skipped_is_not_correct(capsys, monkeypatch):
    """The runner's consensus is skipped: the J updates lose their
    consensus term (every subband is solved alone in every ADMM
    iteration, J is never pulled towards B Z) and Z is never fitted (it
    stays at its zero start).  What is written as the global solution
    then models nothing, and the written J are nowhere near B Z.

    Dropping the pull ALONE is not seen by any check on what was
    written, at this deployment's rho of 5: PERF.md, Open questions."""
    from sagecal_tpu.consensus import admm as cadmm
    real = cadmm.sage.sagefit
    monkeypatch.setattr(
        cadmm.sage, "sagefit",
        lambda *a, admm=None, **kw: real(*a, **kw))
    monkeypatch.setattr(
        cadmm.cpoly, "z_from_contributions",
        lambda zsum, Bi: 0.0 * zsum)
    checks = broken(capsys)
    for name in ("consensus_primal", "consensus_over_noise"):
        value, limit = checks[name]
        assert value > 3 * limit
    # each subband's own solution and residual are as sound as ever
    assert checks["residual_over_noise"][0] < checks["residual_over_noise"][1]


def test_an_interval_missing_from_one_subband_is_not_correct(
        capsys, monkeypatch):
    from sagecal_tpu.io import solutions as sol
    real = sol.SolutionWriter.write_interval
    calls = [0]

    def write_interval(self, J, nchunk):
        if self.f.name.endswith(os.path.join("sb1", "obs.ms.solutions")):
            calls[0] += 1
            if calls[0] == 3:
                return          # the window's first interval is lost
        real(self, J, nchunk)

    monkeypatch.setattr(sol.SolutionWriter, "write_interval", write_interval)
    checks = broken(capsys)
    assert all(v != v for v, _ in checks.values())      # every one a NaN


def test_a_tree_without_the_seam_fails_at_once(capsys, monkeypatch):
    """On the parent of PR 30 ``cli_mpi`` has no ``ConsensusStepper``: the
    harness's look-up of the cell ends the process, before the backend is
    opened, with a message and no result line."""
    from sagecal_tpu import cli_mpi
    monkeypatch.delattr(cli_mpi, "ConsensusStepper")
    import run as runner
    with pytest.raises(SystemExit) as e:
        runner.main(["--cells", CELLS, "--workload", "admm-tiny",
                     "--seed", "1", "--seconds", "1", "--trace", "0",
                     "--allow-cpu"])
    assert e.value.code not in (0, None)
    cap = capsys.readouterr()
    assert "no ConsensusStepper" in cap.err and cap.out == ""


# -- the readers of per_device, on faked planes -------------------------------

def fake_run(planes, tiles=1):
    """``planes``: {name: [(operation, start_ns, end_ns)]}."""
    devices = {name: xplane.walk(((op, None), s, e) for op, s, e in ev)
               for name, ev in planes.items()}
    prof = xplane.Profile.__new__(xplane.Profile)
    prof.devices, prof.harness_spans = devices, []
    return types.SimpleNamespace(
        profile=prof.reduce(), slice_tiles=tiles, trace_path="trace.pb",
        slice=types.SimpleNamespace(profile=prof))


def test_collectives_and_skew_read_per_device(capsys):
    ms = 10 ** 6
    run = fake_run({
        "/device:TPU:0": [("fusion.1", 0, 90 * ms),
                          ("all-reduce.3", 90 * ms, 92 * ms)],
        "/device:TPU:1": [("fusion.1", 0, 60 * ms),
                          ("all-reduce.3", 60 * ms, 92 * ms),
                          ("all-reduce.7", 95 * ms, 103 * ms)]}, tiles=2)
    coll = harness.load_module("layer_metrics", "collective_ms.admm")
    assert coll.read(run) == pytest.approx(20.0)        # 40 ms in 2 tiles
    out = capsys.readouterr().out
    assert "all-reduce" in out and "/device:TPU:0 1.0000" in out
    skew = harness.load_module("layer_metrics", "chip_skew_pct")
    assert skew.read(run) == pytest.approx(100 * 8 / 96)
    one = fake_run({"/host:CPU": [("fusion.1", 0, 5 * ms)]})
    assert skew.read(one) is None and coll.read(one) == 0.0


def test_asynchronous_collectives_are_read_from_their_own_line(
        capsys, monkeypatch):
    """Where ``XLA Ops`` holds only ``-start``/``-done`` halves, the
    duration is on ``Async XLA Ops``, which the metric's file reads."""
    ms = 10 ** 6
    run = fake_run({"/device:TPU:0": [
        ("all-reduce-start.3", 10 * ms, 10 * ms + 1000),
        ("fusion.1", 11 * ms, 30 * ms),
        ("all-reduce-done.3", 30 * ms, 30 * ms + 1000)]})

    def event(name, dur):
        return types.SimpleNamespace(name=name, duration_ns=dur)

    trace = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/device:TPU:0", lines=[types.SimpleNamespace(
            name="Async XLA Ops", events=[
                event("%all-reduce-start.3 = f32[8] all-reduce-start()",
                      20 * ms), event("%copy-start.1 = f32[8]", 5 * ms)])])])
    monkeypatch.setattr(xplane, "load", lambda path: trace)
    coll = harness.load_module("layer_metrics", "collective_ms.admm")
    assert coll.read(run) == pytest.approx(20.0)
    assert "Async XLA Ops" in capsys.readouterr().out
