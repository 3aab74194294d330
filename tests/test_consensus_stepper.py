"""``cli_mpi.ConsensusStepper``: the consensus interval loop behind a
seam, the device scopes of what consensus adds, and the benchmark's plain
consensus reference against the program's algebra."""

import filecmp
import math
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu import cli_mpi, sched, skymodel
from sagecal_tpu.consensus import admm as cadmm, poly as cpoly
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import sage

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import reference_consensus as refc      # noqa: E402

N_STA, TILESZ, N_TILES, NF = 8, 3, 3, 2


def make_observation(root):
    """Two subbands of three intervals each, and the sky they see."""
    os.makedirs(root)
    sky_path = os.path.join(root, "sky.txt")
    with open(sky_path, "w") as f:
        f.write("P0A 0 40 0 40 0 0 3.0 0 0 0 -0.7 0 0 0 0 150e6\n"
                "P1A 1 20 0 38 0 0 2.5 0 0 0 -0.7 0 0 0 0 150e6\n")
    clus_path = os.path.join(root, "sky.cluster")
    with open(clus_path, "w") as f:
        f.write("0 1 P0A\n1 1 P1A\n")
    ra0, dec0 = (41 / 60) * math.pi / 12, 40 * math.pi / 180
    freqs = [140e6, 160e6]
    paths = []
    for k, fr in enumerate(freqs):
        srcs = skymodel.parse_sky_model(sky_path, ra0, dec0, fr)
        sky = skymodel.build_cluster_sky(
            srcs, skymodel.parse_cluster_file(clus_path))
        dsky = rp.sky_to_device(sky, jnp.float64)
        jones = ds.random_jones(sky.n_clusters, sky.nchunk, N_STA,
                                seed=1, scale=0.2)
        tiles = [ds.simulate_dataset(
            dsky, n_stations=N_STA, tilesz=TILESZ, freqs=[fr], ra0=ra0,
            dec0=dec0, jones=jones, nchunk=sky.nchunk, noise_sigma=0.01,
            seed=5 + 10 * k + i) for i in range(N_TILES)]
        paths.append(os.path.join(root, f"sb{k}.ms"))
        ds.SimMS.create(paths[-1], tiles)
    return sky_path, clus_path, paths


def argv(root):
    listfile = os.path.join(root, "mslist.txt")
    with open(listfile, "w") as f:
        f.write("".join(os.path.join(root, f"sb{k}.ms") + "\n"
                        for k in range(NF)))
    return ["-f", listfile, "-s", os.path.join(root, "sky.txt"),
            "-c", os.path.join(root, "sky.cluster"),
            "-p", os.path.join(root, "zsol.txt"), "-A", "3", "-P", "2",
            "-Q", "2", "-r", "2", "-e", "2", "-g", "4", "-l", "3", "-j", "0",
            "-t", str(TILESZ)]


def test_stepping_by_hand_writes_the_same_bytes_as_main(tmp_path):
    """Three intervals of two subbands: ``main()`` on one copy of the
    data, a ``ConsensusStepper`` stepped by hand (its own ``Prefetcher``,
    as a driver would) on another.  Residual columns, per-subband
    solutions and the global Z file come out byte for byte the same: it
    is ONE loop."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    make_observation(a)
    shutil.copytree(a, b)
    assert cli_mpi.main(argv(a)) == 0

    st = cli_mpi.ConsensusStepper(
        cli_mpi.build_parser().parse_args(argv(b)), log=lambda *x: None)
    assert st.n_intervals == N_TILES and st.start == 0
    pf = sched.Prefetcher(
        lambda i: (lambda t: (t, st.stage(i, t)))(st.read(i)),
        st.n_intervals, depth=st.depth)
    try:
        for i, (tiles, staged), wait in pf:
            rec = st.step(st.start + i, tiles, staged, wait)
            assert rec["tile"] == i and rec["res_1"] < rec["res_0"]
    finally:
        pf.close()
        st.close()
    assert [r["tile"] for r in st.history] == [0, 1, 2]
    assert set(st.history[0]) == {"tile", "res_0", "res_1", "primal",
                                  "dual"}

    assert filecmp.cmp(os.path.join(a, "zsol.txt"),
                       os.path.join(b, "zsol.txt"), shallow=False)
    for k in range(NF):
        assert filecmp.cmp(os.path.join(a, f"sb{k}.ms.solutions"),
                           os.path.join(b, f"sb{k}.ms.solutions"),
                           shallow=False)
        for t in range(N_TILES):
            ra, rb = (ds.SimMS(os.path.join(d, f"sb{k}.ms"),
                               data_column="CORRECTED_DATA").read_tile(t).x
                      for d in (a, b))
            assert ra.tobytes() == rb.tobytes()
            raw = ds.SimMS(os.path.join(a, f"sb{k}.ms")).read_tile(t).x
            assert np.abs(ra).mean() < 0.1 * np.abs(raw).mean()


def _step_all(root, extra=(), on_step=None):
    """Step the observation under ``root`` to its end as a driver
    would; returns the stepper (closed) and what it wrote."""
    st = cli_mpi.ConsensusStepper(
        cli_mpi.build_parser().parse_args(argv(root) + list(extra)),
        log=lambda *x: None)
    pf = sched.Prefetcher(
        lambda i: (lambda t: (t, st.stage(i, t)))(st.read(i)),
        st.n_intervals, depth=st.depth)
    try:
        for i, (tiles, staged), wait in pf:
            if on_step:
                on_step(st, i, staged)
            st.step(st.start + i, tiles, staged, wait)
    finally:
        pf.close()
        st.close()
    out = [open(os.path.join(root, "zsol.txt"), "rb").read()]
    for k in range(NF):
        out.append(open(os.path.join(root, f"sb{k}.ms.solutions"),
                        "rb").read())
        ms = ds.SimMS(os.path.join(root, f"sb{k}.ms"),
                      data_column="CORRECTED_DATA")
        out += [ms.read_tile(t).x.tobytes() for t in range(N_TILES)]
    return st, out


# -x 120 leaves a seventh of the rows out of the solve; -P 1 a consensus
# that two subbands do not meet exactly, so ``primal`` is a number
UVCUT = ["-x", "120", "-P", "1"]


def test_stage_runs_nothing_on_a_device_and_reads_nothing_back(
        tmp_path, monkeypatch):
    """The reader's half of an interval under ``-x``: no ``wait`` span
    under ``stage`` in the trace, no call of the two device forms whose
    results used to be read back (``rime.predict.uvcut_flags``,
    ``lm.make_weights``) from the reader's thread, and the residual's
    data inputs staged with the interval in the dtypes and shapes
    ``res_jit`` compiled for: the steps after the first compile nothing.

    On the CPU backend ``np.asarray`` of a ``jax.Array`` goes through
    the buffer protocol (a spy on ``jax.Array.__array__`` is never
    called) and ``jax.transfer_guard_device_to_host("disallow")`` does
    not fire on the tree before PR 50 either, so the read-backs are held
    by their sources and their spans; on that tree this test fails on
    both."""
    import threading
    from sagecal_tpu.diag import guard, trace
    from sagecal_tpu.solvers import lm as lm_mod

    root = str(tmp_path / "obs")
    make_observation(root)
    calls = []

    def spy(name, fn):
        def inner(*a, **k):
            calls.append((name, threading.current_thread().name))
            return fn(*a, **k)
        return inner
    monkeypatch.setattr(rp, "uvcut_flags", spy("uvcut_flags",
                                               rp.uvcut_flags))
    monkeypatch.setattr(lm_mod, "make_weights",
                        spy("make_weights", lm_mod.make_weights))
    logged, seen = [], []

    def on_step(st, i, staged):
        logged.append(guard.compiles_logged())
        seen.append(staged)

    diag = str(tmp_path / "diag.jsonl")
    trace.enable(diag)
    try:
        st, _ = _step_all(root, UVCUT, on_step)
        logged.append(guard.compiles_logged())
    finally:
        trace.disable()
    assert st.depth == 1
    assert not [c for c in calls if c[1] != "MainThread"], calls

    ph = {r["id"]: r for r in trace.read(diag) if r["ev"] == "phase"}
    stages = [r for r in ph.values() if r["name"] == "stage"]
    assert len(stages) == N_TILES
    assert {r["thread"] for r in stages} == {"prefetch-read"}
    # host arithmetic and copies alone (PR 53 names them): no ``wait``,
    # no ``dispatch``
    under_stage = {r["name"] for r in ph.values()
                   if r["parent"] is not None
                   and ph[r["parent"]]["name"] == "stage"}
    assert under_stage == {"pack", "copy"}, under_stage
    # the residual's carry is the solved Jones', and it is still there
    carries = [r for r in ph.values() if r["name"] == "carry"
               and r["parent"] is not None
               and ph[r["parent"]]["name"] == "residual"]
    assert len(carries) == N_TILES

    # what the reader staged for the residual: unpadded subbands, the
    # data column in the storage dtype, the geometry in the pipeline's
    nrows = st.t0.nrows
    for staged in seen:
        x_r, u, v, w = staged["res_dev"]
        assert all(isinstance(a, jax.Array) for a in (x_r, u, v, w))
        assert x_r.shape == (NF, nrows, 1, 2, 2, 2) and x_r.dtype == st.sdt
        assert u.shape == v.shape == w.shape == (NF, nrows)
        assert u.dtype == v.dtype == w.dtype == st.rdt
        assert "uvw" not in staged
    assert st._freqs_dev.shape == (NF,) and st._freqs_dev.dtype == st.rdt
    # logged[k]: before step k; the first step compiles, no other does
    assert logged[1] > logged[0]
    assert logged[1] == logged[2] == logged[3], logged


def _device_form_staging(monkeypatch):
    """``apply_uvcut`` and the weights as they were staged until PR 50:
    on the device, and read back."""
    from sagecal_tpu.solvers import lm as lm_mod

    from test_predict import _device_uvcut

    def make_weights_np(flags, dtype):
        return np.asarray(lm_mod.make_weights(
            jnp.asarray(flags, jnp.int32), dtype))
    monkeypatch.setattr(rp, "apply_uvcut", _device_uvcut)
    monkeypatch.setattr(lm_mod, "make_weights_np", make_weights_np)


#: ``history`` of ``argv(root) + UVCUT`` on the tree before PR 50 (its
#: commit 3f74ccf, this observation's seeds, x64 on the CPU)
PARENT_HISTORY = [
    {"tile": 0, "res_0": 0.036468088400320134,
     "res_1": 0.0013268116207259678, "primal": 0.017227996846369432,
     "dual": 0.0056057856696589406},
    {"tile": 1, "res_0": 0.0054959613087502005,
     "res_1": 0.0003712185856553853, "primal": 0.002191364207020298,
     "dual": 0.0006118288925093115},
    {"tile": 2, "res_0": 0.0007957928103041912,
     "res_1": 0.0003396320482975097, "primal": 0.0009001271540460012,
     "dual": 6.458535230297722e-05}]


def test_host_staging_writes_the_parents_bytes(tmp_path, monkeypatch):
    """One seeded observation under ``-x`` stepped three ways: with the
    reader ahead (``--prefetch 1``), inline (``--prefetch 0``), and with
    the uv cut and the weights made on the device and read back as
    before PR 50. Residual columns, solutions files and the Z file are
    the same bytes and ``history`` the same numbers in all three, and
    ``history`` is the one the parent tree printed for this seed (to
    1e-9, a thousand times finer than the log's six digits: another
    CPU's last bits may differ, a moved order of operations would
    not hide there)."""
    src = str(tmp_path / "src")
    make_observation(src)
    runs = []
    for name, extra, device_form in (("ahead", ["--prefetch", "1"], False),
                                     ("inline", ["--prefetch", "0"], False),
                                     ("device", ["--prefetch", "1"], True)):
        root = str(tmp_path / name)
        shutil.copytree(src, root)
        with monkeypatch.context() as mp:
            if device_form:
                _device_form_staging(mp)
            st, out = _step_all(root, UVCUT + extra)
        runs.append((st.history, out))
    (h0, out0), rest = runs[0], runs[1:]
    for h, out in rest:
        assert h == h0
        assert out == out0
    assert [set(r) for r in h0] == [set(r) for r in PARENT_HISTORY]
    for got, want in zip(h0, PARENT_HISTORY):
        for key, val in want.items():
            assert got[key] == pytest.approx(val, rel=1e-9), (key, got)
    # the cut was on: a run without it is another result
    root = str(tmp_path / "nocut")
    shutil.copytree(src, root)
    st, out = _step_all(root, ["-P", "1"])
    assert st.history[0]["res_0"] != h0[0]["res_0"] and out != out0


def _parts(basis, rho, n_clusters, n_sta, nf):
    """The consensus halves of ``make_admm_runner`` with every subband
    local (``ax=None``): nothing of the solve is built or run."""
    cmask = np.ones((n_clusters, 1), bool)
    cfg = cadmm.ADMMConfig(n_admm=3, npoly=basis.shape[1], poly_type=2,
                           rho=rho, sage=sage.SageConfig())
    return cadmm.make_admm_runner(
        None, np.zeros(4, np.int32), np.ones(4, np.int32),
        np.zeros((n_clusters, 4), np.int32), cmask, n_sta, 180e3,
        basis.astype(np.float32), cfg, None, nf, _return_parts=True)


@pytest.mark.parametrize("npoly", [2, 3])
def test_reference_consensus_algebra_matches_the_program(npoly):
    """The benchmark's numpy reference (Bernstein basis, Z update, dual
    update, BZ) against ``consensus/poly.py`` and ONE iteration's
    consensus half of ``make_admm_runner`` on seeded random J, Y, rho.

    Tolerances: the basis is float64 on both sides, 1e-12.  The program's
    consensus state is float32: an einsum over F = 4 subbands and a P x P
    pseudo-inverse whose condition is under 30 (rho within a factor of
    three, Bernstein rows) lose a few ulps of 6e-8 each, so 1e-5 of the
    largest entry is twenty times the expected error and a hundred times
    under any change of the algebra (a dropped rho, B Z at the wrong
    subband)."""
    rng = np.random.default_rng(30 + npoly)
    nf, m, n = 4, 3, 5
    freqs = np.array([120e6, 140e6, 160e6, 180e6])
    basis = refc.bernstein_basis(freqs, npoly)
    np.testing.assert_allclose(
        basis, cpoly.setup_polynomials(freqs, freqs.mean(), npoly, 2),
        rtol=0, atol=1e-12)
    rho_m = rng.uniform(2.0, 6.0, m)
    rho = np.tile(rho_m, (nf, 1))                       # [F, M]
    jones = rng.normal(size=(nf, m, 1, n, 8))
    y = rng.normal(size=(nf, m, 1, n, 8))
    z_old = rng.normal(size=(m, npoly, 1, n, 8))
    f32 = lambda a: jnp.asarray(a, jnp.float32)         # noqa: E731
    zeros = jnp.zeros((m, npoly, 1, n, 8), jnp.float32)
    carry = (f32(jones), f32(y), f32(z_old), f32(rho), f32(y),
             f32(jones), zeros, zeros, f32(rho))
    parts = _parts(basis, rho_m, m, n, nf)
    out, (_, _, dual) = parts["body_post"](
        f32(jones).reshape(nf, -1), f32(np.ones(nf)), f32(np.ones(nf)),
        carry, jnp.asarray(1, jnp.int32), ax=None)
    assert out[2].dtype == jnp.float32
    z_ref = refc.z_update(basis, y, jones, rho)
    y_ref = refc.dual_update(y, jones, basis, z_ref, rho)
    for got, want in ((out[2], z_ref), (out[1], y_ref)):
        assert np.abs(np.asarray(got) - want).max() \
            < 1e-5 * np.abs(want).max()
    # B Z at a subband, and the master's convergence axis
    np.testing.assert_allclose(
        refc.bz(basis, z_ref)[2], np.asarray(cpoly.bz(z_ref, basis[2])),
        rtol=1e-12)
    assert float(dual) == pytest.approx(
        np.linalg.norm(z_ref - z_old) / math.sqrt(z_ref.size), rel=1e-5)
    j_c = jones[..., 0::2] + 1j * jones[..., 1::2]      # any pairing
    z_c = z_ref[..., 0::2] + 1j * z_ref[..., 1::2]
    assert refc.primal_residual(j_c, basis, z_c) == pytest.approx(
        np.linalg.norm(jones - refc.bz(basis, z_ref))
        / math.sqrt(jones.size), rel=1e-12)


def test_z_file_round_trip(tmp_path):
    """The global Z file as ``ConsensusStepper.step`` lays it out, read
    back by the reference's reader."""
    from sagecal_tpu import utils
    from sagecal_tpu.io import solutions as sol
    rng = np.random.default_rng(3)
    m, p, n = 3, 2, 4
    z_r8 = rng.normal(size=(m, p, 1, n, 8))
    path = str(tmp_path / "z.txt")
    w = sol.SolutionWriter(path, 150e6, 60e6, 1.0, n, m, m * p)
    for scale in (1.0, 2.0):
        w.write_interval(utils.jones_r2c_np(
            (scale * z_r8).transpose(0, 2, 1, 3, 4).reshape(m, p, n, 8)),
            np.ones(m, int) * p)
    w.close()
    back = refc.read_z_file(path, p)
    assert len(back) == 2 and back[0].shape == (m, p, n, 2, 2)
    np.testing.assert_allclose(back[1], utils.jones_r2c_np(2 * z_r8[:, :, 0]),
                               rtol=1e-6)


def test_consensus_scopes_are_in_the_mesh_program_only():
    """``sage/consensus`` and ``sage/manifold`` are names in the lowered
    mesh program; the one-chip cells' solve (``cli``'s ``_jit_sagefit``)
    gains no name, so its program and its cache key stay."""
    from jax.sharding import Mesh
    from test_sage import _calib_problem
    from sagecal_tpu.solvers import lm as lm_mod
    sky, dsky, _, tile = _calib_problem(n_stations=5, tilesz=2,
                                        nchunk=(1, 1))
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    scfg = sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=2,
                           solver_mode=0)
    nf, nrows, n = 2, tile.nrows, tile.n_stations
    basis = refc.bernstein_basis([140e6, 160e6], 2)
    mesh = Mesh(np.array(jax.devices()[:nf]), ("freq",))
    runner = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n, tile.fdelta, basis,
        cadmm.ADMMConfig(n_admm=2, npoly=2, rho=2.0, sage=scfg), mesh, nf)
    f64 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float64)   # noqa: E731
    text = runner.lower(
        f64(nf, nrows, 8), f64(nf, nrows), f64(nf, nrows), f64(nf, nrows),
        f64(nf), f64(nf, nrows, 8), f64(nf),
        f64(nf, sky.n_clusters, kmax, n, 8)).as_text(debug_info=True)
    for scope in ("sage/consensus", "sage/manifold", "sage/sweep"):
        assert scope in text, scope

    coh = f64(sky.n_clusters, nrows, 2, 2).update(dtype=jnp.complex128)
    J0 = f64(sky.n_clusters, kmax, n, 2, 2).update(dtype=jnp.complex128)
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32),
                             jnp.float64)
    solo = sage._jit_sagefit.lower(
        f64(nrows, 8), coh, jnp.asarray(tile.sta1), jnp.asarray(tile.sta2),
        jnp.asarray(cidx), jnp.asarray(cmask), J0, n, wt,
        jnp.asarray(2.0), scfg, None, 0,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "sage/sweep" in solo
    assert "sage/consensus" not in solo and "sage/manifold" not in solo
