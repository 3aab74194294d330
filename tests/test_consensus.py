"""Consensus layer tests: polynomials, manifold averaging, mesh ADMM."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sagecal_tpu import skymodel
from sagecal_tpu.config import SolverMode
from sagecal_tpu.consensus import admm as cadmm
from sagecal_tpu.consensus import manifold as mf
from sagecal_tpu.consensus import poly as cpoly
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lm as lm_mod, normal_eq as ne, sage
from sagecal_tpu import utils


def test_polynomial_bases():
    freqs = np.linspace(120e6, 160e6, 8)
    B0 = cpoly.setup_polynomials(freqs, 140e6, 3, ptype=0)
    np.testing.assert_allclose(B0[:, 0], 1.0)
    np.testing.assert_allclose(B0[:, 1], (freqs - 140e6) / 140e6)
    np.testing.assert_allclose(B0[:, 2], ((freqs - 140e6) / 140e6) ** 2)

    B1 = cpoly.setup_polynomials(freqs, 140e6, 3, ptype=1)
    np.testing.assert_allclose((B1 ** 2).sum(0), 1.0, rtol=1e-12)

    B2 = cpoly.setup_polynomials(freqs, 140e6, 3, ptype=2)
    # Bernstein partition of unity
    np.testing.assert_allclose(B2.sum(axis=1), 1.0, rtol=1e-12)

    B3 = cpoly.setup_polynomials(freqs, 140e6, 4, ptype=3)
    np.testing.assert_allclose(B3[:, 1], (freqs - 140e6) / 140e6)
    np.testing.assert_allclose(B3[:, 2], 140e6 / freqs - 1.0)


def test_find_prod_inverse_and_z():
    rng = np.random.default_rng(0)
    nf, P_, M = 6, 3, 2
    B = cpoly.setup_polynomials(np.linspace(120e6, 160e6, nf), 140e6, P_, 2)
    rho = np.abs(rng.normal(2, 0.3, (M, nf)))
    Bi = np.asarray(cpoly.find_prod_inverse(B, rho))
    for m in range(M):
        S = sum(rho[m, f] * np.outer(B[f], B[f]) for f in range(nf))
        np.testing.assert_allclose(Bi[m], np.linalg.pinv(S), rtol=1e-8)

    # consensus recovery oracle: Z true polynomial coefficients; per-freq
    # solutions J_f = B_f Z; then z-sum -> Z recovered exactly
    Ztrue = rng.normal(size=(M, P_, 5))
    Jf = np.einsum("fp,mpx->fmx", B, Ztrue)
    zsum = np.einsum("fp,mf,fmx->mpx", B, rho, Jf)
    Zrec = np.asarray(cpoly.z_from_contributions(jnp.asarray(zsum),
                                                 jnp.asarray(Bi)))
    np.testing.assert_allclose(Zrec, Ztrue, rtol=1e-7, atol=1e-9)


def test_soft_threshold():
    z = jnp.asarray([-3.0, -0.5, 0.2, 2.0])
    out = np.asarray(cpoly.soft_threshold(z, 1.0))
    np.testing.assert_allclose(out, [-2.0, 0.0, 0.0, 1.0])


def test_update_rho_bb():
    rng = np.random.default_rng(1)
    dY = rng.normal(size=(3, 10))
    # perfectly correlated: alphaSD = alphaMG = 2 -> update to 2
    rho = np.asarray(cpoly.update_rho_bb(
        jnp.asarray([5.0, 5.0, 5.0]), jnp.asarray([100.0] * 3),
        jnp.asarray(2 * dY), jnp.asarray(dY), axes=(1,)))
    np.testing.assert_allclose(rho, 2.0, rtol=1e-6)
    # uncorrelated noise: no update
    dJ = rng.normal(size=(3, 10))
    rho2 = np.asarray(cpoly.update_rho_bb(
        jnp.asarray([5.0, 5.0, 5.0]), jnp.asarray([100.0] * 3),
        jnp.asarray(dY), jnp.asarray(dJ), axes=(1,)))
    corr_ok = (dY * dJ).sum(1) / np.sqrt((dY**2).sum(1) * (dJ**2).sum(1)) > 0.2
    assert np.all((rho2 == 5.0) | corr_ok)


def test_polar_unitary():
    rng = np.random.default_rng(2)
    A = rng.normal(size=(5, 2, 2)) + 1j * rng.normal(size=(5, 2, 2))
    U = np.asarray(mf.polar_unitary_2x2(jnp.asarray(A)))
    eye = np.einsum("bij,bkj->bik", U, U.conj())
    np.testing.assert_allclose(eye, np.tile(np.eye(2), (5, 1, 1)), atol=1e-10)
    # U is the closest unitary: for A already unitary, U == A
    Q = np.linalg.qr(A[0])[0]
    U2 = np.asarray(mf.polar_unitary_2x2(jnp.asarray(Q)))
    np.testing.assert_allclose(U2, Q, atol=1e-10)


def test_manifold_average_removes_unitary_ambiguity():
    rng = np.random.default_rng(3)
    nf, M, N = 4, 2, 6
    Jbase = rng.normal(size=(M, N, 2, 2)) + 1j * rng.normal(size=(M, N, 2, 2))
    # per-frequency random unitary corruption: J_f = J U_f
    J = np.zeros((nf, M, N, 2, 2), complex)
    for f in range(nf):
        for m in range(M):
            A = rng.normal(size=(2, 2)) + 1j * rng.normal(size=(2, 2))
            U = np.asarray(mf.polar_unitary_2x2(jnp.asarray(A)))
            J[f, m] = J[f, m] = Jbase[m] @ U
    out = np.asarray(mf.manifold_average(jnp.asarray(J), niter=10))
    # after averaging all frequencies should agree closely
    spread = np.abs(out - out.mean(axis=0, keepdims=True)).max()
    spread_before = np.abs(J - J.mean(axis=0, keepdims=True)).max()
    assert spread < 1e-8
    assert spread_before > 0.1
    # and each block is only rotated: J_out J_out^H == J J^H per station
    for f in range(nf):
        for m in range(M):
            G1 = J[f, m] @ J[f, m].conj().transpose(0, 2, 1)
            G2 = out[f, m] @ out[f, m].conj().transpose(0, 2, 1)
            np.testing.assert_allclose(G1, G2, atol=1e-8)


def _subband_problem(nf=4, n_stations=6, tilesz=2, seed=0):
    rng = np.random.default_rng(seed)
    srcs, clusters = {}, []
    for m in range(2):
        names = []
        for s in range(2):
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.02, 2)
            nn = np.sqrt(1 - ll * ll - mm * mm)
            srcs[nm] = skymodel.Source(
                name=nm, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1, sI=2.0,
                sQ=0, sU=0, sV=0, sI0=2.0, sQ0=0, sU0=0, sV0=0,
                spec_idx=0, spec_idx1=0, spec_idx2=0, f0=150e6)
            names.append(nm)
        clusters.append((m, 1, names))
    sky = skymodel.build_cluster_sky(srcs, clusters)
    dsky = rp.sky_to_device(sky, jnp.float64)
    freqs = 150e6 * (1 + 0.02 * np.arange(nf))

    # smooth-in-frequency true Jones: J_f = J0 + slope * (f-f0)/f0
    Jbase = ds.random_jones(2, sky.nchunk, n_stations, seed=seed + 1,
                            scale=0.15)
    slope = ds.random_jones(2, sky.nchunk, n_stations, seed=seed + 2,
                            scale=0.05) - np.eye(2)
    tiles = []
    Jtrue = []
    for f, fr in enumerate(freqs):
        Jf = Jbase + slope * (fr - 150e6) / 150e6
        Jtrue.append(Jf)
        tiles.append(ds.simulate_dataset(
            dsky, n_stations=n_stations, tilesz=tilesz, freqs=[fr],
            ra0=0.1, dec0=0.9, jones=Jf, nchunk=sky.nchunk,
            noise_sigma=0.01, seed=seed + 3))
    return sky, dsky, freqs, tiles, np.asarray(Jtrue)


@pytest.mark.parametrize("ndev", [4])
@pytest.mark.slow
def test_mesh_admm_roundtrip(ndev):
    nf = 4
    sky, dsky, freqs, tiles, Jtrue = _subband_problem(nf=nf)
    n = tiles[0].n_stations
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("freq",))
    cidx = rp.chunk_indices(tiles[0].tilesz, tiles[0].nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)

    cfg = cadmm.ADMMConfig(
        n_admm=4, npoly=2, rho=2.0, manifold_iters=5,
        sage=sage.SageConfig(max_emiter=2, max_iter=8, max_lbfgs=4,
                             solver_mode=int(SolverMode.LM_LBFGS)))
    runner = cadmm.make_admm_runner(
        dsky, tiles[0].sta1, tiles[0].sta2, cidx, cmask, n,
        tiles[0].fdelta, B, cfg, mesh, nf)

    def stack(fn):
        return np.stack([fn(t) for t in tiles])

    x8F = stack(lambda t: np.stack(
        [t.averaged().reshape(-1, 4).real, t.averaged().reshape(-1, 4).imag],
        -1).reshape(-1, 8))
    uF, vF, wF = stack(lambda t: t.u), stack(lambda t: t.v), stack(lambda t: t.w)
    wtF = stack(lambda t: np.asarray(
        lm_mod.make_weights(jnp.asarray(t.flags, jnp.int32), jnp.float64)))
    fratioF = np.ones(nf)
    J0F = np.asarray(utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex), (nf, sky.n_clusters, kmax, n, 1, 1))))

    sh = NamedSharding(mesh, P("freq"))
    args = [jax.device_put(jnp.asarray(a), sh) for a in
            (x8F, uF, vF, wF, freqs, wtF, fratioF, J0F)]
    JF_r8, Z, rhoF, res0, res1, r1s, duals, Y0F, _ = runner(*args)

    JF = utils.jones_r2c_np(np.asarray(JF_r8)).reshape(
        nf, sky.n_clusters, kmax, n, 2, 2)
    assert np.isfinite(np.asarray(res1)).all()
    # per-subband solves reduced the residual
    assert np.all(np.asarray(res1) < np.asarray(res0))
    # dual residual decreases over iterations (consensus converging)
    duals = np.asarray(duals)
    assert duals[-1] < duals[0] * 2  # non-exploding
    # consensus: gain-invariant products close to the smooth truth
    for f in range(nf):
        for m in range(sky.n_clusters):
            Gs = JF[f, m, 0] @ JF[f, m, 0].conj().transpose(0, 2, 1)
            Gt = Jtrue[f, m, 0] @ Jtrue[f, m, 0].conj().transpose(0, 2, 1)
            err = np.abs(Gs - Gt).mean() / np.abs(Gt).mean()
            assert err < 0.2, (f, m, err)


@pytest.mark.slow
def test_host_loop_admm_matches_traced():
    """host_loop=True (one bounded execution per ADMM iteration, the
    single-chip plan) must reproduce the fully traced runner."""
    nf = 4
    sky, dsky, freqs, tiles, Jtrue = _subband_problem(nf=nf)
    n = tiles[0].n_stations
    mesh = Mesh(np.array(jax.devices()[:4]), ("freq",))
    cidx = rp.chunk_indices(tiles[0].tilesz, tiles[0].nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)

    cfg = cadmm.ADMMConfig(
        n_admm=3, npoly=2, rho=2.0, manifold_iters=3, adaptive_rho=True,
        sage=sage.SageConfig(max_emiter=1, max_iter=5, max_lbfgs=2,
                             solver_mode=int(SolverMode.LM_LBFGS)))
    common = (dsky, tiles[0].sta1, tiles[0].sta2, cidx, cmask, n,
              tiles[0].fdelta, B, cfg, mesh, nf)
    runner_t = cadmm.make_admm_runner(*common)
    runner_h = cadmm.make_admm_runner(*common, host_loop=True)

    def stack(fn):
        return np.stack([fn(t) for t in tiles])

    x8F = stack(lambda t: np.stack(
        [t.averaged().reshape(-1, 4).real,
         t.averaged().reshape(-1, 4).imag], -1).reshape(-1, 8))
    uF, vF, wF = (stack(lambda t: t.u), stack(lambda t: t.v),
                  stack(lambda t: t.w))
    wtF = stack(lambda t: np.asarray(
        lm_mod.make_weights(jnp.asarray(t.flags, jnp.int32), jnp.float64)))
    fratioF = np.ones(nf)
    J0F = np.asarray(utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex), (nf, sky.n_clusters, kmax, n, 1, 1))))
    sh = NamedSharding(mesh, P("freq"))
    args = [jax.device_put(jnp.asarray(a), sh) for a in
            (x8F, uF, vF, wF, freqs, wtF, fratioF, J0F)]

    out_t = runner_t(*args)
    out_h = runner_h(*args)
    names = ("JF", "Z", "rhoF", "res0", "res1", "r1s", "duals", "Y0F")
    for nm, a, b in zip(names, out_t, out_h):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8, err_msg=nm)


@pytest.mark.slow
def test_blocked_admm_matches_host_loop():
    """make_admm_runner_blocked (J-update split into subband blocks, one
    bounded execution each — the north-star single-chip path) must
    reproduce the folded host_loop runner exactly."""
    nf = 6
    sky, dsky, freqs, tiles, Jtrue = _subband_problem(nf=nf)
    n = tiles[0].n_stations
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("freq",))
    cidx = rp.chunk_indices(tiles[0].tilesz, tiles[0].nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)

    cfg = cadmm.ADMMConfig(
        n_admm=3, npoly=2, rho=2.0, manifold_iters=3, adaptive_rho=True,
        sage=sage.SageConfig(max_emiter=1, max_iter=5, max_lbfgs=2,
                             solver_mode=int(SolverMode.LM_LBFGS)))
    runner_h = cadmm.make_admm_runner(
        dsky, tiles[0].sta1, tiles[0].sta2, cidx, cmask, n,
        tiles[0].fdelta, B, cfg, mesh1, nf, host_loop=True)
    timer = []
    runner_b = cadmm.make_admm_runner_blocked(
        dsky, tiles[0].sta1, tiles[0].sta2, cidx, cmask, n,
        tiles[0].fdelta, B, cfg, nf, block_f=4, timer=timer)

    def stack(fn):
        return np.stack([fn(t) for t in tiles])

    x8F = stack(lambda t: np.stack(
        [t.averaged().reshape(-1, 4).real,
         t.averaged().reshape(-1, 4).imag], -1).reshape(-1, 8))
    uF, vF, wF = (stack(lambda t: t.u), stack(lambda t: t.v),
                  stack(lambda t: t.w))
    wtF = stack(lambda t: np.asarray(
        lm_mod.make_weights(jnp.asarray(t.flags, jnp.int32), jnp.float64)))
    fratioF = np.ones(nf)
    J0F = np.asarray(utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex), (nf, sky.n_clusters, kmax, n, 1, 1))))
    sh1 = NamedSharding(mesh1, P("freq"))
    args = [jax.device_put(jnp.asarray(a), sh1) for a in
            (x8F, uF, vF, wF, freqs, wtF, fratioF, J0F)]

    out_h = runner_h(*args)
    out_b = runner_b(*[jnp.asarray(a) for a in
                       (x8F, uF, vF, wF, freqs, wtF, fratioF, J0F)])
    names = ("JF", "Z", "rhoF", "res0", "res1", "r1s", "duals", "Y0F")
    for nm, a, b in zip(names, out_h, out_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8, err_msg=nm)
    # per-execution telemetry recorded: 2 solve blocks x 3 iters + cons
    labels = [l for l, _ in timer]
    assert labels.count("cons0") == 1
    assert sum(l.startswith("solve[") for l in labels) == 2 * 3
