"""End-to-end SAGE-EM calibration tests: the simulation round-trip oracle.

Predict with known Jones -> calibrate -> residual collapse + recovery up to
per-cluster unitary ambiguity (SURVEY.md section 4 test strategy).
"""

import numpy as np
import jax.numpy as jnp

from sagecal_tpu import skymodel
from sagecal_tpu.config import SolverMode
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import sage
import pytest


def _calib_problem(n_stations=8, tilesz=6, n_clusters=2, nchunk=(1, 2),
                   noise=0.0, seed=0):
    rng = np.random.default_rng(seed)
    srcs = {}
    clusters = []
    for m in range(n_clusters):
        names = []
        for s in range(2):
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.02, 2)
            nn = np.sqrt(1 - ll**2 - mm**2)
            flux = float(2 + rng.random())
            srcs[nm] = skymodel.Source(
                name=nm, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1,
                sI=flux, sQ=0.1, sU=0.0, sV=0.0,
                sI0=flux, sQ0=0.1, sU0=0, sV0=0, spec_idx=0, spec_idx1=0,
                spec_idx2=0, f0=150e6)
            names.append(nm)
        clusters.append((m, nchunk[m], names))
    sky = skymodel.build_cluster_sky(srcs, clusters)
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jtrue = ds.random_jones(n_clusters, sky.nchunk, n_stations, seed=seed + 1,
                            scale=0.25)
    tile = ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                               freqs=[150e6], ra0=0.1, dec0=0.8,
                               jones=Jtrue, nchunk=sky.nchunk,
                               noise_sigma=noise, seed=seed + 2)
    return sky, dsky, Jtrue, tile


def _solve(sky, dsky, tile, solver_mode, max_emiter=3, max_iter=12,
           max_lbfgs=10):
    coh = rp.coherencies(dsky, jnp.asarray(tile.u), jnp.asarray(tile.v),
                         jnp.asarray(tile.w), jnp.asarray([tile.freq0]),
                         tile.fdelta)[:, :, 0]  # [M,B,2,2]
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    J0 = np.tile(np.eye(2, dtype=complex), (sky.n_clusters, kmax,
                                            tile.n_stations, 1, 1))
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32),
                             jnp.float64)
    cfg = sage.SageConfig(max_emiter=max_emiter, max_iter=max_iter,
                          max_lbfgs=max_lbfgs, solver_mode=int(solver_mode))
    J, info = sage.sagefit(jnp.asarray(x8), coh, jnp.asarray(tile.sta1),
                           jnp.asarray(tile.sta2), jnp.asarray(cidx),
                           jnp.asarray(cmask), jnp.asarray(J0),
                           tile.n_stations, wt, config=cfg)
    return np.asarray(J), info, coh, cidx


def test_sage_single_cluster_exact():
    # one cluster: SAGE == one LM solve + refine; must collapse to ~0
    sky, dsky, Jtrue, tile = _calib_problem(n_clusters=1, nchunk=(1,),
                                            noise=0.0)
    J, info, coh, cidx = _solve(sky, dsky, tile, SolverMode.LM_LBFGS,
                                max_emiter=2, max_iter=40, max_lbfgs=10)
    assert float(info["res_1"]) < 1e-8 * float(info["res_0"])
    Vs = (J[0][cidx[0], tile.sta1] @ np.asarray(coh[0])
          @ np.conj(J[0][cidx[0], tile.sta2].transpose(0, 2, 1)))
    Vt = (Jtrue[0][cidx[0], tile.sta1] @ np.asarray(coh[0])
          @ np.conj(Jtrue[0][cidx[0], tile.sta2].transpose(0, 2, 1)))
    assert np.abs(Vs - Vt).max() < 1e-6


def test_sage_lm_noiseless_roundtrip():
    # two coupled clusters: EM from cold start reduces the residual by
    # >50x; truth is verified (separately) to be an exact fixed point.
    # Deep convergence of coupled directions takes many tiles in practice
    # (the reference doubles first-tile iterations for the same reason,
    # fullbatch_mode.cpp:281).
    sky, dsky, Jtrue, tile = _calib_problem(noise=0.0)
    J, info, coh, cidx = _solve(sky, dsky, tile, SolverMode.LM_LBFGS)
    res0, res1 = float(info["res_0"]), float(info["res_1"])
    assert res1 < 0.02 * res0
    # gain-invariant check: corrupted model close to truth per cluster
    for m in range(sky.n_clusters):
        Vs = (J[m][cidx[m], tile.sta1] @ np.asarray(coh[m])
              @ np.conj(J[m][cidx[m], tile.sta2].transpose(0, 2, 1)))
        Vt = (Jtrue[m][cidx[m], tile.sta1] @ np.asarray(coh[m])
              @ np.conj(Jtrue[m][cidx[m], tile.sta2].transpose(0, 2, 1)))
        assert np.abs(Vs - Vt).max() < 0.15


def test_sage_warm_start_is_fixed_point():
    # truth must be an exact fixed point of the EM update (no drift)
    sky, dsky, Jtrue, tile = _calib_problem(noise=0.0)
    import jax.numpy as jnp
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod
    coh = rp.coherencies(dsky, jnp.asarray(tile.u), jnp.asarray(tile.v),
                         jnp.asarray(tile.w), jnp.asarray([tile.freq0]),
                         tile.fdelta)[:, :, 0]
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32),
                             jnp.float64)
    cfg = sage.SageConfig(max_emiter=2, max_iter=10, max_lbfgs=5,
                          solver_mode=int(SolverMode.LM_LBFGS))
    J, info = sage.sagefit(jnp.asarray(x8), coh, jnp.asarray(tile.sta1),
                           jnp.asarray(tile.sta2), jnp.asarray(cidx),
                           jnp.asarray(cmask), jnp.asarray(Jtrue),
                           tile.n_stations, wt, config=cfg)
    assert float(info["res_1"]) < 1e-12
    assert np.abs(np.asarray(J) - Jtrue).max() < 1e-10


@pytest.mark.slow
def test_sage_robust_with_outliers():
    sky, dsky, Jtrue, tile = _calib_problem(noise=0.01, seed=3)
    # inject unflagged gross outliers into 5% of rows
    rng = np.random.default_rng(9)
    out = rng.choice(tile.nrows, tile.nrows // 20, replace=False)
    tile.x[out] += 30 * (rng.normal(size=tile.x[out].shape)
                         + 1j * rng.normal(size=tile.x[out].shape))

    Jr, info_r, coh, cidx = _solve(sky, dsky, tile,
                                   SolverMode.RTR_OSRLM_RLBFGS)
    Jp, info_p, _, _ = _solve(sky, dsky, tile, SolverMode.LM_LBFGS)

    def err(J):
        tot = 0.0
        for m in range(sky.n_clusters):
            Vs = (J[m][cidx[m], tile.sta1] @ np.asarray(coh[m])
                  @ np.conj(J[m][cidx[m], tile.sta2].transpose(0, 2, 1)))
            Vt = (Jtrue[m][cidx[m], tile.sta1] @ np.asarray(coh[m])
                  @ np.conj(Jtrue[m][cidx[m], tile.sta2].transpose(0, 2, 1)))
            tot += float(np.mean(np.abs(Vs - Vt) ** 2))
        return tot

    assert err(Jr) < err(Jp)
    assert 2.0 <= float(info_r["mean_nu"]) <= 30.0


@pytest.mark.slow
def test_sage_residual_never_catastrophic():
    sky, dsky, Jtrue, tile = _calib_problem(noise=0.05, seed=5)
    J, info, _, _ = _solve(sky, dsky, tile, SolverMode.RLM_RLBFGS,
                           max_emiter=2, max_iter=8, max_lbfgs=5)
    assert np.isfinite(float(info["res_1"]))
    assert float(info["res_1"]) <= float(info["res_0"])


@pytest.mark.slow  # ~33 s (round-17 tier-1 rebalance, wave 2)
def test_fused_residual_sweep_parity():
    """SageConfig.fuse_residual folds each visit's re-subtract and the
    next visit's add-back into one pass over the running residual; the
    +/- association order is preserved, so the whole solve must be BIT
    IDENTICAL to the plain write-back sweep (both with and without the
    baseline-major normal-equation aggregation)."""
    sky, dsky, Jtrue, tile = _calib_problem(tilesz=4, noise=0.005, seed=11)
    coh = rp.coherencies(dsky, jnp.asarray(tile.u), jnp.asarray(tile.v),
                         jnp.asarray(tile.w), jnp.asarray([tile.freq0]),
                         tile.fdelta)[:, :, 0]
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    J0 = np.tile(np.eye(2, dtype=complex), (sky.n_clusters, kmax,
                                            tile.n_stations, 1, 1))
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32),
                             jnp.float64)
    outs = {}
    for fused in (True, False):
        for nbase in (0, tile.nbase):
            cfg = sage.SageConfig(max_emiter=2, max_iter=4, max_lbfgs=2,
                                  solver_mode=int(SolverMode.OSLM_LBFGS),
                                  fuse_residual=fused, nbase=nbase)
            J, info = sage.sagefit(
                jnp.asarray(x8), coh, jnp.asarray(tile.sta1),
                jnp.asarray(tile.sta2), jnp.asarray(cidx),
                jnp.asarray(cmask), jnp.asarray(J0), tile.n_stations,
                wt, config=cfg)
            outs[(fused, nbase)] = (np.asarray(J), float(info["res_1"]))
    for nbase in (0, tile.nbase):
        a, b = outs[(True, nbase)], outs[(False, nbase)]
        np.testing.assert_array_equal(a[0], b[0])
        assert a[1] == b[1]
    # the two assembly paths differ only by summation order
    np.testing.assert_allclose(outs[(True, 0)][1],
                               outs[(True, tile.nbase)][1], rtol=1e-5)


def test_sagefit_host_zero_retrace(retrace_guard):
    """Tier-1 retrace gate over the host-driven EM path: a second solve
    of the same shape reuses every per-sweep program (prelude, fused
    em_sweep, residual) — zero new compile requests. fuse/promote are
    forced so the execution plan cannot flip between runs."""
    sky, dsky, Jtrue, tile = _calib_problem(n_stations=6, tilesz=4)
    coh = rp.coherencies(dsky, jnp.asarray(tile.u), jnp.asarray(tile.v),
                         jnp.asarray(tile.w), jnp.asarray([tile.freq0]),
                         tile.fdelta)[:, :, 0]
    xa = tile.averaged()
    x8 = jnp.asarray(np.stack([xa.reshape(-1, 4).real,
                               xa.reshape(-1, 4).imag], -1).reshape(-1, 8))
    cidx = jnp.asarray(rp.chunk_indices(tile.tilesz, tile.nbase,
                                        sky.nchunk))
    kmax = int(sky.nchunk.max())
    cmask = jnp.asarray(np.arange(kmax)[None, :] < sky.nchunk[:, None])
    J0 = jnp.asarray(np.tile(np.eye(2, dtype=complex),
                             (sky.n_clusters, kmax, tile.n_stations,
                              1, 1)))
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32),
                             jnp.float64)
    cfg = sage.SageConfig(max_emiter=2, max_iter=4, max_lbfgs=0,
                          solver_mode=int(SolverMode.OSLM_LBFGS),
                          fuse="on", promote="off")

    def thunk():
        return sage.sagefit_host(x8, coh, jnp.asarray(tile.sta1),
                                 jnp.asarray(tile.sta2), cidx, cmask,
                                 J0, tile.n_stations, wt, config=cfg)

    retrace_guard(thunk)
