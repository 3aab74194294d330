"""Scale-up correctness: LOFAR-like shapes + mesh-ADMM subband folding.

VERDICT round-1 item 6: the padding/memory discipline ([M, B] per-cluster
lax.map in predict, [K, 8N, 8N] normal matrices) and the F > n_devices
multiplexing-by-folding claim (consensus/admm.py) were untested at the
shapes that matter. These run on the 8-device CPU mesh with minimal
iteration counts — shape/padding coverage, not convergence depth.
"""

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import skymodel
from sagecal_tpu.config import SolverMode
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import sage
import pytest


def _big_sky(n_clusters=32, seed=21):
    """32 directions with ragged per-cluster source counts and hybrid
    time-chunking (nchunk 1/2/4 mixed) — the padding stress shape."""
    rng = np.random.default_rng(seed)
    srcs, clusters = {}, []
    for m in range(n_clusters):
        names = []
        for s in range(1 + m % 3):          # ragged source counts
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.04, 2)
            nn = np.sqrt(max(1 - ll * ll - mm * mm, 0.0))
            srcs[nm] = skymodel.Source(
                name=nm, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1,
                sI=float(0.5 + 2 * rng.random()), sQ=0.0, sU=0.0, sV=0.0,
                sI0=1.0, sQ0=0, sU0=0, sV0=0, spec_idx=0, spec_idx1=0,
                spec_idx2=0, f0=150e6)
            names.append(nm)
        clusters.append((m, (1, 2, 4)[m % 3], names))   # hybrid chunks
    return skymodel.build_cluster_sky(srcs, clusters)


@pytest.mark.slow
def test_lofar_scale_62_stations_32_directions():
    """One EM pass at 62 stations x 32 directions x hybrid chunks: the
    [K, 8N, 8N] normal systems (K<=4, 8N=496) and padded [M, B] predict
    must produce finite, residual-reducing output."""
    n_stations, tilesz = 62, 4
    sky = _big_sky()
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jtrue = ds.random_jones(sky.n_clusters, sky.nchunk, n_stations,
                            seed=22, scale=0.15)
    tile = ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                               freqs=[150e6], ra0=0.1, dec0=0.9,
                               jones=Jtrue, nchunk=sky.nchunk,
                               noise_sigma=0.005, seed=23)
    kmax = int(sky.nchunk.max())
    assert kmax == 4 and sky.n_clusters == 32
    cidx = jnp.asarray(rp.chunk_indices(tilesz, tile.nbase, sky.nchunk))
    cmask = jnp.asarray(np.arange(kmax)[None, :] < sky.nchunk[:, None])
    xa = tile.averaged()
    x8 = jnp.asarray(np.stack([xa.reshape(-1, 4).real,
                               xa.reshape(-1, 4).imag], -1).reshape(-1, 8))
    coh = rp.coherencies(dsky, jnp.asarray(tile.u), jnp.asarray(tile.v),
                         jnp.asarray(tile.w), jnp.asarray([tile.freq0]),
                         tile.fdelta)[:, :, 0]
    assert coh.shape == (32, tile.nrows, 2, 2)
    wt = lm_mod.make_weights(jnp.asarray(tile.flags, jnp.int32), x8.dtype)
    J0 = jnp.asarray(np.tile(np.eye(2, dtype=complex),
                             (32, kmax, n_stations, 1, 1)))
    os_info = lm_mod.os_subset_ids(tilesz, tile.nbase)
    cfg = sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=2,
                          solver_mode=int(SolverMode.OSLM_OSRLM_RLBFGS))
    J, info = sage.sagefit_host(
        x8, coh, jnp.asarray(tile.sta1), jnp.asarray(tile.sta2), cidx,
        cmask, J0, n_stations, wt, config=cfg, os_id=os_info,
        key=jax.random.PRNGKey(5))
    assert np.all(np.isfinite(np.asarray(J)))
    r0, r1 = float(info["res_0"]), float(info["res_1"])
    assert r1 < r0, (r0, r1)
    # padded chunk slots (cmask False) must remain the identity warm start
    Jnp = np.asarray(J)
    for m in range(32):
        for k in range(int(sky.nchunk[m]), kmax):
            np.testing.assert_array_equal(Jnp[m, k],
                                          np.asarray(J0)[m, k])


@pytest.mark.slow
def test_mesh_admm_subband_folding():
    """F = 2 x n_devices subbands folded onto the mesh (admm.py local
    leading axis): the consensus Z-update must see ALL F subbands, and
    per-subband outputs must be finite and ordered."""
    from sagecal_tpu import utils
    from sagecal_tpu.consensus import admm as cadmm
    from sagecal_tpu.consensus import poly as cpoly
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ndev = len(jax.devices())
    assert ndev == 8
    F = 2 * ndev
    n_stations, tilesz = 6, 2
    rng = np.random.default_rng(31)
    srcs, clusters = {}, []
    for m in range(2):
        names = []
        for s in range(2):
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.02, 2)
            nn = np.sqrt(1 - ll * ll - mm * mm)
            srcs[nm] = skymodel.Source(
                name=nm, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1, sI=2.0,
                sQ=0.0, sU=0.0, sV=0.0, sI0=2.0, sQ0=0, sU0=0, sV0=0,
                spec_idx=0, spec_idx1=0, spec_idx2=0, f0=150e6)
            names.append(nm)
        clusters.append((m, 1, names))
    sky = skymodel.build_cluster_sky(srcs, clusters)
    dsky = rp.sky_to_device(sky, jnp.float64)
    tile = ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                               freqs=[150e6], ra0=0.1, dec0=0.9,
                               noise_sigma=0.01, seed=32)
    kmax = int(sky.nchunk.max())
    cidx = rp.chunk_indices(tilesz, tile.nbase, sky.nchunk)
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    freqs = 150e6 * (1.0 + 0.01 * np.arange(F))
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    mesh = Mesh(np.array(jax.devices()), axis_names=("freq",))

    cfg = cadmm.ADMMConfig(
        n_admm=2, npoly=2, rho=2.0, manifold_iters=3,
        sage=sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=2,
                             solver_mode=int(SolverMode.LM_LBFGS)))
    runner = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n_stations, tile.fdelta,
        Bpoly, cfg, mesh, F)

    B = tile.nrows
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    x8F = np.broadcast_to(x8, (F, B, 8)).copy()
    wt = np.asarray(lm_mod.make_weights(
        jnp.asarray(tile.flags, jnp.int32), jnp.float64))
    J0 = np.tile(np.eye(2, dtype=complex),
                 (F, sky.n_clusters, kmax, n_stations, 1, 1))
    sh = NamedSharding(mesh, P("freq"))
    args = [jax.device_put(jnp.asarray(a, jnp.float64), sh) for a in
            (x8F,
             np.broadcast_to(tile.u, (F, B)).copy(),
             np.broadcast_to(tile.v, (F, B)).copy(),
             np.broadcast_to(tile.w, (F, B)).copy(),
             freqs,
             np.broadcast_to(wt, (F,) + wt.shape).copy(),
             np.ones(F),
             utils.jones_c2r_np(J0))]
    JF, Z, rhoF, res0, res1, r1s, duals, Y0F, _ = runner(*args)
    jax.block_until_ready(JF)
    assert JF.shape[0] == F          # every folded subband produced output
    assert np.all(np.isfinite(np.asarray(res1)))
    assert np.all(np.isfinite(np.asarray(Z)))

    # the sharding must not change the answer: the same problem folded
    # onto ONE device (F subbands on one shard) must agree with the
    # 8-device run where each shard holds F/ndev subbands
    mesh1 = Mesh(np.array(jax.devices()[:1]), axis_names=("freq",))
    runner1 = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n_stations, tile.fdelta,
        Bpoly, cfg, mesh1, F)
    sh1 = NamedSharding(mesh1, P("freq"))
    args1 = [jax.device_put(a, sh1) for a in args]
    JF1, Z1, *_ = runner1(*args1)
    np.testing.assert_allclose(np.asarray(Z), np.asarray(Z1),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(JF), np.asarray(JF1),
                               rtol=1e-8, atol=1e-10)


def test_baseline_axis_sharding_matches_single_device():
    """P1 intra-subband row sharding (SURVEY long-context item): the
    full predict+SAGE solve jitted with its [B]-indexed inputs sharded
    over an 8-way "base" mesh axis must equal the single-device solve —
    GSPMD inserts the all-reduces where the math contracts over rows
    (normal equations, residual norms, robust statistics). Rows are
    padded to the mesh with zero weight."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sagecal_tpu import parallel, utils

    n_stations, tilesz = 10, 3
    sky = _big_sky(n_clusters=4)
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jtrue = ds.random_jones(sky.n_clusters, sky.nchunk, n_stations,
                            seed=51, scale=0.15)
    tile = ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                               freqs=[150e6], ra0=0.1, dec0=0.9,
                               jones=Jtrue, nchunk=sky.nchunk,
                               noise_sigma=0.01, seed=52,
                               flag_fraction=0.05)
    kmax = int(sky.nchunk.max())
    cidx = np.asarray(rp.chunk_indices(tilesz, tile.nbase, sky.nchunk))
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    wt = np.asarray(lm_mod.make_weights(
        jnp.asarray(tile.flags, jnp.int32), jnp.float64))
    J0 = utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex), (sky.n_clusters, kmax, n_stations, 1, 1)))
    cfg = sage.SageConfig(max_emiter=2, max_iter=5, max_lbfgs=3,
                          solver_mode=int(SolverMode.LM_LBFGS))

    mesh8 = parallel.base_mesh(8)
    mesh1 = parallel.base_mesh(1)
    B = tile.nrows
    (x8p, up, vp, wp, s1p, s2p), wtp, bpad = parallel.pad_rows(
        (x8, tile.u, tile.v, tile.w, tile.sta1, tile.sta2), wt, B, 8)
    cidxp = np.concatenate(
        [cidx, np.zeros((sky.n_clusters, bpad - B), cidx.dtype)], axis=1)
    freq = np.array([tile.freq0])

    outs = {}
    os_ids, os_nsub = lm_mod.os_subset_ids(tilesz, tile.nbase)
    os_p = np.concatenate([np.asarray(os_ids),
                           np.zeros(bpad - B, np.asarray(os_ids).dtype)])
    ts = np.asarray(ds.row_tslot(B, tile.nbase))
    ts_p = np.concatenate([ts, np.zeros(bpad - B, ts.dtype)])
    for name, mesh in (("sharded", mesh8), ("single", mesh1)):
        solve = parallel.sharded_sagefit(mesh, dsky, tile.fdelta, cmask,
                                         n_stations, config=cfg,
                                         os_nsub=os_nsub)
        args = parallel.shard_rows(mesh, x8p, up, vp, wp, s1p, s2p)
        (cidx_d,) = parallel.shard_rows(mesh, cidxp, row_axis=1)
        (wt_d,) = parallel.shard_rows(mesh, wtp)
        (os_d,) = parallel.shard_rows(mesh, os_p)
        (ts_d,) = parallel.shard_rows(mesh, ts_p)
        repl = NamedSharding(mesh, P())
        J, r0, r1, mnu = solve(
            *args, cidx_d, wt_d,
            jax.device_put(jnp.asarray(J0), repl),
            jax.device_put(jnp.asarray(freq), repl),
            os_d, jax.device_put(jax.random.PRNGKey(7), repl),
            ts_d, None)
        assert np.isfinite(float(mnu))
        outs[name] = (np.asarray(J), float(r0), float(r1))
        # the sharded run must actually shard: every [B]-input lives
        # across all 8 devices
        if name == "sharded":
            assert len(args[0].sharding.device_set) == 8

    Js, r0s, r1s = outs["sharded"]
    J1, r01, r11 = outs["single"]
    np.testing.assert_allclose(r0s, r01, rtol=1e-9)
    np.testing.assert_allclose(r1s, r11, rtol=1e-6)
    np.testing.assert_allclose(Js, J1, rtol=1e-6, atol=1e-9)
    assert r1s < r0s
