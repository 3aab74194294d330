"""2-D (freq x time) mesh consensus + bounded-staleness tests (ISSUE 14).

Coverage map:
- jax.shard_map over a multi-axis mesh reduces one named axis only;
- pad_time / divergence_reset padding+seam primitives;
- make_admm_runner_2d: wavefront host-loop == fully traced scan, and
  the time-shard-0 prefix reproduces the sequential warm-start chain
  (matched per-device subband width) while seam intervals land at the
  chain's COLD level — the parity contract the MESH2D bank gates;
- make_admm_runner_stale: S=0 (and any S with no fault plan) is
  BIT-identical to the synchronous blocked chain; an injected slow
  subband under S>0 skips exactly the allowed rounds, is forced when
  the bound is exhausted, and converges within a stated residual
  envelope; a fatal (dead) subband is masked out and the survivors
  keep converging;
- cli_mpi --time-shard end to end vs the sequential interval loop.

The fast subset (everything not slow-marked) joins the CI fail-fast
step: a staleness-consensus regression silently corrupts every
straggler-tolerant chain, and a 2-D spec regression breaks the pod
path at trace time.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sagecal_tpu import faults, skymodel, utils
from sagecal_tpu.config import SolverMode
from sagecal_tpu.consensus import admm as cadmm
from sagecal_tpu.consensus import poly as cpoly
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lm as lm_mod, sage


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_shard_map_multi_axis():
    """On a 2-D ('freq', 'time') mesh, psum over ONE named axis reduces
    only that axis's groups."""
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("freq", "time"))

    def f(x):
        return x + jax.lax.psum(jnp.sum(x), "freq")

    prog = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("freq", "time"),),
                             out_specs=P("freq", "time"),
                             check_vma=False))
    x = np.arange(16.0).reshape(4, 4)
    out = np.asarray(prog(jnp.asarray(x)))
    # the freq-psum reduces over the freq axis ONLY: every cell gains
    # the total of its own time-column block, never the other's
    for j in range(2):
        blk = x[:, 2 * j:2 * j + 2]
        np.testing.assert_allclose(out[:, 2 * j:2 * j + 2],
                                   blk + blk.sum(), rtol=1e-12)


def test_pad_time():
    a = np.arange(2 * 3 * 4).reshape(2, 3, 4).astype(float)
    (ap,), tpad = cadmm.pad_time([a], 3, 2)
    assert tpad == 4 and ap.shape == (2, 4, 4)
    np.testing.assert_array_equal(ap[:, 3], a[:, 2])   # last replicated
    (aq,), tq = cadmm.pad_time([a], 3, 3)
    assert tq == 3 and aq.shape == a.shape             # no-op


def test_divergence_reset():
    F = 4
    JF = np.full((F, 1, 1, 1, 8), 2.0)
    J0 = np.zeros((F, 1, 1, 1, 8))
    res0 = np.full(F, 1.0)
    res_fin = np.array([0.5, np.nan, 0.0, 6.0])
    out = np.asarray(cadmm.divergence_reset(
        jnp.asarray(JF), jnp.asarray(J0), jnp.asarray(res0),
        jnp.asarray(res_fin)))
    np.testing.assert_array_equal(out[0], JF[0])       # healthy: kept
    for f in (1, 2, 3):                                # nan/zero/blown
        np.testing.assert_array_equal(out[f], J0[f])


def test_admm_subband_slow_draw():
    """faults.draw: kind-preserving, bounded by times, at-key scoped,
    and a no-op without a plan."""
    assert faults.draw("admm_subband_slow", key=1) is None
    faults.enable([
        {"point": "admm_subband_slow", "at": [1], "times": 2},
        {"point": "admm_subband_slow", "at": [2], "times": 1,
         "kind": "fatal"}])
    try:
        assert faults.draw("admm_subband_slow", key=0) is None
        assert faults.draw("admm_subband_slow", key=1) == "transient"
        assert faults.draw("admm_subband_slow", key=1) == "transient"
        assert faults.draw("admm_subband_slow", key=1) is None  # spent
        assert faults.draw("admm_subband_slow", key=2) == "fatal"
    finally:
        faults.disable()


def test_stale_runner_contracts():
    """Config combinations the stale runner must refuse loudly."""
    dummy = dict(dsky=None, sta1=None, sta2=None, cidx=None,
                 cmask=np.ones((1, 1), bool), n_stations=2,
                 fdelta=1e6, B_poly=np.ones((2, 2)), nf_total=2)
    with pytest.raises(ValueError, match="adaptive_rho"):
        cadmm.make_admm_runner_stale(
            dummy["dsky"], dummy["sta1"], dummy["sta2"], dummy["cidx"],
            dummy["cmask"], dummy["n_stations"], dummy["fdelta"],
            dummy["B_poly"],
            cadmm.ADMMConfig(adaptive_rho=True), 2)
    with pytest.raises(ValueError, match="staleness"):
        cadmm.make_admm_runner_stale(
            dummy["dsky"], dummy["sta1"], dummy["sta2"], dummy["cidx"],
            dummy["cmask"], dummy["n_stations"], dummy["fdelta"],
            dummy["B_poly"], cadmm.ADMMConfig(), 2, staleness=-1)


def test_runner_2d_needs_freq_time_mesh():
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("freq",))
    with pytest.raises(ValueError, match="freq.*time"):
        cadmm.make_admm_runner_2d(
            None, None, None, None, np.ones((1, 1), bool), 2, 1e6,
            np.ones((2, 2)), cadmm.ADMMConfig(), mesh1, 2, 2)


# ---------------------------------------------------------------------------
# shared tiny calibration problem
# ---------------------------------------------------------------------------

def _problem(nf, nt, n_stations=6, tilesz=2, seed=0):
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
    Jbase = ds.random_jones(2, sky.nchunk, n_stations, seed=seed + 1,
                            scale=0.15)
    slope = ds.random_jones(2, sky.nchunk, n_stations, seed=seed + 2,
                            scale=0.05) - np.eye(2)
    tiles = {}
    for f, fr in enumerate(freqs):
        Jf = Jbase + slope * (fr - 150e6) / 150e6
        for t in range(nt):
            tiles[(f, t)] = ds.simulate_dataset(
                dsky, n_stations=n_stations, tilesz=tilesz, freqs=[fr],
                ra0=0.1, dec0=0.9, jones=Jf, nchunk=sky.nchunk,
                noise_sigma=0.01, seed=seed + 3 + 17 * t)
    return sky, dsky, freqs, tiles


def _x8(t):
    xa = np.asarray(t.averaged())
    return np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                    -1).reshape(-1, 8)


def _wt(t):
    return np.asarray(lm_mod.make_weights(
        jnp.asarray(t.flags, jnp.int32), jnp.float64))


def _stack_ft(tiles, nf, nt, fn):
    return np.stack([np.stack([fn(tiles[(f, t)]) for t in range(nt)])
                     for f in range(nf)])


def _common(sky, tiles, nf):
    t00 = tiles[(0, 0)]
    n = t00.n_stations
    cidx = rp.chunk_indices(t00.tilesz, t00.nbase, sky.nchunk)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    J0F = np.asarray(utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex),
        (nf, sky.n_clusters, kmax, n, 1, 1))))
    return t00, n, cidx, cmask, kmax, J0F


def _stale_cfg(t00, n_admm=3, max_iter=4, max_lbfgs=2):
    return cadmm.ADMMConfig(
        n_admm=n_admm, npoly=2, rho=2.0, manifold_iters=3,
        sage=sage.SageConfig(max_emiter=1, max_iter=max_iter,
                             max_lbfgs=max_lbfgs,
                             solver_mode=int(SolverMode.LM_LBFGS),
                             nbase=t00.nbase))


def _interval0_args(sky, tiles, nf, freqs, J0F):
    x8F = np.stack([_x8(tiles[(f, 0)]) for f in range(nf)])
    uF = np.stack([tiles[(f, 0)].u for f in range(nf)])
    vF = np.stack([tiles[(f, 0)].v for f in range(nf)])
    wF = np.stack([tiles[(f, 0)].w for f in range(nf)])
    wtF = np.stack([_wt(tiles[(f, 0)]) for f in range(nf)])
    return tuple(jnp.asarray(a) for a in
                 (x8F, uF, vF, wF, freqs, wtF, np.ones(nf), J0F))


# ---------------------------------------------------------------------------
# bounded staleness (the CI fail-fast subset's heart)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~65 s (round-17 tier-1 rebalance); still a CI
# fail-fast gate — ci.yml runs it by -k without the 'not slow' filter
def test_stale_s0_bit_identical_and_slow_envelope():
    """(a) With no fault plan the stale runner is BIT-identical to the
    synchronous blocked chain (block_f=1) — every output array, every
    round. (b) One injected slow subband under S=2 skips exactly the
    allowed rounds, is FORCED once the bound is exhausted, and the
    chain converges within the stated envelope: non-slow subbands
    within 5% of the synchronous final residuals, the slow subband
    within 4x (it ran fewer updates), everything finite and falling."""
    nf = 3
    sky, dsky, freqs, tiles = _problem(nf=nf, nt=1, n_stations=5)
    t00, n, cidx, cmask, kmax, J0F = _common(sky, tiles, nf)
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)
    cfg = _stale_cfg(t00, n_admm=4, max_iter=3, max_lbfgs=1)
    args = _interval0_args(sky, tiles, nf, freqs, J0F)
    common = (dsky, t00.sta1, t00.sta2, cidx, cmask, n, t00.fdelta, B,
              cfg, nf)

    out_sync = [np.asarray(o) for o in
                cadmm.make_admm_runner_blocked(
                    *common, block_f=1, nbase=t00.nbase)(*args)]
    out_s0 = [np.asarray(o) for o in
              cadmm.make_admm_runner_stale(
                  *common, staleness=0, nbase=t00.nbase)(*args)]
    for nm, a, b in zip(("JF", "Z", "rhoF", "res0", "res1", "r1s",
                         "duals", "Y0F"), out_sync, out_s0):
        np.testing.assert_array_equal(a, b, err_msg=nm)

    # (b) slow subband 1 for 2 rounds, S=2
    faults.enable([{"point": "admm_subband_slow", "at": [1],
                    "times": 2}])
    try:
        run = cadmm.make_admm_runner_stale(
            *common, staleness=2, nbase=t00.nbase)
        out_st = [np.asarray(o) for o in run(*args)]
    finally:
        faults.disable()
    sched = np.stack(run.schedule[0])           # [rounds, F]
    assert sched[0, 1] == 0 and sched[1, 1] == 0     # skipped
    assert sched[2, 1] == 1                          # bound forces it
    assert sched[:, 0].all() and sched[:, 2].all()   # peers never skip
    fin_sync, fin_st = out_sync[5][-1], out_st[5][-1]
    assert np.all(np.isfinite(fin_st)) and np.all(fin_st < out_st[3])
    delta = np.abs(fin_st - fin_sync) / fin_sync
    assert delta[0] < 0.05 and delta[2] < 0.05, delta
    assert delta[1] < 4.0, delta


@pytest.mark.slow
def test_stale_dead_subband_masked():
    """A kind="fatal" admm_subband_slow rule marks the subband DEAD:
    masked out of every later consensus (like a padded mesh slot),
    logged in run.dead, while the surviving subbands keep
    converging."""
    nf = 3
    sky, dsky, freqs, tiles = _problem(nf=nf, nt=1)
    t00, n, cidx, cmask, kmax, J0F = _common(sky, tiles, nf)
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)
    cfg = _stale_cfg(t00, n_admm=4)
    args = _interval0_args(sky, tiles, nf, freqs, J0F)
    faults.enable([{"point": "admm_subband_slow", "at": [1],
                    "times": 1, "kind": "fatal"}])
    try:
        run = cadmm.make_admm_runner_stale(
            dsky, t00.sta1, t00.sta2, cidx, cmask, n, t00.fdelta, B,
            cfg, nf, staleness=1, nbase=t00.nbase)
        out = [np.asarray(o) for o in run(*args)]
    finally:
        faults.disable()
    assert run.dead == [(0, 1, 1)]              # (interval, round, f)
    sched = np.stack(run.schedule[0])
    assert not sched[:, 1].any()                # never updates again
    fin, res0 = out[5][-1], out[3]
    for f in (0, 2):
        assert np.isfinite(fin[f]) and fin[f] < res0[f]


# ---------------------------------------------------------------------------
# the 2-D mesh program
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mesh2d_wavefront_scan_and_chain_parity():
    """Three contracts of make_admm_runner_2d on a 2x2 (freq x time)
    mesh over 2 subbands x 4 intervals:

    - the wavefront host loop reproduces the fully traced time scan
      (identical math, different execution granularity);
    - the time-shard-0 interval block (the seam-free prefix)
      reproduces the SEQUENTIAL warm-start chain run at matched
      per-device subband width;
    - the cold-seam intervals (first interval of time shard 1) land at
      the chain's COLD interval level — the like-for-like reference
      the MESH2D bank gates — and every residual falls."""
    nf, nt = 2, 4
    sky, dsky, freqs, tiles = _problem(nf=nf, nt=nt)
    t00, n, cidx, cmask, kmax, J0F = _common(sky, tiles, nf)
    B = cpoly.setup_polynomials(freqs, float(np.mean(freqs)), 2, 2)
    cfg = _stale_cfg(t00, n_admm=3)

    x8FT = _stack_ft(tiles, nf, nt, _x8)
    uFT = _stack_ft(tiles, nf, nt, lambda t: t.u)
    vFT = _stack_ft(tiles, nf, nt, lambda t: t.v)
    wFT = _stack_ft(tiles, nf, nt, lambda t: t.w)
    wtFT = _stack_ft(tiles, nf, nt, _wt)
    frFT = np.ones((nf, nt))

    mesh2d = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("freq", "time"))
    common = (dsky, t00.sta1, t00.sta2, cidx, cmask, n, t00.fdelta, B,
              cfg, mesh2d, nf, nt)
    out_scan = cadmm.make_admm_runner_2d(*common, nbase=t00.nbase)(
        x8FT, uFT, vFT, wFT, freqs, wtFT, frFT, J0F)
    timer = []
    out_wave = cadmm.make_admm_runner_2d(
        *common, nbase=t00.nbase, host_loop=True, timer=timer)(
        x8FT, uFT, vFT, wFT, freqs, wtFT, frFT, J0F)
    names = ("JT", "ZT", "rhoT", "res0T", "res1T", "r1sT", "dualsT",
             "Y0T")
    for nm, a, b in zip(names, out_scan, out_wave):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-9, err_msg=nm)
    assert [l for l, _ in timer] == ["wave[0]", "wave[1]"]

    # sequential chain at matched width: 2 subbands over 2 freq devices
    mesh_seq = Mesh(np.array(jax.devices()[:2]), ("freq",))
    run1 = cadmm.make_admm_runner(
        dsky, t00.sta1, t00.sta2, cidx, cmask, n, t00.fdelta, B, cfg,
        mesh_seq, nf, host_loop=True, nbase=t00.nbase)
    sh = NamedSharding(mesh_seq, P("freq"))
    Jc = J0F.copy()
    seq_fin = np.zeros((nt, nf))
    for t in range(nt):
        argsd = [jax.device_put(jnp.asarray(a), sh) for a in
                 (x8FT[:, t], uFT[:, t], vFT[:, t], wFT[:, t], freqs,
                  wtFT[:, t], frFT[:, t], Jc)]
        o = run1(*argsd)
        Jf, r0 = np.asarray(o[0]), np.asarray(o[3])
        rfin = np.asarray(o[5])[-1]
        seq_fin[t] = rfin
        bad = (~np.isfinite(rfin)) | (rfin == 0) | (rfin > 5 * r0)
        Jc = np.where(bad[:, None, None, None, None], J0F, Jf)

    r1sT = np.asarray(out_scan[5])              # [T, A-1, F]
    mesh_fin = r1sT[:, -1, :]
    res0T = np.asarray(out_scan[3])
    assert np.all(np.isfinite(mesh_fin)) and np.all(mesh_fin < res0T)
    # prefix (intervals 0-1 = time shard 0): the same warm chain
    np.testing.assert_allclose(mesh_fin[:2], seq_fin[:2], rtol=1e-5,
                               atol=1e-9)
    # seam (interval 2 = shard 1's cold start): matches the chain's
    # own cold level, not the warm one
    cold_ref = seq_fin[0].mean()
    seam_vs_cold = mesh_fin[2].mean() / cold_ref
    assert 1 / 2.5 < seam_vs_cold < 2.5, seam_vs_cold


@pytest.mark.slow
def test_cli_time_shard_matches_sequential(tmp_path):
    """cli_mpi --time-shard 2 end to end: rc 0, worker + global
    solution files written, and the written residual column matches
    the sequential interval loop bit-for-bit on the shard-0 prefix
    and to solver tolerance on the seam intervals."""
    import math
    import shutil
    from sagecal_tpu import cli_mpi

    nf, nt, n_sta, tilesz = 2, 4, 6, 2
    sky_txt = "P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6\n"
    (tmp_path / "sky.txt").write_text(sky_txt)
    (tmp_path / "sky.txt.cluster").write_text("0 1 P0A\n")
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(tmp_path / "sky.txt"), ra0,
                                    dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(
            str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(1, sky.nchunk, n_sta, seed=5, scale=0.15)
    paths = []
    for f in range(nf):
        fc = 140e6 + 10e6 * f
        fr = np.linspace(fc - 1e6, fc + 1e6, 2)
        tls = [ds.simulate_dataset(
            dsky, n_stations=n_sta, tilesz=tilesz, freqs=fr, ra0=ra0,
            dec0=dec0, jones=Jt, nchunk=sky.nchunk, noise_sigma=0.01,
            seed=7 + f + 31 * t) for t in range(nt)]
        p = tmp_path / f"band{f}.ms"
        ds.SimMS.create(str(p), tls)
        paths.append(str(p))
    seq = tmp_path / "seq"
    m2d = tmp_path / "m2d"
    for d in (seq, m2d):
        d.mkdir()
        for p in paths:
            shutil.copytree(p, str(d / p.split("/")[-1]))
    base = ["-s", str(tmp_path / "sky.txt"),
            "-c", str(tmp_path / "sky.txt.cluster"),
            "-A", "3", "-P", "2", "-r", "1.0", "-j", "1", "-e", "1",
            "-g", "4", "-l", "2"]
    assert cli_mpi.main(["-f", str(seq / "band*.ms"),
                         "-p", str(seq / "z.txt")] + base) == 0
    assert cli_mpi.main(["-f", str(m2d / "band*.ms"),
                         "-p", str(m2d / "z.txt"),
                         "--time-shard", "2"] + base) == 0
    assert (m2d / "z.txt").exists()
    assert (m2d / "band0.ms.solutions").exists()
    Tl = nt // 2
    for f in range(nf):
        a = ds.SimMS(str(seq / f"band{f}.ms"),
                     data_column="CORRECTED_DATA")
        b = ds.SimMS(str(m2d / f"band{f}.ms"),
                     data_column="CORRECTED_DATA")
        for t in range(nt):
            xa, xb = a.read_tile(t).x, b.read_tile(t).x
            rel = np.abs(xa - xb).mean() / np.abs(xa).mean()
            if t < Tl:
                assert rel == 0.0, (f, t, rel)   # prefix: same chain
            else:
                assert rel < 0.05, (f, t, rel)   # seam: converged


def test_cli_time_shard_refuses_unsupported():
    from sagecal_tpu import cli_mpi
    p = cli_mpi.build_parser()
    args = p.parse_args(["-f", "x", "-s", "s", "-c", "c",
                         "--time-shard", "2", "--block-f", "1"])
    assert args.time_shard == 2     # parser accepts; driver refuses
