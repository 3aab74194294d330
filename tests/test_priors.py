"""Warm-start solution prior cache gates (serve/priors.py, ISSUE 18).

The contracts under test (MIGRATION.md "Solution prior cache"):

- store/key/interpolation units: content-keyed tokens, bit-exact
  reuse on matching interval times, linear blending between stored
  intervals, per-band spectral nearest-match, and the REFUSAL rule —
  a mismatched station set or cluster count never partially seeds;
- warm-vs-cold convergence envelopes: a prior-seeded run (LM and RTR
  families through the pipeline, the ADMM family through cli_mpi)
  must converge within a small residual envelope of the cold control
  — tolerance-work, never bit-work;
- ``prior_cache="off"`` (the default) is bit-identical AND
  zero-compile-identical to the pre-prior world, even with a banked
  prior sitting in the store;
- serve end-to-end: a second repeat-field job through the live
  daemon hits the prior store and spends fewer solver sweeps than
  the cold first job (the skipped first-tile EM boost).
"""

import math
import os
import shutil
import sys

import numpy as np
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu import cli_mpi, pipeline, skymodel  # noqa: E402
from sagecal_tpu.diag import guard  # noqa: E402
from sagecal_tpu.io import dataset as ds  # noqa: E402
from sagecal_tpu.rime import predict as rp  # noqa: E402
from sagecal_tpu.serve import priors  # noqa: E402
from sagecal_tpu.serve import queue as jq  # noqa: E402
from sagecal_tpu.serve.api import Client, Server, config_from_dict  # noqa: E402

SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""
CLUSTER = """\
0 1 P0A
1 2 P1A
"""

#: warm must CONVERGE as well as cold, just in fewer sweeps — the
#: final-residual ratio envelope
RES_ENVELOPE = 0.05


@pytest.fixture(autouse=True)
def _fresh_prior_store():
    """Every test starts and ends with an empty process singleton —
    a banked prior must never leak across tests (or into other test
    modules' zero-compile / bit-identity gates)."""
    priors.PRIORS.clear()
    yield
    priors.PRIORS.clear()


def _make_dataset(tmp_path, name, n_tiles=3, n_stations=8, tilesz=4,
                  nchan=2, seed=11):
    sky_path = tmp_path / "sky.txt"
    if not sky_path.exists():
        sky_path.write_text(SKY)
        (tmp_path / "sky.txt.cluster").write_text(CLUSTER)
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(sky_path), ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(sky.n_clusters, sky.nchunk, n_stations, seed=5,
                         scale=0.15)
    freqs = np.linspace(149e6, 151e6, nchan)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations,
                                 tilesz=tilesz, freqs=freqs, ra0=ra0,
                                 dec0=dec0, jones=Jt, nchunk=sky.nchunk,
                                 noise_sigma=0.02, seed=seed + t)
             for t in range(n_tiles)]
    msdir = tmp_path / name
    ds.SimMS.create(str(msdir), tiles)
    return str(msdir), str(sky_path), str(tmp_path / "sky.txt.cluster")


def _base_config(skyf, clusf, **kw):
    cfg = dict(sky_model=skyf, cluster_file=clusf, solver_mode=0,
               max_em_iter=1, max_iter=4, max_lbfgs=2, tile_size=4,
               solve_fuse="on", solve_promote="off")
    cfg.update(kw)
    return cfg


def _run(cfg_dict, msdir, sol):
    cfg = config_from_dict(dict(cfg_dict, ms=msdir, solutions_file=sol))
    pipeline.run(cfg, log=lambda *a: None)


def _corrected(msdir):
    out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
    return [out.read_tile(i).x.copy() for i in range(out.n_tiles)]


def _res_norm(msdir):
    return float(np.sqrt(sum(np.sum(np.abs(t) ** 2)
                             for t in _corrected(msdir))))


# ---------------------------------------------------------------------------
# units: modes, key, entry validation
# ---------------------------------------------------------------------------

def test_modes_and_solver_family():
    assert priors.MODES == ("off", "read", "readwrite")
    assert not priors.reads("off") and not priors.writes("off")
    assert priors.reads("read") and not priors.writes("read")
    assert priors.reads("readwrite") and priors.writes("readwrite")
    assert priors.solver_family(0) == "lm"
    assert priors.solver_family(3) == "lm"
    assert priors.solver_family(4) == "rtr"
    assert priors.solver_family(5) == "rtr"
    assert priors.solver_family(6) == "nsd"
    # constrained-Jones parameterizations are their OWN families: a
    # full-Jones prior must never content-key onto a diag/phase job
    assert priors.solver_family(0, "full") == "lm"
    assert priors.solver_family(0, "diag") == "lm+diag"
    assert priors.solver_family(4, "phase") == "rtr+phase"


def test_prior_key_is_content_keyed(tmp_path):
    sky = tmp_path / "s.txt"
    clus = tmp_path / "c.txt"
    sky.write_text(SKY)
    clus.write_text(CLUSTER)
    k1 = priors.prior_key(str(sky), str(clus), 8, 150e6, "lm")
    assert isinstance(k1, str) and k1
    # same content under ANOTHER path: same key (content, not path)
    sky2 = tmp_path / "s_copy.txt"
    sky2.write_text(SKY)
    assert priors.prior_key(str(sky2), str(clus), 8, 150e6, "lm") == k1
    # edited content, different stations/band/family: different keys
    sky.write_text(SKY + "# edited\n")
    assert priors.prior_key(str(sky), str(clus), 8, 150e6, "lm") != k1
    assert priors.prior_key(str(sky2), str(clus), 9, 150e6, "lm") != k1
    assert priors.prior_key(str(sky2), str(clus), 8, 151e6, "lm") != k1
    assert priors.prior_key(str(sky2), str(clus), 8, 150e6, "rtr") != k1
    # missing input: None, never an exception (cold start downstream)
    assert priors.prior_key(str(tmp_path / "nope"), str(clus), 8,
                            150e6, "lm") is None
    assert priors.prior_key(None, str(clus), 8, 150e6, "lm") is None


def test_make_prior_validates():
    J = np.tile(np.eye(2, dtype=complex), (1, 3, 2, 4, 1, 1))
    e = priors.make_prior(J, [0., 1., 2.], [1.5e8], rho=[5., 6.])
    assert e["n_stations"] == 4 and e["n_clusters"] == 2
    with pytest.raises(ValueError):                 # not complex
        priors.make_prior(J.real, [0., 1., 2.], [1.5e8])
    with pytest.raises(ValueError):                 # T mismatch
        priors.make_prior(J, [0., 1.], [1.5e8])
    with pytest.raises(ValueError):                 # descending times
        priors.make_prior(J, [2., 1., 0.], [1.5e8])
    with pytest.raises(ValueError):                 # F mismatch
        priors.make_prior(J, [0., 1., 2.], [1.5e8, 1.6e8])
    with pytest.raises(ValueError):                 # rho M mismatch
        priors.make_prior(J, [0., 1., 2.], [1.5e8], rho=[5.])


# ---------------------------------------------------------------------------
# units: interpolation + refusal
# ---------------------------------------------------------------------------

def _entry(times=(10., 20., 30.), freqs=(1.4e8, 1.6e8), M=2, N=4,
           seed=3):
    rng = np.random.default_rng(seed)
    F, T = len(freqs), len(times)
    J = (rng.normal(size=(F, T, M, N, 2, 2))
         + 1j * rng.normal(size=(F, T, M, N, 2, 2)))
    return priors.make_prior(J, list(times), list(freqs))


def test_interpolate_exact_times_are_bit_exact():
    e = _entry()
    got = priors.interpolate(e, [10., 30.], 1.4e8, 4, 2)
    assert got.shape == (2, 2, 4, 2, 2)
    want = np.stack([e["J"][0][0], e["J"][0][2]])     # [K, M, N, 2, 2]
    assert np.array_equal(got, np.swapaxes(want, 0, 1))


def test_interpolate_linear_blend_and_clamp():
    e = _entry()
    got = priors.interpolate(e, [15.], 1.4e8, 4, 2)[:, 0]
    assert np.allclose(got, 0.5 * (e["J"][0, 0] + e["J"][0, 1]))
    # outside the stored range: clamped to the nearest end, bit-exact
    lo = priors.interpolate(e, [1.], 1.4e8, 4, 2)[:, 0]
    hi = priors.interpolate(e, [99.], 1.4e8, 4, 2)[:, 0]
    assert np.array_equal(lo, e["J"][0, 0])
    assert np.array_equal(hi, e["J"][0, -1])


def test_interpolate_spectral_nearest_match():
    e = _entry(freqs=(1.4e8, 1.6e8))
    near_lo = priors.interpolate(e, [10.], 1.45e8, 4, 2)[:, 0]
    near_hi = priors.interpolate(e, [10.], 1.58e8, 4, 2)[:, 0]
    assert np.array_equal(near_lo, e["J"][0, 0])
    assert np.array_equal(near_hi, e["J"][1, 0])


def test_interpolate_refuses_mismatch():
    e = _entry(M=2, N=4)
    with pytest.raises(ValueError, match="refusing to seed"):
        priors.interpolate(e, [10.], 1.4e8, 5, 2)     # station set
    with pytest.raises(ValueError, match="refusing to seed"):
        priors.interpolate(e, [10.], 1.4e8, 4, 3)     # cluster count


def test_interpolate_refuses_jones_mode_mismatch():
    """ISSUE 20 satellite: a full-Jones prior must never seed a
    phase-only job (the stored solution lives in a different
    parameterization — amplitude/off-diagonal structure a phase
    retraction can neither represent nor correct), and vice versa.
    Refusal, never a partial seed — same contract as the
    station-mismatch refusal above."""
    e = _entry(M=2, N=4)                 # default: jones_mode="full"
    assert e["jones_mode"] == "full"
    with pytest.raises(ValueError, match="refusing to seed"):
        priors.interpolate(e, [10.], 1.4e8, 4, 2, jones_mode="phase")
    with pytest.raises(ValueError, match="refusing to seed"):
        priors.interpolate(e, [10.], 1.4e8, 4, 2, jones_mode="diag")
    # matched mode seeds bit-exactly, constrained or not
    rng = np.random.default_rng(7)
    Jp = np.exp(1j * rng.normal(size=(1, 3, 2, 4, 1, 1))) \
        * np.eye(2, dtype=complex)
    ep = priors.make_prior(Jp, [10., 20., 30.], [1.4e8],
                           jones_mode="phase")
    got = priors.interpolate(ep, [10.], 1.4e8, 4, 2,
                             jones_mode="phase")
    assert np.array_equal(got[:, 0], ep["J"][0, 0])
    with pytest.raises(ValueError, match="refusing to seed"):
        priors.interpolate(ep, [10.], 1.4e8, 4, 2)    # phase -> full
    with pytest.raises(ValueError):                   # unknown mode
        priors.make_prior(Jp, [10., 20., 30.], [1.4e8],
                          jones_mode="scalar")


def test_store_seed_jones_refusal_is_cold_start():
    """The store-level contract: a jones-mode mismatch on a key hit
    returns (None, None) — a COUNTED cold start, indistinguishable
    downstream from a miss — exactly like the station refusal."""
    st = priors.PriorStore(maxsize=2)
    e = _entry()
    assert st.bank("k1", e["J"], e["times"], e["freqs"])   # full prior
    J0, rho = st.seed("k1", [10.], 1.4e8, 4, 2, jones_mode="phase")
    assert J0 is None and rho is None
    assert st.stats()["refused"] == 1
    # the matched-mode seed on the same key still hits (the refusal
    # itself counted a key hit too — the key matched, the seed didn't)
    J0, _ = st.seed("k1", [10.], 1.4e8, 4, 2, jones_mode="full")
    assert J0 is not None
    assert st.stats()["hits"] == 2 and st.stats()["misses"] == 0


def test_store_seed_counts_miss_hit_refusal():
    st = priors.PriorStore(maxsize=2)
    e = _entry()
    assert not st.bank(None, e["J"], e["times"], e["freqs"])
    assert st.bank("k1", e["J"], e["times"], e["freqs"], rho=[3., 4.])
    # miss
    J0, rho = st.seed("nope", [10.], 1.4e8, 4, 2)
    assert J0 is None and rho is None
    # hit (with the banked rho riding along, a defensive copy)
    J0, rho = st.seed("k1", [10.], 1.4e8, 4, 2)
    assert J0 is not None and np.array_equal(rho, [3., 4.])
    rho[0] = 99.0
    assert np.array_equal(st.seed("k1", [10.], 1.4e8, 4, 2)[1],
                          [3., 4.])
    # refusal: a hit that cannot seed returns (None, None), counted
    J0, rho = st.seed("k1", [10.], 1.4e8, 5, 2)
    assert J0 is None and rho is None
    s = st.stats()
    assert s["misses"] == 1 and s["refused"] == 1 and s["hits"] == 3
    # LRU: newest entry per key, maxsize bounds the store
    st.bank("k2", e["J"], e["times"], e["freqs"])
    st.bank("k3", e["J"], e["times"], e["freqs"])
    assert len(st.inventory()) == 2 and "k1" not in st.inventory()


def test_bank_refuses_to_degrade():
    """A worse-quality chain never supersedes a better one under the
    same key (generational drift: a warm repeat re-banking its own
    slightly-noisier chain would otherwise become the NEXT repeat's
    seed, compounding every generation). Quality-less entries always
    supersede — legacy/ADMM banks keep the newest-wins behavior."""
    st = priors.PriorStore()
    e = _entry()
    Jb = e["J"] + 1.0       # distinguishable payload
    assert st.bank("k", e["J"], e["times"], e["freqs"], quality=5.0)
    # worse quality: kept out, held entry untouched, counted
    assert not st.bank("k", Jb, e["times"], e["freqs"], quality=7.0)
    assert np.array_equal(st.lookup("k")["J"], e["J"])
    # equal quality: the held entry also wins (<=, not <)
    assert not st.bank("k", Jb, e["times"], e["freqs"], quality=5.0)
    assert st.stats()["kept"] == 2 and st.stats()["banked"] == 1
    # better quality supersedes
    assert st.bank("k", Jb, e["times"], e["freqs"], quality=4.0)
    assert np.array_equal(st.lookup("k")["J"], Jb)
    # a quality-less newcomer always supersedes
    assert st.bank("k", e["J"], e["times"], e["freqs"])
    assert st.lookup("k")["quality"] is None
    # ...and a quality-less holder is always superseded
    assert st.bank("k", Jb, e["times"], e["freqs"], quality=9.0)
    assert st.lookup("k")["quality"] == 9.0


# ---------------------------------------------------------------------------
# warm vs cold through the pipeline (LM + RTR families)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~20 s/solver family: three full pipeline runs each
@pytest.mark.parametrize("solver_mode", [0, 5])
def test_warm_vs_cold_envelope_pipeline(tmp_path, solver_mode):
    """A prior-seeded run converges within RES_ENVELOPE of the cold
    control and actually consults the store; banking happened on the
    ordered writer path of the first readwrite run."""
    msdir, skyf, clusf = _make_dataset(tmp_path, "proto.ms")
    base = _base_config(skyf, clusf, solver_mode=solver_mode)
    for name in ("cold.ms", "bankrun.ms", "warm.ms"):
        shutil.copytree(msdir, str(tmp_path / name))

    _run(base, str(tmp_path / "cold.ms"), str(tmp_path / "cold.sol"))
    cold_norm = _res_norm(str(tmp_path / "cold.ms"))

    _run(dict(base, prior_cache="readwrite"),
         str(tmp_path / "bankrun.ms"), str(tmp_path / "bank.sol"))
    st = priors.PRIORS.stats()
    assert st["banked"] == 1, st
    fam = priors.solver_family(solver_mode)
    key = priors.prior_key(skyf, clusf, 8, 150e6, fam)
    assert key in priors.PRIORS.inventory()

    _run(dict(base, prior_cache="readwrite"),
         str(tmp_path / "warm.ms"), str(tmp_path / "warm.sol"))
    st = priors.PRIORS.stats()
    # the warm run's own write-back either superseded the entry (it
    # converged at least as well) or was kept out (refuse-to-degrade)
    # — either way the bank attempt happened
    assert st["hits"] >= 1 and st["banked"] + st["kept"] == 2, st
    warm_norm = _res_norm(str(tmp_path / "warm.ms"))
    assert warm_norm <= (1.0 + RES_ENVELOPE) * cold_norm, (
        f"warm residual {warm_norm} vs cold {cold_norm}: seeding must "
        "change sweep counts, not the convergence target")


def test_off_is_bit_and_compile_identical(tmp_path):
    """prior_cache='off' (the default) with a banked prior SITTING in
    the store is byte-identical to the pre-prior world and adds zero
    compiles — the frozen-bank contract every existing banked record
    relies on."""
    msdir, skyf, clusf = _make_dataset(tmp_path, "proto.ms")
    base = _base_config(skyf, clusf)
    for name in ("a.ms", "bankrun.ms", "c.ms"):
        shutil.copytree(msdir, str(tmp_path / name))

    _run(base, str(tmp_path / "a.ms"), str(tmp_path / "a.sol"))
    res_a = _corrected(str(tmp_path / "a.ms"))
    sol_a = open(str(tmp_path / "a.sol")).read()

    # bank a prior under this exact key, then re-run with off
    _run(dict(base, prior_cache="readwrite"),
         str(tmp_path / "bankrun.ms"), str(tmp_path / "bank.sol"))
    assert priors.PRIORS.stats()["banked"] == 1
    h0 = priors.PRIORS.stats()
    with guard.CompileGuard() as g:
        _run(base, str(tmp_path / "c.ms"), str(tmp_path / "c.sol"))
    assert g.compiles == 0, (
        f"prior_cache=off added {g.compiles} compiles")
    res_c = _corrected(str(tmp_path / "c.ms"))
    for a, c in zip(res_a, res_c):
        assert np.array_equal(a, c)
    assert open(str(tmp_path / "c.sol")).read() == sol_a
    h1 = priors.PRIORS.stats()
    assert (h1["hits"], h1["misses"]) == (h0["hits"], h0["misses"]), (
        "off must never consult the store")


def test_q_init_solutions_wins_over_prior(tmp_path):
    """An explicit -q warm-start file is the operator's seed: with
    init_solutions set, prior_initial_jones never consults the
    store."""
    msdir, skyf, clusf = _make_dataset(tmp_path, "proto.ms")
    base = _base_config(skyf, clusf)
    shutil.copytree(msdir, str(tmp_path / "bankrun.ms"))
    _run(dict(base, prior_cache="readwrite"),
         str(tmp_path / "bankrun.ms"), str(tmp_path / "bank.sol"))
    h0 = priors.PRIORS.stats()
    cfg = config_from_dict(dict(
        base, ms=msdir, prior_cache="read",
        init_solutions=str(tmp_path / "bank.sol"),
        solutions_file=str(tmp_path / "q.sol")))
    ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                         data_column=cfg.input_column,
                         out_column=cfg.output_column)
    meta = ms.meta
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    meta["ra0"], meta["dec0"],
                                    meta["freq0"], cfg.format_3)
    p = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)
    assert p.prior_initial_jones() is None
    h1 = priors.PRIORS.stats()
    assert (h1["hits"], h1["misses"]) == (h0["hits"], h0["misses"])


# ---------------------------------------------------------------------------
# ADMM family through cli_mpi
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~55 s: two full 2-subband consensus runs
def test_warm_vs_cold_envelope_admm(tmp_path):
    """cli_mpi --prior-cache: the first readwrite run banks the final
    chain + per-cluster rho under the 'admm' family; a second run
    seeds from it and stays within the residual envelope."""
    from tests.test_cli_mpi import make_subbands
    sky_path, clus_path, paths, sky = make_subbands(tmp_path, nf=2)
    copies = []
    for tag in ("cold", "bank", "warm"):
        cp = []
        for p in paths:
            dst = str(tmp_path / f"{tag}_{os.path.basename(p)}")
            shutil.copytree(p, dst)
            cp.append(dst)
        lf = tmp_path / f"mslist_{tag}.txt"
        lf.write_text("\n".join(cp) + "\n")
        copies.append((str(lf), cp))
    argv = ["-s", str(sky_path), "-c", str(clus_path),
            "-A", "3", "-P", "2", "-r", "2", "-e", "1", "-g", "4",
            "-l", "2", "-j", "0", "-t", "3"]

    def norm(ms_paths):
        return float(np.sqrt(sum(
            np.sum(np.abs(ds.SimMS(p, data_column="CORRECTED_DATA")
                          .read_tile(0).x) ** 2) for p in ms_paths)))

    assert cli_mpi.main(["-f", copies[0][0],
                         "-p", str(tmp_path / "z0.txt")] + argv) == 0
    cold_norm = norm(copies[0][1])

    assert cli_mpi.main(["-f", copies[1][0],
                         "-p", str(tmp_path / "z1.txt"),
                         "--prior-cache", "readwrite"] + argv) == 0
    st = priors.PRIORS.stats()
    assert st["banked"] == 1, st
    key = priors.prior_key(str(sky_path), str(clus_path), 8,
                           float(np.mean([ds.open_part(p).meta["freq0"]
                                          for p in copies[1][1]])),
                           "admm")
    assert key in priors.PRIORS.inventory()
    ent = priors.PRIORS.lookup(key)
    assert ent["rho"] is not None and ent["rho"].shape == (2,)
    assert ent["J"].shape[0] == 2            # per-subband bands

    assert cli_mpi.main(["-f", copies[2][0],
                         "-p", str(tmp_path / "z2.txt"),
                         "--prior-cache", "readwrite"] + argv) == 0
    st = priors.PRIORS.stats()
    assert st["hits"] >= 2, st               # one seed call per subband
    warm_norm = norm(copies[2][1])
    assert warm_norm <= (1.0 + RES_ENVELOPE) * cold_norm, (
        f"ADMM warm residual {warm_norm} vs cold {cold_norm}")


# ---------------------------------------------------------------------------
# serve end-to-end: the repeat-field regime
# ---------------------------------------------------------------------------

def test_serve_repeat_job_hits_prior_store(tmp_path):
    """Two identical jobs through the live daemon with
    prior_cache=readwrite: the second seeds from the first's banked
    chain (store hit recorded, fewer solver sweeps — the skipped
    first-tile EM boost) and still finishes DONE."""
    from sagecal_tpu.obs import metrics as ometrics
    msdir, skyf, clusf = _make_dataset(tmp_path, "proto.ms")
    msA = str(tmp_path / "jobA.ms")
    msB = str(tmp_path / "jobB.ms")
    shutil.copytree(msdir, msA)
    shutil.copytree(msdir, msB)
    base = _base_config(skyf, clusf, prior_cache="readwrite")
    server = Server(port=0, max_inflight=1)
    server.start()
    try:
        with Client(port=server.port) as c:
            ja = c.submit(dict(base, ms=msA,
                               solutions_file=str(tmp_path / "a.sol")))
            snapA = c.wait(ja, timeout_s=300)
            jb = c.submit(dict(base, ms=msB,
                               solutions_file=str(tmp_path / "b.sol")))
            snapB = c.wait(jb, timeout_s=300)
            m = c.metrics_full()
    finally:
        server.stop()
        ometrics.disable()
    assert snapA["state"] == jq.DONE and snapB["state"] == jq.DONE
    st = priors.PRIORS.stats()
    assert st["banked"] + st["kept"] >= 2 and st["hits"] >= 1, st
    assert snapA["solver_iters"] > 0
    assert snapB["solver_iters"] < snapA["solver_iters"], (
        f"seeded repeat job spent {snapB['solver_iters']} sweeps vs "
        f"cold {snapA['solver_iters']} — the first-tile boost was "
        "not skipped")
    # the scheduler exports the store's counters for the fleet view
    pr = m["scheduler"].get("priors") if isinstance(
        m.get("scheduler"), dict) else None
    if pr is not None:
        assert pr["hits"] >= 1
