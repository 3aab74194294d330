"""Dtype-policy gates (ISSUE 6): storage/accumulate contract.

Two contract families, mirroring the PR 3 cg-vs-chol pattern
(MIGRATION.md "Dtype policy"):

- **f32 identity**: the policy plumbing must cost the default path
  nothing — ``dtype_policy="f32"`` is BIT-identical to a call without
  any policy anywhere (the helpers are literal identities);
- **trajectory tolerance**: reduced policies (bf16/f16) are gated by
  per-policy residual envelopes against the f32 chain, NOT bit parity —
  the reduced path is free to re-lay contractions (normal_eq reduced
  assembly, LU damped solve, OS subset slicing).

All tests run f32 DATA built explicitly (the suite enables x64; the
policy entry-cast covers the staging half of the contract).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb
from sagecal_tpu.solvers import rtr as rtr_mod
from sagecal_tpu.solvers import sage

# residual-drift envelopes per policy (|res/res_f32 - 1|): bf16 keeps
# 8 mantissa bits, f16 11 — sized ~4x above the measured drifts below
# so noise never flaps, while a broken solve (O(1) drift) always trips
ENVELOPE = {"bf16": 0.25, "f16": 0.10}


def _toy(N=8, T=4, K=1, seed=0, noise=0.02):
    rng = np.random.default_rng(seed)
    p, q = np.triu_indices(N, k=1)
    nbase = len(p)
    sta1 = np.tile(p, T).astype(np.int32)
    sta2 = np.tile(q, T).astype(np.int32)
    B = nbase * T
    chunk_id = ((np.arange(B) // nbase) * K // T).astype(np.int32)
    coh = rng.normal(size=(B, 2, 2)) + 1j * rng.normal(size=(B, 2, 2))
    Jtrue = (rng.normal(size=(K, N, 2, 2)) * 0.3
             + 1j * rng.normal(size=(K, N, 2, 2)) * 0.3 + np.eye(2))
    V = (Jtrue[chunk_id, sta1] @ coh
         @ np.conj(Jtrue[chunk_id, sta2].transpose(0, 2, 1)))
    V = V + noise * (rng.normal(size=V.shape) + 1j * rng.normal(size=V.shape))
    x8 = np.stack([V.reshape(B, 4).real, V.reshape(B, 4).imag],
                  axis=-1).reshape(B, 8)
    return (jnp.asarray(x8, jnp.float32),
            jnp.asarray(coh, jnp.complex64),
            jnp.asarray(sta1), jnp.asarray(sta2), jnp.asarray(chunk_id),
            nbase)


def _wt(x8):
    return jnp.ones(x8.shape, jnp.float32)


# ---------------------------------------------------------------------------
# helper identities (the f32 policy must be a literal no-op)
# ---------------------------------------------------------------------------

def test_policy_helpers_identity():
    x = jnp.ones((5, 8), jnp.float32)
    assert dtp.storage_dtype("f32", x.dtype) == x.dtype
    assert dtp.storage_dtype("f32", jnp.float64) == jnp.dtype(jnp.float64)
    assert dtp.to_storage(x, jnp.float32) is x
    assert dtp.acc(x) is x
    assert dtp.pet(jnp.float32) == {}
    assert dtp.pet(jnp.float64) == {}
    xb = x.astype(jnp.bfloat16)
    assert dtp.acc_dtype(xb.dtype) == jnp.dtype(jnp.float32)
    assert dtp.is_reduced(xb.dtype) and not dtp.is_reduced(x.dtype)
    assert "preferred_element_type" in dtp.pet(jnp.bfloat16)
    with pytest.raises(ValueError):
        dtp.validate("f8")


def test_f32_policy_bit_identical_lm():
    x8, coh, sta1, sta2, cid, nbase = _toy()
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 8, 1, 1))
    wt = _wt(x8)
    J_a, info_a = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                                  config=lm_mod.LMConfig(itmax=8),
                                  row_period=nbase)
    J_b, info_b = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                                  config=lm_mod.LMConfig(
                                      itmax=8, dtype_policy="f32"),
                                  row_period=nbase)
    assert bool(jnp.all(J_a == J_b))
    assert bool(jnp.all(info_a["final_cost"] == info_b["final_cost"]))


# ---------------------------------------------------------------------------
# reduced assembly correctness (vs the f32 reference, quantization-level)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,tol", [("bf16", 2e-2), ("f16", 4e-3)])
def test_normal_equations_reduced_close(policy, tol):
    x8, coh, sta1, sta2, cid, nbase = _toy(N=6, T=4)
    wt = _wt(x8) * 0.7
    J = jnp.asarray(np.eye(2) + 0.1 * np.random.default_rng(1).normal(
        size=(1, 6, 2, 2)), jnp.complex64)
    st = dtp.storage_dtype(policy, jnp.float32)
    ref = jax.jit(lambda: ne.normal_equations(
        x8, J, coh, sta1, sta2, cid, wt, 6, 1, row_period=nbase))()
    # baseline-major reduced path
    red = jax.jit(lambda: ne.normal_equations(
        x8.astype(st), J, coh, sta1, sta2, cid, wt.astype(st), 6, 1,
        row_period=nbase))()
    # generic reduced path (no row_period)
    red_g = jax.jit(lambda: ne.normal_equations(
        x8.astype(st), J, coh, sta1, sta2, cid, wt.astype(st), 6, 1))()
    for out in (red, red_g):
        for a, b in zip(out, ref):
            assert a.dtype == jnp.float32          # f32 accumulators
            rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            assert rel < tol, rel


def test_os_subset_equations_exact_vs_masked():
    """The reduced OS fast path (subset-sliced assembly) must equal the
    masked full-[B] pass to quantization: zero-weight rows contribute
    nothing, so slicing is exact up to summation order."""
    x8, coh, sta1, sta2, cid, nbase = _toy(N=6, T=5)
    wt = _wt(x8)
    J = jnp.asarray(np.eye(2) + 0.1 * np.random.default_rng(2).normal(
        size=(1, 6, 2, 2)), jnp.complex64)
    os_ids, ns = lm_mod.os_subset_ids(5, nbase)
    os_ids = jnp.asarray(os_ids)
    ntper = -(-5 // ns)
    st = jnp.bfloat16
    for l in (0, ns - 1):
        wmask = wt * (os_ids == l).astype(jnp.float32)[:, None]
        ref = jax.jit(lambda w: ne.normal_equations(
            x8, J, coh, sta1, sta2, cid, w, 6, 1, cost_wt=wt,
            row_period=nbase))(wmask)
        out = jax.jit(lambda li: ne.os_subset_equations(
            x8.astype(st), J, coh, sta1, sta2, wt.astype(st), os_ids,
            li, ntper, nbase, 6, wt.astype(st)))(jnp.asarray(l, jnp.int32))
        for a, b in zip(out, ref):
            rel = float(jnp.linalg.norm(a - b)
                        / jnp.maximum(jnp.linalg.norm(b), 1e-30))
            assert rel < 2e-2, (l, rel)


def test_gn_factors_matvec_reduced_close():
    x8, coh, sta1, sta2, cid, nbase = _toy(N=6, T=4)
    wt = _wt(x8)
    J = jnp.asarray(np.eye(2) + 0.1 * np.random.default_rng(3).normal(
        size=(1, 6, 2, 2)), jnp.complex64)
    fac0, jte0, c0 = jax.jit(lambda: ne.gn_factors(
        x8, J, coh, sta1, sta2, cid, wt, 6, 1, row_period=nbase))()
    facr, jter, cr = jax.jit(lambda: ne.gn_factors(
        x8.astype(jnp.bfloat16), J, coh, sta1, sta2,
        cid, wt.astype(jnp.bfloat16), 6, 1, row_period=nbase))()
    assert facr.MA.dtype == jnp.bfloat16           # storage factors
    assert facr.D.dtype == jnp.float32             # f32 accumulator
    assert float(jnp.linalg.norm(jter - jte0)
                 / jnp.linalg.norm(jte0)) < 2e-2
    v = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48)),
                    jnp.float32)
    y0 = jax.jit(lambda f, w: ne.gn_matvec(f, w, sta1, sta2, cid, 1, 6,
                                           row_period=nbase))(fac0, v)
    yr = jax.jit(lambda f, w: ne.gn_matvec(f, w, sta1, sta2, cid, 1, 6,
                                           row_period=nbase))(facr, v)
    assert yr.dtype == jnp.float32
    assert float(jnp.linalg.norm(yr - y0) / jnp.linalg.norm(y0)) < 3e-2


# ---------------------------------------------------------------------------
# per-policy trajectory-tolerance gates (LM / robust / RTR / OS-LM)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16", "f16"])
def test_lm_trajectory_envelope(policy):
    x8, coh, sta1, sta2, cid, nbase = _toy(seed=5)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 8, 1, 1))
    wt = _wt(x8)
    _, inf_f = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                               config=lm_mod.LMConfig(itmax=10),
                               row_period=nbase)
    _, inf_p = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                               config=lm_mod.LMConfig(
                                   itmax=10, dtype_policy=policy),
                               row_period=nbase)
    cf = float(inf_f["final_cost"][0])
    cp = float(inf_p["final_cost"][0])
    assert abs(cp / cf - 1.0) < ENVELOPE[policy], (cf, cp)


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16", "f16"])
def test_os_lm_trajectory_envelope(policy):
    """The subset-sliced reduced OS body tracks the f32 masked chain."""
    x8, coh, sta1, sta2, cid, nbase = _toy(N=8, T=6, seed=6)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 8, 1, 1))
    wt = _wt(x8)
    os_ids, ns = lm_mod.os_subset_ids(6, nbase)
    osc = lm_mod.OSConfig(os_id=jnp.asarray(os_ids), n_subsets=ns,
                          key=jax.random.PRNGKey(11))
    _, inf_f = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                               config=lm_mod.LMConfig(itmax=12), os=osc,
                               row_period=nbase)
    _, inf_p = lm_mod.lm_solve(x8, coh, sta1, sta2, cid, wt, J0, 8,
                               config=lm_mod.LMConfig(
                                   itmax=12, dtype_policy=policy),
                               os=osc, row_period=nbase)
    cf = float(inf_f["final_cost"][0])
    cp = float(inf_p["final_cost"][0])
    assert abs(cp / cf - 1.0) < ENVELOPE[policy], (cf, cp)


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16"])
def test_robust_lm_trajectory_envelope(policy):
    x8, coh, sta1, sta2, cid, nbase = _toy(seed=7, noise=0.05)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 8, 1, 1))
    wt = _wt(x8)
    _, nu_f, inf_f = rb.robust_lm_solve(
        x8, coh, sta1, sta2, cid, wt, J0, 8,
        config=lm_mod.LMConfig(itmax=6), row_period=nbase)
    _, nu_p, inf_p = rb.robust_lm_solve(
        x8, coh, sta1, sta2, cid, wt, J0, 8,
        config=lm_mod.LMConfig(itmax=6, dtype_policy=policy),
        row_period=nbase)
    assert nu_p.dtype == jnp.float32               # nu never quantizes
    cf = float(inf_f["final_cost"][0])
    cp = float(inf_p["final_cost"][0])
    assert abs(cp / cf - 1.0) < ENVELOPE[policy], (cf, cp)


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16", "f16"])
def test_rtr_trajectory_envelope(policy):
    # noise floor + enough TR iterations that both chains CONVERGE:
    # at tiny noise the envelope would race convergence rates, not
    # compare converged residuals (measured: itmax=6 noiseless drifts
    # 59% from unfinished descent; itmax=12 at the 0.05 floor, 0.4%)
    x8, coh, sta1, sta2, cid, nbase = _toy(seed=8, noise=0.05)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 8, 1, 1))
    wt = _wt(x8)
    _, nu_f, inf_f = rtr_mod.rtr_solve_robust(
        x8, coh, sta1, sta2, cid, wt, J0, 8,
        config=rtr_mod.RTRConfig(itmax=12), row_period=nbase)
    _, nu_p, inf_p = rtr_mod.rtr_solve_robust(
        x8, coh, sta1, sta2, cid, wt, J0, 8,
        config=rtr_mod.RTRConfig(itmax=12, dtype_policy=policy),
        row_period=nbase)
    cf = float(jnp.sum(inf_f["final_cost"]))
    cp = float(jnp.sum(inf_p["final_cost"]))
    assert abs(cp / cf - 1.0) < ENVELOPE[policy], (cf, cp)


# ---------------------------------------------------------------------------
# SAGE chain + one ADMM chain
# ---------------------------------------------------------------------------

def _sage_problem(M=3, N=8, T=4, seed=9):
    rng = np.random.default_rng(seed)
    p, q = np.triu_indices(N, k=1)
    nbase = len(p)
    sta1 = np.tile(p, T).astype(np.int32)
    sta2 = np.tile(q, T).astype(np.int32)
    B = nbase * T
    coh = rng.normal(size=(M, B, 2, 2)) + 1j * rng.normal(size=(M, B, 2, 2))
    Jtrue = (rng.normal(size=(M, 1, N, 2, 2)) * 0.2
             + 1j * rng.normal(size=(M, 1, N, 2, 2)) * 0.2 + np.eye(2))
    cidx = np.zeros((M, B), np.int32)
    V = np.zeros((B, 2, 2), complex)
    for m in range(M):
        V += (Jtrue[m, 0][sta1] @ coh[m]
              @ np.conj(Jtrue[m, 0][sta2].transpose(0, 2, 1)))
    V += 0.02 * (rng.normal(size=V.shape) + 1j * rng.normal(size=V.shape))
    x8 = np.stack([V.reshape(B, 4).real, V.reshape(B, 4).imag],
                  axis=-1).reshape(B, 8)
    cmask = np.ones((M, 1), bool)
    J0 = np.tile(np.eye(2, dtype=np.complex64), (M, 1, N, 1, 1))
    return (jnp.asarray(x8, jnp.float32), jnp.asarray(coh, jnp.complex64),
            jnp.asarray(sta1), jnp.asarray(sta2), jnp.asarray(cidx),
            jnp.asarray(cmask), jnp.asarray(J0), nbase)


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16", "f16"])
def test_sagefit_trajectory_envelope(policy):
    x8, coh, sta1, sta2, cidx, cmask, J0, nbase = _sage_problem()
    wt = jnp.ones(x8.shape, jnp.float32)
    cfg = sage.SageConfig(max_emiter=2, max_iter=6, max_lbfgs=4,
                          solver_mode=3, nbase=nbase)
    os_id = lm_mod.os_subset_ids(4, nbase)
    _, inf_f = sage.sagefit(x8, coh, sta1, sta2, cidx, cmask, J0, 8, wt,
                            config=cfg, os_id=os_id)
    _, inf_p = sage.sagefit(x8, coh, sta1, sta2, cidx, cmask, J0, 8, wt,
                            config=cfg._replace(dtype_policy=policy),
                            os_id=os_id)
    rf = float(inf_f["res_1"])
    rp = float(inf_p["res_1"])
    assert abs(rp / rf - 1.0) < ENVELOPE[policy], (rf, rp)


@pytest.mark.slow
def test_admm_chain_bf16_envelope():
    """One consensus-augmented solve chain under bf16: the Y/BZ state
    stays f32 and the augmented trajectory holds its envelope."""
    x8, coh, sta1, sta2, cidx, cmask, J0, nbase = _sage_problem(seed=12)
    wt = jnp.ones(x8.shape, jnp.float32)
    M, N = 3, 8
    Y = jnp.zeros((M, 1, N, 8), jnp.float32)
    BZ = jnp.asarray(ne.jones_c2r(J0.reshape(M, 1, N, 2, 2)), jnp.float32)
    rho = jnp.full((M,), 2.0, jnp.float32)
    cfg = sage.SageConfig(max_emiter=2, max_iter=6, max_lbfgs=0,
                          solver_mode=1, nbase=nbase)
    _, inf_f = sage.sagefit(x8, coh, sta1, sta2, cidx, cmask, J0, 8, wt,
                            config=cfg, admm=(Y, BZ, rho))
    _, inf_p = sage.sagefit(x8, coh, sta1, sta2, cidx, cmask, J0, 8, wt,
                            config=cfg._replace(dtype_policy="bf16"),
                            admm=(Y, BZ, rho))
    rf = float(inf_f["res_1"])
    rp = float(inf_p["res_1"])
    assert abs(rp / rf - 1.0) < ENVELOPE["bf16"], (rf, rp)


# ---------------------------------------------------------------------------
# staging: DonatedRing slots + prefetch bit-identity under bf16
# ---------------------------------------------------------------------------

def test_donated_ring_carries_storage_dtype():
    from sagecal_tpu import sched
    ring = sched.DonatedRing(2)
    buf = jnp.ones((16, 8), jnp.bfloat16)
    ring.stage(0, buf)
    out = ring.take(0)
    assert out.dtype == jnp.bfloat16


@pytest.mark.slow
def test_pipeline_overlap_bit_identical_bf16(tmp_path):
    """--prefetch 0 vs 2 under --dtype-policy bf16: written residuals
    and solutions stay bit-identical (only data movement overlaps; the
    storage dtype rides the ring slots and the residual readback)."""
    from tests.test_overlap import _make_dataset, _cfg, _assert_bitident
    from sagecal_tpu import pipeline, skymodel
    from sagecal_tpu.io import dataset as ds
    msdir, skyf, clusf = _make_dataset(tmp_path)
    cfg = _cfg(msdir, skyf, clusf, extra=("--dtype-policy", "bf16"))
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)
    assert pipe.sdt == jnp.dtype(jnp.bfloat16)
    assert pipe.base_cfg.dtype_policy == "bf16"

    def run(depth, sol):
        return pipe.run(solution_path=sol, prefetch=depth,
                        log=lambda *a: None)

    h = _assert_bitident(msdir, 3, tmp_path, run, tag="bf16")
    assert all(np.isfinite(x["res_1"]) for x in h)


# ---------------------------------------------------------------------------
# the sharded (GSPMD) path: the PR 6 policy-exemption is melted
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_path_applies_policy():
    """ISSUE 14: the row-sharded solve (parallel.sharded_sagefit — the
    path that fell back to f32 with a log line since PR 6) runs with
    bf16 [B]-row staging ACTIVE: the staged arrays really carry the
    storage dtype across the mesh, the solve converges, and the final
    residual sits inside the bf16 envelope of the f32 sharded chain."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sagecal_tpu import parallel, skymodel, utils
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp

    rng = np.random.default_rng(21)
    srcs, clusters = {}, []
    for m in range(2):
        nm = f"P{m}"
        ll, mm = rng.normal(0, 0.04, 2)
        srcs[nm] = skymodel.Source(
            name=nm, ra=0, dec=0, ll=ll, mm=mm,
            nn=np.sqrt(max(1 - ll * ll - mm * mm, 0.0)) - 1, sI=1.5,
            sQ=0.0, sU=0.0, sV=0.0, sI0=1.0, sQ0=0, sU0=0, sV0=0,
            spec_idx=0, spec_idx1=0, spec_idx2=0, f0=150e6)
        clusters.append((m, 1, [nm]))
    sky = skymodel.build_cluster_sky(srcs, clusters)
    dsky = rp.sky_to_device(sky, jnp.float32)
    n_sta, tilesz = 8, 3
    Jtrue = ds.random_jones(sky.n_clusters, sky.nchunk, n_sta, seed=51,
                            scale=0.15)
    tile = ds.simulate_dataset(dsky, n_stations=n_sta, tilesz=tilesz,
                               freqs=[150e6], ra0=0.1, dec0=0.9,
                               jones=Jtrue, nchunk=sky.nchunk,
                               noise_sigma=0.01, seed=52)
    kmax = int(sky.nchunk.max())
    cidx = np.asarray(rp.chunk_indices(tilesz, tile.nbase, sky.nchunk))
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    wt = np.asarray(lm_mod.make_weights(
        jnp.asarray(tile.flags, jnp.int32), jnp.float32))
    J0 = utils.jones_c2r_np(np.tile(
        np.eye(2, dtype=complex), (sky.n_clusters, kmax, n_sta, 1, 1)))
    B = tile.nrows
    (x8p, up, vp, wp, s1p, s2p), wtp, bpad = parallel.pad_rows(
        (x8, tile.u, tile.v, tile.w, tile.sta1, tile.sta2), wt, B, 4)
    cidxp = np.concatenate(
        [cidx, np.zeros((sky.n_clusters, bpad - B), cidx.dtype)],
        axis=1)
    ts = np.asarray(ds.row_tslot(B, tile.nbase))
    ts_p = np.concatenate([ts, np.zeros(bpad - B, ts.dtype)])
    freq = np.array([tile.freq0])
    mesh = parallel.base_mesh(4)
    repl = NamedSharding(mesh, P())

    res = {}
    for policy in ("f32", "bf16"):
        cfg = sage.SageConfig(max_emiter=1, max_iter=4, max_lbfgs=2,
                              solver_mode=int(SolverMode.LM_LBFGS),
                              dtype_policy=policy)
        solve = parallel.sharded_sagefit(mesh, dsky, tile.fdelta,
                                         cmask, n_sta, config=cfg)
        sd = dtp.storage_np(policy, np.float32)
        args = parallel.shard_rows(
            mesh, np.asarray(x8p, sd),
            *[np.asarray(a, np.float32) for a in (up, vp, wp)],
            s1p, s2p)
        if policy == "bf16":
            assert args[0].dtype == jnp.bfloat16     # melt ACTIVE
        (cidx_d,) = parallel.shard_rows(mesh, cidxp, row_axis=1)
        (wt_d,) = parallel.shard_rows(mesh, np.asarray(wtp, sd))
        (os_d,) = parallel.shard_rows(mesh, np.zeros(bpad, np.int32))
        (ts_d,) = parallel.shard_rows(mesh, ts_p)
        J, r0, r1, mnu = solve(
            *args, cidx_d, wt_d,
            jax.device_put(jnp.asarray(J0, jnp.float32), repl),
            jax.device_put(jnp.asarray(freq, jnp.float32), repl),
            os_d, jax.device_put(jax.random.PRNGKey(7), repl),
            ts_d, None)
        r0, r1 = float(r0), float(r1)
        assert np.isfinite(r1) and r1 < r0
        res[policy] = r1
    drift = abs(res["bf16"] - res["f32"]) / res["f32"]
    assert drift < ENVELOPE["bf16"], drift


def test_pipeline_sharded_no_f32_fallback(tmp_path):
    """FullBatchPipeline(shard_baselines=True, dtype_policy="bf16")
    keeps the policy: no "policy-exempt" fallback log line, sdt is the
    storage dtype (the acceptance criterion's "no f32-fallback log
    line")."""
    import math
    from sagecal_tpu import pipeline, skymodel
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.serve.api import config_from_dict

    sky_path = tmp_path / "sky.txt"
    sky_path.write_text(
        "P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6\n")
    (tmp_path / "sky.txt.cluster").write_text("0 1 P0A\n")
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(sky_path), ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(
            str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(1, sky.nchunk, 5, seed=5, scale=0.1)
    tiles = [ds.simulate_dataset(
        dsky, n_stations=5, tilesz=2, freqs=np.array([150e6]), ra0=ra0,
        dec0=dec0, jones=Jt, nchunk=sky.nchunk, noise_sigma=0.01,
        seed=11)]
    msdir = tmp_path / "a.ms"
    ds.SimMS.create(str(msdir), tiles)
    cfg = config_from_dict(dict(
        ms=str(msdir), sky_model=str(sky_path),
        cluster_file=str(tmp_path / "sky.txt.cluster"),
        solver_mode=0, max_em_iter=1, max_iter=2, max_lbfgs=0,
        tile_size=2, shard_baselines=True, dtype_policy="bf16"))
    logs = []
    pipe = pipeline.FullBatchPipeline(cfg, ds.SimMS(str(msdir)), sky,
                                      log=logs.append)
    assert not any("policy-exempt" in str(line) for line in logs)
    assert pipe.dtype_policy == "bf16"
    assert pipe.sdt == jnp.dtype(jnp.bfloat16)
