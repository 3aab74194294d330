"""Matrix-free Krylov inner solver: parity gates for the PR-3 tentpole.

The ``inner="cg"`` path must never change WHAT is solved, only HOW:
- the matrix-free operator (normal_eq.gn_matvec over the Wirtinger
  factors) is bit-tested against ``JTJ @ v`` from the dense reference
  ``_normal_equations_dense`` across the generic and baseline-major
  aggregation paths, OS-style subset weights, robust IRLS-style
  per-component weights, and the ADMM rho shift;
- the station-block preconditioner's blocks are the EXACT station
  diagonal of (JTJ + shift I);
- the full PCG solve follows the Cholesky path's trajectory within the
  documented inexact-Newton tolerance (MIGRATION.md "Inner linear
  solver": same accepted trajectory class, NOT bit parity);
- the chol path's jitter retry (the reference's QR/SVD fallback
  analogue) recovers a singular system instead of silently zeroing dp.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb
from sagecal_tpu.solvers import rtr as rtr_mod


def _toy(N=8, T=4, K=1, seed=0, noise=0.0):
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
    if noise:
        V = V + noise * (rng.normal(size=V.shape)
                         + 1j * rng.normal(size=V.shape))
    x8 = np.stack([V.reshape(B, 4).real, V.reshape(B, 4).imag],
                  -1).reshape(B, 8)
    return (jnp.asarray(x8), jnp.asarray(coh), jnp.asarray(sta1),
            jnp.asarray(sta2), jnp.asarray(chunk_id), Jtrue, nbase)


def _wt_variants(B, nbase, seed):
    """(name, wt [B, 8]) weight sets covering every caller class:
    uniform row masks, OS-style contiguous-subset zeroing, and robust
    IRLS-style smooth per-component weights."""
    rng = np.random.default_rng(seed)
    ones = np.ones((B, 8))
    os_wt = ones.copy()
    os_wt[: 2 * nbase] = 0.0              # two leading time tiles masked
    irls = rng.random((B, 8)) * (rng.random((B, 1)) > 0.1)
    return [("uniform", jnp.asarray(ones)),
            ("os_subset", jnp.asarray(os_wt)),
            ("irls", jnp.asarray(irls))]


def _dense_ref(x8, coh, s1, s2, cid, wt, N, K, p):
    J = ne.jones_r2c(p)
    return J, ne._normal_equations_dense(x8, J, coh, s1, s2, cid, wt, N, K)


def test_gn_matvec_matches_dense_all_paths():
    """gn_matvec == dense JTJ @ v: generic and baseline-major
    aggregation x {uniform, OS-subset, IRLS} weights x {no shift, ADMM
    rho shift}."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=5, K=1, seed=3)
    N, K = 6, 1
    rng = np.random.default_rng(4)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    v = jnp.asarray(rng.normal(size=(K, 8 * N)))
    rho = jnp.asarray([0.7])
    for name, wt in _wt_variants(x8.shape[0], nbase, 5):
        J, (JTJ, JTe_d, cost_d) = _dense_ref(x8, coh, s1, s2, cid, wt,
                                             N, K, p)
        ref = jnp.einsum("kij,kj->ki", JTJ, v)
        ref_sh = ref + rho[:, None] * v
        for rp_ in (0, nbase):
            fac, JTe, cost = ne.gn_factors(x8, J, coh, s1, s2, cid, wt,
                                           N, K, row_period=rp_)
            scale = float(np.abs(ref).max()) + 1e-30
            mv = ne.gn_matvec(fac, v, s1, s2, cid, K, N, row_period=rp_)
            np.testing.assert_allclose(
                np.asarray(mv), np.asarray(ref), atol=5e-9 * scale,
                err_msg=f"{name} rp={rp_}")
            mv_sh = ne.gn_matvec(fac, v, s1, s2, cid, K, N, shift=rho,
                                 row_period=rp_)
            np.testing.assert_allclose(
                np.asarray(mv_sh), np.asarray(ref_sh), atol=5e-9 * scale,
                err_msg=f"{name} rp={rp_} shifted")
            # the factor pass must reproduce the dense gradient/cost too
            np.testing.assert_allclose(np.asarray(JTe),
                                       np.asarray(JTe_d),
                                       atol=5e-9 * scale, err_msg=name)
            np.testing.assert_allclose(np.asarray(cost),
                                       np.asarray(cost_d),
                                       rtol=1e-9, err_msg=name)


def test_gn_matvec_multichunk_generic():
    """Multi-chunk clusters take the generic scatter path; row_period
    must be ignored there (same invariant as normal_equations)."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=5, T=4, K=2, seed=7)
    N, K = 5, 2
    rng = np.random.default_rng(8)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    wt = jnp.asarray(rng.random((x8.shape[0], 8)))
    v = jnp.asarray(rng.normal(size=(K, 8 * N)))
    J, (JTJ, _, _) = _dense_ref(x8, coh, s1, s2, cid, wt, N, K, p)
    ref = jnp.einsum("kij,kj->ki", JTJ, v)
    fac, _, _ = ne.gn_factors(x8, J, coh, s1, s2, cid, wt, N, K)
    mv0 = ne.gn_matvec(fac, v, s1, s2, cid, K, N)
    mv1 = ne.gn_matvec(fac, v, s1, s2, cid, K, N, row_period=nbase)
    scale = float(np.abs(ref).max()) + 1e-30
    np.testing.assert_allclose(np.asarray(mv0), np.asarray(ref),
                               atol=5e-9 * scale)
    np.testing.assert_array_equal(np.asarray(mv0), np.asarray(mv1))


def test_precond_blocks_match_dense_diagonal():
    """The station-block preconditioner must be the EXACT station
    diagonal of (JTJ + shift I): applying it equals block-solving the
    extracted dense diagonal blocks."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=3, K=2, seed=9)
    N, K = 6, 2
    rng = np.random.default_rng(10)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    wt = jnp.asarray(rng.random((x8.shape[0], 8)))
    shift = jnp.asarray([0.3, 1.1])
    J, (JTJ, _, _) = _dense_ref(x8, coh, s1, s2, cid, wt, N, K, p)
    A = np.asarray(JTJ) + np.asarray(shift)[:, None, None] * np.eye(8 * N)
    r = rng.normal(size=(K, 8 * N))
    z_ref = np.zeros_like(r)
    for k in range(K):
        for n in range(N):
            blk = A[k, 8 * n:8 * (n + 1), 8 * n:8 * (n + 1)]
            z_ref[k, 8 * n:8 * (n + 1)] = np.linalg.solve(
                blk, r[k, 8 * n:8 * (n + 1)])
    fac, _, _ = ne.gn_factors(x8, J, coh, s1, s2, cid, wt, N, K)
    Lfac = ne.gn_precond_factor(fac.D, shift)
    z = ne.gn_precond_apply(Lfac, jnp.asarray(r), K, N)
    np.testing.assert_allclose(np.asarray(z), z_ref,
                               atol=1e-9 * float(np.abs(z_ref).max()))


def test_cg_solve_trajectory_matches_chol():
    """Full-solve parity gate: on the clean recovery problem both inner
    solvers must collapse the cost (the inexact-Newton path may take a
    few more damping trips); on a noisy problem the converged costs
    must agree within the documented trajectory tolerance (0.1%,
    MIGRATION.md 'Inner linear solver')."""
    # noiseless: both reach (near) zero
    x8, coh, s1, s2, cid, _, nbase = _toy(N=8, T=4, K=1, seed=2)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    for rp_ in (0, nbase):
        _, info = lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 8, row_period=rp_,
            config=lm_mod.LMConfig(itmax=60, inner="cg"))
        assert float(info["final_cost"][0]) \
            < 1e-15 * float(info["init_cost"][0]) + 1e-18
        assert int(info["cg_iters"]) > 0
    # noisy: converged costs agree to the trajectory tolerance
    x8, coh, s1, s2, cid, _, nbase = _toy(N=8, T=4, K=1, seed=11,
                                          noise=0.05)
    fc = {}
    for inner in ("chol", "cg"):
        _, info = lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 8,
            config=lm_mod.LMConfig(itmax=60, inner=inner))
        fc[inner] = float(info["final_cost"][0])
    assert abs(fc["cg"] - fc["chol"]) <= 1e-3 * fc["chol"], fc


def test_cg_with_admm_and_os():
    """The rho-term rides the operator shift (never a dense += rho I)
    and OS subset equations drive the same PCG: both augmented paths
    must still reduce the augmented objective."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=8, T=4, K=1, seed=12,
                                          noise=0.02)
    B = x8.shape[0]
    wt = lm_mod.make_weights(jnp.zeros(B, jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    rng = np.random.default_rng(13)
    y = jnp.asarray(rng.normal(size=(1, 8, 8)) * 0.01)
    bz = jnp.asarray(ne.jones_c2r(J0).reshape(1, 8, 8))
    fc = {}
    for inner in ("chol", "cg"):
        _, info = lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 8, admm=(y, bz, 2.0),
            config=lm_mod.LMConfig(itmax=40, inner=inner))
        fc[inner] = float(info["final_cost"][0])
        assert fc[inner] < float(info["init_cost"][0])
    assert abs(fc["cg"] - fc["chol"]) <= 5e-3 * abs(fc["chol"]), fc
    # OS path
    os_id, ns = lm_mod.os_subset_ids(4, nbase)
    os_cfg = lm_mod.OSConfig(os_id=jnp.asarray(os_id), n_subsets=ns,
                             key=jax.random.PRNGKey(0), randomize=False)
    _, info = lm_mod.lm_solve(
        x8, coh, s1, s2, cid, wt, J0, 8, os=os_cfg,
        config=lm_mod.LMConfig(itmax=40, inner="cg"))
    assert float(info["final_cost"][0]) < float(info["init_cost"][0])
    assert int(info["cg_iters"]) > 0


def test_robust_cg_counts_trips():
    """The IRLS wrapper must thread the flag and sum executed PCG trips
    over its weighted inner solves."""
    x8, coh, s1, s2, cid, _, _ = _toy(N=6, T=4, K=1, seed=14, noise=0.05)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    _, nu, info = rb.robust_lm_solve(
        x8, coh, s1, s2, cid, wt, J0, 6,
        config=lm_mod.LMConfig(itmax=10, inner="cg"))
    assert int(info["cg_iters"]) > 0
    assert float(info["final_cost"][0]) < float(info["init_cost"][0])


def test_rtr_cg_hessian_matches_dense_trajectory():
    """RTR's matrix-free Hessian operator is the SAME linear map as the
    materialized [K, 8N, 8N] product (fp reordering only) — the TR
    trajectory must land at an equal cost to tight tolerance."""
    x8, coh, s1, s2, cid, _, _ = _toy(N=6, T=4, K=1, seed=15, noise=0.02)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    fc = {}
    for inner in ("chol", "cg"):
        _, info = rtr_mod.rtr_solve(
            x8, coh, s1, s2, cid, wt, J0, 6,
            config=rtr_mod.RTRConfig(itmax=8, inner=inner))
        fc[inner] = float(info["final_cost"][0])
    assert abs(fc["cg"] - fc["chol"]) <= 1e-6 * abs(fc["chol"]) + 1e-12, fc


def test_jitter_retry_recovers_singular_system():
    """Regression for the documented jitter-retry fallback: a chunk
    whose damped normal matrix fails Cholesky must get ONE retry with
    the boosted regularization floor (1e-3 * max|diag|) and recover a
    finite dp — not silently return dp = 0 (the pre-PR-3 behavior the
    lm.py docstring promised away)."""
    k8n = 8
    # chunk 0: healthy SPD; chunk 1: indefinite (tiny negative diag
    # entry) — first factorization yields non-finite dp, the boosted
    # retry (shift 1e-3 * max|diag| = 1e-3) makes it PD
    JTJ = np.zeros((2, k8n, k8n))
    JTJ[0] = np.eye(k8n)
    JTJ[1] = np.diag([1.0] * (k8n - 1) + [-1e-6])
    JTe = np.ones((2, k8n))
    mu = jnp.zeros((2,))
    dp, ok = lm_mod._solve_damped(jnp.asarray(JTJ), jnp.asarray(JTe),
                                  mu, 0.0)
    assert bool(ok[0]) and bool(ok[1]), np.asarray(ok)
    assert np.all(np.isfinite(np.asarray(dp)))
    # the recovered chunk solves the RETRIED system
    A1 = JTJ[1] + 1e-3 * np.eye(k8n)
    np.testing.assert_allclose(A1 @ np.asarray(dp[1]), JTe[1], atol=1e-8)
    # a system the boost cannot save still returns dp = 0, ok = False
    JTJ[1] = np.diag([1.0] * (k8n - 1) + [-1.0])
    dp2, ok2 = lm_mod._solve_damped(jnp.asarray(JTJ), jnp.asarray(JTe),
                                    mu, 0.0)
    assert bool(ok2[0]) and not bool(ok2[1])
    assert np.all(np.asarray(dp2[1]) == 0.0)


def test_sage_threads_inner_flag():
    """SageConfig.inner reaches the per-cluster solves and the executed
    PCG trips surface in info["cg_iters"] (the tile record's
    trip count)."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage
    x8, coh, s1, s2, cid, _, nbase = _toy(N=5, T=2, K=1, seed=16,
                                          noise=0.02)
    M = 2
    cohM = jnp.stack([coh, 0.5 * coh])
    cidxM = jnp.stack([cid, cid])
    cmask = jnp.ones((M, 1), bool)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (M, 1, 5, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    cfg = sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=0,
                          solver_mode=int(SolverMode.LM_LBFGS),
                          nbase=nbase, inner="cg")
    J, info = sage.sagefit(x8, cohM, s1, s2, cidxM, cmask, J0, 5, wt,
                           config=cfg)
    assert int(info["cg_iters"]) > 0
    assert int(info["solver_iters"]) > 0
    cfg_c = cfg._replace(inner="chol")
    _, info_c = sage.sagefit(x8, cohM, s1, s2, cidxM, cmask, J0, 5, wt,
                             config=cfg_c)
    assert int(info_c["cg_iters"]) == 0


@pytest.mark.slow
def test_gn_matvec_heavy_shape():
    """LOFAR-sized equivalence (N=62, K=2): the heavy-shape gate
    at the station count the benchmark's cells run."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=62, T=2, K=2, seed=17)
    N, K = 62, 2
    rng = np.random.default_rng(18)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    wt = jnp.asarray(rng.random((x8.shape[0], 8)))
    v = jnp.asarray(rng.normal(size=(K, 8 * N)))
    J, (JTJ, _, _) = _dense_ref(x8, coh, s1, s2, cid, wt, N, K, p)
    ref = jnp.einsum("kij,kj->ki", JTJ, v)
    fac, _, _ = ne.gn_factors(x8, J, coh, s1, s2, cid, wt, N, K)
    mv = ne.gn_matvec(fac, v, s1, s2, cid, K, N)
    scale = float(np.abs(ref).max()) + 1e-30
    np.testing.assert_allclose(np.asarray(mv), np.asarray(ref),
                               atol=1e-8 * scale)


@pytest.mark.slow
def test_multichip_admm_cg_residuals_fall():
    """The multichip gate of the PR-3 acceptance: the full consensus-
    ADMM program on the (conftest-provided) virtual 8-device CPU mesh
    with the matrix-free inner solver — per-subband residuals must
    still fall, at a small shape."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from sagecal_tpu import utils
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.consensus import admm as cadmm
    from sagecal_tpu.consensus import poly as cpoly
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import sage
    import __graft_entry__ as ge

    dtype = jnp.float32
    ndev = 8
    sky, dsky, tile = ge._tiny_problem(dtype, n_stations=8, n_clusters=2)
    n = tile.n_stations
    kmax = int(sky.nchunk.max())
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    F = ndev
    freqs = 150e6 * (1.0 + 0.01 * np.arange(F))
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    mesh = Mesh(np.array(jax.devices()[:ndev]), axis_names=("freq",))
    B = tile.nrows
    xa = tile.averaged()
    x8 = np.stack([np.asarray(xa).reshape(-1, 4).real,
                   np.asarray(xa).reshape(-1, 4).imag], -1).reshape(-1, 8)
    wt = np.asarray(lm_mod.make_weights(
        jnp.asarray(tile.flags, jnp.int32), dtype))
    J0 = np.tile(np.eye(2, dtype=np.complex64),
                 (F, sky.n_clusters, kmax, n, 1, 1))
    timer = []
    cfg = cadmm.ADMMConfig(
        n_admm=2, npoly=2, rho=2.0, manifold_iters=3,
        sage=sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=0,
                             solver_mode=int(SolverMode.LM_LBFGS),
                             nbase=tile.nbase, inner="cg"))
    runner = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n, tile.fdelta,
        Bpoly, cfg, mesh, F, host_loop=True, nbase=tile.nbase,
        timer=timer)
    sh = NamedSharding(mesh, P("freq"))
    args = [jax.device_put(jnp.asarray(a, dtype), sh) for a in
            (np.broadcast_to(x8, (F, B, 8)),
             np.broadcast_to(tile.u, (F, B)),
             np.broadcast_to(tile.v, (F, B)),
             np.broadcast_to(tile.w, (F, B)), freqs,
             np.broadcast_to(wt, (F,) + wt.shape), np.ones(F),
             utils.jones_c2r_np(J0))]
    JF, Z, rhoF, res0, res1, r1s, duals, Y0F, _ = runner(*args)
    res0 = np.asarray(res0)
    res1 = np.asarray(res1)
    assert np.all(np.isfinite(res1))
    assert np.all(res1 < res0), (res0, res1)
    # the timer contract delivered one record per device execution
    assert [lbl for lbl, _ in timer] == ["iter0", "body[1]"]
    # the consensus-only program runs standalone on the mesh
    cons = runner.consensus_program
    assert cons is not None
