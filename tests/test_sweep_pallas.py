"""Fused-sweep Pallas kernel: parity gates for the ISSUE 11 tentpole.

The ``kernel="pallas"`` path must never change WHAT is solved, only HOW
the rows are streamed (ops/sweep_pallas.py). On CPU the SAME kernel
runs through the Pallas interpreter (the coh_pallas precedent), so
every gate here is an interpret-mode gate:

- the fused assembly (normal_equations_fused / gn_blocks) is tested
  against the dense reference ``_normal_equations_dense`` across the
  single- and multi-chunk shapes, {uniform, OS-subset, IRLS} weights,
  the shared-acceptance ``cost_wt`` split, and the ADMM rho shift —
  tight tolerance at f64 (summation-order freedom only, NOT bit
  parity: the kernel contracts (time, component) axes in a different
  order than the XLA einsums);
- the blocks matvec is the exact action of the dense JTJ (the
  B-independent O(nbase) trip the cg melt is built on);
- full solves (LM / OS-LM / robust / RTR / SAGE threading) land on the
  XLA path's trajectory within the documented tolerances;
- unsupported shapes (kmax > MAX_CHUNKS, no row_period) fall back to
  the XLA path BIT-identically — the ``kernel='xla'`` default stays
  bit-frozen by construction;
- reduced dtype policies (bf16/f16) hold the same per-policy envelopes
  as the XLA reduced path (tests/test_dtype_policy.py ENVELOPE);
- diag/roofline.pallas_cost prices a compiled pallas_call from its
  cost_estimate and skips interpret-mode calls.

Fast subset (everything not slow-marked) joins the CI fail-fast step.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sagecal_tpu.ops import sweep_pallas as swp
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb
from sagecal_tpu.solvers import rtr as rtr_mod


def _toy(N=6, T=4, K=1, seed=0, noise=0.0):
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
    """Weight sets covering every caller class (mirrors
    test_krylov._wt_variants): uniform masks, OS-style contiguous
    subset zeroing, robust IRLS-style smooth per-component weights."""
    rng = np.random.default_rng(seed)
    ones = np.ones((B, 8))
    os_wt = ones.copy()
    os_wt[: 2 * nbase] = 0.0
    irls = rng.random((B, 8)) * (rng.random((B, 1)) > 0.1)
    return [("uniform", jnp.asarray(ones)),
            ("os_subset", jnp.asarray(os_wt)),
            ("irls", jnp.asarray(irls))]


def _dense_ref(x8, coh, s1, s2, cid, wt, N, K, p):
    J = ne.jones_r2c(p)
    return J, ne._normal_equations_dense(x8, J, coh, s1, s2, cid, wt, N, K)


@pytest.mark.parametrize("K,T,N", [(1, 5, 6), (2, 4, 6)])
def test_fused_equations_match_dense(K, T, N):
    """normal_equations_fused == dense reference (JTJ, JTe, cost) over
    single/multi-chunk shapes x all weight classes, interpret mode."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=N, T=T, K=K, seed=3)
    rng = np.random.default_rng(4)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    for name, wt in _wt_variants(x8.shape[0], nbase, 5):
        J, (JTJ_d, JTe_d, cost_d) = _dense_ref(x8, coh, s1, s2, cid, wt,
                                               N, K, p)
        JTJ_f, JTe_f, cost_f = swp.normal_equations_fused(
            x8, J, coh, s1, s2, cid, wt, N, K, nbase, interpret=True)
        scale = float(jnp.abs(JTJ_d).max()) + 1e-30
        np.testing.assert_allclose(np.asarray(JTJ_f), np.asarray(JTJ_d),
                                   atol=5e-9 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(JTe_f), np.asarray(JTe_d),
                                   atol=5e-9 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(cost_f), np.asarray(cost_d),
                                   rtol=1e-9, err_msg=name)


def test_fused_cost_wt_split():
    """The shared-acceptance split: JTJ/JTe weighted by ``wt``, cost by
    ``cost_wt`` (the OS body's one-row-pass contract)."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=6)
    rng = np.random.default_rng(7)
    p = jnp.asarray(rng.normal(size=(1, 6, 8)))
    wt = jnp.asarray(rng.random((x8.shape[0], 8)))
    cw = jnp.asarray(rng.random((x8.shape[0], 8)))
    J = ne.jones_r2c(p)
    JTJ_r, JTe_r, cost_r = ne.normal_equations(
        x8, J, coh, s1, s2, cid, wt, 6, 1, cost_wt=cw)
    JTJ_f, JTe_f, cost_f = swp.normal_equations_fused(
        x8, J, coh, s1, s2, cid, wt, 6, 1, nbase, cost_wt=cw,
        interpret=True)
    scale = float(jnp.abs(JTJ_r).max()) + 1e-30
    np.testing.assert_allclose(np.asarray(JTJ_f), np.asarray(JTJ_r),
                               atol=5e-9 * scale)
    np.testing.assert_allclose(np.asarray(cost_f), np.asarray(cost_r),
                               rtol=1e-9)


@pytest.mark.parametrize("K,T,N", [(1, 5, 6), (2, 4, 6)])
def test_blocks_matvec_matches_dense(K, T, N):
    """gn_matvec_blocks == dense JTJ @ v (+ shift I) — the
    B-independent trip's exactness gate, and GNBlocks.D must equal the
    XLA operator's station-diagonal blocks (the shared preconditioner
    contract)."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=N, T=T, K=K, seed=9)
    rng = np.random.default_rng(10)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    v = jnp.asarray(rng.normal(size=(K, 8 * N)))
    rho = jnp.asarray(rng.random(K) + 0.1)
    for name, wt in _wt_variants(x8.shape[0], nbase, 11):
        J, (JTJ_d, JTe_d, _) = _dense_ref(x8, coh, s1, s2, cid, wt,
                                          N, K, p)
        fac, JTe_b, _ = swp.gn_blocks(x8, J, coh, s1, s2, cid, wt, N, K,
                                      nbase, interpret=True)
        ref = jnp.einsum("kij,kj->ki", JTJ_d, v)
        scale = float(jnp.abs(ref).max()) + 1e-30
        mv = swp.gn_matvec_blocks(fac, v, s1, s2, N, interpret=True)
        np.testing.assert_allclose(np.asarray(mv), np.asarray(ref),
                                   atol=5e-9 * scale, err_msg=name)
        mv_sh = swp.gn_matvec_blocks(fac, v, s1, s2, N, shift=rho,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(mv_sh), np.asarray(ref + rho[:, None] * v),
            atol=5e-9 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(JTe_b), np.asarray(JTe_d),
                                   atol=5e-9 * scale, err_msg=name)
        fx, _, _ = ne.gn_factors(x8, J, coh, s1, s2, cid, wt, N, K,
                                 row_period=nbase)
        np.testing.assert_allclose(np.asarray(fac.D), np.asarray(fx.D),
                                   atol=5e-9 * scale, err_msg=name)


def test_lm_solve_trajectory_matches_xla():
    """Full LM solves under kernel="pallas" land on the XLA chol
    trajectory within the inner-solver tolerances, for both inners,
    and the PCG path counts its executed trips. (Small fast shape —
    the CI fail-fast gate; the 4-way inner x kernel matrix at larger
    shapes runs in the slow-marked solver gates below.)"""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=11,
                                          noise=0.05)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    fc = {}
    for inner, kern in (("chol", "xla"), ("chol", "pallas"),
                        ("cg", "pallas")):
        _, info = lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 6, row_period=nbase,
            config=lm_mod.LMConfig(itmax=30, inner=inner, kernel=kern))
        fc[(inner, kern)] = float(info["final_cost"][0])
        if inner == "cg":
            assert int(info["cg_iters"]) > 0
    base = fc[("chol", "xla")]
    for k, v in fc.items():
        assert abs(v - base) <= 2e-3 * base, (k, v, base)


@pytest.mark.slow
def test_lm_admm_and_os_pallas():
    """The rho-term rides the operator shift and OS subset weights
    drive the same fused pass: both augmented paths must reduce their
    objectives under kernel="pallas" (mirror of test_krylov's gate)."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=8, T=4, K=1, seed=12,
                                          noise=0.02)
    B = x8.shape[0]
    wt = lm_mod.make_weights(jnp.zeros(B, jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    rng = np.random.default_rng(13)
    y = jnp.asarray(rng.normal(size=(1, 8, 8)) * 0.01)
    bz = jnp.asarray(ne.jones_c2r(J0).reshape(1, 8, 8))
    fc = {}
    for kern in ("xla", "pallas"):
        for inner in ("chol", "cg"):
            _, info = lm_mod.lm_solve(
                x8, coh, s1, s2, cid, wt, J0, 8, admm=(y, bz, 2.0),
                row_period=nbase,
                config=lm_mod.LMConfig(itmax=40, inner=inner,
                                       kernel=kern))
            fc[(inner, kern)] = float(info["final_cost"][0])
            assert fc[(inner, kern)] < float(info["init_cost"][0])
    for inner in ("chol", "cg"):
        assert abs(fc[(inner, "pallas")] - fc[(inner, "xla")]) \
            <= 5e-3 * abs(fc[(inner, "xla")]), fc
    # OS path
    os_id, ns = lm_mod.os_subset_ids(4, nbase)
    os_cfg = lm_mod.OSConfig(os_id=jnp.asarray(os_id), n_subsets=ns,
                             key=jax.random.PRNGKey(0), randomize=False)
    for inner in ("chol", "cg"):
        _, info = lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 8, os=os_cfg, row_period=nbase,
            config=lm_mod.LMConfig(itmax=40, inner=inner,
                                   kernel="pallas"))
        assert float(info["final_cost"][0]) < float(info["init_cost"][0])


@pytest.mark.slow
def test_robust_pallas_counts_trips():
    """The IRLS wrapper threads the kernel flag (its curvature weights
    re-enter the fused pass each round) and sums executed PCG trips."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=14,
                                          noise=0.05)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    _, nu, info = rb.robust_lm_solve(
        x8, coh, s1, s2, cid, wt, J0, 6, row_period=nbase,
        config=lm_mod.LMConfig(itmax=10, inner="cg", kernel="pallas"))
    assert int(info["cg_iters"]) > 0
    assert float(info["final_cost"][0]) < float(info["init_cost"][0])


@pytest.mark.slow
def test_rtr_pallas_matches_xla_trajectory():
    """RTR's fused assembly + blocks tCG operator is the SAME linear
    map as the XLA paths (fp reordering only) — equal-cost gate for
    both inners."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=15,
                                          noise=0.02)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    fc = {}
    for inner in ("chol", "cg"):
        for kern in ("xla", "pallas"):
            _, info = rtr_mod.rtr_solve(
                x8, coh, s1, s2, cid, wt, J0, 6, row_period=nbase,
                config=rtr_mod.RTRConfig(itmax=8, inner=inner,
                                         kernel=kern))
            fc[(inner, kern)] = float(info["final_cost"][0])
    for inner in ("chol", "cg"):
        a, b = fc[(inner, "pallas")], fc[(inner, "xla")]
        assert abs(a - b) <= 1e-5 * abs(b) + 1e-12, fc


def test_unsupported_shapes_fall_back_bit_identical():
    """Gating: no row_period, or kmax > MAX_CHUNKS, must fall back to
    the XLA path with BIT-identical results — kernel="pallas" never
    changes an unsupported solve."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=16,
                                          noise=0.03)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 6, 1, 1))
    # row_period=0: generic path
    J_x, ix = lm_mod.lm_solve(x8, coh, s1, s2, cid, wt, J0, 6,
                              config=lm_mod.LMConfig(itmax=10,
                                                     kernel="xla"))
    J_p, ip = lm_mod.lm_solve(x8, coh, s1, s2, cid, wt, J0, 6,
                              config=lm_mod.LMConfig(itmax=10,
                                                     kernel="pallas"))
    np.testing.assert_array_equal(np.asarray(J_x), np.asarray(J_p))
    assert not swp.supported(swp.MAX_CHUNKS + 1, nbase, x8.shape[0])
    assert not swp.supported(1, 0, x8.shape[0])
    assert not swp.supported(1, nbase, x8.shape[0] + 1)


@pytest.mark.slow
def test_sage_threads_kernel_flag():
    """SageConfig.kernel reaches the per-cluster solves: PCG trips are
    counted under inner="cg" for both kernels and the sweep completes
    (the tile record's trip count)."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage
    x8, coh, s1, s2, cid, _, nbase = _toy(N=5, T=2, K=1, seed=17,
                                          noise=0.02)
    M = 2
    cohM = jnp.stack([coh, 0.5 * coh])
    cidxM = jnp.stack([cid, cid])
    cmask = jnp.ones((M, 1), bool)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (M, 1, 5, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    cfg = sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=0,
                          solver_mode=int(SolverMode.LM_LBFGS),
                          nbase=nbase, inner="cg", kernel="pallas")
    J, info = sage.sagefit(x8, cohM, s1, s2, cidxM, cmask, J0, 5, wt,
                           config=cfg)
    assert int(info["cg_iters"]) > 0
    assert int(info["solver_iters"]) > 0
    assert np.all(np.isfinite(np.asarray(J)))


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["bf16", "f16"])
def test_reduced_policy_envelope(policy):
    """Reduced dtype policies under kernel="pallas": storage-quantized
    operands with acc-dtype accumulators, holding the SAME per-policy
    trajectory envelopes as the XLA reduced path (the quantize-at-load
    boundary rounds the same planes the XLA path stores)."""
    from tests.test_dtype_policy import ENVELOPE
    x8, coh, s1, s2, cid, _, nbase = _toy(N=6, T=4, K=1, seed=18,
                                          noise=0.05)
    x8 = x8.astype(jnp.float32)
    coh = coh.astype(jnp.complex64)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 6, 1, 1))
    cf = float(lm_mod.lm_solve(
        x8, coh, s1, s2, cid, wt, J0, 6, row_period=nbase,
        config=lm_mod.LMConfig(itmax=15, kernel="pallas"))[1]
        ["final_cost"][0])
    for inner in ("chol", "cg"):
        cp = float(lm_mod.lm_solve(
            x8, coh, s1, s2, cid, wt, J0, 6, row_period=nbase,
            config=lm_mod.LMConfig(itmax=15, inner=inner, kernel="pallas",
                                   dtype_policy=policy))[1]
            ["final_cost"][0])
        assert abs(cp / cf - 1.0) < ENVELOPE[policy], (inner, cf, cp)


def test_roofline_pallas_cost():
    """diag/roofline.pallas_cost: a COMPILED pallas_call is priced from
    its cost_estimate via the jaxpr walk; an interpret-mode call is
    skipped (cost_analysis already prices its HLO lowering) — the
    silent-drop fix for a priced program."""
    from sagecal_tpu.diag import roofline as rl
    x8, coh, s1, s2, cid, _, nbase = _toy(N=5, T=4, K=1, seed=19)
    x8 = x8.astype(jnp.float32)
    coh = coh.astype(jnp.complex64)
    wt = jnp.ones((x8.shape[0], 8), jnp.float32)
    J = jnp.tile(jnp.eye(2, dtype=jnp.complex64), (1, 5, 1, 1))

    def compiled(x8, J, coh, s1, s2, cid, wt):
        return swp.normal_equations_fused(x8, J, coh, s1, s2, cid, wt,
                                          5, 1, nbase, interpret=False)

    def interp(x8, J, coh, s1, s2, cid, wt):
        return swp.normal_equations_fused(x8, J, coh, s1, s2, cid, wt,
                                          5, 1, nbase, interpret=True)

    args = (x8, J, coh, s1, s2, cid, wt)
    c = rl.pallas_cost(compiled, args)
    assert c["flops"] > 0 and c["bytes_accessed"] > 0
    assert rl.pallas_cost(interp, args) == rl.zero_cost()
    # and the full program_cost folds the correction in on top of the
    # (near-blind) cost-analysis figure for the compiled form
    full = rl.program_cost(jax.jit(interp), args)
    assert full["bytes_accessed"] > 0


@pytest.mark.parametrize("K,T,N", [(1, 5, 6), (2, 4, 6)])
def test_fused_chol_solve_matches_dense(K, T, N):
    """ISSUE 17 tentpole (a): solve_damped_blocks — the fused
    assemble/factor/solve stage on the per-baseline blocks — lands on
    the dense reference (_normal_equations_dense + shift*I + cho_solve)
    to machine epsilon (modulo the documented summation-order freedom
    of the sweep itself), across weight classes x cost_wt x the ADMM
    rho-shift x K in {1, 2}. The shift folds into the station
    diagonals BEFORE the 8x8 expansion — this gate pins that the fold
    is the same damped system, not an approximation of it."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=N, T=T, K=K, seed=30)
    rng = np.random.default_rng(31)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    cw = jnp.asarray(rng.random((x8.shape[0], 8)))
    mu = jnp.asarray(rng.random(K) + 0.5)
    for rho in (0.0, 2.0):
        for name, wt in _wt_variants(x8.shape[0], nbase, 32):
            J, (JTJ_d, JTe_d, _) = _dense_ref(x8, coh, s1, s2, cid,
                                              wt, N, K, p)
            shift = mu + 1e-9 + rho
            A = JTJ_d + shift[:, None, None] * jnp.eye(8 * N)
            dp_ref = jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(A), JTe_d[..., None])[..., 0]
            fac, JTe_b, _ = swp.gn_blocks(x8, J, coh, s1, s2, cid, wt,
                                          N, K, nbase, cost_wt=cw,
                                          interpret=True)
            dp, ok = swp.solve_damped_blocks(fac, JTe_b, mu, 1e-9,
                                             s1, s2, N, rho=rho)
            assert bool(jnp.all(ok)), (name, rho)
            scale = float(jnp.abs(dp_ref).max()) + 1e-30
            np.testing.assert_allclose(np.asarray(dp),
                                       np.asarray(dp_ref),
                                       atol=5e-8 * scale,
                                       err_msg=f"{name} rho={rho}")


def test_fused_chol_retry_boosts_jitter():
    """The nonfinite -> boosted-jitter retry contract: a singular
    system (zero blocks, zero shift) fails its first factorization and
    must come back finite through the 1e-3 * max|diag| boost; a
    well-damped first attempt must solve exactly (diagonal system)."""
    K, N, nb = 1, 4, 6
    p, q = np.triu_indices(N, k=1)
    s1 = jnp.asarray(p.astype(np.int32))
    s2 = jnp.asarray(q.astype(np.int32))
    z = jnp.zeros((K, nb, 2, 4, 4))
    fac = swp.GNBlocks(pp=z, qq=z, pq=jnp.zeros((K, nb, 2, 2, 4, 4)),
                       D=jnp.zeros((K, N, 2, 4, 4)))
    JTe = jnp.ones((K, 8 * N))
    # jitter > 0: A = jitter*I, dp = JTe / jitter exactly, no retry
    dp, ok = swp.solve_damped_blocks(fac, JTe, jnp.zeros(K), 0.25,
                                     s1, s2, N)
    assert bool(jnp.all(ok))
    np.testing.assert_array_equal(np.asarray(dp),
                                  np.asarray(JTe / 0.25))
    # zero shift: first attempt factors the zero matrix (non-finite),
    # the retry's boosted floor must return a finite answer
    dp0, ok0 = swp.solve_damped_blocks(fac, JTe, jnp.zeros(K), 0.0,
                                       s1, s2, N)
    assert np.all(np.isfinite(np.asarray(dp0)))


@pytest.mark.parametrize("batch_wt", [False, True])
def test_visits_batching_matches_serial(batch_wt):
    """ISSUE 17 tentpole (b): vmapping the sweep over cluster visits
    (sage's G-lane jax.vmap) routes onto ONE K-major pallas grid
    (sweep_blocks_visits) instead of V serial pallas_calls — and must
    produce what the serial per-visit sweep produces, for shared AND
    batched weight operands (the OS/IRLS lanes batch wt; the uniform
    sage group shares it)."""
    V, K, N, T = 3, 2, 6, 4
    x8, coh, s1, s2, cid, _, nbase = _toy(N=N, T=T, K=K, seed=33)
    rng = np.random.default_rng(34)
    Js = jnp.asarray(rng.normal(size=(V, K, N, 2, 2))
                     + 1j * rng.normal(size=(V, K, N, 2, 2)))
    if batch_wt:
        wt = jnp.asarray(rng.random((V, x8.shape[0], 8)))
        in_axes = (0, 0)
    else:
        wt = jnp.asarray(rng.random((x8.shape[0], 8)))
        in_axes = (0, None)

    def one(J, w):
        fac, JTe, cost = swp.gn_blocks(x8, J, coh, s1, s2, cid, w,
                                       N, K, nbase, interpret=True)
        return fac.pp, fac.qq, fac.pq, fac.D, JTe, cost

    got = jax.vmap(one, in_axes=in_axes)(Js, wt)
    for v in range(V):
        ref = one(Js[v], wt[v] if batch_wt else wt)
        for g, r, nm in zip(got, ref,
                            ("pp", "qq", "pq", "D", "JTe", "cost")):
            scale = float(jnp.abs(r).max()) + 1e-30
            np.testing.assert_allclose(np.asarray(g[v]), np.asarray(r),
                                       atol=5e-9 * scale,
                                       err_msg=f"lane {v} {nm}")


def test_visits_batched_stations_fall_back():
    """Batched sta1/sta2 operands (no solver does this, but the vmap
    rule must stay total): the dispatch falls back to the serial
    per-lane sweep and still matches it."""
    V, K, N, T = 2, 1, 5, 3
    x8, coh, s1, s2, cid, _, nbase = _toy(N=N, T=T, K=K, seed=35)
    rng = np.random.default_rng(36)
    Js = jnp.asarray(rng.normal(size=(V, K, N, 2, 2))
                     + 1j * rng.normal(size=(V, K, N, 2, 2)))
    s1v = jnp.stack([s1, s1])
    s2v = jnp.stack([s2, s2])
    wt = jnp.ones((x8.shape[0], 8))

    def one(J, a, b):
        _, JTe, cost = swp.gn_blocks(x8, J, coh, a, b, cid, wt,
                                     N, K, nbase, interpret=True)
        return JTe, cost

    got = jax.vmap(one, in_axes=(0, 0, 0))(Js, s1v, s2v)
    for v in range(V):
        ref = one(Js[v], s1, s2)
        for g, r in zip(got, ref):
            scale = float(jnp.abs(r).max()) + 1e-30
            np.testing.assert_allclose(np.asarray(g[v]), np.asarray(r),
                                       atol=5e-9 * scale)


@pytest.mark.slow
def test_fused_equations_heavy_shape():
    """LOFAR-sized equivalence (N=62, K=2): the heavy-shape gate
    at the station count the benchmark's cells run."""
    x8, coh, s1, s2, cid, _, nbase = _toy(N=62, T=2, K=2, seed=20)
    N, K = 62, 2
    rng = np.random.default_rng(21)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    wt = jnp.asarray(rng.random((x8.shape[0], 8)))
    v = jnp.asarray(rng.normal(size=(K, 8 * N)))
    J, (JTJ_d, JTe_d, cost_d) = _dense_ref(x8, coh, s1, s2, cid, wt,
                                           N, K, p)
    JTJ_f, JTe_f, cost_f = swp.normal_equations_fused(
        x8, J, coh, s1, s2, cid, wt, N, K, nbase, interpret=True)
    scale = float(jnp.abs(JTJ_d).max()) + 1e-30
    np.testing.assert_allclose(np.asarray(JTJ_f), np.asarray(JTJ_d),
                               atol=1e-8 * scale)
    fac, _, _ = swp.gn_blocks(x8, J, coh, s1, s2, cid, wt, N, K, nbase,
                              interpret=True)
    mv = swp.gn_matvec_blocks(fac, v, s1, s2, N, interpret=True)
    ref = jnp.einsum("kij,kj->ki", JTJ_d, v)
    np.testing.assert_allclose(
        np.asarray(mv), np.asarray(ref),
        atol=1e-8 * (float(jnp.abs(ref).max()) + 1e-30))


def test_kernel_pallas_refused_on_tpu_backend(monkeypatch):
    """The fused sweep's TPU compile never returns, so --kernel pallas
    is refused before any solve when the backend reports tpu; on CPU
    (the interpreter path) the same call passes."""
    swp.check_kernel("pallas")          # cpu: fine
    monkeypatch.setattr(swp.jax, "default_backend",
                        lambda: "tpu")
    swp.check_kernel("xla")
    with pytest.raises(ValueError, match="does not compile under Mosaic"):
        swp.check_kernel("pallas")
