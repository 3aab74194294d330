"""LM / robust solver tests: Jacobian vs autodiff, Jones recovery oracle."""

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb


def _toy_problem(N=8, B_per_t=None, T=4, K=1, seed=0, noise=0.0, nu=None):
    rng = np.random.default_rng(seed)
    p, q = np.triu_indices(N, k=1)
    nbase = len(p)
    sta1 = np.tile(p, T).astype(np.int32)
    sta2 = np.tile(q, T).astype(np.int32)
    B = nbase * T
    chunk_id = ((np.arange(B) // nbase) * K // T).astype(np.int32)
    coh = (rng.normal(size=(B, 2, 2)) + 1j * rng.normal(size=(B, 2, 2)))
    Jtrue = (rng.normal(size=(K, N, 2, 2)) * 0.3
             + 1j * rng.normal(size=(K, N, 2, 2)) * 0.3 + np.eye(2))
    V = (Jtrue[chunk_id, sta1] @ coh
         @ np.conj(Jtrue[chunk_id, sta2].transpose(0, 2, 1)))
    if noise:
        if nu:  # student's t noise
            g = rng.standard_t(nu, size=V.shape) + 1j * rng.standard_t(nu, size=V.shape)
        else:
            g = rng.normal(size=V.shape) + 1j * rng.normal(size=V.shape)
        V = V + noise * g
    x8 = np.stack([V.reshape(B, 4).real, V.reshape(B, 4).imag],
                  axis=-1).reshape(B, 8)
    return (jnp.asarray(x8), jnp.asarray(coh), jnp.asarray(sta1),
            jnp.asarray(sta2), jnp.asarray(chunk_id), Jtrue)


def test_jacobian_matches_autodiff():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=4, T=2, K=2)
    K, N = 2, 4
    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))

    def res_flat(pflat):
        J = ne.jones_r2c(pflat.reshape(K, N, 8))
        return ne.residual8(x8, J, coh, sta1, sta2, chunk_id).ravel()

    Jad = jax.jacfwd(res_flat)(p.ravel())   # [B*8, K*N*8]
    # analytic: -(dV/dp); assemble from per-baseline blocks
    J = ne.jones_r2c(p)
    Gp, Gq = ne.baseline_jacobians(J, coh, sta1, sta2, chunk_id)
    B = x8.shape[0]
    Jan = np.zeros((B * 8, K * N * 8))
    for b in range(B):
        k, s1, s2 = int(chunk_id[b]), int(sta1[b]), int(sta2[b])
        Jan[b * 8:(b + 1) * 8, (k * N + s1) * 8:(k * N + s1 + 1) * 8] -= np.asarray(Gp[b])
        Jan[b * 8:(b + 1) * 8, (k * N + s2) * 8:(k * N + s2 + 1) * 8] -= np.asarray(Gq[b])
    np.testing.assert_allclose(np.asarray(Jad), Jan, atol=1e-10)


def test_lm_recovers_jones_noiseless():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=8, T=4, K=1, seed=2)
    J0 = jnp.eye(2, dtype=jnp.complex128)[None, None].repeat(1, 0).repeat(8, 1)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J, info = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                              config=lm_mod.LMConfig(itmax=50))
    # cost should collapse to ~0
    assert float(info["final_cost"][0]) < 1e-16 * float(info["init_cost"][0]) + 1e-18
    # solution matches truth up to global unitary ambiguity: compare
    # gain-invariant quantities J_p C J_q^H
    V1 = np.asarray(J[chunk_id, sta1] @ coh
                    @ np.conj(jnp.swapaxes(J[chunk_id, sta2], -1, -2)))
    V2 = np.asarray(jnp.asarray(Jtrue)[chunk_id, sta1] @ coh
                    @ np.conj(jnp.swapaxes(jnp.asarray(Jtrue)[chunk_id, sta2], -1, -2)))
    np.testing.assert_allclose(V1, V2, atol=1e-8)


def test_lm_multichunk():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=6, T=4, K=2, seed=3)
    assert set(np.asarray(chunk_id)) == {0, 1}
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (2, 6, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J, info = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 6,
                              config=lm_mod.LMConfig(itmax=60))
    assert np.all(np.asarray(info["final_cost"])
                  < 1e-12 * np.asarray(info["init_cost"]) + 1e-18)


def test_flagged_rows_do_not_bias():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=8, T=4, seed=4)
    # corrupt half the rows wildly but flag them
    B = x8.shape[0]
    flags = np.zeros(B, np.int32)
    flags[: B // 2] = 1
    x8 = x8.at[: B // 2].set(999.0)
    wt = lm_mod.make_weights(jnp.asarray(flags), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    J, info = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                              config=lm_mod.LMConfig(itmax=50))
    assert float(info["final_cost"][0]) < 1e-14


def test_os_dead_subset_no_false_convergence():
    """A fully-flagged time-tile subset yields identically-zero normal
    equations for the chunk; the carried-equation LM body must neither
    read that zero gradient as convergence nor retry the dead subset
    forever (it adopts the next subset's equations — dp is exactly 0 on
    a dead carry, so they are the old point's)."""
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=8, T=4, K=1,
                                                        seed=6)
    B = x8.shape[0]
    nbase = B // 4
    os_id, ns = lm_mod.os_subset_ids(4, nbase)   # 4 subsets, 1 slot each
    # timeslot 0 entirely flagged -> subset 0 dead; the deterministic
    # rotation starts the solve ON the dead subset (worst case)
    flags = np.zeros(B, np.int32)
    flags[os_id == 0] = 1
    wt = lm_mod.make_weights(jnp.asarray(flags), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    os_cfg = lm_mod.OSConfig(os_id=jnp.asarray(os_id), n_subsets=ns,
                             key=jax.random.PRNGKey(0), randomize=False)
    J, info = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                              config=lm_mod.LMConfig(itmax=40), os=os_cfg)
    # false convergence stops at J0 with final_cost == init_cost
    assert float(info["final_cost"][0]) \
        < 1e-10 * float(info["init_cost"][0]) + 1e-18, dict(info)


def test_robust_lm_downweights_outliers():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem(N=8, T=6, seed=5)
    B = x8.shape[0]
    rng = np.random.default_rng(6)
    # 10% gross outliers, unflagged
    out = rng.choice(B, B // 10, replace=False)
    x8 = x8.at[out].add(jnp.asarray(rng.normal(size=(len(out), 8)) * 20))
    wt = lm_mod.make_weights(jnp.zeros(B, jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))

    Jp, info_plain = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                                     config=lm_mod.LMConfig(itmax=30))
    Jr, nu, info_rb = rb.robust_lm_solve(x8, coh, sta1, sta2, chunk_id, wt,
                                         J0, 8, config=lm_mod.LMConfig(itmax=15))

    def misfit(J):
        V1 = np.asarray(J[chunk_id, sta1] @ coh
                        @ np.conj(jnp.swapaxes(J[chunk_id, sta2], -1, -2)))
        V2 = np.asarray(jnp.asarray(Jtrue)[chunk_id, sta1] @ coh
                        @ np.conj(jnp.swapaxes(jnp.asarray(Jtrue)[chunk_id, sta2],
                                               -1, -2)))
        return np.mean(np.abs(V1 - V2) ** 2)

    assert misfit(Jr) < misfit(Jp) * 0.5  # robust clearly better
    assert 2.0 <= float(nu) <= 30.0


def test_nu_updates():
    # weights from clean gaussian residuals -> nu driven high (gaussian-like)
    rng = np.random.default_rng(7)
    e = jnp.asarray(rng.normal(size=4000))
    w = rb.update_weights(e, 5.0)
    nu = rb.update_nu_ml(w, jnp.ones_like(w, bool), 5.0)
    # single EM step moves nu up toward gaussian
    assert float(nu) > 5.0
    # heavy-tailed residuals -> nu driven lower than the gaussian case
    e2 = jnp.asarray(rng.standard_t(2.5, size=4000) * 2.0)
    w2 = rb.update_weights(e2, 5.0)
    nu2 = rb.update_nu_ml(w2, jnp.ones_like(w2, bool), 5.0)
    assert float(nu2) < float(nu)


def test_fletcher_linesearch_beats_backtracking():
    """Full-batch LBFGS with the Fletcher cubic/zoom search (lbfgs.c:572
    parameters) must reach at-least-as-low cost per iteration budget as
    Armijo backtracking on a quartic valley (VERDICT item 7 criterion)."""
    import jax
    from sagecal_tpu.solvers import lbfgs as lb

    rng = np.random.default_rng(12)
    A = jnp.asarray(rng.normal(size=(30, 12)))
    b = jnp.asarray(rng.normal(size=30))

    def cost(p):
        r = A @ p - b
        return jnp.sum(r * r) + 0.1 * jnp.sum(p ** 4)

    g = jax.grad(cost)
    p0 = jnp.asarray(rng.normal(size=12))
    p_fl = lb.lbfgs_fit(cost, g, p0, itmax=12, M=7, linesearch="fletcher")
    p_bt = lb.lbfgs_fit(cost, g, p0, itmax=12, M=7, linesearch="backtrack")
    c_fl, c_bt, c_0 = float(cost(p_fl)), float(cost(p_bt)), float(cost(p0))
    assert c_fl < 0.05 * c_0, (c_fl, c_0)
    assert c_fl <= c_bt * 1.05, (c_fl, c_bt)


def test_fletcher_linesearch_on_flat_gradient():
    """Degenerate slope must not produce NaN parameters (the bad-alpha
    guard stops iteration instead)."""
    import jax
    from sagecal_tpu.solvers import lbfgs as lb

    cost = lambda p: jnp.sum(p * 0.0)    # flat: zero gradient
    g = jax.grad(cost)
    p0 = jnp.ones(4)
    p1 = lb.lbfgs_fit(cost, g, p0, itmax=3)
    assert np.all(np.isfinite(np.asarray(p1)))


def test_normal_equations_assembly_paths_agree():
    """The traffic-lean structured assembly and the baseline-major
    fast path (row_period, single-chunk clusters) must match the dense
    materialized-Jacobian reference, including per-component (robust
    IRLS-style) weights and a separate cost weight set (cost_wt)."""
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=6, T=5, K=1, seed=3)
    N, K = 6, 1
    nbase = N * (N - 1) // 2
    rng = np.random.default_rng(4)
    p = jnp.asarray(rng.normal(size=(K, N, 8)))
    J = ne.jones_r2c(p)
    wt = jnp.asarray(rng.random(x8.shape)
                     * (rng.random((x8.shape[0], 1)) > 0.1))
    cwt = jnp.asarray(rng.random(x8.shape))
    dense = ne._normal_equations_dense(x8, J, coh, sta1, sta2, chunk_id,
                                       wt, N, K)
    generic = ne.normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt,
                                  N, K)
    fast = ne.normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt,
                               N, K, row_period=nbase)
    for name, d, g, f in zip(("JTJ", "JTe", "cost"), dense, generic, fast):
        scale = np.abs(np.asarray(d)).max() + 1e-30
        np.testing.assert_allclose(np.asarray(g), np.asarray(d),
                                   atol=5e-9 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(f), np.asarray(d),
                                   atol=5e-9 * scale, err_msg=name)
    # cost_wt: JTJ/JTe keep wt, the cost output uses cwt (the OS body's
    # subset-equations + full-data-acceptance sharing)
    dref = ne._normal_equations_dense(x8, J, coh, sta1, sta2, chunk_id,
                                      cwt, N, K)[2]
    for rp_ in (0, nbase):
        JTJc, JTec, costc = ne.normal_equations(
            x8, J, coh, sta1, sta2, chunk_id, wt, N, K, cost_wt=cwt,
            row_period=rp_)
        np.testing.assert_allclose(np.asarray(costc), np.asarray(dref),
                                   atol=5e-9 * float(np.abs(dref).max()))
        np.testing.assert_allclose(np.asarray(JTJc), np.asarray(dense[0]),
                                   atol=5e-9 * float(
                                       np.abs(np.asarray(dense[0])).max()))


def test_normal_equations_generic_for_multichunk():
    """A cluster that spans several hybrid chunks (by timeslot) takes the
    planes with a row period and the generic scatter assembly without:
    the same answer, to the rounding of another summation order."""
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=5, T=4, K=2, seed=5)
    N, K = 5, 2
    nbase = N * (N - 1) // 2
    rng = np.random.default_rng(6)
    J = ne.jones_r2c(jnp.asarray(rng.normal(size=(K, N, 8))))
    wt = jnp.asarray(rng.random(x8.shape))
    a = ne.normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt, N, K)
    b = ne.normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt, N, K,
                            row_period=nbase)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=0,
                                   atol=1e-12 * np.abs(np.asarray(x)).max())


def test_lm_solve_zero_retrace(retrace_guard):
    """Tier-1 retrace gate (runtime complement of jaxlint's static
    checker): an identically shaped second LM solve must hit the trace
    cache — zero new compile requests."""
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=6, T=4, K=2, seed=5)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (2, 6, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    solve = jax.jit(lm_mod.lm_solve,
                    static_argnames=("n_stations", "config",
                                     "row_period"))

    def thunk():
        return solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 6,
                     config=lm_mod.LMConfig(itmax=6))

    retrace_guard(thunk)
