"""RTR / NSD solver tests: manifold ops, Jones recovery, robust behavior."""

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import rtr as rtr_mod

from test_lm import _toy_problem
import pytest


def _toy_problem_scalar(N=8, T=4, K=1, seed=0, noise=0.0, nu=None):
    """Like test_lm._toy_problem but with scalar x identity coherencies —
    the unpolarized-sky case where the cost is exactly invariant under the
    J -> J U gain ambiguity that the quotient manifold divides out."""
    rng = np.random.default_rng(seed)
    p, q = np.triu_indices(N, k=1)
    nbase = len(p)
    sta1 = np.tile(p, T).astype(np.int32)
    sta2 = np.tile(q, T).astype(np.int32)
    B = nbase * T
    chunk_id = ((np.arange(B) // nbase) * K // T).astype(np.int32)
    c = rng.normal(size=B) + 1j * rng.normal(size=B)
    coh = c[:, None, None] * np.eye(2)
    Jtrue = (rng.normal(size=(K, N, 2, 2)) * 0.3
             + 1j * rng.normal(size=(K, N, 2, 2)) * 0.3 + np.eye(2))
    V = (Jtrue[chunk_id, sta1] @ coh
         @ np.conj(Jtrue[chunk_id, sta2].transpose(0, 2, 1)))
    if noise:
        if nu:
            g = (rng.standard_t(nu, size=V.shape)
                 + 1j * rng.standard_t(nu, size=V.shape))
        else:
            g = rng.normal(size=V.shape) + 1j * rng.normal(size=V.shape)
        V = V + noise * g
    x8 = np.stack([V.reshape(B, 4).real, V.reshape(B, 4).imag],
                  axis=-1).reshape(B, 8)
    return (jnp.asarray(x8), jnp.asarray(coh), jnp.asarray(sta1),
            jnp.asarray(sta2), jnp.asarray(chunk_id), Jtrue)


def _invariant_misfit(J, Jtrue, coh, sta1, sta2, chunk_id):
    """Mean |J_p C J_q^H - true|^2: gain-ambiguity-invariant error."""
    V1 = np.asarray(J[chunk_id, sta1] @ coh
                    @ np.conj(jnp.swapaxes(J[chunk_id, sta2], -1, -2)))
    Jt = jnp.asarray(Jtrue)
    V2 = np.asarray(Jt[chunk_id, sta1] @ coh
                    @ np.conj(jnp.swapaxes(Jt[chunk_id, sta2], -1, -2)))
    return float(np.mean(np.abs(V1 - V2) ** 2))


def test_projection_is_horizontal_and_idempotent():
    rng = np.random.default_rng(0)
    K, N = 3, 5
    p = jnp.asarray(rng.normal(size=(K, N * 8)))
    v = jnp.asarray(rng.normal(size=(K, N * 8)))
    h = rtr_mod.project_tangent(p, v, K, N)
    # idempotent
    h2 = rtr_mod.project_tangent(p, h, K, N)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=1e-10)
    # horizontal: X^H eta - eta^H X = 0 (vertical space is X*skew-Herm)
    X = rtr_mod._c(p, K, N)
    E = rtr_mod._c(h, K, N)
    S = (jnp.conj(jnp.swapaxes(X, -1, -2)) @ E
         - jnp.conj(jnp.swapaxes(E, -1, -2)) @ X)
    np.testing.assert_allclose(np.asarray(S), 0, atol=1e-10)
    # vertical directions project to zero: eta = X * Omega, Omega skew-Herm
    Om = rng.normal(size=(K, 2, 2)) + 1j * rng.normal(size=(K, 2, 2))
    Om = Om - np.conj(Om.transpose(0, 2, 1))
    vert = rtr_mod._r(X @ jnp.asarray(Om), K, N)
    hv = rtr_mod.project_tangent(p, vert, K, N)
    np.testing.assert_allclose(np.asarray(hv), 0, atol=1e-9)


def test_rtr_recovers_jones_noiseless():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=8, T=4, K=1, seed=2)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J, info = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                                config=rtr_mod.RTRConfig(itmax=40))
    assert float(info["final_cost"][0]) < 1e-8 * float(info["init_cost"][0])
    assert _invariant_misfit(J, Jtrue, coh, sta1, sta2, chunk_id) < 1e-6


def test_rtr_multichunk_with_mask():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=6, T=4, K=2, seed=3)
    # pad with a dead chunk slot
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (3, 6, 1, 1))
    mask = jnp.asarray([True, True, False])
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J, info = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 6,
                                chunk_mask=mask,
                                config=rtr_mod.RTRConfig(itmax=40))
    fc = np.asarray(info["final_cost"])[:2]
    ic = np.asarray(info["init_cost"])[:2]
    assert np.all(fc < 1e-6 * ic)
    # dead chunk untouched
    np.testing.assert_allclose(np.asarray(J[2]),
                               np.tile(np.eye(2), (6, 1, 1)), atol=0)


def test_robust_rtr_downweights_outliers():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=8, T=6, seed=5)
    B = x8.shape[0]
    rng = np.random.default_rng(6)
    out = rng.choice(B, B // 10, replace=False)
    x8 = x8.at[out].add(jnp.asarray(rng.normal(size=(len(out), 8)) * 20))
    wt = lm_mod.make_weights(jnp.zeros(B, jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))

    Jp, _ = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
                              config=rtr_mod.RTRConfig(itmax=25))
    Jr, nu, _ = rtr_mod.rtr_solve_robust(
        x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
        config=rtr_mod.RTRConfig(itmax=15), wt_rounds=3)
    mis_p = _invariant_misfit(Jp, Jtrue, coh, sta1, sta2, chunk_id)
    mis_r = _invariant_misfit(Jr, Jtrue, coh, sta1, sta2, chunk_id)
    assert mis_r < mis_p * 0.5
    assert 2.0 <= float(nu) <= 30.0


def test_rtr_admm_pulls_toward_consensus():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=6, T=4, K=1, seed=7,
                                                               noise=0.05)
    N = 6
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, N, 1, 1))
    bz = ne.jones_c2r(jnp.asarray(Jtrue)).reshape(1, -1)
    y = jnp.zeros_like(bz)
    J_free, _ = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, N,
                                  config=rtr_mod.RTRConfig(itmax=25))
    J_admm, _ = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, N,
                                  config=rtr_mod.RTRConfig(itmax=25),
                                  admm=(y, bz, 1000.0))
    # the penalty's vertical (gauge) component is projected out on-manifold
    # (the reference gauge-aligns Y/BZ by manifold averaging before the
    # slave solve), so compare gauge-invariantly: Procrustes-align each
    # solution onto the consensus target first
    from sagecal_tpu.consensus import manifold as mf

    Xt = mf.jones_to_blocks(jnp.asarray(Jtrue))          # [1, 2N, 2]

    def gauge_dist(J):
        Xa = mf.procrustes_project(Xt, mf.jones_to_blocks(J))
        return float(jnp.linalg.norm(Xa - Xt))

    d_free = gauge_dist(J_free)
    d_admm = gauge_dist(J_admm)
    assert d_admm < d_free * 0.5


def test_nsd_reduces_cost():
    x8, coh, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=8, T=4, K=1, seed=8,
                                                               noise=0.02)
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, 8, 1, 1))
    J, nu, info = rtr_mod.nsd_solve_robust(
        x8, coh, sta1, sta2, chunk_id, wt, J0, 8,
        config=rtr_mod.NSDConfig(itmax=40))
    assert float(info["final_cost"][0]) < 0.2 * float(info["init_cost"][0])


def _tcg_thirty(hess_fn, rgrad, delta, cfg):
    """The parent's ``rtr._tcg``, kept as the reference: the same body
    under ``lax.fori_loop(0, cfg.tcg_iters, ...)``, every trip executed
    whatever ``done`` says. Returns (eta, mdot)."""
    _dot = rtr_mod._dot
    r0n = jnp.sqrt(_dot(rgrad, rgrad))
    target = r0n * jnp.minimum(cfg.kappa, r0n ** cfg.theta)

    def body(_, s):
        Hd = hess_fn(s.d)
        d_Hd = _dot(s.d, Hd)
        alpha = s.r_r / jnp.where(d_Hd != 0, d_Hd, 1.0)
        e_d = _dot(s.eta, s.d)
        d_d = _dot(s.d, s.d)
        disc = jnp.maximum(e_d * e_d + d_d * (delta * delta - s.e_e), 0.0)
        tau = (-e_d + jnp.sqrt(disc)) / jnp.maximum(d_d, 1e-30)
        hit = (d_Hd <= 0) | (s.e_e + 2 * alpha * e_d
                             + alpha * alpha * d_d >= delta * delta)
        step = jnp.where(hit, tau, alpha)
        eta_new = s.eta + step[:, None] * s.d
        dm = -step * _dot(s.r, s.d) - 0.5 * step * step * d_Hd
        r_new = s.r + step[:, None] * Hd
        rr_new = _dot(r_new, r_new)
        beta = rr_new / jnp.maximum(s.r_r, 1e-30)
        d_new = -r_new + beta[:, None] * s.d
        done_new = s.done | hit | (jnp.sqrt(rr_new) <= target)
        upd = ~s.done
        return rtr_mod._TCGState(
            eta=jnp.where(upd[:, None], eta_new, s.eta),
            r=jnp.where(upd[:, None], r_new, s.r),
            d=jnp.where(upd[:, None], d_new, s.d),
            r_r=jnp.where(upd, rr_new, s.r_r),
            e_e=jnp.where(upd, _dot(eta_new, eta_new), s.e_e),
            mdot=jnp.where(upd, s.mdot + dm, s.mdot),
            done=done_new)

    K, D = rgrad.shape
    init = rtr_mod._TCGState(
        eta=jnp.zeros_like(rgrad), r=rgrad, d=-rgrad, r_r=r0n * r0n,
        e_e=jnp.zeros((K,), rgrad.dtype),
        mdot=jnp.zeros((K,), rgrad.dtype), done=r0n <= 1e-30)
    out = jax.lax.fori_loop(0, cfg.tcg_iters, body, init)
    return out.eta, out.mdot


def _assert_same(new, ref):
    """The kept arithmetic is the same, so the two loops agree BITWISE on
    this backend (the CPU); should a compiler fuse a ``while`` body
    otherwise than a counted loop's, a few ulp would still pass."""
    new, ref = np.asarray(new), np.asarray(ref)
    if np.array_equal(new, ref):
        return
    eps = np.finfo(ref.real.dtype).eps
    np.testing.assert_allclose(new, ref, rtol=8 * eps,
                               atol=8 * eps * float(np.abs(ref).max()))
    pytest.fail("equal to a few ulp, NOT bitwise: the CPU used to be")


def _spd_problem(K, D, seed, cond, dtype):
    """K symmetric positive definite [D, D] operators with eigenvalues
    spread log-uniformly over ``cond`` decades, a gradient each."""
    rng = np.random.default_rng(seed)
    H = []
    for _ in range(K):
        Q, _ = np.linalg.qr(rng.normal(size=(D, D)))
        H.append((Q * np.logspace(0, cond, D)) @ Q.T)
    H = jnp.asarray(np.stack(H), dtype)
    g = jnp.asarray(rng.normal(size=(K, D)), dtype)
    return H, g


# name: (K, D, eigenvalue decades, kappa, delta per chunk, expected trips)
_TCG_CASES = {
    # well conditioned, the radius far away: the residual target stops it
    "early": (1, 40, 0.3, 0.1, [1e6], "below"),
    # a residual target nothing reaches and 48 distinct eigenvalues: the
    # cap is the count
    "cap": (1, 48, 4.0, 1e-30, [1e6], "cap"),
    # two chunks of one call that stop at different trips (one on its
    # radius): the loop runs for the slower, the faster stays frozen
    "chunks": (2, 40, 1.0, 0.1, [1e6, 1e-3], "below"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("case", sorted(_TCG_CASES))
def test_tcg_equals_thirty_trip_loop(case, dtype):
    """``_tcg`` ends when every chunk is done and returns what the
    parent's thirty blind trips returned, with the bodies it executed."""
    K, D, cond, kappa, delta, expect = _TCG_CASES[case]
    H, g = _spd_problem(K, D, seed=11, cond=cond, dtype=dtype)
    delta = jnp.asarray(delta, dtype)
    cfg = rtr_mod.RTRConfig(kappa=kappa)
    hv = lambda v: jnp.einsum("kij,kj->ki", H, v)
    eta, md, trips = jax.jit(lambda g, d: rtr_mod._tcg(hv, g, d, cfg))(
        g, delta)
    eta_r, md_r = jax.jit(lambda g, d: _tcg_thirty(hv, g, d, cfg))(g, delta)
    _assert_same(eta, eta_r)
    _assert_same(md, md_r)
    assert trips.dtype == jnp.int32 and trips.shape == ()
    if expect == "cap":
        assert int(trips) == cfg.tcg_iters
    else:
        assert 1 <= int(trips) < cfg.tcg_iters // 2
    if case == "chunks":
        # the slow chunk alone needs as many; the fast one fewer
        alone = [int(rtr_mod._tcg(
            lambda v, k=k: jnp.einsum("ij,kj->ki", H[k], v),
            g[k:k + 1], delta[k:k + 1], cfg)[2]) for k in range(K)]
        assert int(trips) == max(alone) and min(alone) < max(alone)


def test_tcg_under_vmap_counts_each_element():
    """A batched ``while_loop`` runs until its last element is done and
    freezes the others: each element's eta, mdot AND count are the ones
    it has alone."""
    T, D = 4, 40
    cfg = rtr_mod.RTRConfig()
    Hs, gs = _spd_problem(T, D, seed=12, cond=1.0, dtype=jnp.float64)
    # element 0 stops on its radius at once, element 3 never moves
    deltas = jnp.asarray([1e-3, 1e6, 3.0, 1e6])
    gs = gs.at[3].set(0.0)

    def one(H, g, d):
        return rtr_mod._tcg(lambda v: v @ H.T, g[None], d[None], cfg)

    def one_ref(H, g, d):
        return _tcg_thirty(lambda v: v @ H.T, g[None], d[None], cfg)

    eta, md, trips = jax.jit(jax.vmap(one))(Hs, gs, deltas)
    eta_r, md_r = jax.jit(jax.vmap(one_ref))(Hs, gs, deltas)
    _assert_same(eta, eta_r)
    _assert_same(md, md_r)
    alone = [int(one(Hs[t], gs[t], deltas[t])[2]) for t in range(T)]
    assert np.asarray(trips).tolist() == alone
    assert alone[3] == 0 and alone[0] == 1
    assert len(set(alone)) >= 3 and max(alone) < cfg.tcg_iters


# name: (chunks, mask, robust)
_SOLVE_CASES = {"K1": (1, None, False), "K2-masked": (2, [True, False],
                                                      False),
                "K1-robust": (1, None, True), "K2-robust": (2, None, True)}


@pytest.mark.parametrize("case", sorted(_SOLVE_CASES))
def test_rtr_solve_equals_thirty_trip_loop(case, monkeypatch):
    """``rtr_solve`` / ``rtr_solve_robust`` over the early-stopping tCG
    return the parent's Jones, costs and outer trip count, and the
    executed tCG bodies: at least one an outer trip, fewer than the cap's."""
    K, mask, robust = _SOLVE_CASES[case]
    N = 6
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem_scalar(
        N=N, T=4, K=K, seed=13, noise=0.02)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (K, N, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    cfg = rtr_mod.RTRConfig(itmax=8)
    cmask = None if mask is None else jnp.asarray(mask)

    def solve():
        if robust:
            J, nu, info = rtr_mod.rtr_solve_robust(
                x8, coh, sta1, sta2, chunk_id, wt, J0, N, chunk_mask=cmask,
                config=cfg)
            return J, nu, info
        J, info = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0,
                                    N, chunk_mask=cmask, config=cfg)
        return J, 0.0, info

    J, nu, info = solve()
    monkeypatch.setattr(
        rtr_mod, "_tcg",
        lambda *a: _tcg_thirty(*a) + (jnp.asarray(a[3].tcg_iters,
                                                  jnp.int32),))
    J_r, nu_r, info_r = solve()
    _assert_same(J, J_r)
    _assert_same(nu, nu_r)
    _assert_same(info["final_cost"], info_r["final_cost"])
    its = int(info["iters"])
    assert its == int(info_r["iters"]) > 0
    assert int(info_r["cg_iters"]) == its * cfg.tcg_iters
    assert its <= int(info["cg_iters"]) < its * cfg.tcg_iters


def _complex_model(Jp, coh, Jq):
    """The row model as the parent wrote it: complex 2 x 2 products."""
    return Jp @ coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))


@pytest.mark.parametrize("dtype, rtol", [(np.float64, 1e-12),
                                         (np.float32, 1e-5)],
                         ids=["f64", "f32"])
def test_row_model_matches_complex_products(dtype, rtol):
    """``normal_eq.row_model``: V = Jp C Jq^H and the Wirtinger factors
    A = C Jq^H, Bm = Jp C on real planes, rows on the minor axis, against
    the complex matrix products."""
    rng = np.random.default_rng(21)
    B = 37
    cplx = lambda: jnp.asarray(
        (rng.normal(size=(B, 2, 2)) + 1j * rng.normal(size=(B, 2, 2))
         ).astype(np.complex128 if dtype == np.float64 else np.complex64))
    Jp, Jq, C = cplx(), cplx(), cplx()
    planes = lambda M: ne.jones_c2r(M).T
    v, a, bm = ne.row_model(planes(Jp), planes(Jq), planes(C))
    assert v.dtype == a.dtype == bm.dtype == dtype and v.shape == (8, B)
    JqH = jnp.conj(jnp.swapaxes(Jq, -1, -2))
    for got, want in ((v, _complex_model(Jp, C, Jq)), (a, C @ JqH),
                      (bm, Jp @ C)):
        want = np.asarray(planes(want))
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=rtol * np.abs(want).max())


def _reference_cost(x8, coh, sta1, sta2, chunk_id, wt, K, N, mode, Jref,
                    nu, admm):
    """``rtr.make_cost``'s cost written once more HERE, in complex, for
    ``jax.grad`` to differentiate: the written-out gradient is held
    against autodiff of this and of nothing in the package."""
    def cost(p):
        if mode == "full":
            J = ne.jones_r2c(p.reshape(K, N, 8))
        else:
            J = ne.jones_from_params(
                p.reshape(K, N, ne.jones_npar(mode)), mode, Jref)
        V = _complex_model(J[chunk_id, sta1], coh, J[chunk_id, sta2])
        vf = V.reshape(-1, 4)
        e = (x8 - jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8)) * wt
        per = e * e if nu is None else jnp.log1p(e * e / nu)
        ck = jax.ops.segment_sum(jnp.sum(per, -1), chunk_id,
                                 num_segments=K)
        if admm is not None:
            y, bz, rho = admm
            d = p - bz
            ck = ck + 2.0 * jnp.sum(y * d, -1) + rho * jnp.sum(d * d, -1)
        return ck
    return cost


# name: (chunks, row_period given): with the rows' period a chunk's
# timeslots are summed first and chunks x nbase rows scattered; without
# one every row is scattered
_ROW_LAYOUTS = {"K2": (2, True), "K2-flat": (2, False),
                "K1-period": (1, True), "K1-flat": (1, False)}
_GRAD_CASES = [(c, m) for c in ("gauss", "robust") for m in
               ("full", "diag", "phase")] + [("admm", "full"),
                                             ("robust-admm", "full")]


@pytest.mark.parametrize("cost, mode", _GRAD_CASES,
                         ids=["-".join(c) for c in _GRAD_CASES])
@pytest.mark.parametrize("layout", sorted(_ROW_LAYOUTS))
def test_written_out_gradient_matches_autodiff(layout, cost, mode):
    """``rtr.make_row_pass``: its cost and its Euclidean gradient
    (elementwise shares from the pass's own Wirtinger factors, one
    segment sum, the ADMM term, ``jax.vjp`` of the station-sized p -> J
    map for the constrained modes) against ``jax.grad`` of the complex
    cost above, some rows flagged."""
    K, period = _ROW_LAYOUTS[layout]
    N, T = 5, 4
    nbase = N * (N - 1) // 2
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=N, T=T, K=K, seed=22,
                                                    noise=0.3)
    rng = np.random.default_rng(23)
    flags = jnp.asarray(rng.random(x8.shape[0]) < 0.2, jnp.int32)
    wt = lm_mod.make_weights(flags, x8.dtype) \
        * jnp.asarray(rng.uniform(0.5, 1.5, size=x8.shape))
    J = jnp.asarray(rng.normal(size=(K, N, 2, 2)) * 0.4
                    + 1j * rng.normal(size=(K, N, 2, 2)) * 0.4 + np.eye(2))
    Jref = None if mode == "full" else ne.jones_constrain(J, mode)
    D = N * ne.jones_npar(mode)
    if mode == "full":
        p = ne.jones_c2r(J).reshape(K, D)
    else:
        p = ne.params_from_jones(Jref, mode).reshape(K, D) \
            + jnp.asarray(rng.normal(size=(K, D)) * 0.1)
    nu = 3.5 if "robust" in cost else None
    admm = None
    if "admm" in cost:
        admm = (jnp.asarray(rng.normal(size=(K, D))),
                jnp.asarray(rng.normal(size=(K, D))),
                jnp.asarray(rng.uniform(1.0, 5.0, size=K)))
    rows = ne.RowPlanes(x8, coh, wt, sta1, sta2, chunk_id, K, N,
                        nbase if period else 0)
    assert rows.periodic == period
    row_pass, egrad = rtr_mod.make_row_pass(
        rows, K, N, admm=admm, robust_nu=nu, mode=mode, Jref=Jref)
    ck, e, shares = jax.jit(row_pass)(p)
    g = jax.jit(egrad)(p, shares)
    ref = _reference_cost(x8, coh, sta1, sta2, chunk_id, wt, K, N, mode,
                          Jref, nu, admm)
    g_ref = jax.grad(lambda q: jnp.sum(ref(q)))(p)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(ref(p)),
                               rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-10,
                               atol=1e-12 * float(jnp.abs(g_ref).max()))
    # the pass's residual is residual8's, in plane form
    J_at = ne.jones_r2c(p.reshape(K, N, 8)) if mode == "full" else \
        ne.jones_from_params(p.reshape(K, N, -1), mode, Jref)
    np.testing.assert_allclose(
        np.asarray(rows.to_rows(e)),
        np.asarray(ne.residual8(x8, J_at, coh, sta1, sta2, chunk_id) * wt),
        atol=1e-12)


def _fresh_residual(x8, J, coh, sta1, sta2, chunk_id, wt):
    return np.asarray(ne.residual8(x8, J, coh, sta1, sta2, chunk_id) * wt)


# name: (chunks, mask, row_period given, robust nu)
_ONCE_CASES = {"K1-period": (1, None, True, None),
               "K1-flat-robust": (1, None, False, 4.0),
               "K2-masked": (2, [True, False], True, None),
               "K2-robust": (2, None, True, 4.0)}


@pytest.mark.parametrize("case", sorted(_ONCE_CASES))
def test_rtr_solve_one_row_pass_per_point(case):
    """``rtr_solve`` evaluates the row model once at the start and once
    per trial point, and the residual it hands back is the one at the
    Jones it returns (a masked chunk's at its J0)."""
    K, mask, period, nu = _ONCE_CASES[case]
    N = 6
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=N, T=4, K=K, seed=24,
                                                    noise=0.05)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (K, N, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J, info = rtr_mod.rtr_solve(
        x8, coh, sta1, sta2, chunk_id, wt, J0, N,
        chunk_mask=None if mask is None else jnp.asarray(mask),
        config=rtr_mod.RTRConfig(itmax=7), robust_nu=nu,
        row_period=N * (N - 1) // 2 if period else 0)
    its = int(info["iters"])
    assert 0 < its <= 7 and int(info["row_passes"]) == 1 + its
    assert info["row_passes"].dtype == jnp.int32
    assert float(info["final_cost"][0]) < float(info["init_cost"][0])
    np.testing.assert_allclose(
        np.asarray(info["residual"]),
        _fresh_residual(x8, J, coh, sta1, sta2, chunk_id, wt), atol=1e-12)


@pytest.mark.parametrize("rounds", [1, 3])
def test_rtr_solve_robust_estep_reads_the_solves_residual(rounds):
    """``rtr_solve_robust``: rounds + iters row passes, and a round's nu
    (its weights' statistic) is the one a fresh ``residual8`` at the
    round's Jones gives."""
    from sagecal_tpu.solvers import robust as rb
    N = 6
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(
        N=N, T=4, K=1, seed=25, noise=0.05, nu=3.0)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, N, 1, 1))
    flags = jnp.asarray(np.arange(x8.shape[0]) % 7 == 0, jnp.int32)
    wt = lm_mod.make_weights(flags, x8.dtype)
    cfg = rtr_mod.RTRConfig(itmax=5)
    kw = dict(config=cfg, row_period=N * (N - 1) // 2)
    J, nu, info = rtr_mod.rtr_solve_robust(
        x8, coh, sta1, sta2, chunk_id, wt, J0, N, nu0=2.0,
        wt_rounds=rounds, **kw)
    its = int(info["iters"])
    assert its >= rounds and int(info["row_passes"]) == rounds + its
    # the rounds once more by hand, the E-step from a fresh residual
    Jh, nuh = J0, jnp.asarray(2.0)
    for _ in range(rounds):
        Jh, _ = rtr_mod.rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, Jh, N,
                                  robust_nu=nuh, **kw)
        w = rb.update_weights(
            _fresh_residual(x8, Jh, coh, sta1, sta2, chunk_id, wt), nuh)
        nuh = rb.update_nu_aecm(rb.mean_logsumw(w, np.asarray(wt) > 0),
                                nuh, p=2)
    np.testing.assert_allclose(np.asarray(J), np.asarray(Jh), atol=1e-12)
    np.testing.assert_allclose(float(nu), float(nuh), rtol=1e-12)


def test_row_passes_under_vmap_counts_each_element():
    """Batched solves freeze an element that is done: its ``row_passes``
    is the count it has alone, one more than its own iterations."""
    N, caps = 6, [0, 2, 6]
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem(N=N, T=4, K=1, seed=26,
                                                    noise=0.05)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (1, N, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)

    def one(cap):
        return rtr_mod.rtr_solve(
            x8, coh, sta1, sta2, chunk_id, wt, J0, N,
            config=rtr_mod.RTRConfig(itmax=6), itmax_dynamic=cap,
            row_period=N * (N - 1) // 2)[1]

    info = jax.jit(jax.vmap(one))(jnp.asarray(caps, jnp.int32))
    assert np.asarray(info["iters"]).tolist() == caps
    assert np.asarray(info["row_passes"]).tolist() == [c + 1 for c in caps]
    alone = [int(one(jnp.asarray(c, jnp.int32))["row_passes"])
             for c in caps]
    assert alone == [c + 1 for c in caps]


@pytest.mark.slow
def test_sage_dispatches_rtr_modes():
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage

    x8, coh_b, sta1, sta2, chunk_id, Jtrue = _toy_problem_scalar(N=6, T=2, K=1,
                                                                 seed=9, noise=0.01)
    # fake 2-cluster problem: split coherencies
    coh = jnp.stack([coh_b, 0.5 * coh_b])
    Vsum = sage.full_model8(
        jnp.asarray(Jtrue)[None].repeat(2, 0) * jnp.asarray([1.0, 0.7]
                                                            )[:, None, None, None, None],
        coh, sta1, sta2, chunk_id[None].repeat(2, 0))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (2, 1, 6, 1, 1))
    cidx = chunk_id[None].repeat(2, 0)
    cmask = jnp.ones((2, 1), bool)
    for mode in (SolverMode.RTR_OSLM_LBFGS, SolverMode.RTR_OSRLM_RLBFGS,
                 SolverMode.NSD_RLBFGS):
        cfg = sage.SageConfig(max_emiter=2, max_iter=6, max_lbfgs=4,
                              solver_mode=int(mode))
        J, info = sage.sagefit(Vsum, coh, sta1, sta2, cidx, cmask, J0, 6,
                               wt, config=cfg)
        assert float(info["res_1"]) < float(info["res_0"]), mode


def test_rtr_solve_zero_retrace(retrace_guard):
    """Tier-1 retrace gate: identically shaped RTR solves share one
    compiled program (zero compile requests on the re-run)."""
    x8, coh, sta1, sta2, chunk_id, _ = _toy_problem_scalar(N=6, T=4,
                                                           K=2, seed=7)
    J0 = jnp.tile(jnp.eye(2, dtype=jnp.complex128), (2, 6, 1, 1))
    wt = lm_mod.make_weights(jnp.zeros(x8.shape[0], jnp.int32), x8.dtype)
    solve = jax.jit(rtr_mod.rtr_solve,
                    static_argnames=("n_stations", "config"))

    def thunk():
        return solve(x8, coh, sta1, sta2, chunk_id, wt, J0, 6,
                     config=rtr_mod.RTRConfig(itmax=6))

    retrace_guard(thunk)
