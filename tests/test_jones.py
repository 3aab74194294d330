"""Constrained-Jones melt gates (ISSUE 20).

The tentpole contract, tested from the algebra up:

- the reduced-mode normal equations are EXACTLY the T-projection of
  the dense full-Jones system (JTJ_m = T^T JTJ T, JTe_m = T^T JTe
  with T the per-station direction matrix of the reduced params),
  at machine epsilon, across {uniform, IRLS} weights x cost_wt x
  rho-shift and under the OS subset masking;
- jones_mode="full" is bit-frozen: the mode entry points reproduce
  the pre-mode path bit-for-bit, solver included;
- on constrained-truth data every constrained solver path converges
  (the quality envelope of the constrained modes);
- consensus ADMM refuses constrained modes loudly (the y/bz vectors
  are full-Jones parameters);
- the gauge projection removes exactly the global-phase direction.

Heavy solver grids are slow-marked per the round-17 tier-1 budget
rule; the fast subset stays in the CI fail-fast lane.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb
from sagecal_tpu.solvers import rtr as rtr_mod

REL = 5e-12          # x64 machine-epsilon-scale relative parity


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-30))


# ---------------------------------------------------------------------------
# geometry + reference projection
# ---------------------------------------------------------------------------

def _geometry(N=5, nb=8, T=6, K=2, seed=0):
    """Tiled rows (row_period = nb), time-contiguous chunks — the
    layout every solver path agrees on."""
    rng = np.random.default_rng(seed)
    s1 = np.array([0, 1, 2, 3, 4, 0, 1, 2])[:nb]
    s2 = np.array([1, 2, 3, 4, 0, 2, 3, 4])[:nb]
    B = nb * T
    sta1 = jnp.asarray(np.tile(s1, T).astype(np.int32))
    sta2 = jnp.asarray(np.tile(s2, T).astype(np.int32))
    chunk = jnp.asarray((np.repeat(np.arange(T), nb)
                         * K // T).astype(np.int32))
    coh = jnp.asarray(rng.normal(size=(B, 2, 2))
                      + 1j * rng.normal(size=(B, 2, 2)))
    x8 = jnp.asarray(rng.normal(size=(B, 8)))
    Jfull = jnp.asarray(rng.normal(size=(K, N, 2, 2))
                        + 1j * rng.normal(size=(K, N, 2, 2)))
    return dict(N=N, nb=nb, T=T, K=K, B=B, rng=rng, sta1=sta1,
                sta2=sta2, chunk=chunk, coh=coh, x8=x8, Jfull=Jfull)


def _weights(kind, g):
    """Per-component weights: uniform, or genuine IRLS E-step weights
    w = (nu+1)/(nu + e^2) on the residual at a perturbed J."""
    if kind == "uniform":
        return jnp.ones((g["B"], 8))
    Jp = jnp.take(g["Jfull"], g["sta1"], axis=1)[g["chunk"],
                                                 jnp.arange(g["B"])]
    Jq = jnp.take(g["Jfull"], g["sta2"], axis=1)[g["chunk"],
                                                 jnp.arange(g["B"])]
    model = jnp.einsum("bij,bjk,blk->bil", Jp, g["coh"], Jq.conj())
    mf = model.reshape(g["B"], 4)
    m8 = jnp.stack([mf.real, mf.imag], -1).reshape(g["B"], 8)
    return rb.update_weights(g["x8"] - m8, 5.0)


def _tmat(J, mode):
    """Full-param directions of the reduced params, [K, N, 8, npar]:
    column m is d(full 8-real params)/d(reduced param m). diag: unit
    vectors into the diagonal slots (0, 1, 6, 7); phase: d j_cc /
    d theta_c = i j_cc -> (-Im, Re) in the (Re, Im) slots."""
    J = np.asarray(J)
    K, N = J.shape[:2]
    if mode == "diag":
        T = np.zeros((K, N, 8, 4))
        for m, ix in enumerate((0, 1, 6, 7)):
            T[:, :, ix, m] = 1.0
    else:
        T = np.zeros((K, N, 8, 2))
        for c, (re, im) in enumerate(((0, 1), (6, 7))):
            jcc = J[:, :, c, c]
            T[:, :, re, c] = -jcc.imag
            T[:, :, im, c] = jcc.real
    return T


def _block_T(J, mode):
    """Block-diagonal station stack of :func:`_tmat`: [K, 8N, npar*N]."""
    T = _tmat(J, mode)
    K, N, _, npar = T.shape
    Tb = np.zeros((K, 8 * N, npar * N))
    for k in range(K):
        for n in range(N):
            Tb[k, 8 * n:8 * n + 8, npar * n:npar * n + npar] = T[k, n]
    return Tb


def _constrained_truth(g, mode, seed=7, noise=0.0):
    """Data generated by a truth INSIDE the constraint set, plus the
    natural J0 start (identity; phase keeps the truth amplitudes in
    Jref so only the phases are unknown)."""
    rng = np.random.default_rng(seed)
    K, N, B = g["K"], g["N"], g["B"]
    th = rng.uniform(-0.8, 0.8, size=(K, N, 2))
    amp = (rng.uniform(0.7, 1.3, size=(K, N, 2)) if mode == "diag"
           else np.ones((K, N, 2)))
    d = amp * np.exp(1j * th)
    Jt = np.zeros((K, N, 2, 2), complex)
    Jt[..., 0, 0] = d[..., 0]
    Jt[..., 1, 1] = d[..., 1]
    Jt = jnp.asarray(Jt)
    Jp = jnp.take(Jt, g["sta1"], axis=1)[g["chunk"], jnp.arange(B)]
    Jq = jnp.take(Jt, g["sta2"], axis=1)[g["chunk"], jnp.arange(B)]
    V = jnp.einsum("bij,bjk,blk->bil", Jp, g["coh"], Jq.conj())
    vf = np.asarray(V).reshape(B, 4)
    if noise:
        vf = vf + noise * (rng.normal(size=(B, 4))
                           + 1j * rng.normal(size=(B, 4)))
    x8 = jnp.asarray(np.stack([vf.real, vf.imag], -1).reshape(B, 8))
    if mode == "phase":
        J0 = jnp.asarray(np.abs(np.asarray(Jt))
                         * np.eye(2))                  # amplitudes known
    else:
        J0 = jnp.tile(jnp.eye(2, dtype=Jt.dtype), (K, N, 1, 1))
    return x8, J0, Jt


# ---------------------------------------------------------------------------
# parameterization layer
# ---------------------------------------------------------------------------

def test_mode_helpers_roundtrip():
    assert ne.JONES_MODES == ("full", "diag", "phase")
    assert [ne.jones_mdim(m) for m in ne.JONES_MODES] == [4, 2, 1]
    assert [ne.jones_npar(m) for m in ne.JONES_MODES] == [8, 4, 2]
    g = _geometry()
    J = g["Jfull"]
    assert np.array_equal(np.asarray(ne.jones_constrain(J, "full")),
                          np.asarray(J))
    for mode in ("diag", "phase"):
        Jc = np.asarray(ne.jones_constrain(J, mode))
        assert np.all(Jc[..., 0, 1] == 0) and np.all(Jc[..., 1, 0] == 0)
        assert np.array_equal(np.diagonal(Jc, axis1=-2, axis2=-1),
                              np.asarray(np.diagonal(np.asarray(J),
                                                     axis1=-2, axis2=-1)))
    # diag: additive coordinates round-trip exactly
    Jd = ne.jones_constrain(J, "diag")
    p = ne.params_from_jones(Jd, "diag")
    assert _rel(ne.jones_from_params(p, "diag"), Jd) == 0.0
    # phase: p0 = 0 is the convention — the retraction rides Jref
    pp = ne.params_from_jones(Jd, "phase")
    assert np.all(np.asarray(pp) == 0.0)
    assert _rel(ne.jones_from_params(pp, "phase", Jref=Jd),
                ne.jones_constrain(Jd, "phase")) == 0.0
    # the additive step IS J * exp(i theta)
    th = jnp.asarray(np.random.default_rng(1).normal(
        size=np.asarray(pp).shape))
    got = np.asarray(ne.jones_from_params(th, "phase", Jref=Jd))
    d0 = np.asarray(Jd)[..., 0, 0] * np.exp(1j * np.asarray(th)[..., 0])
    d1 = np.asarray(Jd)[..., 1, 1] * np.exp(1j * np.asarray(th)[..., 1])
    assert _rel(got[..., 0, 0], d0) < REL and _rel(got[..., 1, 1], d1) < REL


# ---------------------------------------------------------------------------
# projected blocks vs the dense masked-full reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wkind", ("uniform", "irls"))
@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_projected_blocks_match_masked_full(mode, wkind):
    """JTJ_m == T^T JTJ_full T and JTe_m == T^T JTe_full at the
    constrained point, machine epsilon, with the cost riding the
    cost_wt channel identically to the full path."""
    g = _geometry()
    wt = _weights(wkind, g)
    cwt = jnp.asarray(np.abs(g["rng"].normal(size=(g["B"], 8))) + 0.1)
    Jc = ne.jones_constrain(g["Jfull"], mode)
    JTJf, JTef, _ = ne._normal_equations_dense(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], g["chunk"], wt,
        g["N"], g["K"])
    JTJm, JTem, costm = ne.normal_equations_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], g["chunk"], wt,
        g["N"], g["K"], mode, cost_wt=cwt)
    Tb = _block_T(Jc, mode)
    assert _rel(np.einsum("kij,kim,kjn->kmn", np.asarray(JTJf), Tb, Tb),
                JTJm) < REL
    assert _rel(np.einsum("ki,kim->km", np.asarray(JTef), Tb),
                JTem) < REL
    # the cost channel is parameterization-independent: the full-mode
    # entry point at the same constrained J must price it identically
    _, _, costf = ne.normal_equations_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], g["chunk"], wt,
        g["N"], g["K"], "full", cost_wt=cwt)
    assert _rel(costm, costf) < REL


@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_os_subset_matches_masked_full(mode):
    """The OS-subset reduced equations equal the full-B reduced
    equations under the subset mask (the OS contract, per mode)."""
    g = _geometry(K=1, T=6)
    wt = _weights("irls", g)
    cwt = jnp.asarray(np.abs(g["rng"].normal(size=(g["B"], 8))) + 0.1)
    Jc = ne.jones_constrain(g["Jfull"][:1], mode)
    ntper = 2
    os_id = jnp.asarray(np.repeat(np.arange(g["T"]) // ntper,
                                  g["nb"]).astype(np.int32))
    sub = 1
    JTJo, JTeo, costo = ne.os_subset_equations_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], wt, os_id, sub,
        ntper, g["nb"], g["N"], cwt, mode)
    mwt = wt * (os_id == sub).astype(wt.dtype)[:, None]
    JTJr, JTer, costr = ne.normal_equations_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"],
        jnp.zeros(g["B"], jnp.int32), mwt, g["N"], 1, mode,
        cost_wt=cwt)
    assert _rel(JTJo, JTJr) < REL
    assert _rel(JTeo, JTer) < REL
    assert abs(float(costo[0]) - float(costr[0])) \
        <= REL * abs(float(costr[0]))


@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_gn_factors_matvec_shift_parity(mode):
    """The matrix-free factors price the SAME reduced JTJ: matvec
    with a per-chunk rho-shift equals the dense product, and the
    station-block preconditioner round-trips shapes."""
    g = _geometry()
    wt = _weights("irls", g)
    Jc = ne.jones_constrain(g["Jfull"], mode)
    npar = ne.jones_npar(mode)
    JTJm, JTem, _ = ne.normal_equations_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], g["chunk"], wt,
        g["N"], g["K"], mode)
    fac, JTe2, _ = ne.gn_factors_mode(
        g["x8"], Jc, g["coh"], g["sta1"], g["sta2"], g["chunk"], wt,
        g["N"], g["K"], mode)
    assert _rel(JTem, JTe2) < REL
    v = jnp.asarray(g["rng"].normal(size=(g["K"], npar * g["N"])))
    sh = jnp.asarray(np.array([0.3, 0.7])[:g["K"]])
    y = ne.gn_matvec_mode(fac, v, g["sta1"], g["sta2"], g["chunk"],
                          g["K"], g["N"], shift=sh)
    yref = (np.einsum("kij,kj->ki", np.asarray(JTJm), np.asarray(v))
            + np.asarray(sh)[:, None] * np.asarray(v))
    assert _rel(yref, y) < REL
    Lf = ne.gn_precond_factor(fac.D, jnp.asarray([0.5, 0.9][:g["K"]]))
    z = ne.gn_precond_apply(Lf, v, g["K"], g["N"])
    assert z.shape == v.shape and bool(jnp.all(jnp.isfinite(z)))


def test_full_mode_entry_points_bit_identical():
    """jones_mode='full' is the bit-frozen default: the mode entry
    points reproduce the pre-mode normal equations bit-for-bit (the
    acceptance gate)."""
    g = _geometry()
    wt = _weights("irls", g)
    cwt = jnp.asarray(np.abs(g["rng"].normal(size=(g["B"], 8))) + 0.1)
    a = ne.normal_equations(g["x8"], g["Jfull"], g["coh"], g["sta1"],
                            g["sta2"], g["chunk"], wt, g["N"], g["K"],
                            cost_wt=cwt)
    b = ne.normal_equations_mode(g["x8"], g["Jfull"], g["coh"],
                                 g["sta1"], g["sta2"], g["chunk"], wt,
                                 g["N"], g["K"], "full", cost_wt=cwt)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.slow
def test_full_mode_solver_bit_identical():
    """The mode-threaded SOLVER at jones_mode='full' reproduces the
    default-config solve bit-for-bit (compile-path twin of the
    normal-equations gate above; slow-marked — two solver compiles —
    per the round-17 tier-1 budget rule)."""
    g = _geometry()
    x8, J0, _ = _constrained_truth(g, "diag")
    w1 = jnp.ones((g["B"], 8))
    Jd, id_ = lm_mod.lm_solve(x8, g["coh"], g["sta1"], g["sta2"],
                              g["chunk"], w1, J0, g["N"],
                              config=lm_mod.LMConfig(itmax=3),
                              row_period=g["nb"])
    Jm, im_ = lm_mod.lm_solve(x8, g["coh"], g["sta1"], g["sta2"],
                              g["chunk"], w1, J0, g["N"],
                              config=lm_mod.LMConfig(
                                  itmax=3, jones_mode="full"),
                              row_period=g["nb"])
    assert np.array_equal(np.asarray(Jd), np.asarray(Jm))
    assert np.array_equal(np.asarray(id_["final_cost"]),
                          np.asarray(im_["final_cost"]))


# ---------------------------------------------------------------------------
# constrained-truth convergence envelope
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_constrained_truth_convergence_fast(mode):
    """CI fail-fast subset: the default (chol) constrained solve
    drives a constrained truth to numerical zero."""
    g = _geometry(K=1)
    x8, J0, _ = _constrained_truth(g, mode)
    wt = jnp.ones((g["B"], 8))
    cfg = lm_mod.LMConfig(itmax=25, jones_mode=mode)
    _, info = lm_mod.lm_solve(x8, g["coh"], g["sta1"], g["sta2"],
                              g["chunk"], wt, J0, g["N"], config=cfg,
                              row_period=g["nb"])
    init = float(np.ravel(np.asarray(info["init_cost"]))[0])
    final = float(np.ravel(np.asarray(info["final_cost"]))[0])
    assert final < 1e-8 * init


@pytest.mark.slow
@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_constrained_convergence_grid_lm(mode):
    """The full inner x OS grid converges on a constrained truth —
    every reduced-block lane, not just the default."""
    g = _geometry(K=1)
    x8, J0, _ = _constrained_truth(g, mode)
    wt = jnp.ones((g["B"], 8))
    for inner in ("chol", "cg"):
        cfg = lm_mod.LMConfig(itmax=25, jones_mode=mode, inner=inner)
        _, info = lm_mod.lm_solve(x8, g["coh"], g["sta1"],
                                  g["sta2"], g["chunk"], wt, J0,
                                  g["N"], config=cfg,
                                  row_period=g["nb"])
        init = float(np.ravel(np.asarray(info["init_cost"]))[0])
        final = float(np.ravel(np.asarray(info["final_cost"]))[0])
        assert final < 1e-6 * init, (inner, final / init)
    osid, nsub = lm_mod.os_subset_ids(g["T"], g["nb"], 3)
    os = lm_mod.OSConfig(os_id=jnp.asarray(osid), n_subsets=nsub,
                         key=jax.random.PRNGKey(0))
    cfg = lm_mod.LMConfig(itmax=25, jones_mode=mode)
    _, info = lm_mod.lm_solve(x8, g["coh"], g["sta1"], g["sta2"],
                              g["chunk"], wt, J0, g["N"], config=cfg,
                              os=os, row_period=g["nb"])
    init = float(np.ravel(np.asarray(info["init_cost"]))[0])
    final = float(np.ravel(np.asarray(info["final_cost"]))[0])
    assert final < 1e-6 * init


@pytest.mark.slow
@pytest.mark.parametrize("mode", ("diag", "phase"))
def test_constrained_convergence_rtr_nsd(mode):
    """The trust-region and NSD lanes retract INSIDE the constraint
    set and converge on a constrained truth (phase retraction is
    J * exp(i theta) via the accumulated-rotation params)."""
    g = _geometry(K=1)
    x8, J0, _ = _constrained_truth(g, mode)
    wt = jnp.ones((g["B"], 8))
    cfg = rtr_mod.RTRConfig(itmax=30, jones_mode=mode)
    Jf, info = rtr_mod.rtr_solve(x8, g["coh"], g["sta1"],
                                 g["sta2"], g["chunk"], wt, J0,
                                 g["N"], config=cfg,
                                 row_period=g["nb"])
    final = float(np.ravel(np.asarray(info["final_cost"]))[0])
    init = float(np.ravel(np.asarray(info["init_cost"]))[0])
    assert final < 1e-6 * init, final / init
    # the retracted solution STAYS in the constraint set
    Jf = np.asarray(Jf)
    assert np.abs(Jf[..., 0, 1]).max() == 0.0
    assert np.abs(Jf[..., 1, 0]).max() == 0.0
    if mode == "phase":
        assert _rel(np.abs(np.diagonal(Jf, axis1=-2, axis2=-1)),
                    np.ones((1, g["N"], 2))) < 1e-6
    ncfg = rtr_mod.NSDConfig(itmax=50, jones_mode=mode)
    _, _, info = rtr_mod.nsd_solve_robust(x8, g["coh"], g["sta1"],
                                          g["sta2"], g["chunk"], wt,
                                          J0, g["N"], config=ncfg)
    final = float(np.ravel(np.asarray(info["final_cost"]))[0])
    init = float(np.ravel(np.asarray(info["init_cost"]))[0])
    assert final < 0.5 * init          # gradient-only: descent, not zero


def test_admm_refuses_constrained_modes():
    """Consensus ADMM carries full-Jones y/bz vectors — a constrained
    solve under it must refuse loudly, never silently mis-project."""
    g = _geometry(K=1)
    x8, J0, _ = _constrained_truth(g, "phase")
    wt = jnp.ones((g["B"], 8))
    admm = (jnp.zeros((1, 8 * g["N"])), jnp.zeros((1, 8 * g["N"])),
            0.1)
    with pytest.raises(ValueError, match="jones_mode"):
        lm_mod.lm_solve(x8, g["coh"], g["sta1"], g["sta2"], g["chunk"],
                        wt, J0, g["N"],
                        config=lm_mod.LMConfig(jones_mode="phase"),
                        admm=admm, row_period=g["nb"])
    with pytest.raises(ValueError, match="jones_mode"):
        rtr_mod.rtr_solve(x8, g["coh"], g["sta1"], g["sta2"],
                          g["chunk"], wt, J0, g["N"],
                          config=rtr_mod.RTRConfig(jones_mode="diag"),
                          admm=admm, row_period=g["nb"])


def test_gauge_projection_removes_global_phase_only():
    """project_tangent_mode removes EXACTLY the global-phase
    direction: the gauge direction maps to zero, the projection is
    idempotent, and an orthogonal direction passes through."""
    g = _geometry(K=2)
    rng = g["rng"]
    for mode in ("diag", "phase"):
        npar = ne.jones_npar(mode)
        Jc = ne.jones_constrain(g["Jfull"], mode)
        p = np.asarray(ne.params_from_jones(Jc, mode)).reshape(
            g["K"], -1)
        if mode == "phase":
            gauge = np.ones_like(p)
        else:
            d = np.stack([np.asarray(Jc)[..., 0, 0],
                          np.asarray(Jc)[..., 1, 1]], -1)
            gauge = np.stack([-d.imag, d.real], -1).reshape(g["K"], -1)
        pj = jnp.asarray(p)
        out = np.asarray(rtr_mod.project_tangent_mode(
            pj, jnp.asarray(gauge), g["K"], g["N"], mode))
        assert float(np.abs(out).max()) < REL * np.abs(gauge).max()
        v = rng.normal(size=(g["K"], npar * g["N"]))
        once = np.asarray(rtr_mod.project_tangent_mode(
            pj, jnp.asarray(v), g["K"], g["N"], mode))
        twice = np.asarray(rtr_mod.project_tangent_mode(
            pj, jnp.asarray(once), g["K"], g["N"], mode))
        assert _rel(once, twice) < REL
        # projected output is orthogonal to the gauge direction
        assert float(np.abs(np.sum(once * gauge, -1)).max()) \
            < 1e-9 * float(np.abs(v).max() * np.abs(gauge).max())
