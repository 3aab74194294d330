"""Riemannian trust-region / Nesterov solvers on the Jones quotient manifold.

Capability parity with reference ``rtr_solve_nocuda`` (rtr_solve.c:1208),
``rtr_solve_nocuda_robust`` + ``nsd_solve_nocuda_robust``
(rtr_solve_robust.c:1441, :1878) and ``rtr_solve_nocuda_robust_admm``
(rtr_solve_robust_admm.c:1425). Each (cluster, time-chunk) solution is a
2N x 2 complex matrix X (N stacked 2x2 Jones blocks); the physical search
space is the quotient of full-rank X by right-multiplication with a 2x2
unitary (the global gain ambiguity):

- metric          g(eta, gamma) = 2 Re tr(eta^H gamma)  (rtr_solve.c:323)
- horiz. proj.    eta - X Omega with Omega skew-Hermitian solving the 2x2
                  Sylvester system (X^H X) Omega + Omega (X^H X)
                  = X^H eta - eta^H X                    (rtr_solve.c:340)
- retraction      R_X(eta) = X + eta                     (rtr_solve.c:419)

TPU re-architecture vs. the reference:
- ALL hybrid time chunks of a cluster solve simultaneously: every tangent
  vector is [K, 8N] real with per-chunk scalars (costs, radii, tCG
  coefficients) as [K] arrays — one batched computation instead of a
  sequential chunk loop;
- the row model V = J_p C J_q^H is real elementwise arithmetic on planes
  with the rows on the minor axis (normal_eq.row_model) and is evaluated
  ONCE per point: a trial point's pass yields its cost and, if the point
  is accepted, the euclidean gradient (written out from the same pass's
  Wirtinger factors: -(G A^H) and -(G^H Bm) summed to the stations) and
  the residual the next iteration's curvature weights and the robust
  E-step read; the passes executed are counted (info["row_passes"]);
- tCG Hessian-vector products use an analytic Gauss-Newton normal matrix
  assembled once per outer TR point from the Wirtinger factors
  (normal_eq.py) — one batched matrix-unit matvec per product instead of
  re-traversing the residual graph (the analogue of the reference's
  hand-derived fns_fhess);
- per-station gradient normalization by baseline counts (rtr_solve.c
  fns_fcount / iw weights, Dirac.h:1114) is kept as a diagonal
  preconditioner on the euclidean differentials;
- the truncated-CG inner iteration (rtr_solve.c:886-1155) runs under
  ``lax.while_loop`` with convergence masks per chunk: it ends when
  every chunk has stopped, as the reference breaks out of its loop, and
  ``tcg_iters`` is the cap; the bodies executed are counted
  (info["cg_iters"]).

Robust variants follow the IRLS structure of robust.py: rounds of
{weighted RTR solve -> Student's-t E-step weight update -> nu grid update}
(rtr_solve_robust.c inner loop).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb


class RTRConfig(NamedTuple):
    itmax: int = 10            # outer TR iterations (-l)
    tcg_iters: int = 30        # max inner tCG iterations
    kappa: float = 0.1         # tCG linear convergence target
    theta: float = 1.0         # tCG superlinear exponent
    rho_accept: float = 0.0    # accept step if rho > this
    rho_regularize: float = 1e-12
    delta0_frac: float = 0.25  # Delta0 = frac * ||X0||_F per chunk
    delta_bar_frac: float = 2.0
    eps_grad: float = 1e-12    # relative gradient stop
    # tCG Hessian operator representation: "chol" materializes the
    # [K, 8N, 8N] Gauss-Newton normal matrix once per outer TR point
    # and each product is a dense batched matvec; "cg" keeps the
    # operator matrix-free (normal_eq.gn_factors + gn_matvec: one
    # [B]-pass of Wirtinger-factor contractions per product) — the
    # SAME linear operator to fp reordering, so unlike lm.py's
    # inexact-Newton path this changes traffic, not trajectory class.
    inner: str = "chol"
    # storage dtype policy (sagecal_tpu.dtypes; see lm.LMConfig): the
    # [B]-data and Wirtinger-factor storage quantize under bf16/f16
    # while the manifold point, tangent vectors and every accumulator
    # stay f32; "f32" is the bit-frozen identity
    dtype_policy: str = "f32"
    # constrained-Jones parameterization (normal_eq.JONES_MODES):
    # "full" (bit-frozen default), "diag" (4 real params/station/pol
    # pair), "phase" (2 real params/station). Non-full modes solve and
    # retract in the reduced space; the U(2) Sylvester gauge projection
    # specializes to the diagonal-U(1)^2 stabilizer (see
    # project_tangent_mode)
    jones_mode: str = "full"


class NSDConfig(NamedTuple):
    itmax: int = 20
    ls_tries: int = 10         # backtracking halvings per step
    alpha0: float = 0.1        # initial step relative to grad norm scale
    jones_mode: str = "full"   # see RTRConfig.jones_mode


def _c(p, kmax, n_stations):
    """[K, 8N] real params -> [K, 2N, 2] complex manifold point."""
    return ne.jones_r2c(p.reshape(kmax, n_stations, 8)).reshape(
        kmax, 2 * n_stations, 2)


def _r(X, kmax, n_stations):
    """[K, 2N, 2] complex -> [K, 8N] real."""
    return ne.jones_c2r(X.reshape(kmax, n_stations, 2, 2)).reshape(kmax, -1)


def _dot(a, b):
    """Riemannian inner products per chunk: Re tr(eta^H gamma) == real dot."""
    return jnp.sum(a * b, axis=-1)


def project_tangent(p, v, kmax, n_stations):
    """Horizontal projection of tangent v at point p (both [K, 8N] real).

    Solves the 2x2 Sylvester system A Omega + Omega A = X^H eta - eta^H X
    (A = X^H X Hermitian positive definite, RHS skew-Hermitian, so Omega is
    skew-Hermitian) via a batched 4x4 complex solve (rtr_solve.c:340-418
    uses zgels on the same system).
    """
    X = _c(p, kmax, n_stations)
    E = _c(v, kmax, n_stations)
    A = jnp.conj(jnp.swapaxes(X, -1, -2)) @ X                   # [K,2,2]
    R = (jnp.conj(jnp.swapaxes(X, -1, -2)) @ E
         - jnp.conj(jnp.swapaxes(E, -1, -2)) @ X)               # [K,2,2]
    I2 = jnp.eye(2, dtype=A.dtype)
    # vec (column-major) of A Om + Om A: M vec(Om) with
    # M = I (x) A + A^T (x) I, built as batched Kronecker products
    M = (jnp.einsum("ij,kab->kiajb", I2, A).reshape(-1, 4, 4)
         + jnp.einsum("kij,ab->kiajb", jnp.swapaxes(A, -1, -2),
                      I2).reshape(-1, 4, 4))
    rhs = jnp.swapaxes(R, -1, -2).reshape(-1, 4, 1)   # column-major vec
    Om = jnp.linalg.solve(M, rhs).reshape(-1, 2, 2)
    Om = jnp.swapaxes(Om, -1, -2)                      # back from vec
    H = E - X @ Om
    return _r(H, kmax, n_stations)


def project_tangent_mode(p, v, kmax, n_stations, mode):
    """Gauge projection of tangent v at point p per jones_mode.

    full: the U(2) Sylvester horizontal projection
    (:func:`project_tangent`). For constrained modes the only EXACT
    continuous symmetry of the cost is the global phase U = e^{i phi} I
    (a scalar commutes with every coherency C, so
    J_p U C U^H J_q^H == J_p C J_q^H identically; the two-parameter
    diagonal subgroup diag(e^{i phi_0}, e^{i phi_1}) rotates the
    off-diagonal coherencies and is NOT flat for polarized models —
    projecting it out would bias the gradient). One real direction per
    chunk:

    - phase: d theta_nc / d phi = 1 for every (station, component) —
      projection subtracts the per-chunk mean of the theta gradient;
    - diag: d (j_ncc e^{i phi}) / d phi = i j_ncc, i.e. the single
      direction u[n, c] = (-Im j_ncc, Re j_ncc) across ALL (Re, Im)
      parameter slots.
    """
    if mode == "full":
        return project_tangent(p, v, kmax, n_stations)
    npar = ne.jones_npar(mode)
    vr = v.reshape(kmax, n_stations * npar)
    if mode == "phase":
        return (vr - jnp.mean(vr, axis=-1, keepdims=True)).reshape(
            kmax, -1)
    J = ne.jones_from_params(p.reshape(kmax, n_stations, npar), "diag")
    d = jnp.stack([J[..., 0, 0], J[..., 1, 1]], -1)    # [K, N, 2] cplx
    u = jnp.stack([-d.imag, d.real], -1).reshape(kmax, -1)
    num = jnp.sum(u * vr, axis=-1, keepdims=True)
    den = jnp.maximum(jnp.sum(u * u, axis=-1, keepdims=True), 1e-30)
    return (vr - (num / den) * u).reshape(kmax, -1)


def _mode_p2j(mode, Jref, kmax, n_stations):
    """params [K, npar*N] -> J [K, N, 2, 2] map for a jones_mode (the
    full branch is the exact pre-mode jones_r2c path)."""
    npar = ne.jones_npar(mode)

    def p_to_J(p):
        if mode == "full":
            return ne.jones_r2c(p.reshape(kmax, n_stations, 8))
        return ne.jones_from_params(
            p.reshape(kmax, n_stations, npar), mode, Jref)

    return p_to_J


def _mode_p2planes(mode, Jref, kmax, n_stations):
    """params [K, npar*N] -> the stations' Jones as real planes
    [K, N, 8] (:func:`ne.jones_c2r` order) for a jones_mode: what
    :meth:`ne.RowPlanes.gather` reads."""
    p_to_J = _mode_p2j(mode, Jref, kmax, n_stations)

    def station_planes(p):
        if mode == "full":
            return p.reshape(kmax, n_stations, 8)
        return ne.jones_c2r(p_to_J(p))

    return station_planes


def station_precond(wt, sta1, sta2, chunk_id, kmax, n_stations,
                    npar: int = 8):
    """iw diagonal preconditioner: 1 / (# live baselines per station) per
    chunk, replicated over the station's 8 params (rtr_solve.c fns_fcount,
    count_baselines baseline_utils.c)."""
    # baseline counts accumulate in the acc dtype: a bf16 scatter-add
    # goes inexact past 256 rows/station (storage-accum boundary)
    live = (jnp.sum(wt, axis=-1) > 0).astype(dtp.acc_dtype(wt.dtype))
    flat1 = chunk_id * n_stations + sta1
    flat2 = chunk_id * n_stations + sta2
    cnt = (jnp.zeros((kmax * n_stations,), live.dtype)
           .at[flat1].add(live).at[flat2].add(live))
    iw = 1.0 / jnp.maximum(cnt, 1.0)
    iw = iw / jnp.maximum(jnp.mean(iw), 1e-30)         # mean-normalized
    return jnp.repeat(iw.reshape(kmax, n_stations), npar, axis=-1)


def make_cost(x8, coh, sta1, sta2, chunk_id, wt, kmax, n_stations,
              admm=None, robust_nu=None, mode: str = "full", Jref=None):
    """Per-chunk cost [K] as a function of real params [K, 8N].

    Gaussian: sum w^2 r^2; robust: sum log(1 + (w r)^2 / nu)
    (func_robust, robust_lbfgs.c:94). ADMM adds
    2 y^T(p - bz) + rho ||p - bz||^2 per chunk (rtr_solve_robust_admm.c
    augmented Lagrangian, in the un-halved cost convention of lm.py).
    """
    if admm is not None:
        admm_y, admm_bz, admm_rho = admm
        admm_y = admm_y.reshape(kmax, -1)
        admm_bz = admm_bz.reshape(kmax, -1)
    p_to_J = _mode_p2j(mode, Jref, kmax, n_stations)

    def cost(p):
        J = p_to_J(p)
        # the residual stream stays in the data's storage dtype; the
        # norm/robust reductions upcast (identity for f32/f64)
        e = dtp.acc(ne.residual8(x8, J, coh, sta1, sta2, chunk_id) * wt)
        if robust_nu is None:
            per_row = jnp.sum(e * e, axis=-1)
        else:
            per_row = jnp.sum(jnp.log1p(e * e / robust_nu), axis=-1)
        ck = jax.ops.segment_sum(per_row, chunk_id, num_segments=kmax)
        if admm is not None:
            d = p - admm_bz
            ck = ck + 2.0 * jnp.sum(admm_y * d, axis=-1) \
                + admm_rho * jnp.sum(d * d, axis=-1)
        return ck

    return cost


def make_row_pass(rows: ne.RowPlanes, kmax, n_stations, admm=None,
                  robust_nu=None, mode: str = "full", Jref=None):
    """:func:`make_cost`'s cost with its gradient written out, on row data
    in plane form, so that ONE evaluation of the row model at a point
    serves both. Returns (row_pass, egrad):

    ``row_pass(p)`` -> (cost [K], e, shares): ``e`` the weighted residual
    planes at ``p`` (storage dtype), ``shares`` the rows' shares of the
    cost's gradient with respect to the Jones of their first and second
    station, elementwise on the planes the pass holds (with G the complex
    form of wt * f'(e), f' = 2e Gaussian, 2e/(nu + e^2) robust:
    dc/dJ_p = -(G A^H), dc/dJ_q = -(G^H Bm)) and already summed over
    time where the rows have a period;

    ``egrad(p, shares)`` -> the Euclidean gradient [K, D] at ``p``: the
    segment sum of the shares to the stations, the transpose of the
    station-sized p -> J map for the constrained modes, the ADMM term
    2 y + 2 rho (p - bz)."""
    station_planes = _mode_p2planes(mode, Jref, kmax, n_stations)
    if admm is not None:
        admm_y, admm_bz, admm_rho = admm
        admm_y = admm_y.reshape(kmax, -1)
        admm_bz = admm_bz.reshape(kmax, -1)

    def row_pass(p):
        jp, jq = rows.gather(station_planes(p))
        v, a, bm = ne.row_model(jp, jq, rows.c)
        # the residual stream stays in the data's storage dtype; the
        # norm/robust reductions and the gradient upcast
        e = (rows.x - dtp.to_storage(v, rows.x.dtype)) * rows.w
        ea = dtp.acc(e)
        if robust_nu is None:
            per, fp = ea * ea, 2.0 * ea
        else:
            per = jnp.log1p(ea * ea / robust_nu)
            fp = 2.0 * ea / (robust_nu + ea * ea)
        ck = rows.chunk_sum(per)
        if admm is not None:
            d = p - admm_bz
            ck = ck + 2.0 * jnp.sum(admm_y * d, axis=-1) \
                + admm_rho * jnp.sum(d * d, axis=-1)
        gp, gq = ne.row_grad(dtp.acc(rows.w) * fp, a, bm)
        return ck, e, (rows.time_sum(gp), rows.time_sum(gq))

    def egrad(p, shares):
        gJ = -rows.station_sum(*shares)
        if mode == "full":
            eg = gJ.reshape(kmax, -1)
        else:
            eg, = jax.vjp(station_planes, p)[1](gJ)
        if admm is not None:
            eg = eg + 2.0 * admm_y \
                + 2.0 * jnp.asarray(admm_rho)[..., None] * (p - admm_bz)
        return eg

    return row_pass, egrad


class _TCGState(NamedTuple):
    eta: jax.Array      # [K, D] current inner solution
    r: jax.Array        # [K, D] residual
    d: jax.Array        # [K, D] search direction
    r_r: jax.Array      # [K]
    e_e: jax.Array      # [K] ||eta||^2
    mdot: jax.Array     # [K] model decrease accumulated
    done: jax.Array     # [K] bool


def _tcg(hess_fn, rgrad, delta, cfg: RTRConfig):
    """Batched Steihaug-Toint truncated CG (rtr_solve.c:886-1155).

    hess_fn: [K, D] -> [K, D] (projected, preconditioned Hessian-vector).
    The loop ends when every chunk is ``done`` (boundary hit, negative
    curvature or residual target) or at ``cfg.tcg_iters`` bodies; the
    body freezes a ``done`` chunk, so the state on exit is the one a
    fixed ``tcg_iters`` trips end with.
    Returns (eta [K, D], model_decrease [K], bodies executed i32).
    """
    r0n = jnp.sqrt(_dot(rgrad, rgrad))
    target = r0n * jnp.minimum(cfg.kappa, r0n ** cfg.theta)

    def body(carry):
        i, s = carry
        Hd = hess_fn(s.d)
        d_Hd = _dot(s.d, Hd)
        alpha = s.r_r / jnp.where(d_Hd != 0, d_Hd, 1.0)
        e_d = _dot(s.eta, s.d)
        d_d = _dot(s.d, s.d)
        # boundary crossing: ||eta + tau d|| = delta
        disc = jnp.maximum(e_d * e_d + d_d * (delta * delta - s.e_e), 0.0)
        tau = (-e_d + jnp.sqrt(disc)) / jnp.maximum(d_d, 1e-30)
        hit = (d_Hd <= 0) | (s.e_e + 2 * alpha * e_d
                             + alpha * alpha * d_d >= delta * delta)
        step = jnp.where(hit, tau, alpha)
        eta_new = s.eta + step[:, None] * s.d
        # model decrease of this move: -<r, step d> - 0.5 step^2 <d, Hd>
        # (r is the model gradient at eta)
        dm = -step * _dot(s.r, s.d) - 0.5 * step * step * d_Hd
        r_new = s.r + step[:, None] * Hd
        rr_new = _dot(r_new, r_new)
        beta = rr_new / jnp.maximum(s.r_r, 1e-30)
        d_new = -r_new + beta[:, None] * s.d
        done_new = s.done | hit | (jnp.sqrt(rr_new) <= target)
        upd = ~s.done
        return i + 1, _TCGState(
            eta=jnp.where(upd[:, None], eta_new, s.eta),
            r=jnp.where(upd[:, None], r_new, s.r),
            d=jnp.where(upd[:, None], d_new, s.d),
            r_r=jnp.where(upd, rr_new, s.r_r),
            e_e=jnp.where(upd, _dot(eta_new, eta_new), s.e_e),
            mdot=jnp.where(upd, s.mdot + dm, s.mdot),
            done=done_new)

    K, D = rgrad.shape
    init = _TCGState(eta=jnp.zeros_like(rgrad), r=rgrad, d=-rgrad,
                     r_r=r0n * r0n, e_e=jnp.zeros((K,), rgrad.dtype),
                     mdot=jnp.zeros((K,), rgrad.dtype),
                     done=r0n <= 1e-30)
    trips, out = jax.lax.while_loop(
        lambda c: (c[0] < cfg.tcg_iters) & jnp.any(~c[1].done),
        body, (jnp.zeros((), jnp.int32), init))
    return out.eta, out.mdot, trips


class _RTRState(NamedTuple):
    p: jax.Array
    g: jax.Array        # Riemannian gradient at p (computed once per point)
    cost: jax.Array
    e: jax.Array        # weighted residual planes at p (storage dtype)
    delta: jax.Array
    stop: jax.Array
    k: jax.Array
    cg: jax.Array       # i32 tCG bodies executed so far


def _rtr_rows(rows: ne.RowPlanes, J0, n_stations: int, chunk_mask,
              config: RTRConfig, itmax_dynamic, admm, robust_nu,
              row_period: int):
    """:func:`rtr_solve` on row data already in plane form (``rows``
    holds the storage-quantized data and sqrt-weights, which
    ``make_hess`` hands to the assembly as they are). Returns (J, info,
    e): ``e`` the weighted residual planes at the returned J, which the
    one row pass that reached it left behind."""
    kmax = J0.shape[0]
    dtype = dtp.acc_dtype(rows.x.dtype)
    mode = config.jones_mode
    npar = ne.jones_npar(mode)
    D = n_stations * npar
    if mode == "full":
        Jref = None
        p0 = ne.jones_c2r(J0).reshape(kmax, -1).astype(dtype)
    else:
        if admm is not None:
            raise ValueError(
                "consensus ADMM requires jones_mode='full': the y/bz "
                "vectors are full-Jones parameters")
        Jref = ne.jones_constrain(J0, mode)
        p0 = ne.params_from_jones(Jref, mode).reshape(
            kmax, -1).astype(dtype)
    p_to_J = _mode_p2j(mode, Jref, kmax, n_stations)
    if chunk_mask is None:
        chunk_mask = jnp.ones((kmax,), bool)
    row_pass, egrad = make_row_pass(rows, kmax, n_stations, admm=admm,
                                    robust_nu=robust_nu, mode=mode,
                                    Jref=Jref)

    # NOTE: the reference's per-station iw scaling (fns_fcount) is a
    # diagonal preconditioner; applied one-sidedly it would destroy the
    # symmetry tCG requires, so the TR path uses the exact (projected)
    # gradient/Hessian pair instead — station balance enters through the
    # row weights ``wt``.
    def rgrad(p, shares):
        return project_tangent_mode(p, egrad(p, shares), kmax, n_stations,
                                    mode)

    admm_rho2 = None if admm is None else 2.0 * admm[2]
    station_planes = _mode_p2planes(mode, Jref, kmax, n_stations)

    def dense_hv(p, JTJ):
        """v -> the projected (2 JTJ [+ 2 rho I]) v at the point ``p``."""
        def hv(v):
            Hv = 2.0 * jnp.einsum("kij,kj->ki", JTJ, v)
            if admm_rho2 is not None:
                Hv = Hv + admm_rho2 * v
            return project_tangent_mode(p, Hv, kmax, n_stations, mode)
        return hv

    # the one assembly of a full-Jones f32/f64 solve on rows with a
    # period: straight from the planes the solve already holds
    planes_hess = (config.inner == "chol"
                   and mode == "full" and rows.periodic
                   and not dtp.is_reduced(rows.x.dtype))
    if not planes_hess:
        # the other assemblies take rows: laid out once a solve
        x8, coh, wt = rows.flat()
        sta1, sta2, chunk_id = rows.sta1, rows.sta2, rows.chunk_id

    def make_hess(p, e):
        """Gauss-Newton Hessian operator at the outer TR point ``p``,
        ``e`` the weighted residual planes the point's row pass left.

        The reference evaluates a cheap hand-derived Hessian inside tCG
        (rtr_solve.c:886-1155); the autodiff analogue (forward-over-
        reverse through the gradient) re-traverses the whole residual
        graph for EVERY tCG product. Here the block-sparse Gauss-Newton
        normal matrix is assembled ONCE per outer iteration from the
        analytic Wirtinger factors and each tCG product is a single
        batched [K,8N,8N]@[K,8N] matvec on the matrix unit. On rows
        with a period (``[tilesz, nbase]``, any chunk count: every
        cluster solve of a calibration with ``nbase`` set) the assembly
        is normal_eq.plane_equations on the planes this solve holds
        (``rows``, the point's station planes, the curvature weights as
        planes): its own evaluation of the row model's Wirtinger
        factors, elementwise, and a sum over each chunk's timeslots, of
        which XLA keeps what JTJ needs. Rows without a period,
        ``inner="cg"`` and the constrained modes take the ``[B, 8]``
        assemblies of normal_eq.

        Curvature model per residual element e (e already includes wt):
          gaussian  sum e^2:          f'' = 2          -> weights wt
          robust    sum log1p(e^2/nu): f''(e) = 2(nu - e^2)/(nu + e^2)^2,
            approximated by its PSD surrogate 2*nu/(nu + e^2)^2, folded
            in as sqrt-curvature row weights wt*sqrt(nu)/(nu + e^2),
            read from the carried ``e``: no pass of their own.
        The ADMM augmentation contributes its exact Hessian 2*rho*I.
        """
        if robust_nu is None:
            w8 = rows.w
        else:
            # the curvature weights stay in the storage dtype, so that
            # a reduced policy's assembly stays on its reduced path
            # (identity for f32/f64)
            w8 = dtp.to_storage(
                rows.w * jnp.sqrt(robust_nu) / (robust_nu + e * e),
                rows.w.dtype)
        if planes_hess:
            JTJ, _, _ = ne.plane_equations(rows, station_planes(p), w8)
            return dense_hv(p, JTJ)
        Jm = p_to_J(p)
        wt_eff = wt if robust_nu is None else rows.to_rows(w8)
        if config.inner == "cg":
            # matrix-free operator: JTJ @ v straight from the Wirtinger
            # factors (one [B]-pass per product), never forming the
            # [K, 8N, 8N] matrix; the unused JTe/cost outputs are
            # dead-code-eliminated by XLA
            if mode == "full":
                fac, _, _ = ne.gn_factors(x8, Jm, coh, sta1, sta2,
                                          chunk_id, wt_eff, n_stations,
                                          kmax, row_period=row_period)

                def hv(v):
                    Hv = 2.0 * ne.gn_matvec(fac, v, sta1, sta2,
                                            chunk_id, kmax, n_stations,
                                            row_period=row_period)
                    if admm_rho2 is not None:
                        Hv = Hv + admm_rho2 * v
                    return project_tangent(p, Hv, kmax, n_stations)
                return hv
            fac, _, _ = ne.gn_factors_mode(x8, Jm, coh, sta1, sta2,
                                           chunk_id, wt_eff, n_stations,
                                           kmax, mode=mode)

            def hv(v):
                Hv = 2.0 * ne.gn_matvec_mode(fac, v, sta1, sta2,
                                             chunk_id, kmax, n_stations)
                return project_tangent_mode(p, Hv, kmax, n_stations,
                                            mode)
            return hv
        if mode == "full":
            JTJ, _, _ = ne.normal_equations(
                x8, Jm, coh, sta1, sta2, chunk_id, wt_eff, n_stations,
                kmax, row_period=row_period)
        else:
            JTJ, _, _ = ne.normal_equations_mode(
                x8, Jm, coh, sta1, sta2, chunk_id, wt_eff, n_stations,
                kmax, mode, row_period=row_period)

        return dense_hv(p, JTJ)

    cost0, e0, shares0 = row_pass(p0)
    xnorm0 = jnp.sqrt(_dot(p0, p0))
    if mode == "phase":
        # phase parameters start at theta = 0, so ||p0|| cannot seed
        # the TR radius — use the unit-phase scale sqrt(D) instead
        xnorm0 = jnp.maximum(xnorm0,
                             jnp.sqrt(jnp.asarray(float(D), dtype)))
    delta_bar = config.delta_bar_frac * xnorm0
    delta0 = config.delta0_frac * xnorm0
    g0 = rgrad(p0, shares0)
    g0n = jnp.sqrt(_dot(g0, g0))

    itmax = (jnp.minimum(jnp.asarray(itmax_dynamic, jnp.int32), config.itmax)
             if itmax_dynamic is not None else config.itmax)

    def cond(s: _RTRState):
        return (s.k < itmax) & jnp.any(~s.stop & chunk_mask)

    def body(s: _RTRState):
        hess = make_hess(s.p, s.e)
        eta, md, trips = _tcg(hess, s.g, s.delta, config)
        p_new = s.p + eta
        # the iteration's ONE row pass: the trial point's cost now, its
        # residual and gradient if the point is accepted
        c_new, e_new, shares = row_pass(p_new)
        rho = (s.cost - c_new + config.rho_regularize) \
            / (md + config.rho_regularize)
        good = (md > 0) & jnp.all(jnp.isfinite(p_new), axis=-1)
        accept = good & (rho > config.rho_accept) & ~s.stop & chunk_mask
        en = jnp.sqrt(_dot(eta, eta))
        shrink = (rho < 0.25) | ~good
        grow = (rho > 0.75) & (en >= 0.99 * s.delta)
        delta = jnp.where(shrink, 0.25 * s.delta,
                          jnp.where(grow, jnp.minimum(2.0 * s.delta,
                                                      delta_bar), s.delta))
        p = jnp.where(accept[:, None], p_new, s.p)
        cost = jnp.where(accept, c_new, s.cost)
        # chunks are independent: an accepted chunk's gradient is the
        # trial point's, a rejected one keeps its own
        g_next = jax.lax.cond(
            jnp.any(accept),
            lambda: jnp.where(accept[:, None], rgrad(p_new, shares), s.g),
            lambda: s.g)
        gn = jnp.sqrt(_dot(g_next, g_next))
        # budget exhaustion joins the stop mask (vmap-exactness: see
        # lm.py body note — a finished tile must freeze while other
        # batch elements keep iterating)
        stop = s.stop | (gn <= config.eps_grad * jnp.maximum(g0n, 1e-30)) \
            | (delta <= 1e-12 * jnp.maximum(xnorm0, 1e-30)) \
            | (s.k + 1 >= itmax)
        return _RTRState(p=p, g=g_next, cost=cost,
                         e=rows.select(accept, e_new, s.e), delta=delta,
                         stop=stop, k=s.k + 1, cg=s.cg + trips)

    init = _RTRState(p=p0, g=g0, cost=cost0, e=e0, delta=delta0,
                     stop=jnp.zeros((kmax,), bool),
                     k=jnp.zeros((), jnp.int32),
                     cg=jnp.zeros((), jnp.int32))
    final = jax.lax.while_loop(cond, body, init)
    J = p_to_J(final.p)
    J = jnp.where(chunk_mask[:, None, None, None], J,
                  J0 if mode == "full" else Jref)
    # one row pass at p0 and one per trial point
    return J, {"init_cost": cost0, "final_cost": final.cost,
               "iters": final.k, "cg_iters": final.cg,
               "row_passes": final.k + 1}, final.e


def _storage_rows(x8, coh, sta1, sta2, chunk_id, wt, J0, n_stations,
                  config: RTRConfig, row_period: int):
    """dtype policy: storage-quantize the data at entry (identity under
    "f32"; manifold point/tangents/costs live in the accumulator dtype,
    see lm.lm_solve) and bring the row data to plane form, ONCE for all
    the evaluations of a solve."""
    stq = dtp.storage_dtype(config.dtype_policy, x8.dtype)
    return ne.RowPlanes(dtp.to_storage(x8, stq), coh,
                        dtp.to_storage(wt, stq), sta1, sta2, chunk_id,
                        J0.shape[0], n_stations, row_period)


def rtr_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, n_stations: int,
              chunk_mask=None, config: RTRConfig = RTRConfig(),
              itmax_dynamic=None, admm=None, robust_nu=None,
              row_period: int = 0):
    """Trust-region solve of all chunks of one cluster (rtr_solve.c:1208).

    Same call convention as lm.lm_solve; ``robust_nu`` switches the
    objective to fixed-nu Student's t (the robust wrapper re-estimates nu
    between calls). The row model is evaluated ONCE per point: at the
    start, and at each trial point, whose pass yields the cost, and on
    acceptance the gradient and the residual the next iteration's
    curvature weights read. Returns (J [K,N,2,2], info);
    ``info["cg_iters"]`` is the tCG bodies executed, summed over the
    outer iterations, ``info["row_passes"]`` the evaluations of the row
    model (1 + iters; the assembly's own not counted),
    ``info["residual"]`` [B, 8] the weighted residual at the returned J.
    """
    rows = _storage_rows(x8, coh, sta1, sta2, chunk_id, wt, J0,
                         n_stations, config, row_period)
    J, info, e = _rtr_rows(rows, J0, n_stations, chunk_mask, config,
                           itmax_dynamic, admm, robust_nu, row_period)
    return J, {**info, "residual": rows.to_rows(e)}


def rtr_rows(rows: ne.RowPlanes, J0, n_stations: int, chunk_mask=None,
             config: RTRConfig = RTRConfig(), itmax_dynamic=None,
             admm=None, row_period: int = 0):
    """:func:`rtr_solve` for a caller that holds one cluster's row data
    on planes already, storage-quantized (the sweep of
    ``solvers/sage.py``: ``rows.x`` its running residual with the
    cluster's model added, ``rows.w`` the sqrt-weights). Returns
    (J, info) without ``info["residual"]``."""
    J, info, _ = _rtr_rows(rows, J0, n_stations, chunk_mask, config,
                           itmax_dynamic, admm, None, row_period)
    return J, info


def rtr_solve_robust(x8, coh, sta1, sta2, chunk_id, wt_base, J0,
                     n_stations: int, nu0=2.0, nulow=2.0, nuhigh=30.0,
                     chunk_mask=None, config: RTRConfig = RTRConfig(),
                     wt_rounds: int = 2, itmax_dynamic=None, admm=None,
                     row_period: int = 0):
    """Student's-t robust RTR (rtr_solve_nocuda_robust,
    rtr_solve_robust.c:1441; ADMM variant rtr_solve_robust_admm.c:1425):
    IRLS rounds of {fixed-nu robust RTR -> weight E-step -> nu grid update}.
    The row data go to plane form once for all rounds, and a round's
    E-step reads the residual its solve ended on.

    Returns (J, nu, info)."""
    rows = _storage_rows(x8, coh, sta1, sta2, chunk_id, wt_base, J0,
                         n_stations, config, row_period)
    return rtr_rows_robust(rows, J0, n_stations, nu0, nulow, nuhigh,
                           chunk_mask, config, wt_rounds, itmax_dynamic,
                           admm, row_period)


def rtr_rows_robust(rows: ne.RowPlanes, J0, n_stations: int, nu0=2.0,
                    nulow=2.0, nuhigh=30.0, chunk_mask=None,
                    config: RTRConfig = RTRConfig(), wt_rounds: int = 2,
                    itmax_dynamic=None, admm=None, row_period: int = 0):
    """:func:`rtr_solve_robust` on one cluster's row data already on
    planes (see :func:`rtr_rows`)."""
    mask = rows.w > 0

    def round_body(carry, _):
        J, nu = carry
        Jn, info, e = _rtr_rows(rows, J, n_stations, chunk_mask, config,
                                itmax_dynamic, admm, nu, row_period)
        w = rb.update_weights(e, nu)
        # AECM nu update with p=2, matching the robust-RTR family
        # (rtr_solve_robust.c:374, rtr_solve_robust_admm.c:394 call
        # update_nu with p=2; the LM family uses the ML grid instead)
        nu_new = rb.update_nu_aecm(rb.mean_logsumw(w, mask), nu, p=2,
                                   nulow=nulow, nuhigh=nuhigh)
        return (Jn, nu_new), (info["init_cost"], info["final_cost"],
                              info["iters"], info["cg_iters"],
                              info["row_passes"])

    (J, nu), costs = jax.lax.scan(
        round_body,
        (J0, jnp.asarray(nu0, dtp.acc_dtype(rows.x.dtype))), None,
        length=wt_rounds)
    # "iters": executed outer TR iterations summed over IRLS rounds
    # (the tile record's solver_iters); "cg_iters": their tCG bodies;
    # "row_passes": their evaluations of the row model (rounds + iters)
    info = {"init_cost": costs[0][0], "final_cost": costs[1][-1],
            "iters": jnp.sum(costs[2]).astype(jnp.int32),
            "cg_iters": jnp.sum(costs[3]).astype(jnp.int32),
            "row_passes": jnp.sum(costs[4]).astype(jnp.int32)}
    return J, nu, info


def nsd_solve_robust(x8, coh, sta1, sta2, chunk_id, wt_base, J0,
                     n_stations: int, nu0=2.0, nulow=2.0, nuhigh=30.0,
                     chunk_mask=None, config: NSDConfig = NSDConfig(),
                     itmax_dynamic=None, admm=None):
    """Nesterov accelerated steepest descent with Student's-t cost
    (nsd_solve_nocuda_robust, rtr_solve_robust.c:1878; ADMM variant
    Dirac.h:1260-1314): momentum sequence t_{k+1} = (1+sqrt(1+4t_k^2))/2
    with per-chunk backtracking line search on the projected gradient.

    Returns (J, nu, info)."""
    kmax = J0.shape[0]
    dtype = dtp.acc_dtype(x8.dtype)
    mode = config.jones_mode
    npar = ne.jones_npar(mode)
    if mode == "full":
        Jref = None
        p0 = ne.jones_c2r(J0).reshape(kmax, -1).astype(dtype)
    else:
        if admm is not None:
            raise ValueError(
                "consensus ADMM requires jones_mode='full': the y/bz "
                "vectors are full-Jones parameters")
        Jref = ne.jones_constrain(J0, mode)
        p0 = ne.params_from_jones(Jref, mode).reshape(
            kmax, -1).astype(dtype)
    p_to_J = _mode_p2j(mode, Jref, kmax, n_stations)
    if chunk_mask is None:
        chunk_mask = jnp.ones((kmax,), bool)
    nu = jnp.asarray(nu0, dtype)

    cost_of = lambda nu_: make_cost(x8, coh, sta1, sta2, chunk_id, wt_base,
                                    kmax, n_stations, admm=admm,
                                    robust_nu=nu_, mode=mode, Jref=Jref)
    iw = station_precond(wt_base, sta1, sta2, chunk_id, kmax, n_stations,
                         npar=npar)
    mask = wt_base > 0

    itmax = (jnp.minimum(jnp.asarray(itmax_dynamic, jnp.int32),
                         config.itmax)
             if itmax_dynamic is not None else config.itmax)

    def rgrad(p, nu_):
        g = jax.grad(lambda q: jnp.sum(cost_of(nu_)(q)))(p)
        return project_tangent_mode(p, g * iw, kmax, n_stations, mode)

    def step(carry, k):
        p, p_prev, t, nu_ = carry
        cfn = cost_of(nu_)
        tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y = p + ((t - 1.0) / tn) * (p - p_prev)
        g = rgrad(y, nu_)
        gn = jnp.sqrt(_dot(g, g))
        c_y = cfn(y)
        ynorm = jnp.sqrt(_dot(y, y))
        if mode == "phase":
            # theta starts at 0: seed the step length from the
            # unit-phase scale instead of the (zero) point norm
            ynorm = jnp.maximum(
                ynorm, jnp.sqrt(jnp.asarray(float(npar * n_stations),
                                            dtype)))
        alpha0 = config.alpha0 * ynorm / jnp.maximum(gn, 1e-30)

        def ls_body(_, st):
            alpha, best_p, best_c, found = st
            cand = y - alpha[:, None] * g
            c_c = cfn(cand)
            better = (c_c < best_c) & ~found
            return (alpha * 0.5,
                    jnp.where(better[:, None], cand, best_p),
                    jnp.where(better, c_c, best_c),
                    found | better)

        _, p_new, c_new, found = jax.lax.fori_loop(
            0, config.ls_tries, ls_body,
            (alpha0, y, c_y, jnp.zeros((kmax,), bool)))
        # restart momentum for chunks where the line search failed
        p_new = jnp.where((found & chunk_mask)[:, None], p_new, p)
        # nu E-step every step (inner nu/weight updates,
        # rtr_solve_robust.c:1640-1700; AECM p=2 like the TR variant)
        e = ne.residual8(x8, p_to_J(p_new), coh, sta1, sta2,
                         chunk_id) * wt_base
        w = rb.update_weights(e, nu_)
        nu_new = rb.update_nu_aecm(rb.mean_logsumw(w, mask), nu_, p=2,
                                   nulow=nulow, nuhigh=nuhigh)
        live = k < itmax
        out = (jnp.where(live, p_new, p),
               jnp.where(live, p, p_prev),
               jnp.where(live, tn, t),
               jnp.where(live, nu_new, nu_))
        return out, cfn(out[0])

    cost0 = cost_of(nu)(p0)
    (p, _, _, nu), costs = jax.lax.scan(
        step, (p0, p0, jnp.ones((), dtype), nu),
        jnp.arange(config.itmax))
    J = p_to_J(p)
    J = jnp.where(chunk_mask[:, None, None, None], J,
                  J0 if mode == "full" else Jref)
    # the scan body executes all config.itmax steps (budget exhaustion
    # only freezes the carry), so the executed trip count is static
    return J, nu, {"init_cost": cost0, "final_cost": costs[-1],
                   "iters": jnp.asarray(config.itmax, jnp.int32)}
