"""Batched Levenberg-Marquardt on per-(cluster, time-chunk) Jones blocks.

Capability parity with reference ``clevmar_der_single_nocuda``
(clmfit.c:29, a levmar clone) and its ordered-subsets variant
(clmfit.c:1074), re-architected: every hybrid time chunk of a cluster is an
independent 8N-parameter problem, so ALL chunks solve simultaneously as one
batched damped Gauss-Newton iteration under ``lax.while_loop`` — the
reference's sequential per-chunk loop (lmfit.c:897-967) becomes a batch
axis. The damped normal system is solved by one of two flag-selectable
inner solvers (``LMConfig.inner``):

- ``"chol"`` (default): normal equations assembled densely (normal_eq.py)
  and the 8N x 8N systems solved with batched Cholesky, mirroring
  linsolv=0; a failed factorization gets ONE jittered retry (the QR/SVD
  fallbacks of the reference collapse to this — see _solve_damped), and
  chunks that still fail return dp = 0 and recover through mu-growth.
- ``"cg"``: matrix-free preconditioned CG — the [K, 8N, 8N] matrix is
  never formed; each matvec is one [B]-pass over the Wirtinger factors
  (normal_eq.gn_matvec) under the station-block preconditioner
  (gn_precond_factor), stopped at the inexact-Newton forcing tolerance
  ||r|| <= cg_tol * ||JTe|| with per-chunk early-stop masking. Executed
  CG trips are counted (info["cg_iters"]) and reach the tile record
  (benchmarks/ reads them as ``tcg_trips``).

Damping schedule = classic levmar (as cloned by clmfit.c):
  mu0 = tau * max(diag(JTJ)); accept if gain rho > 0 with
  mu *= max(1/3, 1-(2 rho-1)^3); reject -> mu *= nu, nu *= 2.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.solvers import normal_eq as ne

#: executed-iteration counters a solver info dict may carry; the keys
#: the host-side telemetry (diag tile records, obs trip counters,
#: benchmarks/' solver_trips) reads through executed_trips()
TRIP_KEYS = ("solver_iters", "cg_iters", "row_passes", "lbfgs_iters",
             "refine_passes", "rejected_groups", "solve_dispatches")


def executed_trips(info) -> dict:
    """Host-side executed-trip totals from a solver ``info`` dict.

    Sums each :data:`TRIP_KEYS` entry present (device arrays fetch
    here — callers gate on ``dtrace.active() or obs.active()``, so the
    telemetry-off path never pays the sync). One definition shared by
    the tile-record emitter and the obs counters, so "trips" can never
    mean two different things in two readouts."""
    out = {}
    if not isinstance(info, dict):
        return out
    for k in TRIP_KEYS:
        if k in info:
            out[k] = int(np.asarray(info[k]).sum())
    return out


class LMConfig(NamedTuple):
    itmax: int = 10
    tau: float = 1e-3          # CLM_INIT_MU (Dirac_common.h:44)
    eps1: float = 1e-15        # ||JTe||_inf stop
    eps2: float = 1e-15        # ||dp||/||p|| stop
    eps3: float = 1e-15        # ||e||^2 stop
    jitter: float = 1e-9       # Cholesky regularization floor
    # inner linear solver for (JTJ + mu I) dp = JTe: "chol" = dense
    # assembly + batched Cholesky (bit-reference path); "cg" =
    # matrix-free preconditioned CG (inexact Newton — same accepted
    # trajectory within the forcing tolerance, NOT bit-identical; see
    # MIGRATION.md "Inner linear solver")
    inner: str = "chol"
    cg_tol: float = 0.1        # forcing eta: stop at ||r|| <= eta ||JTe||
    cg_maxiter: int = 25       # static PCG trip cap per damping iteration
    # storage dtype policy (sagecal_tpu.dtypes): "f32" is the identity
    # (bit-frozen default); "bf16"/"f16" quantize the [B]-data and
    # Wirtinger-factor storage while every accumulator stays f32 —
    # trajectory gated by tolerance, not bit parity (MIGRATION.md
    # "Dtype policy")
    dtype_policy: str = "f32"
    # constrained-Jones parameterization (normal_eq.JONES_MODES):
    # "full" (bit-frozen default, 8 reals/station), "diag" (4 —
    # diagonal complex gains), "phase" (2 — phase-only, amplitudes
    # frozen at the entry Jones; retraction J0 * exp(i theta)). The
    # solve runs entirely in the reduced parameter space — reduced
    # Gram blocks, reduced damped solves (MIGRATION.md "Jones modes")
    jones_mode: str = "full"


class LMState(NamedTuple):
    p: jax.Array        # [K, 8N] real parameters
    JTJ: jax.Array      # inner="chol": [K, 8N, 8N] normal matrix at p;
                        # inner="cg": normal_eq.GNFactors (matrix-free op)
    JTe: jax.Array      # [K, 8N] gradient at p
    mu: jax.Array       # [K]
    nu: jax.Array       # [K]
    cost: jax.Array     # [K] current weighted cost
    stop: jax.Array     # [K] bool
    live: jax.Array     # [K] bool: carried JTJ/JTe built from >=1 usable
                        # row of this chunk (always True outside OS)
    k: jax.Array        # iteration counter
    cg: jax.Array       # executed PCG trips (0 under inner="chol")


class OSConfig(NamedTuple):
    """Ordered-subsets acceleration (clmfit.c:1074 oslevmar semantics):
    each LM iteration builds the normal equations from ONE contiguous
    time-tile subset; acceptance still tests the FULL-data cost
    (clmfit.c:1404 computes pDp_eL2 over all N rows).

    Rejected-step semantics now match the reference: a rejected chunk
    keeps the SAME subset's normal equations with increased damping
    (clmfit.c:1449 inner while loop) — it simply holds on to the
    entering JTJ/JTe instead of re-evaluating them. Accepted chunks
    advance to the next subset at the new point. (Rounds <= PR 1 had a
    documented deviation here: rejection advanced the subset too.)
    A carried subset with NO usable rows of a chunk (fully flagged, or a
    time block outside the chunk) is never retried and its zero gradient
    never reads as convergence — see the ``live`` carry in lm_solve."""

    os_id: jax.Array       # [B] subset id per data row (os_subset_ids)
    n_subsets: int         # static subset count (<= 10, reference default)
    key: jax.Array         # PRNG key for subset randomization
    randomize: bool = True  # False -> deterministic (k % n_subsets) rotation


def os_subset_ids(tilesz: int, nbase: int, n_subsets: int = 10):
    """[tilesz*nbase] contiguous-time subset ids + actual subset count.

    Mirrors the reference partition (clmfit.c:1311-1358): Nsubsets =
    min(10, tilesz) contiguous blocks of ceil(tilesz/Nsubsets) timeslots;
    the tail block is short. Rows are ordered [tilesz, nbase].
    """
    import numpy as np
    ns = min(n_subsets, tilesz)
    ntper = -(-tilesz // ns)              # ceil
    tslot = np.arange(tilesz * nbase) // nbase
    os_id = (tslot // ntper).astype(np.int32)
    return os_id, int(os_id.max()) + 1


def _chol_solve_shift(JTJ, JTe, shift):
    """ONE batched shifted-Cholesky attempt: solve (JTJ + shift I) dp =
    JTe over chunks; returns dp, ok (dp all-finite per chunk — the f32
    analogue of LAPACK potrf info). This is the executed all-ok body of
    :func:`_solve_damped`; a test that prices a trip lowers THIS function
    rather than ``_solve_damped`` because XLA cost analysis sums BOTH
    branches of a lax.cond — pricing the wrapper would charge every
    damping trip for a jitter-retry factorization the common case never
    executes."""
    k8n = JTJ.shape[-1]
    eye = jnp.eye(k8n, dtype=JTJ.dtype)[None]
    A = JTJ + shift[:, None, None] * eye
    L, lower = jax.scipy.linalg.cho_factor(A, lower=True)
    dp = jax.scipy.linalg.cho_solve((L, lower), JTe[..., None])[..., 0]
    return dp, jnp.all(jnp.isfinite(dp), axis=-1)


def _lu_solve_shift(JTJ, JTe, shift):
    """Reduced-policy analogue of :func:`_chol_solve_shift`: solve the
    damped system with one batched LU instead of Cholesky. On the CPU
    cost model a getrf+getrs pair prices ~8 MB/trip below
    cho_factor+cho_solve at the config-1 shape (the triangular-solve
    custom calls are charged ~8 operand passes each), and the damped
    matrix is PD by construction (Gram + positive shift) so partial
    pivoting is as stable as the Cholesky here. Only the reduced
    (bf16/f16) storage policy takes this body — its trajectory contract
    is tolerance-based; the f32 path keeps the bit-frozen Cholesky.
    A singular system still yields non-finite dp -> ok=False, so the
    jitter-retry/mu-growth recovery semantics are unchanged."""
    k8n = JTJ.shape[-1]
    eye = jnp.eye(k8n, dtype=JTJ.dtype)[None]
    A = JTJ + shift[:, None, None] * eye
    dp = jnp.linalg.solve(A, JTe[..., None])[..., 0]
    return dp, jnp.all(jnp.isfinite(dp), axis=-1)


def _solve_damped(JTJ, JTe, mu, jitter, reduced: bool = False):
    """Solve (JTJ + mu I) dp = JTe batched over chunks; returns dp, ok.

    A failed factorization (non-finite dp: the f32 analogue of LAPACK
    potrf info > 0) gets ONE jittered retry with the regularization
    floor boosted to 1e-3 * max|diag(JTJ)| per chunk — the QR/SVD
    fallbacks of the reference (linsolv 1/2, clmfit.c) exist exactly
    for these near-singular systems, and a scaled-jitter Cholesky is
    their batched-TPU equivalent. Chunks that still fail return dp = 0
    and recover through mu-growth on rejection. The retry hides behind
    a lax.cond, so the all-ok common case pays nothing; under vmap
    (tile-batch / in-flight groups) the cond lowers to a select and
    both factorizations execute — an accepted cost on those opt-in
    paths (tests/test_krylov.py gates the recovery). ``reduced``
    (static) routes the dtype-policy reduced path through the cheaper
    LU body (:func:`_lu_solve_shift`); the default stays Cholesky."""
    def solve(shift):
        if reduced:
            return _lu_solve_shift(JTJ, JTe, shift)
        return _chol_solve_shift(JTJ, JTe, shift)

    dp, ok = solve(mu + jitter)

    def done():
        return jnp.where(ok[:, None], dp, 0.0), ok

    def retry():
        diag_max = jnp.max(jnp.abs(jnp.diagonal(JTJ, axis1=-2, axis2=-1)),
                           axis=-1)
        dp2, ok2 = solve(mu + jitter + 1e-3 * jnp.maximum(diag_max, 1e-30))
        dpw = jnp.where(ok[:, None], dp,
                        jnp.where(ok2[:, None], dp2, 0.0))
        return dpw, ok | ok2

    return jax.lax.cond(jnp.all(ok), done, retry)


def _solve_damped_cg(fac, JTe, mu, jitter, rho, sta1, sta2, chunk_id,
                     kmax: int, n_stations: int, row_period: int,
                     eta: float, maxiter: int, active=None):
    """Matrix-free preconditioned CG for (JTJ + (mu+jitter) I [+ rho I])
    dp = JTe, batched over chunks; returns (dp, ok, trips).

    The operator applies straight from the Wirtinger factors
    (normal_eq.gn_matvec — one [B]-pass per trip), preconditioned by
    the factored station-diagonal blocks (gn_precond_factor: D + shift,
    batched 4x4 Cholesky). Inexact-Newton forcing: each chunk stops at
    ||r||^2 <= (eta ||JTe||)^2; converged chunks freeze (masked
    updates) while the batch runs to the slowest live chunk, and
    ``trips`` counts the executed loop iterations — the number the
    tile record carries as ``cg_iters``. A
    chunk with JTe == 0 (dead OS subset) starts converged and returns
    dp = 0 exactly, preserving the carried-equation semantics the OS
    body builds on. ``active`` [K] masks chunks out entirely (their rhs
    zeroes, so they start converged) — the LM body passes its live mask
    so already-stopped chunks never drive extra trips under vmap.

    ``fac`` is normal_eq.GNFactors or, in a constrained Jones mode,
    GNFactorsMode: each matvec is one [B]-row pass over the Wirtinger
    factors; the branch is trace-time static."""
    shift = mu + jitter + rho                          # [K], always > 0
    Lfac = ne.gn_precond_factor(fac.D, shift)
    b = JTe if active is None else jnp.where(active[:, None], JTe, 0.0)
    bnorm2 = jnp.sum(b * b, axis=-1)
    tol2 = (eta * eta) * bnorm2
    tiny = jnp.asarray(1e-30, b.dtype)

    if type(fac).__name__ == "GNFactorsMode":
        def matvec(v):
            return ne.gn_matvec_mode(fac, v, sta1, sta2, chunk_id,
                                     kmax, n_stations, shift=shift)
    else:
        def matvec(v):
            return ne.gn_matvec(fac, v, sta1, sta2, chunk_id, kmax,
                                n_stations, shift=shift,
                                row_period=row_period)

    x0 = jnp.zeros_like(b)
    z0 = ne.gn_precond_apply(Lfac, b, kmax, n_stations)
    rz0 = jnp.sum(b * z0, axis=-1)

    def active_of(r):
        return jnp.sum(r * r, axis=-1) > tol2

    def cond(s):
        x, r, p, rz, k = s
        return (k < maxiter) & jnp.any(active_of(r))

    def body(s):
        x, r, p, rz, k = s
        act = active_of(r)
        Ap = matvec(p)
        pAp = jnp.sum(p * Ap, axis=-1)
        alpha = jnp.where(act & (pAp > 0), rz / jnp.maximum(pAp, tiny),
                          0.0)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * Ap
        z = ne.gn_precond_apply(Lfac, r, kmax, n_stations)
        rz_new = jnp.sum(r * z, axis=-1)
        beta = jnp.where(act, rz_new / jnp.maximum(rz, tiny), 0.0)
        p = jnp.where(act[:, None], z + beta[:, None] * p, p)
        rz = jnp.where(act, rz_new, rz)
        return x, r, p, rz, k + 1

    x, r, p, rz, k = jax.lax.while_loop(
        cond, body, (x0, b, z0, rz0, jnp.zeros((), jnp.int32)))
    ok = jnp.all(jnp.isfinite(x), axis=-1)
    return jnp.where(ok[:, None], x, 0.0), ok, k


def lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J0, n_stations: int,
             chunk_mask=None, config: LMConfig = LMConfig(),
             itmax_dynamic=None, admm=None, os: OSConfig | None = None,
             row_period: int = 0):
    """Levenberg-Marquardt solve of all chunks of one cluster.

    Args:
      x8: [B, 8] real data (residual + this cluster's model).
      coh: [B, 2, 2] complex coherencies of this cluster.
      sta1, sta2, chunk_id: [B] int32.
      wt: [B, 8] sqrt-weights (0 = excluded row).
      J0: [K, N, 2, 2] complex initial Jones.
      chunk_mask: [K] bool for live chunks (padded chunk slots frozen).
      itmax_dynamic: optional traced iteration cap <= config.itmax, for the
        SAGE driver's weighted iteration allocation (lmfit.c:859-882).
      admm: optional (y, bz, rho): consensus-ADMM augmentation with
        y, bz [K, 8N] real vectors and scalar rho. The solve objective
        becomes 1/2||w r||^2 + y^T(theta - bz) + rho/2 ||theta - bz||^2
        (the augmented Lagrangian of rtr_solve_robust_admm.c:199-215 /
        robust_batchmode_lbfgs.c Dirac.h:314-338, with the Gauss-Newton
        data term).
      os: optional ordered-subsets acceleration (clmfit.c:1074): each
        iteration's JTJ/JTe come from one random (or rotating) time-tile
        subset while acceptance tests the full cost; a rejected chunk
        retries the SAME subset with increased damping (see OSConfig).
      row_period: the rows' baseline period (nbase) when the caller's
        layout is [tilesz, nbase] — enables normal_eq's baseline-major
        aggregation for single-chunk clusters; 0 = generic path.

    Returns (J [K,N,2,2], info dict with init_cost/final_cost [K]).

    Traffic note: each damping iteration makes exactly ONE pass over the
    visibility rows — the normal equations, the gradient, and the
    acceptance cost all come out of a single model/residual evaluation
    at the trial point (normal_eq's cost_wt sharing), and rejected
    chunks keep their entering JTJ/JTe by a per-chunk select instead of
    a re-evaluation at the old point (same values: the old point's
    equations ARE the entering ones). Rounds <= PR 1 paid a separate
    full-data cost pass plus a conditional rebuild per iteration.
    """
    kmax = J0.shape[0]
    # dtype policy: quantize the [B]-data to the storage dtype at entry
    # (identity under "f32" / pre-quantized inputs); the SOLVE state
    # (p, mu, costs, JTJ/JTe accumulators) always lives in the
    # accumulator dtype — solutions J stay c64
    st = dtp.storage_dtype(config.dtype_policy, x8.dtype)
    x8 = dtp.to_storage(x8, st)
    wt = dtp.to_storage(wt, st)
    reduced = dtp.is_reduced(x8.dtype)
    dtype = dtp.acc_dtype(x8.dtype)
    # constrained-Jones mode (static): the solve state p lives in the
    # reduced parameter space; the full path below is byte-untouched
    mode = config.jones_mode
    npar = ne.jones_npar(mode)
    if mode == "full":
        Jref = None
        p0 = ne.jones_c2r(J0).reshape(kmax, -1).astype(dtype)
    else:
        if admm is not None:
            raise ValueError(
                "consensus ADMM requires jones_mode='full': the y/bz "
                f"vectors are full-Jones parameters (got {mode!r})")
        # amplitude/off-diagonal reference: the constrained entry Jones
        # (phase retracts multiplicatively off it; diag re-encodes it)
        Jref = ne.jones_constrain(J0, mode)
        p0 = ne.params_from_jones(Jref, mode).reshape(
            kmax, -1).astype(dtype)

    def p_to_J(p):
        if mode == "full":
            return ne.jones_r2c(p.reshape(kmax, n_stations, 8))
        return ne.jones_from_params(
            p.reshape(kmax, n_stations, npar), mode, Jref)

    if chunk_mask is None:
        chunk_mask = jnp.ones((kmax,), bool)
    inner_cg = config.inner == "cg"

    rho_aug = 0.0
    if admm is not None:
        admm_y, admm_bz, admm_rho = admm
        admm_y = admm_y.reshape(kmax, -1).astype(dtype)
        admm_bz = admm_bz.reshape(kmax, -1).astype(dtype)
        # the matrix-free path never forms JTJ, so the ADMM rho-term
        # rides the operator shift instead of a dense += rho I
        rho_aug = admm_rho

    def aug_cost(p, cost_data):
        """Add 2*(y^T d + rho/2 ||d||^2), consistent with the un-halved
        data cost convention used for the gain ratio."""
        if admm is None:
            return cost_data
        d = p - admm_bz
        return cost_data + 2.0 * jnp.sum(admm_y * d, axis=-1) \
            + admm_rho * jnp.sum(d * d, axis=-1)

    # reduced-policy OS fast path: the subset's equations assemble from
    # the subset's contiguous rows ONLY (ne.os_subset_equations — exact,
    # zero-weight rows contribute nothing; the bit-frozen f32 path keeps
    # the masked full-[B] pass). Static geometry: ntper timeslots per
    # contiguous subset block.
    os_ntper = 0
    if (reduced and os is not None and kmax == 1 and row_period > 0
            and x8.shape[0] % row_period == 0 and not inner_cg):
        _tilesz = x8.shape[0] // row_period
        os_ntper = -(-_tilesz // int(os.n_subsets))

    def nrm_eq(p, w=None, cw=None, os_subset=None):
        """Normal equations + acceptance cost from ONE row pass: ``w``
        weights JTJ/JTe (subset weights under OS), ``cw`` the cost
        (full-data weights under OS; defaults to ``w``). Under
        inner="cg" the first return is the matrix-free GNFactors
        operator instead of the dense [K, 8N, 8N] matrix. With the
        reduced OS fast path active, ``os_subset`` (traced index)
        routes through the subset-sliced assembly."""
        J = p_to_J(p)
        if os_subset is not None and os_ntper:
            op, JTe, cost = ne.os_subset_equations_mode(
                x8, J, coh, sta1, sta2, wt, os.os_id, os_subset,
                os_ntper, row_period, n_stations, cw, mode=mode)
            if admm is not None:
                d = p - admm_bz
                JTe = JTe - admm_y - admm_rho * d
                op = op + admm_rho * jnp.eye(op.shape[-1], dtype=op.dtype)
                cost = aug_cost(p, cost)
            return op, JTe, cost
        if inner_cg:
            op, JTe, cost = ne.gn_factors_mode(
                x8, J, coh, sta1, sta2, chunk_id,
                wt if w is None else w, n_stations, kmax,
                mode=mode, cost_wt=cw, row_period=row_period)
        else:
            op, JTe, cost = ne.normal_equations_mode(
                x8, J, coh, sta1, sta2, chunk_id,
                wt if w is None else w, n_stations, kmax, mode=mode,
                cost_wt=cw, row_period=row_period)
        if admm is not None:
            d = p - admm_bz
            JTe = JTe - admm_y - admm_rho * d
            if not inner_cg:
                # the matrix-free operator is never formed densely: its
                # ADMM rho-term rides the solve shift
                op = op + admm_rho * jnp.eye(op.shape[-1], dtype=op.dtype)
            cost = aug_cost(p, cost)
        return op, JTe, cost

    if os is not None:
        n_sub = int(os.n_subsets)

        def subset_for(k):
            if os.randomize:
                # fresh uniform subset per iteration: the first entry of
                # the reference's per-iteration random permutation
                # (clmfit.c:1378) is exactly a uniform draw
                return jax.random.randint(jax.random.fold_in(os.key, k),
                                          (), 0, n_sub)
            return jnp.mod(k, n_sub)           # clmfit.c:1388 (k+ositer)%Ns

        def os_wt(l):
            return wt * (os.os_id == l).astype(wt.dtype)[:, None]

        def os_live(w):
            """[K] per-chunk: subset contributes >=1 usable row to chunk
            k. A subset is a contiguous time block, so it can miss a
            hybrid chunk entirely (or be fully flagged) — that chunk's
            equations are identically zero and must not drive the solve."""
            row = jnp.any(w > 0, axis=1).astype(dtype)
            return jnp.zeros((kmax,), dtype).at[chunk_id].max(row) > 0

        l0 = subset_for(jnp.zeros((), jnp.int32))
        wt0 = os_wt(l0)
        JTJ0, JTe0, cost0 = nrm_eq(p0, wt0, cw=wt,
                                   os_subset=l0 if os_ntper else None)
        live0 = os_live(wt0)
    else:
        JTJ0, JTe0, cost0 = nrm_eq(p0)
        live0 = jnp.ones((kmax,), bool)
    if inner_cg:
        # max diag of the (never-formed) dense matrix: the matrix
        # diagonal lives entirely in the station-diagonal blocks D, and
        # the chol path's ADMM += rho I rides the diag as a uniform
        # shift — add rho_aug so mu0 matches the dense seed
        dd = jnp.diagonal(JTJ0.D, axis1=-2, axis2=-1)     # [K, N, 2, 4]
        diag_max = jnp.max(jnp.abs(dd.reshape(kmax, -1)), axis=-1) \
            + rho_aug
    else:
        diag_max = jnp.max(jnp.abs(jnp.diagonal(JTJ0, axis1=-2, axis2=-1)),
                           axis=-1)
    mu0 = config.tau * jnp.maximum(diag_max, 1e-30)

    itmax = (jnp.minimum(jnp.asarray(itmax_dynamic, jnp.int32), config.itmax)
             if itmax_dynamic is not None else config.itmax)

    def cond(s: LMState):
        return (s.k < itmax) & jnp.any(~s.stop & chunk_mask)

    def body(s: LMState):
        if inner_cg:
            dp, ok, trips = _solve_damped_cg(
                s.JTJ, s.JTe, s.mu, config.jitter, rho_aug, sta1, sta2,
                chunk_id, kmax, n_stations, row_period, config.cg_tol,
                config.cg_maxiter, active=~s.stop & chunk_mask)
        else:
            dp, ok = _solve_damped(s.JTJ, s.JTe, s.mu, config.jitter,
                                   reduced=reduced)
            trips = jnp.zeros((), jnp.int32)
        pnew = s.p + dp
        # ONE row pass per iteration: normal equations AND acceptance
        # cost at the trial point (OS: subset equations + full-data
        # cost, sharing the same model/residual evaluation)
        if os is not None:
            ln = subset_for(s.k + 1)
            wt_next = os_wt(ln)
            JTJn, JTen, cost_new = nrm_eq(pnew, wt_next, cw=wt,
                                          os_subset=ln if os_ntper
                                          else None)
            # a subset with no usable rows of chunk k gives zero
            # equations there; that is not convergence (per-chunk)
            sub_live = os_live(wt_next)
        else:
            JTJn, JTen, cost_new = nrm_eq(pnew)
        # gain ratio: dL = dp^T (mu dp + JTe)
        dL = jnp.sum(dp * (s.mu[:, None] * dp + s.JTe), axis=-1)
        dF = s.cost - cost_new
        accept = ok & (dF > 0) & (dL > 0) & ~s.stop & chunk_mask
        rho = dF / jnp.maximum(dL, 1e-30)
        mu_acc = s.mu * jnp.maximum(1.0 / 3.0,
                                    1.0 - (2.0 * rho - 1.0) ** 3)
        mu = jnp.where(accept, mu_acc, s.mu * s.nu)
        nu = jnp.where(accept, 2.0, s.nu * 2.0)
        p = jnp.where(accept[:, None], pnew, s.p)
        cost = jnp.where(accept, cost_new, s.cost)
        # rejected chunks keep their entering equations: numerically the
        # old point's equations ARE the carried ones (non-OS), and under
        # OS this is the reference's retry-same-subset (clmfit.c:1449).
        # Exception: a DEAD carried subset (zero equations for this
        # chunk) must not be retried — data-only its dp is exactly 0, so
        # pnew == p and the new subset's equations at pnew are the old
        # point's; adopting them on rejection un-freezes the chunk.
        # (Under ADMM the prior terms make dp != 0, so adoption stays
        # accept-only there; the live gate below still blocks the zero
        # data gradient from reading as convergence.)
        if os is not None and admm is None:
            adopt = accept | (~s.live & chunk_mask)
        else:
            adopt = accept
        if inner_cg:
            # the matrix-free operator carries per-ROW factors (MA/MB/w2
            # over [B]) next to the per-chunk D blocks: the per-chunk
            # adopt select maps onto rows through chunk_id — rows of a
            # rejected chunk keep the entering point's factors, exactly
            # the dense path's kept JTJ
            if mode == "full":
                ra = adopt[chunk_id][:, None, None, None]
                JTJ = ne.GNFactors(
                    MA=jnp.where(ra, JTJn.MA, s.JTJ.MA),
                    MB=jnp.where(ra, JTJn.MB, s.JTJ.MB),
                    w2=jnp.where(ra, JTJn.w2, s.JTJ.w2),
                    D=jnp.where(adopt[:, None, None, None, None],
                                JTJn.D, s.JTJ.D))
            else:
                # reduced factors carry one extra mode axis — select
                # ndim-generically per leaf (rows through chunk_id,
                # D per chunk)
                rab = adopt[chunk_id]

                def _sel(new, old):
                    return jnp.where(
                        rab.reshape(rab.shape + (1,) * (new.ndim - 1)),
                        new, old)

                JTJ = ne.GNFactorsMode(
                    FA=_sel(JTJn.FA, s.JTJ.FA),
                    FB=_sel(JTJn.FB, s.JTJ.FB),
                    w2=_sel(JTJn.w2, s.JTJ.w2),
                    D=jnp.where(adopt[:, None, None, None, None],
                                JTJn.D, s.JTJ.D))
        else:
            JTJ = jnp.where(adopt[:, None, None], JTJn, s.JTJ)
        JTe = jnp.where(adopt[:, None], JTen, s.JTe)
        live = jnp.where(adopt, sub_live, s.live) if os is not None \
            else s.live
        # convergence tests (levmar-style)
        small_grad = jnp.max(jnp.abs(JTe), axis=-1) <= config.eps1
        if os is not None:
            small_grad = small_grad & live
        small_dp = (jnp.linalg.norm(dp, axis=-1)
                    <= config.eps2 * (jnp.linalg.norm(s.p, axis=-1) + 1e-30))
        # eps3 applies to the (nonnegative) data cost only: the augmented-
        # Lagrangian cost is signed, so a small/negative value there does
        # not mean convergence
        small_cost = (cost <= config.eps3) if admm is None else jnp.zeros_like(s.stop)
        # iteration-budget exhaustion joins the stop mask so the body is
        # a no-op past itmax — required for exact semantics under vmap
        # (batched while_loop keeps running the body until EVERY batch
        # element's cond is false; sagefit_tiles vmaps over tiles whose
        # dynamic iteration budgets differ)
        stop = s.stop | small_grad | (accept & small_dp) | small_cost \
            | (s.k + 1 >= itmax)
        return LMState(p=p, JTJ=JTJ, JTe=JTe, mu=mu, nu=nu, cost=cost,
                       stop=stop, live=live, k=s.k + 1, cg=s.cg + trips)

    init = LMState(p=p0, JTJ=JTJ0, JTe=JTe0, mu=mu0,
                   nu=jnp.full((kmax,), 2.0, dtype),
                   cost=cost0, stop=jnp.zeros((kmax,), bool),
                   live=live0, k=jnp.zeros((), jnp.int32),
                   cg=jnp.zeros((), jnp.int32))
    final = jax.lax.while_loop(cond, body, init)
    J = p_to_J(final.p)
    J = jnp.where(chunk_mask[:, None, None, None], J,
                  J0 if mode == "full" else Jref)
    return J, {"init_cost": cost0, "final_cost": final.cost,
               "iters": final.k, "cg_iters": final.cg}


def make_weights(flags, dtype=jnp.float32, extra=None):
    """[B, 8] sqrt-weights from row flags: only flag==0 rows enter the solve
    (flag 2 = uv-cut rows are subtracted later but not solved on,
    SURVEY.md data model)."""
    w = (flags == 0).astype(dtype)[:, None] * jnp.ones((1, 8), dtype)
    if extra is not None:
        w = w * extra
    return w


def make_weights_np(flags, dtype=np.float32):
    """:func:`make_weights` without ``extra``, on the host: the same
    [B, 8] array in numpy, for staging code that must not queue a device
    execution and a read-back behind a running solve (cli_mpi's reader
    thread)."""
    return np.repeat((np.asarray(flags) == 0).astype(dtype)[:, None], 8,
                     axis=1)
