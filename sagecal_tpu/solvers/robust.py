"""Robust (Student's t) machinery: IRLS weights, nu estimation, robust LM.

Capability parity with reference ``src/lib/Dirac/updatenu.c`` (update_nu:264,
update_w_and_nu:137, digamma:35) and the IRLS structure of ``robustlm.c``
(rlevmar_der_single_nocuda:2008: wt_itmax=3 rounds of {weighted LM -> E-step
weight update w=(nu+1)/(nu+e^2) -> grid-search nu}), vectorized: the weight
E-step is one elementwise op, the nu grid search evaluates all Nd candidates
at once with jax.scipy digamma.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne


def update_weights(e, nu):
    """E-step weights w = (nu+1)/(nu + e^2) per residual component
    (updatenu.c:63, robust.cu updateweights)."""
    return (nu + 1.0) / (nu + e * e)


def nu_grid(nulow, nuhigh, nd: int = 30):
    # jaxlint: disable=dtype-promotion -- 30-element grid; the wide
    # intermediates are deliberate for the digamma root-find and the
    # selected nu is cast to the caller's dtype (update_nu_* .astype)
    return nulow + jnp.arange(nd) * (nuhigh - nulow) / nd


def update_nu_ml(w, mask, nu_old, nulow=2.0, nuhigh=30.0, nd: int = 30):
    """ML nu update from current weights (update_w_and_nu, updatenu.c:137):
    root of psi((nu+1)/2)-ln((nu+1)/2)-psi(nu/2)+ln(nu/2)+1 - mean(w-ln w)=0
    over a grid; ``mask`` [same shape as w] selects live residuals."""
    nlive = jnp.maximum(jnp.sum(mask), 1.0)
    sumq = jnp.sum(jnp.where(mask, w - jnp.log(jnp.maximum(w, 1e-30)), 0.0)
                   ) / nlive
    nus = nu_grid(nulow, nuhigh, nd)
    q = (jax.scipy.special.digamma((nus + 1.0) * 0.5)
         - jnp.log((nus + 1.0) * 0.5)
         - jax.scipy.special.digamma(nus * 0.5) + jnp.log(nus * 0.5)
         - sumq + 1.0)
    # the grid is built at default precision; return in the caller's nu
    # dtype so IRLS scan carries stay type-stable (f32 data under x64)
    return nus[jnp.argmin(jnp.abs(q))].astype(jnp.asarray(nu_old).dtype)


def mean_logsumw(w, mask):
    """1/N sum(ln w_i - w_i) over live residuals — the AECM sufficient
    statistic (updatenu.c:253-259)."""
    nlive = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(jnp.where(mask,
                             jnp.log(jnp.maximum(w, 1e-30)) - w, 0.0)) / nlive


def update_nu_aecm(logsumw, nu_old, p: int = 8, nulow=2.0, nuhigh=30.0,
                   nd: int = 30):
    """AECM nu update (update_nu, updatenu.c:264) for p-variate t:
    ``logsumw`` = mean(ln w - w) over live residuals (:func:`mean_logsumw`).
    The robust RTR/NSD family calls this with p=2
    (rtr_solve_robust.c:374); the robust LM family uses
    :func:`update_nu_ml` (update_w_and_nu) instead."""
    dgm = (jax.scipy.special.digamma((nu_old + p) * 0.5)
           - jnp.log((nu_old + p) * 0.5))
    nus = nu_grid(nulow, nuhigh, nd)
    q = (-jax.scipy.special.digamma(nus * 0.5) + jnp.log(nus * 0.5)
         - (-logsumw - dgm) + 1.0)
    # dtype-stable for scan carries, like update_nu_ml
    return nus[jnp.argmin(jnp.abs(q))].astype(jnp.asarray(nu_old).dtype)


def robust_lm_solve(x8, coh, sta1, sta2, chunk_id, wt_base, J0,
                    n_stations: int, nu0=2.0, nulow=2.0, nuhigh=30.0,
                    chunk_mask=None, config=lm_mod.LMConfig(),
                    wt_rounds: int = 3, itmax_dynamic=None, admm=None,
                    os=None, row_period: int = 0):
    """Student's-t IRLS-LM: parity with rlevmar_der_single_nocuda
    (robustlm.c:2008); with ``os`` set it is the ordered-subsets variant
    osrlevmar_der_single_nocuda (robustlm.c:2607) — the weighted inner LM
    sees random tile subsets while the E-step weight/nu updates stay
    full-data.

    ``wt_base`` [B, 8]: 0/1 row mask weights. Robust sqrt(w) multiplies it.
    Returns (J, nu, info). nu is a scalar (all chunks share one nu, like the
    reference which averages over chunks afterwards in lmfit.c:1002-1017).
    """
    kmax = J0.shape[0]
    mask = wt_base > 0

    def round_body(carry, rs):
        J, nu, first = carry
        e = ne.residual8(x8, J, coh, sta1, sta2, chunk_id)
        w = update_weights(e, nu)
        w = jnp.where(first, jnp.ones_like(w), w)
        # IRLS weights fold back into the STORAGE dtype (identity for
        # f32/f64): the E-step itself ran in the accumulator dtype (w
        # promotes through nu), only the [B]-resident product quantizes
        wt = dtp.to_storage(wt_base * jnp.sqrt(w), wt_base.dtype)
        # distinct subset draws per IRLS round
        os_r = (os._replace(key=jax.random.fold_in(os.key, 7919 + rs))
                if os is not None else None)
        Jn, info = lm_mod.lm_solve(x8, coh, sta1, sta2, chunk_id, wt, J,
                                   n_stations, chunk_mask, config,
                                   itmax_dynamic=itmax_dynamic, admm=admm,
                                   os=os_r, row_period=row_period)
        # ML nu update from post-solve residuals
        e2 = ne.residual8(x8, Jn, coh, sta1, sta2, chunk_id)
        w2 = update_weights(e2, nu)
        nu_new = update_nu_ml(w2, mask, nu, nulow, nuhigh)
        return (Jn, nu_new, jnp.zeros((), bool)), (info["init_cost"],
                                                   info["final_cost"],
                                                   info["iters"],
                                                   info["cg_iters"])

    (J, nu, _), costs = jax.lax.scan(
        round_body, (J0, jnp.asarray(nu0, dtp.acc_dtype(x8.dtype)),
                     jnp.ones((), bool)),
        jnp.arange(wt_rounds))
    # "iters": executed inner-LM damping iterations summed over IRLS
    # rounds; "cg_iters": executed PCG trips under config.inner="cg"
    # (0 otherwise) — both reach the tile record through lm.TRIP_KEYS
    info = {"init_cost": costs[0][0], "final_cost": costs[1][-1],
            "iters": jnp.sum(costs[2]).astype(jnp.int32),
            "cg_iters": jnp.sum(costs[3]).astype(jnp.int32)}
    return J, nu, info


def ncp_weight(uvdist):
    """Inverse uv-density taper 1/(1 + 1.8 exp(-0.05 d)), flat for
    d > 400 wavelengths (updatenu.c:343-350)."""
    import jax.numpy as jnp
    w = 1.0 / (1.0 + 1.8 * jnp.exp(-0.05 * uvdist))
    return jnp.where(uvdist > 400.0, 1.0, w)


def whiten_data(x, u, v, freq0):
    """uv-density whitening of visibilities (-W flag; updatenu.c:386
    ``whiten_data``): every correlation of baseline row b is scaled by
    ``ncp_weight(|uv_b|)`` in wavelengths at ``freq0``. u, v in seconds.

    x: [B, ...] complex or real visibility rows.
    """
    import jax.numpy as jnp
    uu = u * freq0
    vv = v * freq0
    a = ncp_weight(jnp.sqrt(uu * uu + vv * vv))
    return x * a.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.real.dtype)
