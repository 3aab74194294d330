"""Limited-memory BFGS: full-batch and persistent-state stochastic variants.

Capability parity with reference ``src/lib/Dirac/lbfgs.c``:
- two-loop recursion ``mult_hessian`` (:33) with circular (s, y) storage;
- full-batch ``lbfgs_fit_fullbatch`` (:479);
- stochastic ``lbfgs_fit_minibatch`` (:717): persistent curvature pairs
  across minibatches (``persistent_data_t``, Dirac.h:84-104), online
  gradient-variance estimate -> adaptive initial step
  ``alphabar = 10/(1 + sum_var/((niter-1)*||g||))`` (:796-824), Armijo
  backtracking (:444), trust-region damping ``y += 1e-6 s`` (:871-875),
  and the skip-storage-on-batch-change rule (:849-858);
- generic optimizer API surface (demo in reference test/Dirac/demo.c).

Re-architected for JAX: the persistent state is an immutable pytree carried
through ``lax.while_loop``; cost/grad are arbitrary jit-traceable closures
(autodiff supplies gradients where the reference hand-codes kernels). The
full-batch path uses the Fletcher cubic/zoom line search with the
reference's parameters; the stochastic path uses Armijo backtracking, the
variant the reference uses in production minibatch mode.

A caller whose cost is cheap to restrict to a line may hand the full-batch
path a third closure, ``line_func(xk, pk) -> (a -> (phi(a), dphi(a)))``,
built once per iteration; the Fletcher search then evaluates its trial
steps on that restriction and calls cost/grad for no trial (the joint
refine of ``solvers/sage.py`` does, where the model is a polynomial in the
step). The loop counts its passes through the caller's model either way.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-15
#: what the pass counter charges for one ``line_func(xk, pk)``: the cost's
#: first directional derivative at ``xk`` (one ``jvp``) and one plain
#: evaluation at ``pk``, which is all an exact restriction of a model
#: quadratic in the step needs
LINE_FUNC_PASSES = 2


class LBFGSMemory(NamedTuple):
    """Persistent curvature state (reference persistent_data_t)."""

    s: jax.Array             # [M, m] parameter deltas
    y: jax.Array             # [M, m] gradient deltas
    rho: jax.Array           # [M] 1/(y^T s)
    head: jax.Array          # next write slot (reference `vacant`)
    nfilled: jax.Array       # live pairs <= M
    niter: jax.Array         # global iteration count across batches
    running_avg: jax.Array   # [m] online mean of gradients
    running_avg_sq: jax.Array  # [m] online (co)variance accumulator


def lbfgs_memory_init(m: int, M: int, dtype=jnp.float32) -> LBFGSMemory:
    """Parity: lbfgs_persist_init (lbfgs.c:954)."""
    return LBFGSMemory(
        s=jnp.zeros((M, m), dtype), y=jnp.zeros((M, m), dtype),
        rho=jnp.zeros((M,), dtype), head=jnp.zeros((), jnp.int32),
        nfilled=jnp.zeros((), jnp.int32), niter=jnp.zeros((), jnp.int32),
        running_avg=jnp.zeros((m,), dtype),
        running_avg_sq=jnp.zeros((m,), dtype))


def lbfgs_memory_reset(mem: LBFGSMemory) -> LBFGSMemory:
    """Parity: lbfgs_persist_reset (lbfgs.c, used on divergence)."""
    return lbfgs_memory_init(mem.s.shape[1], mem.s.shape[0], mem.s.dtype)


@jax.named_scope("direction")      # sage/refine/direction in a solve
def mult_hessian(g, mem: LBFGSMemory):
    """Two-loop recursion: H_k g with implicit H0 = gamma I (lbfgs.c:33)."""
    M = mem.s.shape[0]
    q = g
    alphas = []
    # newest -> oldest: slot (head-1-j) mod M
    idxs = [(mem.head - 1 - j) % M for j in range(M)]
    live = [j < mem.nfilled for j in range(M)]
    for j in range(M):
        s_j = mem.s[idxs[j]]
        y_j = mem.y[idxs[j]]
        a = jnp.where(live[j], mem.rho[idxs[j]] * jnp.dot(s_j, q), 0.0)
        q = q - a * y_j
        alphas.append(a)
    # gamma from newest pair
    s_n, y_n = mem.s[idxs[0]], mem.y[idxs[0]]
    gamma = jnp.where(mem.nfilled > 0,
                      jnp.dot(s_n, y_n) / jnp.maximum(jnp.dot(y_n, y_n), _EPS),
                      1.0)
    r = gamma * q
    for j in range(M - 1, -1, -1):
        s_j = mem.s[idxs[j]]
        y_j = mem.y[idxs[j]]
        b = jnp.where(live[j], mem.rho[idxs[j]] * jnp.dot(y_j, r), 0.0)
        r = r + (alphas[j] - b) * s_j
    return r


def linesearch_backtrack(cost_func: Callable, xk, pk, gk, alpha0,
                         c: float = 1e-4, max_steps: int = 15):
    """Armijo backtracking (lbfgs.c:444): halve alpha until
    f(x+a p) <= f(x) + c a p^T g (NaN treated as failure).

    The body re-tests the Armijo condition and freezes satisfied states:
    under vmap the loop runs until EVERY batch element passes, and an
    already-accepted alpha must not keep halving."""
    f0 = cost_func(xk)
    slope = c * jnp.dot(pk, gk)

    def _bad(alpha, fnew):
        return jnp.isnan(fnew) | (fnew > f0 + alpha * slope)

    def cond(state):
        alpha, fnew, i = state
        return (i < max_steps) & _bad(alpha, fnew)

    def body(state):
        alpha, fnew, i = state
        bad = _bad(alpha, fnew)
        alpha2 = jnp.where(bad, alpha * 0.5, alpha)
        fnew2 = jnp.where(bad, cost_func(xk + alpha2 * pk), fnew)
        return alpha2, fnew2, i + 1

    alpha0 = jnp.asarray(alpha0, xk.dtype)
    fnew0 = cost_func(xk + alpha0 * pk)
    alpha, _, _ = jax.lax.while_loop(cond, body, (alpha0, fnew0,
                                                  jnp.zeros((), jnp.int32)))
    return alpha


@jax.named_scope("linesearch")     # sage/refine/linesearch in a solve
def linesearch_fletcher(cost_func, grad_func, xk, pk, gk=None,
                        alpha1: float = 10.0, sigma: float = 0.1,
                        rho: float = 0.01, t1: float = 9.0, t2: float = 0.1,
                        t3: float = 0.5, on_line=None):
    """Fletcher line search with cubic interpolation (lbfgs.c:116-443:
    ``cubic_interp`` / ``linesearch_zoom`` / ``linesearch``), used by the
    full-batch path with the reference's parameters (lbfgs.c:572).
    Returns ``(alpha, passes)``: the step, and how many times the search
    went through ``cost_func`` or ``grad_func`` (int32).

    Deviations from the reference: directional derivatives are exact
    (``grad . pk``) instead of central finite differences, and the cubic
    minimizer evaluates the trial point at ``z0`` itself (the reference's
    mixed absolute/fractional use of ``z0`` evaluates at a+z0(b-a) while
    bounds-checking z0 in alpha units). Where the caller has the cost
    restricted to this line (``on_line``: ``a -> (phi(a), dphi(a))``, what
    ``line_func(xk, pk)`` returned), every trial reads the two halves of
    that one function, ``cost_func`` and ``grad_func`` are not called and
    ``passes`` is 0: the same search over the same function, evaluated
    where it is cheap (the reference walks the full cost at every trial).
    """
    dtype = xk.dtype
    eps = jnp.asarray(1e-30, dtype)

    if on_line is None:
        def phi(a):
            return cost_func(xk + a * pk)

        def dphi(a):
            return jnp.dot(grad_func(xk + a * pk), pk)
    else:
        # XLA drops the half a site does not use
        def phi(a):
            return on_line(a)[0]

        def dphi(a):
            return on_line(a)[1]
    # passes through the caller's model that one phi or dphi makes
    unit = 1 if on_line is None else 0

    phi_0 = phi(jnp.asarray(0.0, dtype))
    # reuse the caller's gradient at xk when given (saves one full
    # gradient eval per LBFGS iteration)
    gphi_0 = jnp.dot(gk, pk) if gk is not None \
        else dphi(jnp.asarray(0.0, dtype))
    tol = jnp.minimum(0.01 * phi_0, 1e-6)
    mu = (tol - phi_0) / (rho * gphi_0)

    def cubic(a, b):
        """Minimizer of the Hermite cubic through (a, f0, f0d), (b, f1,
        f1d); falls back to the lower endpoint (cubic_interp:116-189)."""
        f0, f1 = phi(a), phi(b)
        f0d, f1d = dphi(a), dphi(b)
        ba = jnp.where(jnp.abs(b - a) > eps, b - a, eps)
        aa = 3.0 * (f0 - f1) / ba + (f1d - f0d)
        disc = aa * aa - f0d * f1d
        has_root = disc > 0.0
        cc = jnp.sqrt(jnp.maximum(disc, 0.0))
        den = f1d - f0d + 2.0 * cc
        z0 = b - (f1d + cc - aa) * ba / jnp.where(jnp.abs(den) > eps,
                                                  den, eps)
        lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
        in_bounds = (z0 >= lo) & (z0 <= hi) & jnp.isfinite(z0)
        fz0 = jnp.where(in_bounds, phi(jnp.where(in_bounds, z0, a)),
                        f0 + f1)
        pick_root = jnp.where((f0 < f1) & (f0 < fz0), a,
                              jnp.where(f1 < fz0, b, z0))
        return jnp.where(has_root, pick_root, jnp.where(f0 < f1, a, b))

    # --- phase 1: bracketing (linesearch:298-420). state codes:
    # 0 continue, 1 found alphak, 2 zoom(aj, bj)
    def p1_cond(s):
        ci, alphai, alphai1, phi_i1, alphak, code, aj, bj, npass = s
        return (ci < 10) & (code == 0)

    def p1_body(s):
        ci, alphai, alphai1, phi_i1, alphak, code, aj, bj, npass = s
        phi_i = phi(alphai)
        cond0 = phi_i < tol
        cond1 = (phi_i > phi_0 + alphai * gphi_0) | ((ci > 1)
                                                     & (phi_i >= phi_i1))
        gphi_i = dphi(alphai)
        cond2 = jnp.abs(gphi_i) <= -sigma * gphi_0
        cond3 = gphi_i >= 0.0

        i32 = lambda v: jnp.asarray(v, jnp.int32)
        code_n = jnp.where(cond0, i32(1),
                           jnp.where(cond1, i32(2),
                                     jnp.where(cond2, i32(1),
                                               jnp.where(cond3, i32(2),
                                                         i32(0)))))
        alphak_n = jnp.where(cond0 | (~cond1 & cond2), alphai, alphak)
        aj_n = jnp.where(cond1, alphai1, jnp.where(cond3, alphai, aj))
        bj_n = jnp.where(cond1, alphai, jnp.where(cond3, alphai1, bj))

        # advance: next alpha by mu or cubic in the extended interval;
        # cubic is 3 cost and 2 gradient evaluations, so only run it when
        # the branch is live (linesearch:409-416 evaluates it only in the
        # else)
        take_mu = mu <= (2.0 * alphai - alphai1)
        lo = 2.0 * alphai - alphai1
        hi = jnp.minimum(mu, alphai + t1 * (alphai - alphai1))
        skip_cubic = take_mu | (code_n != 0)
        alpha_adv = jax.lax.cond(
            skip_cubic, lambda: mu,
            # jaxlint: disable=cond-cost -- cubic's phi/dphi are
            # closure-bound, so a module-level split could not be priced
            # standalone either. What pricing both branches overstates:
            # without ``on_line`` five passes through the caller's whole
            # model a trip (the joint refine's: all clusters, twice with
            # a backward pass), with it five passes over the restricted
            # residual, which is nothing (``refine_passes`` counts them)
            lambda: cubic(lo, hi))
        alphai1_n = jnp.where(code_n == 0, alphai, alphai1)
        alphai_n = jnp.where(code_n == 0, alpha_adv, alphai)
        phi_i1_n = jnp.where(code_n == 0, phi_i, phi_i1)
        npass_n = npass + unit * jnp.where(skip_cubic, 2, 7)
        return (ci + 1, alphai_n, alphai1_n, phi_i1_n, alphak_n, code_n,
                aj_n, bj_n, npass_n)

    z = jnp.asarray(0.0, dtype)
    # phi_0 above is the first pass; gphi_0 one more where gk is not given
    npass0 = jnp.asarray(unit * (1 if gk is not None else 2), jnp.int32)
    (ci, alphai, alphai1, phi_i1, alphak, code, aj, bj,
     npass) = jax.lax.while_loop(
        p1_cond, p1_body,
        (jnp.asarray(1, jnp.int32), jnp.asarray(alpha1, dtype), z, phi_0,
         jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32), z, z, npass0))

    # --- phase 2: zoom (linesearch_zoom:211-284), only when code == 2
    def p2_cond(s):
        cj, aj, bj, alphaj, found, npass = s
        return (cj < 10) & ~found

    def p2_body(s):
        cj, aj, bj, alphaj, found, npass = s
        alphaj_n = cubic(aj + t2 * (bj - aj), bj - t3 * (bj - aj))
        phi_j = phi(alphaj_n)
        phi_aj = phi(aj)
        no_suff = (phi_j > phi_0 + rho * alphaj_n * gphi_0) \
            | (phi_j >= phi_aj)
        gphi_j = dphi(alphaj_n)
        term_round = (aj - alphaj_n) * gphi_j <= 1e-9  # Fletcher pp.38
        term_curv = jnp.abs(gphi_j) <= -sigma * gphi_0
        found_n = ~no_suff & (term_round | term_curv)
        # bracket update
        bj_n = jnp.where(no_suff, alphaj_n,
                         jnp.where(gphi_j * (bj - aj) >= 0.0, aj, bj))
        aj_n = jnp.where(no_suff, aj, alphaj_n)
        # freeze finished states: under vmap the loop keeps running until
        # every batch element finds its alpha, and a found alphaj must
        # not drift with further bracket updates
        upd = ~found
        # cubic's five, phi_j, phi_aj and gphi_j
        return (cj + 1, jnp.where(upd, aj_n, aj), jnp.where(upd, bj_n, bj),
                jnp.where(upd, alphaj_n, alphaj), found | found_n,
                npass + unit * jnp.where(upd, 8, 0))

    _, _, _, alphaj, _, npass = jax.lax.while_loop(
        p2_cond, p2_body,
        (jnp.asarray(0, jnp.int32), aj, bj, jnp.asarray(1.0, dtype),
         code != 2, npass))

    alpha_out = jnp.where(code == 1, alphak,
                          jnp.where(code == 2, alphaj, alphai))
    # degenerate slope: hand back mu (caller's bad-alpha check stops the
    # iteration, matching the reference's !isnormal(mu) early return)
    return jnp.where(jnp.isfinite(mu) & (jnp.abs(mu) > 0), alpha_out,
                     mu), npass


class _IterState(NamedTuple):
    x: jax.Array
    g: jax.Array
    mem: LBFGSMemory
    alphabar: jax.Array
    k: jax.Array
    done: jax.Array
    passes: jax.Array        # passes through the caller's model so far


def _lbfgs_loop(cost_func, grad_func, x0, mem0: LBFGSMemory, itmax: int,
                stochastic: bool, force_backtrack: bool = False,
                line_func=None):
    """Returns (x, memory, iterations, passes). ``passes`` counts what
    went through the caller's model: every cost and gradient evaluation
    of the loop and of the Fletcher search, and ``LINE_FUNC_PASSES`` for
    every restriction built (the Armijo search returns no count: its
    cost evaluations are left out)."""
    g0 = grad_func(x0)

    def cond(s: _IterState):
        return (s.k < itmax) & ~s.done

    def body(s: _IterState):
        mem = s.mem
        batch_changed = stochastic & (mem.niter > 0) & (s.k == 0)
        # niter freezes once done (vmap: body runs past convergence)
        mem = mem._replace(niter=mem.niter + jnp.where(s.done, 0, 1))
        gradnrm = jnp.linalg.norm(s.g)

        alphabar = s.alphabar
        if stochastic:
            # online gradient variance -> adaptive initial step (lbfgs.c:796)
            def upd(mem):
                g_min_rold = s.g - mem.running_avg
                ravg = mem.running_avg + g_min_rold / mem.niter.astype(s.g.dtype)
                g_min_rnew = s.g - ravg
                rsq = mem.running_avg_sq + g_min_rold * g_min_rnew
                ab = 10.0 / (1.0 + jnp.sum(jnp.abs(rsq))
                             / (jnp.maximum(mem.niter.astype(s.g.dtype) - 1.0,
                                            1.0) * jnp.maximum(gradnrm, _EPS)))
                return mem._replace(running_avg=ravg, running_avg_sq=rsq), ab
            mem, alphabar = jax.lax.cond(
                batch_changed, upd, lambda m: (m, s.alphabar), mem)

        pk = -mult_hessian(s.g, mem)
        if stochastic or force_backtrack:
            # production stochastic path uses Armijo backtracking
            # (lbfgs.c:444 linesearch_backtrack)
            alphak = linesearch_backtrack(cost_func, s.x, pk, s.g, alphabar)
            ls_passes = 0
        else:
            # full-batch path uses the Fletcher search with the
            # reference's parameters (lbfgs.c:572), on the caller's
            # restriction of the cost to this line where there is one
            on_line = None if line_func is None else line_func(s.x, pk)
            alphak, ls_passes = linesearch_fletcher(
                cost_func, grad_func, s.x, pk, gk=s.g, on_line=on_line)
            if line_func is not None:
                ls_passes = ls_passes + LINE_FUNC_PASSES
        bad_alpha = ~jnp.isfinite(alphak) | (jnp.abs(alphak) < 1e-12)
        x1 = s.x + alphak * pk
        g1 = grad_func(x1)
        g1nrm = jnp.linalg.norm(g1)

        sk = x1 - s.x
        yk = g1 - s.g
        # trust-region damping (lbfgs.c:871-875)
        lm0 = 1e-6
        yk = jnp.where(g1nrm > 1e3 * lm0, yk + lm0 * sk, yk)
        rhok = 1.0 / jnp.where(jnp.abs(jnp.dot(yk, sk)) > _EPS,
                               jnp.dot(yk, sk), jnp.inf)
        # freeze after done: under vmap the loop body keeps running until
        # every batch element is done, and a finished element must not
        # take further steps (unbatched, cond exits before this matters)
        store = ~batch_changed & ~bad_alpha & jnp.isfinite(g1nrm) & ~s.done

        def do_store(mem):
            return mem._replace(
                s=mem.s.at[mem.head].set(sk),
                y=mem.y.at[mem.head].set(yk),
                rho=mem.rho.at[mem.head].set(rhok),
                head=(mem.head + 1) % mem.s.shape[0],
                nfilled=jnp.minimum(mem.nfilled + 1, mem.s.shape[0]))
        mem = jax.lax.cond(store, do_store, lambda m: m, mem)

        done = s.done | bad_alpha | ~jnp.isfinite(g1nrm) | (g1nrm < _EPS)
        frozen = bad_alpha | s.done
        x_out = jnp.where(frozen, s.x, x1)
        g_out = jnp.where(frozen, s.g, g1)
        # the search's passes and g1's, frozen like the rest once done
        passes = s.passes + jnp.where(s.done, 0, ls_passes + 1)
        return _IterState(x=x_out, g=g_out, mem=mem, alphabar=alphabar,
                          k=s.k + 1, done=done, passes=passes)

    init = _IterState(
        x=x0, g=g0, mem=mem0,
        alphabar=jnp.asarray(1.0, x0.dtype),
        k=jnp.zeros((), jnp.int32),
        done=jnp.linalg.norm(g0) < _EPS,
        passes=jnp.ones((), jnp.int32))     # g0
    out = jax.lax.while_loop(cond, body, init)
    return out.x, out.mem, out.k, out.passes


def lbfgs_fit(cost_func, grad_func, p0, itmax: int = 20, M: int = 7,
              linesearch: str = "fletcher", return_iters: bool = False,
              line_func=None):
    """Full-batch LBFGS (lbfgs_fit, lbfgs.c:933): fresh memory each call.

    ``linesearch``: "fletcher" (reference full-batch default) or
    "backtrack" (Armijo). ``line_func(xk, pk)``, where the caller has
    one, returns the cost restricted to the line ``xk + a pk`` as
    ``a -> (phi(a), dphi(a))``; the Fletcher search then runs its trials
    on it. ``return_iters`` additionally returns the executed iteration
    count (the tile record's ``lbfgs_iters``) and the passes through the
    caller's model (``_lbfgs_loop``)."""
    mem = lbfgs_memory_init(p0.shape[0], M, p0.dtype)
    x, _, k, passes = _lbfgs_loop(
        cost_func, grad_func, p0, mem, itmax, stochastic=False,
        force_backtrack=(linesearch == "backtrack"), line_func=line_func)
    return (x, k, passes) if return_iters else x


def lbfgs_fit_minibatch(cost_func, grad_func, p0, mem: LBFGSMemory,
                        itmax: int = 10):
    """Stochastic LBFGS step over one minibatch with persistent state
    (lbfgs_fit_minibatch, lbfgs.c:717). Returns (p, updated memory,
    executed iteration count)."""
    return _lbfgs_loop(cost_func, grad_func, p0, mem, itmax,
                       stochastic=True)[:3]
